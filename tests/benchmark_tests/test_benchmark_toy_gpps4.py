"""A beam laid over four devices by channels and searched by the mesh
of the same four (``block_layout`` over 4 with ``dm_shards: 4``), through
the harness on the eight virtual CPU devices of ``tests/conftest.py``:
the FAST GPPS plan (``ddplan.survey_plan("gpps")``: six steps at
downsamp 1-32, 102 DMs a pass) at a toy width and length that keep the
survey's band and 16 channels a subband.  A cell added AS FILES ONLY
(``toy_gpps4/``: a configuration and a traffic mix that lists the
plan's steps as 1, 2, 3, 4, 5, 0) runs through ``runner.measure`` and is
``correct`` against the plain reference, which reads the laid-out
block piece by piece; with the pass's one exchange broken (one share's
subbands zeroed on their way into stage 2) it is not.
"""

import json
import os
import shutil
import time

import pytest

from benchmark.harness import cells, runner

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_gpps4")
SEED = 2 ** 31 + 4300


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_gpps4_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_gpps4", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_gpps4.json", "why": "toy"})
    bench["workloads"].append(
        {"name": "toy_gpps4_steps", "config": "toy_gpps4",
         "traffic": "toy_steps_noaccel_dm146", "chips": 4, "why": "toy"})
    # attached the way the real cell is: its name appended to the
    # `workloads` of the metrics gpps_steps_noaccel_mesh4 reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpps_steps_noaccel_mesh4" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_gpps4_steps"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def cell(toy_root):
    return cells.load_cell("toy_gpps4_steps", root=toy_root)


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_slice_is_the_plans_six_first_passes_dm146_first(cell):
    from tpulsar.plan import ddplan

    plan = cells.plan_slice(cell)
    full = ddplan.survey_plan("gpps")
    assert [s.downsamp for s in plan] == [2, 4, 8, 16, 32, 1]
    assert sorted(s.lodm for s in plan) == [s.lodm for s in full]
    assert [s.numpasses for s in plan] == [1] * 6
    assert sum(s.numdms for s in plan) == 612
    lo, hi = cells.first_pass_dms(plan)
    assert (lo, round(hi, 3)) == (144.84, 147.9)
    assert cell.block_layout == {"axis": "channel", "devices": 4}
    assert cells.search_params(cell).dm_shards == 4


@pytest.fixture(scope="module")
def gpps_run(cell):
    """One traced run: every listed per-layer metric has something to
    read on the laid-out path."""
    return runner.measure(cell, SEED, 0.5, True, t_process=time.time(),
                          warm=True, log=lambda m: None)


def test_the_laid_out_cell_runs_on_the_mesh_and_is_correct(gpps_run):
    res = gpps_run
    got = numbers(res)
    assert res["correct"] is True, got
    assert res["attempted"] == 612 * len(res["calls"])
    assert res["failed"] == 0
    assert res["counters"]["inline_compiles"] == 0
    assert all(n["ok"] for n in got.values()), got
    assert got["trials_not_searched"]["value"] == 0
    assert got["pulsar_missing"]["value"] == 0
    # float32 end to end and sums of bytes exact share by share: far
    # inside the toy's limits, in every pass
    assert got["lo_power_gap"]["n"] >= 6
    assert got["lo_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["n"] >= 6 and got["sp_snr_gap"]["value"] < 1e-2
    assert got["lo_best_missing"]["value"] == 0
    # the mesh searched it: one exchange a pass, by name
    stages = res["calls"][-1]["stage_s"]
    assert {"mesh-exchange", "mesh-place", "sharded-search",
            "mesh-candidates", "subbanding"} <= set(stages)
    assert "dedispersing" not in stages


def test_every_listed_per_layer_metric_reads_a_number(cell, gpps_run):
    """What BENCHMARK.json lists for the real cell is what the laid-out
    path opens spans for: none reads null (the five stage-1 steps only
    where the Pallas tier runs: the chip, not this CPU)."""
    got = gpps_run["metrics"]
    listed = {m["name"] for m in cell.per_layer()}
    pallas_only = {n for n in listed if n.startswith("subband_")
                   and n.endswith("_s_per_pass")
                   and n != "subband_s_per_pass"}
    assert "mesh_exchange_s_per_pass" in listed
    # the CPU backend reports no memory peak
    for name in sorted(listed - pallas_only - {"hbm_peak_gib"}):
        assert name in got and got[name]["value"] is not None, name
    assert got["mesh_exchange_s_per_pass"]["value"] > 0.0


def test_a_broken_exchange_is_not_correct(cell, monkeypatch):
    """One share's subbands zeroed on their way into stage 2: every
    series is a quarter short, and the check says so."""
    from tpulsar.search import executor

    sound = executor._mesh_exchange

    def broken(mesh, subb, *a, **k):
        out = sound(mesh, subb, *a, **k)
        return out.at[: out.shape[0] // 4].set(0.0)

    monkeypatch.setattr(executor, "_mesh_exchange", broken)
    res = runner.measure(cell, SEED, 0.5, False, t_process=time.time(),
                         warm=False, log=lambda m: None)
    got = numbers(res)
    assert res["correct"] is False
    assert not got["lo_power_gap"]["ok"] or not got["sp_snr_gap"]["ok"] \
        or not got["pulsar_missing"]["ok"], got
