"""The GBNCC plan (``ddplan.survey_plan("gbncc")``: five steps at
downsamp 1-16, 102 DMs a pass) through the harness on the CPU, at a toy
width and length that keep the survey's band and 16 channels a subband:
a cell added AS FILES ONLY (``toy_gbncc/``: a configuration and a
traffic mix that lists the plan's steps as 1, 2, 3, 4, 0, so that the
pulsar's pass is the slice's first) runs through ``runner.measure``,
is ``correct`` against the plain reference with the control caught,
and reports stage 1 in the unit the other layers have.  A second run
drives the same slice with stage 1 on its Pallas tier, tiled over
subband groups, and gets the same candidates.
"""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import cells, runner, window

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_gbncc")
SEED = 2 ** 31 + 3300


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_gbncc_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_gbncc", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_gbncc.json", "why": "toy"})
    bench["workloads"].append(
        {"name": "toy_gbncc_steps", "config": "toy_gbncc",
         "traffic": "toy_steps_noaccel_dm52", "chips": 1, "why": "toy"})
    # attached the way the real cell is: its name appended to the
    # `workloads` of the metrics gbncc_steps_noaccel reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gbncc_steps_noaccel" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_gbncc_steps"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def cell(toy_root):
    return cells.load_cell("toy_gbncc_steps", root=toy_root)


@pytest.fixture(scope="module")
def gbncc_run(cell):
    return runner.measure(cell, SEED, 0.5, True, t_process=time.time(),
                          warm=True, control=True, log=lambda m: None)


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_slice_is_the_plans_five_first_passes_dm52_first(cell):
    from tpulsar.plan import ddplan

    plan = cells.plan_slice(cell)
    full = ddplan.survey_plan("gbncc")
    assert [s.downsamp for s in plan] == [2, 4, 8, 16, 1]
    assert sorted(s.lodm for s in plan) == [s.lodm for s in full]
    assert [s.numpasses for s in plan] == [1] * 5
    assert sum(s.numdms for s in plan) == 510
    lo, hi = cells.first_pass_dms(plan)
    assert (lo, round(hi, 3)) == (51.714, 52.224)


def test_gbncc_cell_runs_and_is_correct(gbncc_run):
    res = gbncc_run
    assert res["correct"] is True
    assert res["attempted"] == 510 * len(res["calls"])
    assert res["failed"] == 0
    assert res["counters"]["inline_compiles"] == 0
    got = numbers(res)
    assert all(n["ok"] for n in got.values()), got
    assert got["trials_not_searched"]["value"] == 0
    assert got["pulsar_missing"]["value"] == 0
    # float32 end to end: far inside the toy's limits, in every pass
    assert got["lo_power_gap"]["n"] >= 5
    assert got["lo_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["n"] >= 5 and got["sp_snr_gap"]["value"] < 1e-3
    assert got["lo_best_missing"]["value"] == 0
    assert got["lo_best_missing"]["n"] >= 1


def test_gbncc_control_is_caught(gbncc_run):
    ctrl = numbers(gbncc_run, "control")
    assert not ctrl["lo_power_gap"]["ok"]
    assert not ctrl["sp_snr_gap"]["ok"]


def test_gbncc_cell_reports_stage_1_per_trial(cell, gbncc_run):
    got = gbncc_run["metrics"]
    assert set(got) <= {m["name"] for m in cell.per_layer()}
    assert {"subband_ms_per_trial", "subband_s_per_pass",
            "dedisp_ms_per_trial", "spectra_ms_per_trial",
            "sp_ms_per_trial", "inline_compiles"} <= set(got)
    assert "hiaccel_ms_per_trial" not in got
    # the same stage seconds in two units: per pass, and per trial
    assert got["subband_ms_per_trial"]["unit"] == "ms"
    assert got["subband_ms_per_trial"]["value"] == pytest.approx(
        1000.0 * got["subband_s_per_pass"]["value"] * 5 / 510)


def test_the_pallas_tier_tiles_stage_1_and_finds_the_same(
        cell, gbncc_run, monkeypatch):
    """The same slice with stage 1 forced onto its Pallas tier (the
    interpreter off a TPU) under a VMEM budget that holds 8 of the 16
    subbands: every pass's `subbanding` span says 2 groups, and the
    slice's raw candidates are the XLA twin's."""
    from tpulsar.kernels import pallas_dd
    from tpulsar.obs import trace

    block, _psr, plan, params = runner.setup(cell, SEED, {})
    base = window.slice_call(block, cell.freqs, cell.dt, plan, params)
    monkeypatch.setenv("TPULSAR_PALLAS_SB", "1")
    monkeypatch.setattr(pallas_dd, "STAGE1_VMEM_BUDGET", 1_100_000)
    assert pallas_dd.stage1_plan(256, 16, 256, 1)[:3] == (1024, 1280, 8)
    trace.start()
    try:
        tiled = window.slice_call(block, cell.freqs, cell.dt, plan, params)
        spans = [e["args"] for e in trace.events()
                 if e["name"] == "subbanding"]
    finally:
        trace.reset()
    assert len(spans) == 5
    assert {a["sb_groups"] for a in spans} == {2}
    assert all(a["sb_slabs"] >= 1 and a["sb_block_t"] >= 512
               and a["sb_overhang"] >= 256 for a in spans)
    assert not tiled.degraded and tiled.ntrials_done == 510
    for want, got in zip(base.dumps, tiled.dumps):
        for f in ("r", "dm", "numharm", "power"):
            np.testing.assert_array_equal(got["cands"][f],
                                          want["cands"][f])
