"""The acceleration search over a slice that lists a downsampled pass
before the ds=1 pass (``steps`` [1, 0]: the only order in which a plan
whose own first pass lies under the sifter's DM cutoff holds a
recoverable pulsar), through the harness on the CPU at a toy width and
length.  Two cells added AS FILES ONLY: the toy of ``gbncc120_hiaccel``,
and of the cell still owed over FAST GPPS's laid-out beam (hi-accel
behind the exchange has met no chip yet: ``PERF.md`` section 7):

  toy_gbncc120/       the GBNCC plan on one device, hi-accel on;
  toy_gpps4_hiaccel/  the FAST GPPS plan over a block laid over four
                      (virtual) devices by channels with ``dm_shards``
                      4, hi-accel on inside the fused mesh program,
                      the exchange ``replicate`` at ds=2 and ``partial``
                      at ds=1 as the real cell's is.

Each runs through ``runner.measure`` and is ``correct`` against the
plain reference with ``pulsar_z_err_bins`` reported; with the hi stage
broken (powers scaled; one share's subbands zeroed before the exchange)
it is not.  The cost file the real cell brings, and the reader the owed
mesh cell will bring (``toy_gpps4_hiaccel/layer_metrics/``, a new file
like the rest), read a number on the toys' traced runs.
"""

import json
import math
import os
import shutil
import time

import pytest

from benchmark.harness import cells, layers, runner

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 4800

#: toy root -> (configuration, traffic, chips, the accepted cell whose
#: metrics' `workloads` it joins, as the real cell joins them)
TOYS = {
    "toy_gbncc120": ("toy_gbncc120", "toy_ds21_hiaccel_dm52", 1,
                     "mock_ds2_hiaccel"),
    "toy_gpps4_hiaccel": ("toy_gpps4_hiaccel", "toy_ds21_hiaccel_dm146", 4,
                          "gpps_steps_noaccel_mesh4"),
}
#: the metric each toy reports beside the accepted ones: the real
#: cell's (in BENCHMARK.json), and the one the toy's own root brings
NEW_METRICS = {"toy_gbncc120": "hiaccel_fullres_roofline",
               "toy_gpps4_hiaccel": "mesh_hi_rows_per_call"}
TOY_METRIC = {"name": "mesh_hi_rows_per_call", "unit": "rows",
              "better": "higher", "source": "program_span",
              "layer": "mesh pass, search/executor.py::_sharded_pass",
              "moves": "trials_per_s", "workloads": []}
FIRST_PASS = {"toy_gbncc120": (51.714, 52.224),
              "toy_gpps4_hiaccel": (144.84, 147.9)}


def _toy_root(tmp_path_factory, toy: str) -> str:
    config, traffic, chips, like = TOYS[toy]
    root = str(tmp_path_factory.mktemp(toy + "_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in sorted(os.listdir(os.path.join(HERE, toy))):
        for f in os.listdir(os.path.join(HERE, toy, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(HERE, toy, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": config, "source": "tests", "reduced": ["passes"],
        "file": f"benchmark/configs/{config}.json", "why": "toy"})
    bench["workloads"].append(
        {"name": toy, "config": config, "traffic": traffic,
         "chips": chips, "why": "toy"})
    if toy == "toy_gpps4_hiaccel":
        bench["per_layer"].append(dict(TOY_METRIC))
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads", ())
        if (like in ws and "roofline" not in m["name"]) \
                or m["name"] == NEW_METRICS[toy]:
            m["workloads"] = list(ws) + [toy]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module", params=sorted(TOYS))
def toy(request):
    return request.param


@pytest.fixture(scope="module")
def cell(toy, tmp_path_factory):
    return cells.load_cell(toy, root=_toy_root(tmp_path_factory, toy))


@pytest.fixture(scope="module")
def run(cell):
    """One traced run with the control beside it."""
    return runner.measure(cell, SEED, 0.5, True, t_process=time.time(),
                          warm=True, control=True, log=lambda m: None)


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_slice_is_the_ds2_pass_then_the_ds1_pass_hi_accel_on(toy, cell):
    plan = cells.plan_slice(cell)
    assert [s.downsamp for s in plan] == [2, 1]
    assert [s.numpasses for s in plan] == [1, 1]
    assert sum(s.numdms for s in plan) == 204
    lo, hi = cells.first_pass_dms(plan)
    assert (lo, round(hi, 3)) == FIRST_PASS[toy]
    sp = cells.search_params(cell)
    assert sp.run_hi_accel and sp.hi_accel_zmax == 50
    assert sp.dm_shards == cell.chips
    assert (cell.block_layout is not None) == (cell.chips == 4)


def test_the_cell_runs_and_is_correct_with_the_drift_recovered(toy, run):
    res = run
    got = numbers(res)
    assert res["correct"] is True, got
    assert res["attempted"] == 204 * len(res["calls"])
    assert res["failed"] == 0
    assert res["counters"]["inline_compiles"] == 0
    assert res["counters"]["hi_trials_per_dm"] == 0
    assert all(n["ok"] for n in got.values()), got
    assert got["trials_not_searched"]["value"] == 0
    assert got["degraded_or_rescued_flags"]["value"] == 0
    assert got["pulsar_missing"]["value"] == 0
    # the drifting pulsar came back at its own z, from the ds=2 pass
    assert got["pulsar_z_err_bins"]["value"] <= 2.0
    # powers of hi candidates compared in both passes; float32 plane
    # off a TPU, so far inside the toy's limit
    assert got["hi_power_gap"]["n"] >= 4
    assert got["hi_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["n"] >= 2
    stages = res["calls"][-1]["stage_s"]
    if toy == "toy_gpps4_hiaccel":
        assert {"mesh-exchange", "mesh-place", "sharded-search",
                "mesh-candidates", "subbanding"} <= set(stages)
        assert not {"dedispersing", "hi-accelsearch"} & set(stages)
    else:
        assert {"subbanding", "dedispersing", "hi-accelsearch"} \
            <= set(stages)


def test_the_lower_precision_control_is_caught_by_hi_power_gap(run):
    ctrl = numbers(run, "control")
    assert not ctrl["hi_power_gap"]["ok"]
    assert ctrl["hi_power_gap"]["value"] > 2 * ctrl["hi_power_gap"]["limit"]


def test_every_attached_per_layer_metric_reads_a_number(toy, cell, run):
    """What BENCHMARK.json lists for the real cell is what this path
    opens spans for (the five stage-1 steps only where the Pallas tier
    runs, the memory peak and a module's roofline only on the chip)."""
    got = run["metrics"]
    listed = {m["name"] for m in cell.per_layer()}
    chip_only = {n for n in listed if n.startswith("subband_")
                 and n.endswith("_s_per_pass")
                 and n != "subband_s_per_pass"}
    chip_only |= {"hbm_peak_gib", "hiaccel_fullres_roofline"}
    assert NEW_METRICS[toy] in listed
    for name in sorted(listed - chip_only):
        assert name in got and math.isfinite(got[name]["value"]), name
    if toy == "toy_gpps4_hiaccel":
        assert got["mesh_exchange_s_per_pass"]["value"] > 0.0
    else:
        assert got["hiaccel_ms_per_trial"]["value"] > 0.0


def test_the_mesh_reader_gives_the_rows_a_device_took(toy, cell, run):
    """`mesh_hi_rows_per_call`: the program's own `rows_per_device` of
    the calls that ran the hi stage; nothing to read on one device."""
    from tpulsar.kernels import accel
    from tpulsar.obs import trace

    spans = [e["args"] for e in trace.events() if e["name"] == "mesh_chunk"]
    if toy != "toy_gpps4_hiaccel":
        assert "mesh_hi_rows_per_call" not in run["metrics"]
        return
    rows = run["metrics"]["mesh_hi_rows_per_call"]["value"]
    assert rows >= 1 and rows == int(rows)
    assert spans and all(a["hi"] for a in spans)
    assert rows in {a["rows_per_device"] for a in spans}
    # both exchanges in one call, by name, ds=2 first
    forms = [e["args"]["form"] for e in trace.events()
             if e["name"] == "mesh-exchange"]
    assert forms[:2] == ["replicate", "partial"]
    # and the rows are plane_dm_chunk's, under the fused program's cap
    nbins = 131072 // 2 + 1
    assert max(a["rows_per_device"] for a in spans) <= max(
        accel.plane_dm_chunk(nbins, 51, max_chunk=32),
        accel.plane_dm_chunk(nbins // 2 + 1, 51, max_chunk=32))


@pytest.mark.parametrize("nsamp", [131072, 1_361_920, 1_464_320, 3_932_160])
def test_the_full_length_cost_is_finite_and_grows_with_the_length(
        cell, nsamp, monkeypatch):
    """`hiaccel_chunk_fullres` counts at choose_n(nsamp), whatever the
    slice's first pass; at one row a program (a TPU's), more samples
    are more work, and the bin count is the full length's."""
    from tpulsar.kernels import accel
    from tpulsar.plan import ddplan

    monkeypatch.setattr(accel, "corr_form", lambda: "direct")
    cost = layers.load_cost(cell.bench_dir, "hiaccel_chunk_fullres")
    plain = layers.load_cost(cell.bench_dir, "hiaccel_chunk")
    shapes = {"nsamp": nsamp, "nbins": 7, "hi_rows": 99, "nz": 51,
              "zmax": 50, "numharm": 8, "topk": 32}
    ops, nbytes = cost(shapes)
    assert math.isfinite(ops) and math.isfinite(nbytes) and ops > 0
    full = ddplan.choose_n(nsamp) // 2 + 1
    assert (ops, nbytes) == plain({**shapes, "nbins": full, "hi_rows": 1})
    more = cost({**shapes, "nsamp": 2 * nsamp})
    assert more[0] > ops and more[1] > nbytes


def test_a_broken_hi_stage_is_not_correct(toy, cell, monkeypatch):
    """One device: the hi stage's powers scaled by 5%.  The mesh: one
    share's subbands zeroed on their way into stage 2, so every series
    the fused program searches is a quarter short."""
    from tpulsar.kernels import accel
    from tpulsar.search import executor

    if toy == "toy_gpps4_hiaccel":
        sound = executor._mesh_exchange

        def broken(mesh, subb, *a, **k):
            out = sound(mesh, subb, *a, **k)
            return out.at[: out.shape[0] // 4].set(0.0)

        monkeypatch.setattr(executor, "_mesh_exchange", broken)
    else:
        sound = accel.accel_search_batch

        def broken(*a, **k):
            return {h: (1.05 * t[0],) + tuple(t[1:])
                    for h, t in sound(*a, **k).items()}

        monkeypatch.setattr(accel, "accel_search_batch", broken)
    res = runner.measure(cell, SEED, 0.5, False, t_process=time.time(),
                         warm=False, log=lambda m: None)
    got = numbers(res)
    assert res["correct"] is False
    assert not got["hi_power_gap"]["ok"] or not got["pulsar_missing"]["ok"] \
        or not got["sp_snr_gap"]["ok"], got
