"""The rest of a run, driven past the harness's look for a chip, on a toy
cell that is ADDED AS FILES ONLY: a temporary copy of BENCHMARK.json and
``benchmark/`` gains a configuration, two traffic mixes, two per-layer
metrics and a cost function as new files plus new entries, and no file
that was there is edited.

On that cell: the check passes on a sound run; it fails when the
program's powers take a bfloat16 round trip, when the lower-precision
control stands in the program's place, and when the timed path is
broken underneath (an answer altered where it is produced, a part of
the work left out).
"""

import copy
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import cells, check, layers, runner, window

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_cell")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    before = {}
    for d, _sub, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    for sub in ("configs", "traffic", "layer_metrics", "costs"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_wapp", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_wapp.json", "why": "toy"})
    bench["workloads"] += [
        {"name": "toy_hi", "config": "toy_wapp",
         "traffic": "toy_ds1_hiaccel", "chips": 1, "why": "toy"},
        {"name": "toy_steps", "config": "toy_wapp",
         "traffic": "toy_steps_noaccel", "chips": 1, "why": "toy"}]
    toy_cells = ["toy_hi", "toy_steps"]
    bench["per_layer"] += [
        {"name": "toy_sift_s", "unit": "s", "better": "lower",
         "source": "program_span", "layer": "sift", "moves": "finish_s",
         "workloads": toy_cells},
        {"name": "toy_cands", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "sift", "moves": "finish_s",
         "workloads": toy_cells}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        # every slice-call metric that names its cells (a read-in
        # cell's are another unit of work's)
        if "workloads" in m and not m["name"].startswith("toy_") \
                and "mock_readin" not in m["workloads"]:
            m["workloads"] = m["workloads"] + (
                ["toy_hi"] if "hiaccel" in m["name"] else toy_cells)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    # nothing that was there has changed
    for path, data in before.items():
        assert open(path, "rb").read() == data
    return root


def run(toy_root, name, seed, warm=False, **kw):
    cell = cells.load_cell(name, root=toy_root)
    return cell, runner.measure(cell, seed, 0.5, kw.pop("trace", False),
                                t_process=time.time(), warm=warm,
                                log=lambda m: None, **kw)


@pytest.fixture(scope="module")
def hi_run(toy_root):
    # warmed as a real run is: where an earlier test of the same process
    # has switched the persistent compile cache on, the program's
    # counter sees every first compile, and one inside the window
    # makes a run not correct
    return run(toy_root, "toy_hi", 2 ** 31 + 12345, warm=True,
               control=True)


@pytest.fixture(scope="module")
def steps_run(toy_root):
    return run(toy_root, "toy_steps", 4000000001, warm=True, control=True,
               trace=True)


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_a_cell_added_as_files_only_runs_and_is_correct(hi_run):
    cell, res = hi_run
    assert cell.bench_dir.endswith("benchmark") and cell.name == "toy_hi"
    assert res["correct"] is True
    assert res["attempted"] == 76 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert "trials_per_s" in res["metrics"] and "readin_s" not in \
        res["metrics"]
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["metrics"]["trials_per_s"]["unit"] == "trials/s"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(res)                   # the result line is plain JSON


def test_every_number_compared_is_printed_beside_its_limit(hi_run):
    _cell, res = hi_run
    got = numbers(res)
    assert set(got) == {
        "trials_not_searched", "degraded_or_rescued_flags",
        "pulsar_missing", "pulsar_period_frac_err", "pulsar_z_err_bins",
        "lo_best_missing", "lo_power_gap", "hi_power_gap", "sp_snr_gap"}
    assert all(n["ok"] and n["value"] <= n["limit"] for n in got.values())
    # the numeric gaps had answers to compare, and agree far inside
    for name in ("hi_power_gap", "sp_snr_gap"):
        assert got[name]["n"] >= 3
    assert got["hi_power_gap"]["value"] < 1e-4
    assert got["lo_best_missing"]["n"] >= 1


@pytest.mark.parametrize("which", ["hi", "steps"])
def test_the_lower_precision_control_comes_out_not_correct(
        which, hi_run, steps_run):
    _cell, res = hi_run if which == "hi" else steps_run
    ctrl = numbers(res, "control")
    assert ctrl and not all(n["ok"] for n in ctrl.values())
    # and by a margin: at least three times the limit somewhere
    assert max(n["value"] / n["limit"] for n in ctrl.values()) > 3


def test_traced_run_reports_the_cells_per_layer_metrics(steps_run):
    cell, res = steps_run
    assert res["correct"] is True and res["attempted"] == 228
    want = {m["name"] for m in cell.per_layer()}
    assert "hiaccel_ms_per_trial" not in want      # not this cell's
    assert {"toy_sift_s", "toy_cands"} <= want     # the added readers
    got = set(res["metrics"])
    # a reader with nothing to read leaves its metric out: no device
    # plane and no memory statistics on a CPU
    assert got <= want
    assert {"dedisp_ms_per_trial", "spectra_ms_per_trial", "refine_s",
            "rfifind_s", "inline_compiles", "toy_sift_s", "toy_cands"} <= got
    assert "dedisp_roofline" not in got and "hbm_peak_gib" not in got
    assert res["metrics"]["toy_cands"]["value"] >= 1
    assert res["metrics"]["inline_compiles"]["value"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert len(res["calls"][0]["stage_s"]) >= 6


def test_deep_steps_were_compared_too(steps_run, toy_root):
    """Series lengths that downsamp 5 and 25 do not divide: the
    single-pulse gap draws from every pass of the slice."""
    _cell, res = steps_run
    got = numbers(res)
    assert got["sp_snr_gap"]["n"] > 10 and got["sp_snr_gap"]["ok"]
    assert got["lo_power_gap"]["ok"] and got["lo_power_gap"]["value"] < 1e-4


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def test_check_fails_when_the_programs_powers_take_a_bf16_round_trip(
        toy_root):
    cell = cells.load_cell("toy_steps", root=toy_root)
    host = {}
    block, psr, plan, params = runner.setup(cell, 99, host)
    call = window.slice_call(block, cell.freqs, cell.dt, plan, params)
    sound = check.check(cell, plan, psr, call, block, 99)
    assert sound["correct"], sound["numbers"]
    bad = copy.copy(call)
    bad.dumps = copy.deepcopy(call.dumps)
    for d in bad.dumps:
        d["cands"]["power"] = _bf16(d["cands"]["power"])
    verdict = check.check(cell, plan, psr, bad, block, 99)
    failed = [n["name"] for n in verdict["numbers"] if not n["ok"]]
    assert not verdict["correct"] and failed == ["lo_power_gap"]


def test_correct_is_false_when_an_answer_is_altered_where_it_is_made(
        toy_root, monkeypatch):
    """The timed path broken underneath: the lo stage's powers scaled
    by 1% inside the program, the rest of the run driven as it is."""
    from tpulsar.kernels import fourier as fr
    real = fr.lo_stage_candidates

    def skewed(wspec, stages, topk):
        return {h: (v * 1.01, b) for h, (v, b) in
                real(wspec, stages, topk).items()}

    monkeypatch.setattr(fr, "lo_stage_candidates", skewed)
    _cell, res = run(toy_root, "toy_steps", 4242)
    assert res["correct"] is False
    assert not numbers(res)["lo_power_gap"]["ok"]
    assert numbers(res)["sp_snr_gap"]["ok"]


def test_correct_is_false_when_a_part_of_the_work_is_left_out(toy_root):
    from tpulsar.search import executor

    def lazy(data, freqs, dt, plan, params, **kw):
        return executor.search_block(data, freqs, dt, plan[:-1], params,
                                     **kw)

    _cell, res = run(toy_root, "toy_steps", 4243, search_block=lazy)
    assert res["correct"] is False
    assert res["failed"] == 76 and res["attempted"] == 228
    assert not numbers(res)["trials_not_searched"]["ok"]


def test_failed_counts_a_forced_degraded_flag(toy_root):
    from tpulsar.search import degraded, executor

    def flagged(data, freqs, dt, plan, params, **kw):
        out = executor.search_block(data, freqs, dt, plan, params, **kw)
        degraded.note("accel_batch_pinned", "forced by the test")
        return out

    _cell, res = run(toy_root, "toy_steps", 4244, search_block=flagged)
    assert res["failed"] == res["attempted"] == 228
    assert res["correct"] is False
    assert not numbers(res)["degraded_or_rescued_flags"]["ok"]


def test_added_cost_function_is_found_by_name(toy_root):
    cost = layers.load_cost(os.path.join(toy_root, "benchmark"), "toy_cost")
    assert cost({"T": 10}) == (20.0, 80.0)
