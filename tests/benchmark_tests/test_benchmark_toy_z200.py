"""The deep acceleration search (zmax 200, 16 harmonics: BASELINE
config 3) through the harness on the CPU, at a toy length: a cell added
AS FILES ONLY (``toy_z200/``: a configuration and a traffic mix whose
pulsar drifts |z| = 100 bins, twice the edge of a zmax 50 bank) runs
through ``runner.measure``, is ``correct`` against the plain reference
(``reference.HiStage`` takes any zmax), and its lower-precision control
fails ``hi_power_gap``.
"""

import json
import os
import shutil
import time

import pytest

from benchmark.harness import cells, runner

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_z200")
SEED = 2 ** 31 + 2700


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_z200_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_wapp_z200", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_wapp_z200.json", "why": "toy"})
    bench["workloads"].append(
        {"name": "toy_z200", "config": "toy_wapp_z200",
         "traffic": "toy_ds1_hiaccel_z100", "chips": 1, "why": "toy"})
    # attached the way a real cell is: its name appended to the
    # `workloads` of the slice-call metrics that were there
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "mock_readin" not in m["workloads"]:
            m["workloads"] = m["workloads"] + ["toy_z200"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def z200_run(toy_root):
    cell = cells.load_cell("toy_z200", root=toy_root)
    res = runner.measure(cell, SEED, 0.5, False, t_process=time.time(),
                         warm=True, control=True, log=lambda m: None)
    return cell, res


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_cell_states_the_deep_search(z200_run):
    cell, _res = z200_run
    sp = cells.search_params(cell)
    assert (sp.hi_accel_zmax, sp.hi_accel_numharm) == (200, 16)
    assert sp.run_hi_accel and cell.traffic["pulsar"]["abs_z"] == [100.0,
                                                                    100.0]


def test_z200_cell_runs_and_is_correct(z200_run):
    _cell, res = z200_run
    assert res["correct"] is True
    assert res["attempted"] == 76 and res["failed"] == 0
    assert res["counters"]["hi_trials_per_dm"] == 0
    assert res["counters"]["inline_compiles"] == 0
    got = numbers(res)
    assert all(n["ok"] for n in got.values()), got
    # the drifting pulsar came back at its own z, and powers of
    # candidates at |z| > 50 and of 16-harmonic sums were compared
    assert got["pulsar_z_err_bins"]["value"] <= 2.0
    assert got["hi_power_gap"]["n"] >= 3
    assert got["hi_power_gap"]["value"] < 1e-4      # float32 plane here


def test_z200_control_fails_hi_power_gap(z200_run):
    _cell, res = z200_run
    ctrl = numbers(res, "control")
    assert not ctrl["hi_power_gap"]["ok"]
    assert ctrl["hi_power_gap"]["value"] > 2 * ctrl["hi_power_gap"]["limit"]
