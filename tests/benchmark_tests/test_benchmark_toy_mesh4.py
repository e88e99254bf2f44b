"""The DM-sharded mesh (``SearchParams.dm_shards`` = 4: the north-star
deployment's layout) through the harness on the CPU, at a toy length:
a cell added AS FILES ONLY (``toy_mesh4/``: a configuration whose
``search_params`` state the layout, and a traffic mix) runs through
``runner.measure`` with ``chips`` 4 on four of the eight virtual
devices ``tests/conftest.py`` forces.  The harness hands
``search_block`` no ``mesh=``: the layout reaches it as data.  The run
is ``correct`` against the plain reference, reports the mesh's three
stage metrics, and compiles nothing inside the window.
"""

import json
import os
import shutil
import time

import pytest

from benchmark.harness import cells, runner

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_mesh4")
SEED = 2 ** 31 + 3100
MESH_METRICS = {"mesh_search_ms_per_trial", "mesh_place_s_per_pass",
                "mesh_candidates_s_per_pass"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_mesh4_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_wapp_mesh4", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_wapp_mesh4.json", "why": "toy"})
    bench["workloads"].append(
        {"name": "toy_mesh4", "config": "toy_wapp_mesh4",
         "traffic": "toy_ds1_hiaccel_mesh", "chips": 4, "why": "toy"})
    # attached the way the real mesh cell is: its name appended to the
    # `workloads` of the metrics mock_ds1_hiaccel_mesh4 reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mock_ds1_hiaccel_mesh4" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_mesh4"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def mesh_run(toy_root):
    cell = cells.load_cell("toy_mesh4", root=toy_root)
    res = runner.measure(cell, SEED, 0.5, True, t_process=time.time(),
                         warm=True, control=True, log=lambda m: None)
    return cell, res


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_cell_states_the_layout_as_data(mesh_run):
    cell, _res = mesh_run
    assert cell.chips == 4
    assert cells.search_params(cell).dm_shards == 4
    assert MESH_METRICS <= {m["name"] for m in cell.per_layer()}


def test_mesh_cell_runs_and_is_correct(mesh_run):
    _cell, res = mesh_run
    assert res["correct"] is True
    assert res["attempted"] == 76 * len(res["calls"]) and res["failed"] == 0
    assert res["counters"]["inline_compiles"] == 0
    got = numbers(res)
    assert all(n["ok"] for n in got.values()), got
    assert got["trials_not_searched"]["value"] == 0
    assert got["degraded_or_rescued_flags"]["value"] == 0
    # float32 plane off a TPU: the sharded program's powers and SNRs
    # agree with the plain reference far inside the toy's limits
    assert got["hi_power_gap"]["n"] >= 3
    assert got["hi_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["n"] >= 3 and got["sp_snr_gap"]["value"] < 1e-3


def test_mesh_cell_reports_the_three_stage_metrics(mesh_run):
    cell, res = mesh_run
    got = res["metrics"]
    assert set(got) <= {m["name"] for m in cell.per_layer()}
    assert MESH_METRICS <= set(got)
    assert all(got[m]["value"] > 0 for m in MESH_METRICS)
    assert got["inline_compiles"]["value"] == 0
    # the mesh's stages were the pass: every call timed all three, and
    # none of the solo chunk loop's
    for call in res["calls"]:
        assert {"mesh-place", "sharded-search", "mesh-candidates",
                "subbanding"} <= set(call["stage_s"])
        assert not {"dedispersing", "hi-accelsearch"} & set(call["stage_s"])
    assert got["mesh_search_ms_per_trial"]["unit"] == "ms"


def test_mesh_control_fails_hi_power_gap(mesh_run):
    _cell, res = mesh_run
    ctrl = numbers(res, "control")
    assert not ctrl["hi_power_gap"]["ok"]
