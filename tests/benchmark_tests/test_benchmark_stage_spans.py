"""The per-layer metrics that read the spans INSIDE the stages
(``subband_*_s_per_pass``, ``finish_subband_s``, ``fold_device_s``,
``mesh_wait_`` / ``mesh_fetch_s_per_pass``) are data files alone: a
``stage_timers`` reader over keys that the program's spans leave in a
traced call's ``StageTimers.times``.  ``_stage_timers`` reads a key
that is not there as 0.0, so a mistyped key would report a silent
zero on the chip: every ``stages`` entry of every ``stage_timers``
file has to be a key that a traced toy run or the two-slab search of
``tests/test_stage_spans.py`` produced.
"""

import glob
import json
import os

import pytest

from benchmark.harness import cells
from test_benchmark_toy_mesh4 import toy_root as mesh_root  # noqa: F401
from test_benchmark_toy_run import run, toy_root  # noqa: F401
from test_stage_spans import traced_two_slab_search

ROOT = cells.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
INSIDE = ["subband_slice_s_per_pass", "subband_layout_s_per_pass",
          "subband_kernel_s_per_pass", "subband_join_s_per_pass",
          "subband_downsample_s_per_pass", "finish_subband_s",
          "fold_device_s", "mesh_wait_s_per_pass", "mesh_fetch_s_per_pass"]


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as fh:
        return json.load(fh)


STAGE_TIMERS = sorted(
    name for name in (os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")))
    if _spec(name)["reader"] == "stage_timers")


@pytest.fixture(scope="module")
def steps_run(toy_root):  # noqa: F811
    return run(toy_root, "toy_steps", 4000000077, warm=True, trace=True)


@pytest.fixture(scope="module")
def mesh_run(mesh_root):  # noqa: F811
    return run(mesh_root, "toy_mesh4", 2 ** 31 + 3177, warm=True,
               trace=True)


@pytest.fixture(scope="module")
def produced(steps_run, mesh_run, tmp_path_factory):
    """{where: keys of a traced call's `times`}."""
    _events, timers = traced_two_slab_search(
        str(tmp_path_factory.mktemp("two_slabs")))
    return {
        "toy_steps": set().union(*(c["stage_s"]
                                   for c in steps_run[1]["calls"])),
        "toy_mesh4": set().union(*(c["stage_s"]
                                   for c in mesh_run[1]["calls"])),
        "two_slabs": {k for k, v in timers.times.items() if v}}


def test_the_metrics_inside_the_stages_are_data_files_alone():
    assert set(INSIDE) <= set(STAGE_TIMERS)
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    layers_before = {m["layer"] for m in BENCH["per_layer"]
                     if m["name"] not in INSIDE}
    for name in INSIDE:
        m = entries[name]
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert m["source"] == "program_span" and m["unit"] == "s"
        assert m["layer"] in layers_before and m["workloads"]
    # put together, in this order, after everything that was there
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(INSIDE[0])
    assert names[at:at + len(INSIDE)] == INSIDE and at >= 31


#: an accepted metric's stage that none of the three enters: a pass of
#: two chunks never has two in flight to wait for; and `rfifind`, the
#: stage of `search_beam` before the slice (a read-in cell's, which
#: test_benchmark_readin.py reads off a traced toy read-in)
NOT_IN_A_TOY = {"pipeline-wait", "rfifind"}


@pytest.mark.parametrize("metric", STAGE_TIMERS)
def test_every_stage_timers_key_is_one_a_traced_run_leaves(
        metric, produced):
    seen = set().union(NOT_IN_A_TOY, *produced.values())
    for key in _spec(metric)["stages"]:
        assert key in seen, (
            f"{metric}.json reads {key!r}: no traced toy run left that "
            "key in StageTimers.times, so the metric would read 0.0")


def test_the_toy_cells_report_the_new_metrics(steps_run, mesh_run):
    cell, res = steps_run
    got = res["metrics"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(INSIDE) <= {m["name"] for m in cell.per_layer()}
    # the finish re-forms subbands for the one candidate it folds; on
    # the CPU stage 1 is the XLA map, which has no steps to name
    assert 0 < got["finish_subband_s"]["value"] \
        <= got["refine_s"]["value"] + got["fold_s"]["value"]
    assert 0 < got["fold_device_s"]["value"] <= got["fold_s"]["value"]
    assert got["subband_kernel_s_per_pass"]["value"] == 0.0
    cell, res = mesh_run
    got = res["metrics"]
    assert res["correct"] is True and res["failed"] == 0
    search_s = sum(c["stage_s"]["sharded-search"] for c in res["calls"]) \
        / len(res["calls"])        # one pass a call
    wait, fetch = (got[m]["value"] for m in ("mesh_wait_s_per_pass",
                                             "mesh_fetch_s_per_pass"))
    assert wait > 0 and fetch > 0
    assert 0.5 * search_s < wait + fetch <= search_s
    # the wait is the program, the fetch what is left once it is done
    assert fetch < wait


def test_the_harness_keeps_the_programs_own_annotations(steps_run):
    """`runner.measure` hands `tracered.load_xplane` the keys of the
    calls' `stage_s` as the host events to keep: with the spans' own
    names among them, `idle_gaps` can name a gap by the program's span
    (the innermost kept annotation) where it named the stage."""
    _cell, res = steps_run
    kept = set().union(*(c["stage_s"] for c in res["calls"]))
    assert {"sp-events", "lo-candidates", "pass-checkpoint",
            "refine-series", "fold-subbands"} <= kept
