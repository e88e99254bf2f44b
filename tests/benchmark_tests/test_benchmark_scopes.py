"""The reduction that reads the program's named scopes and spans
(benchmark/harness/scopes.py) and the per-layer metrics built on it:
the wire-format reader against jax's own serializer, the arithmetic by
hand, a recorded sample cut from a real chip trace, every new reader
on a context with nothing to read, and the toy run's result line with
the new entries attached to the toy cells."""

import json
import os
import types

import pytest

from benchmark.harness import cells, layers, scopes

# the toy checkout and its runner, as the toy-run tests build them
from test_benchmark_toy_run import run, toy_root  # noqa: F401

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
LAYOUT = json.load(open(os.path.join(ROOT, "benchmark", "trace_layout.json")))
SCOPES, WHOLE = scopes.known_scopes(os.path.join(ROOT, "benchmark"))

DEVICE_METRICS = sorted(
    m["name"] for m in BENCH["per_layer"] if os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    and m["source"] == "device_trace" and m["moves"] != "readin_s")
SPAN_METRICS = ["pass_end_host_s_per_pass", "loop_uncovered_pct",
                "refine_host_s", "fold_host_s", "cands_folded"]


# ------------------------------------------------------ by hand

def test_an_operation_goes_to_the_innermost_scope_on_its_path():
    assert scopes.scope_of(
        "jit(accel_chunk_topk)/jit(_accel_block_topk)/hiaccel/harmsum/"
        "vmap(jit(_harmonic_stage_maxes))/add:", SCOPES) == "hiaccel/harmsum"
    assert scopes.scope_of(
        "jit(boxcar_search)/sp/boxcar/jit(blockmax_topk)/top_k:",
        SCOPES) == "sp/boxcar"
    # nested: the inner one wins; a look-alike component does not match
    assert scopes.scope_of("jit(f)/lo/harmsum/x/lo/topk/max:",
                           SCOPES) == "lo/topk"
    assert scopes.scope_of("jit(f)/solo/topk2/add:", SCOPES) is None
    assert scopes.scope_of("", SCOPES) is None
    assert scopes.program_of("jit_accel_chunk_topk(72744038)") == \
        "jit_accel_chunk_topk"


def test_exclusive_time_takes_nested_events_out_of_their_parent():
    #            while 0-100 [ body 10-30, body 40-90 [ inner 50-60 ] ], 120-130
    events = [(0, 100), (10, 20), (40, 50), (50, 10), (120, 10)]
    assert scopes.exclusive_ns(events) == [30.0, 20.0, 40.0, 10.0, 10.0]
    # order does not matter, and the parts add up to the union
    shuffled = [events[k] for k in (3, 0, 4, 2, 1)]
    assert sum(scopes.exclusive_ns(shuffled)) == 110.0


def test_a_calls_host_seconds_are_named_by_span():
    """The log's `span_self_s`: a stall inside a stage is either in one
    of its host spans or in the stage's own seconds (dispatch, fence)."""
    from tpulsar.obs import trace

    events = [
        {"name": "single-pulse", "id": 1, "parent_id": 0, "ph": "X",
         "ts": 0.0, "dur": 2e6},
        {"name": "sp-events", "id": 2, "parent_id": 1, "ph": "X",
         "ts": 1.5e6, "dur": 0.25e6},
        {"name": "single-pulse", "id": 3, "parent_id": 0, "ph": "X",
         "ts": 3e6, "dur": 1e6}]
    assert scopes._self_by_name(trace, events) == {
        "single-pulse": [2, 3.0, 2.75], "sp-events": [1, 0.25, 0.25]}


XSPACE = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 200000000 duration_ps: 30000000 }
    events { metadata_id: 6 offset_ps: 300000000 duration_ps: 30000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 50000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 60000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 201000000 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 301000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 315000000 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 321000000 duration_ps: 3000000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 900000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_prog(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = while(...)"
      display_name: "while.1"
      stats { metadata_id: 8 int64_value: 5 }
      stats { metadata_id: 7 str_value: "jit(prog)/spectra/whiten/while:" } } }
  event_metadata { key: 3 value { id: 3 name: "%sort.2 = sort(...)"
      stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(5)" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.3 = copy(...)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_solo(7)" } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.9 = fusion(...)"
      stats { metadata_id: 7 str_value: "jit(solo)/sp/boxcar/add:" } } }
  event_metadata { key: 8 value { id: 8 name: "%reduce-window.1 = ..."
      stats { metadata_id: 7 str_value: "reduce_window_sum:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value {
      id: 9 name: "jit(prog)/spectra/whiten/while/body/sp/detrend/sort:" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000
      stats { metadata_id: 1 str_value: "tpulsar" }
      stats { metadata_id: 2 int64_value: 38 } }
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 1000 }
    events { metadata_id: 3 offset_ps: 1000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "dm_chunk" } }
  event_metadata { key: 2 value { id: 2 name: "dm_chunk" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
  stat_metadata { key: 1 value { id: 1 name: "src" } }
  stat_metadata { key: 2 value { id: 2 name: "n" } }
}
'''


@pytest.fixture(scope="module")
def xspace_file(tmp_path_factory):
    import jax

    path = str(tmp_path_factory.mktemp("xs") / "t.xplane.pb")
    with open(path, "wb") as fh:
        fh.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            XSPACE))
    return path


def test_wire_reader_finds_the_op_names_jax_serialized(xspace_file):
    names = scopes.op_names(xspace_file, LAYOUT["device_plane"])
    assert names == {"/device:TPU:0": {
        "%while.1 = while(...)": "jit(prog)/spectra/whiten/while:",
        "%sort.2 = sort(...)":
            "jit(prog)/spectra/whiten/while/body/sp/detrend/sort:",
        "%fusion.9 = fusion(...)": "jit(solo)/sp/boxcar/add:",
        "%reduce-window.1 = ...": "reduce_window_sum:"}}
    # a plane that does not match is not read; another stat is not tf_op
    assert scopes.op_names(xspace_file, "^/nothing$") == {}
    assert scopes.op_names(xspace_file, LAYOUT["device_plane"],
                           stat="flops") == {"/device:TPU:0": {}}


def test_a_written_xspace_reduces_to_the_numbers_by_hand(xspace_file):
    planes = scopes.load_planes(xspace_file, LAYOUT)
    dev, host = planes
    assert [ln["kind"] for ln in dev["lines"]] == ["modules", "ops"]
    # of the doubled name both are kept, the unrelated event is not
    assert [e[0] for e in host["lines"][0]["events"]] == ["dm_chunk"] * 2
    names = scopes.op_names(xspace_file, LAYOUT["device_plane"])
    red = scopes.reduce_planes(planes, names, SCOPES)
    # while 2-52 us holds the sort (3-13 us): 40 us of its own.  The
    # compiler's copy, which has no op_name, and a bare
    # `reduce_window_sum:` are under <program>/other in every program
    assert red["scope_s"] == pytest.approx({
        "spectra/whiten": 40e-6, "sp/detrend": 10e-6,
        "jit_prog/other": 20e-6, "jit_other/other": 20e-6,
        "sp/boxcar": 10e-6, "jit_solo/other": 8e-6})
    assert red["reassigned_s"] == {}
    # ... unless the scopes' file says that a program's whole body is
    # one scope: then all of its operations are that scope's, and what
    # the rule moved is said beside it
    red = scopes.reduce_planes(planes, names, SCOPES,
                               {"jit_solo": "sp/boxcar"})
    assert red["scope_s"] == pytest.approx({
        "spectra/whiten": 40e-6, "sp/detrend": 10e-6,
        "jit_prog/other": 20e-6, "jit_other/other": 20e-6,
        "sp/boxcar": 18e-6})
    assert red["reassigned_s"] == pytest.approx({"jit_solo": 8e-6})
    # a program none of whose operations carries the scope was not
    # compiled with it (the parent commit, a stale compile cache): the
    # rule leaves it alone
    bare = scopes.reduce_planes(planes, names, SCOPES,
                                {"jit_other": "sp/boxcar"})
    assert bare["scope_s"]["jit_other/other"] == pytest.approx(20e-6)
    assert bare["reassigned_s"] == {}
    assert red["program_s"] == pytest.approx(
        {"jit_prog": 70e-6, "jit_other": 20e-6, "jit_solo": 18e-6})
    assert red["module_calls"] == {"jit_prog": 1, "jit_other": 1,
                                   "jit_solo": 1}
    assert [row[:3] for row in red["other_top"]] == [
        ["jit_prog", "%copy.3 = copy(...)", ""],
        ["jit_other", "%copy.3 = copy(...)", ""]]
    # the program's annotation only: its n, not the harness's twin
    assert red["trials"] == 38 and len(red["annotations"]) == 1


# --------------------------------- a sample cut from a real chip trace

RECORDED = json.load(open(os.path.join(HERE, "recorded_scopes.json")))


def test_recorded_sample_has_what_the_reduction_reads():
    assert "TPU v5 lite" in RECORDED["recorded"]
    dev = [p for p in RECORDED["planes"] if p["device"]]
    host = [p for p in RECORDED["planes"] if not p["device"]]
    assert len(dev) == 1 and len(host) == 1
    kinds = {ln["kind"] for ln in dev[0]["lines"]}
    assert kinds == {"modules", "ops"}
    programs = {scopes.program_of(e[0]) for ln in dev[0]["lines"]
                if ln["kind"] == "modules" for e in ln["events"]}
    assert {"jit_accel_chunk_topk", "jit_whitened_spectrum",
            "jit_lo_stage_candidates", "jit_normalize_series",
            "jit_boxcar_search", "jit__dedisperse_chunk"} <= programs
    found = {scopes.scope_of(v, SCOPES)
             for v in RECORDED["op_names"][dev[0]["name"]].values()}
    assert set(SCOPES) <= found
    # stage names twice in the host plane: the harness's and the program's
    stage = [e for ln in host[0]["lines"] for e in ln["events"]
             if e[0] == "dedispersing"]
    assert {e[3].get("src") for e in stage} == {"tpulsar", None}


def test_recorded_sample_reduces_to_the_numbers_read_off_it_by_hand():
    red = scopes.reduce_planes(RECORDED["planes"], RECORDED["op_names"],
                               SCOPES, WHOLE)
    want = RECORDED["by_hand"]
    assert red["reassigned_s"] == pytest.approx(want["reassigned_s"])
    assert red["trials"] == want["trials"]
    assert red["module_calls"] == want["module_calls"]
    assert red["scope_s"] == pytest.approx(want["scope_s"], rel=1e-9)
    # every operation is counted once: scopes and <program>/other add up
    # to the programs, and those to the union of the sampled operations
    assert sum(red["scope_s"].values()) == pytest.approx(
        sum(red["program_s"].values()))
    for prog, total in red["program_s"].items():
        mine = sum(v for k, v in red["scope_s"].items()
                   if k == prog + "/other")
        assert mine <= total + 1e-12
    assert all(a["stats"]["src"] == "tpulsar" for a in red["annotations"])
    # of doubled stage names only the program's; its whole chain below
    # the pass is there (the pass and the search_block close after the
    # harness stops the profiler: a Mock pass outlasts the traced span),
    # each with the id of its search_block as `call`
    names = [a["name"] for a in red["annotations"]]
    assert names == ["subbanding", "dm_chunk", "dedispersing",
                     "single-pulse", "FFT", "lo-accelsearch",
                     "hi-accelsearch", "accel-dispatch", "accel-sync",
                     "accel-candidates"]
    by = {a["name"]: a["stats"] for a in red["annotations"]}
    assert len({st["call"] for st in by.values()}) == 1
    assert len({st["id"] for st in by.values()}) == len(by)
    assert by["dm_chunk"]["n"] == 38 and by["dm_chunk"]["pass_idx"] == 0
    assert (by["accel-dispatch"]["chunks"], by["accel-dispatch"]["rows"],
            by["accel-sync"]["chunks"]) == (19, 38, 19)


# ------------------------------------ nothing to read: nothing reported

def _ctx(tmp_path, trace):
    bench_dir = os.path.join(str(tmp_path), "benchmark")
    os.makedirs(bench_dir, exist_ok=True)
    with open(os.path.join(bench_dir, "trace_scopes.json"), "w") as fh:
        json.dump({"scopes": SCOPES, "whole_programs": WHOLE}, fh)
    return {"calls": [], "trials": 0, "passes": 0, "ncalls": 0,
            "trace": trace, "layout": LAYOUT, "bench_dir": bench_dir}


@pytest.mark.parametrize("metric", DEVICE_METRICS + SPAN_METRICS)
@pytest.mark.parametrize("trace", [None, {"planes": []}],
                         ids=["untraced", "no-trace-file"])
def test_a_reader_with_nothing_to_read_returns_none(metric, trace, tmp_path):
    ctx = _ctx(tmp_path, trace)
    mod = layers._load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".py"))
    assert mod.read(ctx) is None


@pytest.mark.parametrize("metric", DEVICE_METRICS)
def test_a_device_reader_returns_none_without_a_device_plane(
        metric, tmp_path, xspace_file):
    """A CPU run's trace: a host plane, no /device:TPU plane."""
    import jax
    import shutil

    ctx = _ctx(tmp_path, {"planes": []})
    d = os.path.join(str(tmp_path), ".bench_trace", "plugins", "profile",
                     "2026_01_01")
    os.makedirs(d)
    host_only = XSPACE[XSPACE.index('planes {\n  name: "/host:CPU"'):]
    with open(os.path.join(d, "h.xplane.pb"), "wb") as fh:
        fh.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            host_only))
    assert layers.read_metric(metric, {**ctx, "bench_dir": os.path.join(
        ROOT, "benchmark")}) is None                 # no trace there at all
    shutil.copytree(os.path.join(ROOT, "benchmark", "layer_metrics"),
                    os.path.join(ctx["bench_dir"], "layer_metrics"))
    assert layers.read_metric(metric, ctx) is None
    assert ctx["_scopes"] == {}
    # with the device plane beside it the same reader finds its scope
    shutil.copy(xspace_file, os.path.join(d, "z.xplane.pb"))
    ctx.pop("_scopes")
    got = layers.read_metric("spectra_whiten_ms_per_trial", ctx)
    assert got == pytest.approx(1e3 * 40e-6 / 38)
    assert ctx["notes"]["scopes"]["trials"] == 38


def test_span_readers_cut_the_programs_events_to_the_windows_calls():
    """Two slice calls and a warm-up before them: only the spans inside
    a call's [t_start, t_end] count, each call by its own events."""
    from tpulsar.obs import trace

    trace.reset()
    trace.start()
    try:
        base = trace.epoch()

        def ev(id_, parent, name, t0, dur, **args):
            return {"name": name, "ph": "X", "ts": t0 * 1e6,
                    "dur": dur * 1e6, "id": id_, "parent_id": parent,
                    "call": 0, "args": args}

        fake = []
        for k, t in enumerate((0.0, 100.0, 200.0)):     # warm-up, two calls
            i = 10 * k
            fake += [
                ev(i + 1, 0, "pass", t + 1, 50),
                ev(i + 2, i + 1, "dm_chunk", t + 2, 40),
                ev(i + 3, i + 2, "hi-accelsearch", t + 2, 30),  # 10 s bare
                ev(i + 4, i + 1, "pass-checkpoint", t + 45, 2),
                ev(i + 5, i + 1, "sp-events", t + 48, 1),
                ev(i + 6, 0, "folding", t + 60, 3, n=k + 1),
                ev(i + 7, i + 6, "fold-host", t + 60, 1),
                ev(i + 8, 0, "refine-host", t + 55, 4)]
        calls = [types.SimpleNamespace(t_start=base + t, t_end=base + t + 70)
                 for t in (100.0, 200.0)]
        ctx = {"calls": calls, "trials": 76, "passes": 2, "ncalls": 2,
               "trace": {"planes": []}, "bench_dir": os.path.join(
                   ROOT, "benchmark"), "layout": LAYOUT}
        real = trace.events
        trace.events = lambda: fake
        try:
            got = {m: layers.read_metric(m, ctx) for m in SPAN_METRICS}
        finally:
            trace.events = real
    finally:
        trace.reset()
    assert got["pass_end_host_s_per_pass"] == pytest.approx(3.0)
    # of a 50 s pass: 50 - 40 - 2 - 1 = 7 bare, and 10 through dm_chunk
    assert got["loop_uncovered_pct"] == pytest.approx(34.0)
    assert got["refine_host_s"] == pytest.approx(4.0)
    assert got["fold_host_s"] == pytest.approx(1.0)
    assert got["cands_folded"] == pytest.approx(2.5)    # calls 2 and 3


# ----------------------------------------------------- the toy cells

NEW = {m["name"] for m in BENCH["per_layer"] if os.path.exists(
    os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    and m["moves"] != "readin_s"}      # the read-in unit's: its own test


def test_every_new_entry_has_its_reader_and_names_an_accepted_layer():
    assert NEW == set(DEVICE_METRICS) | set(SPAN_METRICS)
    old_layers = {m["layer"] for m in BENCH["per_layer"]
                  if m["name"] not in NEW}
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["layer"] in old_layers
            assert m["source"] in ("device_trace", "program_span")
            assert set(m["workloads"]) <= {"mock_ds1_hiaccel",
                                           "wapp_steps_noaccel"}


def test_toy_traced_run_reports_the_span_metrics_and_stays_correct(
        toy_root):  # noqa: F811
    cell, res = run(toy_root, "toy_hi", 2 ** 31 + 777, warm=True, trace=True)
    want = {m["name"] for m in cell.per_layer()}
    assert NEW <= want                       # attached to the toy cell
    got = res["metrics"]
    assert res["correct"] is True and set(got) <= want
    # the program's spans are read on any backend ...
    assert set(SPAN_METRICS) <= set(got)
    assert got["cands_folded"]["value"] >= 1
    assert 0.0 <= got["loop_uncovered_pct"]["value"] < 50.0
    assert 0.0 < got["refine_host_s"]["value"] <= got["refine_s"]["value"]
    assert 0.0 < got["fold_host_s"]["value"] <= got["fold_s"]["value"]
    assert got["pass_end_host_s_per_pass"]["value"] > 0.0
    # ... the device's scopes only where there is a device plane
    assert not set(DEVICE_METRICS) & set(got)
    assert got["inline_compiles"]["value"] == 0
