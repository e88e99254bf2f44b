"""The span tree of tpulsar/obs/trace.py: ids, parents and calls over a
toy search, the reducers on hand-built events, the spans as profiler
annotations on CPU, and the disabled path."""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from tpulsar.obs import trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_trace(monkeypatch):
    # the chip's hi-accel path (chunk programs dispatched and drained),
    # not the CPU backend's native consumer
    monkeypatch.setenv("TPULSAR_ACCEL_NATIVE", "0")
    trace.reset()
    yield
    trace.reset()


# --------------------------------------------------- a toy two-pass search

@pytest.fixture(scope="module")
def toy_search(tmp_path_factory):
    """(block, freqs, dt, plan, params): a small beam with one pulsar,
    two dedispersion passes, hi-accel on."""
    import jax.numpy as jnp
    from tpulsar.io import synth
    from tpulsar.io.psrfits import SpectraInfo
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    root = tmp_path_factory.mktemp("spans")
    spec = synth.BeamSpec(nchan=32, nsamp=1 << 14, nbits=4,
                          tsamp_s=5.24288e-4)
    psr = synth.PulsarSpec(period_s=0.15, dm=60.0, snr_per_sample=0.6,
                           width_frac=0.05)
    si = SpectraInfo(synth.synth_beam(str(root / "beam"), spec,
                                      pulsars=[psr], merged=True))
    block = jnp.asarray(np.ascontiguousarray(si.read_all().T))
    plan = [ddplan.DedispStep(lodm=40.0, dmstep=2.0, dms_per_pass=10,
                              numpasses=2, numsub=16, downsamp=1)]
    params = executor.SearchParams(
        nsub=16, hi_accel_zmax=8, topk_per_stage=8, max_dms_per_chunk=5,
        max_cands_to_fold=2, make_plots=False)
    return block, np.asarray(si.freqs), float(si.dt), plan, params


@pytest.fixture(scope="module")
def two_calls(toy_search):
    """The events of two traced slice calls, and their StageTimers."""
    from tpulsar.search import executor
    from tpulsar.search.report import StageTimers

    block, freqs, dt, plan, params = toy_search
    trace.reset()
    trace.start()
    timers = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPULSAR_ACCEL_NATIVE", "0")
        try:
            for _ in range(2):
                timers.append(StageTimers())
                executor.search_block(block, freqs, dt, plan, params,
                                      timers=timers[-1])
            events = trace.events()
        finally:
            trace.reset()
    return events, timers


def _spans(events, name=None):
    # a worker whose earlier tests installed the runtime compile monitor
    # also records retroactive `backend_compile` events: not the tree's
    return [e for e in events if e["ph"] == "X"
            and e["name"] != "backend_compile"
            and (name is None or e["name"] == name)]


def _children(events, parent):
    return [e for e in _spans(events) if e["parent_id"] == parent["id"]]


def test_two_calls_are_two_trees_with_their_own_call(two_calls):
    events, _timers = two_calls
    roots = _spans(events, "search_block")
    assert len(roots) == 2
    assert all(r["parent_id"] == 0 and r["call"] == r["id"] for r in roots)
    assert roots[0]["call"] != roots[1]["call"]
    by_id = {e["id"]: e for e in _spans(events)}
    assert len(by_id) == len(_spans(events))          # ids are unique
    for e in events:
        # every event hangs in the tree of the call it carries
        up = e
        while up.get("parent_id"):
            up = by_id[up["parent_id"]]
            assert up["call"] == e["call"]
        assert up["name"] == "search_block" and up["id"] == e["call"]
        # and the flat readers' fields agree with the ids
        if e.get("parent_id"):
            assert e["args"]["parent"] == by_id[e["parent_id"]]["name"]


def test_pass_chunk_stage_and_finish_nest_as_the_table_says(two_calls):
    events, _timers = two_calls
    for root in _spans(events, "search_block"):
        kids = _children(events, root)
        passes = [k for k in kids if k["name"] == "pass"]
        assert [p["args"]["pass_idx"] for p in passes] == [0, 1]
        assert {k["name"] for k in kids} == {"pass", "finish"}
        for p in passes:
            assert p["args"]["ntrials"] == 10 and p["args"]["downsamp"] == 1
            under = _children(events, p)
            chunks = [k for k in under if k["name"] == "dm_chunk"]
            assert [c["args"]["n"] for c in chunks] == [5, 5]
            assert {"subbanding", "pipeline-drain", "single-pulse",
                    "lo-accelsearch"} <= {k["name"] for k in under}
            for c in chunks:
                stages = {k["name"]: k for k in _children(events, c)}
                assert {"dedispersing", "single-pulse", "FFT",
                        "lo-accelsearch", "hi-accelsearch"} <= set(stages)
                hi = {k["name"]: k for k in
                      _children(events, stages["hi-accelsearch"])}
                assert {"accel-dispatch", "accel-sync",
                        "accel-candidates"} <= set(hi)
                assert hi["accel-dispatch"]["args"]["rows"] == 5
                assert hi["accel-candidates"]["args"]["cands"] >= 0
            # the pass end's host halves, inside the timers that stay
            for k in under:
                inner = {c["name"] for c in _children(events, k)}
                if k["name"] == "single-pulse":
                    assert inner == {"sp-events"}
                if k["name"] == "lo-accelsearch":
                    assert inner == {"lo-candidates"}
        (finish,) = [k for k in kids if k["name"] == "finish"]
        parts = {k["name"]: k for k in _children(events, finish)}
        assert {"sifting", "refinement", "folding"} <= set(parts)
        sift = parts["sifting"]["args"]
        assert sift["n_in"] >= sift["n_out"] >= 1
        nfold = parts["folding"]["args"]["n"]
        assert 1 <= nfold <= 2
        assert parts["refinement"]["args"]["n"] == nfold
        refine = {k["name"] for k in _children(events, parts["refinement"])}
        assert refine == {"refine-series", "refine-device", "refine-host"}
        fold = _children(events, parts["folding"])
        assert {k["name"] for k in fold} == {"fold-device", "fold-host"}
        # the subbands a fold re-forms: inside one of its device spans
        assert "fold-subbands" in {
            c["name"] for k in fold for c in _children(events, k)}


def test_a_checkpointed_pass_has_its_checkpoint_span(toy_search):
    from benchmark.harness.window import PassDumpStore
    from tpulsar.search import executor

    block, freqs, dt, plan, params = toy_search
    trace.start()
    store = PassDumpStore()
    executor.search_block(block, freqs, dt, plan, params, checkpoint=store)
    cks = _spans(trace.events(), "pass-checkpoint")
    assert [c["args"]["parent"] for c in cks] == ["pass", "pass"]
    assert [c["args"]["bytes"] for c in cks] == [
        len(store.passes[0]), len(store.passes[1])]
    assert all(c["args"]["durable"] is False for c in cks)


def test_a_groups_tree_is_the_solos_with_nbeams(tmp_path, monkeypatch):
    """Two beams through search_beam_batch: the one pass loop's tree
    under the group's root — pass > dm_chunk > stages, the pass end's
    host halves and checkpoint once per beam — with nbeams on pass and
    dm_chunk, and no span of the group's own besides the root."""
    from tpulsar.io import synth
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    plan = [ddplan.DedispStep(lodm=40.0, dmstep=2.0, dms_per_pass=10,
                              numpasses=2, numsub=16, downsamp=1)]
    monkeypatch.setattr(executor.ddplan, "plan_for",
                        lambda si, **kw: (plan, None, 16))
    params = executor.SearchParams(
        nsub=16, hi_accel_zmax=8, topk_per_stage=8, max_dms_per_chunk=5,
        max_cands_to_fold=1, make_plots=False)
    psr = synth.PulsarSpec(period_s=0.15, dm=60.0, snr_per_sample=0.6,
                           width_frac=0.05)
    specs = []
    for i in range(2):
        fns = synth.synth_beam(
            str(tmp_path / f"beam{i}"),
            synth.BeamSpec(nchan=32, nsamp=1 << 13, nbits=4,
                           tsamp_s=5.24288e-4, scan=300 + i),
            pulsars=[psr], merged=True)
        specs.append(executor.BeamSpec(
            fns=fns, workdir=str(tmp_path / f"w{i}"),
            resultsdir=str(tmp_path / f"r{i}"), baryv=0.0,
            checkpoint_dir=str(tmp_path / f"ck{i}")))
    trace.start()
    res = executor.search_beam_batch(specs, params)
    events = trace.events()
    assert [r.path for r in res] == ["batched", "batched"], \
        [(r.fallout, r.error) for r in res]

    (root,) = _spans(events, "search_beam_batch")
    assert root["args"]["nbeams"] == 2 and root["args"]["npasses"] == 2
    kids = _children(events, root)
    assert [k["name"] for k in kids] == ["pass", "pass", "finish",
                                         "finish"]
    assert {e["name"] for e in _spans(events)}.isdisjoint(
        {"beam_batch_chunk", "search_block"})
    for p in kids[:2]:
        assert p["args"]["nbeams"] == 2 and p["args"]["ntrials"] == 10
        under = _children(events, p)
        chunks = [k for k in under if k["name"] == "dm_chunk"]
        assert [(c["args"]["n"], c["args"]["nbeams"])
                for c in chunks] == [(5, 2)] * 2
        assert {"hi_rows", "dd_calls", "dd_rows", "lo", "pass_idx"} \
            <= set(chunks[0]["args"])
        for c in chunks:
            stages = {k["name"]: k for k in _children(events, c)}
            assert set(stages) == {"dedispersing", "single-pulse", "FFT",
                                   "lo-accelsearch", "hi-accelsearch"}
            hi = _children(events, stages["hi-accelsearch"])
            # one stacked dispatch of 2 x 5 rows, candidates per beam
            assert [k["args"]["rows"] for k in hi
                    if k["name"] == "accel-dispatch"] == [10]
            assert [k["name"] for k in hi].count("accel-candidates") == 2
        # pass end: both host halves once per (chunk, beam), one
        # checkpoint per beam
        names = [k["name"] for k in under]
        assert names.count("pass-checkpoint") == 2
        assert names.count("single-pulse") == names.count(
            "lo-accelsearch") == 4
        inner = [c["name"] for k in under
                 if k["name"] in ("single-pulse", "lo-accelsearch")
                 for c in _children(events, k)]
        assert inner.count("sp-events") == inner.count(
            "lo-candidates") == 4


def test_stage_timers_totals_equal_the_trace_rollup(two_calls):
    events, timers = two_calls
    roll = trace.rollup(events)
    # the timer reads its clock just outside the span's own reads: the
    # two agree to the repo's contract (5%, tools/trace_summarize.py),
    # with a floor for the short stages on a loaded host
    # (a span's share of one stage, "<stage>/<name>", is no event's
    # name: tests/test_span_sink.py holds those to the bare names)
    for stage in timers[0].times:
        if "/" in stage:
            continue
        total = sum(t.times[stage] for t in timers)
        assert roll.get(stage, {"seconds": 0.0})["seconds"] == \
            pytest.approx(total, rel=0.05, abs=5e-3)
    assert roll["hi-accelsearch"]["count"] == 8       # 2 x 2 x 2 chunks


def test_pass_loop_is_covered_by_its_spans(two_calls):
    events, _timers = two_calls
    for p in _spans(events, "pass"):
        through = trace.uncovered_share(events, p["id"],
                                        through=("dm_chunk",))
        direct = trace.uncovered_share(events, p["id"])
        assert 0.0 <= direct <= through < 0.2


# ------------------------------------------- the reducers, by hand

def _ev(id_, parent, ts, dur, name="s", **kw):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "id": id_,
            "parent_id": parent, "call": 1, "args": {}, **kw}


HAND = [
    _ev(1, 0, 0.0, 100e6, "root"),
    _ev(2, 1, 10e6, 40e6, "group"),          # covers 10-50 of the root
    _ev(3, 2, 12e6, 8e6, "leaf"),            # 12-20
    _ev(4, 2, 30e6, 20e6, "leaf"),           # 30-50
    _ev(5, 1, 60e6, 20e6, "leaf"),           # 60-80
    _ev(6, 1, 70e6, 20e6, "late"),           # 70-90: overlaps 5 by 10
    {"name": "tick", "ph": "i", "ts": 5e6, "parent_id": 1, "call": 1,
     "args": {}},
]


def test_self_seconds_is_duration_less_what_children_cover():
    own = trace.self_seconds(HAND)
    # root: 100 - (40 + union of 60-90 = 30) = 30
    assert own[1] == pytest.approx(30.0)
    assert own[2] == pytest.approx(40.0 - 8.0 - 20.0)
    assert own[3] == pytest.approx(8.0) and own[6] == pytest.approx(20.0)
    assert set(own) == {1, 2, 3, 4, 5, 6}             # instants have none


def test_uncovered_share_looks_through_grouping_spans():
    assert trace.uncovered_share(HAND, 1) == pytest.approx(0.30)
    # looked through, the group's own 12 s count as the root's
    assert trace.uncovered_share(HAND, 1, through=("group",)) == \
        pytest.approx(0.42)
    assert trace.uncovered_share(HAND, 3) == pytest.approx(1.0)


def test_in_window_cuts_by_unix_time_against_an_epoch():
    inside = trace.in_window(HAND, 1000.0 + 9.0, 1000.0 + 51.0,
                             epoch_unix=1000.0)
    assert sorted(e["id"] for e in inside) == [2, 3, 4]
    trace.start()
    with trace.span("now"):
        pass
    (e,) = trace.events()
    t = trace.epoch() + e["ts"] / 1e6
    assert trace.in_window(trace.events(), t - 1.0, t + 1.0) == [e]
    assert trace.in_window(trace.events(), t + 1.0, t + 2.0) == []


# ----------------------------------- annotate, complete, the decorator

def test_annotate_adds_to_the_innermost_open_span():
    trace.start()
    with trace.span("outer", a=1):
        with trace.span("inner"):
            trace.annotate(n=3)
        trace.annotate(b=2)
        trace.complete("retro", 0.001)
        trace.instant("tick")
    trace.annotate(lost=1)                    # no open span: dropped
    by = {e["name"]: e for e in trace.events()}
    assert by["inner"]["args"]["n"] == 3 and "n" not in by["outer"]["args"]
    assert by["outer"]["args"]["a"] == 1 and by["outer"]["args"]["b"] == 2
    assert by["retro"]["parent_id"] == by["outer"]["id"] == \
        by["tick"]["parent_id"]
    assert by["retro"]["call"] == by["outer"]["call"] == by["outer"]["id"]


def test_annotate_by_name_reaches_the_enclosing_span_of_that_name():
    """A layer reports what it did to the scope it was called in
    (pallas_dd's dd_calls on the executor's dm_chunk): the innermost
    open span of the name, and nothing where there is none."""
    trace.start()
    with trace.span("dm_chunk", n=1):
        with trace.span("dm_chunk", n=2):
            with trace.span("dedispersing"):
                trace.annotate("dm_chunk", dd_calls=2)
                trace.annotate("no-such-span", lost=1)
    by_n = {e["args"].get("n"): e["args"] for e in trace.events()}
    assert by_n[2]["dd_calls"] == 2 and "dd_calls" not in by_n[1]
    assert not any("lost" in e["args"] for e in trace.events())


def test_span_decorates_a_function_with_a_fresh_span_each_call():
    @trace.span("work", kind="decorated")
    def work(x):
        return x + 1

    assert work(1) == 2 and trace.events() == []      # disabled: nothing
    trace.start()
    assert work(2) == 3 and work(3) == 4
    assert [e["name"] for e in trace.events()] == ["work", "work"]
    assert len({e["id"] for e in trace.events()}) == 2


# ----------------------------------------------------- the disabled path

def test_disabled_path_records_nothing_and_reads_no_env(monkeypatch):
    assert not trace.enabled()
    # the switch was resolved at reset(): a later env change is not seen
    monkeypatch.setenv("TPULSAR_TRACE", "1")
    with trace.span("invisible", n=1):
        trace.annotate(k=2)
        trace.instant("also-invisible")
        trace.complete("nor-this", 0.1)
    assert trace.events() == [] and trace.current_span() == ""
    trace.reset()                             # ... until it is resolved again
    assert trace.enabled()
    trace.stop()
    assert not trace.enabled()


def test_tracer_imports_and_records_without_jax():
    code = (
        "import sys\n"
        "from tpulsar.obs import trace\n"
        "assert 'jax' not in sys.modules\n"
        "trace.start()\n"
        "with trace.span('a', k=1):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "assert trace.profile_session('').__enter__() is None\n"
        "print(trace.events()[0]['id'], trace.events()[0]['call'])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": _REPO})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["1", "1"]


# ------------------------------ the spans in the profiler's own trace

def _host_annotations(trace_dir):
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = {k: v for k, v in e.stats}
                if stats.get("src") == "tpulsar":
                    out.append((e.name, e.start_ns, e.duration_ns, stats))
    return out


def test_spans_land_in_a_profiler_session_with_their_attrs(tmp_path):
    import jax
    import jax.numpy as jnp

    trace.start()
    d = str(tmp_path / "prof")
    jax.profiler.start_trace(d)
    try:
        with trace.span("pass", pass_idx=3, downsamp=2):
            with trace.span("dm_chunk", n=38, lo_form="strided"):
                jnp.arange(8).sum().block_until_ready()
            trace.complete("retro", 0.001)    # cannot annotate: not there
    finally:
        jax.profiler.stop_trace()
    by = {e["name"]: e for e in trace.events()}
    notes = {n: (s, dur, st) for n, s, dur, st in _host_annotations(d)}
    assert set(notes) == {"pass", "dm_chunk"}
    _s, _d, st = notes["pass"]
    assert (st["pass_idx"], st["downsamp"]) == (3, 2)
    assert st["id"] == by["pass"]["id"] == st["call"]
    s_c, d_c, st_c = notes["dm_chunk"]
    assert (st_c["n"], st_c["lo_form"]) == (38, "strided")
    assert st_c["id"] == by["dm_chunk"]["id"]
    assert st_c["call"] == by["pass"]["id"]
    # on the profiler's clock: the child lies inside its parent there too
    s_p, d_p, _ = notes["pass"]
    assert s_p <= s_c and s_c + d_c <= s_p + d_p
    assert d_c == pytest.approx(by["dm_chunk"]["dur"] * 1e3, rel=0.5)


def test_profile_session_wraps_a_search_in_one_xprof_trace(
        toy_search, tmp_path, monkeypatch):
    """TPULSAR_TRACE=1 TPULSAR_PROFILE=<dir>: the program's span chain
    search_block -> pass -> dm_chunk -> stage -> accel-dispatch is in
    the profiler's host plane, ids and attributes as stats."""
    from tpulsar.search import executor

    block, freqs, dt, plan, params = toy_search
    d = str(tmp_path / "xprof")
    monkeypatch.setenv("TPULSAR_PROFILE", d)
    trace.start()
    executor.search_block(block, freqs, dt, plan, params)
    notes = _host_annotations(d)
    by_id = {st["id"]: (name, st) for name, _s, _d, st in notes}
    spans = {e["id"]: e for e in _spans(trace.events())}
    # every span is there under its id, the root included: the session
    # opens before the search_block span and closes after it
    for sid, e in spans.items():
        assert by_id[sid][0] == e["name"]
        assert by_id[sid][1]["call"] == e["call"]
    (dispatch,) = [st for name, st in by_id.values()
                   if name == "accel-dispatch"][:1]
    assert dispatch["rows"] == 5
    chain = []
    e = spans[dispatch["id"]]
    while e["parent_id"]:
        e = spans[e["parent_id"]]
        chain.append(e["name"])
    assert chain == ["hi-accelsearch", "dm_chunk", "pass", "search_block"]
    chunk = [st for name, st in by_id.values() if name == "dm_chunk"]
    assert sum(st["n"] for st in chunk) == 20


def test_the_benchmarks_scope_list_is_the_programs():
    """One list of scope names: the kernels take theirs from
    kernels/scopes.py (any other name raises there), the compile-cache
    salt is its hash, and the benchmark's reduction reads the same."""
    import json

    from tpulsar.kernels import scopes

    with open(os.path.join(_REPO, "benchmark", "trace_scopes.json")) as fh:
        spec = json.load(fh)
    assert tuple(spec["scopes"]) == scopes.SCOPES
    assert set(spec["whole_programs"].values()) <= set(scopes.SCOPES)



# ------------------------------------- a laid-out beam's spans (PR 43)

def test_a_laid_out_beams_pass_has_its_exchange_by_name(toy_search):
    """The names docs/operations.md and the benchmark read a laid-out
    beam's pass by: `pass` says `block_shards`, `subbanding` says
    `shards`, and `mesh-exchange` (a stage, sibling of `mesh-place`
    under `pass`, with `bytes`, `form` and `devices`) comes once a pass;
    `tpulsar_mesh_exchange_bytes_total{form}` counts the same bytes.
    A block on one device opens none of it."""
    import dataclasses

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.obs import telemetry
    from tpulsar.search import executor
    from tpulsar.search.report import StageTimers

    block, freqs, dt, plan, params = toy_search
    params = dataclasses.replace(params, dm_shards=4, run_hi_accel=False)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("chan",))
    laid = jax.device_put(np.asarray(block),
                          NamedSharding(mesh, P("chan", None)))

    def exchanged():
        snap = telemetry.metrics.REGISTRY.snapshot()
        return sum((snap.get("tpulsar_mesh_exchange_bytes_total")
                    or {}).get("series", {}).values())

    base = exchanged()
    timers = StageTimers()
    trace.start()
    executor.search_block(laid, freqs, dt, plan, params, timers=timers)
    events = trace.events()
    trace.reset()
    passes = _spans(events, "pass")
    assert [p["args"]["block_shards"] for p in passes] == [4, 4]
    for p in passes:
        kids = [k["name"] for k in _children(events, p)]
        assert kids[:4] == ["subbanding", "mesh-exchange", "mesh-place",
                            "sharded-search"]
    ex = _spans(events, "mesh-exchange")
    assert len(ex) == 2
    for e in ex:
        assert e["args"]["form"] in ("replicate", "partial", "time")
        assert e["args"]["devices"] == 4 and e["args"]["bytes"] > 0
    assert {s["args"]["shards"] for s in _spans(events, "subbanding")} == {4}
    assert timers.times["mesh-exchange"] > 0.0
    assert exchanged() - base == sum(e["args"]["bytes"] for e in ex)

    trace.start()
    executor.search_block(block, freqs, dt, plan, params)
    events = trace.events()
    trace.reset()
    assert not _spans(events, "mesh-exchange")
    assert {p["args"]["block_shards"] for p in _spans(events, "pass")} == {1}
    assert all("shards" not in s["args"]
               for s in _spans(events, "subbanding"))
