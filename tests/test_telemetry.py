"""Unified telemetry tests: span tracer, metrics registry, the shared
heartbeat event shape, and the executor/trace-file/rollup contract.

The PR-2 acceptance properties pinned here:
  * span nesting + exception safety, Chrome-trace export validity;
  * histogram bucket edges and snapshot JSON round-trip;
  * a traced tiny-beam search writes a Chrome-trace whose span tree
    covers the stage sequence with per-chunk child spans, the
    `.report` text format is unchanged, and tools/trace_summarize.py
    reproduces the report's stage totals within 5%;
  * a TPULSAR_FAULTS injection run shows nonzero retry/rescue
    counters in the metrics snapshot and circuit-breaker transitions
    in the trace.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from tpulsar.obs import metrics, telemetry, trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with a quiet tracer; the global
    metrics REGISTRY is shared process state, so tests assert on
    deltas or private Registry instances, never on absolutes."""
    trace.reset()
    yield
    trace.reset()


# ----------------------------------------------------------- tracer

def test_span_nesting_records_parent_and_depth():
    trace.start()
    with trace.span("outer", k=1):
        with trace.span("inner"):
            with trace.span("leaf"):
                pass
    by_name = {e["name"]: e for e in trace.events()}
    assert by_name["outer"]["args"]["depth"] == 0
    assert "parent" not in by_name["outer"]["args"]
    assert by_name["inner"]["args"] == {"parent": "outer", "depth": 1}
    assert by_name["leaf"]["args"] == {"parent": "inner", "depth": 2}
    # containment: children begin/end inside the parent window
    for child, parent in (("inner", "outer"), ("leaf", "inner")):
        c, p = by_name[child], by_name[parent]
        assert c["ts"] >= p["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6


def test_span_exception_safety():
    trace.start()
    with pytest.raises(ValueError):
        with trace.span("outer"):
            with trace.span("boom"):
                raise ValueError("dead chip")
    # both spans closed and recorded despite the raise, each marked
    # with the error that unwound through it; the thread-local stack
    # is empty again
    by_name = {e["name"]: e for e in trace.events()}
    assert by_name["boom"]["args"]["error"].startswith("ValueError")
    assert by_name["outer"]["args"]["error"].startswith("ValueError")
    assert trace.current_span() == ""
    # the tracer still works after the unwind
    with trace.span("after"):
        pass
    assert any(e["name"] == "after" for e in trace.events())


def test_disabled_tracer_records_nothing():
    assert not trace.enabled()
    with trace.span("invisible"):
        trace.instant("also-invisible")
    assert trace.events() == []


def test_chrome_trace_export_is_valid(tmp_path):
    trace.start()
    with trace.span("stage", dm_lo=40.0):
        trace.instant("tick", n=3)
    path = trace.save(str(tmp_path / "t.json"))
    with open(path) as fh:
        obj = json.load(fh)                     # valid JSON
    assert isinstance(obj["traceEvents"], list)
    assert obj["displayTimeUnit"] == "ms"
    for e in obj["traceEvents"]:
        # the Chrome-trace event contract Perfetto requires
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    phases = {e["name"]: e["ph"] for e in obj["traceEvents"]}
    assert phases == {"stage": "X", "tick": "i"}
    args = {e["name"]: e["args"] for e in obj["traceEvents"]}
    assert args["stage"]["dm_lo"] == 40.0
    assert args["tick"] == {"n": 3, "parent": "stage"}


def test_event_cap_drops_not_grows(monkeypatch):
    monkeypatch.setattr(trace, "MAX_EVENTS", 5)
    trace.start()
    for i in range(10):
        with trace.span(f"s{i}"):
            pass
    assert len(trace.events()) == 5
    assert trace.export()["otherData"]["dropped_events"] == 5


def test_rollup_totals_and_counts():
    trace.start()
    for _ in range(3):
        with trace.span("a"):
            pass
    with trace.span("b"):
        pass
    roll = trace.rollup()
    assert roll["a"]["count"] == 3
    assert roll["b"]["count"] == 1
    assert roll["a"]["seconds"] >= 0.0


def test_trace_id_context_stamps_events():
    """The cross-process trace context: while a worker holds a
    beam's trace id (set_trace_id), every event it records carries
    it — and clearing the context stops the stamping (thread-local,
    so the stage-in thread stamps its OWN beam)."""
    trace.start()
    trace.set_trace_id("beam-abc123")
    with trace.span("stage"):
        trace.instant("tick")
    trace.complete("retro", 0.001)
    trace.set_trace_id("")
    with trace.span("after"):
        pass
    by_name = {e["name"]: e for e in trace.events()}
    for name in ("stage", "tick", "retro"):
        assert by_name[name]["args"]["trace_id"] == "beam-abc123"
    assert "trace_id" not in by_name["after"]["args"]
    assert trace.get_trace_id() == ""


# ---------------------------------------------------------- metrics

def test_histogram_bucket_edges():
    r = metrics.Registry()
    h = r.histogram("h", "edges", buckets=(0.1, 1.0, 10.0))
    # on-edge values land in the bucket whose UPPER bound they equal
    # (Prometheus `le` semantics), above-all lands in +Inf
    for v in (0.05, 0.1, 0.100001, 1.0, 10.0, 11.0):
        h.observe(v)
    s = h.series()
    assert s["counts"] == [2, 2, 1, 1]
    assert s["count"] == 6
    assert s["sum"] == pytest.approx(22.250001)


def test_histogram_rejects_bad_buckets():
    r = metrics.Registry()
    with pytest.raises(metrics.MetricError):
        r.histogram("bad", buckets=(1.0, 0.5))
    with pytest.raises(metrics.MetricError):
        r.histogram("bad2", buckets=())


def test_counter_labels_and_monotonicity():
    r = metrics.Registry()
    c = r.counter("c_total", "x", labelnames=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5
    assert c.value(kind="b") == 1.0
    assert c.value(kind="never") == 0.0
    with pytest.raises(metrics.MetricError):
        c.inc(-1, kind="a")
    with pytest.raises(metrics.MetricError):
        c.inc(wrong_label="a")


def test_get_or_create_idempotent_but_typesafe():
    r = metrics.Registry()
    c1 = r.counter("x_total", "first", labelnames=("a",))
    c2 = r.counter("x_total", "second registration", labelnames=("a",))
    assert c1 is c2
    with pytest.raises(metrics.MetricError):
        r.gauge("x_total")                  # type clash
    with pytest.raises(metrics.MetricError):
        r.counter("x_total", labelnames=("b",))  # label clash


def test_snapshot_json_round_trip(tmp_path):
    r = metrics.Registry()
    r.counter("c_total", "c", ("k",)).inc(3, k="v")
    r.gauge("g", "g").set(-1.5)
    h = r.histogram("h_seconds", "h", ("stage",), buckets=(1.0, 5.0))
    h.observe(0.5, stage="FFT")
    h.observe(7.0, stage="FFT")
    snap = r.snapshot()
    # the round-trip contract: through JSON and back, unchanged
    assert json.loads(json.dumps(snap)) == snap
    assert snap["c_total"]["series"]["v"] == 3
    assert snap["g"]["series"][""] == -1.5
    assert snap["h_seconds"]["series"]["FFT"] == {
        "counts": [1, 0, 1], "sum": 7.5, "count": 2,
        "quantiles": {"p50": 1.0, "p95": 5.0, "p99": 5.0}}
    assert snap["h_seconds"]["buckets"] == [1.0, 5.0]
    # jsonl export appends parseable timestamped lines
    p = str(tmp_path / "m.jsonl")
    r.write_jsonl(p, daemon="test")
    r.write_jsonl(p)
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 2
    assert lines[0]["metrics"] == snap
    assert lines[0]["daemon"] == "test"


def test_diff_snapshots_is_per_interval():
    """metrics.json per results dir is a beam-start delta: counters
    and histograms subtract, gauges stay point-in-time, zero-delta
    series vanish."""
    r = metrics.Registry()
    c = r.counter("c_total", "c", ("k",))
    g = r.gauge("g", "g")
    h = r.histogram("h_seconds", "h", buckets=(1.0,))
    c.inc(10, k="old")       # beam A's activity
    g.set(3.0)
    h.observe(0.5)
    base = r.snapshot()
    c.inc(2, k="new")        # beam B's activity
    h.observe(2.0)
    delta = metrics.diff_snapshots(r.snapshot(), base)
    assert delta["c_total"]["series"] == {"new": 2}   # old dropped
    assert delta["g"]["series"][""] == 3.0            # current value
    # quantiles describe the SUBTRACTED interval, re-derived from
    # the delta counts (beam B's only observation was 2.0 s -> +Inf
    # bucket, clamped to the highest finite bound)
    assert delta["h_seconds"]["series"][""] == {
        "counts": [0, 1], "sum": 2.0, "count": 1,
        "quantiles": {"p50": 1.0, "p95": 1.0, "p99": 1.0}}
    # nothing-happened interval -> empty delta (gauges excepted)
    assert "c_total" not in metrics.diff_snapshots(r.snapshot(),
                                                   r.snapshot())


def test_prometheus_text_format(tmp_path):
    r = metrics.Registry()
    r.counter("jobs_total", "jobs", ("status",)).inc(2, status="ok")
    h = r.histogram("lat_seconds", "lat", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    text = r.prometheus_text()
    assert '# TYPE jobs_total counter' in text
    assert 'jobs_total{status="ok"} 2' in text
    assert 'lat_seconds_bucket{le="1.0"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text    # cumulative
    assert 'lat_seconds_sum 2.5' in text
    assert 'lat_seconds_count 2' in text
    # the quantile surface: advertised in HELP, estimated per series
    # in a trailing COMMENT row (never a scrapeable series)
    assert "bucket-interpolated" in text
    assert "# lat_seconds p50=" in text
    for line in text.splitlines():
        if "p50=" in line:
            assert line.startswith("#")
    p = str(tmp_path / "m.prom")
    r.write_prom(p)
    assert open(p).read() == text


def test_histogram_bucket_quantiles():
    """Bucket-interpolated p50/p95/p99 (the satellite every consumer
    previously re-derived by hand): exact interior interpolation,
    +Inf observations clamped to the highest finite bound."""
    r = metrics.Registry()
    h = r.histogram("q_seconds", "q", buckets=(1.0, 2.0, 4.0))
    assert h.quantiles() == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    for v in (0.5, 1.5, 1.6, 3.0):
        h.observe(v)
    q = h.quantiles()
    # rank p50 = 2.0 of 4 -> second bucket (1,2], cum hits 3 there:
    # lb 1.0 + (2-1) * (2-1)/2
    assert q["p50"] == pytest.approx(1.5)
    assert q["p95"] <= 4.0 and q["p95"] > q["p50"]
    h.observe(100.0)               # +Inf bucket
    assert h.quantiles()["p99"] == 4.0     # clamped, not invented
    # the registry-level helper agrees with prometheus semantics
    assert metrics.bucket_quantile((1.0,), [0, 1], 0.5) == 1.0


# ------------------------------------------------- shared event shape

def test_event_record_shape_matches_heartbeat_contract():
    rec = telemetry.event_record("begin", stage="FFT", t_stage=12.5,
                                 info="chunk 3")
    # the keys bench.py's _read_heartbeat/_attribute_kill consume
    assert set(rec) == {"t", "event", "stage", "t_stage", "info"}
    assert rec["stage"] == "FFT" and rec["t_stage"] == 12.5
    # progress-line usage: extras are additive, core keys stable
    rec2 = telemetry.event_record("pass", pass_idx=3, beam=0)
    assert rec2["event"] == "pass" and rec2["pass_idx"] == 3
    assert "stage" not in rec2


def test_report_beat_uses_shared_shape(monkeypatch, tmp_path):
    from tpulsar.search import report as rep
    hb = str(tmp_path / "hb.json")
    monkeypatch.setattr(rep, "_HEARTBEAT", hb)
    monkeypatch.setattr(rep, "_CUR_STAGE", [])
    t = rep.StageTimers()
    with t.timing("dedispersing"):
        pass
    beat = json.load(open(hb))
    # historical heartbeat contract: stage/t_stage ALWAYS present
    for key in ("t", "stage", "event", "t_stage"):
        assert key in beat
    assert beat["event"] == "end"


def test_stage_timers_emit_spans_and_histogram():
    trace.start()
    t0 = telemetry.stage_seconds().series(stage="sifting")["count"]
    from tpulsar.search.report import StageTimers
    timers = StageTimers()
    with timers.timing("sifting"):
        pass
    assert [e["name"] for e in trace.events()] == ["sifting"]
    assert telemetry.stage_seconds().series(
        stage="sifting")["count"] == t0 + 1


# ------------------------------------- resilience policy telemetry

def test_policy_call_counts_retries_and_backoff():
    from tpulsar.resilience import policy as rpolicy
    before_r = telemetry.retry_attempts_total().value(
        point="test.point")
    before_b = telemetry.backoff_seconds_total().value(
        point="test.point")
    sleeps = []
    pol = rpolicy.RetryPolicy(max_attempts=3, backoff_base_s=0.25,
                              backoff_mult=1.0, backoff_max_s=0.25)
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise IOError("nope")
        return "ok"

    assert rpolicy.call(flaky, pol, sleeper=sleeps.append,
                        label="test.point") == "ok"
    assert telemetry.retry_attempts_total().value(
        point="test.point") == before_r + 2
    assert telemetry.backoff_seconds_total().value(
        point="test.point") == pytest.approx(before_b + 0.5)
    assert sleeps == [0.25, 0.25]


def test_circuit_breaker_transitions_recorded():
    from tpulsar.resilience.policy import CircuitBreaker
    trace.start()
    clock = [0.0]
    before_open = telemetry.circuit_transitions_total().value(
        point="test.breaker", state="open")
    br = CircuitBreaker(failure_threshold=2, cooloff_s=10.0,
                        clock=lambda: clock[0], name="test.breaker")
    br.record_failure()
    br.record_failure()            # -> open
    assert not br.allow()
    clock[0] = 11.0                # cooloff elapsed -> half-open
    assert br.allow()
    br.record_failure()            # half-open trial failed -> reopen
    clock[0] = 22.0
    br.record_success()            # trial succeeded -> closed
    c = telemetry.circuit_transitions_total()
    assert c.value(point="test.breaker",
                   state="open") == before_open + 1
    assert c.value(point="test.breaker", state="reopen") >= 1
    assert c.value(point="test.breaker", state="closed") >= 1
    names = [e["name"] for e in trace.events()]
    assert "circuit_open" in names and "circuit_closed" in names


def test_faulted_accel_run_shows_rescue_metrics_and_trace(monkeypatch):
    """Acceptance: a TPULSAR_FAULTS injection run has nonzero
    retry/rescue counters in the metrics snapshot and the circuit
    transitions on the trace timeline."""
    import jax.numpy as jnp

    import tpulsar.kernels.accel as ak
    from tpulsar.resilience import faults

    monkeypatch.setenv("TPULSAR_ACCEL_BATCH", "0")
    monkeypatch.setattr(ak, "_BATCH_OK", None)
    # threshold below the row count so the poisoned-session breaker
    # actually trips inside this tiny block (default is 8)
    monkeypatch.setenv("TPULSAR_ACCEL_BREAKER_THRESHOLD", "3")
    bank = ak.build_template_bank(8.0, seg=1 << 10)
    rng = np.random.default_rng(0)
    spec = (rng.standard_normal((6, 4096))
            + 1j * rng.standard_normal((6, 4096))).astype(np.complex64)
    trace.start()
    rescued0 = telemetry.rescue_rows_total().value(outcome="rescued")
    lost0 = telemetry.rescue_rows_total().value(outcome="lost")
    retries0 = telemetry.retry_attempts_total().value(
        point="accel.row_dispatch")
    faults.configure("accel.row_dispatch:unimplemented:rate=1.0")
    try:
        ak.accel_search_batch(jnp.asarray(spec), bank,
                              max_numharm=4, topk=8)
    finally:
        faults.reset()
    snap = metrics.REGISTRY.snapshot()
    rescue_series = snap["tpulsar_rescue_rows_total"]["series"]
    # disjoint outcome accounting: all 6 refused rows rescued, none
    # lost, and the breaker-skipped subset only in the separate
    # undispatched diagnostic (it must not inflate the outcome sum)
    assert rescue_series["rescued"] == rescued0 + 6
    assert rescue_series.get("lost", 0) == lost0
    assert telemetry.accel_undispatched_rows_total().value() > 0
    assert telemetry.retry_attempts_total().value(
        point="accel.row_dispatch") > retries0
    names = [e["name"] for e in trace.events()]
    assert "circuit_open" in names       # breaker opened on refusals
    assert "accel_rows_refused" in names


# ------------------------------------ executor smoke + tool contract

@pytest.fixture(scope="module")
def traced_beam(tmp_path_factory):
    """One tiny traced beam searched end-to-end (module-scoped: the
    search is the expensive part; every contract test reads its
    artifacts)."""
    from tpulsar.io import synth
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    root = tmp_path_factory.mktemp("telem")
    os.environ["TPULSAR_TRACE"] = "1"
    trace.reset()        # the switch is resolved here, not per span
    try:
        spec = synth.BeamSpec(nchan=32, nsamp=1 << 13, nbits=4,
                              tsamp_s=5.24288e-4)
        fns = synth.synth_beam(str(root / "data"), spec, merged=True)
        plan = [ddplan.DedispStep(lodm=0.0, dmstep=2.0,
                                  dms_per_pass=8, numpasses=1,
                                  numsub=16, downsamp=1)]
        params = executor.SearchParams(
            nsub=16, hi_accel_zmax=8, topk_per_stage=8,
            max_cands_to_fold=1, make_plots=False)
        out = executor.search_beam(fns, str(root / "w"),
                                   str(root / "r"), params=params,
                                   plan=plan)
    finally:
        os.environ.pop("TPULSAR_TRACE", None)
        trace.reset()
    return out


def test_executor_trace_file_span_tree(traced_beam):
    out = traced_beam
    tpath = os.path.join(out.resultsdir, f"{out.basenm}_trace.json")
    assert os.path.exists(tpath)
    events = json.load(open(tpath))["traceEvents"]
    names = {e["name"] for e in events}
    # the stage sequence, as spans
    for stage in ("rfifind", "subbanding", "dedispersing",
                  "single-pulse", "FFT", "lo-accelsearch",
                  "hi-accelsearch", "sifting", "folding",
                  "search_block", "dm_chunk"):
        assert stage in names, f"missing span {stage}"
    # per-chunk child spans nest under dm_chunk, which nests under
    # its pass, which nests under the search_block root
    chunk = next(e for e in events if e["name"] == "dm_chunk")
    assert chunk["args"]["parent"] == "pass"
    assert chunk["args"]["n"] == 8
    its_pass = next(e for e in events if e.get("id") == chunk["parent_id"])
    assert its_pass["name"] == "pass"
    assert its_pass["args"]["parent"] == "search_block"
    per_chunk = [e for e in events
                 if e["args"].get("parent") == "dm_chunk"]
    assert {"dedispersing", "single-pulse", "FFT",
            "lo-accelsearch"} <= {e["name"] for e in per_chunk}


def test_executor_report_text_unchanged(traced_beam):
    """The .report format is byte-stable under telemetry: same
    header, same '<stage>: <secs> s  (<pct>%)' rows, same stage set
    as the historical StageTimers output."""
    import re
    out = traced_beam
    rep = open(os.path.join(out.resultsdir,
                            f"{out.basenm}.report")).read()
    lines = rep.splitlines()
    assert lines[0].startswith("-" * 20)
    assert lines[1] == f"Timing report for {out.basenm}"
    assert re.match(r"   Total time: \d+\.\d\d s", lines[3])
    stage_rows = [ln for ln in lines if re.match(
        r"\s+[\w./ -]+:\s+\d+\.\d\d s  \(\s*\d+\.\d%\)", ln)]
    got_stages = [ln.split(":")[0].strip() for ln in stage_rows]
    from tpulsar.search.report import STAGES
    for s in STAGES:
        assert s in got_stages
    assert got_stages[-1] == "other"


def test_metrics_snapshot_written_with_results(traced_beam):
    snap = json.load(open(os.path.join(traced_beam.resultsdir,
                                       "metrics.json")))
    assert snap["tpulsar_passes_total"]["series"][""] >= 1
    assert snap["tpulsar_dm_trials_total"]["series"][""] >= 8
    assert "tpulsar_stage_seconds" in snap


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summarize_reproduces_report(traced_beam, capsys):
    """tools/trace_summarize.py rollup vs the .report stage totals:
    the 5% acceptance bound, via the tool's own --compare-report."""
    ts = _load_tool("trace_summarize")
    out = traced_beam
    report = os.path.join(out.resultsdir, f"{out.basenm}.report")
    rc = ts.main([out.resultsdir, "--compare-report", report])
    assert rc == 0, capsys.readouterr().err
    text = capsys.readouterr().out
    assert "dedispersing" in text and "matches" in text
    # and the totals really do agree with the in-memory timers
    summary = ts.summarize(ts.find_trace_file(out.resultsdir))
    for stage, secs in out.timers.times.items():
        if secs < 0.05:
            continue
        got = summary["rollup"].get(stage, {}).get("seconds", 0.0)
        assert got == pytest.approx(secs, rel=0.05, abs=0.05), stage


def test_trace_summarize_json_mode(traced_beam, capsys):
    ts = _load_tool("trace_summarize")
    assert ts.main([traced_beam.resultsdir, "--json"]) == 0
    obj = json.loads(capsys.readouterr().out)
    assert obj["n_events"] > 0 and "rollup" in obj


def test_cli_trace_subcommand(traced_beam, capsys):
    from tpulsar.cli import main as cli
    rc = cli.main(["trace", traced_beam.resultsdir])
    assert rc == 0
    text = capsys.readouterr().out
    assert "search_block" in text and "dm_chunk" in text


def test_cli_trace_subcommand_no_trace(tmp_path, capsys):
    from tpulsar.cli import main as cli
    assert cli.main(["trace", str(tmp_path)]) == 1


# --------------------------------------------------- log.py satellite

def test_get_logger_keeps_explicit_level():
    import logging

    from tpulsar.obs.log import get_logger
    lg = get_logger("telemtestlvl", screen=False,
                    level=logging.DEBUG)
    assert lg.level == logging.DEBUG
    # a later default-level fetch must NOT reset the earlier DEBUG
    lg2 = get_logger("telemtestlvl", screen=False)
    assert lg2 is lg and lg.level == logging.DEBUG
    # an explicit later level still wins
    get_logger("telemtestlvl", screen=False, level=logging.WARNING)
    assert lg.level == logging.WARNING
    # first default-level configuration gets INFO
    fresh = get_logger("telemtestlvl2", screen=False)
    assert fresh.level == logging.INFO
