"""A search call's `StageTimers` sums the spans that close under it
(`StageTimers.collecting` over `trace.collect`): the keys it leaves in
`times`, a stage's own entry, two threads, the untraced path and the
`.report` text."""

import os
import sys
import threading
import types

import pytest

from tpulsar.obs import trace
from tpulsar.search import report
from tpulsar.search.report import STAGES, StageTimers

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import trace_summarize  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.reset()
    yield
    trace.reset()


def _scripted_clock(monkeypatch, reads):
    """`report`'s own clock reads, in order: the timers' `time.time()`
    and nobody else's (the tracer keeps the real clock)."""
    it = iter(reads)
    monkeypatch.setattr(report, "time",
                        types.SimpleNamespace(time=lambda: next(it)))


def _work(timers):
    """Two stages, spans inside one of them, one span outside any."""
    with timers.collecting():
        with trace.span("root"):
            with timers.timing("subbanding"):
                with trace.span("sb-kernel", slab=0):
                    pass
                with trace.span("sb-kernel", slab=1):
                    with trace.span("inner"):
                        pass
            with trace.span("pass-checkpoint"):
                pass
            with timers.timing("folding"):
                with trace.span("fold-subbands"):
                    with trace.span("sb-kernel", slab=0):
                        pass


def test_a_nested_span_lands_under_its_name_and_under_its_stage():
    trace.start()
    timers = StageTimers()
    _work(timers)
    spans = {"root", "pass-checkpoint", "sb-kernel", "inner",
             "fold-subbands", "subbanding/sb-kernel", "subbanding/inner",
             "folding/sb-kernel", "folding/fold-subbands"}
    assert timers.span_keys == spans
    assert set(timers.times) == set(STAGES) | spans
    roll = trace.rollup()
    # the bare name sums every stage's share, to the span's own clock
    assert timers.times["sb-kernel"] == pytest.approx(
        roll["sb-kernel"]["seconds"], abs=1e-5)
    assert timers.times["sb-kernel"] == pytest.approx(
        timers.times["subbanding/sb-kernel"]
        + timers.times["folding/sb-kernel"])
    assert roll["sb-kernel"]["count"] == 3
    # a stage is never a span's key, nor filed under another stage
    assert not any(k.endswith(("/subbanding", "/folding"))
                   for k in timers.times)


def test_spans_outside_the_scope_are_not_collected():
    trace.start()
    timers = StageTimers()
    with trace.span("before"):
        pass
    with timers.collecting():
        with trace.span("inside"):
            pass
    with trace.span("after"):
        pass
    assert timers.span_keys == {"inside"}


def test_the_innermost_scope_collects_and_hands_back():
    trace.start()
    outer, inner = StageTimers(), StageTimers()
    with outer.collecting():
        with inner.collecting():
            with trace.span("a"):
                pass
        with trace.span("b"):
            pass
    assert inner.span_keys == {"a"} and outer.span_keys == {"b"}


@pytest.mark.parametrize("traced", [False, True])
def test_a_stages_entry_is_its_own_two_clock_reads(monkeypatch, traced):
    if traced:
        trace.start()
    # _t0, then begin / end of each stage: 1.5 s and 0.25 s whatever
    # closed inside them
    _scripted_clock(monkeypatch, [100.0, 101.0, 102.5, 103.0, 103.25])
    timers = StageTimers()
    _work(timers)
    assert timers.times["subbanding"] == 1.5
    assert timers.times["folding"] == 0.25
    assert bool(timers.span_keys) is traced


def test_untraced_times_hold_the_stages_only():
    timers = StageTimers()
    _work(timers)
    assert list(timers.times) == list(STAGES)
    assert timers.span_keys == set()
    assert trace.events() == []


#: what the parent commit's `report_text` wrote for these seconds
PARENT_REPORT = """\
---------------------------------------------------------
Timing report for beam0
---------------------------------------------------------
   Total time: 10.00 s

           rfifind:      0.00 s  (  0.0%)
        subbanding:      1.50 s  ( 15.0%)
      dedispersing:      0.00 s  (  0.0%)
      single-pulse:      0.00 s  (  0.0%)
               FFT:      0.00 s  (  0.0%)
    lo-accelsearch:      0.00 s  (  0.0%)
    hi-accelsearch:      0.00 s  (  0.0%)
           sifting:      0.00 s  (  0.0%)
           folding:      0.25 s  (  2.5%)
             other:      8.25 s  ( 82.5%)
"""


def test_an_untraced_report_is_the_parents_byte_for_byte(monkeypatch):
    _scripted_clock(monkeypatch,
                    [100.0, 101.0, 102.5, 103.0, 103.25, 110.0])
    timers = StageTimers()
    _work(timers)
    assert timers.report_text("beam0") == PARENT_REPORT


def test_a_traced_report_lists_the_detail_under_its_stage(
        monkeypatch, tmp_path):
    trace.start()
    _scripted_clock(monkeypatch,
                    [100.0, 101.0, 102.5, 103.0, 103.25, 110.0, 110.0])
    timers = StageTimers()
    _work(timers)
    for key in timers.span_keys:
        timers.times[key] = 0.5
    text = timers.report_text("beam0")
    lines = text.splitlines()
    # every line of the untraced report, in its order, "other" as it was
    assert [ln for ln in lines if not ln.lstrip().startswith(">")] == \
        PARENT_REPORT.splitlines()
    at = lines.index("        subbanding:      1.50 s  ( 15.0%)")
    assert lines[at + 1:at + 3] == [
        "           > sb-kernel:      0.50 s  (  5.0%)",
        "               > inner:      0.50 s  (  5.0%)"]
    at = lines.index("           folding:      0.25 s  (  2.5%)")
    assert [ln.split(":")[0].strip() for ln in lines[at + 1:at + 3]] == \
        ["> sb-kernel", "> fold-subbands"]
    # a bare span name is no row; the tool that holds the rollup to the
    # report reads the stages' rows and not the detail
    assert not any(ln.strip().startswith(("sb-kernel", "root"))
                   for ln in lines)
    path = tmp_path / "beam0.report"
    timers.write_report(str(path), "beam0")
    assert trace_summarize.parse_report_stages(str(path)) == {
        **{s: 0.0 for s in STAGES}, "subbanding": 1.5, "folding": 0.25}


def test_two_threads_timers_do_not_mix():
    trace.start()
    both_open = threading.Barrier(2, timeout=30)
    timers = {"a": StageTimers(), "b": StageTimers()}
    errors = []

    def search(name):
        try:
            with timers[name].collecting():
                with timers[name].timing("subbanding"):
                    both_open.wait()
                    for _ in range(200):
                        with trace.span("span-" + name):
                            pass
                    both_open.wait()
        except Exception as exc:       # read below: a thread's own
            errors.append(exc)

    threads = [threading.Thread(target=search, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errors
    assert timers["a"].span_keys == {"span-a", "subbanding/span-a"}
    assert timers["b"].span_keys == {"span-b", "subbanding/span-b"}
    assert trace.rollup()["span-a"]["count"] == 200
