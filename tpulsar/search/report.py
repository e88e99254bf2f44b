"""Per-stage timing and the .report artifact.

Reproduces the reference's search instrumentation: per-stage timers
started in obs_info (PALFA2_presto_search.py:277-288), timed execution
of every stage (:95-139), and the percentage-breakdown report file
written at the end of the search (write_report, :336-372).  The
.report format is preserved so baseline comparisons line up.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

from tpulsar.obs import telemetry, trace


STAGES = ("rfifind", "subbanding", "dedispersing", "single-pulse",
          "FFT", "lo-accelsearch", "hi-accelsearch", "sifting", "folding")

# TPULSAR_STAGE_TRACE=1: print begin/end of every timed stage to
# stderr, flushed.  A run that blocks inside a remote device dispatch
# leaves no per-pass progress record (the callback fires only at pass
# end), so without this there is no way to tell WHICH stage a wedged
# pass is stuck in — the exact blind spot of the 2026-07-31 04:xx TPU
# hang (bench log: nothing between `rfifind done` and the deadline
# kill, 25 min later).
_TRACE = os.environ.get("TPULSAR_STAGE_TRACE", "") == "1"

# TPULSAR_STAGE_HEARTBEAT=<path>: write a JSON beat to <path> at every
# stage begin/end and at chunk drains inside long stages.  A
# supervising parent distinguishes a *stalled* child (no heartbeat for
# many minutes -> hung dispatch, kill it) from a slow but progressing
# one (heartbeat fresh -> let it run): killing a healthy child
# mid-dispatch wedges the chip for hours, so the parent must never
# kill on elapsed time alone.  The beat carries the CURRENT STAGE NAME
# and its begin time, so a kill — deadline, stall, or per-stage budget
# — can always name the stage it interrupted (the 2026-07-31 03:44
# on-chip run died at +1500 s with no record of which stage ate ~24
# minutes; this field is that record).
_HEARTBEAT = os.environ.get("TPULSAR_STAGE_HEARTBEAT", "")

# current innermost timed stage: (name, begin_time) — module-level so
# progress_beat() callers (executor chunk loops, accel drain) need no
# handle on the StageTimers instance
_CUR_STAGE: list[tuple[str, float]] = []


def _beat(stage: str = "", event: str = "", info: str = "") -> None:
    if not _HEARTBEAT:
        return
    t_stage = _CUR_STAGE[-1][1] if _CUR_STAGE else 0.0
    # one event constructor shared with bench.py's progress lines
    # (telemetry.event_record), so the bench supervisor's stall
    # detector and this heartbeat cannot drift apart in shape; the
    # stage/t_stage keys stay present even when empty — the
    # historical heartbeat contract the parent's parser grew up on
    rec = telemetry.event_record(event, stage=stage, info=info,
                                 t_stage=t_stage)
    rec.setdefault("stage", stage)
    rec.setdefault("t_stage", t_stage)
    try:
        # atomic replace: the supervising parent reads this file
        # between polls, and a torn half-written JSON read as garbage
        # would cost the kill its attribution at the worst moment
        tmp = _HEARTBEAT + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(rec, fh)
        os.replace(tmp, _HEARTBEAT)
    except OSError:
        pass


def progress_beat(info: str = "") -> None:
    """Refresh the heartbeat from inside a long timed stage (a chunk
    drained, a window synced).  Keeps the stage's begin time, so the
    parent's per-stage budget still measures total in-stage time while
    the stall detector sees live progress."""
    if _HEARTBEAT and _CUR_STAGE:
        _beat(_CUR_STAGE[-1][0], "progress", info)


class StageTimers:
    def __init__(self) -> None:
        self.times: dict[str, float] = {s: 0.0 for s in STAGES}
        #: the keys of `times` that are no stage's but a span's, summed
        #: by `collecting` in a traced search (empty with tracing off)
        self.span_keys: set[str] = set()
        self._t0 = time.time()

    def collecting(self):
        """The scope of the search call these timers belong to: while
        tracing is on, every non-stage span that closes on this thread
        inside it adds its seconds to `times` under its own name
        ("mesh-fetch") and, inside a stage, under "<stage>/<name>"
        ("folding/sb-kernel": one function runs in three stages).  So
        a stage's seconds can be read apart by whoever reads `times`
        (`progress_cb`'s `stage_s`, a traced `.report`, the
        benchmark's `stage_timers` reader), and nothing is threaded
        through the kernels' signatures.  A stage's own entry is
        `timing`'s, as ever."""
        return trace.collect(self._add_span)

    def _add_span(self, name: str, stage: str, seconds: float) -> None:
        for key in (name, f"{stage}/{name}") if stage else (name,):
            self.times[key] = self.times.get(key, 0.0) + seconds
            self.span_keys.add(key)

    @contextlib.contextmanager
    def timing(self, stage: str):
        """One timed scope = one telemetry span + one histogram
        observation + the times[] accumulation this class has always
        done.  StageTimers is now a thin view over the span tracer:
        span begin/end use the same clock reads as times[], so a
        trace-file rollup reproduces the .report totals exactly (the
        tools/trace_summarize.py contract) and the .report text stays
        byte-stable."""
        self.times.setdefault(stage, 0.0)
        start = time.time()
        _CUR_STAGE.append((stage, start))
        try:
            with trace.span(stage, _stage=True):
                # beat + stderr trace INSIDE the span: their file/
                # stream I/O (ms-scale on a loaded host) then counts
                # toward both instruments identically instead of
                # opening a per-scope gap between timer and span
                _beat(stage, "begin")
                if _TRACE:
                    print(f"[stage-trace +{start - self._t0:8.1f}s] "
                          f"begin {stage}", file=sys.stderr,
                          flush=True)
                yield
        finally:
            end = time.time()
            self.times[stage] += end - start
            telemetry.stage_seconds().observe(end - start, stage=stage)
            if _CUR_STAGE and _CUR_STAGE[-1][0] == stage:
                _CUR_STAGE.pop()
            _beat(stage, "end")
            if _TRACE:
                print(f"[stage-trace +{end - self._t0:8.1f}s] end   "
                      f"{stage} ({end - start:.1f} s)",
                      file=sys.stderr, flush=True)

    @property
    def total(self) -> float:
        return time.time() - self._t0

    def report_text(self, basenm: str) -> str:
        total = max(self.total, 1e-9)
        lines = [f"---------------------------------------------------------",
                 f"Timing report for {basenm}",
                 f"---------------------------------------------------------",
                 f"   Total time: {total:.2f} s", ""]
        accounted = 0.0
        for stage, secs in self.times.items():
            if stage in self.span_keys:
                continue
            accounted += secs
            lines.append(f"{stage:>18s}: {secs:9.2f} s  ({100*secs/total:5.1f}%)")
            # a traced search: where the stage's seconds went, by the
            # spans that closed inside it (not rows of their own: they
            # are part of the stage's, and "other" keeps its meaning)
            for key, part in self.times.items():
                if key in self.span_keys and key.startswith(stage + "/"):
                    lines.append(
                        f"{'> ' + key[len(stage) + 1:]:>22s}: "
                        f"{part:9.2f} s  ({100*part/total:5.1f}%)")
        lines.append(f"{'other':>18s}: {total-accounted:9.2f} s  "
                     f"({100*(total-accounted)/total:5.1f}%)")
        return "\n".join(lines) + "\n"

    def write_report(self, path: str, basenm: str,
                     degraded: dict[str, str] | None = None,
                     rescued: dict[str, str] | None = None) -> None:
        """degraded: fallback-path flags (search.degraded.snapshot())
        appended so a results directory is self-explaining about
        which code paths produced it.  rescued: host-rescue
        provenance (degraded.provenance_snapshot()) — refused device
        work recomputed elsewhere; listed under its own heading so an
        operator can tell 'complete beam, some rows slower' from a
        genuinely degraded beam."""
        with open(path, "w") as fh:
            fh.write(self.report_text(basenm))
            if degraded:
                fh.write("\nDegraded modes (fallback paths taken):\n")
                for flag, detail in sorted(degraded.items()):
                    fh.write(f"  {flag}: {detail}\n")
            if rescued:
                fh.write("\nRescued work (recomputed on a fallback "
                         "device; science complete):\n")
                for flag, detail in sorted(rescued.items()):
                    fh.write(f"  {flag}: {detail}\n")
