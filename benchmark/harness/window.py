"""The measured window: slice calls, their stamps and the arithmetic
of the end-to-end metrics.

A slice call is ``search_block(block, freqs, dt, plan_slice, params,
timers=, progress_cb=, checkpoint=)``.  The harness starts slice calls
while fewer than ``--seconds`` have passed since the window opened, at
least one, and lets the one in flight finish: a closed loop of one
client that stops issuing at the deadline and drains.
"""

from __future__ import annotations

import contextlib
import dataclasses
import io
import statistics
import time

import numpy as np

CAND_FIELDS = ("r", "z", "sigma", "power", "numharm", "dm")


class PassDumpStore:
    """The ``checkpoint=`` object handed to ``search_block``: it keeps
    each pass's own dump (its raw candidates before sifting and
    refinement, its single-pulse events, its trial count) in memory and
    stores nothing else, so a slice call does no file I/O and resumes
    nothing.  ``search_block`` hands a pass's dump to ``save`` as npz
    bytes at the pass's end, as the served path does to its on-disk
    store."""

    def __init__(self) -> None:
        self.passes: dict[int, bytes] = {}

    def load(self, key: str):
        return None

    def save(self, key: str, data: bytes, *, kind: str = "artifact",
             **extra) -> bool:
        if kind == "pass":
            self.passes[int(extra["pass_idx"])] = data
        return False      # nothing is durable: no journal line follows

    def discard(self, key: str, reason: str = "") -> None:
        pass

    def journal(self, event: str, **extra) -> None:
        pass

    def decoded(self) -> list[dict]:
        """[{cands: {field: array}, events, ntrials}] in pass order."""
        out = []
        for idx in sorted(self.passes):
            with np.load(io.BytesIO(self.passes[idx]),
                         allow_pickle=False) as z:
                out.append({
                    "cands": {f: np.asarray(z[f]) for f in CAND_FIELDS},
                    "events": np.asarray(z["events"]),
                    "ntrials": int(z["ntrials"])})
        return out


def make_timers(annotate: bool, on_stage=None):
    """A ``StageTimers`` of the harness's own.  In a traced run its
    ``timing(stage)`` also enters ``jax.profiler.TraceAnnotation``, so
    the host's stage names land in the profiler's trace on the same
    clock as the device's operations, with no edit to the program.
    `on_stage` is called at each stage's entry, before its clock starts
    (the tracer stops itself there)."""
    from tpulsar.search.report import StageTimers

    class _Timers(StageTimers):
        @contextlib.contextmanager
        def timing(self, stage: str):
            if on_stage is not None:
                on_stage()
            if annotate:
                import jax
                note = jax.profiler.TraceAnnotation(stage)
            else:
                note = contextlib.nullcontext()
            with note, super().timing(stage):
                yield

    return _Timers()


@dataclasses.dataclass
class SliceCall:
    """One slice call's stamps and what it returned."""
    t_start: float
    t_passes: list[float]
    t_end: float
    ntrials_given: int
    ntrials_done: int
    stage_s: dict
    degraded: dict
    rescued: dict
    result: tuple | None = None       # (cands, folded, sp_events, n)
    dumps: list | None = None         # PassDumpStore.decoded()

    @property
    def loop_s(self) -> float:
        return self.t_passes[-1] - self.t_start

    @property
    def finish_s(self) -> float:
        return self.t_end - self.t_passes[-1]


def slice_call(block, cell_freqs, dt, plan, params, *, annotate=False,
               keep=True, clock=time.time, search_block=None,
               on_stage=None, on_pass=None) -> SliceCall:
    from tpulsar.search import degraded

    if search_block is None:
        from tpulsar.search.executor import search_block
    timers = make_timers(annotate, on_stage)
    store = PassDumpStore()
    stamps: list[float] = []
    given = sum(s.numdms for s in plan)

    def progress(rec):
        stamps.append(clock())
        if on_pass is not None:
            on_pass(int(rec["pass_idx"]))

    t0 = clock()
    result = search_block(block, cell_freqs, dt, plan, params,
                          timers=timers, checkpoint=store,
                          progress_cb=progress)
    t1 = clock()
    return SliceCall(
        t_start=t0, t_passes=stamps or [t1], t_end=t1,
        ntrials_given=given, ntrials_done=int(result[3]),
        stage_s=dict(timers.times), degraded=degraded.snapshot(),
        rescued=degraded.provenance_snapshot(),
        result=result if keep else None,
        dumps=store.decoded() if keep else None)


def run_window(one_call, seconds: float, clock=time.time) -> list[SliceCall]:
    """Start slice calls while fewer than `seconds` have passed since
    the window opened, at least one; only the last call keeps its
    result (the check reads that one)."""
    calls: list[SliceCall] = []
    t_open = clock()
    while not calls or clock() - t_open < seconds:
        if calls:
            calls[-1].result = calls[-1].dumps = None
        calls.append(one_call())
    return calls


def end_to_end(calls: list[SliceCall]) -> dict:
    """trials_per_s: trials of the window's slice calls over the
    seconds from each call's start to its last pass's stamp.
    finish_s: median over the calls of the seconds from that stamp to
    the call's return."""
    return {
        "trials_per_s": (sum(c.ntrials_done for c in calls)
                         / sum(c.loop_s for c in calls)),
        "finish_s": statistics.median(c.finish_s for c in calls),
    }


def attempted_failed(calls: list[SliceCall], lost_trials: int) -> tuple:
    """attempted = trials the calls were given.  failed = those not
    searched, plus those whose powers came from a rescue, a per-DM
    fallback or a zero-fill (`lost_trials`, from the program's own
    counters over the window), plus all of a call's trials if it
    left a degraded or rescued flag."""
    attempted = sum(c.ntrials_given for c in calls)
    failed = lost_trials
    for c in calls:
        if c.degraded or c.rescued:
            failed += c.ntrials_given
        else:
            failed += max(0, c.ntrials_given - c.ntrials_done)
    return attempted, min(failed, attempted)
