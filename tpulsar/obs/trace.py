"""Span tracer with Chrome-trace/Perfetto JSON export.

The per-beam half of the unified telemetry layer: nested ``span``
scopes record wall time per stage/pass/chunk, and the whole beam
exports as one Chrome-trace JSON — load the file into
https://ui.perfetto.dev (or chrome://tracing) and the stage/chunk
structure of a search is a timeline instead of a percentage table.
The reference never had this (its PRESTO subprocesses were opaque);
the GPU accel-search lineage (Dimoudi et al. 2018) attributes its
wins to exactly this per-stage device-time accounting.

One span tree: every span carries ``id``, ``parent_id`` (the
innermost open span on its thread) and ``call`` (the id of the root
span it descends from — one per ``search_block``), so the spans of
two slice calls or two passes are told apart and a span's self time
can be computed (``self_seconds``, ``uncovered_share``).  The
parent's *name* and the depth stay in ``args`` for the flat readers
(``summarize_events``, tools/trace_summarize.py).

The profiler's clock: while tracing is enabled and jax is loaded, a
span also enters ``jax.profiler.TraceAnnotation`` with its attributes,
``id``, ``call`` and ``src="tpulsar"`` as stats.  Whatever profiler
session is running (``TPULSAR_PROFILE=<dir>``, a benchmark's own) then
holds the program's spans in its host plane, on the same clock as the
device's operations: a program span can be laid over a device gap.
This module never imports jax (the orchestrators import it): it takes
``sys.modules.get("jax")``.

A call's own sums: inside ``collect(sink)`` every span that closes on
the thread and is not a stage's own (``StageTimers.timing`` opens
those, ``_stage=True``) is handed to the sink with the name of the
stage it closed in.  ``StageTimers.collecting`` is that sink for a
search call, so ``times`` holds ``"mesh-fetch"`` and
``"folding/sb-kernel"`` beside the stages, and whoever reads ``times``
reads inside a stage with nothing threaded through the kernels.

Wall time vs device time: JAX dispatch is async, so a span around an
enqueue measures dispatch cost, not compute.  Spans are therefore
wall-clock by default (cheap, safe to leave on), and DEVICE
attribution is opt-in per span via ``fence(...)`` — an explicit
``jax.block_until_ready`` at scope exit, recorded on the span as
``fenced: true`` so a trace always says which spans are
device-attributed.  Fencing serializes the pipeline it measures; it
is enabled only when ``TPULSAR_TRACE_SYNC=1`` (the executor's chunk
loops call ``fence`` unconditionally — this module makes it a no-op
unless the operator opted in).  Device seconds BY LAYER need no fence:
the hot programs carry ``jax.named_scope`` names (kernels/accel.py,
fourier.py, singlepulse.py) that the profiler's device plane keeps.

Enabling: ``TPULSAR_TRACE=1`` in the environment, or ``start()``
programmatically (tests).  The switch is resolved once — at import,
``start()``, ``stop()`` and ``reset()`` — so a disabled ``span()``
costs one flag test and a yield: cheap enough for per-chunk loops.
Thread safety: events append under a lock; span nesting state is
thread-local, and each thread's spans carry its tid, which is exactly
how Perfetto reconstructs nesting (same-track time containment).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time

#: completed-event cap: a full survey beam emits ~10 events per chunk
#: x ~1300 chunks — far below this; the cap is a runaway backstop so
#: an unbounded loop cannot OOM the host through its own telemetry
MAX_EVENTS = 200_000

_LOCK = threading.Lock()
_EVENTS: list[dict] = []
_DROPPED = 0
_FORCED: bool | None = None      # start()/stop(); None = the env
_ON = os.environ.get("TPULSAR_TRACE", "") == "1"   # the resolved switch
_T0 = time.time()                # trace epoch: unix seconds of ts 0
_TLS = threading.local()
_IDS = itertools.count(1)        # span ids, unique in the process


def _resolve() -> None:
    global _ON
    _ON = (_FORCED if _FORCED is not None
           else os.environ.get("TPULSAR_TRACE", "") == "1")


def enabled() -> bool:
    return _ON


def sync_enabled() -> bool:
    """Opt-in device fencing (see module docstring)."""
    return _ON and os.environ.get("TPULSAR_TRACE_SYNC", "") == "1"


def start(clear: bool = True) -> None:
    """Enable tracing programmatically (overrides the env)."""
    global _FORCED, _T0
    with _LOCK:
        _FORCED = True
        _resolve()
        if clear:
            _EVENTS.clear()
            _T0 = time.time()


def stop() -> None:
    global _FORCED
    with _LOCK:
        _FORCED = False
        _resolve()


def reset() -> None:
    """Back to env-controlled (TPULSAR_TRACE is read again, here),
    events dropped (tests).  Clears the calling thread's trace-id
    context too."""
    global _FORCED, _T0, _DROPPED
    with _LOCK:
        _FORCED = None
        _resolve()
        _EVENTS.clear()
        _DROPPED = 0
        _T0 = time.time()
    _TLS.trace_id = ""


def epoch() -> float:
    """Unix seconds of the events' ``ts`` 0 (the export's
    ``trace_epoch_unix_s``)."""
    return _T0


class _Frame:
    """One open span of a thread's stack."""
    __slots__ = ("name", "id", "call", "attrs", "stage")

    def __init__(self, name: str, id_: int, call: int, attrs: dict,
                 stage: bool):
        self.name, self.id, self.call, self.attrs = name, id_, call, attrs
        self.stage = stage


def _stack() -> list[_Frame]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def set_trace_id(trace_id: str) -> None:
    """Adopt a cross-process trace context on THIS thread: every
    span/instant/complete event recorded while it is set carries
    ``trace_id`` in its args.  The id is minted once at ticket
    submission (serve/protocol.write_ticket) and travels in the
    ticket JSON, so the spans a beam leaves behind in DIFFERENT
    worker processes — a claim, a crash, a steal, a finish — all
    carry the same id and can be stitched into one Perfetto timeline
    (tools/trace_summarize.py --stitch).  Thread-local on purpose:
    the serve worker's main thread processes beam N while its
    stage-in thread prepares beam N+1, and each must stamp its own
    beam's id.  Pass '' to clear."""
    _TLS.trace_id = trace_id


def get_trace_id() -> str:
    return getattr(_TLS, "trace_id", "") or ""


def _ctx_args(args: dict) -> dict:
    tid = get_trace_id()
    if tid:
        args.setdefault("trace_id", tid)
    return args


def current_span() -> str:
    """Name of the innermost open span on this thread ('' if none)."""
    st = _stack()
    return st[-1].name if st else ""


def _append(event: dict) -> None:
    global _DROPPED
    with _LOCK:
        if len(_EVENTS) >= MAX_EVENTS:
            _DROPPED += 1
            return
        _EVENTS.append(event)


def _profiler_note(name: str, attrs: dict, id_: int, call: int):
    """The span as a ``jax.profiler.TraceAnnotation`` (a no-op outside
    a profiler session), or a null context where jax is not loaded."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    stats = {k: v for k, v in attrs.items() if k != "name"}
    stats.update(id=id_, call=call, src="tpulsar")
    return profiler.TraceAnnotation(name, **stats)


@contextlib.contextmanager
def collect(sink):
    """While the scope is open, every span that closes on THIS thread
    and is not a stage's own is handed to ``sink(name, stage,
    seconds)``: ``stage`` is the nearest enclosing stage span's name,
    '' outside any.  ``StageTimers.collecting`` is the one caller: a
    search call's timers sum the spans that close under it.  Per
    thread, so two searches in two threads do not mix; with tracing
    off no span reaches its on-path and nothing is collected."""
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = sink
    try:
        yield
    finally:
        _TLS.sink = prev


@contextlib.contextmanager
def span(name: str, _stage: bool = False, **attrs):
    """Record a nested Chrome-trace complete event around the scope.

    Exception-safe: the span closes (and records ``error``) when the
    body raises.  Nesting is per-thread.  The event carries ``id``,
    ``parent_id`` and ``call`` (a root span's own id, inherited by
    everything under it); the parent's name and the depth ride in args
    so a flat event list still states the tree.  ``_stage``: the span
    is a ``StageTimers.timing`` scope, which keeps its own seconds: it
    is what ``collect`` files the spans inside it under, and is not
    collected itself."""
    if not _ON:
        yield
        return
    st = _stack()
    parent = st[-1] if st else None
    depth = len(st)
    sid = next(_IDS)
    frame = _Frame(name, sid, parent.call if parent else sid, dict(attrs),
                   _stage)
    st.append(frame)
    error = ""
    t_begin = time.time()
    try:
        with _profiler_note(name, attrs, sid, frame.call):
            yield
    except BaseException as exc:
        error = f"{type(exc).__name__}: {exc}"[:200]
        raise
    finally:
        t_end = time.time()
        if st and st[-1] is frame:
            st.pop()
        sink = getattr(_TLS, "sink", None)
        if sink is not None and not _stage:
            sink(name, next((f.name for f in reversed(st) if f.stage), ""),
                 t_end - t_begin)
        args = frame.attrs
        if parent:
            args["parent"] = parent.name
        args["depth"] = depth
        if error:
            args["error"] = error
        _append({
            "name": name, "cat": "tpulsar", "ph": "X",
            "ts": round((t_begin - _T0) * 1e6, 1),
            "dur": round((t_end - t_begin) * 1e6, 1),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "id": sid, "parent_id": parent.id if parent else 0,
            "call": frame.call,
            "args": _ctx_args(args),
        })


def annotate(_span: str = "", **attrs) -> None:
    """Add attributes to the innermost open span's in-memory event
    (with ``_span``: to the innermost open span of that name, for a
    layer that reports what it did to the scope it was called in) —
    for counts known only inside or at the end of the scope (candidates
    sifted, bytes checkpointed).  They reach ``events()`` and the
    Chrome-trace file, not the profiler's annotation, which takes its
    stats at entry."""
    if not _ON:
        return
    for frame in reversed(_stack()):
        if not _span or frame.name == _span:
            frame.attrs.update(attrs)
            return


def profile_session(profile_dir: str):
    """``jax.profiler.trace(profile_dir)`` around a scope, or a null
    context for an empty ``profile_dir`` (TPULSAR_PROFILE unset).
    With TPULSAR_TRACE=1 beside it, the one xprof trace holds the
    program's spans over the device's operations."""
    if not profile_dir:
        return contextlib.nullcontext()
    import jax.profiler
    return jax.profiler.trace(profile_dir)


def _under_current(args: dict) -> tuple[dict, dict]:
    """(args with the enclosing span's name, {parent_id, call}) for an
    event recorded without a scope of its own."""
    st = _stack()
    if not st:
        return args, {"parent_id": 0, "call": 0}
    args["parent"] = st[-1].name
    return args, {"parent_id": st[-1].id, "call": st[-1].call}


def complete(name: str, dur_s: float, **attrs) -> None:
    """Retroactive completed span ending NOW with the given duration.

    For durations learned after the fact — jax.monitoring reports a
    backend compile's seconds only once it finishes, so the AOT
    runtime monitor cannot wrap it in ``span``.  The event still
    lands on the caller's thread track with the enclosing span noted
    in args, so Perfetto shows the compile inside the stage that
    triggered it."""
    if not _ON:
        return
    t_end = time.time()
    args, tree = _under_current(dict(attrs))
    _append({
        "name": name, "cat": "tpulsar", "ph": "X",
        "ts": round((t_end - dur_s - _T0) * 1e6, 1),
        "dur": round(dur_s * 1e6, 1),
        "pid": os.getpid(), "tid": threading.get_ident(),
        "id": next(_IDS), **tree,
        "args": _ctx_args(args),
    })


def instant(name: str, **attrs) -> None:
    """Zero-duration marker (circuit transitions, rescue decisions):
    shows as a tick on the Perfetto track."""
    if not _ON:
        return
    args, tree = _under_current(dict(attrs))
    _append({
        "name": name, "cat": "tpulsar", "ph": "i",
        "ts": round((time.time() - _T0) * 1e6, 1),
        "pid": os.getpid(), "tid": threading.get_ident(),
        "s": "t", **tree, "args": _ctx_args(args),
    })


def fence(*arrays) -> None:
    """Opt-in device fence: block until the given device values are
    ready, attributing their compute time to the ENCLOSING span (the
    span's exit records the post-fence clock).  No-op unless
    TPULSAR_TRACE_SYNC=1 — fencing serializes the async pipeline it
    measures, so it must never be the default."""
    if not sync_enabled() or not arrays:
        return
    import jax
    jax.block_until_ready(arrays)
    instant("device_fence", span=current_span())


def events() -> list[dict]:
    """Copy of the recorded events (tests / exporters)."""
    with _LOCK:
        return [dict(e, args=dict(e["args"])) for e in _EVENTS]


def export() -> dict:
    """The Chrome-trace JSON object (the ``save`` payload)."""
    with _LOCK:
        evs = [dict(e, args=dict(e["args"])) for e in _EVENTS]
        dropped = _DROPPED
    obj = {"traceEvents": evs, "displayTimeUnit": "ms",
           "otherData": {"producer": "tpulsar",
                         "trace_epoch_unix_s": _T0}}
    if dropped:
        obj["otherData"]["dropped_events"] = dropped
    return obj


def save(path: str) -> str:
    """Write the Chrome-trace file (atomic replace: a kill mid-write
    must not leave a half-JSON that ui.perfetto.dev rejects)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(export(), fh)
    os.replace(tmp, path)
    return path


def find_trace_file(path: str) -> str:
    """`path` itself when it is a file, else the newest *_trace.json
    beneath it (recursive) — 'the last beam's trace'."""
    import glob
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*_trace.json"),
                            recursive=True),
                  key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(
            f"no *_trace.json under {path} (run the search with "
            f"TPULSAR_TRACE=1)")
    return hits[-1]


def summarize_events(trace_events: list, trace_file: str = "") -> dict:
    """Rollup summary of a traceEvents list: {trace_file, rollup,
    root_seconds, n_events}.  The one implementation behind both
    `tpulsar trace` and tools/trace_summarize.py — root_seconds is
    the search_block span when present, else the total of top-level
    (depth-0) spans.  Split from summarize_file so a caller that
    already parsed the JSON (trace_summarize's compile rollup shares
    the same load) doesn't parse it twice."""
    roll = rollup(trace_events)
    root_s = roll.get("search_block", {}).get("seconds", 0.0)
    if not root_s:
        root_s = sum(e.get("dur", 0.0) / 1e6 for e in trace_events
                     if e.get("ph") == "X"
                     and e.get("args", {}).get("depth") == 0)
    return {"trace_file": trace_file, "rollup": roll,
            "root_seconds": round(root_s, 3),
            "n_events": len(trace_events)}


def summarize_file(trace_path: str) -> dict:
    """summarize_events over a saved trace file."""
    with open(trace_path) as fh:
        obj = json.load(fh)
    return summarize_events(obj.get("traceEvents", []),
                            trace_file=trace_path)


def render_summary(summary: dict) -> str:
    """The per-span seconds/share/scopes table."""
    roll = summary["rollup"]
    root_s = max(summary["root_seconds"], 1e-9)
    lines = [f"trace: {summary['trace_file']} "
             f"({summary['n_events']} events)",
             f"{'span':>18s}  {'seconds':>9s}  {'share':>6s}  "
             f"{'scopes':>6s}"]
    for name in sorted(roll, key=lambda n: -roll[n]["seconds"]):
        rec = roll[name]
        lines.append(f"{name:>18.18s}  {rec['seconds']:9.2f}  "
                     f"{100.0 * rec['seconds'] / root_s:5.1f}%  "
                     f"{rec['count']:6d}")
    return "\n".join(lines)


def _by_id(trace_events: list[dict]) -> dict[int, dict]:
    return {e["id"]: e for e in trace_events
            if e.get("ph") == "X" and e.get("id")}


def self_seconds(trace_events: list[dict]) -> dict[int, float]:
    """{span id: seconds of its own}: a span's duration minus the part
    of its interval that its children (by ``parent_id``) cover."""
    spans = _by_id(trace_events)
    kids: dict[int, list[tuple[float, float]]] = {}
    for e in spans.values():
        if e.get("parent_id") in spans:
            kids.setdefault(e["parent_id"], []).append(
                (e["ts"], e["ts"] + e["dur"]))
    out = {}
    for sid, e in spans.items():
        lo, hi = e["ts"], e["ts"] + e["dur"]
        covered, edge = 0.0, lo
        for s, t in sorted(kids.get(sid, ())):
            s, t = max(s, edge), min(t, hi)
            if t > s:
                covered += t - s
                edge = t
        out[sid] = (e["dur"] - covered) / 1e6
    return out


def in_window(trace_events: list[dict], t0_unix: float, t1_unix: float,
              epoch_unix: float | None = None) -> list[dict]:
    """The events that lie inside [t0_unix, t1_unix] (``time.time()``
    stamps, as the spans' own clock is).  ``epoch_unix``: the unix
    seconds of ts 0 — this process's by default, a saved file's
    ``trace_epoch_unix_s`` otherwise."""
    base = _T0 if epoch_unix is None else epoch_unix
    lo, hi = (t0_unix - base) * 1e6, (t1_unix - base) * 1e6
    return [e for e in trace_events
            if e["ts"] >= lo and e["ts"] + e.get("dur", 0.0) <= hi]


def uncovered_share(trace_events: list[dict], root_id: int,
                    through: tuple[str, ...] = ()) -> float:
    """The share of span ``root_id``'s duration that none of its
    children covers.  Children named in ``through`` are looked
    through: they group spans and do no work of their own
    (``dm_chunk``), so their own uncovered time counts as the root's
    and only their children cover."""
    spans = _by_id(trace_events)
    own = self_seconds(trace_events)
    kids: dict[int, list[int]] = {}
    for e in spans.values():
        kids.setdefault(e.get("parent_id", 0), []).append(e["id"])

    def bare(sid: int) -> float:
        return own[sid] + sum(bare(k) for k in kids.get(sid, ())
                              if spans[k]["name"] in through)

    dur = spans[root_id]["dur"] / 1e6
    return bare(root_id) / dur if dur > 0 else 0.0


def rollup(trace_events: list[dict] | None = None
           ) -> dict[str, dict]:
    """Per-name {seconds, count} totals over complete ('X') events.

    Over the events StageTimers emits this reproduces the .report
    stage totals: one span per timing scope, same begin/end clocks
    (tools/trace_summarize.py renders this as the rollup table)."""
    evs = trace_events if trace_events is not None else events()
    out: dict[str, dict] = {}
    for e in evs:
        if e.get("ph") != "X":
            continue
        rec = out.setdefault(e["name"], {"seconds": 0.0, "count": 0})
        rec["seconds"] += e.get("dur", 0.0) / 1e6
        rec["count"] += 1
    for rec in out.values():
        rec["seconds"] = round(rec["seconds"], 6)
    return out
