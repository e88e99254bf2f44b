"""What the program's own names give a traced run: device seconds by
``jax.named_scope``, and the host spans of ``tpulsar.obs.trace``.

The harness's reduced trace (``tracered.load_xplane``) keeps only the
events' names, and of the host plane only the stage names.  The
readers of ``benchmark/layer_metrics/*.py`` that need more come here;
this module reads the newest ``.xplane.pb`` under
``<checkout>/.bench_trace`` itself (``layers.read_all`` runs before the
runner deletes it), once per run (the reduction is kept in the context).

Where a scope shows in a TPU trace (looked at by hand, one TPU v5 lite,
jax 0.9.0): an "XLA Ops" event is named by its whole HLO line and
carries three timing stats of its own; the HLO ``op_name`` — the path
of ``jit(...)`` and named scopes, ``jit(f)/spectra/whiten/div:`` — is
the ``tf_op`` stat of the event's METADATA, which
``jax.profiler.ProfileData`` does not hand out.  So the metadata is
read from the file's wire format (a few fields of the XSpace proto,
below; the events themselves, millions, still come from ProfileData).
An operation belongs to the program whose "XLA Modules" event contains
it; one under none of the scopes is summed under ``<program>/other``,
never dropped.  The compiler's own operations (copies and pads it
inserts, its expansion of a cumulative sum) carry no op_name, or a bare
primitive's with no path (``reduce_window_sum:``): they stay under
``<program>/other``.  The one exception is a rule stated in
``trace_scopes.json``, not inferred from the trace: a program listed
under ``whole_programs`` is a jitted function whose whole body is one
scope (``jit_boxcar_search``: ``sp/boxcar``), so every operation of
its modules is the scope's, the compiler's own too (half of
``boxcar_search``'s device time is such); what the rule moved is
reported beside it (``reassigned_s``).  A ``while`` and the operations
of its body are events of the same line, so seconds are exclusive: an
event's duration minus what the events inside it cover.

Everything returns None where there is nothing to read: no trace
directory, no device plane (a CPU run), a program without the scope or
the span (the parent commit), so the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict

#: the program's annotations carry this stat (tpulsar/obs/trace.py);
#: the harness's own stage annotations, of the same names, do not
SRC = "tpulsar"


# ------------------------------------------------ the file's wire format
#
#   XSpace          1: repeated XPlane
#   XPlane          2: name   4: map<id, XEventMetadata>
#                   5: map<id, XStatMetadata>          (3: lines, skipped)
#   XEventMetadata  2: name   5: repeated XStat
#   XStatMetadata   1: id     2: name
#   XStat           1: metadata_id   5: str_value   7: ref_value
#   map entry       1: key    2: value

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) over one message: an int for a varint, a
    (start, end) pair for a length-delimited field; fixed-width fields
    are skipped."""
    while i < end:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    for field, value in _fields(buf, *span):
        if field == 2:
            return value
    return None


def op_names(path: str, plane_pattern: str, stat: str = "tf_op") -> dict:
    """{plane: {event name: that event metadata's `stat`}} for the
    planes whose name matches: the HLO op_name of every operation the
    device plane names."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    pat = re.compile(plane_pattern)
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats_md = _map_value(buf, v)
                sid = sname = None
                for g, w in _fields(buf, *stats_md):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(buf, w)
                stats[sid] = sname
        if not pat.search(name):
            continue
        want = {sid for sid, sname in stats.items() if sname == stat}
        names = {}
        for entry in events:
            md = _map_value(buf, entry)
            ev_name, op = "", None
            for g, w in _fields(buf, *md):
                if g == 2:
                    ev_name = _text(buf, w)
                elif g == 5:
                    st = dict(_fields(buf, *w))
                    if st.get(1) in want:
                        if 5 in st:
                            op = _text(buf, st[5])
                        elif 7 in st:
                            op = stats.get(st[7])
            if op:
                names.setdefault(ev_name, op)
        out[name] = names
    return out


# ------------------------------------------------------- the reduction

def scope_of(op_name: str, scopes) -> str | None:
    """The innermost of `scopes` on an op_name's path, or None."""
    path = "/" + op_name.rstrip(":") + "/"
    best, where = None, -1
    for s in scopes:
        k = path.rfind("/" + s + "/")
        if k > where:
            best, where = s, k
    return best


def exclusive_ns(events) -> list[float]:
    """Each event's duration minus what the events nested in it cover.
    `events`: [(start_ns, duration_ns)] of ONE line, in any order."""
    order = sorted(range(len(events)),
                   key=lambda k: (events[k][0], -events[k][1]))
    own = [float(d) for _s, d in events]
    stack: list[tuple[float, int]] = []        # (end, index)
    for k in order:
        s, d = events[k]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(s + d, stack[-1][0]) - s
        stack.append((s + d, k))
    return own


def program_of(module_name: str) -> str:
    """`jit_accel_chunk_topk(7274403845111628453)` -> the program."""
    return module_name.partition("(")[0]


def reduce_planes(planes: list[dict], op_name_of: dict, scopes,
                  whole_programs: dict | None = None) -> dict:
    """The numbers, from a neutral structure a recorded sample can
    give too:

        planes: [{"name", "device": bool, "lines": [{"name", "kind":
                  "ops" | "modules" | "host", "events":
                  [[name, start_ns, duration_ns, stats]]}]}]
        op_name_of: {plane: {event name: op_name}}
        whole_programs: {program: the one scope its whole body is}

    -> scope_s {scope or "<program>/other": device seconds},
       program_s {program: device seconds}, module_calls {program:
       calls}, reassigned_s {program of `whole_programs`: the seconds
       of its operations that carry no scope themselves, which without
       the rule would be its `other`}, other_top [[program, operation,
       op_name, seconds] of the longest operations under no scope],
       annotations [the program's host annotations], trials (the `n`
       of its dm_chunk annotations).  Seconds are averaged over the
       device planes."""
    whole = whole_programs or {}
    scope_ns: dict = defaultdict(float)
    program_ns: dict = defaultdict(float)
    moved_ns: dict = defaultdict(float)
    calls: dict = defaultdict(int)
    other_ns: dict = defaultdict(float)
    notes, ndev = [], 0
    for plane in planes:
        lines = {ln["kind"]: ln for ln in plane["lines"]}
        if not plane["device"]:
            for ln in plane["lines"]:
                notes.extend(
                    {"name": n, "start_ns": s, "duration_ns": d,
                     "stats": st}
                    for n, s, d, st in ln["events"]
                    if st.get("src") == SRC)
            continue
        if "ops" not in lines:
            continue
        ndev += 1
        mods = sorted((s, s + d, program_of(n)) for n, s, d, _st in
                      lines.get("modules", {"events": []})["events"])
        for _s, _e, prog in mods:
            calls[prog] += 1
        starts = [m[0] for m in mods]
        names = op_name_of.get(plane["name"], {})
        ops = lines["ops"]["events"]
        own = exclusive_ns([(s, d) for _n, s, d, _st in ops])
        rows = []
        carried = set()           # (program, scope) some operation names
        for (name, s, _d, _st), ns in zip(ops, own):
            k = bisect.bisect_right(starts, s) - 1
            prog = (mods[k][2] if k >= 0 and s < mods[k][1]
                    else "(no module)")
            op_name = names.get(name, "")
            scope = scope_of(op_name, scopes)
            carried.add((prog, scope))
            rows.append((prog, name, op_name, scope, ns))
        for prog, name, op_name, scope, ns in rows:
            # the stated rule, and only for a program that was compiled
            # with the scope (the parent commit's was not, nor is one
            # that a stale compile cache handed out)
            want = whole.get(prog)
            if scope is None and want and (prog, want) in carried:
                scope = want
                moved_ns[prog] += ns
            scope_ns[scope or prog + "/other"] += ns
            program_ns[prog] += ns
            if scope is None:
                other_ns[(prog, name[:96], op_name)] += ns
    if not ndev:
        return {}
    return {"scope_s": {k: v / ndev / 1e9 for k, v in scope_ns.items()},
            "program_s": {k: v / ndev / 1e9
                          for k, v in program_ns.items()},
            "module_calls": {k: v // ndev for k, v in calls.items()},
            "reassigned_s": {k: v / ndev / 1e9
                             for k, v in moved_ns.items()},
            "other_top": [
                [*key, ns / ndev / 1e9] for key, ns in sorted(
                    other_ns.items(), key=lambda kv: -kv[1])[:12]],
            "annotations": notes,
            "trials": sum(int(a["stats"].get("n", 0)) for a in notes
                          if a["name"] == "dm_chunk")}


def load_planes(path: str, layout: dict) -> list[dict]:
    """The file's device planes (ops and modules lines) and host plane
    (every event that carries stats), through ProfileData."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    dev, host = re.compile(layout["device_plane"]), \
        re.compile(layout["host_plane"])
    kinds = {"ops": re.compile(layout["ops_line"]),
             "modules": re.compile(layout["modules_line"])}
    planes = []
    for plane in data.planes:
        if dev.search(plane.name):
            lines = [{"name": ln.name, "kind": kind,
                      "events": [[e.name, e.start_ns, e.duration_ns, {}]
                                 for e in ln.events]}
                     for ln in plane.lines
                     for kind, pat in kinds.items() if pat.search(ln.name)]
            planes.append({"name": plane.name, "device": True,
                           "lines": lines})
        elif host.search(plane.name):
            lines = []
            for ln in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns,
                        {k: v for k, v in e.stats}] for e in ln.events]
                # the program's annotations, and the events that share
                # a name with one (the harness's own stage annotations)
                ours = {e[0] for e in evs if e[3].get("src") == SRC}
                evs = [e for e in evs if e[0] in ours]
                if evs:
                    lines.append({"name": ln.name, "kind": "host",
                                  "events": evs})
            planes.append({"name": plane.name, "device": False,
                           "lines": lines})
    return planes


def sample(planes: list[dict], op_name_of: dict, scopes,
           per_program: int = 40, host_events: int = 60) -> dict:
    """A cut-down copy for a recorded sample kept with the tests: of
    each device plane the first call of every program — its module
    event, the `per_program` longest operations inside it and the
    longest under each scope met there — with the operations' names cut
    to name, opcode and shape and the op_names of just those; of the
    host plane the first `host_events` events."""
    from benchmark.harness.tracered import short_name

    out_planes, out_names = [], {}
    for plane in planes:
        if not plane["device"]:
            out_planes.append({**plane, "lines": [
                {**ln, "events": ln["events"][:host_events]}
                for ln in plane["lines"]]})
            continue
        lines = {ln["kind"]: ln for ln in plane["lines"]}
        first: dict = {}
        for ev in sorted(lines.get("modules", {"events": []})["events"],
                         key=lambda e: e[1]):
            first.setdefault(program_of(ev[0]), ev)
        names = op_name_of.get(plane["name"], {})
        ops, kept = [], {}
        for _n, s0, d0, _st in first.values():
            inside = sorted((e for e in lines["ops"]["events"]
                             if s0 <= e[1] < s0 + d0),
                            key=lambda e: -e[2])
            chosen = inside[:per_program]
            seen = {scope_of(names.get(e[0], ""), scopes) for e in chosen}
            for e in inside[per_program:]:
                scope = scope_of(names.get(e[0], ""), scopes)
                if scope not in seen:
                    seen.add(scope)
                    chosen.append(e)
            for name, s, d, st in sorted(chosen, key=lambda e: e[1]):
                ops.append([short_name(name), s, d, st])
                if name in names:
                    kept[short_name(name)] = names[name]
        out_planes.append({"name": plane["name"], "device": True, "lines": [
            {**lines["modules"], "events": list(first.values())},
            {**lines["ops"], "events": ops}]})
        out_names[plane["name"]] = kept
    return {"planes": out_planes, "op_names": out_names}


def known_scopes(bench_dir: str) -> tuple[list[str], dict]:
    """(the scope names, {program: the one scope its whole body is})."""
    with open(os.path.join(bench_dir, "trace_scopes.json")) as fh:
        spec = json.load(fh)
    return list(spec["scopes"]), dict(spec.get("whole_programs", {}))


def reduced(ctx: dict) -> dict:
    """The run's reduction (made once, kept in the context); {} where
    there is no trace or no device plane in it."""
    if "_scopes" not in ctx:
        ctx["_scopes"] = _reduce_newest(ctx)
    return ctx["_scopes"]


def _reduce_newest(ctx: dict) -> dict:
    if ctx.get("trace") is None:
        return {}
    files = sorted(glob.glob(os.path.join(
        os.path.dirname(ctx["bench_dir"]), ".bench_trace", "plugins",
        "profile", "*", "*.xplane.pb")))
    if not files:
        return {}
    layout = ctx["layout"]
    red = reduce_planes(load_planes(files[-1], layout),
                        op_names(files[-1], layout["device_plane"]),
                        *known_scopes(ctx["bench_dir"]))
    if red:
        ctx.setdefault("notes", {})["scopes"] = {
            "scope_s": red["scope_s"], "program_s": red["program_s"],
            "module_calls": red["module_calls"], "trials": red["trials"],
            "reassigned_s": red["reassigned_s"],
            "other_top": red["other_top"],
            "annotations": len(red["annotations"])}
    return red


def ms_per_trial(ctx: dict, scopes: tuple[str, ...]):
    """Device milliseconds under `scopes` in the traced span, per trial
    of the program's dm_chunk annotations that closed inside it."""
    red = reduced(ctx)
    if not red or not red["trials"]:
        return None
    found = [red["scope_s"][s] for s in scopes if s in red["scope_s"]]
    if not found:
        return None
    return 1e3 * sum(found) / red["trials"]


# ------------------------------------------------- the program's spans

def call_events(ctx: dict):
    """[events inside each of the window's slice calls], from the
    program's span tracer; None where the program has no span tree."""
    from tpulsar.obs import trace

    if not hasattr(trace, "in_window"):
        return None
    if "_call_events" not in ctx:           # one copy for all readers
        events = [e for e in trace.events() if e.get("ph") == "X"]
        ctx["_call_events"] = [trace.in_window(events, c.t_start, c.t_end)
                               for c in ctx["calls"]]
        # for the log: where each call's host seconds are, by span name
        # (a stall of one call then has a name, beside the other call's)
        ctx.setdefault("notes", {})["span_self_s"] = [
            _self_by_name(trace, evs) for evs in ctx["_call_events"]]
    return ctx["_call_events"]


def _self_by_name(trace, events) -> dict:
    """{span name: [spans, seconds, seconds of its own]} of one call."""
    own = trace.self_seconds(events)
    out: dict = {}
    for e in events:
        row = out.setdefault(e["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] = round(row[1] + e["dur"] / 1e6, 6)
        row[2] = round(row[2] + own.get(e.get("id"), 0.0), 6)
    return out


def span_seconds(ctx: dict, names: tuple[str, ...]):
    """Seconds inside the window's slice calls under spans of these
    names, summed; None where there is no such span."""
    per_call = call_events(ctx)
    if per_call is None:
        return None
    durs = [e["dur"] / 1e6 for evs in per_call for e in evs
            if e["name"] in names]
    return sum(durs) if durs else None


def per(ctx: dict, value, unit: str):
    """`value` per trial, pass or call of the window (None stays)."""
    n = {"trial": ctx["trials"], "pass": ctx["passes"],
         "call": ctx["ncalls"]}[unit]
    return None if value is None or not n else value / n
