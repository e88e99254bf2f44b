"""From the profiler's trace to numbers: three readings.

  1. busy / idle share of the traced span — the union of the
     intervals in which an operation ran on the device;
  2. device time of the XLA modules whose name matches a pattern;
  3. from that and a cost function, a kernel's share of its roofline.

Plus the ``breakdown`` of a traced run: the device operations that
took most time, and the longest idle gaps by the stage annotation the
host was in.

The reduction works on a neutral structure, so that it can be checked
on a small recorded trace kept with the tests:

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

Which planes and lines are the device's is data
(``benchmark/trace_layout.json``), looked at by hand in a real trace.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from collections import defaultdict


def short_name(name: str, limit: int = 96) -> str:
    """An operation's name without its operands: the profiler names an
    XLA op by its whole HLO line (`%fusion.2 = bf16[...] fusion(...)`).
    Kept: the result's name, its opcode and its shape."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:limit]
    m = re.search(r"\s([a-z][\w\-]*)\(", rhs)
    if m is None:
        return name[:limit]
    return f"{lhs} {m.group(1)} {rhs[:m.start()]}"[:limit]


def load_xplane(trace_dir: str, layout: dict, stage_names) -> dict:
    """The newest .xplane.pb under `trace_dir`, cut down to what the
    reduction reads: the device planes' ops and modules lines, and the
    host plane's stage annotations."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    dev = re.compile(layout["device_plane"])
    host = re.compile(layout["host_plane"])
    keep_lines = [re.compile(layout["ops_line"]),
                  re.compile(layout["modules_line"])]
    stages = set(stage_names)
    planes, inventory = [], []
    for plane in data.planes:
        inventory.append({"plane": plane.name,
                          "lines": [ln.name for ln in plane.lines]})
        if dev.search(plane.name):
            lines = [{"name": ln.name,
                      "events": [[short_name(e.name), e.start_ns,
                                  e.duration_ns] for e in ln.events]}
                     for ln in plane.lines
                     if any(p.search(ln.name) for p in keep_lines)]
            planes.append({"name": plane.name, "lines": lines})
        elif host.search(plane.name):
            lines = []
            for ln in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in ln.events if e.name in stages]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "inventory": inventory}


def _lines(trace: dict, plane_pat: str, line_pat: str):
    pp, lp = re.compile(plane_pat), re.compile(line_pat)
    for plane in trace["planes"]:
        if pp.search(plane["name"]):
            for line in plane["lines"]:
                if lp.search(line["name"]):
                    yield plane["name"], line


def merge_intervals(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_busy(trace: dict, layout: dict) -> dict:
    """{plane: merged busy intervals (ns)} from the ops lines."""
    per_plane = defaultdict(list)
    for plane, line in _lines(trace, layout["device_plane"],
                              layout["ops_line"]):
        per_plane[plane].extend((s, s + d) for _n, s, d in line["events"])
    return {p: merge_intervals(iv) for p, iv in per_plane.items()}


def busy_seconds(busy: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not busy:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy) / 1e9


def idle_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def module_durations(trace: dict, layout: dict, pattern: str) -> dict:
    """{module event name: [seconds, ...]} of the modules line's events
    whose name matches `pattern`.  One name is one compiled program
    (the name carries the program's id), so the same function at
    another shape is another key."""
    pat = re.compile(pattern)
    out = defaultdict(list)
    for _plane, line in _lines(trace, layout["device_plane"],
                               layout["modules_line"]):
        for name, _s, d in line["events"]:
            if pat.search(name):
                out[name].append(d / 1e9)
    return dict(out)


def slowest_variant(durations: dict) -> list[float]:
    """The durations of the program that runs longest per call: of one
    function at several shapes, the one at the largest."""
    if not durations:
        return []
    return max(durations.values(), key=statistics.median)


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 peak: dict) -> tuple[float, str]:
    """The least time the chip could take over the time it took, in
    per cent, and which bound it is."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound


def top_ops(trace: dict, layout: dict, n: int = 10) -> list:
    total = defaultdict(float)
    for _plane, line in _lines(trace, layout["device_plane"],
                               layout["ops_line"]):
        for name, _s, d in line["events"]:
            total[name] += d / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, layout: dict, busy: dict, n: int = 10,
              longest: int = 20000) -> list:
    """Idle seconds between the device's busy intervals, summed by the
    innermost stage annotation the host was in at the gap's middle.
    Only the `longest` gaps are attributed one by one; the many short
    ones between a program's operations are summed under one name."""
    import numpy as np

    stages = []
    for _plane, line in _lines(trace, layout["host_plane"], ""):
        stages.extend((s, s + d, name) for name, s, d in line["events"])
    st_s = np.asarray([s for s, _e, _n in stages], np.float64)
    st_e = np.asarray([e for _s, e, _n in stages], np.float64)
    total = defaultdict(float)
    for iv in busy.values():
        gaps = sorted(((s1 - e0, 0.5 * (e0 + s1))
                       for (_s0, e0), (s1, _e1) in zip(iv, iv[1:])),
                      reverse=True)
        for length, mid in gaps[:longest]:
            inside = np.flatnonzero((st_s <= mid) & (mid < st_e))
            if len(inside):
                k = inside[np.argmin(st_e[inside] - st_s[inside])]
                total[stages[k][2]] += length / 1e9
            else:
                total["(between stages)"] += length / 1e9
        if len(gaps) > longest:
            total["(short gaps)"] += sum(g for g, _m in gaps[longest:]) / 1e9
    scale = max(1, len(busy))
    return [[k, v / scale] for k, v in sorted(total.items(),
                                              key=lambda kv: -kv[1])[:n]]


def sample(trace: dict, per_line: int = 400) -> dict:
    """A cut-down copy: the first `per_line` events of every line, for
    a recorded trace small enough to keep with the tests."""
    return {"planes": [
        {"name": p["name"],
         "lines": [{"name": ln["name"], "events": ln["events"][:per_line]}
                   for ln in p["lines"]]} for p in trace["planes"]],
        "inventory": trace.get("inventory", [])}
