"""Device time inside a traced read-in: the busy intervals of the
profiler's device plane cut to the harness's ``readin`` annotations
(each from a read-in call's start to the entry of ``search_block``)."""

from __future__ import annotations

from benchmark.harness import tracered


def readin_spans(ctx) -> list[tuple[int, int]]:
    """[(start_ns, end_ns)] of the traced read-ins, on the trace's clock."""
    if ctx.get("trace") is None:
        return []
    return [(s, s + d)
            for _p, line in tracered._lines(ctx["trace"],
                                            ctx["layout"]["host_plane"], "")
            for name, s, d in line["events"] if name == "readin"]


def _busy_ns(busy: dict, a: int, b: int) -> float:
    """Busy nanoseconds inside [a, b], averaged over the chips."""
    return sum(max(0, min(e, b) - max(s, a))
               for iv in busy.values() for s, e in iv) / max(1, len(busy))


def busy_inside(ctx):
    """(device-busy seconds inside the read-in spans, averaged over the
    chips; the spans' seconds), or None where nothing was traced."""
    spans, busy = readin_spans(ctx), ctx.get("busy")
    if not spans or not busy:
        return None
    return (sum(_busy_ns(busy, a, b) for a, b in spans) / 1e9,
            sum(b - a for a, b in spans) / 1e9)


def idle_gaps(trace: dict, layout: dict, busy: dict, n: int = 10) -> list:
    """The traced call's idle seconds by what the host was doing.  A
    read-in's one long gap runs from the call's start to the first
    operation on the chip, which `tracered.idle_gaps` (gaps BETWEEN
    operations, named by their middle) does not see: it is cut here at
    the entry of the program's `rfifind` stage into the part before it
    (header, plan, the 4-bit decode) and the stage's own (transpose,
    transfer, mask).  What follows the block is `tracered`'s."""
    ctx = {"trace": trace, "layout": layout, "busy": busy}
    stages = sorted((s, s + d) for _p, line in tracered._lines(
        trace, layout["host_plane"], "")
        for name, s, d in line["events"] if name == "rfifind")

    before = during = 0.0
    for a, b in readin_spans(ctx):
        cut = next((s for s, _e in stages if a <= s <= b), b)
        before += ((cut - a) - _busy_ns(busy, a, cut)) / 1e9
        during += ((b - cut) - _busy_ns(busy, cut, b)) / 1e9
    rest = [g for g in tracered.idle_gaps(trace, layout, busy, n)
            if g[0] not in ("readin", "rfifind")]
    mine = [["readin, before the rfifind stage", before],
            ["readin, rfifind stage", during]]
    return sorted((g for g in mine + rest if g[1] > 0.0),
                  key=lambda g: -g[1])[:n]
