"""Seconds a read-in call before its `rfifind` stage opens: `readin_s`
less that stage (`SearchOutcome.timers`), i.e. the header, the plan,
the checkpoint store and `read_all_uint8`, the 4-bit decode to the
requantised uint8 block on the host."""

import statistics


def read(ctx):
    calls = ctx["calls"]
    if not calls or not all("rfifind" in c.stage_s for c in calls):
        return None
    return statistics.median(c.readin_s - c.stage_s["rfifind"]
                             for c in calls)
