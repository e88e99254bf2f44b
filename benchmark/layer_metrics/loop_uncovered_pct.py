"""The share of the pass loop that no span of the program covers: of
the window's `pass` spans, the seconds outside every child (a
`dm_chunk` groups its stages and covers nothing itself), in per cent."""

from benchmark.harness import scopes


def read(ctx):
    from tpulsar.obs import trace

    per_call = scopes.call_events(ctx)
    if per_call is None:
        return None
    total = bare = 0.0
    for events in per_call:
        for e in events:
            if e["name"] == "pass":
                total += e["dur"]
                bare += e["dur"] * trace.uncovered_share(
                    events, e["id"], through=("dm_chunk",))
    return 100.0 * bare / total if total else None
