"""Candidates folded a slice call: the `n` the program notes on its
`folding` span."""

from benchmark.harness import scopes


def read(ctx):
    per_call = scopes.call_events(ctx)
    if per_call is None:
        return None
    counts = [e["args"]["n"] for events in per_call for e in events
              if e["name"] == "folding" and "n" in e["args"]]
    return scopes.per(ctx, float(sum(counts)) if counts else None, "call")
