"""Host seconds a slice call under `fold-host`: the float64 phase bins
and grids before the fold program, the results after its fetches (the
device part is fold_s less this)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.per(ctx, scopes.span_seconds(ctx, ("fold-host",)),
                      "call")
