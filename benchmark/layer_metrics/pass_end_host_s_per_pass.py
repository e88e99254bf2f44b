"""Host seconds a pass at its end: the single-pulse events, the lo and
hi candidate lists and the pass's checkpoint, from the program's spans
inside the window's slice calls."""

from benchmark.harness import scopes

SPANS = ("sp-events", "lo-candidates", "accel-candidates",
         "pass-checkpoint")


def read(ctx):
    return scopes.per(ctx, scopes.span_seconds(ctx, SPANS), "pass")
