"""Device milliseconds a trial under hiaccel/correlate: the chunk program's
overlap-save correlation (segment FFTs, template products, inverse FFTs,
powers). Read from the profiler's trace by the program's named scopes
(harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("hiaccel/correlate",))
