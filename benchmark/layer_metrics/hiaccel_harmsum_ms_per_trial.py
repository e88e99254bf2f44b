"""Device milliseconds a trial under hiaccel/harmsum: the chunk program's
harmonic sums over the (z, r) plane and their maxima over z. Read from
the profiler's trace by the program's named scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("hiaccel/harmsum",))
