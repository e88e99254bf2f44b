"""Device milliseconds a trial under sp/detrend: the single-pulse detrend,
block medians (a sort) and the normalisation. Read from the profiler's
trace by the program's named scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("sp/detrend",))
