"""Device milliseconds a trial under sp/boxcar: the boxcar ladder,
cumulative sum, differences and top-k per width. Read from the
profiler's trace by the program's named scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("sp/boxcar",))
