"""Device milliseconds a trial under hiaccel/topk: the chunk program's
block maxima and top-k per harmonic stage. Read from the profiler's
trace by the program's named scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("hiaccel/topk",))
