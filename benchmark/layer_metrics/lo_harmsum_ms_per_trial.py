"""Device milliseconds a trial under lo/harmsum + lo/topk:
lo_stage_candidates' interbinning, strided harmonic sums and top-k of
every stage. Read from the profiler's trace by the program's named
scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("lo/harmsum", "lo/topk"))
