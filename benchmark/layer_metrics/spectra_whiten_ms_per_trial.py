"""Device milliseconds a trial under spectra/whiten: whitened_spectrum's
block medians (the sorts), their interpolation and the scaling. Read
from the profiler's trace by the program's named scopes
(harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("spectra/whiten",))
