"""Device milliseconds a trial under spectra/fft: whitened_spectrum's pad
and real FFT. Read from the profiler's trace by the program's named
scopes (harness/scopes.py)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_trial(ctx, ("spectra/fft",))
