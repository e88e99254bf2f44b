"""Resilience primitives: deterministic fault injection, the shared
retry/backoff/deadline/circuit-breaker policy engine, and host rescue
of device-refused work.

A TPU runtime can refuse valid programs flakily
(UNIMPLEMENTED at execution) or hang on a poisoned session, and none of
the resulting degrade paths used to be exercisable off the hardware.
This package makes them first-class:

  faults.py  — named fault points that deterministically raise
               refusal-shaped errors, simulate hangs, or poison the
               session, driven by TPULSAR_FAULTS, so every degrade
               path reproduces on CPU CI;
  policy.py  — ONE bounded-retry/backoff/deadline/circuit-breaker
               primitive replacing the ad-hoc retry loops that had
               grown in kernels/accel.py, orchestrate/downloader.py,
               orchestrate/uploader.py, orchestrate/jobtracker.py and
               queue_managers/;
  rescue.py  — recompute refused device work on the JAX CPU backend
               (same program, host device): a refused DM row becomes
               a slower row, not lost science.
"""

from tpulsar.resilience import faults, policy  # noqa: F401

# rescue imports numpy; faults/policy (and their jax-free consumers:
# the journal, the serve protocol, the contract linter's CI job with
# nothing installed) must stay stdlib-only, so the rescue submodule
# loads lazily on first attribute access (PEP 562) — `from
# tpulsar.resilience import rescue` keeps working either way.


def __getattr__(name: str):
    if name == "rescue":
        import importlib
        return importlib.import_module("tpulsar.resilience.rescue")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
