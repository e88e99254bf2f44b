"""tpulsar — a TPU-native pulsar-search framework.

A brand-new framework with the capabilities of the PALFA pipeline2.0
(reference: NihanPol/pipeline2.0): end-to-end survey pulsar search —
data acquisition, durable job tracking, cluster fan-out, the search
itself, and verified result upload. Unlike the reference, which shells
out to PRESTO's C executables for all compute, tpulsar implements the
search (RFI masking, dedispersion, FFT periodicity + acceleration
search, single-pulse search, folding) as JAX/XLA/Pallas programs that
run on TPU, with DM trials and beams sharded over a device mesh.

Layout (mirrors SURVEY.md section 7):
  io/          PSRFITS + data formats, synthetic beam generator
  plan/        dedispersion planning (DDplan) + survey plans
  kernels/     JAX/Pallas compute kernels (the PRESTO-C replacements)
  parallel/    mesh construction, sharded search, a beam laid over chips
  search/      the per-beam search executor, sifting, reports
  orchestrate/ job tracker, job pool, queue managers, downloader, uploader
  config/      typed validated configuration
  obs/         logging, timing, mail notification, debug flags
  astro/       time/coordinate/angle utilities
  cli/         operator command-line tools
"""

__version__ = "0.1.0"


def cpu_subprocess_env(base: dict | None = None) -> dict:
    """Environment for a subprocess that must run CPU-only and never
    touch the accelerator (a chip belongs to one process)."""
    import os

    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def apply_platform_env() -> None:
    """Pin jax's platform config to the JAX_PLATFORMS environment
    variable.  Every process entry point (CLI daemons, search
    workers) calls this before any jax use, so a process told
    JAX_PLATFORMS=cpu stays off the accelerator even when jax was
    imported (and its config frozen from the environment) before the
    variable was set."""
    import os

    want = os.environ.get("JAX_PLATFORMS", "").strip()
    if want:
        import jax

        try:
            jax.config.update("jax_platforms", want)
        except Exception as exc:
            # do NOT run silently on whatever backend jax picked
            import warnings

            warnings.warn(
                f"could not pin JAX platform to {want!r} ({exc}); "
                f"this process may run on an unintended backend")
