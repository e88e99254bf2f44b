"""Host rescue: recompute device-refused work on the JAX CPU backend.

A TPU runtime can refuse some valid programs at execution
(UNIMPLEMENTED) — flakily, per dispatch.  The old last resort
zero-filled refused DM rows: science silently dropped, exactly what
the verify-after-write discipline everywhere else exists to prevent.
A slower healthy device is always available — the host — and the
accel row program is an ordinary jitted JAX function, so the rescue
is the SAME program placed on the CPU backend: a refused row becomes
a slower row, and the beam stays complete.

On a CPU-only run (CI, fault-injection reproductions) the rescue
executes the identical jitted row executable on the identical device,
so rescued results are bit-identical to a clean run of the per-DM
path — the property tests/test_resilience.py pins.  (Against the
BATCHED chunk program the top-k bins/z agree but powers differ in
the last ulp — different reduction order — which sifting's thresholds
absorb; an armed accel fault pins the per-DM path anyway.)

TPULSAR_HOST_RESCUE=0 disables the layer (restoring the zero-fill
behavior, e.g. to re-measure the degraded path itself).
"""

from __future__ import annotations

import os

import numpy as np


def enabled() -> bool:
    return os.environ.get("TPULSAR_HOST_RESCUE", "").strip() != "0"


def cpu_device():
    """The host CPU device, or None when the CPU platform is somehow
    unavailable (rescue then reports every row lost rather than
    raising from inside a degrade path)."""
    try:
        import jax
        return jax.devices("cpu")[0]
    except Exception:
        return None


def _fetch_deadline_s() -> float:
    """The accel dispatch watchdog deadline also bounds rescue's
    fetches FROM the refusing device: on a wedged session the fetch
    hangs rather than raises, and an unbounded rescue would undo the
    stall bound the watchdog just enforced.  0 = no deadline."""
    try:
        return float(os.environ.get(
            "TPULSAR_ACCEL_DISPATCH_DEADLINE_S", "0"))
    except ValueError:
        return 0.0


def _fetch_host(x) -> np.ndarray | None:
    """Device array -> host ndarray; None when even the fetch is
    refused or outlives the watchdog deadline (a fully poisoned
    session has nothing left to rescue from)."""
    from tpulsar.resilience.policy import run_with_deadline
    try:
        return run_with_deadline(lambda: np.asarray(x),
                                 _fetch_deadline_s(),
                                 label="host-rescue fetch")
    except Exception:
        return None


def rescue_accel_rows(spectra, bank, rows, *, max_numharm: int,
                      topk: int) -> tuple[dict[int, tuple], bool]:
    """Recompute refused accel rows with the same row program on the
    host CPU device.

    spectra: the (ndms, nbins) complex spectra block (device or host).
    bank: the TemplateBank the refused dispatches used.
    rows: row indices refused twice by the primary device.

    Returns ``(rescued, recompute_ran)``: {row: (vals[nstages, topk],
    rbins, zidx)} for the rows that rescued (missing rows are lost —
    the caller zero-fills and records them), and whether the host
    recompute loop actually RAN.  recompute_ran=False means the
    rescue never got to compute (disabled, no CPU device, or the
    fetch from the primary device was itself refused) — a later
    retry with a fresh fetch is a genuine second chance, whereas a
    recompute that ran and recovered nothing is exhausted.  Never
    raises: this runs inside a degrade path.
    """
    if not rows or not enabled():
        return {}, False
    cpu = cpu_device()
    if cpu is None:
        return {}, False
    host = _fetch_host(spectra)
    if host is None:
        return {}, False
    import jax

    from tpulsar.kernels import accel as ak

    # the bank may also live on the wedged device: its fetch gets the
    # same deadline bound as the spectra fetch above
    bank_host = _fetch_host(bank.bank_fft)
    if bank_host is None:
        return {}, False
    out: dict[int, tuple] = {}
    try:
        block = jax.device_put(host, cpu)
        bank_fft = jax.device_put(bank_host, cpu)
    except Exception:
        return {}, False
    for i in rows:
        try:
            tup = ak.accel_row_topk(
                block, bank_fft, np.int32(i), seg=bank.seg,
                step=bank.step, width=bank.width, nz=len(bank.zs),
                max_numharm=max_numharm, topk=topk)
            out[int(i)] = tuple(np.asarray(a) for a in tup)
        except Exception:
            continue        # this row stays lost; others may rescue
    return out, True


def rescue_accel_chunk(spectra, bank, *, max_numharm: int, topk: int):
    """Whole-chunk host rescue for the executor's refused-chunk path
    (AccelStageRefused: the runtime rejected every dispatch of the
    chunk).  Recomputes the rows on the host and returns
    ``(stages_dict, lost_rows)`` where stages_dict is the same
    {stage: (powers, rbins, zvals)} dict accel_search_batch would
    have and lost_rows are the indices whose own recompute failed —
    those rows are zero-filled (zero power sifts below every
    threshold, the kernel's own per-row convention) and the caller
    records them as lost.  One flaky row must not throw away the
    hundreds that DID recompute.  Returns None when the rescue is
    impossible or recovered nothing — the caller then falls back to
    the loud degraded skip."""
    if not enabled():
        return None
    host = _fetch_host(spectra)
    if host is None:
        return None
    from tpulsar.kernels.fourier import harmonic_stages

    ndms = host.shape[0]
    per_row, _ = rescue_accel_rows(host, bank, list(range(ndms)),
                                   max_numharm=max_numharm, topk=topk)
    if not per_row:
        return None
    stages = harmonic_stages(max_numharm)
    nstages = len(stages)
    vals = np.zeros((ndms, nstages, topk), np.float32)
    rbins = np.zeros((ndms, nstages, topk), np.int32)
    zidx = np.zeros((ndms, nstages, topk), np.int32)
    for i, tup in per_row.items():
        vals[i], rbins[i], zidx[i] = tup
    lost_rows = sorted(set(range(ndms)) - set(per_row))
    zs = np.asarray(bank.zs)
    return ({h: (vals[:, si, :], rbins[:, si, :], zs[zidx[:, si, :]])
             for si, h in enumerate(stages)}, lost_rows)
