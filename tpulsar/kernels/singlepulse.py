"""Single-pulse (boxcar matched filter) search on TPU.

Replaces PRESTO's single_pulse_search.py (reference invocation:
lib/python/PALFA2_presto_search.py:540-543): each DM time series is
detrended, normalized, and convolved with a ladder of boxcar widths;
events above threshold become single-pulse candidates.

Boxcars are computed with cumulative-sum differencing — one cumsum per
series serves every width — and the whole ladder is jitted over the
(ndms, T) block.  The width ladder matches PRESTO's default
downfact ladder up to 30 samples.
"""

from __future__ import annotations

from functools import partial

import os

import jax
import jax.numpy as jnp
import numpy as np

from tpulsar.kernels import scopes

DEFAULT_WIDTHS = (1, 2, 3, 4, 6, 9, 14, 20, 30)

#: device-side top-k events kept per (width, DM) before host dedup —
#: the single constant both the single-device and sharded paths use
#: (they must agree for their event sets to be identical)
DEFAULT_TOPK = 128

#: structured dtype of single-pulse event records (shared by the
#: executor's empty fallback and checkpoint round-trips)
SP_EVENT_DTYPE = np.dtype([("dm", "f8"), ("sigma", "f8"),
                           ("time_s", "f8"), ("sample", "i8"),
                           ("downfact", "i4")])


def _baseline_stat(x: jnp.ndarray, estimator: str) -> jnp.ndarray:
    """Per-block baseline statistic over the last axis — every block
    (including a short tail) is normalized by ITS OWN sample count."""
    if estimator == "median":
        return jnp.median(x, axis=-1)
    if estimator == "median_sub4":
        return jnp.median(x[..., ::4], axis=-1)
    if estimator == "clipped_mean":
        mu = x.mean(axis=-1, keepdims=True)
        sd = jnp.maximum(x.std(axis=-1, keepdims=True), 1e-9)
        w = (jnp.abs(x - mu) <= 3.0 * sd).astype(x.dtype)
        return (x * w).sum(-1) / jnp.maximum(w.sum(-1), 1.0)
    raise ValueError(f"unknown SP detrend estimator {estimator!r}")


@scopes.scope("sp/detrend")
def detrend_normalize(series: jnp.ndarray, detrend_block: int = 1000,
                      estimator: str = "median"):
    """The detrend/normalize BODY (traceable, not itself jitted).

    One implementation shared by two jitted programs:
    ``normalize_series`` below (the standalone SP detrend pass) and
    the tree dedispersion family's fused residual program
    (kernels/tree_dd.py), which inlines the detrend into the same
    device program as the final shift layer so the (ndms, T) series
    never makes an extra HBM round-trip just to be baselined."""
    ndms, T = series.shape
    detrend_block = min(detrend_block, T)
    nblk = max(1, T // detrend_block)
    usable = nblk * detrend_block
    blocks = series[:, :usable].reshape(ndms, nblk, detrend_block)
    med = _baseline_stat(blocks, estimator)
    baseline = jnp.repeat(med, detrend_block, axis=-1)
    if T > usable:
        # A tail shorter than detrend_block gets a baseline estimated
        # from its own samples (its own length as the denominator) —
        # reusing the last full block's baseline inflates tail sigmas
        # whenever the local level drifts across the block boundary.
        tail_med = _baseline_stat(series[:, usable:], estimator)
        baseline = jnp.concatenate(
            [baseline,
             jnp.repeat(tail_med[:, None], T - usable, axis=-1)],
            axis=-1)
    detrended = series - baseline
    std = jnp.maximum(jnp.std(detrended, axis=-1, keepdims=True), 1e-9)
    return detrended / std


@partial(jax.jit, static_argnames=("detrend_block", "estimator"))
def normalize_series(series: jnp.ndarray, detrend_block: int = 1000,
                     estimator: str = "median"):
    """Remove a piecewise-constant baseline and scale to unit
    variance, per DM series.

    estimator — the per-block baseline statistic:
      "median"       exact block median (PRESTO single_pulse_search's
                     robust detrend; the parity default).  The sort
                     is the SP stage's dominant cost on both CPU and
                     TPU (round-2 evidence: ~3.5x the whole boxcar
                     ladder), hence the alternatives:
      "median_sub4"  median of a stride-4 subsample — same robustness
                     character, 4x less sort work; baseline estimator
                     std grows from ~0.040 to ~0.079 sigma per block
                     (vs the 5-sigma event threshold: negligible)
      "clipped_mean" mean of samples within 3 sigma of the block mean
                     (two pure reductions, no sort — VPU/MXU
                     friendly); robust to pulses/RFI bursts but not
                     to heavy-tailed baselines
    Select per-run with SearchParams.sp_detrend / TPULSAR_SP_DETREND
    for the on-chip A/B; the default stays exact-median until a TPU
    measurement justifies switching.
    """
    return detrend_normalize(series, detrend_block, estimator)


_ESTIMATORS = ("median", "median_sub4", "clipped_mean")


def detrend_estimator(params_value: str | None = None) -> str:
    """Resolve the SP detrend estimator: TPULSAR_SP_DETREND env (the
    bench A/B knob) beats the SearchParams value beats the default.
    Validates here so a typo fails at process start, not as a
    ValueError at jit-trace time deep inside a measured run."""
    env = os.environ.get("TPULSAR_SP_DETREND", "").strip()
    val = env or params_value or "median"
    if val not in _ESTIMATORS:
        raise ValueError(
            f"SP detrend estimator must be one of {_ESTIMATORS}, "
            f"got {val!r}"
            + (" (from TPULSAR_SP_DETREND)" if env else ""))
    return val


@partial(jax.jit, static_argnames=("widths", "topk"))
@scopes.scope("sp/boxcar")
def boxcar_search(norm_series: jnp.ndarray,
                  widths: tuple[int, ...] = DEFAULT_WIDTHS,
                  topk: int = DEFAULT_TOPK):
    """Matched-filter SNR for each boxcar width via cumsum differencing.

    norm_series: (ndms, T), zero-mean unit-variance.
    Returns (snrs, times) each (nwidths, ndms, topk): top-k peak SNRs
    and their sample indices per width per DM.
    """
    from tpulsar.kernels.fourier import blockmax_topk

    ndms, T = norm_series.shape
    cs = jnp.cumsum(norm_series, axis=-1)
    cs = jnp.pad(cs, ((0, 0), (1, 0)))  # cs[i, t] = sum of first t samples

    all_snrs = []
    all_idx = []
    for w in widths:
        sums = cs[:, w:] - cs[:, :-w]          # (ndms, T-w+1)
        snr = sums / jnp.sqrt(float(w))
        # Hierarchical top-k: max per 32-sample block then top-k over
        # block maxima — the downstream dedup clusters events into the
        # same 32-sample buckets, so per-block maxima lose nothing,
        # and a full-width lax.top_k per width per DM was a large
        # fraction of the search wall-clock.
        vals, idx = blockmax_topk(snr, topk, block_r=32)
        all_snrs.append(vals)
        all_idx.append(idx)
    return jnp.stack(all_snrs), jnp.stack(all_idx)


def device_search(series: jnp.ndarray,
                  widths: tuple[int, ...] = DEFAULT_WIDTHS,
                  topk: int = DEFAULT_TOPK,
                  estimator: str | None = None):
    """The DEVICE half of the SP search: normalize + boxcar top-k.
    Returns the (snrs, idx) device arrays WITHOUT syncing — callers
    that batch host transfers (the executor defers all of a pass's
    chunks to one device_get) feed these to events_from_topk later.
    One definition so the single-device executor, single_pulse_search,
    and the AOT gate stay in lockstep on the exact jitted programs."""
    norm = normalize_series(series,
                            estimator=detrend_estimator(estimator))
    return boxcar_search(norm, tuple(widths), topk)


def single_pulse_search(series: jnp.ndarray, dms: np.ndarray, dt: float,
                        threshold: float = 5.0,
                        widths: tuple[int, ...] = DEFAULT_WIDTHS,
                        topk: int = DEFAULT_TOPK,
                        estimator: str | None = None) -> np.ndarray:
    """Full SP search of a DM-series block.

    Returns a structured array of events (dm, sigma, time_s, sample,
    downfact), deduplicated so each (dm, sample-cluster) keeps its
    best width — mirroring the reference's .singlepulse output columns
    (PRESTO single_pulse_search format).
    """
    snrs, idx = device_search(series, widths, topk, estimator)
    return events_from_topk(snrs, idx, dms, dt, threshold, widths)


def events_from_topk(snrs, idx, dms: np.ndarray, dt: float,
                     threshold: float = 5.0,
                     widths: tuple[int, ...] = DEFAULT_WIDTHS
                     ) -> np.ndarray:
    """Host half of the SP search: threshold + dedup the device top-k
    output (snrs, idx) of shape (nwidths, ndms, k) into event records.
    Shared by the single-device path and the sharded per-pass search
    (which all_gathers the top-k blocks over the dm mesh axis first).
    """
    snrs = np.asarray(snrs)                       # (nw, ndms, k)
    idx = np.asarray(idx).astype(np.int64)
    dms = np.atleast_1d(np.asarray(dms))
    widths_arr = np.asarray(widths)

    # Vectorized dedup: within each DM, cluster events into 32-sample
    # buckets across all widths and keep the best-SNR representative.
    wi, di, _ = np.indices(snrs.shape, sparse=True)
    keep = snrs >= threshold
    snr_f = snrs[keep]
    if snr_f.size == 0:
        return np.empty(0, dtype=SP_EVENT_DTYPE)
    wi_f = np.broadcast_to(wi, snrs.shape)[keep]
    di_f = np.broadcast_to(di, snrs.shape)[keep]
    samp_f = idx[keep]

    cluster = samp_f // 32
    combo = di_f * (cluster.max() + 1) + cluster
    order = np.lexsort((-snr_f, combo))
    combo_sorted = combo[order]
    first = np.ones(len(order), dtype=bool)
    first[1:] = combo_sorted[1:] != combo_sorted[:-1]
    sel = order[first]

    out = np.empty(len(sel), dtype=SP_EVENT_DTYPE)
    out["dm"] = dms[di_f[sel]]
    out["sigma"] = snr_f[sel]
    out["time_s"] = samp_f[sel] * dt
    out["sample"] = samp_f[sel]
    out["downfact"] = widths_arr[wi_f[sel]]
    return np.sort(out, order="sigma")[::-1]


def write_singlepulse_file(path: str, events: np.ndarray, dm: float) -> None:
    """Write one .singlepulse file (PRESTO-compatible columns)."""
    with open(path, "w") as fh:
        fh.write("# DM      Sigma      Time (s)     Sample    Downfact\n")
        sel = events[events["dm"] == dm] if len(events) else events
        for ev in sel:
            fh.write(f"{ev['dm']:7.2f} {ev['sigma']:10.2f} "
                     f"{ev['time_s']:13.6f} {ev['sample']:10d} "
                     f"{ev['downfact']:8d}\n")
