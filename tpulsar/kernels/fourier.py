"""Fourier-domain periodicity search on TPU.

Replaces four PRESTO C programs (reference invocations:
lib/python/PALFA2_presto_search.py:549-567):

  realfft   -> batched jnp.fft.rfft over the DM-trial axis
  zapbirds  -> barycentre-corrected zaplist mask multiplication
  rednoise  -> log-spaced block-median spectral whitening
  accelsearch (zmax=0) -> incoherent harmonic summing + top-k

The whole chain is jittable; powers are normalized so that pure-noise
summed powers of n harmonics follow Gamma(n, 1), which makes the
host-side sigma conversion (sigma_from_power) exact.
"""

from __future__ import annotations

import dataclasses
import os

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from scipy import special as sps

from tpulsar.kernels import decimate, scopes


# ----------------------------------------------------------------- rfft

@partial(jax.jit, static_argnames=("nfft",))
def pad_series(series: jnp.ndarray, nfft: int) -> jnp.ndarray:
    """Pad (..., T) series to length nfft with each row's mean (the
    reference pads to PRESTO's choose_N the same way via prepsubband
    -numout, PALFA2_presto_search.py:518 — mean padding avoids the
    broadband leakage a zero-pad step discontinuity would inject)."""
    T = series.shape[-1]
    if T == nfft:
        return series
    if T > nfft:
        return series[..., :nfft]
    mean = jnp.mean(series, axis=-1, keepdims=True)
    pad = jnp.broadcast_to(mean, series.shape[:-1] + (nfft - T,))
    return jnp.concatenate([series, pad], axis=-1)


@jax.jit
def complex_spectrum(series: jnp.ndarray) -> jnp.ndarray:
    """(ndms, T) real time series -> (ndms, T//2+1) complex spectrum
    with the DC bin zeroed (equivalent to mean subtraction).  Computed
    ONCE per DM chunk and shared by the zero-accel power search and
    the accelsearch correlation (the round-1 executor re-FFTed the
    same series for the hi stage, verdict weakness #4)."""
    spec = jnp.fft.rfft(series.astype(jnp.float32), axis=-1)
    return spec.at[..., 0].set(0.0)


@jax.jit
def power_spectrum(series: jnp.ndarray) -> jnp.ndarray:
    """(ndms, T) real time series -> (ndms, T//2+1) raw powers.

    The DC bin is zeroed (PRESTO drops it too: bin 0 holds the mean).
    """
    return jnp.abs(complex_spectrum(series)) ** 2


# ------------------------------------------------------------- rednoise

MAX_WHITEN_BLOCK = 8192


def _block_edges(nbins: int, first_block: int = 6,
                 growth: float = 1.5) -> np.ndarray:
    """Logarithmically growing block edges for the low-frequency
    section of the local-normalization estimate (short blocks track
    steep red noise).  Stops once blocks reach MAX_WHITEN_BLOCK — the
    remaining spectrum is handled with one reshaped equal-block median
    (keeps the compiled graph small for multi-million-bin spectra)."""
    edges = [1]  # skip DC
    size = first_block
    while edges[-1] < nbins and size < MAX_WHITEN_BLOCK:
        edges.append(min(nbins, edges[-1] + int(size)))
        size = size * growth
    return np.asarray(edges, dtype=np.int64)


def whiten_estimator() -> str:
    """TPULSAR_WHITEN_ESTIMATOR: block noise-level estimator for the
    rednoise whitening.  'median' (default) is PRESTO's robust choice
    (median/ln2 = mean for exponential noise) but a sort per block —
    the dominant cost of the on-chip FFT stage (~90 s of
    cfg2_quarter's 186.6 s, 2026-08-01).  'clipped_mean' replaces the
    sort with two reductions: mean, clip at 4x the mean (kills bright
    bins/birdies the way the median's breakdown point does for
    moderate contamination), re-mean with the exponential-tail
    correction 1/(1-e^-4).  Opt-in until an on-chip candidate-list
    A/B validates it (same protocol as TPULSAR_SP_DETREND)."""
    val = os.environ.get("TPULSAR_WHITEN_ESTIMATOR", "median").strip()
    if val not in ("median", "clipped_mean"):
        raise ValueError(
            f"TPULSAR_WHITEN_ESTIMATOR must be median|clipped_mean, "
            f"got {val!r}")
    return val


def _block_level(x: jnp.ndarray, estimator: str) -> jnp.ndarray:
    """Mean-noise-level estimate over the last axis (exponential
    noise): robust to bright bins, already in MEAN units (the median
    path applies the median->mean factor 1/ln2 here, not at the
    caller)."""
    if estimator == "median":
        return jnp.median(x, axis=-1) / float(np.log(2.0))
    m1 = jnp.mean(x, axis=-1, keepdims=True)
    clipped = jnp.minimum(x, 4.0 * m1)
    # E[min(X, 4 mu)] = mu (1 - e^-4) for X ~ Exp(mu)
    return jnp.mean(clipped, axis=-1) / (1.0 - float(np.exp(-4.0)))


def _level_pieces(centers: list[float],
                  nbins: int) -> list[tuple[int, int, np.ndarray]]:
    """The static half of the level's linear interpolation between
    block centres: pieces (k, n, ramp), in bin order.  A piece covers
    the n adjacent segments k .. k+n-1 (segment j holds the bins in
    (centers[j], centers[j+1]]), all len(ramp) bins long and sharing
    the weights `ramp` of the upper centre.  The equal-width tail
    (integer centres MAX_WHITEN_BLOCK apart: ramp j / 8192, exact in
    float32) is ONE piece; each log-spaced head segment is its own.
    The two end segments run to the spectrum's ends with the weight
    clipped to 0 / 1: jnp.interp's constant extrapolation."""
    c = np.asarray(centers, dtype=np.float64)
    bounds = np.floor(c).astype(np.int64) + 1
    bounds[0], bounds[-1] = 0, nbins
    pieces: list[tuple[int, int, np.ndarray]] = []
    for k in range(len(c) - 1):
        bins = np.arange(bounds[k], bounds[k + 1], dtype=np.float64)
        ramp = np.clip((bins - c[k]) / (c[k + 1] - c[k]),
                       0.0, 1.0).astype(np.float32)
        if pieces and np.array_equal(ramp, pieces[-1][2]):
            pieces[-1] = (pieces[-1][0], pieces[-1][1] + 1, ramp)
        else:
            pieces.append((k, 1, ramp))
    return pieces


def whiten_powers(powers: jnp.ndarray, edges: tuple[int, ...],
                  estimator: str | None = None) -> jnp.ndarray:
    """Divide powers by a piecewise local noise level estimated from
    block statistics (median/ln2 or clipped mean — see
    whiten_estimator), linearly interpolated between block centers.

    powers: (..., nbins).  edges: static log-section boundaries; bins
    past edges[-1] are normalized with equal MAX_WHITEN_BLOCK blocks.

    The estimator resolves OUTSIDE the jit boundary so an env change
    retraces instead of silently reusing the first compilation (the
    sp_detrend pattern)."""
    if estimator is None:
        estimator = whiten_estimator()
    elif estimator not in ("median", "clipped_mean"):
        raise ValueError(
            f"estimator must be median|clipped_mean, got {estimator!r}"
            " (a silently ignored value would change the whitening "
            "statistics with no warning)")
    return _whiten_powers_jit(powers, edges, estimator)


@partial(jax.jit, static_argnames=("edges", "estimator"))
def _whiten_powers_jit(powers: jnp.ndarray, edges: tuple[int, ...],
                       estimator: str) -> jnp.ndarray:
    nbins = powers.shape[-1]
    centers: list[float] = []
    med_parts: list[jnp.ndarray] = []
    # The log-spaced HEAD blocks always use the median: they are tiny
    # (6..8192 bins — their sorts are noise next to the ~2M-bin
    # tail's), and a mean-clip is not robust there (one 4000-power
    # birdie in a 6-bin block inflates the clip threshold enough to
    # keep most of its power; the median gives ~the true level).
    # The estimator choice only governs the equal-width tail blocks,
    # where a single birdie cannot move the first-pass mean.
    for lo, hi in zip(edges[:-1], edges[1:]):
        centers.append(0.5 * (lo + hi))
        med_parts.append(_block_level(powers[..., lo:hi],
                                      "median")[..., None])

    tail_start = int(edges[-1])
    ntail = nbins - tail_start
    m = ntail // MAX_WHITEN_BLOCK
    if m > 0:
        tail = powers[..., tail_start: tail_start + m * MAX_WHITEN_BLOCK]
        tail = tail.reshape(powers.shape[:-1] + (m, MAX_WHITEN_BLOCK))
        med_parts.append(_block_level(tail, estimator))
        centers.extend(tail_start + (j + 0.5) * MAX_WHITEN_BLOCK
                       for j in range(m))
    rem = ntail - m * MAX_WHITEN_BLOCK
    if rem > 16:
        # the remainder block can be as small as 17 bins — median,
        # for the same robustness reason as the head blocks
        lo = nbins - rem
        centers.append(0.5 * (lo + nbins))
        med_parts.append(_block_level(powers[..., lo:],
                                      "median")[..., None])

    med = jnp.concatenate(med_parts, axis=-1)
    med = jnp.maximum(med, 1e-30)
    if len(centers) == 1:
        return powers / med
    # Only `med` depends on the data: which two centres a bin lies
    # between, and how far along, is fixed by (edges, nbins).  So each
    # static piece of the spectrum (_level_pieces) is divided by its
    # level in the piece's own (segments, bins) shape: slices of `med`
    # against a constant weight ramp, jnp.interp's formula.  A search
    # for the segments is not folded away for being over constants:
    # it would run on the device in every call of the program (nine
    # sequential 2M-index gathers at a survey spectrum), and per-bin
    # gathers of `med` cost as much again.
    lead = powers.shape[:-1]
    slabs, start = [], 0
    for k, n, ramp in _level_pieces(centers, nbins):
        stop = start + n * len(ramp)
        lo_v = lax.slice_in_dim(med, k, k + n, axis=-1)[..., None]
        hi_v = lax.slice_in_dim(med, k + 1, k + n + 1, axis=-1)[..., None]
        slab = powers[..., start:stop].reshape(lead + (n, len(ramp)))
        slab = slab / (lo_v * (1.0 - ramp) + hi_v * ramp)
        slabs.append(slab.reshape(lead + (stop - start,)))
        start = stop
    return jnp.concatenate(slabs, axis=-1)


def whiten(powers: jnp.ndarray,
           estimator: str | None = None) -> jnp.ndarray:
    edges = tuple(int(e) for e in _block_edges(powers.shape[-1]))
    return whiten_powers(powers, edges, estimator=estimator)


# ------------------------------------------------------------- zapbirds

def parse_zaplist(path: str) -> np.ndarray:
    """Read a PRESTO-style zaplist: lines of 'freq(Hz) width(Hz)',
    '#' comments.  Returns (n, 2) array."""
    rows = []
    with open(path) as fh:
        for line in fh:
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            rows.append((float(parts[0]), float(parts[1])))
    return np.asarray(rows, dtype=np.float64).reshape(-1, 2)


def zap_mask(nbins: int, T: float, zaplist: np.ndarray,
             baryv: float = 0.0) -> np.ndarray:
    """Boolean keep-mask over rfft bins.  Each (freq, width) birdie is
    barycentre-corrected (f_topo = f_bary / (1 + baryv); reference
    zapbirds is passed -baryv, PALFA2_presto_search.py:551-553) and the
    covered bins are dropped."""
    keep = np.ones(nbins, dtype=bool)
    if zaplist is None or len(zaplist) == 0:
        return keep
    df = 1.0 / T  # Hz per bin
    for freq, width in np.atleast_2d(zaplist):
        f = freq / (1.0 + baryv)
        lo = int(np.floor((f - width / 2) / df))
        hi = int(np.ceil((f + width / 2) / df)) + 1
        lo = max(lo, 0)
        hi = min(hi, nbins)
        if hi > lo:
            keep[lo:hi] = False
    return keep


# ------------------------------------------------- whitening pipeline

def whitened_powers(spec: jnp.ndarray,
                    keep_mask: jnp.ndarray | None = None,
                    estimator: str | None = None) -> tuple:
    """(powers, wpow) from a complex spectrum: zap -> whiten -> re-zap
    (the re-zap because the local level estimate only partially
    excludes zapped bins).  THE definition of the spectral whitening
    sequence — the executor, periodicity_search, and
    normalize_spectrum all share it."""
    powers = jnp.abs(spec) ** 2
    if keep_mask is not None:
        powers = powers * keep_mask.astype(powers.dtype)
    wpow = whiten(powers, estimator=estimator)
    if keep_mask is not None:
        wpow = wpow * keep_mask.astype(wpow.dtype)
    return powers, wpow


def scale_spectrum(spec: jnp.ndarray, powers: jnp.ndarray,
                   wpow: jnp.ndarray) -> jnp.ndarray:
    """Scale the complex spectrum by the whitening level already
    computed from its powers (so noise |X|^2 has unit mean); zapped
    bins (wpow == 0) vanish from the result."""
    return spec * jnp.sqrt(wpow / jnp.maximum(powers, 1e-30)
                           ).astype(spec.dtype)


@partial(jax.jit, static_argnames=("nfft",))
def whitened_spectrum(series: jnp.ndarray, nfft: int) -> jnp.ndarray:
    """pad -> rfft -> whiten -> scale as ONE compiled program.

    The executor's FFT stage previously ran this as four jitted calls
    plus ~6 eager elementwise ops — each eager op its own tiny
    compiled program, and each
    materializing a (rows, nbins)-sized intermediate in HBM.  Fusing
    lets XLA keep the whitening math in registers and gives
    tools/aot_check.py ONE program per shape family to gate."""
    with scopes.scope("spectra/fft"):
        spec = complex_spectrum(pad_series(series, nfft))
    with scopes.scope("spectra/whiten"):
        powers, wpow = whitened_powers(spec)
        return scale_spectrum(spec, powers, wpow)


@partial(jax.jit, static_argnames=("nfft",))
def whitened_spectrum_masked(series: jnp.ndarray, keep: jnp.ndarray,
                             nfft: int) -> jnp.ndarray:
    """whitened_spectrum with a zaplist keep-mask (separate program:
    the mask multiply changes the HLO)."""
    with scopes.scope("spectra/fft"):
        spec = complex_spectrum(pad_series(series, nfft))
    with scopes.scope("spectra/whiten"):
        powers, wpow = whitened_powers(spec, keep)
        return scale_spectrum(spec, powers, wpow)


@jax.jit
def interbin_powers(wspec: jnp.ndarray) -> jnp.ndarray:
    """Half-bin detection grid from a whitened complex spectrum —
    PRESTO's interbinning (accelsearch searches at ACCEL_DR = 0.5;
    a dr=1 grid loses up to ~64% of a half-bin tone's summed power
    to scalloping, interbinning caps the loss at ~7%).

    out[..., 2k]   = |X_k|^2
    out[..., 2k+1] = (pi^2/16) |X_k - X_{k+1}|^2   (~ |X_{k+1/2}|^2)

    The estimate is EXACT in amplitude for a tone at exactly k+1/2
    (adjacent-bin responses are equal and opposite in phase there).
    Half-bin samples are not independent trials: numindep stays the
    true bin count.  Index r in the output is in HALF-BIN units
    (frequency = 0.5 * r / T_s).
    """
    p = jnp.abs(wspec) ** 2
    half = (np.pi ** 2 / 16.0) * jnp.abs(
        wspec[..., :-1] - wspec[..., 1:]) ** 2
    half = jnp.pad(half, [(0, 0)] * (half.ndim - 1) + [(0, 1)])
    return jnp.stack([p, half], axis=-1).reshape(*p.shape[:-1], -1)


# ------------------------------------------- harmonic summing + candidates

def harmonic_stages(max_numharm: int) -> list[int]:
    """PRESTO searches stages 1,2,4,8,16 up to numharm."""
    stages = []
    h = 1
    while h <= max_numharm:
        stages.append(h)
        h *= 2
    return stages


@partial(jax.jit, static_argnames=("numharm",))
def harmonic_sum(powers: jnp.ndarray, numharm: int) -> jnp.ndarray:
    """Incoherent harmonic sum: S_n(r) = sum_{h=1..n} P(h*r).

    Strided slicing (P[h*r] == P[::h][r]): the plain form, which a
    program lowers OFF the TPU and the tests' oracle of the tiled
    kernel below.  On a TPU each slice is a gather along the lanes
    (~1 ns an element: PERF.md, PR 39), so no search runs it there.
    Output length nbins//numharm (fundamentals must keep harmonic
    numharm*r inside the spectrum).
    """
    nbins = powers.shape[-1]
    L = nbins // numharm
    acc = powers[..., :L]
    for h in range(2, numharm + 1):
        acc = acc + powers[..., ::h][..., :L]
    return acc


# r-block width for the hierarchical top-k.  One candidate survives
# per block per stage, so the block must stay well below the minimum
# separation of signals we care to distinguish: 64 bins is ~0.25 Hz
# for a 257 s observation (distinct pulsars/harmonics are farther
# apart; a peak's shoulder bins are much closer) while still cutting
# the top-k input by 64x.
BLOCK_R = 64


def _block_maxima(summed: jnp.ndarray, block_r: int):
    """(max, first-index argmax) of each `block_r` columns of the last
    axis, the last block padded with -inf: (..., ceil(L / block_r))
    each."""
    L = summed.shape[-1]
    nb = -(-L // block_r)
    pad = nb * block_r - L
    if pad:
        summed = jnp.pad(summed,
                         ((0, 0),) * (summed.ndim - 1) + ((0, pad),),
                         constant_values=-jnp.inf)
    resh = summed.reshape(summed.shape[:-1] + (nb, block_r))
    return resh.max(axis=-1), resh.argmax(axis=-1).astype(jnp.int32)


def _topk_blocks(bmax: jnp.ndarray, barg: jnp.ndarray, topk: int,
                 block_r: int):
    """Top-k over block maxima -> (vals, bins), zero-padded to topk
    where there are fewer blocks."""
    nb = bmax.shape[-1]
    k = min(topk, nb)
    vals, blk = jax.lax.top_k(bmax, k)
    bins = blk * block_r + jnp.take_along_axis(barg, blk, axis=-1)
    if k < topk:
        vals = jnp.pad(vals,
                       ((0, 0),) * (vals.ndim - 1) + ((0, topk - k),))
        bins = jnp.pad(bins,
                       ((0, 0),) * (bins.ndim - 1) + ((0, topk - k),))
    return vals, bins


@partial(jax.jit, static_argnames=("topk", "block_r"))
def blockmax_topk(summed: jnp.ndarray, topk: int, block_r: int = BLOCK_R):
    """Hierarchical top-k over the last axis: max-reduce fixed r
    blocks (keeping the argmax), then top-k over the block maxima.

    Returns (vals, bins) of shape (..., k).  A full-width lax.top_k
    over multi-million-bin spectra is a sort-scale operation repeated
    per DM per stage (round-1 verdict weakness #4); the block
    reduction is one cheap memory-bound pass, and taking at most one
    candidate per `block_r` bins also deduplicates a peak's shoulder
    bins (replacing the explicit local-max suppression).
    """
    return _topk_blocks(*_block_maxima(summed, block_r), topk, block_r)


# --- the lo stage's harmonic sums on a TPU ---------------------------
# One Pallas kernel computes every stage's block maxima of the
# harmonic-summed rows.  DM rows on the sublanes, columns on the lanes,
# a grid over (row groups, column tiles); harmonic hh of an output tile
# is every hh-th column of a contiguous block of the same array, taken
# on the MXU (kernels/decimate.py, shared with hi-accel's kernel,
# accel._harmsum_zmax: this is its sibling with the DM rows where that
# one has its z rows, the row map the identity and no max over rows).
# Stage 2h continues stage h's accumulator over its own column range
# and adds hh = h+1 .. 2h: harmonic_sum's left-to-right float32
# additions, so its bits, each harmonic decimated once.  The maximum
# and first-index argmax of every BLOCK_R columns are taken on the
# accumulator in VMEM (a log-step lane rotate and select), and only
# those leave the kernel: no summed array, no decimated copy in HBM.

#: tiles tried, widest first; the most rows a grid step takes (more
#: are split evenly over row groups: 40 rows afford a tile of 1024,
#: 320 stacked rows a selection matrix, and a tile writes 128 lanes of
#: block maxima whatever its width); the scoped VMEM a tile is chosen
#: for / the most the kernel may ask of a v5e's 128 MiB
_LO_TILES = (2048, 1024, 512, 256, 128)
_LO_ROWS_MAX = 40
#: the platforms a program is lowered with the kernel for, and the
#: most rows a call gives it there (lo_form: the one rule that
#: _stage_block_maxima branches on and lo_dispatch_attrs reports).
#: Everything else lowers harmonic_sum's strided form: off a TPU it is
#: the plain form; on one XLA carries 64 rows and more on the LANES,
#: where a stride is over sublanes and no gather, and the fuller those
#: lanes the less the kernel has to offer.  The shipped kernel against
#: the strided form on a v5e, ms a lo_stage_candidates call (PERF.md,
#: PR 39): 38 rows x 3,932,162 columns 52 / 410; 64 x 1,966,082
#: 41.1 / 48.4; 76 x 1,310,722 36.0 / 34.7; 102 x 1,361,922
#: 51.1 / 39.6 (gbncc_steps_noaccel fell 2.1% with the kernel there)
_LO_TILED_PLATFORMS = ("tpu",)
_LO_TILED_ROWS = 64
_LO_VMEM_TARGET = 72 << 20
_LO_VMEM_MAX = 96 << 20


@dataclasses.dataclass(frozen=True)
class LoHarmsumPlan(decimate.StagePlan):
    """Tile, row group and VMEM bytes of the lo stage's harmonic-sum
    kernel, derived from what it can see of its input."""
    rows: int
    ncols: int
    stages: tuple[int, ...]   # those with a column to give
    row_block: int            # rows a grid step (whole sublanes)
    tile: int                 # output columns a grid step, T
    ntiles: tuple[int, ...]   # per stage: grid steps that write it
    vmem_bytes: int           # blocks x2 + scratch + live values
    vmem_limit: int           # the scoped-VMEM limit it requests


def _lo_vmem_bytes(row_block: int, tile: int, numharm: int,
                   nstages: int) -> int:
    tri = numharm * (numharm + 1) // 2
    blocks = 2 * row_block * tile * 4 * tri        # inputs, 2 buffers
    outs = 2 * 2 * nstages * row_block * decimate.LANES * 4
    acc = row_block * tile * 4
    # live values of one harmonic (the masked block, its stacked copy,
    # the float32 parts the six passes split it into) and of one
    # stage's block maxima (value and index, rotated and selected)
    live = row_block * tile * 4 * (3 * numharm + 6)
    return blocks + outs + decimate.sel_bytes(numharm, 4) + acc + live


def lo_harmsum_plan(rows: int, ncols: int,
                    stages: tuple[int, ...]) -> LoHarmsumPlan:
    """The kernel's tiling for (rows, ncols) float32 powers.  Stages
    too high for the array to have a column are dropped.  What the
    kernel cannot take is refused here, loudly."""
    stages = decimate.check_stages(stages, ncols, "lo harmonic-sum kernel")
    if rows < 1:
        raise ValueError(f"lo harmonic-sum kernel: no rows ({rows})")
    numharm = stages[-1]
    groups = -(-rows // _LO_ROWS_MAX)
    row_block = -(-(-(-rows // groups)) // 8) * 8
    widest = -(-ncols // decimate.LANES) * decimate.LANES
    for tile in _LO_TILES:
        need = _lo_vmem_bytes(row_block, tile, numharm, len(stages))
        if tile <= widest and need <= _LO_VMEM_TARGET:
            break
    if need > _LO_VMEM_MAX:
        raise ValueError(
            f"lo harmonic-sum kernel: {numharm} harmonics over "
            f"{row_block} rows need {need} B of VMEM at the smallest "
            f"tile, over the {_LO_VMEM_MAX} B the kernel may ask for")
    ntiles = tuple(-(-(ncols // h) // tile) for h in stages)
    return LoHarmsumPlan(
        rows=rows, ncols=ncols, stages=stages,
        row_block=row_block, tile=tile, ntiles=ntiles, vmem_bytes=need,
        vmem_limit=max(32 << 20, need + (8 << 20)))


def _lo_kernel(p: LoHarmsumPlan):
    """The kernel body for one plan: refs are the numharm source
    blocks (hh = 1..numharm), then (block max, argmax) per stage, then
    the selection matrices and the accumulator."""
    RB, T, H, ns = p.row_block, p.tile, p.numharm, len(p.stages)
    G = T // decimate.LANES
    tile3 = (G, RB, decimate.LANES)

    def block_maxima(acc, ncols, j):
        """acc (G, RB, 128): columns j*T + g*128 + lane of a stage with
        `ncols` columns -> (max, argmax in its block) of the tile's
        2*G blocks, even blocks at lanes [0, G), odd at [64, 64+G)."""
        g = jax.lax.broadcasted_iota(jnp.int32, tile3, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, tile3, 2)
        # pad columns read -inf, as blockmax_topk's padding
        v = jnp.where(j * T + g * decimate.LANES + lane < ncols,
                      acc, -jnp.inf)
        i = lane % BLOCK_R
        # lane l takes over [l, l + 2s) from [l, l + s) and
        # [l + s, l + 2s): every index of the second is the larger, so
        # a tie keeps the first (argmax's rule)
        for s in (1, 2, 4, 8, 16, 32):
            v2 = pltpu.roll(v, decimate.LANES - s, 2)
            i2 = pltpu.roll(i, decimate.LANES - s, 2)
            take = v2 > v
            v = jnp.where(take, v2, v)
            i = jnp.where(take, i2, i)
        # lanes 0 and 64 of group g now hold its two blocks
        slot = jax.lax.broadcasted_iota(
            jnp.int32, tile3[1:], 1) % BLOCK_R
        top, arg = v[0], i[0]
        for k in range(1, G):
            top = jnp.where(slot == k, pltpu.roll(v[k], k, 1), top)
            arg = jnp.where(slot == k, pltpu.roll(i[k], k, 1), arg)
        return top, arg

    def kernel(*refs):
        x_refs = refs[:H]
        out_refs = refs[H:H + 2 * ns]
        sel_ref, acc_ref = refs[H + 2 * ns:]
        j = pl.program_id(1)

        if H > 1:
            pl.when(j == 0)(
                lambda: decimate.write_selection(sel_ref, H, jnp.float32))

        prev = 0
        for si, h in enumerate(p.stages):
            def stage(si=si, h=h, prev=prev):
                for hh in range(prev + 1, h + 1):
                    if hh == 1:
                        acc_ref[...] = decimate.stack_groups(
                            x_refs[0][...], G, decimate.LANES
                        ).reshape(tile3)
                    else:
                        acc_ref[...] = acc_ref[...] + decimate.decimated_tile(
                            x_refs[hh - 1], sel_ref, hh, G,
                            p.ncols - j * (hh * T)).reshape(tile3)
                top, arg = block_maxima(acc_ref[...], p.ncols // h, j)
                out_refs[2 * si][...] = top
                out_refs[2 * si + 1][...] = arg
            # a tile past a stage's range adds nothing to it; stage
            # 2h's range lies inside stage h's, so acc carries over
            pl.when(j < p.ntiles[si])(stage)
            prev = h

    return kernel


@partial(jax.jit, static_argnames=("stages", "interpret"))
def _lo_block_maxima(powers: jnp.ndarray, stages: tuple[int, ...],
                     interpret: bool) -> dict:
    """powers (rows, ncols) float32 -> per stage the (max, argmax) of
    every BLOCK_R columns of its harmonic sum, each
    (rows, ceil(ncols // h / BLOCK_R)): the Pallas call itself (a stage
    the array has no column for is answered empty, outside the call).

    The powers must be finite: see kernels/decimate.py (a whitened,
    zapped spectrum is; held by tests)."""
    if powers.dtype != jnp.float32:
        raise ValueError(
            f"lo harmonic-sum kernel: powers are {powers.dtype}, not "
            "float32")
    rows, ncols = powers.shape
    p = lo_harmsum_plan(rows, ncols, stages)
    T, RB = p.tile, p.row_block
    G = T // decimate.LANES

    def clamped(last):
        # past a stage's last tile the block index stays: no DMA, and
        # the finished output block is not touched again
        return lambda i, j: (i, jnp.minimum(j, last))

    in_specs = [pl.BlockSpec((RB, hh * T),
                             clamped(p.ntiles[p.stage_of(hh)] - 1))
                for hh in range(1, p.numharm + 1)]
    out_specs, out_shape = [], []
    for nt in p.ntiles:
        for dt in (jnp.float32, jnp.int32):
            out_specs.append(pl.BlockSpec(
                (None, RB, decimate.LANES),
                lambda i, j, last=nt - 1: (jnp.minimum(j, last), i, 0)))
            out_shape.append(jax.ShapeDtypeStruct(
                (nt, rows, decimate.LANES), dt))
    outs = pl.pallas_call(
        _lo_kernel(p),
        grid=(-(-rows // RB), p.ntiles[0]),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((max(decimate.sel_row(p.numharm + 1), 8),
                        decimate.LANES), jnp.float32),
            pltpu.VMEM((G, RB, decimate.LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=p.vmem_limit),
        interpret=interpret, name="lo_harmsum",
    )(*([powers] * p.numharm))

    def blocks(a, h):
        # tile t's lanes [0, G) and [64, 64 + G) are its even and odd
        # blocks: (tiles, rows, 128) -> (rows, blocks)
        pair = jnp.stack([a[:, :, :G], a[:, :, BLOCK_R:BLOCK_R + G]],
                         axis=-1)
        return jnp.moveaxis(pair, 0, 1).reshape(rows, -1)[
            :, :-(-(ncols // h) // BLOCK_R)]

    out = {h: (blocks(outs[2 * si], h), blocks(outs[2 * si + 1], h))
           for si, h in enumerate(p.stages)}
    for h in stages[len(p.stages):]:
        out[h] = (jnp.zeros((rows, 0), jnp.float32),
                  jnp.zeros((rows, 0), jnp.int32))
    return out


def lo_form(rows: int, platform: str) -> str:
    """The form of the lo stage's harmonic sums in a program lowered
    for `platform` at `rows` rows a call: "tiled" (the kernel) or
    "strided" (see _LO_TILED_ROWS)."""
    tiled = platform in _LO_TILED_PLATFORMS and rows <= _LO_TILED_ROWS
    return "tiled" if tiled else "strided"


def _stage_block_maxima(powers: jnp.ndarray,
                        stages: tuple[int, ...]) -> dict:
    """Per stage the block maxima of harmonic_sum(powers, h), in the
    form lo_form names for each platform the program may be lowered
    for — chosen per lowering, as hi-accel's
    (accel._harmonic_stage_maxes), and to the same bits (tests hold
    the kernel, run in Pallas's interpreter, to the strided form's)."""
    lead = powers.shape[:-1]
    flat = powers.reshape((-1, powers.shape[-1]))

    def strided(x):
        return {h: _block_maxima(harmonic_sum(x, h), BLOCK_R)
                for h in stages}

    def tiled(x):
        return _lo_block_maxima(x, stages, interpret=False)

    # no branch where no platform takes the kernel at these rows: the
    # program is then the strided form's own, whatever it is lowered for
    tiled_on = [plat for plat in _LO_TILED_PLATFORMS
                if lo_form(flat.shape[0], plat) == "tiled"]
    out = (jax.lax.platform_dependent(
        flat, default=strided, **dict.fromkeys(tiled_on, tiled))
        if tiled_on else strided(flat))
    return {h: tuple(a.reshape(lead + a.shape[-1:]) for a in pair)
            for h, pair in out.items()}


def lo_dispatch_attrs(rows: int, nbins: int, stages: tuple[int, ...],
                      platform: str) -> dict:
    """What a lo_stage_candidates program lowered for `platform` (that
    of the devices its operands live on) runs for (rows, nbins)
    spectra, for the chunk's span (docs/operations.md): `lo_form`, by
    the rule _stage_block_maxima branches on, and `lo_tile`, the
    kernel's tile (0 for the strided form)."""
    form = lo_form(rows, platform)
    tile = (lo_harmsum_plan(rows, 2 * nbins, tuple(stages)).tile
            if form == "tiled" else 0)
    return {"lo_form": form, "lo_tile": tile}


@partial(jax.jit, static_argnames=("stages", "topk"))
def all_stage_candidates(powers: jnp.ndarray, stages: tuple[int, ...],
                         topk: int) -> dict:
    """Every harmonic stage's top-k in ONE compiled program: per stage
    (values, bins), each (..., topk); bins are fundamental bin indices
    of `powers`.  Each harmonic is read once for all stages; on a TPU
    by the tiled kernel, elsewhere by strided slices
    (_stage_block_maxima)."""
    stages = tuple(stages)
    with scopes.scope("lo/harmsum"):
        maxima = _stage_block_maxima(powers, stages)
    with scopes.scope("lo/topk"):
        return {h: _topk_blocks(*maxima[h], topk, BLOCK_R)
                for h in stages}


@partial(jax.jit, static_argnames=("stages", "topk"))
def lo_stage_candidates(wspec: jnp.ndarray, stages: tuple[int, ...],
                        topk: int) -> dict:
    """interbin + every harmonic stage's top-k as ONE program, from
    the whitened complex spectrum (rows, nbins); bins are in HALF-BIN
    units."""
    with scopes.scope("lo/harmsum"):
        powers = interbin_powers(wspec)
    return all_stage_candidates(powers, stages, topk)


# ----------------------------------------------------------- significance

def sigma_from_power(summed_power, numharm: int, numindep: int = 1):
    """Equivalent Gaussian significance of a summed power from
    `numharm` harmonics of unit-mean exponential noise.

    P(S > s) for S ~ Gamma(n, 1) is the regularized upper incomplete
    gamma Q(n, s); computed in log space so sigma stays finite for
    very strong signals (PRESTO's candidate_sigma equivalent).

    numindep: number of independent trials searched to find this
    candidate (PRESTO passes the searched bin count per harmonic
    stage).  The single-trial p-value is corrected to
    p_corr = 1 - (1 - p)^numindep before conversion, so sigma means
    "significance given how hard we looked" and matches the scale the
    reference's sifting thresholds were tuned for.
    """
    s = np.asarray(summed_power, dtype=np.float64)
    n = int(numharm)
    with np.errstate(divide="ignore"):
        # logQ via asymptotic-safe route: use gammaincc then log, but
        # fall back to the large-s expansion when it underflows.
        q = sps.gammaincc(n, s)
        logq = np.where(q > 0, np.log(np.maximum(q, 1e-300)), -np.inf)
        # large-s: Q(n,s) ~ s^(n-1) e^(-s) / Gamma(n)
        tail = (n - 1) * np.log(np.maximum(s, 1e-30)) - s - sps.gammaln(n)
        logq = np.where(np.isfinite(logq) & (q > 1e-290), logq, tail)
    if numindep > 1:
        # log(1 - (1-p)^M) with p = exp(logq).  Two regimes:
        #   p tiny (logq < -30): p_corr ~ M*p  =>  logq + log M —
        #     NEVER through exp(logq) (it underflows for strong
        #     signals, which would cap sigma and create ties);
        #   otherwise: exact via log1p/exp (safe: logq >= -30).
        with np.errstate(invalid="ignore", over="ignore",
                         divide="ignore"):
            small = logq < -30.0
            safe_logq = np.clip(logq, -30.0, -1e-17)
            m_log1mp = numindep * np.log1p(-np.exp(safe_logq))
            exact = np.where(
                m_log1mp > -1e-8,
                # 1-(1-p)^M ~ -M*log(1-p) when tiny
                np.log(np.maximum(-m_log1mp, 1e-300)),
                np.log1p(-np.exp(np.clip(m_log1mp, -745.0, -1e-17))))
            logq = np.where(small, logq + np.log(numindep), exact)
        logq = np.minimum(logq, 0.0)
    return -sps.ndtri_exp(logq) if hasattr(sps, "ndtri_exp") else \
        sps.ndtri(1.0 - np.exp(logq))


def power_threshold(sigma: float, numharm: int) -> float:
    """Summed-power threshold giving the requested Gaussian sigma."""
    from scipy import optimize
    return float(optimize.brentq(
        lambda s: sigma_from_power(s, numharm) - sigma,
        1e-3, 1e4, xtol=1e-6))


# ------------------------------------------------------------ full search

def periodicity_search(series: jnp.ndarray, T_s: float,
                       keep_mask: np.ndarray | None = None,
                       max_numharm: int = 16, topk: int = 64):
    """Zero-acceleration periodicity search of (ndms, T) DM series.

    Returns a dict: stage -> (powers[ndms, topk], bins[ndms, topk]) as
    numpy, plus the TRUE (independent) spectrum bin count.  Bins are
    in HALF-BIN units (interbinned detection grid, dr=0.5 — the same
    semantics as the executor's lo stage); fundamental r = 0.5*bin.
    Host code converts to sigmas and merges with sifting
    (bin_scale=0.5).
    """
    keep = jnp.asarray(keep_mask) if keep_mask is not None else None
    spec = complex_spectrum(series)
    powers, wpow = whitened_powers(spec, keep)
    p2 = interbin_powers(scale_spectrum(spec, powers, wpow))
    res = all_stage_candidates(p2, tuple(harmonic_stages(max_numharm)),
                               topk)
    return ({h: (np.asarray(v), np.asarray(b))
             for h, (v, b) in res.items()}, wpow.shape[-1])
