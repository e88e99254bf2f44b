"""Single-pulse (boxcar matched filter) search on TPU.

Replaces PRESTO's single_pulse_search.py (reference invocation:
lib/python/PALFA2_presto_search.py:540-543): each DM time series is
detrended, normalized, and convolved with a ladder of boxcar widths;
events above threshold become single-pulse candidates.

Every width's windowed sum is a short chain of shifted adds over the
sums of narrower windows (boxcar_chain: 30 = 20 + 9 + 1, ten shifts
for the nine default widths), and only each 32-sample block's maximum
and first-index argmax go on to the top-k.  On a TPU the whole ladder
is ONE pass over the series in a Pallas kernel (_ladder_block_maxima);
elsewhere the same chain in plain jnp, to the same bits.  The width
ladder matches PRESTO's default downfact ladder up to 30 samples.
"""

from __future__ import annotations

import dataclasses
import os

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulsar.kernels import fourier as fr
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


@partial(jax.jit, static_argnames=("detrend_block", "estimator"))
@scopes.scope("sp/detrend")
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


# --- the boxcar ladder ----------------------------------------------
# boxcar_search wants, for every width w, the (max, first-index argmax)
# of each BLOCK samples of snr_w[t] = sum(x[t : t + w]) / sqrt(w), a
# window that runs past the series reading -inf.  Both forms below build
# the sums by the same chain of float32 additions, so to the same bits.

#: samples a block: events_from_topk clusters events into the same
#: buckets, so one candidate a block a width loses nothing
BLOCK = 32


def boxcar_chain(widths: tuple[int, ...]) -> tuple:
    """The additions that build every width's window sum from the
    series: ((w, parts), ...) in the order to compute them, where
    sum_w[t] = sum_p0[t] + sum_p1[t + p0] + sum_p2[t + p0 + p1] + ...,
    added left to right, and every part is 1 (the series itself) or an
    earlier entry's w.  Greedy over what is there, widest first, after
    doubling the widest while it fits (so a lone wide boxcar costs
    log2(w) shifts, not w): DEFAULT_WIDTHS take ten shifts."""
    have = [1]
    chain = []
    for w in sorted(set(int(w) for w in widths)):
        if w < 1:
            raise ValueError(f"boxcar widths must be >= 1, got {w}")
        while 2 * have[-1] <= w and 2 * have[-1] not in have:
            chain.append((2 * have[-1], (have[-1], have[-1])))
            have.append(2 * have[-1])
        if w in have:
            continue
        parts, rem = [], w
        while rem:
            parts.append(max(v for v in have if v <= rem))
            rem -= parts[-1]
        chain.append((w, tuple(parts)))
        have.append(w)
        have.sort()
    return tuple(chain)


def _scale(w: int) -> np.float32:
    return np.float32(1.0 / np.sqrt(float(w)))


def _plain_block_maxima(norm: jnp.ndarray, widths: tuple[int, ...]):
    """The ladder's block maxima in plain jnp (shifted slices, then
    fourier._block_maxima): what every platform but a TPU lowers, and
    the oracle of the kernel below.  (nwidths, rows, ceil(T / BLOCK))
    maxima and in-block argmax."""
    rows, T = norm.shape
    sums = {1: norm}                    # sums[w]: (rows, max(T-w+1, 0))
    for w, parts in boxcar_chain(widths):
        n = max(T - w + 1, 0)
        acc, off = sums[parts[0]][:, :n], parts[0]
        for p in parts[1:]:
            acc = acc + sums[p][:, off:off + n]
            off += p
        sums[w] = acc
    out = []
    for w in widths:
        snr = sums[w] * _scale(w)
        snr = jnp.pad(snr, ((0, 0), (0, T - snr.shape[-1])),
                      constant_values=-jnp.inf)
        out.append(fr._block_maxima(snr, BLOCK))
    return (jnp.stack([m for m, _ in out]),
            jnp.stack([a for _, a in out]))


# On a TPU: rows on the sublanes, samples on the lanes, a grid over
# (groups of 8 rows, tiles of _SP_TILE samples).  A grid step has two
# phases:
#  1. the tile's 32 chunks of 128 lanes (and one more, the right halo:
#     the first 128 samples of the next tile, a second operand), eight
#     at a time, the last eight first: a sum shifted by `off` samples
#     is the chunk's own lanes rotated, the top `off` lanes taken from
#     the NEXT chunk's rotation (the eighth chunk's from what the step
#     before left in registers).  Scaled, the windows past the series'
#     end set to -inf by a plain select, each width's chunk goes to a
#     VMEM scratch.
#  2. per width, the 32 chunks' block maxima by a butterfly: a log-step
#     (s = 1, 2, 4, 8, 16) combines lane l with lane l + s, and only
#     lanes with bit s clear hold a result after it, so each step takes
#     TWO chunks at once, the second's results on the lanes the first
#     leaves idle (two rotates a pair a step where a chunk alone costs
#     five, and the 32 chunks end as ONE register of 128 maxima).  The
#     kernel is bound by its lane rotates, ~2.7 cycles each as a v5e
#     schedules them: 330 a tile in phase 1, 100 a width here.
#     Which chunks pair at which step is chosen (the scratch slot of
#     _slot) so that two bit swaps of the lane index put block b of the
#     tile on lane b.  The in-block index travels with the value; a tie
#     keeps the lower sample (argmax's rule).
# Only (nwidths, rows, ceil(T / 32)) maxima and indices leave the
# kernel: no prefix sum, no array of sums, no relayout in HBM.

_LANES = 128
_SP_CHUNKS = 32                  # chunks a tile: 128 blocks, one register
_SP_TILE = _SP_CHUNKS * _LANES   # samples a grid step (a row)
#: rows a grid step: one register's sublanes.  The kernel's time goes
#: with the rows it computes, not with its grid steps (a v5e, ms a call
#: at 102 rows x 1,361,920: 30.7 in steps of 8, 104 rows computed, 35.1
#: in steps of 40, 120 computed; at 38 x 3,932,160: 33.8 either way;
#: PERF.md, PR 44), so the smallest group wastes the fewest
_SP_ROWS = 8
#: chunks the sums are formed for at once (one loop step): a shifted
#: read's rotate comes back ~30 cycles after it is issued, and eight
#: independent chunks fill the wait (the compiler's schedule for a v5e:
#: 1474 bundles a tile at one chunk a step, 1018 at four, 923 at eight)
_SP_BATCH = 8
#: the platforms a program is lowered with the kernel for (sp_form: the
#: one rule boxcar_search branches on and sp_dispatch_attrs reports)
_SP_TILED_PLATFORMS = ("tpu",)


@dataclasses.dataclass(frozen=True)
class BoxcarPlan:
    """The ladder's kernel for one series: its tile, its row group
    and the additions that build its sums."""
    rows: int
    nsamp: int
    widths: tuple[int, ...]
    chain: tuple                  # boxcar_chain(widths)
    row_block: int                # rows a grid step (whole sublanes)
    tile: int                     # samples a grid step
    ntiles: int


def sp_boxcar_plan(rows: int, nsamp: int,
                   widths: tuple[int, ...]) -> BoxcarPlan:
    """The kernel's tiling for a (rows, nsamp) float32 series.  What
    the kernel cannot take is refused here, loudly."""
    widths = tuple(int(w) for w in widths)
    if rows < 1 or nsamp < 1 or not widths:
        raise ValueError(
            f"boxcar kernel: nothing to search (rows={rows}, "
            f"nsamp={nsamp}, widths={widths})")
    if max(widths) > _LANES:
        raise ValueError(
            f"boxcar kernel: width {max(widths)} reaches past the "
            f"{_LANES}-sample halo")
    return BoxcarPlan(rows=rows, nsamp=nsamp, widths=widths,
                      chain=boxcar_chain(widths), row_block=_SP_ROWS,
                      tile=_SP_TILE, ntiles=-(-nsamp // _SP_TILE))


def _slot(g):
    """Scratch slot of chunk g (bits c4..c0): c3 c4 c0 c1 c2.  Step k
    of the butterfly pairs the slots that differ in their top bit and
    leaves that bit on lane bit k, so the last register's lane reads
    q1 q0 c2 c1 c0 c4 c3 (q: the block within its chunk), and swapping
    bits 6, 1 and 5, 0 makes it c4 .. c0 q1 q0: the block's number."""
    bit = lambda b: (g >> b) & 1
    return (bit(3) << 4) | (bit(4) << 3) | (bit(0) << 2) | (bit(1) << 1) \
        | bit(2)


def _ladder_kernel(p: BoxcarPlan):
    """The kernel body for one plan: refs are the tile, its right halo,
    the block maxima and in-block indices (nwidths, 8, 128), and the
    scratch of scaled sums (nwidths, 32, 8, 128)."""
    G, nw, B = _SP_CHUNKS, len(p.widths), _SP_BATCH
    vreg = (_SP_ROWS, _LANES)
    nshifts = sum(len(parts) - 1 for _, parts in p.chain)

    def chunk_sums(x, nxt, first):
        """Some chunks in a row: x (n, 8, 128) their samples, nxt the
        rotations (one a shifted read) of the chunk after them -> the
        first chunk's rotations and each width's scaled, masked sums.
        `first`: the first chunk's first sample."""
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
        pos = lane + _LANES * jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        sums, rot = {1: x}, []
        for w, parts in p.chain:
            acc, off = sums[parts[0]], parts[0]
            for q in parts[1:]:
                r = pltpu.roll(sums[q], _LANES - off, 2)
                up = nxt[len(rot)][None]
                if x.shape[0] > 1:
                    up = jnp.concatenate([r[1:], up], 0)
                rot.append(r[0])
                acc = acc + jnp.where(lane < _LANES - off, r, up)
                off += q
            sums[w] = acc
        # window t is whole while t + w <= nsamp: a plain select on
        # every chunk (PERF.md, PR 39: no pl.when, no lax.cond value)
        snr = [jnp.where(pos <= p.nsamp - w - first,
                         sums[w] * _scale(w), -jnp.inf)
               for w in p.widths]
        return tuple(rot), snr

    def swap_bits(a, hi, lo):
        """a (8, 128) with bits `hi` and `lo` of the lane index
        swapped."""
        lane = jax.lax.broadcasted_iota(jnp.int32, vreg, 1)
        d = (1 << hi) - (1 << lo)
        bh, bl = (lane >> hi) & 1, (lane >> lo) & 1
        return jnp.where((bh == 0) & (bl == 1),
                         pltpu.roll(a, _LANES - d, 1),
                         jnp.where((bh == 1) & (bl == 0),
                                   pltpu.roll(a, d, 1), a))

    def block_maxima(v):
        """v (32, 8, 128), chunks by slot -> (max, in-block argmax)
        of the tile's 128 blocks, block b on lane b, each (8, 128)."""
        i = None
        for k in range(5):
            s, n = 1 << k, v.shape[0] // 2
            lane = jax.lax.broadcasted_iota(jnp.int32, (n,) + vreg, 2)
            low = (lane & s) == 0
            a, b = v[:n], v[n:]
            # lanes with bit s clear: a's lane l against its l + s;
            # the others: b's lane l - s against its l.  The second
            # of each pair is the later sample: a tie keeps the first
            first = jnp.where(low, a, pltpu.roll(b, s, 2))
            second = jnp.where(low, pltpu.roll(a, _LANES - s, 2), b)
            if i is None:       # lanes hold their own index in a block
                pos = lane % BLOCK
                ifirst, isecond = pos & ~1, pos | 1
            else:
                ia, ib = i[:n], i[n:]
                ifirst = jnp.where(low, ia, pltpu.roll(ib, s, 2))
                isecond = jnp.where(low, pltpu.roll(ia, _LANES - s, 2),
                                    ib)
            take = second > first
            v = jnp.where(take, second, first)
            i = jnp.where(take, isecond, ifirst)
        v, i = v[0], i[0]
        return (swap_bits(swap_bits(v, 6, 1), 5, 0),
                swap_bits(swap_bits(i, 6, 1), 5, 0))

    def kernel(x_ref, halo_ref, max_ref, arg_ref, snr_ref):
        t0 = pl.program_id(1) * _SP_TILE
        # the halo chunk's rotations; past it nothing is read (its own
        # top lanes are windows of the next tile)
        nxt, _ = chunk_sums(
            halo_ref[...][None],
            (jnp.zeros(vreg, jnp.float32),) * nshifts, t0 + _SP_TILE)

        def chunks(k, nxt):
            g = G - B * (k + 1)
            x = jnp.stack([
                x_ref[:, pl.ds(pl.multiple_of((g + b) * _LANES, _LANES),
                               _LANES)] for b in range(B)])
            nxt, snr = chunk_sums(x, nxt, t0 + g * _LANES)
            base = _slot(g)     # g is a multiple of B: the bits add
            for wi in range(nw):
                for b in range(B):
                    snr_ref[wi, base + _slot(b)] = snr[wi][b]
            return nxt

        jax.lax.fori_loop(0, G // B, chunks, nxt, unroll=True)

        def width(wi, carry):
            max_ref[wi], arg_ref[wi] = block_maxima(snr_ref[wi])
            return carry

        jax.lax.fori_loop(0, nw, width, 0)

    return kernel


@partial(jax.jit, static_argnames=("widths", "interpret"))
def _ladder_block_maxima(norm: jnp.ndarray, widths: tuple[int, ...],
                         interpret: bool):
    """norm (rows, T) float32 -> the ladder's block maxima and in-block
    argmax, each (nwidths, rows, ceil(T / BLOCK)): the Pallas call
    itself, _plain_block_maxima's bits."""
    if norm.dtype != jnp.float32:
        raise ValueError(
            f"boxcar kernel: the series is {norm.dtype}, not float32")
    rows, T = norm.shape
    p = sp_boxcar_plan(rows, T, widths)
    nb = -(-T // BLOCK)
    if T < _SP_TILE:
        # a series shorter than a tile is padded to one (what the pad
        # holds is past the end: masked) and its blocks cut out after
        norm = jnp.pad(norm, ((0, 0), (0, _SP_TILE - T)))
    nhalo = -(-norm.shape[-1] // _LANES)
    RB, nw = p.row_block, len(p.widths)
    outs = pl.pallas_call(
        _ladder_kernel(p),
        grid=(-(-rows // RB), p.ntiles),
        in_specs=[
            pl.BlockSpec((RB, _SP_TILE), lambda i, j: (i, j)),
            # the next tile's first chunk; past the last tile any chunk
            # does (every window that reads it is masked)
            pl.BlockSpec((RB, _LANES), lambda i, j: (
                i, jnp.minimum((j + 1) * _SP_CHUNKS, nhalo - 1)))],
        out_specs=[pl.BlockSpec((nw, RB, _LANES),
                                lambda i, j: (0, i, j))] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((nw, rows, max(nb, _LANES)), dt)
            for dt in (jnp.float32, jnp.int32)],
        scratch_shapes=[pltpu.VMEM((nw, _SP_CHUNKS, RB, _LANES),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="sp_boxcar",
    )(norm, norm)
    return tuple(a[..., :nb] for a in outs)


def sp_form(widths: tuple[int, ...], platform: str) -> str:
    """The form of the boxcar ladder in a program lowered for
    `platform`: "tiled" (the kernel) or "plain" (shifted slices; also
    where a width reaches past the kernel's halo)."""
    tiled = platform in _SP_TILED_PLATFORMS and max(widths) <= _LANES
    return "tiled" if tiled else "plain"


def sp_dispatch_attrs(rows: int, nsamp: int, widths: tuple[int, ...],
                      platform: str) -> dict:
    """What a boxcar_search program lowered for `platform` (that of the
    devices its operand lives on) runs for a (rows, nsamp) float32
    series, for the chunk's span (docs/operations.md): `sp_form`, by the
    rule boxcar_search branches on, and `sp_tile`, the kernel's tile (0
    for the plain form)."""
    form = sp_form(tuple(widths), platform)
    tile = (sp_boxcar_plan(rows, nsamp, tuple(widths)).tile
            if form == "tiled" else 0)
    return {"sp_form": form, "sp_tile": tile}


@partial(jax.jit, static_argnames=("widths", "topk"))
@scopes.scope("sp/boxcar")
def boxcar_search(norm_series: jnp.ndarray,
                  widths: tuple[int, ...] = DEFAULT_WIDTHS,
                  topk: int = DEFAULT_TOPK):
    """Matched-filter SNR for each boxcar width: direct float32 window
    sums (boxcar_chain), the tiled kernel on a TPU and shifted slices
    elsewhere, chosen per lowering (lax.platform_dependent, as
    fourier._stage_block_maxima), to the same bits.

    norm_series: (ndms, T), zero-mean unit-variance.
    Returns (snrs, times) each (nwidths, ndms, topk): top-k peak SNRs
    and their sample indices per width per DM.
    """
    widths = tuple(int(w) for w in widths)

    def plain(x):
        return _plain_block_maxima(x, widths)

    def tiled(x):
        return _ladder_block_maxima(x, widths, interpret=False)

    tiled_on = [plat for plat in _SP_TILED_PLATFORMS
                if norm_series.dtype == jnp.float32
                and sp_form(widths, plat) == "tiled"]
    bmax, barg = (jax.lax.platform_dependent(
        norm_series, default=plain, **dict.fromkeys(tiled_on, tiled))
        if tiled_on else plain(norm_series))
    # Hierarchical top-k: max per 32-sample block then top-k over
    # block maxima — the downstream dedup clusters events into the
    # same 32-sample buckets, so per-block maxima lose nothing,
    # and a full-width lax.top_k per width per DM was a large
    # fraction of the search wall-clock.
    # a width at a time: XLA's TPU top-k takes (rows, blocks), and
    # sorts a batch of such whole
    pairs = [fr._topk_blocks(bmax[wi], barg[wi], topk, BLOCK)
             for wi in range(len(widths))]
    return (jnp.stack([v for v, _ in pairs]),
            jnp.stack([i for _, i in pairs]))


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

    cluster = samp_f // BLOCK
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
