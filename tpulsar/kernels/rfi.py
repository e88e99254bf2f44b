"""RFI detection and masking on TPU.

Replaces PRESTO's rfifind (reference invocation:
lib/python/PALFA2_presto_search.py:482-485): the dynamic spectrum is
cut into (time-block, channel) cells; per-cell statistics (mean,
standard deviation, max Fourier power) are computed in one jitted
pass, robust z-scores flag outlier cells, and rows/columns whose bad
fraction exceeds a threshold are zapped entirely.  The result is an
RFIMask the dedispersion kernel consumes by replacing masked cells
with their channel's mean unmasked level.

The block length mirrors rfifind's `-time` parameter (reference
config: lib/python/config/searching_example.py rfifind_chunk_time).

Memory discipline: a full Mock beam is (960, 3.9M) samples — 3.8 GB
at uint8 and ~4x the chip's HBM once cast to float32 with a complex
spectrum alongside.  All whole-beam work here therefore (a) runs in
the pipeline's native channel-major (nchan, T) orientation so no
full-block transpose is ever materialized, (b) streams the float32
cast + per-cell rfft a few channels at a time through `lax.map`, and
(c) applies the mask as a fused elementwise select in the input's
dtype using a per-channel fill level precomputed at detection time.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpulsar.obs import trace


@dataclasses.dataclass
class RFIMask:
    """Mask over (nblocks, nchan) cells plus fully-zapped channels and
    time intervals. Serializable to .npz (the reference writes PRESTO's
    binary .mask; ours is an equivalent artifact)."""
    block_len: int
    dt: float
    cell_mask: np.ndarray        # (nblocks, nchan) bool — True = bad
    bad_channels: np.ndarray     # (nchan,) bool
    bad_blocks: np.ndarray       # (nblocks,) bool
    chan_fill: np.ndarray | None = None   # (nchan,) float32 — mean
    #                              unmasked level, the apply-time fill

    @property
    def masked_fraction(self) -> float:
        full = (self.cell_mask | self.bad_channels[None, :]
                | self.bad_blocks[:, None])
        # a degenerate observation can have zero cells; the fraction
        # must stay finite (NaN cannot round-trip the results DB)
        return float(full.mean()) if full.size else 0.0

    def full_mask(self) -> np.ndarray:
        return (self.cell_mask | self.bad_channels[None, :]
                | self.bad_blocks[:, None])

    def save(self, path: str, qscale=None, qoff=None) -> None:
        """qscale/qoff: the per-channel affine dequantization map of
        the uint8 block the mask was derived from (value = q * scale
        + off).  Persisted so a mask saved from a quantized run can be
        re-applied to calibrated float32 data later — chan_fill is in
        QUANTIZED units whenever they are present."""
        np.savez_compressed(
            path, block_len=self.block_len, dt=self.dt,
            cell_mask=self.cell_mask, bad_channels=self.bad_channels,
            bad_blocks=self.bad_blocks,
            chan_fill=(self.chan_fill if self.chan_fill is not None
                       else np.zeros(0, np.float32)),
            qscale=(np.asarray(qscale, np.float32) if qscale is not None
                    else np.zeros(0, np.float32)),
            qoff=(np.asarray(qoff, np.float32) if qoff is not None
                  else np.zeros(0, np.float32)))

    @classmethod
    def load(cls, path: str) -> "RFIMask":
        z = np.load(path)
        fill = z["chan_fill"] if "chan_fill" in z.files else None
        if fill is not None and fill.size == 0:
            fill = None
        return cls(block_len=int(z["block_len"]), dt=float(z["dt"]),
                   cell_mask=z["cell_mask"], bad_channels=z["bad_channels"],
                   bad_blocks=z["bad_blocks"], chan_fill=fill)

    @staticmethod
    def load_quantization(path: str):
        """(qscale, qoff) per-channel dequantization arrays saved with
        the mask, or None if the mask came from a float32 run."""
        z = np.load(path)
        if "qscale" not in z.files or z["qscale"].size == 0:
            return None
        return z["qscale"], z["qoff"]


@partial(jax.jit, static_argnames=("block_len", "chunk"))
def _cell_stats_chan(data: jnp.ndarray, block_len: int, chunk: int = 16):
    """(nchan, T) -> per-cell (mean, std, max FFT power), each
    (nblocks, nchan), streaming `chunk` channels at a time through the
    float32 cast and the per-cell rfft (a whole-beam float32 copy plus
    its complex spectrum is ~4x HBM at full Mock-beam scale)."""
    nchan, T = data.shape
    nblocks = T // block_len
    x = data[:, : nblocks * block_len].reshape(nchan, nblocks, block_len)
    chunk = min(chunk, nchan)
    n_outer = -(-nchan // chunk)
    pad = n_outer * chunk - nchan
    if pad:
        # zero-padded channels yield garbage stats rows that are
        # sliced off below; they never reach the mask
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    x = x.reshape(n_outer, chunk, nblocks, block_len)

    def one_chunk(c):
        c = c.astype(jnp.float32)
        mean = c.mean(axis=-1)
        var = c.var(axis=-1)
        spec = jnp.fft.rfft(c - mean[..., None], axis=-1)
        maxpow = (jnp.abs(spec[..., 1:]) ** 2).max(axis=-1) / jnp.maximum(
            block_len * var, 1e-9)
        return mean, jnp.sqrt(var), maxpow      # each (chunk, nblocks)

    mean, std, maxpow = jax.lax.map(one_chunk, x)
    return tuple(s.reshape(n_outer * chunk, nblocks)[:nchan].T
                 for s in (mean, std, maxpow))


@functools.lru_cache(maxsize=None)
def _cell_stats_shares(mesh, block_len: int):
    """`_cell_stats_chan` over a block laid over `mesh` by channels:
    each chip the cells of its own channels, no sample leaving it (the
    solo program's `lax.map` walks the CHANNEL axis, which the
    partitioner could serve only by gathering the block)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return jax.jit(shard_map(
        lambda share: _cell_stats_chan(share, block_len), mesh=mesh,
        in_specs=P("chan", None), out_specs=(P(None, "chan"),) * 3,
        check_vma=False))


def cell_stats(data: jnp.ndarray, block_len: int):
    """(T, nchan) row-major entry point -> (mean, std, maxpow), each
    (nblocks, nchan).  Small-array convenience; whole-beam callers use
    the channel-major path (`find_rfi_chan`) to avoid the transpose."""
    return _cell_stats_chan(jnp.asarray(data).T, block_len)


def _robust_z(x: np.ndarray, axis: int = 0) -> np.ndarray:
    """z-scores from median/MAD along an axis (outlier-resistant)."""
    med = np.median(x, axis=axis, keepdims=True)
    mad = np.median(np.abs(x - med), axis=axis, keepdims=True)
    return (x - med) / np.maximum(1.4826 * mad, 1e-9)


def find_rfi_chan(data, dt: float, block_len: int = 2048,
                  threshold: float = 4.0, chan_frac: float = 0.3,
                  block_frac: float = 0.3) -> RFIMask:
    """Compute an RFIMask from a channel-major (nchan, T) dynamic
    spectrum (the pipeline's native block orientation — no transpose
    is materialized on device).

    A cell is bad if any of its robust z-scores (mean / std / max
    Fourier power, each standardized per-channel across time) exceeds
    `threshold`.  Channels (blocks) with more than `chan_frac`
    (`block_frac`) bad cells are zapped entirely — the same
    recommended-channel/interval semantics as rfifind's mask.
    """
    # Observations shorter than one block still get (exactly) one
    # cell; without the clamp nblocks=0 and every downstream statistic
    # of the empty mask is NaN.
    block_len = min(block_len, int(data.shape[1]))
    from tpulsar.parallel import mesh as pmesh

    shares = pmesh.channel_mesh(data)
    if shares is None:
        mean, std, maxpow = _cell_stats_chan(jnp.asarray(data), block_len)
    else:
        # a beam laid over several chips by channels: each its own
        # cells; the (nblocks, nchan) statistics meet on the host
        mean, std, maxpow = _cell_stats_shares(shares, block_len)(data)
        trace.annotate("rfifind", shards=shares.size)
    mean, std, maxpow = (np.asarray(x) for x in (mean, std, maxpow))

    # Standardize each statistic both across time (catches bursts: a
    # block that deviates from its channel's history) and across
    # channels (catches persistent tones: a channel that deviates from
    # the band in every block).
    zs = np.stack([np.abs(_robust_z(s, axis=ax))
                   for s in (mean, std, maxpow) for ax in (0, 1)])
    cell_mask = (zs > threshold).any(axis=0)

    bad_channels = cell_mask.mean(axis=0) > chan_frac
    bad_blocks = cell_mask.mean(axis=1) > block_frac
    mask = RFIMask(block_len=block_len, dt=dt, cell_mask=cell_mask,
                   bad_channels=bad_channels, bad_blocks=bad_blocks)
    full = mask.full_mask()
    good = ~full
    denom = np.maximum(good.sum(axis=0), 1)
    mask.chan_fill = (np.where(good, mean, 0.0).sum(axis=0)
                      / denom).astype(np.float32)
    return mask


def find_rfi(data, dt: float, block_len: int = 2048,
             threshold: float = 4.0, chan_frac: float = 0.3,
             block_frac: float = 0.3) -> RFIMask:
    """Row-major (T, nchan) entry point (see find_rfi_chan)."""
    return find_rfi_chan(data.T, dt, block_len=block_len,
                         threshold=threshold, chan_frac=chan_frac,
                         block_frac=block_frac)


def mask_fill_or_default(mask: RFIMask) -> np.ndarray:
    """The mask's per-channel fill level; masks saved before the
    chan_fill field existed fall back to zeros (the pre-change
    apply_mask derived the level from the data — callers that still
    have the data can recompute via find_rfi_chan instead)."""
    if mask.chan_fill is not None:
        return mask.chan_fill
    return np.zeros(mask.cell_mask.shape[1], np.float32)


@jax.jit
def channel_major(block: jnp.ndarray) -> jnp.ndarray:
    """The beam block as the reader leaves it, (T, nchan), turned to
    the (nchan, T) every later layer works on, in its own dtype: the
    read-in sends the block as read and transposes it here, on the
    chip (the same copy on the host is a strided pass of ~0.15 GB/s).
    Peak HBM is the input plus the output for the length of the call.
    """
    return block.T


@partial(jax.jit, static_argnames=("block_len",))
def apply_mask_chan(data: jnp.ndarray, cell_mask: jnp.ndarray,
                    fill: jnp.ndarray, block_len: int) -> jnp.ndarray:
    """Replace masked cells of channel-major (nchan, T) data with the
    mask's per-channel fill level.

    One select a block of `block_len` samples, written in place into a
    copy of the input, in the input's dtype: peak HBM is the input plus
    the output and nothing else (uint8 beams stay uint8; nothing
    inflates to float32 and no transpose or index matrix is
    materialized).  ONE fused select over the block reshaped to
    (nchan, nblocks, block_len) costs a THIRD copy on a TPU: its
    compiler turns the block channel-minor for the mask's broadcast
    and back, 5.59 GiB of temporaries beside 2 x 5.59 at GBNCC's 120 s
    pointing, which a v5e cannot hold (PERF.md section 6, PR 48).
    Samples past the last whole block stay as they are.
    """
    nblocks = cell_mask.shape[0]
    if jnp.issubdtype(data.dtype, jnp.integer):
        fill = jnp.round(fill)
    fillv = fill.astype(data.dtype)[:, None]

    def one_block(b, out):
        cols = jax.lax.dynamic_slice_in_dim(out, b * block_len, block_len,
                                            axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(cell_mask[b][:, None], fillv, cols),
            b * block_len, axis=1)

    return jax.lax.fori_loop(0, nblocks, one_block, data)


@partial(jax.jit, static_argnames=("block_len", "chunk"))
def apply_mask(data: jnp.ndarray, cell_mask: jnp.ndarray,
               block_len: int, chunk: int = 64) -> jnp.ndarray:
    """Row-major (T, nchan) masking that derives the fill level from
    the data itself (mean of unmasked samples per channel, computed
    over streamed block means).  Small-array convenience; whole-beam
    callers use apply_mask_chan with the mask's precomputed fill.
    """
    T, nchan = data.shape
    nblocks = cell_mask.shape[0]
    usable = nblocks * block_len
    cells = data[:usable].reshape(nblocks, block_len, nchan)

    chunk = min(chunk, nblocks)
    n_outer = -(-nblocks // chunk)
    pad = n_outer * chunk - nblocks
    padded = jnp.pad(cells, ((0, pad), (0, 0), (0, 0))) if pad else cells
    cmeans = jax.lax.map(
        lambda c: c.astype(jnp.float32).mean(axis=1),
        padded.reshape(n_outer, chunk, block_len, nchan),
    ).reshape(n_outer * chunk, nchan)[:nblocks]

    good = ~cell_mask
    denom = jnp.maximum(good.sum(axis=0), 1)
    fill = (jnp.where(good, cmeans, 0.0).sum(axis=0) / denom)  # (nchan,)
    if jnp.issubdtype(data.dtype, jnp.integer):
        fill = jnp.round(fill)
    fill = fill.astype(data.dtype)
    filled = jnp.where(cell_mask[:, None, :], fill[None, None, :], cells)
    out = filled.reshape(usable, nchan)
    if usable < T:
        out = jnp.concatenate([out, data[usable:]], axis=0)
    return out
