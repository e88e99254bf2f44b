"""Distributed FFT: time-axis ("sequence") parallelism for series too
long for one chip.

The reference's analogous long-sequence machinery is disk streaming
(SURVEY.md section 5.7).  On TPU the equivalent is sharding the time
axis across the mesh and computing the FFT with the classic four-step
algorithm, with the inter-chip transpose expressed as an all_to_all
that XLA lowers onto ICI:

  x (length N = A*B, viewed as rows[a, b] = x[a*B+b], rows sharded)
    1. all_to_all transpose so each device holds all a for a b-chunk
    2. local FFT along a              -> F1[k1, b]
    3. twiddle exp(-2*pi*i*k1*b/N)
    4. all_to_all transpose back so each device holds all b for a
       k1-chunk
    5. local FFT along b              -> out[k1, k2] = X[k1 + A*k2]

The output is returned in (k1, k2) "transposed digit" order together
with an index map, which downstream power-spectrum consumers use
directly (candidate bins are mapped back to true frequencies on host —
no global re-sort is ever materialized).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


#: jitted program caches — a fresh closure per call would re-trace
#: the whole distributed program on EVERY invocation (the per-trial
#: loop in mesh.seq_dist_search calls these once per DM trial)
_FFT_FN_CACHE: dict = {}
_TAIL_FN_CACHE: dict = {}


def dist_fft(x: jnp.ndarray, mesh: Mesh, axis_name: str = "dm"):
    """FFT of a complex series sharded along its (single) axis.

    x: (N,) complex64, N = A*B with A divisible by the mesh axis size.
    Returns X_t of shape (B, A): X_t[b, a] = X[a*B + b] — the true
    spectrum in transposed-digit order, still sharded (B rows over the
    axis).
    """
    N = x.shape[0]
    key = (mesh, axis_name, N)
    if key not in _FFT_FN_CACHE:
        _FFT_FN_CACHE[key] = _build_fft_fn(mesh, axis_name, N)
    return _FFT_FN_CACHE[key](x.astype(jnp.complex64))


def _build_fft_fn(mesh: Mesh, axis_name: str, N: int):
    n_dev = mesh.shape[axis_name]
    A = _choose_A(N, n_dev)
    B = N // A
    A_loc, B_loc = A // n_dev, B // n_dev

    def body(x_shard):
        # x_shard: (N/n,) == A_loc contiguous rows of length B.
        rows = x_shard.reshape(A_loc, B)
        # --- transpose 1: (A_loc, B) -> (A, B_loc)
        t1 = rows.reshape(A_loc, n_dev, B_loc).transpose(1, 0, 2)
        t1 = jax.lax.all_to_all(t1, axis_name, 0, 0)   # (n, A_loc, B_loc)
        cols = t1.reshape(A, B_loc)                    # [a, b_loc]
        # --- FFT along a (the DFT over the slow digit must come first)
        f1 = jnp.fft.fft(cols, axis=0)                 # [k1, b_loc]
        # --- twiddle exp(-2 pi i k1 b / N)
        b_idx = (jax.lax.axis_index(axis_name) * B_loc
                 + jnp.arange(B_loc))
        k1 = jnp.arange(A)
        tw = jnp.exp(-2j * jnp.pi * (k1[:, None] * b_idx[None, :]) / N)
        g = (f1 * tw).astype(jnp.complex64)
        # --- transpose 2: (A, B_loc) -> (A_loc, B)
        t2 = g.reshape(n_dev, A_loc, B_loc)
        t2 = jax.lax.all_to_all(t2, axis_name, 0, 0)   # (n, A_loc, B_loc)
        full = t2.transpose(1, 0, 2).reshape(A_loc, B)  # [k1_loc, b]
        # --- FFT along b
        return jnp.fft.fft(full, axis=1)               # [k1_loc, k2]

    from jax import shard_map
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(axis_name),
                             out_specs=P(axis_name, None),
                             check_vma=False))


def _choose_A(N: int, n_dev: int) -> int:
    """Pick A ~ sqrt(N) with n_dev | A and n_dev | N//A."""
    A = int(np.sqrt(N))
    while A > n_dev:
        if N % A == 0 and A % n_dev == 0 and (N // A) % n_dev == 0:
            return A
        A -= 1
    return n_dev


def transposed_index_map(N: int, A: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side map between transposed-digit order and natural order:
    out[k1, k2] = X[k1 + A*k2].  Returns (to_natural, B) where
    to_natural[k1, k2] = k1 + A*k2."""
    B = N // A
    k1 = np.arange(A)[:, None]
    k2 = np.arange(B)[None, :]
    return k1 + A * k2, B


def dist_fft_natural(x: np.ndarray, mesh: Mesh, axis_name: str = "dm"
                     ) -> np.ndarray:
    """Convenience wrapper (host in/out, natural order) for tests and
    moderate sizes; production consumers keep transposed order."""
    N = len(x)
    n_dev = mesh.shape[axis_name]
    A = _choose_A(N, n_dev)
    Xt = np.asarray(dist_fft(jnp.asarray(x), mesh, axis_name))
    idx, B = transposed_index_map(N, A)
    out = np.empty(N, dtype=np.complex64)
    out[idx.ravel()] = Xt.ravel()
    return out


# ----------------------------------------------- distributed spectral search
#
# The production consumer (executor seq-shard spectral tail, gated on
# the per-trial series size): search ONE ultra-long real series whose
# padded complex spectrum does not fit a single device.  The series
# arrives time-sharded (seq_dedisperse output); the spectrum stays
# sharded in transposed-digit order end to end — only the top-k
# candidate bins ever leave the mesh.
#
# Whitening in transposed order: device d's rows k1 in
# [d*A_loc, (d+1)*A_loc) hold natural bins k = k1 + A*k2 — for every
# k2, a CONTIGUOUS run of A_loc bins, strided A apart.  Each device
# therefore sees an A_loc/A uniform sample of EVERY whitening block,
# so per-device block medians are an unbiased estimate of the global
# block medians (sample >= block_len/n_dev points; the estimate error
# is O(1/sqrt(sample)) of the local power scale).  This is
# deliberately NOT bit-identical to the single-device whitening —
# callers get a documented statistical tolerance instead of a 2x
# memory blow-up.  Harmonic summing is fundamental-only here: summing
# h*k across transposed shards is a residue permutation we have not
# needed yet (the gate only engages for series far beyond the survey
# workload; extend if such a survey materializes).


def dist_spectral_topk(x_sharded, mesh: Mesh, axis_name: str,
                       N: int, topk: int = 64, block: int = 1 << 15):
    """Top-k whitened power bins of a length-N complex series sharded
    over `axis_name` (natural contiguous shards, N = A*B as in
    dist_fft).

    Returns (powers[topk], bins[topk]) as numpy, bins in NATURAL
    frequency order, powers whitened to unit-mean noise.  Only the
    per-device top-k (a few hundred bytes) crosses the mesh at the
    end.
    """
    Xt = dist_fft(x_sharded, mesh, axis_name)   # (A, B) sharded rows
    key = (mesh, axis_name, N, topk, block)
    if key not in _TAIL_FN_CACHE:
        _TAIL_FN_CACHE[key] = _build_tail_fn(mesh, axis_name, N, topk,
                                             block)
    vals, bins = _TAIL_FN_CACHE[key](Xt)
    return np.asarray(vals), np.asarray(bins)


def _build_tail_fn(mesh: Mesh, axis_name: str, N: int, topk: int,
                   block: int):
    n_dev = mesh.shape[axis_name]
    A = _choose_A(N, n_dev)
    B = N // A
    A_loc = A // n_dev

    def tail(xt_shard):
        # xt_shard: (A_loc, B) rows k1 -> natural bins k1 + A*k2
        pw = jnp.abs(xt_shard) ** 2
        # distributed whitening: block medians over the LOCAL comb
        # sample of each natural-frequency block.  Natural bin of
        # column k2 is k1 + A*k2 ~ A*k2: block index = A*k2 // block,
        # identical for all local rows — group columns.
        cols_per_block = min(max(1, block // A), B)
        nblk = max(1, B // cols_per_block)
        usable = nblk * cols_per_block
        med = jnp.median(
            pw[:, :usable].reshape(A_loc, nblk, cols_per_block),
            axis=(0, 2))                         # (nblk,)
        med = jnp.maximum(med, 1e-30) / jnp.log(2.0)  # median -> mean
        scale = jnp.repeat(med, cols_per_block, total_repeat_length=usable)
        scale = jnp.concatenate(
            [scale, jnp.full((B - usable,), med[-1])])
        white = pw / scale[None, :]
        # real input: keep only the non-mirrored half (bin k and N-k
        # carry equal power), and never report DC
        d0 = jax.lax.axis_index(axis_name)
        k1_col = d0 * A_loc + jnp.arange(A_loc)[:, None]
        nat_grid = k1_col + A * jnp.arange(B)[None, :]
        white = jnp.where((nat_grid >= 1) & (nat_grid <= N // 2),
                          white, 0.0)
        # local top-k over the flattened shard
        flat = white.reshape(-1)
        vals, idx = jax.lax.top_k(flat, topk)
        # natural bin: k1 = d*A_loc + idx//B (row), k2 = idx % B
        k1 = d0 * A_loc + idx // B
        k2 = idx % B
        nat = k1 + A * k2
        # gather every device's top-k, reduce to the global top-k
        all_vals = jax.lax.all_gather(vals, axis_name)   # (n, topk)
        all_nat = jax.lax.all_gather(nat, axis_name)
        gvals, gidx = jax.lax.top_k(all_vals.reshape(-1), topk)
        return gvals, all_nat.reshape(-1)[gidx]

    from jax import shard_map
    return jax.jit(shard_map(tail, mesh=mesh,
                             in_specs=P(axis_name, None),
                             out_specs=(P(), P()), check_vma=False))


def spectral_bytes_per_trial(nfft: int) -> int:
    """Peak per-device bytes for ONE trial's single-device spectral
    tail (complex spectrum + powers + whitened copy) — the gate
    quantity for switching to the distributed tail (same bookkeeping
    style as executor._budget_dm_chunk)."""
    nbins = nfft // 2 + 1
    return 8 * nbins + 4 * nbins + 4 * nbins + 4 * nfft
