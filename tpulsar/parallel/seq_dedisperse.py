"""Sequence-parallel dedispersion: the time axis sharded across chips
with a ring halo exchange.

The reference never needed this — PRESTO streams passes through disk
(SURVEY.md 5.7) — but a TPU search wants the whole filterbank block
resident, and a long observation (or a small-HBM chip) can exceed one
device.  This module shards the *time* axis of the subband array over
a mesh axis, in the same spirit as ring attention: each device owns a
contiguous time chunk plus a halo of `max_shift` samples received from
its right neighbour over ICI (`lax.ppermute`), which is exactly the
window the dispersion shift-gather reads past its chunk end.

out[d, t] = sum_s subb[s, min(t + shift[d, s], T-1)]

matches kernels/dedisperse.dedisperse_subbands bit-for-bit; the last
device's halo replicates its final sample (edge clamp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def halo_extend(subb_loc: jnp.ndarray, S: int, axis_name: str,
                n_dev: int) -> jnp.ndarray:
    """Extend a per-device (nsub, chunk) time shard with an S-sample
    halo: the first S columns of the RIGHT neighbour over a ring
    ppermute; the last device clamps by replicating its final sample
    (matching the single-device edge semantics).  Shared by the
    standalone seq_dedisperse and the production sharded pass."""
    nsub = subb_loc.shape[0]
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, i - 1) for i in range(1, n_dev)]
    halo = jax.lax.ppermute(subb_loc[:, :S], axis_name, perm)
    edge = jnp.broadcast_to(subb_loc[:, -1:], (nsub, S))
    halo = jnp.where(idx == n_dev - 1, edge, halo.astype(subb_loc.dtype))
    return jnp.concatenate([subb_loc, halo], axis=1)   # (nsub, chunk+S)


def seq_dedisperse(subbands, sub_shifts: np.ndarray, mesh: Mesh,
                   axis_name: str = "dm", max_shift: int | None = None):
    """(nsub, T) time-sharded over `axis_name` + (ndms, nsub) shifts
    -> (ndms, T) DM series, time-sharded the same way.

    T must divide the axis size; every shift must be <= max_shift and
    max_shift <= T // axis_size (the halo is one neighbour deep).
    """
    shifts_np = np.asarray(sub_shifts, np.int32)
    n_dev = mesh.shape[axis_name]
    nsub, T = subbands.shape
    if T % n_dev:
        raise ValueError(f"T={T} not divisible by {n_dev} devices")
    chunk = T // n_dev
    actual_max = int(shifts_np.max(initial=0))
    S = actual_max if max_shift is None else max_shift
    if actual_max > S:
        raise ValueError(
            f"shift table max {actual_max} exceeds max_shift={S}")
    if S > chunk:
        raise ValueError(
            f"max shift {S} exceeds per-device chunk {chunk}; "
            f"use fewer devices or a deeper halo")

    def body(subb_loc, shifts):
        # subb_loc: (nsub, chunk) — this device's time chunk
        from tpulsar.kernels.dedisperse import dedisperse_window_scan

        ext = halo_extend(subb_loc, S, axis_name, n_dev)
        return dedisperse_window_scan(ext, shifts, chunk)  # (ndms, chunk)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis_name), P(None, None)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    return jax.jit(fn)(subbands, jnp.asarray(shifts_np))
