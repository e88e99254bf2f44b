"""Every hh-th column of a contiguous tile, on the MXU.

A harmonic sum reads P[hh * r]: along the lanes that is a strided
gather, which a TPU runs at ~1 ns an element (PERF.md, PR 26 and PR
39).  Both harmonic-sum kernels (accel._harmsum_zmax over a (z, r)
plane, fourier._lo_block_maxima over DM rows) instead tile the OUTPUT
over r: for the output tile [j*T, (j+1)*T) harmonic hh needs the
CONTIGUOUS source columns [hh*j*T, hh*(j+1)*T), block j of width hh*T
of the same array, and takes every hh-th of them while the block is in
VMEM, by a 0/1 selection matrix: for each group of 128 output columns

    x[:, g*hh*128:(g+1)*hh*128] @ S_hh,    S_hh[c, k] = (c == hh*k)

with float32 accumulation.  One product by 1.0 and zeros per output:
exact for bfloat16, and for float32 at Precision.HIGHEST (six bf16
passes carry all 24 bits of x * 1.0).

The source must be finite where it is read: the product multiplies
every source column of a 128-column output group by 0 or 1, so one inf
or NaN turns its row NaN over the whole group.  Columns past the
array's end (whatever the DMA left there) are zeroed first.

This module is the one definition of that decimation; the kernels
differ in what they do with the decimated tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128           # output columns per selection matmul


class StagePlan:
    """What both kernels' plans share: `stages` (increasing from 1,
    each continuing the one before) and `ntiles` (grid steps that
    write each)."""
    stages: tuple[int, ...]
    ntiles: tuple[int, ...]

    @property
    def numharm(self) -> int:
        return self.stages[-1]

    def stage_of(self, hh: int) -> int:
        """Index of the stage whose sum harmonic hh first enters."""
        return next(i for i, h in enumerate(self.stages) if h >= hh)


def check_stages(stages: tuple[int, ...], ncols: int,
                 what: str) -> tuple[int, ...]:
    """The stages the array has a column for (ncols // h > 0); they
    must start at 1 and increase, or the incremental sum is not
    defined."""
    kept = tuple(h for h in stages if ncols // h > 0)
    if not kept or kept[0] != 1 or any(
            b <= a for a, b in zip(kept, kept[1:])):
        raise ValueError(
            f"{what}: stages {tuple(stages)} must start at 1 and "
            f"increase, over an array with a column (ncols={ncols})")
    return kept


def sel_row(hh: int) -> int:
    """First row of S_hh in the scratch that stacks S_2 .. S_H, each
    (hh * 128, 128); sel_row(H + 1) is the scratch's height."""
    return LANES * (hh * (hh - 1) // 2 - 1)


def sel_bytes(numharm: int, itemsize: int) -> int:
    return max(sel_row(numharm + 1), 8) * LANES * itemsize


def write_selection(sel_ref, numharm: int, dtype) -> None:
    """Fill the scratch with S_2 .. S_numharm (once a kernel call)."""
    for hh in range(2, numharm + 1):
        shape = (hh * LANES, LANES)
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        sel_ref[sel_row(hh):sel_row(hh + 1), :] = (
            c == hh * k).astype(jnp.float32).astype(dtype)


def stack_groups(x, groups: int, width: int):
    """The `groups` column groups of x, each `width` wide, stacked on
    the rows (x's rows are whole tiles: no data moves), so that one
    stationary matrix serves them all."""
    if groups == 1:
        return x
    return jnp.concatenate(
        [x[:, g * width:(g + 1) * width] for g in range(groups)], axis=0)


def decimated_tile(x_ref, sel_ref, hh: int, groups: int, limit):
    """Columns 0, hh, 2*hh, ... of the block in x_ref (rows,
    groups * hh * 128), as (groups * rows, 128) float32 with the
    column groups stacked on the rows.  `limit` (traced) is how many
    of the block's columns lie inside the array: columns at and past
    it are zeroed first (0 x NaN would reach real columns through the
    matmul).  A plain select over every block: only a harmonic's last
    block is ragged, but a lax.cond that hands the block on as a value
    cost the lo kernel 90 ms of a 32 ms call, and zeroing the ragged
    block in its buffer under pl.when 3-6% more than the select in
    both kernels (PERF.md, PR 39)."""
    x = x_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < limit, x, jnp.zeros_like(x))
    precision = (jax.lax.Precision.HIGHEST
                 if x.dtype == jnp.float32 else None)
    return jnp.dot(stack_groups(x, groups, hh * LANES),
                   sel_ref[sel_row(hh):sel_row(hh + 1), :],
                   preferred_element_type=jnp.float32,
                   precision=precision)
