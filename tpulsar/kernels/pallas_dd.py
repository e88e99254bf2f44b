"""Pallas TPU kernels for incoherent dedispersion.

Stage 2 replaces the XLA gather formulation of `dedisperse_subbands`
(tpulsar/kernels/dedisperse.py) on TPU.  The reference's equivalent
native component is PRESTO's `prepsubband` C program (invoked at
lib/python/PALFA2_presto_search.py:514-529), which re-reads the
subband file once per DM pass; the XLA gather likewise re-reads the
(nsub, T) array once per DM trial.

The kernel stages each time block in VMEM *once* and accumulates
every DM trial's shifted sum out of that tile, so HBM input traffic
drops from ndms*nsub*T to nsub*T per pass (~76x for the survey plan),
and it works on full (8, 128) vector registers: a grid step handles 8
consecutive time segments of `seg` samples, one on each sublane, so
one scalar shift moves all 8 at once (`_kernel_dd`).  The integer
shift table rides in SMEM via scalar prefetch.

Semantics match the gather version exactly:
    out[d, t] = sum_s subb[s, min(t + shift[d, s], T-1)]
summed in subband order from zero in float32 (the edge clamp is
applied where a segment's overhang is put beside it in VMEM).

Stage 1 (`_kernel_sb`) is the same form one level down, over the
channels of each subband (`prepsubband -sub`; it replaces the XLA
`lax.map` of `dedisperse._form_subbands_jit`, which serializes the
subbands):
    out[b, t] = sum_c data[b*cps + c, min(t + shift[b, c], T-1)]
summed in channel order from zero in float32, the beam read slab by
slab in its own dtype from a segment layout (`_segment_slab`).
"""

from __future__ import annotations

import functools
import os
import typing

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulsar.obs import trace


#: registers of the stage-2 accumulator: a wider segment is summed in
#: column pieces of this many 128-lane chunks
_ACC_CHUNKS = 16


def _shifted(slab, row, sh, c0, pc, lane):
    """slab[row] at lanes sh + 128 * c0 : ... + 128 * pc, as (pc, 8,
    128) registers: the chunks from sh // 128 on (a dynamic index on
    the untiled axis), every register's lanes rotated by the rest in
    one operation, a select between neighbours.  lane: the (8, 128)
    lane iota.  Both kernels' shifted read."""
    rest = sh & 127
    win = slab[row, pl.ds((sh >> 7) + c0, pc + 1)]
    rot = pltpu.roll(win, (128 - rest) & 127, 2)
    return jnp.where((lane < 128 - rest)[None], rot[:-1], rot[1:])


def _kernel_dd(shift_ref, seg_ref, nxt_ref, edge_ref, out_ref, slab, *,
               nsub, group, rows, seg, window, n_seg, unroll):
    """One grid step (i, g): 8 consecutive time segments of `seg`
    samples, one on each sublane, for every row of the call, summed
    over the g-th group of `group` subbands.

    seg_ref: the (group, 8, seg) block i of the segment-layout
    subbands; nxt_ref: the first min(window - seg, seg) lanes of block
    i+1; edge_ref: (group, 8, 128), each subband's last sample.  First
    each subband's slab[s] is put together in VMEM as window / 128
    registers (chunk, 8, 128): sublane j holds segment 8i+j followed
    by its overhang, the start of segments 8i+j+1, ... (a sublane
    rotate of the two blocks), or the subband's last sample past the
    end of the series (the edge clamp).  Then
        out[d] += sum_s slab[s] at lanes shift[d,s] : shift[d,s] + seg
    in subband order, from zero at the first group (the output block
    stays in VMEM across the groups, so the float32 additions are the
    sequential sum's whatever the grouping).  The shifted read takes
    the chunks from shift // 128 on (a dynamic index on the untiled
    axis), rotates every register's lanes by the rest in one
    operation and selects between neighbours: its cost does not
    depend on the overhang, and a subband is a handful of operations
    to trace and lower however wide the segment.  `unroll` subbands go
    into one loop iteration so that their rotates overlap."""
    nc = seg // 128                     # chunks of a segment
    pc = min(nc, _ACC_CHUNKS)           # ... of a column piece
    i, g = pl.program_id(0), pl.program_id(1)
    first = g * group       # this group's first subband

    def fill(s, _):
        a = seg_ref[s]
        b = nxt_ref[s]
        for k in range(nc):
            slab[s, k] = a[:, k * 128:(k + 1) * 128]
        for m in range(1, -(-(window - seg) // seg) + 1):
            w = min(seg, window - m * seg)
            sub = jax.lax.broadcasted_iota(jnp.int32, (8, w), 0)
            # roll's amount must not be negative: 8 - m is -m (mod 8)
            nxt = jnp.where(sub < 8 - m,
                            pltpu.roll(a[:, :w], 8 - m, 0),
                            pltpu.roll(b[:, :w], 8 - m, 0))
            edge = jnp.concatenate([edge_ref[s]] * (w // 128), axis=1)
            nxt = jnp.where(8 * i + sub + m >= n_seg, edge, nxt)
            for k in range(w // 128):
                slab[s, m * nc + k] = nxt[:, k * 128:(k + 1) * 128]
        return 0

    jax.lax.fori_loop(0, group, fill, 0)

    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def dm_body(d, _):
        for c0 in range(0, nc, pc):
            def sb_body(it, acc):
                def one(u, acc):
                    s = it * unroll + u
                    return acc + _shifted(slab, s, shift_ref[d, first + s],
                                          c0, pc, lane)

                # traced once, unrolled when the kernel is lowered
                return jax.lax.fori_loop(0, unroll, one, acc, unroll=True)

            def cols(k):
                return slice((c0 + k) * 128, (c0 + k + 1) * 128)

            acc0 = jnp.zeros((pc, 8, 128), jnp.float32)
            if group < nsub:    # later groups add to the block
                acc0 = jnp.where(g == 0, acc0, jnp.stack(
                    [out_ref[d, :, cols(k)] for k in range(pc)]))
            acc = jax.lax.fori_loop(0, group // unroll, sb_body, acc0)
            for k in range(pc):
                out_ref[d, :, cols(k)] = acc[k]
        return 0

    jax.lax.fori_loop(0, rows, dm_body, 0)


def _kernel_sb(shift_ref, seg_ref, nxt_ref, tail_ref, out_ref, slab, *,
               group, cps, seg, lanes, unroll):
    """Stage-1 subband formation, one grid step (i, g): 8 consecutive
    time segments of `seg` samples, one on each sublane, for every
    channel of the g-th group of `group` whole subbands:
        out[b] = sum_c slab[c] at lanes sh[b,c] : sh[b,c] + seg
    in channel order from zero in float32, so one scalar shift, one
    lane rotate and one add move eight segments.

    seg_ref: the (group * cps, 8, seg) block i of the segment layout
    `_segment_slab` writes; nxt_ref: the first min(lanes - seg, seg)
    lanes of block i+1; tail_ref: the same lanes of what follows the
    slab's last block (the next slab's start, or the edge padding).  A
    subband at a time, each of its `cps` channels' slab[c] is put
    together in VMEM as lanes / 128 float32 registers (chunk, 8, 128):
    sublane j holds segment 8i+j followed by its overhang, the start of
    segments 8i+j+1, ... (a sublane rotate of the two blocks, as
    `_kernel_dd`'s) — a slab a SUBBAND, which the next subband
    overwrites, because a staged channel is read once and not once per
    DM row.  8-bit beams are staged as they are and widened here
    through int32 (Mosaic has no 8-bit -> f32 cast).  The shifted read
    is stage 2's (`_shifted`).  `unroll` channels go
    into one loop iteration so that their rotates overlap; the blocks
    are pipelined, the next step's DMA running under this step's sums.
    A subband's sum never crosses a group, so the grouping changes no
    addition.  Reference native component: the subband pass of
    `prepsubband -sub` (PALFA2_presto_search.py:506-511)."""
    nc = seg // 128                     # chunks of a segment
    pc = min(nc, _ACC_CHUNKS)           # ... of a column piece
    i = pl.program_id(0)
    first = pl.program_id(1) * group    # this group's first subband
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def widen(x):
        if x.dtype.itemsize == 1:
            x = x.astype(jnp.int32)
        return x.astype(jnp.float32)

    def fill(b, c):
        ch = b * cps + c
        for k in range(nc):
            slab[c, k] = widen(seg_ref[ch, :, k * 128:(k + 1) * 128])
        for m in range(1, -(-(lanes - seg) // seg) + 1):
            w = min(seg, lanes - m * seg)
            sub = jax.lax.broadcasted_iota(jnp.int32, (8, w), 0)
            nxt = jnp.where(i == pl.num_programs(0) - 1,
                            widen(tail_ref[ch, :, :w]),
                            widen(nxt_ref[ch, :, :w]))
            # roll's amount must not be negative: 8 - m is -m (mod 8)
            nxt = jnp.where(
                sub < 8 - m,
                pltpu.roll(widen(seg_ref[ch, :, :w]), 8 - m, 0),
                pltpu.roll(nxt, 8 - m, 0))
            for k in range(w // 128):
                slab[c, m * nc + k] = nxt[:, k * 128:(k + 1) * 128]

    def channels(body, init):
        def step(it, carry):
            # traced once, unrolled when the kernel is lowered
            return jax.lax.fori_loop(
                0, unroll, lambda u, x: body(it * unroll + u, x), carry,
                unroll=True)

        return jax.lax.fori_loop(0, cps // unroll, step, init)

    def sb_body(b, _):
        channels(lambda c, _: fill(b, c), None)
        for c0 in range(0, nc, pc):
            acc = channels(
                lambda c, acc: acc + _shifted(
                    slab, c, shift_ref[first + b, c], c0, pc, lane),
                jnp.zeros((pc, 8, 128), jnp.float32))
            for k in range(pc):
                out_ref[b, :, (c0 + k) * 128:(c0 + k + 1) * 128] = acc[k]
        return 0

    jax.lax.fori_loop(0, group, sb_body, 0)


@functools.partial(jax.jit, static_argnames=("seg",))
def _segment_layout(subbands: jnp.ndarray, seg: int):
    """(nsub, T) -> the (nsub, n_seg, seg) segment layout stage 2
    reads (n_seg a multiple of 8: one grid step's 8 sublanes), and
    each subband's last sample as (nsub, 8, 128).  One relayout copy
    in HBM where T is a multiple of 8 * seg (every Mock length and the
    WAPP's 2^22), a pad before it elsewhere."""
    nsub, T = subbands.shape
    edge = jnp.broadcast_to(subbands[:, -1][:, None, None],
                            (nsub, 8, 128))
    pad = -T % (8 * seg)        # to Stage2Plan.n_seg segments
    if pad:
        subbands = jnp.pad(subbands, ((0, 0), (0, pad)), mode="edge")
    return subbands.reshape(nsub, -1, seg), edge


@functools.partial(jax.jit,
                   static_argnames=("window", "group", "unroll",
                                    "vmem_bytes", "interpret"))
def _dedisperse_chunk(segs: jnp.ndarray, edge: jnp.ndarray,
                      shifts: jnp.ndarray, window: int, group: int,
                      unroll: int, vmem_bytes: int,
                      interpret: bool) -> jnp.ndarray:
    """segs, edge: `_segment_layout`'s.  shifts: (rows, nsub) int32,
    all in [0, window - seg - 128].  group: subbands in VMEM at a time
    (a divisor of nsub; nsub itself wherever they fit).  Returns
    (rows, n_seg * seg) f32 (the wrapper cuts it to T)."""
    nsub, n_seg, seg = segs.shape
    rows = shifts.shape[0]
    n_blocks = n_seg // 8

    def block(index_map, width=seg):
        return pl.BlockSpec((group, 8, width), index_map,
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks, nsub // group),
        # the same array twice: a step's 8 segments, and of the 8
        # after them the heads that are the step's overhang (whole
        # segments only where the overhang is longer than one), both
        # pipelined
        in_specs=[block(lambda i, g, s_ref: (g, i, 0)),
                  block(lambda i, g, s_ref: (
                      g, jnp.minimum(i + 1, n_blocks - 1), 0),
                      min(window - seg, seg)),
                  block(lambda i, g, s_ref: (g, 0, 0), 128)],
        # the same block for every group: it is summed in VMEM
        out_specs=pl.BlockSpec((rows, 8, seg),
                               lambda i, g, s_ref: (0, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((group, window // 128, 8, 128),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_dd, nsub=nsub, group=group, rows=rows,
                          seg=seg, window=window, n_seg=n_seg,
                          unroll=unroll),
        out_shape=jax.ShapeDtypeStruct((rows, n_seg, seg), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(shifts, segs, segs, edge)
    # not free: XLA relays the (n_seg, seg) tiles out to (rows, T) rows
    return out.reshape(rows, n_seg * seg)


# --- wrapper geometry ------------------------------------------------
# Static shapes of the kernel calls as functions of the pass, shared
# by the wrappers below and the AOT gate's shape-builders
# (tpulsar/aot/registry.py), so the gate compiles what the wrappers
# dispatch.

def stage_overhang(smax: int) -> int:
    """Staging overhang for a maximum shift: rounded up to a power of
    two (>= 256) so (block, window) signatures are shared across
    passes with similar max shifts."""
    return max(256, 1 << int(np.ceil(np.log2(max(smax, 1)))))


#: most rows of one stage-2 program call: bounds the SMEM shift
#: table and the VMEM output block
STAGE2_MAX_ROWS = 32

#: what stage 2 may ask of VMEM: half of a v5e's 128 MiB
STAGE2_VMEM_BUDGET = 64 << 20


class Stage2Plan(typing.NamedTuple):
    """Geometry of the stage-2 calls for one chunk of DM rows."""
    seg: int         # samples of one time segment (a sublane's share)
    n_seg: int       # segments of the padded series, a multiple of 8
    window: int      # lanes of a segment with its overhang
    group: int       # subbands in VMEM at a time (divides nsub)
    unroll: int      # subbands per loop iteration (divides group)
    calls: int       # program calls for the chunk
    rows: int        # rows of a call (the last takes what is left)
    vmem_bytes: int  # scoped-VMEM request of a call

    def call_rows(self, n: int) -> list[int]:
        """Rows of each call for a chunk of n rows: never padded."""
        return [min(self.rows, n - c0) for c0 in range(0, n, self.rows)]

    def kernel_args(self) -> dict:
        """`_dedisperse_chunk`'s static arguments but `interpret`."""
        return dict(window=self.window, group=self.group,
                    unroll=self.unroll, vmem_bytes=self.vmem_bytes)


def stage2_plan(nsub: int, S: int, rows: int, T: int) -> Stage2Plan:
    """Everything static about stage 2 for `rows` DM rows of (nsub, T)
    subbands with overhang S, from the shapes alone.

    Rows: ceil(rows / 32) calls of ceil(rows / calls) rows, never a
    call padded up (38 -> 2 x 19; 76 -> 26 + 26 + 24; 1 -> 1 x 1).
    Segment: 2048 samples where the series is long enough to fill 8 of
    them (measured on a v5e at Mock ds=1: 25.7 ms a 19-row call
    against 30.5 at 1024 and 41.6 at 512), and never so short that an
    overhang spans more than 7 segments (the 8 sublanes of the next
    block are all the kernel has): 4096 at S 16384, 8192 at 32768.
    Group: all subbands where their tile fits the VMEM budget (every
    pass of the Mock and WAPP plans), else the largest divisor of nsub
    that does (the GBNCC plan's 128 subbands in every pass past DM
    0.3: 64 at an overhang of 8192, 32 at 16384; a fold's series at an
    overhang of 16384 and more, where the XLA scan takes 22.4 ms for
    that one row whatever the overhang, this kernel 9.8-10.0).
    Unroll: the most subbands per loop iteration
    that divide the group, up to 32 (same call: 55.5 ms at 1, 29.9 at
    4, 25.7 at 8, 22.5 at 32, 22.0 at all 96: the rotates of one
    subband wait for nothing of the next); they are unrolled when the
    kernel is lowered, not when it is traced, which is what a
    process pays for each program on its first call."""
    calls = -(-rows // STAGE2_MAX_ROWS)
    per_call = -(-rows // calls)
    over = S + 128      # a 128-aligned start and one vreg for the rest
    seg = 2048
    while seg > 128 and 8 * seg > T:
        seg //= 2
    while -(-over // seg) > 7:
        seg *= 2

    def vmem(group):
        # both input blocks and the output block double-buffered, the
        # slab and the edge once, and room for Mosaic's own scratch
        words = (2 * group * 8 * (seg + min(over, seg))
                 + group * 8 * (seg + over)
                 + 2 * per_call * 8 * seg + 2 * group * 8 * 128)
        return 4 * words + (4 << 20)

    divisors = [g for g in range(nsub, 0, -1) if nsub % g == 0]
    group = next((g for g in divisors
                  if vmem(g) <= STAGE2_VMEM_BUDGET), 1)
    unroll = max(u for u in range(1, 33) if group % u == 0)
    return Stage2Plan(seg=seg, n_seg=-(-T // (8 * seg)) * 8,
                      window=seg + over, group=group, unroll=unroll,
                      calls=calls, rows=per_call,
                      vmem_bytes=max(16 << 20, vmem(group)))


#: what a stage-1 step may ask of VMEM, as stage 2: half of a v5e's
#: 128 MiB
STAGE1_VMEM_BUDGET = 64 << 20

#: samples of a stage-1 time segment (a sublane's share of a grid
#: step), longest first
_STAGE1_SEGS = (4096, 2048, 1024, 512, 256, 128)


class Stage1Plan(typing.NamedTuple):
    """Geometry of the stage-1 calls of one pass."""
    block_t: int     # output samples of a grid step: 8 segments
    window: int      # block_t + overhang S
    group: int       # subbands staged at a time (divides nsub)
    vmem_bytes: int  # scoped-VMEM request of a call

    @property
    def seg(self) -> int:
        """Samples of a time segment: one sublane's share of a step."""
        return self.block_t // 8

    @property
    def lanes(self) -> int:
        """Lanes of a segment's slab: itself, its overhang S, and one
        register for the rest of a shift that is no multiple of 128."""
        return self.seg + self.window - self.block_t + 128

    @property
    def head(self) -> int:
        """Lanes a step reads of the block after it: its overhang, or
        whole segments where the overhang is longer than one."""
        return min(self.lanes - self.seg, self.seg)

    def kernel_args(self) -> dict:
        """`_form_subbands_block`'s static arguments but `nsub` and
        `interpret`."""
        return self._asdict()


def stage1_plan(nchan: int, nsub: int, S: int, itemsize: int) -> Stage1Plan:
    """Everything static about stage 1 for a (nchan, T) block of
    `itemsize`-byte samples and overhang S, from the shapes alone.

    A grid step sums 8 time segments of `seg` samples for `group` whole
    subbands (block_t = 8 * seg).  In VMEM it holds, twice each (the
    pipeline's two buffers), the staged block of its channels, the head
    of the block after it, the slab's tail and the float32 output
    block, and one subband's float32 slab (`_kernel_sb`): they must fit
    STAGE1_VMEM_BUDGET.  A segment's overhang is put beside it once a
    channel, so the longer the segment the less of a step goes into
    that: the longest segment at which the smallest group fits, and
    never one so short that an overhang spans more than 7 segments
    (the 8 sublanes of the next block are all the kernel has): 4096
    samples at every overhang the Mock, WAPP and GBNCC plans reach (S
    256 is 9% of it, S 2048 53%), 8192 at S 32768.  Then the largest
    group that fits beside it: all 64 subbands of WAPP's 256 uint8
    channels, 48 of Mock's 96, 16 of GBNCC's 128.  A group is at least
    8 subbands (all, of fewer): a step's sums then hide what a step
    costs to start.  Where nothing fits, the smallest group at the
    shortest segment, with the scoped VMEM it needs stated."""
    cps = nchan // nsub
    over = S + 128      # a 128-aligned start and one vreg for the rest

    def step_bytes(group, seg):
        return (2 * itemsize * group * cps * 8 * (seg + 2 * min(over, seg))
                + 2 * 4 * group * 8 * seg + 4 * cps * 8 * (seg + over))

    def fits(group, seg):
        return step_bytes(group, seg) <= STAGE1_VMEM_BUDGET

    groups = [g for g in range(nsub, 0, -1)
              if nsub % g == 0 and g >= min(8, nsub)]
    short = _STAGE1_SEGS[-1]
    while -(-over // short) > 7:
        short *= 2
    segs = [s for s in _STAGE1_SEGS if s > short] + [short]
    seg = next((s for s in segs if fits(groups[-1], s)), short)
    group = next((g for g in groups if fits(g, seg)), groups[-1])
    return Stage1Plan(block_t=8 * seg, window=8 * seg + S, group=group,
                      vmem_bytes=max(16 << 20,
                                     step_bytes(group, seg) + (4 << 20)))


class Stage1Slab(typing.NamedTuple):
    """One time slab of the stage-1 sweep, as `_segment_slab` and
    `_form_subbands_block` take it."""
    t0: int                 # first output column
    cols: int               # output columns [t0, t0 + cols)
    n_blocks: int           # grid steps over time: ceil(cols / block_t)
    body: tuple[int, int]   # beam columns [a, b) under its blocks
    rest: tuple[int, int]   # ... after them: the overhang's, or the
    #                         beam's last sample where it ends before


def stage1_slabs(T: int, nchan: int, itemsize: int, block_t: int,
                 S: int, slab_bytes: int = 1_000_000_000
                 ) -> list[Stage1Slab]:
    """Time slabs of the stage-1 sweep: a slab's output columns need
    the beam's columns as far as its last block's overhang S reaches,
    the next slab's start among them; only the final slab meets the
    beam's end, and is edge-padded (`_segment_slab`).  Slabbing keeps
    the staged copy of a beam from ever being a whole-beam allocation
    (3.8 GB at full survey scale, beside the beam itself).  The budget
    is in the block's own dtype: the segment layout holds what the
    block holds."""
    slab_elems = slab_bytes // (itemsize * nchan)
    slab_t = max(block_t, (slab_elems // block_t) * block_t)
    out = []
    for t0 in range(0, T, slab_t):
        cols = min(slab_t, T - t0)
        n_blocks = -(-cols // block_t)
        end = min(t0 + n_blocks * block_t, T)
        stop = min(end + S, T)
        out.append(Stage1Slab(t0, cols, n_blocks, (t0, end),
                              (min(end, stop - 1), stop)))
    return out


def dedisperse_subbands_pallas(subbands, sub_shifts,
                               interpret: bool | None = None):
    """(nsub, T) + (ndms, nsub) int32 -> (ndms, T) f32.

    The rows go through `stage2_plan`'s calls: at most 32 a call (the
    SMEM shift table and the VMEM output block are bounded by it) and
    only the rows there are, so a chunk costs what its rows cost
    (the executor's 38-row chunk runs as 19 + 19, a fold's one series
    as one row).  The subbands are put into the segment layout once
    for all of them."""
    interpret = _resolve_interpret(interpret)
    subbands = jnp.asarray(subbands, jnp.float32)
    shifts_np = np.asarray(sub_shifts, np.int32)
    nsub, T = subbands.shape
    ndms = shifts_np.shape[0]

    S = stage_overhang(int(shifts_np.max(initial=0)))
    plan = stage2_plan(nsub, S, ndms, T)
    segs, edge = _segment_layout(subbands, plan.seg)
    outs = []
    for c0 in range(0, ndms, plan.rows):
        res = _dedisperse_chunk(
            segs, edge, jnp.asarray(shifts_np[c0:c0 + plan.rows]),
            interpret=interpret, **plan.kernel_args())
        outs.append(res if res.shape[1] == T else res[:, :T])
    # what ran, on the executor's chunk span (docs/operations.md)
    trace.annotate("dm_chunk", dd_calls=len(outs), dd_rows=plan.rows,
                   dd_groups=nsub // plan.group)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("n_blocks", "seg", "head"))
def _segment_slab(body: jnp.ndarray, rest: jnp.ndarray, n_blocks: int,
                  seg: int, head: int):
    """One slab in its own dtype -> the segment layout stage 1 reads,
    as `_segment_layout`'s for stage 2.  body: (nchan, <= n_blocks * 8
    * seg), the slab's columns as far as its blocks reach (edge-padded
    here where a beam ends inside its last block), cut into segments
    of `seg` samples: (nchan, n_seg, seg), n_seg = 8 * n_blocks (one
    grid step's 8 sublanes).  rest: the columns after them (what is
    left of the overhang S, or the beam's last sample), edge-padded to
    the slab's tail: the first `head` samples of the 8 segments after
    the last block, (nchan, 8, head).  One relayout copy in HBM,
    written by a program of its own because Mosaic refuses a DMA into
    one sublane of a tiled VMEM buffer; no widening (the kernel takes
    8-bit samples through int32), so a slab costs its own bytes once
    more and not twice."""
    nchan, cols = body.shape
    if cols < n_blocks * 8 * seg:
        body = jnp.pad(body, ((0, 0), (0, n_blocks * 8 * seg - cols)),
                       mode="edge")
    tail = jnp.pad(rest, ((0, 0), (0, 8 * seg - rest.shape[1])),
                   mode="edge")
    return (body.reshape(nchan, n_blocks * 8, seg),
            tail.reshape(nchan, 8, seg)[:, :, :head])


@functools.partial(jax.jit,
                   static_argnames=("nsub", "block_t", "window", "group",
                                    "vmem_bytes", "interpret"))
def _form_subbands_block(segs: jnp.ndarray, tail: jnp.ndarray,
                         shifts: jnp.ndarray, nsub: int,
                         block_t: int, window: int, group: int,
                         vmem_bytes: int,
                         interpret: bool) -> jnp.ndarray:
    """segs, tail: `_segment_slab`'s.  shifts: (nsub, cps) int32, all
    in [0, S].  group: subbands staged at a time (a divisor of nsub;
    nsub itself wherever they fit).  Returns (nsub, n_blocks*block_t)
    f32 (un-downsampled)."""
    nchan, n_seg, seg = segs.shape
    cps = nchan // nsub
    n_blocks = n_seg // 8
    lanes = Stage1Plan(block_t, window, group, vmem_bytes).lanes
    head = tail.shape[2]

    def block(index_map, width=seg):
        return pl.BlockSpec((group * cps, 8, width), index_map,
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks, nsub // group),
        # the same array twice: a step's 8 segments, and of the 8
        # after them the heads that are the step's overhang, both
        # pipelined; the tail stands in for them at the last block
        in_specs=[block(lambda i, g, s_ref: (g, i, 0)),
                  block(lambda i, g, s_ref: (
                      g, jnp.minimum(i + 1, n_blocks - 1), 0), head),
                  block(lambda i, g, s_ref: (g, 0, 0), head)],
        out_specs=pl.BlockSpec((group, 8, seg),
                               lambda i, g, s_ref: (g, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((cps, lanes // 128, 8, 128),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel_sb, group=group, cps=cps, seg=seg, lanes=lanes,
            unroll=max(u for u in range(1, 33) if cps % u == 0)),
        out_shape=jax.ShapeDtypeStruct((nsub, n_seg, seg), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(shifts, segs, segs, tail)
    # not free: XLA relays the (8, seg) tiles out to (nsub, T) rows
    return out.reshape(nsub, n_seg * seg)


@functools.lru_cache(maxsize=None)
def _share_programs(mesh, n_blocks: int, seg: int, head: int, nsub: int,
                    interpret: bool, kernel_args: tuple):
    """`_segment_slab` and `_form_subbands_block` for a beam laid over
    `mesh` by channels: each chip lays out its own channels' slab and
    sums its own nsub subbands from them (a subband never straddles
    two chips), the kernel and its geometry the solo ones at a
    share's shapes.  No sample and no sum leaves its chip; the
    subbands come out laid over the same chips by subband.  One
    program for all the chips, compiled once."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rows, cube = P("chan", None), P("chan", None, None)
    segment = jax.jit(shard_map(
        lambda body, rest: _segment_slab(body, rest, n_blocks, seg, head),
        mesh=mesh, in_specs=(rows, rows), out_specs=(cube, cube),
        check_vma=False))
    block = jax.jit(shard_map(
        lambda segs, tail, shifts: _form_subbands_block(
            segs, tail, shifts, nsub, interpret=interpret,
            **dict(kernel_args)),
        mesh=mesh, in_specs=(cube, cube, rows), out_specs=rows,
        check_vma=False))
    return segment, block


def form_subbands_pallas(data, chan_shifts, nsub: int, downsamp: int,
                         block_t: int | None = None,
                         group: int | None = None,
                         interpret: bool | None = None,
                         slab_bytes: int = 1_000_000_000):
    """Stage-1 Pallas path: (nchan, T) + per-channel shifts ->
    (nsub, T // downsamp) f32.  Same contract as
    dedisperse._form_subbands_jit (shift clamp to the pad bucket,
    edge-sample padding, floor-truncating sum-downsample) with the
    sweep restructured as one VMEM-staged program on full vector
    registers instead of a 96-step serialized `lax.map`.  block_t (a
    multiple of 1024: 8 segments of whole registers, none shorter than
    a seventh of the overhang), group: the tests' way to a geometry
    `stage1_plan` does not choose.

    A block laid over several devices by channels
    (`parallel.mesh.channel_mesh`) goes through the same sweep share
    by share: every step below is then one program over all the
    chips, each on its own channels with the geometry of a share
    (`_share_programs`), and the subbands come back laid over the
    same chips by subband.  Sums of bytes are exact in float32, so
    they are the whole block's rows to the bit."""
    from tpulsar.parallel import mesh as pmesh

    interpret = _resolve_interpret(interpret)
    data = jnp.asarray(data)
    nchan, T = data.shape
    cps = nchan // nsub
    shifts_np = np.asarray(chan_shifts, np.int32).reshape(nsub, cps)
    S = stage_overhang(int(shifts_np.max(initial=0)))
    # same clamp as the XLA formulation's min(shift, pad) — a no-op
    # while S >= smax, kept so the two paths cannot drift
    shifts_np = np.minimum(shifts_np, S)
    shares = pmesh.channel_mesh(data)
    n_shares = 1 if shares is None else shares.size
    if nsub % n_shares:
        raise ValueError(f"{nsub} subbands over {n_shares} shares of the "
                         "block: a subband would straddle two chips")
    plan = stage1_plan(nchan // n_shares, nsub // n_shares, S,
                       data.dtype.itemsize)
    if block_t is not None:
        plan = plan._replace(block_t=block_t, window=block_t + S)
        if block_t % 1024 or 7 * plan.seg < S + 128:
            raise ValueError(f"stage 1 cannot tile block_t {block_t} "
                             f"at overhang {S}")
    if group is not None:
        plan = plan._replace(group=group)
    shifts_dev = jnp.asarray(shifts_np)
    outs = []
    slabs = stage1_slabs(T, nchan // n_shares, data.dtype.itemsize,
                         plan.block_t, S, slab_bytes)

    def programs(slab):
        """(the slab's layout, its kernel call): the solo programs, or
        their twins over the block's shares."""
        if shares is None:
            return (functools.partial(_segment_slab, n_blocks=slab.n_blocks,
                                      seg=plan.seg, head=plan.head),
                    functools.partial(_form_subbands_block, nsub=nsub,
                                      interpret=interpret,
                                      **plan.kernel_args()))
        return _share_programs(
            shares, slab.n_blocks, plan.seg, plan.head, nsub // n_shares,
            interpret, tuple(sorted(plan.kernel_args().items())))

    def dispatch(k: int):
        """Slab k's programs, enqueued in the order they always were;
        -> (the later of its two slices, its segment layout)."""
        slab = slabs[k]
        body = jax.lax.slice_in_dim(data, *slab.body, axis=1)
        rest = jax.lax.slice_in_dim(data, *slab.rest, axis=1)
        segment, block = programs(slab)
        laid = segment(body, rest)
        del body
        if len(outs) >= 2:
            # 2-deep backpressure (the executor's pending[-2]
            # pattern): a hard per-slab block serializes the sweep,
            # while NO block lets async dispatch allocate every
            # staged slab copy concurrently — the RESOURCE_EXHAUSTED
            # peak the slabbing bounds.  Two slabs in flight, and the
            # copy of slab k overlaps the compute of slab k-1.
            with trace.span("sb-wait", slab=k):
                jax.block_until_ready(outs[-2])
        res = block(*laid, shifts_dev)
        outs.append(res[:, :slab.cols])
        return rest, laid

    # A traced run's spans, one a device step (docs/operations.md).
    # The device runs the steps back to back, so a span ENDS on a
    # fence of its step's result and reads from the end of the step
    # before it: under TPULSAR_TRACE_SYNC=1 the step's device time.
    # Slab k+1 is enqueued before slab k's first fence, inside
    # `sb-slice`: a fence with nothing queued behind it leaves the
    # chip idle for the host's round trip (0.8 ms a step on a v5e, a
    # quarter of GBNCC's stage: PERF.md section 6), and with one slab
    # in flight `hbm_peak_gib` would read under the untraced loop's
    # two.  Untraced and unfenced the order of the enqueues is the
    # plain loop's: slab 0, 1, the wait, 2, ...
    ahead = None
    for k, slab in enumerate(slabs):
        with trace.span("sb-slice", slab=k, cols=slab.cols):
            rest, laid = ahead or dispatch(k)
            ahead = dispatch(k + 1) if k + 1 < len(slabs) else None
            trace.fence(rest)
        with trace.span("sb-layout", slab=k, n_blocks=slab.n_blocks):
            trace.fence(*laid)
        with trace.span("sb-kernel", slab=k):
            trace.fence(outs[k])
        del rest, laid
    # what ran, on the executor's stage span (docs/operations.md)
    trace.annotate("subbanding", sb_groups=nsub // n_shares // plan.group,
                   sb_block_t=plan.block_t, sb_seg=plan.seg,
                   sb_overhang=S, sb_slabs=len(outs),
                   **({"shards": n_shares} if n_shares > 1 else {}))

    def downsampled(x):
        n_ds = (T // downsamp) * downsamp
        return x[:, :n_ds].reshape(nsub, -1, downsamp).sum(axis=-1)

    # the block's two steps the same way: both dispatched, then read
    out = outs[0]
    if len(outs) > 1:
        with trace.span("sb-join", slabs=len(outs)):
            out = joined = jnp.concatenate(outs, axis=1)
            if downsamp > 1:
                out = downsampled(joined)
            trace.fence(joined)
    elif downsamp > 1:
        out = downsampled(out)
    if downsamp > 1:
        with trace.span("sb-downsample", downsamp=downsamp):
            trace.fence(out)
    return out


_DISABLED_SIGS: dict[tuple, str] = {}


def is_tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def forced() -> bool:
    """TPULSAR_PALLAS=1: no-fallback mode — kernel failures re-raise so
    CI catches real Mosaic regressions instead of silently degrading to
    the ~76x-more-HBM-traffic XLA gather.  (On a TPU backend kernel
    failures always re-raise; see dedisperse.py.)"""
    return os.environ.get("TPULSAR_PALLAS", "").strip() in ("1", "on",
                                                            "true")


def _resolve_interpret(interpret: bool | None) -> bool:
    """interpret=None -> interpret mode exactly off the TPU.  An
    explicit True on a TPU backend is refused: the interpreter on a
    real chip is a catastrophic slowdown that still returns right
    answers, so nothing downstream would notice."""
    on_tpu = is_tpu_backend()
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise AssertionError(
            "Pallas interpret mode requested on a TPU backend")
    return interpret


def use_pallas() -> bool:
    """Stage-2 Pallas gate: the TPU backend, unless switched off
    (TPULSAR_PALLAS=0) or forced on (=1) by env."""
    env = os.environ.get("TPULSAR_PALLAS", "").strip()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true"):
        return True
    return is_tpu_backend()


def use_pallas_sb() -> bool:
    """Stage-1 Pallas gate.  TPULSAR_PALLAS=0 turns off every Pallas
    tier; TPULSAR_PALLAS_SB=0/1 then overrides for stage 1 alone
    (TPULSAR_PALLAS=1 forces both tiers on, so the no-fallback CI
    contract covers stage 1 too)."""
    genv = os.environ.get("TPULSAR_PALLAS", "").strip()
    if genv in ("0", "off", "false"):
        return False
    env = os.environ.get("TPULSAR_PALLAS_SB", "").strip()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true") or genv in ("1", "on", "true"):
        return True
    return is_tpu_backend()


def signature_enabled(sig: tuple) -> bool:
    return sig not in _DISABLED_SIGS


def disable_signature(sig: tuple, reason: str) -> None:
    """Disable the Pallas path for one (shape) signature after a
    caught runtime/compile failure — a transient size-dependent error
    (e.g. HBM OOM on the largest pass) must not degrade every other
    pass (round-1 advisor finding)."""
    if sig not in _DISABLED_SIGS:
        _DISABLED_SIGS[sig] = reason
        import warnings
        warnings.warn(f"Pallas dedispersion disabled for {sig}, using "
                      f"XLA fallback: {reason}")
