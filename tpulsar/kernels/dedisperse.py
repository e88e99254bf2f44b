"""Incoherent dedispersion on TPU.

Replaces PRESTO's `prepsubband` (both the `-sub` subband-forming mode
and the subband->DM-series mode; reference invocation:
lib/python/PALFA2_presto_search.py:506-529) with jittable JAX ops:

  * stage 1 `form_subbands`: per-channel integer shift at the pass
    sub-DM, channel-group sum into `nsub` subbands, time downsampling;
  * stage 2 `dedisperse_subbands`: per-subband residual shift for each
    target DM — vmapped over the DM-trial axis, which is the axis the
    parallel layer shards across chips.

Shifts are realized as clamped gathers along the time axis with
statically-shaped index arrays, so each (downsamp, ndms) signature
compiles once and reruns for every pass of the plan.  All delays are
computed relative to the *highest* frequency in the band (delay >= 0),
matching the convention the synthesizer and oracle use.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpulsar.constants import KDM, dispersion_delay_s as delays_s
from tpulsar.obs import trace


def shift_samples(dm, freqs_mhz, ref_mhz, dt) -> np.ndarray:
    """Integer sample shifts (host-side, static per compile)."""
    return np.round(delays_s(dm, freqs_mhz, ref_mhz) / dt).astype(np.int32)


def _pad_bucket(maxshift: int) -> int:
    """Round a maximum shift up to a power-of-two bucket (>=256) so the
    static pad width takes few distinct values across a survey plan's
    passes and compile signatures stay bounded.  A zero maximum shift
    needs NO pad at all: every gather start is 0 and the slice is the
    row itself — padding 256 samples per row there bought nothing but
    a widened copy of the whole block on zero-shift passes."""
    if maxshift <= 0:
        return 0
    p = 256
    while p < maxshift:
        p *= 2
    return p


def _edge_pad(data: jnp.ndarray, pad: int) -> jnp.ndarray:
    """Extend each row of (nrows, T) with `pad` copies of its last
    sample — THE edge-clamp realization every shift formulation here
    composes on (indices past T-1 read the replicated tail, exactly
    out[t] = data[min(t, T-1)]).  pad=0 returns the input unchanged
    (zero-shift passes; see _pad_bucket)."""
    if pad <= 0:
        return data
    nrows = data.shape[0]
    tail = jnp.broadcast_to(data[:, -1:],
                            (nrows, pad)).astype(data.dtype)
    return jnp.concatenate([data, tail], axis=1)


@partial(jax.jit, static_argnames=("pad",))
def _shift_rows(data: jnp.ndarray, shifts: jnp.ndarray,
                pad: int) -> jnp.ndarray:
    """out[i, t] = data[i, min(t + shifts[i], T-1)] for shifts <= pad.

    The shift is one edge-value pad plus a vmapped dynamic slice, so
    the gather indices are one scalar per row.  (A materialized
    (nrows, T) int32 index matrix — the obvious take_along_axis
    formulation — is 15 GB at full Mock-beam scale, ~4x the raw block.)
    """
    nrows, T = data.shape
    padded = _edge_pad(data, pad)
    starts = jnp.minimum(shifts.astype(jnp.int32), pad)
    return jax.vmap(
        lambda row, s: jax.lax.dynamic_slice_in_dim(row, s, T)
    )(padded, starts)


def _shift_gather(data: jnp.ndarray, shifts) -> jnp.ndarray:
    """Shift row i of (nrows, T) left by shifts[i] (clamped at the end).

    Host entry point: `shifts` must be concrete (NumPy or device
    array), never a tracer — the pad width is derived from its max.
    """
    shifts_np = np.asarray(shifts)
    pad = _pad_bucket(int(shifts_np.max(initial=0)))
    return _shift_rows(data, jnp.asarray(shifts_np), pad)


def downsample(x: jnp.ndarray, factor: int, axis: int = -1) -> jnp.ndarray:
    """Sum-downsample along an axis.  Lengths not divisible by the
    factor are truncated (merged Mock blocks lose leading rows, so the
    plan's divisibility guarantee does not survive preprocessing)."""
    if factor == 1:
        return x
    axis = axis % x.ndim
    n = (x.shape[axis] // factor) * factor
    x = jax.lax.slice_in_dim(x, 0, n, axis=axis)
    newshape = x.shape[:axis] + (n // factor, factor) + x.shape[axis + 1:]
    return x.reshape(newshape).sum(axis=axis + 1)


@partial(jax.jit, static_argnames=("nsub", "downsamp", "pad"))
def _form_subbands_jit(data: jnp.ndarray, chan_shifts: jnp.ndarray,
                       nsub: int, downsamp: int, pad: int) -> jnp.ndarray:
    nchan, T = data.shape
    cps = nchan // nsub
    padded = _edge_pad(data, pad)                      # native dtype
    grouped = padded.reshape(nsub, cps, T + pad)
    starts = jnp.minimum(chan_shifts.astype(jnp.int32),
                         pad).reshape(nsub, cps)
    n_ds = (T // downsamp) * downsamp

    def one_sub(args):
        rows, s = args      # (cps, T+pad) native dtype, (cps,) int32
        sl = jax.vmap(
            lambda r, st: jax.lax.dynamic_slice_in_dim(r, st, T)
        )(rows, s)
        # Cast after the slice: only one subband group is ever float32
        # (a whole-beam float32 copy is ~4x HBM at full Mock scale).
        acc = sl.astype(jnp.float32).sum(axis=0)
        if downsamp > 1:
            acc = acc[:n_ds].reshape(-1, downsamp).sum(axis=-1)
        return acc

    return jax.lax.map(one_sub, (grouped, starts))


def form_subbands(data: jnp.ndarray, chan_shifts, nsub: int,
                  downsamp: int) -> jnp.ndarray:
    """Stage 1: (nchan, T) -> (nsub, T // downsamp) float32.

    chan_shifts: per-channel integer shifts at the pass sub-DM,
    *relative to the reference frequency of the channel's own subband*
    (so each subband is internally dedispersed to the sub-DM but keeps
    its inter-subband delay for stage 2).  Must be concrete (the pad
    width is derived host-side from its max).
    """
    nchan = data.shape[0]
    if nchan % nsub:
        raise ValueError(f"nchan {nchan} not divisible by nsub {nsub}")
    from tpulsar.kernels import pallas_dd

    shifts_np = np.asarray(chan_shifts)
    # Stage-1 Pallas tier (same gate/fallback discipline as stage 2):
    # the XLA `lax.map` formulation serializes the subbands and
    # measured 160.6 s of config 1's 176.5 s on-chip wall-clock
    # (rung_cfg1_full.json, 2026-08-01) — the VMEM-staged kernel is
    # the production TPU path, the map the portable fallback.
    sig = ("sb", tuple(data.shape), int(nsub), int(downsamp))
    if pallas_dd.use_pallas_sb() and pallas_dd.signature_enabled(sig):
        try:
            out = pallas_dd.form_subbands_pallas(data, shifts_np,
                                                 nsub, downsamp)
            # force execution so a kernel fault lands in this except
            # (async dispatch would surface it downstream)
            jax.block_until_ready(out)
            return out
        except Exception as e:
            # a kernel fault on the chip is a fault, not a cue to run
            # the beam on the XLA formulation; TPULSAR_PALLAS=1 is the
            # same no-fallback contract for CI off the chip
            if pallas_dd.forced() or pallas_dd.is_tpu_backend():
                raise
            pallas_dd.disable_signature(sig, reason=str(e)[:200])
            from tpulsar.search import degraded
            degraded.note("pallas_sb_disabled",
                          f"kernel fault, XLA fallback: {str(e)[:160]}")
    elif pallas_dd.is_tpu_backend():
        from tpulsar.search import degraded
        degraded.note("pallas_sb_disabled",
                      "switched off by env; XLA lax.map subband path")
    pad = _pad_bucket(int(shifts_np.max(initial=0)))
    from tpulsar.parallel import mesh as pmesh

    shares = pmesh.channel_mesh(data)
    if shares is not None:
        # a block laid over several chips by channels: each forms its
        # own subbands from its own channels, as the Pallas tier does
        if nsub % shares.size:
            raise ValueError(
                f"{nsub} subbands over {shares.size} shares of the "
                "block: a subband would straddle two chips")
        trace.annotate("subbanding", shards=shares.size)
        return _form_subbands_shares(shares, nsub // shares.size,
                                     downsamp, pad)(
            data, jnp.asarray(shifts_np))
    return _form_subbands_jit(data, jnp.asarray(shifts_np), nsub,
                              downsamp, pad)


@functools.lru_cache(maxsize=None)
def _form_subbands_shares(mesh, nsub: int, downsamp: int, pad: int):
    """`_form_subbands_jit` over a block laid over `mesh` by channels:
    nsub subbands a chip from its own channels, no exchange; the
    subbands come out laid over the same chips by subband."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return jax.jit(shard_map(
        lambda share, shifts: _form_subbands_jit(share, shifts, nsub,
                                                 downsamp, pad),
        mesh=mesh, in_specs=(P("chan", None), P("chan")),
        out_specs=P("chan", None), check_vma=False))


@partial(jax.jit, static_argnames=("pad",))
def _dedisperse_subbands_scan(subbands: jnp.ndarray,
                              sub_shifts: jnp.ndarray,
                              pad: int) -> jnp.ndarray:
    """Shift-and-sum over the DM-trial axis as a scan over subbands.

    Each scan step slices one edge-padded subband row at every trial's
    shift (a batched dynamic slice — scalar gather indices) and adds it
    to the (ndms, T) accumulator, so peak HBM is the accumulator plus
    one padded copy of the subband block, never the (ndms, nsub, T)
    gather product (~114 GB at full beam scale)."""
    nsub, T = subbands.shape
    padded = _edge_pad(subbands, pad)
    starts = jnp.minimum(sub_shifts.astype(jnp.int32), pad)  # (ndms, nsub)
    return dedisperse_window_scan(padded, starts, T)


def _dedisperse_subbands_xla(subbands: jnp.ndarray,
                             sub_shifts) -> jnp.ndarray:
    """XLA (non-Pallas) stage 2.  `sub_shifts` must be concrete."""
    shifts_np = np.asarray(sub_shifts)
    pad = _pad_bucket(int(shifts_np.max(initial=0)))
    return _dedisperse_subbands_scan(subbands, jnp.asarray(shifts_np), pad)


@partial(jax.jit, static_argnames=("out_len",))
def dedisperse_window_scan(ext: jnp.ndarray, sub_shifts: jnp.ndarray,
                           out_len: int) -> jnp.ndarray:
    """Shift-and-sum over a pre-extended window (no edge handling):

        out[d, t] = sum_s ext[s, t + sub_shifts[d, s]],  t < out_len

    Callers guarantee max(sub_shifts) + out_len <= ext.shape[1] (e.g.
    a time shard with its halo already attached).  Same scan-over-
    subbands accumulation as _dedisperse_subbands_scan: scalar gather
    indices, peak HBM = accumulator + the window."""
    def body(acc, inp):
        row, s = inp   # row (L,), s (ndms,)
        sl = jax.vmap(
            lambda st: jax.lax.dynamic_slice_in_dim(row, st, out_len))(s)
        return acc + sl, None

    starts = sub_shifts.astype(jnp.int32)
    acc0 = jnp.zeros((starts.shape[0], out_len), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (ext, starts.T))
    return acc


def dedisperse_subbands(subbands: jnp.ndarray,
                        sub_shifts: jnp.ndarray) -> jnp.ndarray:
    """Stage 2: (nsub, T') + (ndms, nsub) shifts -> (ndms, T') DM series.

    On TPU this dispatches to the Pallas sliding-window kernel
    (kernels/pallas_dd.py), which stages each time block in VMEM once
    for all DM trials; elsewhere (and under TPULSAR_PALLAS=0) it runs
    the XLA gather formulation.
    """
    from tpulsar.kernels import pallas_dd

    from tpulsar.resilience import faults

    sig = (tuple(subbands.shape), tuple(np.asarray(sub_shifts).shape))
    use_p = pallas_dd.use_pallas()
    sig_on = pallas_dd.signature_enabled(sig)
    noted = False
    if (use_p and sig_on) or faults.targets("dedisperse.pallas"):
        # an armed dedisperse.pallas fault enters this branch even on
        # backends that never take the Pallas path (CPU CI), so the
        # kernel-fault fallback below is exercisable off the hardware
        try:
            faults.fire("dedisperse.pallas", detail=f"stage-2 {sig}")
            if use_p and sig_on:
                out = pallas_dd.dedisperse_subbands_pallas(subbands,
                                                           sub_shifts)
                # jax dispatch is async: force execution here so a
                # kernel fault is caught by this except (and triggers
                # the fallback) rather than surfacing downstream
                jax.block_until_ready(out)
                return out
        except Exception as e:
            # on the chip a kernel fault (or an injected one) fails the
            # beam; the handler below is the off-chip path CPU CI
            # exercises through the dedisperse.pallas fault point
            if pallas_dd.forced() or pallas_dd.is_tpu_backend():
                raise
            pallas_dd.disable_signature(sig, reason=str(e)[:200])
            from tpulsar.search import degraded
            degraded.note("pallas_dd_disabled",
                          f"kernel fault, XLA fallback: {str(e)[:160]}")
            noted = True
    # NOT an elif of the fault-armed branch: an armed spec whose
    # fault happens not to fire on this call (count exhausted,
    # rate<1) must not swallow the TPU-backend provenance note below
    if pallas_dd.is_tpu_backend() and not noted:
        # flagship kernel switched off by env on the TPU backend:
        # the result must say which stage-2 path produced it.
        # Non-TPU backends are NOT degraded: the XLA path is their
        # only and intended path.
        from tpulsar.search import degraded
        degraded.note("pallas_dd_disabled",
                      "TPULSAR_PALLAS=0; XLA scan path")
    return _dedisperse_subbands_xla(subbands, sub_shifts)


def subband_reference_freqs(freqs_mhz: np.ndarray, nsub: int) -> np.ndarray:
    """Reference (highest) frequency of each subband; channels must be
    in ascending frequency order."""
    nchan = len(freqs_mhz)
    return np.asarray(freqs_mhz).reshape(nsub, nchan // nsub)[:, -1]


def plan_pass_shifts(freqs_mhz: np.ndarray, nsub: int, subdm: float,
                     dms: np.ndarray, dt: float, downsamp: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Static shift tables for one dedispersion pass.

    Returns (chan_shifts[nchan] at full rate for stage 1,
             sub_shifts[ndms, nsub] at the downsampled rate for stage 2).
    """
    freqs_mhz = np.asarray(freqs_mhz, dtype=np.float64)
    subrefs = subband_reference_freqs(freqs_mhz, nsub)
    nchan = len(freqs_mhz)
    chan_sub = np.repeat(subrefs, nchan // nsub)
    # Delay of each channel relative to its own subband's reference.
    chan_shifts = np.round(
        KDM * subdm * (freqs_mhz ** -2.0 - chan_sub ** -2.0) / dt
    ).astype(np.int64)
    band_ref = freqs_mhz[-1]
    dms = np.atleast_1d(np.asarray(dms, dtype=np.float64))
    dt_down = dt * downsamp
    sub_shifts = np.stack([
        shift_samples(dm, subrefs, band_ref, dt_down) for dm in dms])
    return chan_shifts.astype(np.int32), sub_shifts.astype(np.int32)


def dedisperse_pass(data: jnp.ndarray, freqs_mhz: np.ndarray, nsub: int,
                    subdm: float, dms: np.ndarray, dt: float,
                    downsamp: int) -> jnp.ndarray:
    """Full two-stage pass: (nchan, T) -> (ndms, T // downsamp)."""
    chan_shifts, sub_shifts = plan_pass_shifts(
        freqs_mhz, nsub, subdm, dms, dt, downsamp)
    subbands = form_subbands(data, jnp.asarray(chan_shifts), nsub, downsamp)
    return dedisperse_subbands(subbands, jnp.asarray(sub_shifts))


def dedisperse_exact(data: np.ndarray, freqs_mhz: np.ndarray,
                     dms: np.ndarray, dt: float,
                     downsamp: int = 1) -> np.ndarray:
    """Single-stage exact dedispersion (NumPy oracle): per-channel
    shift at each target DM, no subband approximation."""
    data = np.asarray(data)
    nchan, T = data.shape
    band_ref = float(np.asarray(freqs_mhz)[-1])
    out = []
    for dm in np.atleast_1d(dms):
        shifts = shift_samples(float(dm), freqs_mhz, band_ref, dt)
        ts = np.zeros(T, dtype=np.float64)
        for c in range(nchan):
            s = min(int(shifts[c]), T)
            if s < T:
                ts[: T - s] += data[c, s:]
            if s:
                ts[T - s:] += data[c, -1]  # clamp, matching the kernel
        out.append(ts)
    arr = np.stack(out)
    if downsamp > 1:
        arr = arr[:, : (T // downsamp) * downsamp]
        arr = arr.reshape(arr.shape[0], -1, downsamp).sum(-1)
    return arr


def max_shift_samples(freqs_mhz: np.ndarray, max_dm: float, dt: float) -> int:
    """Worst-case shift — samples at the end of every DM series that
    are contaminated by edge clamping and must be ignored."""
    f = np.asarray(freqs_mhz, dtype=np.float64)
    return int(np.ceil(KDM * max_dm * (f.min() ** -2 - f.max() ** -2) / dt))


# ----------------------------------------------------------- streaming entry
#
# The streaming plane (tpulsar/stream/) dedisperses chunk-at-a-time
# against carried channel state.  It reuses dedisperse_window_scan —
# the SAME jitted program as the batch time-shard path — at one static
# (nchan, stream_window_width) signature per session geometry, so a
# warm worker compiles nothing at session start and every emitted
# sample is the bit-identical fold-left channel sum the batch kernel
# produces (same program, same scan order, same f32 adds).

def stream_shift_table(freqs_mhz, dms, dt: float) -> np.ndarray:
    """(ndms, nchan) int32 per-channel shifts for DIRECT streaming
    dedispersion (no subband approximation — a stream session's DM
    list is small enough that stage 1 would buy nothing), delays
    relative to the highest frequency like everything else here."""
    freqs_mhz = np.asarray(freqs_mhz, dtype=np.float64)
    band_ref = float(freqs_mhz[-1])
    return np.stack([
        shift_samples(float(dm), freqs_mhz, band_ref, dt)
        for dm in np.atleast_1d(np.asarray(dms, dtype=np.float64))
    ]).astype(np.int32)


def stream_window_width(chunk_len: int, maxshift: int) -> int:
    """Static width of the streaming emission window: chunk_len output
    samples plus the power-of-two shift bucket (columns past
    maxshift + chunk_len are never read — they exist only to keep the
    compile signature stable across session geometries)."""
    return chunk_len + _pad_bucket(maxshift)


def dedisperse_stream_step(window: jnp.ndarray, shifts: jnp.ndarray,
                           chunk_len: int) -> jnp.ndarray:
    """One streaming emission: (nchan, W) window -> (ndms, chunk_len).
    Thin alias of the registered dedisperse_window_scan program so the
    stream plane and the AOT gate name the same compiled signature."""
    return dedisperse_window_scan(window, shifts, chunk_len)


def dedisperse_stream_batch(data, shifts) -> jnp.ndarray:
    """Batch reference for the streaming plane: dedisperse the whole
    (nchan, T) block in one call with the same edge clamp the chunked
    path realizes at session close.  Used by parity tests and
    ``bench --stream`` — a chunked run must match this bit-for-bit."""
    data = jnp.asarray(data, jnp.float32)
    shifts_np = np.asarray(shifts)
    pad = _pad_bucket(int(shifts_np.max(initial=0)))
    ext = _edge_pad(data, pad)
    return dedisperse_window_scan(ext, jnp.asarray(shifts_np),
                                  data.shape[1])
