"""Device mesh construction and the sharded per-beam search step.

TPU-native equivalent of the reference's parallelism inventory
(SURVEY.md section 2.4): beams are data-parallel (the reference fans
them out as cluster jobs; here they ride a mesh axis), and within a
beam the DM-trial axis — the reference's per-DM subprocess loop,
PALFA2_presto_search.py:532-594 — is sharded across chips with a
single all_gather at the end to collect per-trial top-k candidates.

Layout choices:
  * subbands (nsub, T') are replicated across the `dm` axis (nsub=96
    subbands are small; replication avoids a halo exchange for the
    shift gathers);
  * stage-2 shift tables (ndms, nsub) are sharded along `dm`;
  * each device computes its DM chunk's series, spectrum, whitening,
    harmonic sums, and top-k locally — candidates (k floats per trial)
    are the only thing crossing ICI.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_beam: int = 1, n_dm: int | None = None,
              devices=None) -> Mesh:
    """Build a (beam, dm) mesh over the available devices."""
    devices = devices if devices is not None else jax.devices()
    n_dev = len(devices)
    if n_dm is None:
        if n_dev % n_beam:
            raise ValueError(f"{n_dev} devices not divisible by beam={n_beam}")
        n_dm = n_dev // n_beam
    if n_beam * n_dm != n_dev:
        raise ValueError(f"mesh {n_beam}x{n_dm} != {n_dev} devices")
    arr = np.asarray(devices).reshape(n_beam, n_dm)
    return Mesh(arr, axis_names=("beam", "dm"))


def channel_mesh(x) -> Mesh | None:
    """The one-axis mesh ("chan",) of the devices an array is laid
    over by its FIRST axis, in the order of their pieces; None for an
    array on one device (or no jax.Array at all).  The layout is the
    operand's: a beam block that arrives as equal, contiguous shares
    of its channels (a subband block: of its subbands) on several
    devices is worked on share by share, each chip on its own, and
    any other way of spreading an array over devices is refused here
    rather than gathered."""
    sh = getattr(x, "sharding", None)
    if sh is None or len(sh.device_set) == 1:
        return None
    shards = sorted(x.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    rows = x.shape[0] // len(shards)
    for k, s in enumerate(shards):
        if (s.data.shape != (rows,) + tuple(x.shape[1:])
                or (s.index[0].start or 0) != k * rows):
            raise ValueError(
                f"an array of shape {tuple(x.shape)} laid over "
                f"{len(sh.device_set)} devices as {sh} is no layout by "
                f"equal shares of its first axis: piece {k} is "
                f"{s.data.shape} at {s.index}")
    return Mesh(np.asarray([s.device for s in shards]), ("chan",))


def require_same_devices(block_mesh: Mesh | None, mesh: Mesh | None) -> None:
    """A block laid over several devices is searched by a mesh of
    exactly those devices, in that order, as its `dm` axis: anything
    else is an error before a pass starts, never a gather onto one
    chip.  (No mesh at all, dm_shards = 1, is the one-device pass
    loop: it reads each pass's subbands, never the block, whole on
    the block's first device.)"""
    if block_mesh is None or mesh is None:
        return
    have, want = list(block_mesh.devices.flat), list(mesh.devices.flat)
    if have != want:
        raise ValueError(
            f"the block is laid over {len(have)} devices {have} and the "
            f"search's mesh is {want}: a laid-out beam is searched with "
            f"dm_shards={len(have)} on the same devices in the same "
            f"order")


def as_dm_rows(mesh: Mesh, x):
    """A laid-out array's pieces, as they lie, under the search mesh's
    own sharding P("dm", None): no byte moves (the devices are the
    mesh's, in its order: `require_same_devices`)."""
    sharding = NamedSharding(mesh, P("dm", None))
    by_dev = {s.device: s.data for s in x.addressable_shards}
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding,
        [by_dev[d] for d in sharding.addressable_devices_indices_map(
            x.shape)])


def on_first_device(x):
    """A laid-out array whole on its first piece's device (the finish
    reads one candidate's subbands there); an array on one device as
    it is."""
    cm = channel_mesh(x)
    return x if cm is None else _first_copy(x, cm)


@dataclasses.dataclass(frozen=True)
class SearchStepSpec:
    """Static configuration of one sharded search step."""
    nsub: int
    nfft: int          # padded FFT length (power of 2)
    max_numharm: int
    topk: int
    whiten_edges: tuple[int, ...]
    whiten_est: str = "median"  # block noise estimator (static spec
    #                             config, NOT an ambient env read — an
    #                             env change under the outer jit would
    #                             silently reuse the stale trace).
    #                             Builders that honour
    #                             TPULSAR_WHITEN_ESTIMATOR must thread
    #                             fr.whiten_estimator() in HERE, like
    #                             the executor does for PassSpec
    dd_pad: int = 0    # static stage-2 shift bound (>= max sub_shift);
    #                    0 = pad by the full series length (always
    #                    correct, 2x subband HBM — fine for demos)


def _local_search(subbands, sub_shifts, keep_mask, spec: SearchStepSpec):
    """Per-device body: dedisperse local DM chunk -> rfft -> whiten ->
    interbin -> harmonic top-k.  Returns dict of stage -> (vals,
    bins); bins are in HALF-BIN units (the production dr=0.5
    detection grid — fourier.interbin_powers)."""
    from tpulsar.kernels.dedisperse import _dedisperse_subbands_scan
    from tpulsar.kernels.fourier import (all_stage_candidates,
                                         harmonic_stages, interbin_powers,
                                         scale_spectrum, whiten_powers)

    pad = spec.dd_pad or subbands.shape[-1]
    series = _dedisperse_subbands_scan(subbands, sub_shifts, pad)
    series = series - series.mean(axis=-1, keepdims=True)
    nfft = spec.nfft
    T = series.shape[-1]
    if T < nfft:
        series = jnp.pad(series, ((0, 0), (0, nfft - T)))
    else:
        series = series[:, :nfft]
    cspec = jnp.fft.rfft(series, axis=-1)
    powers = jnp.abs(cspec) ** 2
    powers = powers.at[..., 0].set(0.0)
    powers = powers * keep_mask
    wpow = whiten_powers(powers, spec.whiten_edges,
                         estimator=spec.whiten_est)
    wpow = wpow * keep_mask
    p2 = interbin_powers(scale_spectrum(cspec, powers, wpow))

    # the single-device lo stage: every harmonic read once
    return all_stage_candidates(
        p2, tuple(harmonic_stages(spec.max_numharm)), spec.topk)


def sharded_search_step(mesh: Mesh, spec: SearchStepSpec):
    """Build the jitted multi-chip search step for one dedispersion
    pass.

    Returns fn(subbands[nbeams, nsub, T'], sub_shifts[nbeams, ndms, nsub],
               keep_mask[nfft//2+1])
    -> {stage: (vals[nbeams, ndms_total... -> (nbeams, ndms, topk)], bins)}

    Sharding: beams over the `beam` axis, DM trials over `dm`; output
    candidate blocks are all_gathered over `dm` so every host sees the
    full candidate set.

    AOT note: this module's jit sites are per-mesh shard_map closures
    (the jit captures the live Mesh), so they cannot be registered in
    tpulsar/aot/registry.py — they are on its EXEMPT_SITES list and
    validated by the multichip rehearsal, not the single-chip gate.
    """

    def step(subbands, sub_shifts, keep_mask):
        def per_shard(subb, shifts, mask):
            # shapes here are the per-device shards:
            # subb (1, nsub, T'), shifts (1, ndms_loc, nsub)
            res = _local_search(subb[0], shifts[0], mask, spec)
            # gather DM-chunk results across the dm axis
            return {h: (jax.lax.all_gather(v, "dm", axis=0, tiled=True)[None],
                        jax.lax.all_gather(b, "dm", axis=0, tiled=True)[None])
                    for h, (v, b) in res.items()}

        from jax import shard_map
        return shard_map(
            per_shard, mesh=mesh,
            in_specs=(P("beam", None, None), P("beam", "dm", None), P()),
            out_specs={h: (P("beam", None, None), P("beam", None, None))
                       for h in _stages(spec)},
            check_vma=False,
        )(subbands, sub_shifts, keep_mask)

    return jax.jit(step)


def _stages(spec: SearchStepSpec):
    from tpulsar.kernels.fourier import harmonic_stages
    return harmonic_stages(spec.max_numharm)


@dataclasses.dataclass(frozen=True)
class PassSpec:
    """Static configuration of the full sharded per-pass search (the
    production pipeline: dedisperse -> SP boxcars -> whiten -> lo
    harmonic stages -> hi z-template correlation)."""
    nfft: int                   # FFT-friendly padded series length
    max_numharm: int            # lo-accel harmonic stages
    topk: int
    sp_widths: tuple[int, ...]
    sp_topk: int
    hi: bool                    # run the accelerated (zmax>0) search
    sp_detrend: str = "median"  # SP baseline estimator (see
    #                             kernels/singlepulse.normalize_series)
    whiten_est: str = "median"  # whitening block estimator (static
    #                             spec config for the same stale-trace
    #                             reason as SearchStepSpec.whiten_est)
    hi_numharm: int = 8
    hi_seg: int = 0             # TemplateBank geometry (static)
    hi_step: int = 0
    hi_width: int = 0
    hi_nz: int = 0
    pallas_dd: bool = False     # stage-2 dedispersion via the Pallas
    #                             sliding-window kernel (decided
    #                             host-side with the same gate as the
    #                             single-device path)
    dd_stage_s: int = 0         # static staging overhang (>= max
    #                             shift, power of 2) for the Pallas
    #                             kernel's sliding window
    dd_interpret: bool = False  # Pallas interpret mode (CPU testing)
    dd_pad: int = 0             # static stage-2 shift bound for the
    #                             XLA scan path (>= max sub_shift);
    #                             0 = pad by the full series length
    sub_sharded: bool = False   # the subbands arrive laid over the dm
    #                             axis BY SUBBAND (stage 1 of a beam
    #                             laid out by channels) and stay so:
    #                             each chip runs stage 2 over its own
    #                             subbands for ALL the call's rows, and
    #                             a reduce-scatter over dm leaves it its
    #                             own rows' full sums (`_partial_dd`).
    #                             The shift table comes sharded the
    #                             same way, by subband


def _pallas_dd_local(subb, shifts, stage_s: int, interpret: bool):
    """Per-shard stage-2 dedispersion via the Pallas kernel
    (tpulsar/kernels/pallas_dd.py) — same HBM-bandwidth win as the
    single-device product path, expressed with static staging
    geometry so it traces inside shard_map (the host wrapper
    dedisperse_subbands_pallas inspects the shift table with NumPy,
    which a traced shard cannot).  stage_s must be >= the max shift of
    the FULL pass table (computed host-side once, shared by every
    shard so all shards compile the same kernel); the rest of the
    geometry is pallas_dd.stage2_plan's, from the shard's shapes."""
    from tpulsar.kernels import pallas_dd

    interpret = pallas_dd._resolve_interpret(interpret)
    ndms_loc = shifts.shape[0]
    nsub, T = subb.shape
    plan = pallas_dd.stage2_plan(nsub, stage_s, ndms_loc, T)
    segs, edge = pallas_dd._segment_layout(subb.astype(jnp.float32),
                                           plan.seg)
    rows = []
    for c0 in range(0, ndms_loc, plan.rows):
        chunk = jax.lax.dynamic_slice_in_dim(
            shifts, c0, min(plan.rows, ndms_loc - c0), axis=0)
        rows.append(pallas_dd._dedisperse_chunk(
            segs, edge, chunk, interpret=interpret,
            **plan.kernel_args())[:, :T])
    return jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]


#: most bytes of partial sums a chip holds for one reduce-scatter of
#: `_partial_dd` (a group of rows x T float32)
PARTIAL_GROUP_BYTES = 3 << 29


def partial_groups(rows_per_device: int, n_dev: int, T: int) -> int:
    """Rows a device KEEPS of one group of `_partial_dd`: the largest
    divisor of its rows whose group (n_dev times as many rows of T
    float32 partial sums) stays under PARTIAL_GROUP_BYTES; 1 at
    least."""
    fits = [q for q in range(1, rows_per_device + 1)
            if rows_per_device % q == 0
            and n_dev * q * T * 4 <= PARTIAL_GROUP_BYTES]
    return max(fits, default=1)


def _partial_dd(subb_loc, shifts_loc, spec: PassSpec, n_dev: int):
    """Stage 2 over subbands that stay where stage 1 left them: this
    chip's (nsub / n_dev, T) subbands and its columns of the call's
    whole shift table, (rows, nsub / n_dev) -> its own rows' series,
    (rows / n_dev, T), rows [d, d + 1) * rows / n_dev on chip d as the
    row-sharded forms leave them.  The solo stage-2 kernel over the
    local subbands for every row of a group, then ONE reduce-scatter a
    group: the partial sums are whole numbers under 2^24 (sums of
    bytes), so the order of the additions changes no bit.  A group
    holds q rows of every chip (`partial_groups`), so what a chip
    holds of partial sums is bounded whatever the call's rows."""
    from tpulsar.kernels.dedisperse import _dedisperse_subbands_scan

    rows, T = shifts_loc.shape[0], subb_loc.shape[1]
    per_dev = rows // n_dev
    q = partial_groups(per_dev, n_dev, T)
    outs = []
    for j in range(per_dev // q):
        take = np.concatenate([d * per_dev + j * q + np.arange(q)
                               for d in range(n_dev)])
        shifts = shifts_loc[take]
        if spec.pallas_dd:
            part = _pallas_dd_local(subb_loc, shifts, spec.dd_stage_s,
                                    spec.dd_interpret)
        else:
            part = _dedisperse_subbands_scan(
                subb_loc, shifts, spec.dd_pad or T)
        # a reduce-scatter spelled as all_to_all + a local sum: the
        # TPU compiler turns psum_scatter over these row-minor tiles
        # into an all-reduce of the whole group (twice the bytes, and
        # the group's buffer alive for the rest of the program)
        mine = jax.lax.all_to_all(part.reshape(n_dev, q, T), "dm",
                                  split_axis=0, concat_axis=0, tiled=True)
        outs.append(mine.sum(axis=0))
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def sharded_pass_fn(mesh: Mesh, spec: PassSpec):
    """Build the jitted sharded per-pass search.

    Returns fn(subbands[nsub, T'], sub_shifts[ndms, nsub],
               keep_mask[nbins] float, bank_fft[nz, seg] complex,
               taps: accel.corr_taps of the bank where the hi stage
               correlates directly (accel.corr_form), else None)
    -> dict of gathered arrays:
         lo_vals/lo_bins: (nstages_lo, ndms, topk)
         sp_snr/sp_idx:   (nwidths, ndms, sp_topk)
         hi_vals/hi_rbins/hi_zidx: (ndms, nstages_hi, topk)  [hi only]

    ndms must be a multiple of mesh.shape['dm'] (shard_dm_table pads).
    Subbands and masks are replicated; only the DM-trial axis is
    sharded, and the per-trial top-k blocks are the only arrays that
    cross ICI (one tiled all_gather each) — the TPU realization of the
    reference's embarrassingly-parallel per-DM loop
    (PALFA2_presto_search.py:532-594, SURVEY.md section 2.4).
    """
    from jax import shard_map

    from tpulsar.kernels import accel as ak
    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.kernels.dedisperse import _dedisperse_subbands_scan

    n_dev = int(mesh.shape["dm"])

    def body(subb, shifts, keep, bank, taps):
        if spec.sub_sharded:
            series = _partial_dd(subb, shifts, spec, n_dev)
        elif spec.pallas_dd:
            series = _pallas_dd_local(subb, shifts, spec.dd_stage_s,
                                      spec.dd_interpret)
        else:
            series = _dedisperse_subbands_scan(
                subb, shifts, spec.dd_pad or subb.shape[-1])
        norm = sp_k.normalize_series(series, estimator=spec.sp_detrend)
        sp_snr, sp_idx = sp_k.boxcar_search(norm, spec.sp_widths,
                                            spec.sp_topk)
        cspec = fr.complex_spectrum(fr.pad_series(series, spec.nfft))
        powers, wpow = fr.whitened_powers(
            cspec, keep, estimator=spec.whiten_est)
        # half-bin detection grid (interbinning, PRESTO ACCEL_DR=0.5)
        # — identical to the single-device path; bin indices are in
        # half-bin units and the host applies bin_scale=0.5
        wspec = fr.scale_spectrum(cspec, powers, wpow)
        stages_lo = tuple(fr.harmonic_stages(spec.max_numharm))
        lo = fr.lo_stage_candidates(wspec, stages_lo, spec.topk)
        lo_vals = [lo[h][0] for h in stages_lo]
        lo_bins = [lo[h][1] for h in stages_lo]

        def g(x, axis):
            return jax.lax.all_gather(x, "dm", axis=axis, tiled=True)

        out = {
            "lo_vals": g(jnp.stack(lo_vals), 1),
            "lo_bins": g(jnp.stack(lo_bins), 1),
            "sp_snr": g(sp_snr, 1),
            "sp_idx": g(sp_idx, 1),
        }
        if spec.hi:
            hv, hr, hz = ak._accel_block_topk(
                wspec, bank, spec.hi_seg, spec.hi_step, spec.hi_width,
                spec.hi_nz, spec.hi_numharm, spec.topk, taps=taps)
            out["hi_vals"] = g(hv, 0)
            out["hi_rbins"] = g(hr, 0)
            out["hi_zidx"] = g(hz, 0)
        return out

    out_specs = {k: P() for k in
                 (("lo_vals", "lo_bins", "sp_snr", "sp_idx")
                  + (("hi_vals", "hi_rbins", "hi_zidx")
                     if spec.hi else ()))}
    if spec.sub_sharded:
        in_specs = (P("dm", None), P(None, "dm"), P(), P(), P())
    else:
        in_specs = (P(), P("dm", None), P(), P(), P())
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))


def shard_dm_table(sub_shifts: np.ndarray, n_dm: int) -> np.ndarray:
    """Pad the (ndms, nsub) stage-2 shift table so ndms divides the dm
    axis size (padded trials repeat the last row; their duplicate
    candidates merge away in sifting)."""
    ndms = sub_shifts.shape[0]
    rem = (-ndms) % n_dm
    if rem:
        pad = np.repeat(sub_shifts[-1:], rem, axis=0)
        sub_shifts = np.concatenate([sub_shifts, pad], axis=0)
    return sub_shifts


# ------------------------------------------- a laid-out array, re-laid

_RESHARD_FNS: dict = {}


def reshard(x, sharding):
    """An array laid over a mesh's devices, under another sharding of
    the same devices, by ONE compiled program over all the chips: an
    all-gather or an all-to-all on the interconnect.  (`jax.device_put`
    between two layouts copies piece by piece: 1.45 GiB of subbands to
    every chip of a v5e 2x2 took 3.0 s that way, 2.9 GiB to one chip
    3.6 s; PERF.md section 6, PR 43.)"""
    if sharding not in _RESHARD_FNS:
        _RESHARD_FNS[sharding] = jax.jit(lambda a: a, out_shardings=sharding)
    return _RESHARD_FNS[sharding](x)


def _first_copy(x, cm: Mesh):
    """`on_first_device` of an array laid over `cm`: gathered onto
    every chip by the interconnect, the first chip's copy kept (the
    others' are freed with the gathered array)."""
    whole = reshard(x, NamedSharding(cm, P()))
    first = cm.devices.flat[0]
    return next(s.data for s in whole.addressable_shards
                if s.device == first)
