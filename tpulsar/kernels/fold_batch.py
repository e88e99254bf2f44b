"""Batched candidate folding — one jitted device program per period
tier, replacing the per-candidate host loop around kernels/fold.py.

Why this exists (round-2 verdict, hotspot #2): the per-candidate fold
cost ~6.6 s/candidate on the evidence run, dominated not by FLOPs but
by structure — per-candidate scatter-adds over the whole (nsub, T)
block and ~6 host-synced device launches per candidate (each a network
round-trip on a remote TPU runtime).  This module folds a TIER of
candidates (same profile geometry) in one program:

* **Scatter-free fold.**  Phase-bin accumulation is a one-hot matmul
  per subintegration — (nsub, L) @ (L, nbin) rides the MXU — instead
  of a scatter-add (TPU scatters serialize).  All candidates in the
  batch share the data block; only their (T,) bin indices differ.
* **Fold once, rotate later.**  Subbands are folded UNALIGNED with a
  shared per-candidate phase; the candidate DM's inter-subband delays
  become per-subband fractional-bin rotations of the folded profiles
  (linear interpolation).  This is exactly prepfold's subband-fold
  scheme — fold .sub files once, search DM by rotating profiles
  (reference: PALFA2_presto_search.py:168-175) — with the rotation
  kept fractional instead of rounded to whole bins.
* **Coordinate descent on device.**  The (dp, dpdot) grid, the DM
  grid, and the second (dp, dpdot) grid run inside ONE jit with
  device argmaxes: zero host round-trips between rounds.

The search geometry (grids in profile-bin-drift units, period tiers)
matches kernels/fold.py, whose docstrings carry the prepfold rule
citations (reference: PALFA2_presto_search.py:142-228).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpulsar.constants import KDM
from tpulsar.kernels.fold import FoldResult, FoldRules, fold_rules
from tpulsar.obs import trace


# ------------------------------------------------------------- device pieces
#
# All profile rotations live in the Fourier domain: rolling x by a
# REAL shift s (out[b] = x[(b + s) mod nbin]) multiplies rfft(x)[k] by
# exp(+2*pi*i*k*s/nbin).  This is prepfold's own fftrotate scheme, and
# on TPU it turns every rotation into a small complex einsum (MXU)
# plus a batched length-nbin irfft — the gather formulation this
# replaces was the CPU evidence run's per-candidate bottleneck and
# lowers to unaligned-lane gathers on TPU.


def _phase(shifts, nbin: int):
    """exp(+2*pi*i*k*s/nbin) for rfft bin k: (..., K) from (...,)."""
    k = jnp.arange(nbin // 2 + 1, dtype=jnp.float32)
    ang = (2.0 * jnp.pi / nbin) * shifts[..., None] * k
    return jax.lax.complex(jnp.cos(ang), jnp.sin(ang))


def _collapse_hat(F_stack, F_cnt, var_ps, sub_shifts, nbin: int):
    """Collapse the subband axis at one DM row, in rfft space.

    F_stack (npart, nsub, K) rfft of centered profiles,
    F_cnt (npart, K) rfft of per-bin counts (shared across subbands —
    every subband of a candidate folds with the same bins),
    var_ps (npart, nsub) measured sample variance,
    sub_shifts (nsub,) REAL per-subband bin rotations.
    Returns (S1h, C1h, V1h), each (npart, K).
    """
    ph = _phase(sub_shifts, nbin)                        # (nsub, K)
    S1h = jnp.einsum("psk,sk->pk", F_stack, ph)
    C1h = F_cnt * ph.sum(axis=0)
    V1h = F_cnt * jnp.einsum("ps,sk->pk",
                             var_ps.astype(F_cnt.dtype), ph)
    return S1h, C1h, V1h


def _chi2_profiles(prof, csum, vsum, nbin: int):
    """Reduced chi-square against a flat baseline, batched over
    leading axes (kernels/fold.py _profile_chi2 with the
    measured-variance model)."""
    tot = csum.sum(-1)
    mean_rate = prof.sum(-1) / jnp.maximum(tot, 1.0)
    expected = mean_rate[..., None] * csum
    var = jnp.maximum(vsum, 1e-9)
    return ((prof - expected) ** 2 / var).sum(-1) / (nbin - 1)


def _part_shift(dp, dpd, part_times, period, nbin: int):
    """Real-valued per-subint bin shift for a (dp, dpdot) offset —
    kernels/fold.py _pp_shifts without the integer rounding."""
    dphi = -(dp * part_times + 0.5 * dpd * part_times ** 2) / period ** 2
    return dphi * nbin


def _grid_profiles(S1h, C1h, V1h, a, nbin: int):
    """Apply per-subint rotations a (..., npart) to the collapsed
    rfft profiles and return bin-space (prof, csum, vsum), each
    (..., nbin)."""
    A = _phase(a, nbin)                                 # (..., npart, K)
    prof = jnp.fft.irfft(jnp.einsum("...pk,pk->...k", A, S1h), nbin,
                         axis=-1)
    csum = jnp.fft.irfft(jnp.einsum("...pk,pk->...k", A, C1h), nbin,
                         axis=-1)
    vsum = jnp.fft.irfft(jnp.einsum("...pk,pk->...k", A, V1h), nbin,
                         axis=-1)
    return prof, csum, vsum


def _pp_best(S1h, C1h, V1h, dps, dpds, part_times, period, nbin: int):
    """chi2 over the (dp, dpdot) grid, on device; returns
    (best_dp, best_dpd)."""
    dp_g = jnp.repeat(dps, dpds.shape[0])
    dpd_g = jnp.tile(dpds, dps.shape[0])
    G = dp_g.shape[0]
    C = 256
    pad = (-G) % C
    dp_p = jnp.pad(dp_g, (0, pad))
    dpd_p = jnp.pad(dpd_g, (0, pad))

    def chunk(args):
        dpc, dpdc = args                                 # (C,)
        a = _part_shift(dpc[:, None], dpdc[:, None], part_times[None],
                        period, nbin)                    # (C, npart)
        prof, csum, vsum = _grid_profiles(S1h, C1h, V1h, a, nbin)
        return _chi2_profiles(prof, csum, vsum, nbin)

    chis = jax.lax.map(
        chunk, (dp_p.reshape(-1, C), dpd_p.reshape(-1, C))
    ).reshape(-1)[:G]
    k = jnp.argmax(chis)
    return dp_g[k], dpd_g[k]


def _optimize_one(F_stack, F_cnt, var_ps, r_dm, dps, dpds, part_times,
                  period, j0: int, nbin: int):
    """Full coordinate descent for ONE candidate, entirely on device:
    (dp, dpdot) at the nominal DM row, then the DM axis, then
    (dp, dpdot) again — kernels/fold.py fold_subbands_and_optimize's
    schedule with device argmaxes instead of host syncs."""
    # round 1: p/pdot at the nominal DM row
    S0h, C0h, V0h = _collapse_hat(F_stack, F_cnt, var_ps, r_dm[j0],
                                  nbin)
    bdp, bdpd = _pp_best(S0h, C0h, V0h, dps, dpds, part_times, period,
                         nbin)

    # DM axis at the best (p, pdot): all rows collapsed in one einsum
    a_best = _part_shift(bdp, bdpd, part_times, period, nbin)  # (npart,)
    ph_dm = _phase(r_dm, nbin)                       # (nddm, nsub, K)
    A_best = _phase(a_best, nbin)                    # (npart, K)
    SJ = jnp.einsum("psk,jsk,pk->jk", F_stack, ph_dm, A_best)
    phsum = ph_dm.sum(axis=1)                        # (nddm, K)
    CJ = jnp.einsum("pk,jk,pk->jk", F_cnt, phsum, A_best)
    vph = jnp.einsum("ps,jsk->jpk", var_ps.astype(SJ.dtype), ph_dm)
    VJ = jnp.einsum("pk,jpk,pk->jk", F_cnt, vph, A_best)
    chis_dm = _chi2_profiles(jnp.fft.irfft(SJ, nbin, axis=-1),
                             jnp.fft.irfft(CJ, nbin, axis=-1),
                             jnp.fft.irfft(VJ, nbin, axis=-1), nbin)
    bj = jnp.argmax(chis_dm)

    # round 2: p/pdot at the best DM row
    S2h, C2h, V2h = _collapse_hat(F_stack, F_cnt, var_ps, r_dm[bj],
                                  nbin)
    bdp, bdpd = _pp_best(S2h, C2h, V2h, dps, dpds, part_times, period,
                         nbin)
    a2 = _part_shift(bdp, bdpd, part_times, period, nbin)
    prof, csum, vsum = _grid_profiles(S2h, C2h, V2h, a2, nbin)
    chi2 = _chi2_profiles(prof, csum, vsum, nbin)
    # subints at the candidate's NOMINAL parameters (FoldResult
    # contract: the diagnostic subint stack before optimization)
    sub0 = jnp.fft.irfft(
        jnp.einsum("psk,sk->pk", F_stack, _phase(r_dm[j0], nbin)),
        nbin, axis=-1)
    return bdp, bdpd, bj, chi2, prof, sub0


@partial(jax.jit, static_argnames=("nbin", "npart", "L", "j0"))
def _fold_and_optimize_batch(subb, w, bins, r_dm, dps, dpds, periods,
                             part_times,
                             nbin: int, npart: int, L: int, j0: int):
    """The whole tier batch: fold cubes + coordinate descent.

    subb (nsub, npart*L) float32 normalized subbands (zero-padded),
    w (npart*L,) 0/1 valid-sample mask,
    bins (ncand, npart*L) int32 phase bins (shared across subbands),
    r_dm (ncand, nddm, nsub) float32 per-DM-trial subband rotations,
    dps/dpds (ncand, ndp/ndpd) float32 per-candidate offset grids,
    periods (ncand,) float32,
    part_times (npart,) float32 subint mid-times in SECONDS.
    """
    nsub = subb.shape[0]
    ncand = bins.shape[0]

    # per-(part, sub) measured sample stats (candidate-independent)
    subb3 = subb.reshape(nsub, npart, L)
    w3 = w.reshape(npart, L)
    n_p = jnp.maximum(w3.sum(-1), 1.0)                     # (npart,)
    sum_ps = (subb3 * w3[None]).sum(-1)                    # (nsub, npart)
    ssq_ps = (subb3 ** 2 * w3[None]).sum(-1)
    mean_ps = (sum_ps / n_p).T                             # (npart, nsub)
    var_ps = jnp.maximum((ssq_ps / n_p).T - mean_ps ** 2, 1e-9)

    def part_fn(p):
        seg = jax.lax.dynamic_slice(subb, (0, p * L), (nsub, L))
        wseg = jax.lax.dynamic_slice(w, (p * L,), (L,))
        binseg = jax.lax.dynamic_slice(bins, (0, p * L), (ncand, L))
        oh = jax.nn.one_hot(binseg, nbin, dtype=subb.dtype)
        # one-hot matmuls: (nsub, L) @ (ncand, L, nbin) on the MXU
        prof = jnp.einsum("sl,clb->csb", seg, oh)
        cntp = jnp.einsum("l,clb->cb", wseg, oh)
        return prof, cntp

    prof_parts, cnt_parts = jax.lax.map(part_fn, jnp.arange(npart))
    stack = jnp.moveaxis(prof_parts, 0, 1)      # (ncand, npart, nsub, nbin)
    cnt = jnp.moveaxis(cnt_parts, 0, 1)         # (ncand, npart, nbin)

    # center each (subint, subband) on its measured baseline; weight
    # variance by its measured scatter (red-noise robustness — same
    # model as kernels/fold.py)
    stack = stack - mean_ps[None, :, :, None] * cnt[:, :, None, :]

    # one rfft of the folded cubes serves every rotation downstream
    F_stack = jnp.fft.rfft(stack, axis=-1)      # (ncand, npart, nsub, K)
    F_cnt = jnp.fft.rfft(cnt, axis=-1)          # (ncand, npart, K)

    return jax.vmap(
        lambda fs, fc, rd, dp, dpd, per: _optimize_one(
            fs, fc, var_ps, rd, dp, dpd, part_times, per, j0, nbin),
        in_axes=(0, 0, 0, 0, 0, 0),
    )(F_stack, F_cnt, r_dm, dps, dpds, periods)


# --------------------------------------------------------------- host driver

def _sym_grid(extent: int, step: int) -> np.ndarray:
    """Symmetric grid around 0 (0 is always a point) — same
    construction as kernels/fold.py fold_subbands_and_optimize."""
    pos = np.arange(0, extent + 1, step)
    return np.concatenate([-pos[:0:-1], pos]).astype(np.float64)


# Samples a block of the host's phase arithmetic: its four float64
# scratches (1 MiB) stay in the host's L2.  On the chip's host a
# candidate of 2^22 samples takes 11 ms at this size, 9-10 ms at
# 65,536-262,144, 27-49 ms at 2,048 and 174 ms as one full-length block
# (PERF.md section 6, PR 46); a constant, not a knob.
PHASE_BLOCK = 32768


def phase_bins_batch(periods, T: int, Tp: int, dt: float,
                     nbin: int) -> np.ndarray:
    """(len(periods), Tp) int32 phase bins: sample n of candidate i
    falls in bin min(int(frac((n * dt) / period_i) * nbin), nbin - 1),
    in float64 (~T/p turns cannot live in float32); the padded tail
    [T, Tp) is bin 0 (its weight is 0).

    Made block by block in reused scratches, so no full-length float64
    array exists: at T = 2^22 each would be a fresh 32 MiB mapping,
    page-faulted in and unmapped again.  The same operations in the
    same order on the same float64 values as
    ``np.minimum((np.mod(np.arange(T) * dt / p, 1.0) * nbin)
    .astype(np.int32), nbin - 1)``: for x >= 0 ``x - floor(x)`` and
    ``fmod(x, 1.0)`` are both the exact fractional part, so every bin
    is the same bit (tests/test_fold_batch.py holds it)."""
    bins = np.empty((len(periods), Tp), np.int32)
    bins[:, T:] = 0
    base = np.arange(PHASE_BLOCK, dtype=np.float64)
    t = np.empty(PHASE_BLOCK, np.float64)
    x = np.empty(PHASE_BLOCK, np.float64)
    fl = np.empty(PHASE_BLOCK, np.float64)
    for lo in range(0, T, PHASE_BLOCK):
        m = min(PHASE_BLOCK, T - lo)
        tm, xm, fm = t[:m], x[:m], fl[:m]
        # lo + n is an exact integer in float64; the candidates of the
        # chunk share the block's times
        np.add(base[:m], float(lo), out=tm)
        np.multiply(tm, dt, out=tm)
        for i, period in enumerate(periods):
            out = bins[i, lo:lo + m]
            np.divide(tm, period, out=xm)
            np.floor(xm, out=fm)
            np.subtract(xm, fm, out=xm)
            np.multiply(xm, nbin, out=xm)
            np.copyto(out, xm, casting="unsafe")
            np.minimum(out, nbin - 1, out=out)
    return bins


@dataclasses.dataclass(frozen=True)
class _TierGeom:
    """Static grid geometry for one period tier (one compile per
    (tier, T, ncand-bucket))."""
    rules: FoldRules
    ndp: int
    ndpd: int
    nddm: int


def fold_subbands_batch(subbands, sub_freqs_mhz, dt: float,
                        cands: list[tuple[float, float]],
                        rules: FoldRules,
                        max_onehot_bytes: int = 512 << 20,
                        ) -> list[FoldResult]:
    """Fold + optimize a TIER of candidates from one subband block.

    subbands: (nsub, T) stage-1 output at the pass's subdm/downsamp,
    NOT inter-subband aligned (alignment is absorbed into per-subband
    profile rotations).  cands: [(period_s, dm)] sharing `rules`.
    dt: the block's (downsampled) sample interval.

    The candidate batch is chunked so the per-part one-hot stays under
    max_onehot_bytes.
    """
    subb = jnp.asarray(subbands, jnp.float32)
    nsub, T = subb.shape
    rules_nbin, npart = rules.nbin, rules.npart
    # unit variance per subband (chi2 variance-model conditioning)
    subb = (subb - subb.mean(axis=1, keepdims=True)) \
        / jnp.maximum(subb.std(axis=1, keepdims=True), 1e-9)

    # pad T to npart*L
    L = -(-T // npart)
    Tp = npart * L
    if Tp != T:
        subb = jnp.pad(subb, ((0, 0), (0, Tp - T)))
    w = jnp.asarray(
        np.concatenate([np.ones(T, np.float32),
                        np.zeros(Tp - T, np.float32)]))

    sub_freqs = np.asarray(sub_freqs_mhz, np.float64)
    ref_mhz = float(sub_freqs[-1])
    band_span = float(sub_freqs[0] ** -2 - ref_mhz ** -2)
    T_s = T * dt

    delays_unit = KDM * (sub_freqs ** -2 - ref_mhz ** -2)  # s per DM

    out: list[FoldResult] = []
    # chunk candidates to bound the one-hot transient
    per_cand = L * rules_nbin * 4
    max_batch = max(1, int(max_onehot_bytes // per_cand))
    for lo in range(0, len(cands), max_batch):
        chunk = cands[lo: lo + max_batch]
        nc = len(chunk)
        # host half (phase bins and grids in float64), device half
        # (transfers, the fold program, up to its fetches), host half
        # (the results): spans per candidate chunk, never per row
        with trace.span("fold-host", n=nc):
            bins = phase_bins_batch([p for p, _ in chunk], T, Tp, dt,
                                    rules_nbin)
            r_dm_l, dps_l, dpds_l, ddms_l = [], [], [], []
            for period, dm in chunk:
                # grids in profile-bin-drift units (prepfold's unit)
                dp_unit = period ** 2 / (rules_nbin * T_s)
                dpd_unit = 2.0 * period ** 2 / (rules_nbin * T_s ** 2)
                dps = _sym_grid(rules.mp * rules_nbin, rules.pstep) * dp_unit
                if rules.search_pdot:
                    dpds = _sym_grid(rules.mp * rules_nbin,
                                     rules.pdstep) * dpd_unit
                else:
                    dpds = np.zeros(1)
                ddm_unit = period / (rules_nbin * KDM
                                     * max(abs(band_span), 1e-12))
                ddms = _sym_grid(rules.mdm * rules_nbin,
                                 rules.dmstep) * ddm_unit
                # ABSOLUTE per-subband rotation at each DM trial: folding
                # unaligned subbands puts subband s's profile at phase
                # +delay_s/p relative to the aligned fold, so collapsing
                # at trial DM D rolls by +nbin*delay_s(D)/p (the roll
                # convention out[b] = x[b + s])
                D = dm + ddms                                   # (nddm,)
                r_dm = (rules_nbin * delays_unit[None, :]
                        * D[:, None] / period)                  # (nddm, nsub)
                r_dm_l.append(r_dm)
                dps_l.append(dps)
                dpds_l.append(dpds)
                ddms_l.append(ddms)
            j0 = (r_dm_l[0].shape[0] - 1) // 2   # ddm=0 row (center)

            part_times = ((np.arange(npart, dtype=np.float32) + 0.5)
                          * (L * dt))
        with trace.span("fold-device", n=nc):
            bdp, bdpd, bj, chi2, prof, sub0 = _fold_and_optimize_batch(
                subb, w, jnp.asarray(bins),
                jnp.asarray(np.stack(r_dm_l), jnp.float32),
                jnp.asarray(np.stack(dps_l), jnp.float32),
                jnp.asarray(np.stack(dpds_l), jnp.float32),
                jnp.asarray([p for p, _ in chunk], jnp.float32),
                jnp.asarray(part_times),
                nbin=rules_nbin, npart=npart, L=L, j0=j0)
            bdp = np.asarray(bdp, np.float64)
            bdpd = np.asarray(bdpd, np.float64)
            bj = np.asarray(bj)
            chi2 = np.asarray(chi2, np.float64)
            prof = np.asarray(prof)
            sub0 = np.asarray(sub0)
        with trace.span("fold-host", n=nc):
            for i, (period, dm) in enumerate(chunk):
                ddm = float(ddms_l[i][int(bj[i])])
                out.append(FoldResult(
                    period_s=period - float(bdp[i]),
                    pdot=-float(bdpd[i]), dm=dm + ddm,
                    nbin=rules_nbin, npart=npart,
                    profile=prof[i], subints=sub0[i],
                    reduced_chi2=float(chi2[i]),
                    delta_p=float(bdp[i]), delta_pdot=float(bdpd[i]),
                    delta_dm=ddm))
    return out


def fold_candidates_by_pass(data, freqs, dt: float, plan, cand_list,
                            nsub: int, form_subbands_fn):
    """Group candidates by their originating dedispersion pass, form
    each pass's subband block ONCE (same program/shape the search
    passes compiled — a cache hit), tier-group within the pass, and
    batch-fold each tier.

    This mirrors the reference exactly: prepfold folds the PASS's
    subband files at the pass's downsampling, searching DM around the
    candidate (PALFA2_presto_search.py:168-175, :514-529) — it does
    not re-dedisperse the raw data per candidate.

    cand_list: [(k, period_s, dm)] — k is the caller's index, carried
    through so results land back in the caller's order.  nsub: the
    executor's RESOLVED subband count (params.nsub adapted to the
    actual channel count — the plan's own numsub is the survey
    default and can exceed nchan on small beams).
    Returns {k: FoldResult}.
    """
    from tpulsar.kernels import dedisperse as dd

    # candidate -> (step_idx, pass_idx) whose subdm is nearest
    assignments: dict[tuple[int, int], list[tuple[int, float, float]]] = {}
    for k, period, dm in cand_list:
        best = None
        for si, step in enumerate(plan):
            for pi, ppass in enumerate(step.passes()):
                d = abs(dm - ppass.subdm)
                if best is None or d < best[0]:
                    best = (d, si, pi)
        assignments.setdefault((best[1], best[2]), []).append(
            (k, period, dm))

    results: dict[int, FoldResult] = {}
    for (si, pi), group in assignments.items():
        step = plan[si]
        ppass = step.passes()[pi]
        ch_sh, _ = dd.plan_pass_shifts(freqs, nsub, ppass.subdm,
                                       np.asarray(ppass.dms), dt,
                                       step.downsamp)
        with trace.span("fold-device", n=len(group)), \
                trace.span("fold-subbands", downsamp=int(step.downsamp),
                           pass_idx=sum(s.numpasses for s in plan[:si])
                           + pi):
            subb = form_subbands_fn(data, ch_sh, nsub, step.downsamp)
            trace.fence(subb)
        subrefs = dd.subband_reference_freqs(freqs, nsub)
        dt_ds = dt * step.downsamp
        # tier-group: one batch program per FoldRules geometry
        tiers: dict[FoldRules, list[tuple[int, float, float]]] = {}
        for k, period, dm in group:
            tiers.setdefault(fold_rules(period), []).append(
                (k, period, dm))
        for rules, tcands in tiers.items():
            res = fold_subbands_batch(
                subb, subrefs, dt_ds,
                [(p, d) for _, p, d in tcands], rules)
            for (k, _, _), r in zip(tcands, res):
                results[k] = r
        del subb
    return results
