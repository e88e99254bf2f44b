"""Tier-batched fold kernel (kernels/fold_batch.py): parity with the
per-candidate fold path, batch-size invariance, and injected-pulsar
recovery through the pass-grouped driver."""

import numpy as np
import pytest

from tpulsar.constants import KDM
from tpulsar.kernels import dedisperse as dd
from tpulsar.kernels import fold as fold_k
from tpulsar.kernels import fold_batch as fb

NSUB, T, DT = 8, 1 << 13, 5e-4
P_TRUE, DM_TRUE = 0.15, 60.0
FREQS = np.linspace(1214.0, 1536.0, 64)


def _subrefs():
    return dd.subband_reference_freqs(FREQS, NSUB)


def _synth(snr=4.0, seed=0):
    """Unaligned subband block with a dispersed pulsar."""
    rng = np.random.default_rng(seed)
    subrefs = _subrefs()
    t = np.arange(T) * DT
    subb = rng.normal(0, 1, (NSUB, T)).astype(np.float32)
    delays = KDM * DM_TRUE * (subrefs ** -2 - subrefs[-1] ** -2)
    for s in range(NSUB):
        ph = np.mod((t - delays[s]) / P_TRUE, 1.0)
        subb[s] += snr * np.exp(
            -0.5 * (np.minimum(ph, 1 - ph) / 0.03) ** 2)
    return subb, delays


def test_matches_per_candidate_fold_path():
    """The batch kernel and kernels/fold.py agree on the optimized
    candidate (their rotation schemes differ — fractional FFT vs
    integer bins — so agreement is to grid-step tolerance)."""
    subb, delays = _synth()
    rules = fold_k.fold_rules(P_TRUE)
    r_new = fb.fold_subbands_batch(subb, _subrefs(), DT,
                                   [(P_TRUE, DM_TRUE)], rules)[0]
    sub_sh0 = np.round(delays / DT).astype(np.int64)
    r_old = fold_k.fold_subbands_and_optimize(
        subb, _subrefs(), DT, P_TRUE, DM_TRUE, rules=rules,
        sub_shifts_dm0=sub_sh0)
    T_s = T * DT
    dp_step = P_TRUE ** 2 / (rules.nbin * T_s)
    # the old path rounds rotations to whole bins and can wander a
    # couple of grid steps off the truth; the FFT path must be at
    # least as close
    assert abs(r_new.period_s - r_old.period_s) <= 4 * dp_step
    assert abs(r_new.period_s - P_TRUE) <= abs(r_old.period_s - P_TRUE)
    assert abs(r_new.reduced_chi2 - r_old.reduced_chi2) \
        <= 0.05 * r_old.reduced_chi2
    # both must see a very strong detection
    assert r_new.reduced_chi2 > 50


def test_exact_parameters_need_no_offset():
    """Folding at the true (p, DM) must optimize to zero offsets —
    the FFT rotations are exact, so nothing should beat the truth."""
    subb, _ = _synth()
    rules = fold_k.fold_rules(P_TRUE)
    r = fb.fold_subbands_batch(subb, _subrefs(), DT,
                               [(P_TRUE, DM_TRUE)], rules)[0]
    assert r.delta_p == 0.0
    assert r.delta_dm == 0.0


def test_recovers_offset_parameters():
    """A candidate handed in slightly off in (p, DM) is pulled back
    toward the truth by the coordinate descent — to within the DM
    grid's resolution (at this short observation one DM grid step is
    ~1.4 DM units, so an offset of 1.0 is sub-resolution)."""
    subb, _ = _synth(snr=8.0)
    rules = fold_k.fold_rules(P_TRUE)
    subrefs = _subrefs()
    band_span = abs(subrefs[0] ** -2 - subrefs[-1] ** -2)
    ddm_step = (P_TRUE / (rules.nbin * KDM * band_span)) * rules.dmstep
    # offset by 3 period-grid steps (an offset under half a step is
    # sub-resolution: the grid correctly stays at zero)
    dp_step = P_TRUE ** 2 / (rules.nbin * T * DT)
    p_off = P_TRUE + 3 * dp_step
    r = fb.fold_subbands_batch(subb, subrefs, DT,
                               [(p_off, DM_TRUE + 1.0)], rules)[0]
    assert abs(r.period_s - P_TRUE) <= 1.5 * dp_step
    assert abs(r.dm - DM_TRUE) <= 1.0 + 2 * ddm_step
    assert r.reduced_chi2 > 50


def test_batch_equals_singles():
    """One batched call == per-candidate calls (same tier)."""
    subb, _ = _synth()
    rules = fold_k.fold_rules(P_TRUE)
    cands = [(P_TRUE, DM_TRUE), (P_TRUE * 1.001, DM_TRUE + 2.0),
             (P_TRUE * 0.999, DM_TRUE - 2.0)]
    batch = fb.fold_subbands_batch(subb, _subrefs(), DT, cands, rules)
    for cand, rb in zip(cands, batch):
        rs = fb.fold_subbands_batch(subb, _subrefs(), DT, [cand],
                                    rules)[0]
        assert rb.period_s == pytest.approx(rs.period_s, rel=1e-6)
        assert rb.dm == pytest.approx(rs.dm, abs=1e-6)
        assert rb.reduced_chi2 == pytest.approx(rs.reduced_chi2,
                                                rel=1e-4)


def test_no_pdot_tier_has_flat_pdot_axis():
    """Slow-pulsar tier (p >= 0.5 s) must not search pdot
    (reference rule: RFI-prone slow folds, PALFA2_presto_search.py:
    195-211)."""
    rng = np.random.default_rng(1)
    subb = rng.normal(0, 1, (NSUB, T)).astype(np.float32)
    rules = fold_k.fold_rules(0.8)
    assert not rules.search_pdot
    r = fb.fold_subbands_batch(subb, _subrefs(), DT, [(0.8, 10.0)],
                               rules)[0]
    assert r.delta_pdot == 0.0


def test_pass_grouped_driver(tmp_path):
    """fold_candidates_by_pass folds candidates from their plan
    pass's subband geometry and returns results keyed by caller
    index."""
    import jax.numpy as jnp

    from tpulsar.plan import ddplan

    rng = np.random.default_rng(2)
    nchan, nsamp, dt = 64, 1 << 13, 5e-4
    freqs = np.linspace(1214.0, 1536.0, nchan)
    t = np.arange(nsamp) * dt
    data = rng.normal(8, 2, (nchan, nsamp)).astype(np.float32)
    delays = KDM * DM_TRUE * (freqs ** -2 - freqs[-1] ** -2)
    for c in range(nchan):
        ph = np.mod((t - delays[c]) / P_TRUE, 1.0)
        data[c] += 5.0 * np.exp(
            -0.5 * (np.minimum(ph, 1 - ph) / 0.03) ** 2)

    plan = [ddplan.DedispStep(lodm=0.0, dmstep=2.0, dms_per_pass=38,
                              numpasses=2, numsub=NSUB, downsamp=1)]
    results = fb.fold_candidates_by_pass(
        jnp.asarray(data), freqs, dt, plan,
        [(0, P_TRUE, DM_TRUE), (1, 2 * P_TRUE, DM_TRUE)], NSUB,
        lambda d, ch_sh, ns, ds: dd.form_subbands(
            d, jnp.asarray(ch_sh), ns, ds))
    assert set(results) == {0, 1}
    r = results[0]
    assert abs(r.dm - DM_TRUE) < 4.0
    assert r.reduced_chi2 > 20
    # the fundamental should beat the 2x-period alias
    assert r.reduced_chi2 > results[1].reduced_chi2


# ------------------------------------------------- the host's phase bins

def _plain_bins(periods, T, Tp, dt, nbin):
    """The formula `fb.phase_bins_batch` replaced (PR 46), full-length
    float64 arrays and all: the plain reference its bins are held to,
    bit for bit."""
    bins = np.zeros((len(periods), Tp), np.int32)
    for i, p in enumerate(periods):
        bins[i, :T] = np.minimum(
            (np.mod(np.arange(T) * dt / p, 1.0) * nbin).astype(np.int32),
            nbin - 1)
    return bins


_B = fb.PHASE_BLOCK
_DT_MOCK, _DT_WAPP, _DT_GPPS = 65.476e-6, 64e-6, 49.152e-6
# one period of each `fold_rules` tier (nbin 24, 50, 100, 200) and a
# second slow one
_PERIODS = [1.57e-3, 12.3e-3, 0.15, 1.23, 4.7]
# (id, T, dt, Tp - T): the cells' fold lengths (Mock ds=1, WAPP, Mock
# ds=2, FAST's pulsar at ds=2), and the block loop's edges
_LENGTHS = [
    ("mock_ds1", 3_932_160, _DT_MOCK, 0),
    ("wapp", 4_194_304, _DT_WAPP, 16),
    ("mock_ds2", 1_966_080, 2 * _DT_MOCK, 0),
    ("gpps_ds2", 3_051_520, 2 * _DT_GPPS, 20),
    ("under_a_block", 1000, _DT_WAPP, 0),
    ("one_block", _B, _DT_WAPP, 0),
    ("block_multiple_plus_1", 3 * _B + 1, _DT_MOCK, 0),
    ("block_multiple_minus_1", 3 * _B - 1, _DT_MOCK, 0),
    ("padded_tail", 100_003, _DT_MOCK, 17),
    ("padded_tail_under_a_block", 8191, 5e-4, 29),
]
_BIN_CASES = [
    pytest.param([p], T, T + pad, dt, id=f"{name}-p{p:g}")
    for name, T, dt, pad in _LENGTHS for p in _PERIODS
] + [
    pytest.param([12.3e-3, 12.31e-3], 2 * _B + 5, 2 * _B + 40, _DT_WAPP,
                 id="two_in_a_chunk"),
    pytest.param([0.15, 0.1501, 0.31], 5 * _B - 3, 5 * _B + 27,
                 _DT_MOCK, id="three_in_a_chunk"),
    pytest.param([1.57e-3, 1.9e-3], 4_194_304, 4_194_350, _DT_WAPP,
                 id="two_in_a_chunk_wapp"),
]


@pytest.mark.parametrize("periods, T, Tp, dt", _BIN_CASES)
def test_phase_bins_equal_the_full_length_formula(periods, T, Tp, dt):
    """Block by block in a reused scratch, the same bits as the
    full-length float64 arrays: every bin, the padded tail 0."""
    nbin = fold_k.fold_rules(periods[0]).nbin
    got = fb.phase_bins_batch(periods, T, Tp, dt, nbin)
    assert got.dtype == np.int32 and got.shape == (len(periods), Tp)
    assert np.array_equal(got, _plain_bins(periods, T, Tp, dt, nbin))


def test_phase_bins_hold_no_full_length_float64():
    """At WAPP's T = 2^22 (where a float64 array is a 32 MiB mapping)
    the host half peaks at `bins` itself and its block scratches."""
    import tracemalloc

    T, npart = 1 << 22, 40
    Tp = npart * -(-T // npart)
    tracemalloc.start()
    try:
        bins = fb.phase_bins_batch([12.3e-3], T, Tp, _DT_WAPP, 50)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak <= bins.nbytes + (2 << 20), (peak, bins.nbytes)


def test_fold_results_identical_with_the_full_length_formula(monkeypatch):
    """The fold of the file's pulsar gives the same results, bit for
    bit, with the bins made block by block (three ragged blocks here)
    and by the formula they replaced, in one process: nothing recorded
    that another machine's XLA could miss."""
    subb, _ = _synth()
    rules = fold_k.fold_rules(P_TRUE)
    cands = [(P_TRUE, DM_TRUE), (P_TRUE * 1.001, DM_TRUE + 2.0)]
    monkeypatch.setattr(fb, "PHASE_BLOCK", 3000)
    new = fb.fold_subbands_batch(subb, _subrefs(), DT, cands, rules)
    monkeypatch.setattr(fb, "phase_bins_batch", _plain_bins)
    old = fb.fold_subbands_batch(subb, _subrefs(), DT, cands, rules)
    for rn, ro in zip(new, old):
        assert (rn.period_s, rn.dm, rn.reduced_chi2, rn.pdot) \
            == (ro.period_s, ro.dm, ro.reduced_chi2, ro.pdot)
        assert np.array_equal(rn.profile, ro.profile)
        assert np.array_equal(rn.subints, ro.subints)
