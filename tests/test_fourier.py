"""Fourier search kernel tests."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.kernels import fourier as fr


def _tone_series(T=16384, freq_hz=37.0, dt=1e-3, amp=0.5, ndms=3, seed=5):
    rng = np.random.default_rng(seed)
    t = np.arange(T) * dt
    base = rng.standard_normal((ndms, T))
    if ndms > 1:
        base[1] += amp * np.sin(2 * np.pi * freq_hz * t)  # signal in row 1
    return base.astype(np.float32), t


def test_power_spectrum_parseval_and_dc():
    x, _ = _tone_series(amp=0.0, ndms=1)
    p = np.asarray(fr.power_spectrum(jnp.asarray(x)))
    assert p[0, 0] == 0.0
    # Parseval (real FFT): sum powers ~ T * sum x^2 / 2 for non-DC bins
    xs = x[0] - x[0].mean()
    lhs = p[0, 1:-1].sum() + p[0, -1] / 2
    rhs = len(xs) * (xs ** 2).sum() / 2
    assert abs(lhs - rhs) / rhs < 0.01


def test_whiten_flattens_red_noise():
    rng = np.random.default_rng(0)
    T = 1 << 15
    # strongly red spectrum: integrate white noise
    red = np.cumsum(rng.standard_normal(T)).astype(np.float32)[None]
    p = fr.power_spectrum(jnp.asarray(red))
    w = np.asarray(fr.whiten(p))[0]
    lo = np.median(w[10:500])
    hi = np.median(w[-5000:])
    # whitened medians comparable across the band (raw differ by >>10x)
    assert 0.2 < lo / hi < 5.0
    raw = np.asarray(p)[0]
    assert np.median(raw[10:500]) / np.median(raw[-5000:]) > 100


def test_whitened_noise_is_unit_exponential():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 1 << 14)).astype(np.float32)
    w = np.asarray(fr.whiten(fr.power_spectrum(jnp.asarray(x))))
    med = np.median(w[:, 10:])
    assert 0.55 < med < 0.85  # exponential median = ln2 ~ 0.69


def test_tone_detected_with_correct_bin_and_sigma():
    dt = 1e-3
    x, t = _tone_series(T=1 << 14, freq_hz=37.0, dt=dt, amp=0.8)
    T_s = x.shape[1] * dt
    res, nbins = fr.periodicity_search(jnp.asarray(x), T_s, max_numharm=1,
                                       topk=8)
    vals, bins = res[1]
    # bins are interbinned half-bin indices (dr=0.5)
    best_bin = 0.5 * bins[1, 0]
    expect_bin = round(37.0 * T_s)
    assert abs(best_bin - expect_bin) <= 1
    sig_signal = fr.sigma_from_power(vals[1, 0], 1)
    sig_noise = fr.sigma_from_power(vals[0, 0], 1)
    assert sig_signal > 8.0
    assert sig_signal > sig_noise + 4.0


def test_harmonic_sum_strides():
    p = jnp.arange(100, dtype=jnp.float32)[None]
    s2 = np.asarray(fr.harmonic_sum(p, 2))[0]
    # S2(r) = P(r) + P(2r)
    for r in (3, 17, 49):
        assert s2[r] == r + 2 * r


def test_harmonic_summing_helps_narrow_pulses():
    """A narrow periodic pulse train spreads power over harmonics: the
    16-harmonic stage must yield higher summed significance than the
    fundamental alone."""
    rng = np.random.default_rng(2)
    T, dt = 1 << 15, 1e-3
    t = np.arange(T) * dt
    period = 0.25
    phase = (t / period) % 1.0
    sig = (np.exp(-0.5 * ((np.minimum(phase, 1 - phase)) / 0.01) ** 2)).astype(np.float32)
    x = (rng.standard_normal(T).astype(np.float32) + 1.2 * sig)[None]
    res, _ = fr.periodicity_search(jnp.asarray(x), T * dt, max_numharm=16,
                                   topk=8)
    # bins are half-bin indices (interbinned grid): the fundamental
    # sits at half-index 2 * T_s / period
    fund_bin = round(2 * T * dt / period)
    # find the candidate at the fundamental in stage 1 and stage 16
    def power_at(stage):
        vals, bins = res[stage]
        hit = np.abs(bins[0] - fund_bin) <= 2
        return vals[0][hit].max() if hit.any() else 0.0
    s1 = fr.sigma_from_power(power_at(1), 1)
    s16 = fr.sigma_from_power(power_at(16), 16)
    assert s16 > s1


def test_zap_mask(tmp_path):
    zap = np.array([[60.0, 1.0]])
    T_s = 100.0
    mask = fr.zap_mask(10000, T_s, zap, baryv=0.0)
    df = 1 / T_s
    assert not mask[int(60.0 / df)]
    assert mask[int(50.0 / df)]
    # barycentric shift moves the zapped window
    mask2 = fr.zap_mask(10000, T_s, zap, baryv=1e-3)
    assert not mask2[int(60.0 / (1 + 1e-3) / df)]

    # file parsing
    p = tmp_path / "test.zaplist"
    p.write_text("# comment\n60.0 1.0\n120.0 2.0  # another\n")
    parsed = fr.parse_zaplist(str(p))
    np.testing.assert_allclose(parsed, [[60.0, 1.0], [120.0, 2.0]])


def test_sigma_from_power_reference_values():
    # P(S>s)=exp(-s) for 1 harmonic: s=10 -> p=4.54e-5 -> sigma~3.91
    assert abs(fr.sigma_from_power(10.0, 1) - 3.906) < 0.01
    # large power must not overflow to inf
    big = fr.sigma_from_power(1000.0, 16)
    assert np.isfinite(big) and big > 30
    # threshold inversion round-trips
    thr = fr.power_threshold(6.0, 8)
    assert abs(fr.sigma_from_power(thr, 8) - 6.0) < 1e-3


def test_whitened_spectrum_fusion_matches_sequence():
    """The fused pad->rfft->whiten->scale program must reproduce the
    separate-call sequence to float32 rounding (XLA refuses the math
    across the fusion boundary, so bit-identity is not expected),
    with and without a zaplist keep-mask."""
    import numpy as np
    import jax.numpy as jnp
    from tpulsar.kernels import fourier as fr

    rng = np.random.default_rng(3)
    series = jnp.asarray(rng.normal(size=(3, 1000)).astype(np.float32))
    nfft = 1024
    nbins = nfft // 2 + 1

    spec = fr.complex_spectrum(fr.pad_series(series, nfft))
    powers, wpow = fr.whitened_powers(spec)
    want = np.asarray(fr.scale_spectrum(spec, powers, wpow))
    got = np.asarray(fr.whitened_spectrum(series, nfft=nfft))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    keep = np.ones(nbins, bool)
    keep[100:120] = False
    powers, wpow = fr.whitened_powers(spec, jnp.asarray(keep))
    want = np.asarray(fr.scale_spectrum(spec, powers, wpow))
    got = np.asarray(fr.whitened_spectrum_masked(
        series, jnp.asarray(keep), nfft=nfft))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.all(got[:, 100:120] == 0)


def _whiten_blocks(edges, nbins):
    """(lo, hi, in_tail) of every block the level is estimated over:
    the tests' own copy of whiten_powers' geometry."""
    blocks = [(int(lo), int(hi), False)
              for lo, hi in zip(edges[:-1], edges[1:])]
    lo, size = int(edges[-1]), fr.MAX_WHITEN_BLOCK
    while nbins - lo >= size:
        blocks.append((lo, lo + size, True))
        lo += size
    if nbins - lo > 16:
        blocks.append((lo, nbins, False))
    return blocks


def _interp_whiten_oracle(powers, edges, estimator="median"):
    """The original formulation of whiten_powers: block levels, then
    a per-row jnp.interp between the block centres."""
    import jax

    nbins = powers.shape[-1]
    blocks = _whiten_blocks(edges, nbins)
    med = jnp.stack(
        [fr._block_level(powers[..., lo:hi], estimator) if in_tail
         else jnp.median(powers[..., lo:hi], axis=-1) / jnp.log(2.0)
         for lo, hi, in_tail in blocks], axis=-1)
    med = jnp.maximum(med, 1e-30)
    carr = jnp.asarray([0.5 * (lo + hi) for lo, hi, _ in blocks],
                       dtype=jnp.float32)
    bins = jnp.arange(nbins, dtype=jnp.float32)
    level = jax.vmap(lambda mrow: jnp.interp(bins, carr, mrow))(
        med.reshape(-1, med.shape[-1])).reshape(
            powers.shape[:-1] + (nbins,))
    return np.asarray(powers / level)


# (leading shape, nbins, estimator): the head's last edge is 17715,
# then m whole tail blocks of 8192 and a remainder block if rem > 16
WHITEN_GEOMETRIES = [
    pytest.param((2,), 7, "median", id="one-centre"),
    pytest.param((2,), 8, "median", id="two-centres"),
    pytest.param((2,), 20, "median", id="three-centres"),
    pytest.param((3,), 9000, "median", id="head-cut-short"),
    pytest.param((3,), 17725, "median", id="head-only-rem10"),
    pytest.param((3,), 21715, "median", id="head-rem4000"),
    pytest.param((3,), 25917, "median", id="one-tail-block-rem10"),
    pytest.param((3,), 25924, "median", id="one-tail-block-rem17"),
    pytest.param((3,), 34115, "median", id="two-tail-blocks-rem16"),
    pytest.param((3,), 40000, "median", id="two-tail-blocks"),
    pytest.param((2,), 245761, "median", id="mock-like"),
    pytest.param((2,), 262145, "median", id="wapp-like"),
    pytest.param((2, 3), 40000, "median", id="batch-2x3"),
    pytest.param((), 40000, "median", id="one-row"),
    pytest.param((2,), 60000, "clipped_mean", id="clipped-mean-tail"),
]


@pytest.mark.parametrize("lead,nbins,estimator", WHITEN_GEOMETRIES)
def test_whiten_level_matches_interp(lead, nbins, estimator):
    """The level assembled from the static block geometry
    (_level_pieces: slices of the block levels against weight ramps)
    must equal jnp.interp bin-for-bin, at every shape of pieces: a
    head alone, an end segment longer or shorter than a remainder
    block, one tail block (no broadcast piece), many."""
    rng = np.random.default_rng(41)
    red = 1.0 + 40.0 / np.sqrt(np.arange(1, nbins + 1))
    powers = jnp.asarray((rng.exponential(size=lead + (nbins,))
                          * red).astype(np.float32))
    edges = tuple(int(e) for e in fr._block_edges(nbins))
    got = np.asarray(fr.whiten_powers(powers, edges,
                                      estimator=estimator))
    want = _interp_whiten_oracle(powers, edges, estimator)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("nbins", [8, 9000, 25917, 40000, 262145])
def test_level_pieces_tile_the_spectrum(nbins):
    """The static pieces cover every bin once, in order, with weights
    in [0, 1]; the equal-width tail segments are ONE piece."""
    edges = fr._block_edges(nbins)
    blocks = _whiten_blocks(edges, nbins)
    centers = [0.5 * (lo + hi) for lo, hi, _ in blocks]
    m = sum(in_tail for _, _, in_tail in blocks)
    rem_centre = not blocks[-1][2]
    pieces = fr._level_pieces(centers, nbins)
    assert sum(n * len(ramp) for _, n, ramp in pieces) == nbins
    assert sum(n for _, n, _ in pieces) == len(centers) - 1
    k_next = 0
    for k, n, ramp in pieces:
        assert k == k_next and ramp.dtype == np.float32
        assert ramp.min() >= 0.0 and ramp.max() <= 1.0
        k_next += n
    if m > 2:
        # the last tail centre's segment is the end segment (longer,
        # its weight clipped) unless a remainder centre follows it
        assert max(n for _, n, _ in pieces) == m - (1 if rem_centre else 2)
        assert len(pieces) <= len(edges) + 2


def _nbins_sized(text, nbins):
    """Lines of a lowered program holding a loop, or a gather whose
    result has a dimension of nbins."""
    bad = []
    for line in text.splitlines():
        if "stablehlo.while" in line:
            bad.append(line.strip()[:200])
        elif "gather" in line and "stablehlo." in line:
            result = line.rsplit("->", 1)[-1]
            if f"{nbins}x" in result:
                bad.append(line.strip()[:80] + " ... -> " + result)
    return bad


def test_whitening_programs_hold_no_search():
    """The whitening's level comes from static slices: the lowered
    programs hold no loop (a binary search over the constant centres
    ran on the chip in every chunk program call until PR 32) and no
    per-bin gather."""
    import jax

    nsamp, nfft = 70000, 80000
    nbins = nfft // 2 + 1
    series = jax.ShapeDtypeStruct((3, nsamp), jnp.float32)
    keep = jax.ShapeDtypeStruct((nbins,), jnp.bool_)
    edges = tuple(int(e) for e in fr._block_edges(nbins))
    texts = {
        "whitened_spectrum": fr.whitened_spectrum.lower(
            series, nfft=nfft).as_text(),
        "whitened_spectrum_masked": fr.whitened_spectrum_masked.lower(
            series, keep, nfft=nfft).as_text(),
        "_whiten_powers_jit": fr._whiten_powers_jit.lower(
            jax.ShapeDtypeStruct((3, nbins), jnp.float32), edges,
            "median").as_text(),
    }
    for name, text in texts.items():
        assert "stablehlo.divide" in text, name
        assert _nbins_sized(text, nbins) == [], name
    # the check sees the form it guards against
    searched = jax.jit(lambda c, b: c[jnp.searchsorted(c, b)]).lower(
        jax.ShapeDtypeStruct((21,), jnp.float32),
        jax.ShapeDtypeStruct((nbins,), jnp.float32)).as_text()
    assert len(_nbins_sized(searched, nbins)) >= 2


def test_whiten_clipped_mean_estimator():
    """The sort-free clipped-mean block estimator agrees with the
    median estimator within a few percent on clean exponential noise,
    stays robust to a bright birdie, and rejects unknown names."""
    import pytest
    import jax.numpy as jnp
    from tpulsar.kernels import fourier as fr

    rng = np.random.default_rng(43)
    nbins = 60000
    powers = rng.exponential(2.5, size=(2, nbins)).astype(np.float32)
    powers[0, 30000] = 4000.0          # a birdie
    pj = jnp.asarray(powers)
    edges = tuple(int(e) for e in fr._block_edges(nbins))
    w_med = np.asarray(fr.whiten_powers(pj, edges,
                                        estimator="median"))
    w_cm = np.asarray(fr.whiten_powers(pj, edges,
                                       estimator="clipped_mean"))
    # whitened level ~1: compare the estimators through the result,
    # away from the log-spaced head where blocks are tiny
    sl = slice(20000, 60000)
    ratio = np.median(w_med[1, sl]) / np.median(w_cm[1, sl])
    assert 0.97 < ratio < 1.03, ratio
    # the birdie must not drag its block's level far from the
    # median's robust estimate
    blk = slice(30000 - 2000, 30000 + 2000)
    r2 = np.median(w_med[0, blk]) / np.median(w_cm[0, blk])
    assert 0.9 < r2 < 1.1, r2

    with pytest.raises(ValueError):
        fr.whiten_powers(pj, edges, estimator="bogus")
