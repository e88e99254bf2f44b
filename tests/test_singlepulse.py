"""Single-pulse search kernel tests."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.kernels import singlepulse as sp


def test_normalize_series():
    rng = np.random.default_rng(0)
    x = (5.0 + 3.0 * rng.standard_normal((2, 4096))).astype(np.float32)
    n = np.asarray(sp.normalize_series(jnp.asarray(x)))
    assert abs(n.mean()) < 0.05
    assert abs(n.std() - 1.0) < 0.05


def test_boxcar_snr_matches_oracle():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, 2048)).astype(np.float32)
    # plant a width-6 pulse
    x[0, 1000:1006] += 4.0
    norm = x - x.mean(axis=-1, keepdims=True)
    norm /= norm.std(axis=-1, keepdims=True)
    snrs, idx = sp.boxcar_search(jnp.asarray(norm), widths=(1, 6), topk=4)
    snrs, idx = np.asarray(snrs), np.asarray(idx)
    # oracle for width 6 at the planted location
    w6 = norm[0, 1000:1006].sum() / np.sqrt(6)
    assert abs(snrs[1, 0, 0] - w6) < 0.05
    assert idx[1, 0, 0] == 1000
    # width-6 filter must beat width-1 on a 6-wide pulse
    assert snrs[1, 0, 0] > snrs[0, 0, 0]


def test_single_pulse_search_event_list():
    rng = np.random.default_rng(2)
    ndms, T, dt = 3, 8192, 1e-3
    x = rng.standard_normal((ndms, T)).astype(np.float32)
    x[1, 5000:5009] += 3.0  # 9-wide pulse in DM row 1
    events = sp.single_pulse_search(jnp.asarray(x), dms=[10.0, 20.0, 30.0],
                                    dt=dt, threshold=5.5)
    assert len(events) >= 1
    best = events[0]
    assert best["dm"] == 20.0
    assert abs(best["time_s"] - 5.0) < 0.02
    assert best["downfact"] >= 6
    assert best["sigma"] > 5.5


def test_write_singlepulse_file(tmp_path):
    events = np.array([(20.0, 7.5, 5.0, 5000, 9)],
                      dtype=[("dm", "f8"), ("sigma", "f8"), ("time_s", "f8"),
                             ("sample", "i8"), ("downfact", "i4")])
    path = tmp_path / "test.singlepulse"
    sp.write_singlepulse_file(str(path), events, 20.0)
    lines = path.read_text().splitlines()
    assert lines[0].startswith("# DM")
    assert "20.00" in lines[1] and "5000" in lines[1]


def test_detrend_estimator_variants_agree_on_pulses():
    """All three baseline estimators must find the same injected
    pulses with SNRs within a few percent on clean data — the
    alternatives exist to dodge the median sort's cost, not to change
    the physics."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    ndms, T, dt = 4, 1 << 15, 1e-3
    series = rng.standard_normal((ndms, T)).astype(np.float32)
    # a slow baseline wander the detrend must remove
    series += 0.5 * np.sin(np.arange(T) / 3000.0)[None, :]
    spots = [5000, 17000, 29000]
    for s in spots:
        series[1, s:s + 4] += 6.0
    dms = np.arange(ndms) * 10.0

    found = {}
    for est in ("median", "median_sub4", "clipped_mean"):
        ev = sp.single_pulse_search(jnp.asarray(series), dms, dt,
                                    estimator=est)
        ev1 = ev[ev["dm"] == 10.0]
        found[est] = {int(e["sample"]) // 32: float(e["sigma"])
                      for e in ev1}
    def _near(d, b):
        """Bucket lookup with +-1 tolerance: a peak one sample before
        a 32-sample bucket boundary can land in the neighbour."""
        return next((d[k] for k in (b, b - 1, b + 1) if k in d), None)

    for s in spots:
        b = s // 32
        sig_med = _near(found["median"], b)
        assert sig_med is not None, (s, found["median"])
        for est in ("median_sub4", "clipped_mean"):
            sig = _near(found[est], b)
            assert sig is not None, (est, s, found[est])
            assert abs(sig - sig_med) / sig_med < 0.05, (est, s)


def test_detrend_env_override(monkeypatch):
    """TPULSAR_SP_DETREND beats the params value (the bench A/B knob)."""
    monkeypatch.setenv("TPULSAR_SP_DETREND", "clipped_mean")
    assert sp.detrend_estimator("median") == "clipped_mean"
    monkeypatch.delenv("TPULSAR_SP_DETREND")
    assert sp.detrend_estimator("median_sub4") == "median_sub4"
    assert sp.detrend_estimator(None) == "median"


def test_detrend_tail_uses_own_length():
    """A tail shorter than detrend_block must be baselined from its
    OWN samples (regression: the old edge-pad reused the last full
    block's baseline, inflating tail sigmas across level drifts)."""
    rng = np.random.default_rng(7)
    blk = 1000
    T = 3 * blk + 137          # non-divisible length -> 137-sample tail
    series = rng.standard_normal((2, T)).astype(np.float32)
    series[:, 3 * blk:] += 50.0   # tail level steps far off the blocks
    out = np.asarray(sp.normalize_series(jnp.asarray(series),
                                         detrend_block=blk))
    # numpy oracle of the fixed behavior
    body = series[:, :3 * blk].reshape(2, 3, blk)
    baseline = np.repeat(np.median(body, axis=-1), blk, axis=-1)
    tail_med = np.median(series[:, 3 * blk:], axis=-1)
    baseline = np.concatenate(
        [baseline, np.repeat(tail_med[:, None], 137, axis=-1)], axis=-1)
    det = series - baseline
    oracle = det / np.maximum(det.std(axis=-1, keepdims=True), 1e-9)
    np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-5)
    # the step must NOT read as a pulse: tail stays near zero mean
    assert abs(np.asarray(out)[:, 3 * blk:].mean()) < 0.5


@pytest.mark.parametrize("widths,platform,want", [
    (sp.DEFAULT_WIDTHS, "tpu", "tiled"), ((1,), "tpu", "tiled"),
    ((1, 128), "tpu", "tiled"), ((1, 129), "tpu", "plain"),
    (sp.DEFAULT_WIDTHS, "cpu", "plain"), ((1, 129), "cpu", "plain"),
])
def test_dispatch_attrs_say_what_boxcar_search_lowers(widths, platform,
                                                      want):
    """sp_form / sp_tile of a chunk's span name the program as it is
    LOWERED for the platform of its operand's devices: the kernel's
    custom call is in boxcar_search's module for a TPU (exported here,
    nothing runs) exactly where sp_dispatch_attrs says "tiled", and
    never in the CPU's."""
    from jax import export

    x = jnp.zeros((6, 9000), jnp.float32)
    got = sp.sp_dispatch_attrs(6, 9000, widths, platform)
    assert got == {"sp_form": want,
                   "sp_tile": sp._SP_TILE if want == "tiled" else 0}
    if platform == "cpu":
        text = sp.boxcar_search.lower(x, widths, 16).as_text()
    else:
        text = export.export(sp.boxcar_search, platforms=(platform,))(
            x, widths, 16).mlir_module()
    assert ("tpu_custom_call" in text) == (want == "tiled")
