"""Dedispersion kernel tests against the exact NumPy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.io import synth
from tpulsar.kernels import dedisperse as dd
from tpulsar.plan import ddplan

# The Mock survey plan's own passes at the benchmark's beam geometry:
# the first and the deepest ds=1 pass (shifts to 119 and 3387 samples)
# and one pass of each deeper downsampling class.  Random tables miss
# what real ones have: monotone ramps, long runs of equal shifts,
# shifts near the series' length.
SURVEY_GEOMS = [(0, 0), (0, 27), (1, 3), (3, 4), (5, 0)]
SURVEY_FREQS = (1375.5 - 322.617 / 2) + (np.arange(960) + 0.5) * (
    322.617 / 960)
SURVEY_DT = 65.476e-6


def _survey_pass(step_idx, pass_idx):
    """(step, pass, channel shifts, subband shifts) of one pass of
    ddplan.survey_plan("pdev")."""
    step = ddplan.survey_plan("pdev")[step_idx]
    ppass = step.passes()[pass_idx]
    ch_sh, sub_sh = dd.plan_pass_shifts(
        SURVEY_FREQS, step.numsub, ppass.subdm, np.asarray(ppass.dms),
        SURVEY_DT, step.downsamp)
    return step, ppass, ch_sh, sub_sh


def _beam(nchan=32, nsamp=4096, dm=50.0, period=0.2, snr=3.0, seed=3):
    spec = synth.BeamSpec(nchan=nchan, nsamp=nsamp, seed=seed)
    psr = synth.PulsarSpec(period_s=period, dm=dm, snr_per_sample=snr)
    data = synth.make_dynamic_spectrum(spec, pulsars=[psr])
    return spec, psr, data.T.astype(np.float32)  # (nchan, T)


def test_shift_tables_sane():
    freqs = np.linspace(1200.0, 1500.0, 32)
    shifts = dd.shift_samples(100.0, freqs, freqs[-1], 1e-3)
    assert shifts[-1] == 0
    assert np.all(np.diff(shifts) <= 0)  # lower freq -> larger delay
    assert shifts[0] > 0


def _two_stage_oracle(data, freqs, nsub, subdm, dms, dt, downsamp):
    """NumPy replica of form_subbands + dedisperse_subbands using the
    same shift tables — must match the kernel exactly."""
    chan_shifts, sub_shifts = dd.plan_pass_shifts(
        freqs, nsub, subdm, dms, dt, downsamp)
    nchan, T = data.shape
    shifted = np.empty_like(data)
    for c in range(nchan):
        idx = np.minimum(np.arange(T) + chan_shifts[c], T - 1)
        shifted[c] = data[c, idx]
    subb = shifted.reshape(nsub, nchan // nsub, T).sum(1)
    if downsamp > 1:
        subb = subb[:, : (T // downsamp) * downsamp]
        subb = subb.reshape(nsub, -1, downsamp).sum(-1)
    Tp = subb.shape[1]
    out = []
    for k in range(len(sub_shifts)):
        ts = np.zeros(Tp)
        for s in range(nsub):
            idx = np.minimum(np.arange(Tp) + sub_shifts[k, s], Tp - 1)
            ts += subb[s, idx]
        out.append(ts)
    return np.stack(out)


def test_two_stage_matches_numpy_oracle():
    """The jitted two-stage kernel must match a NumPy replica of the
    same algorithm bit-for-bit (modulo float accumulation order)."""
    spec, psr, data = _beam()
    freqs = synth.channel_freqs(spec)
    dms = np.array([45.0, 50.0, 55.0])
    out = np.asarray(dd.dedisperse_pass(
        jnp.asarray(data), freqs, nsub=8, subdm=50.0, dms=dms,
        dt=spec.tsamp_s, downsamp=2))
    oracle = _two_stage_oracle(data, freqs, 8, 50.0, dms,
                               spec.tsamp_s, 2)
    np.testing.assert_allclose(out, oracle, rtol=2e-4, atol=2e-3)


def _toy_pass():
    """A 32-channel beam with a pulsar, one trial at the pulsar's DM:
    (data, freqs, dt, nsub, downsamp, subdm, dms, least correlation)."""
    spec, psr, data = _beam()
    return (data, synth.channel_freqs(spec), spec.tsamp_s, 8, 1,
            psr.dm, np.array([psr.dm]), 0.95)


def _survey_noise_pass(step_idx, pass_idx, T=4096):
    """White noise at the Mock beam's channels under one pass of the
    survey plan.  On white noise the two roundings (channel within
    subband, subband within band) cost a channel up to a sample, which
    decorrelates per-sample values but not by much; a wrong table
    would leave nothing."""
    step, ppass, _ch_sh, _sub_sh = _survey_pass(step_idx, pass_idx)
    rng = np.random.default_rng(11 + step_idx)
    data = rng.standard_normal(
        (960, T * step.downsamp)).astype(np.float32)
    return (data, SURVEY_FREQS, SURVEY_DT, step.numsub, step.downsamp,
            ppass.subdm, np.asarray(ppass.dms), 0.7)


@pytest.mark.parametrize("make,args", [
    pytest.param(_toy_pass, (), id="toy-beam")] + [
    pytest.param(_survey_noise_pass, g, id="survey-step%d-pass%d" % g)
    for g in SURVEY_GEOMS])
def test_two_stage_close_to_exact_at_subdm(make, args):
    """At the trial that IS the pass's sub-DM, stage 1 and the XLA
    scan must track the exact single-stage oracle closely (double
    rounding costs at most one sample per channel, decorrelating only
    the per-channel noise): a toy beam, and the survey plan's own
    tables."""
    data, freqs, dt, nsub, ds, subdm, dms, least = make(*args)
    i_sub = int(np.argmin(np.abs(dms - subdm)))
    assert dms[i_sub] == pytest.approx(subdm, abs=1e-9)
    ch_sh, sub_sh = dd.plan_pass_shifts(freqs, nsub, subdm, dms, dt, ds)
    subb = dd.form_subbands(jnp.asarray(data), jnp.asarray(ch_sh),
                            nsub, ds)
    got = np.asarray(dd._dedisperse_subbands_scan(
        subb, jnp.asarray(sub_sh[i_sub:i_sub + 1]),
        dd._pad_bucket(int(sub_sh[i_sub].max()))))[0]
    oracle = dd.dedisperse_exact(data, freqs, [subdm], dt, ds)[0]
    valid = (data.shape[1]
             - dd.max_shift_samples(freqs, subdm, dt) - 1) // ds
    assert valid > 500
    assert np.corrcoef(got[:valid], oracle[:valid])[0, 1] > least


def test_dedispersed_pulse_recovery():
    """S/N of the folded profile must peak at the true DM."""
    spec, psr, data = _beam(dm=60.0, snr=1.5)
    freqs = synth.channel_freqs(spec)
    dms = np.array([0.0, 30.0, 60.0, 90.0, 120.0])
    out = np.asarray(dd.dedisperse_pass(
        jnp.asarray(data), freqs, nsub=8, subdm=60.0, dms=dms,
        dt=spec.tsamp_s, downsamp=1))
    nbin = int(round(psr.period_s / spec.tsamp_s))
    contrasts = []
    for ts in out:
        prof = ts[: (len(ts) // nbin) * nbin].reshape(-1, nbin).mean(0)
        contrasts.append((prof.max() - np.median(prof)) / prof.std())
    assert int(np.argmax(contrasts)) == 2


def test_downsampling_sums():
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 12)
    y = np.asarray(dd.downsample(x, 3))
    assert y.shape == (2, 4)
    np.testing.assert_allclose(y[0], [0 + 1 + 2, 3 + 4 + 5, 6 + 7 + 8, 9 + 10 + 11])


def test_form_subbands_shapes_and_zero_dm():
    spec, _, data = _beam(dm=0.0, snr=0.0)
    freqs = synth.channel_freqs(spec)
    chan_shifts, sub_shifts = dd.plan_pass_shifts(
        freqs, nsub=8, subdm=0.0, dms=[0.0], dt=spec.tsamp_s, downsamp=4)
    assert np.all(chan_shifts == 0)
    assert np.all(sub_shifts == 0)
    subb = dd.form_subbands(jnp.asarray(data), jnp.asarray(chan_shifts),
                            nsub=8, downsamp=4)
    assert subb.shape == (8, data.shape[1] // 4)
    # zero-DM subbands are plain channel-group sums then time sums
    oracle = data.reshape(8, 4, -1).sum(1)
    oracle = oracle.reshape(8, -1, 4).sum(-1)
    np.testing.assert_allclose(np.asarray(subb), oracle, rtol=1e-4, atol=1e-4)


def test_two_stage_error_bounded_across_pass():
    """Across a pass (DMs straddling the subdm), the two-stage result
    must stay close to the exact oracle: the residual subband smearing
    is bounded by the plan's budget."""
    spec, psr, data = _beam(dm=45.0, snr=2.0, nsamp=8192)
    freqs = synth.channel_freqs(spec)
    dms = np.arange(40.0, 50.1, 2.0)
    subdm = 45.0
    fast = np.asarray(dd.dedisperse_pass(
        jnp.asarray(data), freqs, nsub=8, subdm=subdm, dms=dms,
        dt=spec.tsamp_s, downsamp=1))
    oracle = dd.dedisperse_exact(data, freqs, dms, spec.tsamp_s)
    valid = data.shape[1] - dd.max_shift_samples(freqs, dms.max(), spec.tsamp_s) - 1
    for i in range(len(dms)):
        c = np.corrcoef(fast[i, :valid], oracle[i, :valid])[0, 1]
        assert c > 0.90, f"DM {dms[i]}: corr {c}"


def test_window_scan_matches_subband_scan():
    """dedisperse_window_scan on a pre-extended window equals the
    edge-padded stage-2 scan (they share the accumulation; the window
    variant is the halo-exchange building block)."""
    rng = np.random.default_rng(11)
    nsub, T, ndms = 8, 1024, 5
    subb = rng.standard_normal((nsub, T)).astype(np.float32)
    shifts = (rng.integers(0, 64, size=(ndms, nsub))).astype(np.int32)
    want = np.asarray(dd._dedisperse_subbands_xla(jnp.asarray(subb),
                                                  shifts))
    # window = subbands + 64-sample edge-replicated halo
    ext = np.concatenate([subb, np.repeat(subb[:, -1:], 64, axis=1)],
                         axis=1)
    got = np.asarray(dd.dedisperse_window_scan(
        jnp.asarray(ext), jnp.asarray(shifts), T))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_pad_bucket_zero_shift_pads_nothing():
    """maxshift == 0 must yield a ZERO pad bucket (regression: the
    bucket floor of 256 padded 256 samples per row on zero-shift
    passes, widening the whole block for gathers that always start
    at 0), while any positive shift keeps the >=256 bucket ladder."""
    assert dd._pad_bucket(0) == 0
    assert dd._pad_bucket(-3) == 0
    assert dd._pad_bucket(1) == 256
    assert dd._pad_bucket(256) == 256
    assert dd._pad_bucket(257) == 512

    # _edge_pad with pad=0 is the identity (no zero-width concat)
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    assert dd._edge_pad(x, 0) is x

    # a zero-shift pass end-to-end: stage 1 + stage 2 at pad 0 equal
    # the plain channel-group sums (and compile with pad=0 statics)
    spec, _, data = _beam(dm=0.0, snr=0.0)
    nchan, T = data.shape
    zero = np.zeros(nchan, np.int32)
    subb = dd.form_subbands(jnp.asarray(data), zero, nsub=8,
                            downsamp=1)
    np.testing.assert_allclose(np.asarray(subb),
                               data.reshape(8, nchan // 8, T).sum(1),
                               rtol=1e-4, atol=1e-4)
    out = dd.dedisperse_subbands(subb, np.zeros((3, 8), np.int32))
    np.testing.assert_allclose(
        np.asarray(out),
        np.broadcast_to(np.asarray(subb).sum(0), (3, T)),
        rtol=1e-5, atol=1e-3)

    # zero shifts through the host gather entry point too
    same = dd._shift_gather(jnp.asarray(data), zero)
    np.testing.assert_array_equal(np.asarray(same), data)


def test_shift_rows_clamps_and_matches_reference():
    """_shift_rows (edge-pad + dynamic slice) == the index formula
    out[i,t] = data[i, min(t+s, T-1)], including shifts at/above pad."""
    rng = np.random.default_rng(12)
    data = rng.standard_normal((4, 257)).astype(np.float32)
    shifts = np.array([0, 3, 255, 256], dtype=np.int32)
    got = np.asarray(dd._shift_gather(jnp.asarray(data), shifts))
    T = data.shape[1]
    idx = np.minimum(np.arange(T)[None, :] + shifts[:, None], T - 1)
    want = np.take_along_axis(data, idx, axis=1)
    np.testing.assert_allclose(got, want)


def _sequential_sum(subb, shifts):
    """out[d, t] = sum_s subb[s, min(t + shift[d, s], T-1)], float32
    additions in subband order from zero: what the stage-2 kernel must
    equal element for element."""
    nsub, T = subb.shape
    t = np.arange(T)
    out = np.zeros((shifts.shape[0], T), np.float32)
    for d in range(shifts.shape[0]):
        for s in range(nsub):
            out[d] += subb[s, np.minimum(t + shifts[d, s], T - 1)]
    return out


def _random_table(rows, nsub, T, smax):
    rng = np.random.default_rng(rows * 1000 + nsub)
    subb = rng.standard_normal((nsub, T)).astype(np.float32)
    shifts = rng.integers(0, smax + 1, size=(rows, nsub)).astype(np.int32)
    shifts[0, 0] = smax
    shifts[-1, -1] = 0
    shifts[rows // 2, :] = smax
    return subb, shifts


def _survey_table(step_idx, pass_idx, T=4096):
    _step, _ppass, _ch_sh, shifts = _survey_pass(step_idx, pass_idx)
    rng = np.random.default_rng(3)
    subb = rng.standard_normal((shifts.shape[1], T)).astype(np.float32)
    return subb, shifts.astype(np.int32)


# random tables of (rows, nsub, T, largest shift), named for what the
# case is there for, then the survey plan's own
_RANDOM_TABLES = {
    "19-rows-mock-subbands": (19, 96, 1100, 119),
    "a-full-call": (32, 96, 1100, 119),
    "19-rows-wapp-subbands": (19, 64, 1100, 100),
    "one-row": (1, 16, 3000, 128),
    "overhang-of-several-segments": (3, 8, 1500, 700),
    "ragged-T-and-shift-equal-to-S": (5, 8, 1237, 256),
    "shifts-that-clamp-at-the-edge": (3, 4, 400, 350),
    "segment-2048-odd-unroll": (4, 6, 20000, 1000),
    "two-calls-of-19": (38, 8, 1200, 200),
    "three-calls-26-26-24": (76, 8, 1100, 300),
    "segment-4096-in-column-pieces": (2, 4, 40000, 16384),
}
_STAGE2_CASES = [pytest.param(_random_table, args, id=name)
                 for name, args in _RANDOM_TABLES.items()] + [
    pytest.param(_survey_table, g, id="survey-step%d-pass%d" % g)
    for g in SURVEY_GEOMS]


@pytest.mark.parametrize("table,args", _STAGE2_CASES)
def test_pallas_dedisperse_equals_sequential_sum(table, args):
    """The chip's stage-2 form (the Pallas kernel, interpret mode
    off-TPU) and the one XLA form (the scan every other platform
    runs) against the sequential float32 sum in subband order, EQUAL
    element for element: the golden candidate lists hang on these
    bits."""
    from tpulsar.kernels import pallas_dd

    subb, shifts = table(*args)
    want = _sequential_sum(subb, shifts)
    got = np.asarray(pallas_dd.dedisperse_subbands_pallas(
        subb, shifts, interpret=True))
    np.testing.assert_array_equal(got, want)
    scan = np.asarray(dd._dedisperse_subbands_scan(
        jnp.asarray(subb), jnp.asarray(shifts),
        dd._pad_bucket(int(shifts.max()))))
    np.testing.assert_array_equal(scan, want)


def test_pallas_dedisperse_in_subband_groups_equals_sequential_sum(
        monkeypatch):
    """Where a tile of all subbands does not fit the VMEM budget (a
    fold's series at a very deep overhang) they go through VMEM in
    groups, summed into the same output block in subband order: the
    same bits."""
    from tpulsar.kernels import pallas_dd

    nsub, T, rows, smax = 8, 1500, 3, 300
    whole = pallas_dd.stage2_plan(nsub, 512, rows, T)
    assert whole.group == nsub
    monkeypatch.setattr(pallas_dd, "STAGE2_VMEM_BUDGET",
                        (4 << 20) + 150_000)
    plan = pallas_dd.stage2_plan(nsub, 512, rows, T)
    assert 1 < plan.group < nsub and plan.group % plan.unroll == 0
    rng = np.random.default_rng(5)
    subb = rng.standard_normal((nsub, T)).astype(np.float32)
    shifts = rng.integers(0, smax + 1, size=(rows, nsub)).astype(np.int32)
    got = np.asarray(pallas_dd.dedisperse_subbands_pallas(
        subb, shifts, interpret=True))
    np.testing.assert_array_equal(got, _sequential_sum(subb, shifts))


def test_pallas_dedisperse_traced_shifts_equal_sequential_sum():
    """The mesh path hands the kernel TRACED shifts under a static
    overhang (parallel/mesh.py::_pallas_dd_local): same bits."""
    import jax
    import jax.numpy as jnp
    from tpulsar.parallel import mesh as pmesh

    rng = np.random.default_rng(23)
    nsub, T, rows = 8, 1500, 35
    subb = rng.standard_normal((nsub, T)).astype(np.float32)
    shifts = rng.integers(0, 301, size=(rows, nsub)).astype(np.int32)
    fn = jax.jit(lambda a, b: pmesh._pallas_dd_local(a, b, 512, True))
    got = np.asarray(fn(jnp.asarray(subb), jnp.asarray(shifts)))
    np.testing.assert_array_equal(got, _sequential_sum(subb, shifts))


def test_pallas_dedisperse_matches_gather():
    """The Pallas kernel must agree with the XLA gather formulation
    (the product path off the TPU)."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels.dedisperse import _dedisperse_subbands_xla

    rng = np.random.default_rng(7)
    nsub, T, ndms = 16, 1500, 9
    subb = rng.standard_normal((nsub, T)).astype(np.float32)
    shifts = rng.integers(0, 300, size=(ndms, nsub)).astype(np.int32)
    shifts[:, 0] = 0
    shifts[2, 5] = 299

    want = np.asarray(_dedisperse_subbands_xla(jnp.asarray(subb),
                                               jnp.asarray(shifts)))
    got = np.asarray(pallas_dd.dedisperse_subbands_pallas(
        subb, shifts, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_stage2_row_split_never_pads():
    """A chunk's rows go through ceil(n / 32) calls of ceil(n / calls)
    rows and no call is padded up: rows a call is the harness's own
    reckoning (benchmark/harness/runner.py: chunk / ceil(chunk / 32))
    for every chunk size pass_chunk_size gives on the three benchmark
    configurations."""
    import math
    from benchmark.harness import cells
    from tpulsar.kernels import pallas_dd
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    def split(n):
        plan = pallas_dd.stage2_plan(96, 256, n, 3_932_160)
        rows = plan.call_rows(n)
        assert len(rows) == plan.calls and max(rows) == plan.rows
        return rows

    assert split(38) == [19, 19]
    assert split(64) == [32, 32]
    assert split(32) == [32]
    assert split(1) == [1]
    assert split(6) == [6]
    assert split(76) == [26, 26, 24]
    for n in range(1, 200):
        rows = split(n)
        assert sum(rows) == n and max(rows) <= 32      # no padded row
        assert len(rows) == math.ceil(n / 32)
        assert len(rows) * max(rows) - n < len(rows)   # within one a call

    chunks = set()
    for name in ("mock_ds1_hiaccel", "wapp_steps_noaccel",
                 "z200_ds1_hiaccel", "mock_steps_noaccel"):
        cell = cells.load_cell(name)
        params = cells.search_params(cell)
        for step in ddplan.survey_plan(cell.config["backend"]):
            nfft = ddplan.choose_n(cell.nsamp // step.downsamp)
            chunks.add(executor.pass_chunk_size(step.dms_per_pass, nfft,
                                                params))
    assert {38, 64, 76} <= chunks
    for chunk in chunks:
        rows = split(chunk)
        harness = chunk / math.ceil(chunk / 32)
        assert sum(rows) / len(rows) == pytest.approx(harness)
        assert max(rows) == math.ceil(harness)


def test_pallas_dedisperse_edge_clamp():
    """Shifts that run past the end must clamp to the last sample,
    matching the gather semantics."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels.dedisperse import _dedisperse_subbands_xla

    nsub, T = 4, 400
    subb = np.arange(nsub * T, dtype=np.float32).reshape(nsub, T)
    shifts = np.full((3, nsub), 350, dtype=np.int32)
    shifts[1] = 0
    want = np.asarray(_dedisperse_subbands_xla(jnp.asarray(subb),
                                               jnp.asarray(shifts)))
    got = np.asarray(pallas_dd.dedisperse_subbands_pallas(
        subb, shifts, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_pallas_form_subbands_matches_xla():
    """The stage-1 Pallas kernel must agree with the XLA lax.map
    formulation (interpret mode off-TPU): native uint8 input, shift
    clamp, downsampling, and the floor-truncating tail."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels.dedisperse import _form_subbands_jit, _pad_bucket

    rng = np.random.default_rng(13)
    nchan, T, nsub = 32, 1500, 8
    data = rng.integers(0, 255, size=(nchan, T), dtype=np.uint8)
    shifts = rng.integers(0, 290, size=nchan).astype(np.int32)
    shifts[::nchan // nsub] = 0      # one zero per subband group
    for downsamp in (1, 2, 3):
        pad = _pad_bucket(int(shifts.max()))
        want = np.asarray(_form_subbands_jit(
            jnp.asarray(data), jnp.asarray(shifts), nsub, downsamp,
            pad))
        got = np.asarray(pallas_dd.form_subbands_pallas(
            data, shifts, nsub, downsamp, block_t=1024,
            interpret=True))
        assert got.shape == want.shape, downsamp
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3,
                                   err_msg=f"downsamp={downsamp}")


def test_pallas_form_subbands_edge_clamp():
    """Shifted reads past the end clamp to each channel's last sample,
    matching the XLA edge-pad semantics, including float32 input."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels.dedisperse import _form_subbands_jit, _pad_bucket

    nchan, T, nsub = 8, 400, 4
    data = np.arange(nchan * T, dtype=np.float32).reshape(nchan, T)
    shifts = np.full(nchan, 350, dtype=np.int32)
    shifts[1] = 0
    pad = _pad_bucket(int(shifts.max()))
    want = np.asarray(_form_subbands_jit(
        jnp.asarray(data), jnp.asarray(shifts), nsub, 1, pad))
    got = np.asarray(pallas_dd.form_subbands_pallas(
        data, shifts, nsub, 1, block_t=1024, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_form_subbands_dispatch_fallback(monkeypatch):
    """form_subbands off-TPU uses the XLA path (no degraded note);
    TPULSAR_PALLAS_SB=1 forces the Pallas tier through the dispatch
    wrapper and both agree."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd

    rng = np.random.default_rng(17)
    nchan, T, nsub = 16, 900, 4
    data = rng.integers(0, 255, size=(nchan, T), dtype=np.uint8)
    shifts = rng.integers(0, 200, size=nchan).astype(np.int32)

    monkeypatch.delenv("TPULSAR_PALLAS_SB", raising=False)
    base = np.asarray(dd.form_subbands(jnp.asarray(data), shifts,
                                       nsub, 2))
    monkeypatch.setenv("TPULSAR_PALLAS_SB", "1")
    # off-TPU the forced path runs in interpret mode via
    # form_subbands_pallas(interpret=None)
    forced = np.asarray(dd.form_subbands(jnp.asarray(data), shifts,
                                         nsub, 2))
    np.testing.assert_allclose(forced, base, rtol=1e-5, atol=1e-3)
    # the comparison is only meaningful if the Pallas tier actually
    # ran: a throw inside the try would silently fall back to XLA
    # and compare XLA to XLA
    from tpulsar.search import degraded

    sig = ("sb", tuple(data.shape), nsub, 2)
    assert pallas_dd.signature_enabled(sig), pallas_dd._DISABLED_SIGS
    assert "pallas_sb_disabled" not in degraded.snapshot()
    # TPULSAR_PALLAS=1 (the CI no-fallback contract) must force the
    # stage-1 tier on as well, not leave it behind the smoke gate
    monkeypatch.delenv("TPULSAR_PALLAS_SB", raising=False)
    monkeypatch.setenv("TPULSAR_PALLAS", "1")
    assert pallas_dd.use_pallas_sb()


def test_pallas_form_subbands_slabbed_matches_single():
    """The time-slabbed sweep (bounding the staged copy's HBM) must
    agree exactly with the single-slab result, including slab
    boundaries where a slab reads its successor's samples and the
    final slab edge-pads."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd

    rng = np.random.default_rng(47)
    nchan, T, nsub = 16, 3000, 4
    data = rng.integers(0, 255, size=(nchan, T), dtype=np.uint8)
    shifts = rng.integers(0, 290, size=nchan).astype(np.int32)
    one = np.asarray(pallas_dd.form_subbands_pallas(
        data, shifts, nsub, 1, block_t=1024, interpret=True))
    # tiny budget -> many slabs (block_t=1024, nchan=16: slab_t=1024)
    many = np.asarray(pallas_dd.form_subbands_pallas(
        data, shifts, nsub, 1, block_t=1024, interpret=True,
        slab_bytes=16 * 1024))
    np.testing.assert_array_equal(one, many)
    # downsampling composes with slabs
    one_ds = np.asarray(pallas_dd.form_subbands_pallas(
        data, shifts, nsub, 3, block_t=1024, interpret=True))
    many_ds = np.asarray(pallas_dd.form_subbands_pallas(
        data, shifts, nsub, 3, block_t=1024, interpret=True,
        slab_bytes=16 * 1024))
    np.testing.assert_array_equal(one_ds, many_ds)


def test_pallas_gates_are_backend_and_env_only(monkeypatch):
    """No probe, no memo: the Pallas tiers are on exactly on a TPU
    backend, unless switched off (or forced on) by env."""
    from tpulsar.kernels import pallas_dd

    monkeypatch.delenv("TPULSAR_PALLAS", raising=False)
    monkeypatch.delenv("TPULSAR_PALLAS_SB", raising=False)
    assert not pallas_dd.is_tpu_backend()           # CPU CI
    assert not pallas_dd.use_pallas()
    assert not pallas_dd.use_pallas_sb()
    monkeypatch.setattr(pallas_dd, "is_tpu_backend", lambda: True)
    assert pallas_dd.use_pallas() and pallas_dd.use_pallas_sb()
    monkeypatch.setenv("TPULSAR_PALLAS_SB", "0")
    assert pallas_dd.use_pallas() and not pallas_dd.use_pallas_sb()
    monkeypatch.setenv("TPULSAR_PALLAS", "0")
    assert not pallas_dd.use_pallas()


def test_pallas_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    import pytest
    from tpulsar.kernels import pallas_dd

    assert pallas_dd._resolve_interpret(None) is True    # CPU CI
    assert pallas_dd._resolve_interpret(False) is False
    monkeypatch.setattr(pallas_dd, "is_tpu_backend", lambda: True)
    assert pallas_dd._resolve_interpret(None) is False
    with pytest.raises(AssertionError, match="interpret"):
        pallas_dd._resolve_interpret(True)
    with pytest.raises(AssertionError, match="interpret"):
        pallas_dd.dedisperse_subbands_pallas(
            np.zeros((8, 256), np.float32), np.zeros((2, 8), np.int32),
            interpret=True)
