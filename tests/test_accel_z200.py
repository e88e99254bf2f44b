"""The deep acceleration search (accelsearch -zmax 200 -numharm 16:
BASELINE config 3) at toy widths on the CPU: a pulsar drifting |z| =
100 bins through ``search_block``, the chunk program's plane against
the per-DM one at nz 201, and the rows a chunk program is given.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpulsar.kernels import accel
from tpulsar.kernels import accel_batch as abp

# the survey's spectrum lengths at downsamp 1 (Mock, WAPP)
MOCK_NBINS = 1_966_081
WAPP_NBINS = 2_097_153
ROWS_NZ51 = 1       # a TPU's rows a chunk program at zmax 50 (see below)


# ----------------------------------------------- |z| = 100 in search_block

@pytest.fixture(scope="module")
def drifting_beam():
    """(block, freqs, dt, plan, T_s, psr per drift sign): a toy beam
    with one pulsar whose fundamental drifts 100 Fourier bins — twice
    the edge of a zmax 50 bank; its second harmonic sits on the edge of
    a zmax 200 bank."""
    from benchmark.harness import generate
    from tpulsar.plan import ddplan

    nchan, nsamp, dt = 32, 1 << 15, 1e-3
    freqs = generate.channel_freqs(1400.0, 100.0, nchan)
    T_s = ddplan.choose_n(nsamp) * dt
    plan = [ddplan.DedispStep(lodm=0.0, dmstep=2.0, dms_per_pass=8,
                              numpasses=1, numsub=16, downsamp=1)]
    beams = {}
    for z in (100.0, -100.0):
        period = T_s / (round(T_s / 0.02) - 0.5 * z)
        psr = generate.Pulsar(period_s=period, dm=6.0, duty=0.03, z=z,
                              amp=1.0)
        beams[z] = (psr, generate.make_block(27, psr, freqs, dt, nsamp,
                                             T_s))
    return beams, freqs, dt, plan, T_s


def _recovery(drifting_beam, z, zmax, numharm):
    from benchmark.harness import check
    from tpulsar.search import executor

    beams, freqs, dt, plan, T_s = drifting_beam
    psr, block = beams[z]
    params = executor.SearchParams(
        nsub=16, hi_accel_zmax=zmax, hi_accel_numharm=numharm,
        topk_per_stage=16, max_cands_to_fold=0, make_plots=False)
    cands, _folded, _events, ntrials = executor.search_block(
        block, freqs, dt, plan, params)
    assert ntrials == 8
    got = check.recovery(cands, psr, T_s, check.pass_table(plan), True,
                         {"period_frac_err": 1e-3, "z_err_bins": 2.0})
    return {n.name: n for n in got}, cands


@pytest.mark.parametrize("z", [100.0, -100.0])
def test_pulsar_at_z100_is_returned_by_the_deep_search(drifting_beam, z):
    got, cands = _recovery(drifting_beam, z, zmax=200, numharm=16)
    assert got["pulsar_missing"].value == 0.0
    assert got["pulsar_period_frac_err"].ok
    assert got["pulsar_z_err_bins"].value <= 2.0
    best = max(cands, key=lambda c: c.sigma)
    assert abs(best.z - z) <= 2.0 and best.sigma > 30.0


@pytest.mark.parametrize("z", [100.0, -100.0])
def test_pulsar_at_z100_is_not_found_with_the_bank_cut_to_zmax50(
        drifting_beam, z):
    """The survey's default depth cannot return it: nothing at its
    frequency within 2 bins of its drift (what is left of it, if
    anything, comes back smeared at z = 0 from the lo stage)."""
    got, cands = _recovery(drifting_beam, z, zmax=50, numharm=8)
    assert not all(n.ok for n in got.values())
    assert all(abs(c.z) <= 50.0 for c in cands)


# ------------------------------------------------ the plane at nz 201

@pytest.fixture
def chip_settings(monkeypatch):
    """What the chip resolves: a bf16 plane and the direct correlation
    (the FFT form's pieces of 4 z rows reach none of its programs)."""
    monkeypatch.setattr(accel, "_PLANE_DTYPE_RESOLVED", jnp.bfloat16)
    monkeypatch.setattr(accel, "_Z_CHUNK_RESOLVED", 4)
    monkeypatch.setattr(accel, "corr_form", lambda: "direct")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("zmax,nz", [(8.0, 9), (50.0, 51), (200.0, 201)])
@pytest.mark.parametrize("zc", [4, 16])
def test_chunk_programs_plane_is_the_per_dm_plane(monkeypatch, dtype,
                                                  zmax, nz, zc):
    """_correlate_block (the chunk program's plane: pieces of zc bank
    rows, transposed, concatenated, padded) against _correlate_segments
    row by row (the per-DM ladder's plane, one segment at a time
    against the whole bank): the same (nd, nz, 2*nbins) plane at every
    depth, where zc divides nz - 1 and where the last piece is short,
    the width offset where it belongs."""
    monkeypatch.setattr(accel, "_PLANE_DTYPE_RESOLVED", jnp.dtype(dtype))
    monkeypatch.setattr(accel, "_Z_CHUNK_RESOLVED", zc)
    bank = accel.build_template_bank(zmax, seg=1 << 11)
    assert len(bank.zs) == nz
    rng = np.random.default_rng(int(zmax) + zc)
    nbins = 5003
    specs = jnp.asarray((rng.normal(size=(2, nbins))
                         + 1j * rng.normal(size=(2, nbins))
                         ).astype(np.complex64))
    specs = specs.at[0, 777].add(30.0)
    bank_fft = jnp.asarray(bank.bank_fft)
    # un-jitted: the static z_chunk() read must not come from a cached
    # trace of another case
    got = accel._correlate_block.__wrapped__(
        specs, bank_fft, bank.seg, bank.step, bank.width, nz)
    want = jnp.stack([accel._correlate_segments.__wrapped__(
        row, bank_fft, bank.seg, bank.step, bank.width) for row in specs])
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    assert got.shape == want.shape == (2, nz, 2 * nbins)
    got32 = np.asarray(got.astype(jnp.float32))
    want32 = np.asarray(want.astype(jnp.float32))
    # one storage ulp: the two forms batch their FFTs differently
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -20
    np.testing.assert_allclose(got32, want32, rtol=ulp, atol=1e-4)
    assert want32.max() > 100.0 and np.all(got32[:, :, :bank.width] == 0)


# ------------------------------------------- rows per chunk program

# Since PR 30 a TPU's chunk program makes its plane by the direct form
# (accel.corr_plane): the complex64 overlap-save intermediates and the
# plane's second copy are gone, so a row is counted as the plane and
# ~56 B a bin beside it (the compiler's figures: tests/
# test_chip_compile.py), and the 4 GiB budget holds 7 rows at nz 51 (2
# until then) and 2 at nz 201 (1 until then).  The chip found no use for
# them in a chunk program (one row a program was as fast at nz 51 and 4%
# faster at nz 201: accel.PLANE_ROWS_DIRECT), which is given 1 at both
# depths; the DM-sharded mesh program, whose rows share every stage of
# one program, takes what the budget holds (max_chunk=32).

@pytest.mark.parametrize("nbins", [MOCK_NBINS, WAPP_NBINS])
def test_rows_per_program_at_the_surveys_default_depth(chip_settings,
                                                       nbins):
    assert accel.plane_dm_chunk(nbins, 51) == ROWS_NZ51
    assert abp.batch_rows(38, nbins, 51) == ROWS_NZ51
    # a CPU process keeps the FFT form and its count
    assert accel.plane_row_bytes(nbins, 51, 4) > 2 * accel.plane_row_bytes(
        nbins, 51, None)


def test_rows_at_zmax_200_fit_by_their_own_count(chip_settings):
    nz = len(accel.z_grid(200.0))
    assert nz == 201
    assert accel.plane_dm_chunk(MOCK_NBINS, nz) == 1
    assert abp.batch_rows(38, MOCK_NBINS, nz) == 1
    assert accel.plane_dm_chunk(MOCK_NBINS, nz, max_chunk=32) == 2
    row = accel.plane_row_bytes(MOCK_NBINS, nz, None)
    assert (2 * row * (1 + accel.PLANE_COUNT_SLACK)
            <= accel.PLANE_HBM_BUDGET < 3 * row)
    # the plane once (1.58 GB): the kernel writes it and the harmonic
    # sums read it in place; no piece, no second copy, no z-piece term
    assert row == nz * 2 * MOCK_NBINS * 2 + MOCK_NBINS * 56


def test_the_count_is_a_rung_of_the_planners_ladder(chip_settings,
                                                    monkeypatch):
    """plane_dm_chunk says what a program is GIVEN (the benchmark's
    cost function reads it as such): 7 rows fit the budget at nz 51,
    the planner would dispatch 6."""
    row = accel.plane_row_bytes(MOCK_NBINS, 51, None)
    assert int(accel.PLANE_HBM_BUDGET
               // (row * (1 + accel.PLANE_COUNT_SLACK))) == 7
    assert accel.plane_dm_chunk(MOCK_NBINS, 51, max_chunk=32) == 6
    assert (accel.plane_dm_chunk(MOCK_NBINS, 51)
            == accel.PLANE_ROWS_DIRECT == 1)


def test_a_row_too_large_is_refused_on_a_tpu_and_held_by_the_host(
        chip_settings, monkeypatch):
    """Never 1 for a row reckoned too large where the budget is device
    memory; on the CPU the row lives in host RAM, as it always did."""
    nz = 201
    row = accel.plane_row_bytes(MOCK_NBINS, nz, None)
    monkeypatch.setattr(accel, "PLANE_HBM_BUDGET", row)     # no slack
    assert jax.default_backend() == "cpu"
    assert accel.plane_dm_chunk(MOCK_NBINS, nz) == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="TPULSAR_ACCEL_HBM_GB"):
        accel.plane_dm_chunk(MOCK_NBINS, nz)
    monkeypatch.setattr(accel, "PLANE_HBM_BUDGET", 2 * row)
    assert accel.plane_dm_chunk(MOCK_NBINS, nz) == 1


def test_a_deep_cpu_plane_is_not_refused(monkeypatch):
    """A float32 plane at the Mock width and zmax 300 (nz 301) is over
    the 4 GiB the TPU's budget assumes: the CPU still searches it, one
    row a program."""
    monkeypatch.setattr(accel, "_PLANE_DTYPE_RESOLVED", jnp.float32)
    monkeypatch.setattr(accel, "_Z_CHUNK_RESOLVED", 16)
    assert (accel.plane_row_bytes(MOCK_NBINS, 301, 16)
            > accel.PLANE_HBM_BUDGET)
    assert accel.plane_dm_chunk(MOCK_NBINS, 301) == 1


# ------------------------------------------------ spans and counters

def test_spans_say_rows_form_and_path(drifting_beam, monkeypatch):
    """accel-dispatch carries nz and corr (the correlation's form as
    this platform lowers it), dm_chunk the rows per
    chunk program as dispatched, and every trial is counted on path
    `batched` (docs/operations.md, "Where a slow beam's seconds are")."""
    from tpulsar.obs import telemetry, trace
    from tpulsar.search import executor

    # the chip's hi-accel path (chunk programs dispatched and drained),
    # not the CPU backend's native consumer
    monkeypatch.setenv("TPULSAR_ACCEL_NATIVE", "0")
    beams, freqs, dt, plan, _T_s = drifting_beam
    _psr, block = beams[100.0]
    params = executor.SearchParams(
        nsub=16, hi_accel_zmax=200, hi_accel_numharm=16,
        topk_per_stage=16, max_dms_per_chunk=4, max_cands_to_fold=0,
        make_plots=False)
    counter = telemetry.accel_batch_trials_total()
    before = {p: counter.value(path=p)
              for p in ("batched", "per_dm", "rescued")}
    trace.reset()
    trace.start()
    try:
        executor.search_block(block, freqs, dt, plan, params)
        events = trace.events()
    finally:
        trace.reset()
    after = {p: counter.value(path=p) for p in before}
    assert after["batched"] - before["batched"] == 8
    assert after["per_dm"] == before["per_dm"]
    assert after["rescued"] == before["rescued"]
    chunks = [e for e in events if e["name"] == "dm_chunk"]
    dispatches = [e for e in events if e["name"] == "accel-dispatch"]
    assert len(chunks) == len(dispatches) == 2
    nbins = (1 << 15) // 2 + 1
    for ch, d in zip(chunks, dispatches):
        hi_rows = ch["args"]["hi_rows"]
        assert hi_rows == abp.batch_rows(ch["args"]["n"], nbins, 201) >= 1
        assert d["args"]["rows"] == ch["args"]["n"] == 4
        assert d["args"]["chunks"] == -(-4 // hi_rows)
        assert d["args"]["nz"] == 201
        assert d["args"]["corr"] == accel.corr_form() == "fft"
        assert "zpieces" not in d["args"]


def test_dm_chunk_says_no_hi_rows_with_hi_accel_off(drifting_beam):
    from tpulsar.obs import trace
    from tpulsar.search import executor

    beams, freqs, dt, plan, _T_s = drifting_beam
    params = executor.SearchParams(
        nsub=16, run_hi_accel=False, topk_per_stage=16,
        max_cands_to_fold=0, make_plots=False)
    trace.reset()
    trace.start()
    try:
        executor.search_block(beams[100.0][1], freqs, dt, plan, params)
        events = trace.events()
    finally:
        trace.reset()
    chunks = [e for e in events if e["name"] == "dm_chunk"]
    assert chunks and all(e["args"]["hi_rows"] == 0 for e in chunks)
    assert not [e for e in events if e["name"] == "accel-dispatch"]


def test_dm_chunk_says_the_lo_form(drifting_beam):
    """dm_chunk carries lo_form / lo_tile, the lo stage's harmonic sums
    as the dispatched program is lowered: the strided form under
    JAX_PLATFORMS=cpu (`tiled` and the kernel's tile on a TPU:
    fourier.lo_dispatch_attrs), docs/operations.md."""
    from tpulsar.obs import trace
    from tpulsar.search import executor

    beams, freqs, dt, plan, _T_s = drifting_beam
    params = executor.SearchParams(
        nsub=16, run_hi_accel=False, topk_per_stage=16,
        max_cands_to_fold=0, make_plots=False)
    trace.reset()
    trace.start()
    try:
        executor.search_block(beams[100.0][1], freqs, dt, plan, params)
        chunks = [e["args"] for e in trace.events()
                  if e["name"] == "dm_chunk"]
    finally:
        trace.reset()
    assert chunks and {(a["lo_form"], a["lo_tile"]) for a in chunks} == {
        ("strided", 0)}
    # ... and sp_form / sp_tile, the boxcar ladder's (the plain chain
    # here: singlepulse.sp_dispatch_attrs)
    assert {(a["sp_form"], a["sp_tile"]) for a in chunks} == {("plain", 0)}


def test_dm_chunk_says_stage2_calls_and_rows(drifting_beam, monkeypatch):
    """dm_chunk carries dd_calls x dd_rows, the stage-2 program calls
    for the chunk and the rows of a call as dispatched: no padded row
    (docs/operations.md); both 0 where the XLA scan is the path."""
    from tpulsar.kernels import pallas_dd
    from tpulsar.obs import trace
    from tpulsar.search import executor

    beams, freqs, dt, plan, _T_s = drifting_beam
    params = executor.SearchParams(
        nsub=16, run_hi_accel=False, topk_per_stage=16,
        max_cands_to_fold=0, make_plots=False)

    def chunks():
        trace.reset()
        trace.start()
        try:
            executor.search_block(beams[100.0][1], freqs, dt, plan, params)
            return [e["args"] for e in trace.events()
                    if e["name"] == "dm_chunk"]
        finally:
            trace.reset()

    assert not pallas_dd.use_pallas()                   # CPU CI
    assert {(a["dd_calls"], a["dd_rows"]) for a in chunks()} == {(0, 0)}
    monkeypatch.setenv("TPULSAR_PALLAS", "1")           # interpreted
    got = chunks()
    assert got
    for a in got:
        assert a["dd_calls"] == -(-a["n"] // pallas_dd.STAGE2_MAX_ROWS)
        assert a["dd_rows"] == -(-a["n"] // a["dd_calls"])
        assert 0 <= a["dd_calls"] * a["dd_rows"] - a["n"] < a["dd_calls"]
    # the wrapper writes them, so a chunk the kernel did not run says 0
    monkeypatch.setattr(pallas_dd, "signature_enabled", lambda sig: False)
    assert {(a["dd_calls"], a["dd_rows"]) for a in chunks()} == {(0, 0)}
