"""Acceleration search kernel tests."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.kernels import accel


def _chirp_series(T=1 << 15, dt=1e-3, f0=40.0, fdot=0.0, amp=0.6, seed=3):
    """Time series with a linearly drifting tone; drift in bins over
    the observation is z = fdot * T_s^2."""
    rng = np.random.default_rng(seed)
    t = np.arange(T) * dt
    phase = 2 * np.pi * (f0 * t + 0.5 * fdot * t * t)
    x = rng.standard_normal(T).astype(np.float32) + amp * np.sin(phase)
    return x.astype(np.float32), T * dt


def test_z_grid():
    zs = accel.z_grid(50.0)
    assert zs[0] == -50.0 and zs[-1] == 50.0
    assert 0.0 in zs
    assert np.all(np.diff(zs) == accel.DZ)


def test_z_response_normalization():
    """Responses carry (nearly) unit total power."""
    for z in (0.0, 10.0, -30.0):
        resp = accel.gen_z_response(z, accel.template_width(50.0))
        assert abs(np.sum(np.abs(resp) ** 2) - 1.0) < 0.05, f"z={z}"


def test_zero_z_response_is_delta():
    resp = accel.gen_z_response(0.0, 64)
    assert np.argmax(np.abs(resp)) == 32
    assert np.abs(resp[32]) > 0.99


def test_stationary_tone_found_at_z0():
    x, T_s = _chirp_series(fdot=0.0, amp=0.8)
    spec = jnp.fft.rfft(jnp.asarray(x - x.mean()))
    spec = accel.normalize_spectrum(spec)
    bank = accel.build_template_bank(16.0, seg=1 << 11)
    res = accel.accel_search_one(spec, bank, max_numharm=1, topk=8)
    vals, rbins, zvals = res[1]
    true_r = round(40.0 * T_s)
    best = np.argmax(vals)
    # rbins are numbetween=2 half-bin indices (PRESTO ACCEL_DR=0.5)
    assert abs(0.5 * int(rbins[best]) - true_r) <= 1
    assert abs(zvals[best]) <= accel.DZ


def test_drifting_tone_recovered_at_correct_z():
    """A tone drifting z~12 bins is invisible at z=0 but recovered by
    the matching template."""
    T, dt = 1 << 15, 1e-3
    T_s = T * dt
    z_true = 12.0
    fdot = z_true / T_s ** 2
    x, _ = _chirp_series(T=T, dt=dt, f0=40.0, fdot=fdot, amp=0.8)
    spec = jnp.fft.rfft(jnp.asarray(x - x.mean()))
    spec = accel.normalize_spectrum(spec)
    bank = accel.build_template_bank(24.0, seg=1 << 11)
    res = accel.accel_search_one(spec, bank, max_numharm=1, topk=8)
    vals, rbins, zvals = res[1]
    best = np.argmax(vals)
    # mean frequency over the obs: f0 + fdot*T/2 -> bin f0*T + z/2
    true_r = 40.0 * T_s + z_true / 2
    assert abs(zvals[best] - z_true) <= accel.DZ
    assert abs(0.5 * rbins[best] - true_r) <= 2
    # the z=0 response to the same signal is much weaker
    zi0 = list(bank.zs).index(0.0)
    plane = accel._correlate_segments(
        jnp.asarray(np.asarray(spec), np.complex64),
        jnp.asarray(bank.bank_fft), bank.seg, bank.step, bank.width)
    plane = np.asarray(plane)
    r_idx = int(round(2 * true_r))     # half-bin plane index
    zi_best = int(np.argmin(np.abs(np.asarray(bank.zs) - z_true)))
    assert plane[zi_best, r_idx] > 2.0 * plane[zi0, r_idx]


def test_batch_matches_per_dm_path():
    """The rank-2-flattened batched path (_accel_block_topk) and the
    proven per-DM path (_accel_plane_topk) must agree exactly: same
    correlation, different FFT batching (a TPU runtime has rejected
    some batched FFT shapes, so production may run either)."""
    rng = np.random.default_rng(7)
    nbins = 6000
    specs = (rng.normal(size=(3, nbins))
             + 1j * rng.normal(size=(3, nbins))).astype(np.complex64)
    bank = accel.build_template_bank(8.0, seg=1 << 11)
    nz = len(bank.zs)
    bf = jnp.asarray(bank.bank_fft)
    bv, br, bz = accel._accel_block_topk(
        jnp.asarray(specs), bf, bank.seg, bank.step, bank.width, nz, 2, 8)
    for i in range(3):
        sv, sr, sz = accel._accel_plane_topk(
            specs[i], bf, bank.seg, bank.step, bank.width, nz, 2, 8)
        np.testing.assert_allclose(np.asarray(bv[i]), np.asarray(sv),
                                   rtol=2e-4)
        np.testing.assert_array_equal(np.asarray(br[i]), np.asarray(sr))
        np.testing.assert_array_equal(np.asarray(bz[i]), np.asarray(sz))


def test_forced_fallback_matches_batch(monkeypatch):
    """accel_search_batch with TPULSAR_ACCEL_BATCH=0 (per-DM fallback)
    returns the same candidates as the batched path."""
    rng = np.random.default_rng(11)
    nbins = 5000
    specs = jnp.asarray((rng.normal(size=(2, nbins))
                         + 1j * rng.normal(size=(2, nbins))
                         ).astype(np.complex64))
    bank = accel.build_template_bank(8.0, seg=1 << 11)

    monkeypatch.setattr(accel, "_BATCH_OK", True)
    batched = accel.accel_search_batch(specs, bank, max_numharm=2, topk=8)
    monkeypatch.setattr(accel, "_BATCH_OK", False)
    fallback = accel.accel_search_batch(specs, bank, max_numharm=2, topk=8)
    monkeypatch.setattr(accel, "_BATCH_OK", None)
    for h in batched:
        np.testing.assert_allclose(batched[h][0], fallback[h][0], rtol=2e-4)
        np.testing.assert_array_equal(batched[h][1], fallback[h][1])
        np.testing.assert_array_equal(batched[h][2], fallback[h][2])


def test_bf16_plane_optin_matches_f32(monkeypatch):
    """TPULSAR_ACCEL_PLANE_DTYPE=bf16 halves the plane's HBM
    footprint for on-chip A/B.  Exercise the REAL opt-in path (env +
    module reload) and require: bf16 plane dtype in the shipped
    correlation, float32 accumulation, the same winning (z, r) cell,
    < 1% relative power difference, and a larger plane_dm_chunk."""
    import importlib

    import jax.numpy as jnp
    import numpy as np

    from tpulsar.kernels import accel as ak

    rng = np.random.default_rng(1)
    spec = (rng.normal(size=4000) + 1j * rng.normal(size=4000)
            ).astype(np.complex64)
    spec[777] += 30.0            # strong tone
    bank = ak.build_template_bank(8.0, seg=1 << 11)

    def summed_with(dtype_name):
        monkeypatch.setenv("TPULSAR_ACCEL_PLANE_DTYPE", dtype_name)
        # pin the TPU z-chunk: at the CPU default (16) the ifft
        # intermediates dominate plane_dm_chunk for this tiny nz and
        # mask the bf16 plane saving the assertion checks
        monkeypatch.setenv("TPULSAR_ACCEL_Z_CHUNK", "4")
        mod = importlib.reload(ak)
        plane = mod._correlate_segments(
            jnp.asarray(spec), jnp.asarray(bank.bank_fft), bank.seg,
            bank.step, bank.width)
        assert plane.dtype == mod.plane_dtype()
        out = np.asarray(mod._harmonic_sum_plane(
            plane, 2, len(bank.zs)))
        # at the survey's nz: at this toy bank's nz = 9 the plane is
        # a tenth of a row's bytes and both dtypes fit the same rows
        chunk = mod.plane_dm_chunk(1 << 21, 51)
        return out, chunk

    try:
        summed_f32, chunk_f32 = summed_with("f32")
        summed_b16, chunk_b16 = summed_with("bf16")
    finally:
        monkeypatch.setenv("TPULSAR_ACCEL_PLANE_DTYPE", "f32")
        monkeypatch.delenv("TPULSAR_ACCEL_Z_CHUNK", raising=False)
        importlib.reload(ak)

    assert summed_b16.dtype == np.float32   # f32 accumulation
    assert (np.unravel_index(summed_b16.argmax(), summed_b16.shape)
            == np.unravel_index(summed_f32.argmax(), summed_f32.shape))
    rel = abs(summed_b16.max() - summed_f32.max()) / summed_f32.max()
    assert rel < 0.01, rel
    assert chunk_b16 > chunk_f32   # the HBM saving is real


def test_plane_dtype_env_rejects_unknown(monkeypatch):
    """A typo'd dtype env must raise at import, not silently fall
    back to f32 (an A/B would then compare f32 against itself)."""
    import importlib

    import pytest

    from tpulsar.kernels import accel as ak

    monkeypatch.setenv("TPULSAR_ACCEL_PLANE_DTYPE", "bfloat16")
    try:
        with pytest.raises(ValueError, match="f32.*bf16"):
            importlib.reload(ak)
    finally:
        monkeypatch.setenv("TPULSAR_ACCEL_PLANE_DTYPE", "f32")
        importlib.reload(ak)


def test_native_host_path_matches_xla(monkeypatch):
    """The CPU product path (native plane consumer,
    tpulsar/native/accel_host.cpp) must be BIT-identical to the pure
    XLA _accel_block_topk extraction — same f32 addition order, same
    tie-breaking, same padding — across bank/shape/stage variants,
    including a non-pow2 nbins and a topk larger than the block
    count."""
    import jax.numpy as jnp

    from tpulsar import native
    from tpulsar.kernels import accel as ak
    from tpulsar.kernels.fourier import BLOCK_R, harmonic_stages

    if native.load() is None:
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(11)
    cases = [(8.0, 6000, 3, 8, 16), (20.0, 1 << 13, 2, 16, 64),
             (8.0, 700, 1, 4, 64)]
    for zmax, nbins, nd, mh, topk in cases:
        bank = ak.build_template_bank(zmax, seg=1 << 11)
        nz = len(bank.zs)
        specs = jnp.asarray(
            (rng.normal(size=(nd, nbins))
             + 1j * rng.normal(size=(nd, nbins))).astype(np.complex64))
        bf = jnp.asarray(bank.bank_fft)
        want = ak._accel_block_topk(specs, bf, bank.seg, bank.step,
                                    bank.width, nz, mh, topk)
        stages = harmonic_stages(mh)
        # plane-layout kernel
        plane = np.asarray(ak._correlate_block(
            specs, bf, bank.seg, bank.step, bank.width, nz))
        got_p = native.accel_stage_topk(plane, stages, BLOCK_R, topk)
        # raw-pieces kernel (the product path's actual input layout)
        pieces = np.asarray(ak._correlate_pieces(
            specs, bf, seg=bank.seg, step=bank.step, width=bank.width,
            nz=nz))
        got_s = native.accel_stage_topk_segs(
            pieces, bank.width, 2 * nbins, stages, BLOCK_R, topk)
        for got in (got_p, got_s):
            assert got is not None
            for i, w in enumerate(want):
                np.testing.assert_array_equal(got[i], np.asarray(w))


def test_native_search_batch_equals_forced_xla(monkeypatch):
    """accel_search_batch via the native CPU path returns exactly the
    forced-XLA result (the executor consumes this surface)."""
    import jax.numpy as jnp

    from tpulsar import native
    from tpulsar.kernels import accel as ak

    if native.load() is None:
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(12)
    bank = ak.build_template_bank(10.0, seg=1 << 11)
    specs = jnp.asarray(
        (rng.normal(size=(5, 5000))
         + 1j * rng.normal(size=(5, 5000))).astype(np.complex64))
    monkeypatch.delenv("TPULSAR_ACCEL_NATIVE", raising=False)
    got = ak.accel_search_batch(specs, bank, max_numharm=8, topk=16,
                                dm_chunk=2)
    monkeypatch.setenv("TPULSAR_ACCEL_NATIVE", "0")
    want = ak.accel_search_batch(specs, bank, max_numharm=8, topk=16,
                                 dm_chunk=2)
    assert set(got) == set(want)
    for h in want:
        for i in range(3):
            np.testing.assert_array_equal(np.asarray(got[h][i]),
                                          np.asarray(want[h][i]))


def test_stage_maxes_bit_identical_to_per_stage_sums():
    """_harmonic_stage_maxes (incremental cross-stage term reuse +
    static strided slices) must be BIT-identical to summing each
    stage from scratch with _harmonic_sum_plane — same left-to-right
    f32 addition order — for every stage and several nz/nr shapes."""
    import jax.numpy as jnp

    from tpulsar.kernels import accel as ak
    from tpulsar.kernels.fourier import harmonic_stages

    rng = np.random.default_rng(5)
    for nz, nr, mh in ((51, 4096, 16), (9, 1000, 8), (201, 2048, 16),
                       (51, 777, 4)):
        plane = jnp.asarray(rng.normal(size=(nz, nr)).astype(np.float32) ** 2)
        maxes = ak._harmonic_stage_maxes(
            plane, tuple(harmonic_stages(mh)), nz)
        for h in harmonic_stages(mh):
            old = np.asarray(ak._harmonic_sum_plane(plane, h, nz))
            np.testing.assert_array_equal(np.asarray(maxes[h][0]),
                                          old.max(axis=0))
            np.testing.assert_array_equal(np.asarray(maxes[h][1]),
                                          old.argmax(axis=0))


def test_per_dm_fallback_zero_fills_refused_rows(monkeypatch):
    """A runtime-refused row dispatch (UNIMPLEMENTED) is retried once,
    then zero-filled with a degraded-mode note — one flaky trial must
    degrade one DM row, not kill the whole beam."""
    import jax
    from tpulsar.search import degraded

    rng = np.random.default_rng(23)
    nbins = 5000
    specs = jnp.asarray((rng.normal(size=(3, nbins))
                         + 1j * rng.normal(size=(3, nbins))
                         ).astype(np.complex64))
    bank = accel.build_template_bank(8.0, seg=1 << 11)

    monkeypatch.setattr(accel, "_BATCH_OK", False)
    monkeypatch.setattr(accel, "_native_cpu_path_usable",
                        lambda: False)
    clean = accel.accel_search_batch(specs, bank, max_numharm=2,
                                     topk=8)

    real_row = accel.accel_row_topk

    def flaky_row(full, bf, i, **kw):
        if int(i) == 1:
            raise jax.errors.JaxRuntimeError(
                "UNIMPLEMENTED: TPU backend error (Unimplemented).")
        return real_row(full, bf, i, **kw)

    monkeypatch.setattr(accel, "accel_row_topk", flaky_row)
    degraded.reset()
    out = accel.accel_search_batch(specs, bank, max_numharm=2, topk=8)
    for h in clean:
        # surviving rows identical to the clean run
        for r in (0, 2):
            np.testing.assert_allclose(out[h][0][r], clean[h][0][r],
                                       rtol=2e-4)
        # the refused row is zero power, never a candidate
        assert np.all(out[h][0][1] == 0.0)
    snap = degraded.snapshot()
    assert "accel_rows_zero_filled" in snap
    assert snap["accel_rows_zero_filled"].startswith("1/3 across 1")


def test_per_dm_fallback_recovers_deferred_drain_error(monkeypatch):
    """An async error that surfaces at the WINDOW SYNC (jax is
    async — the most plausible surfacing point) must not zero-fill
    the whole window: each pending row is re-dispatched
    synchronously and only individually refused rows are lost."""
    import jax
    from tpulsar.search import degraded

    rng = np.random.default_rng(29)
    nbins = 5000
    specs = jnp.asarray((rng.normal(size=(3, nbins))
                         + 1j * rng.normal(size=(3, nbins))
                         ).astype(np.complex64))
    bank = accel.build_template_bank(8.0, seg=1 << 11)

    monkeypatch.setattr(accel, "_BATCH_OK", False)
    monkeypatch.setattr(accel, "_native_cpu_path_usable",
                        lambda: False)
    clean = accel.accel_search_batch(specs, bank, max_numharm=2,
                                     topk=8)

    real_get = jax.device_get
    state = {"raised": False}

    def flaky_get(x):
        if not state["raised"] and isinstance(x, list) and len(x) > 1:
            state["raised"] = True
            raise jax.errors.JaxRuntimeError(
                "UNIMPLEMENTED: TPU backend error (Unimplemented).")
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", flaky_get)
    degraded.reset()
    out = accel.accel_search_batch(specs, bank, max_numharm=2, topk=8)
    monkeypatch.setattr(jax, "device_get", real_get)
    assert state["raised"]
    for h in clean:
        np.testing.assert_allclose(out[h][0], clean[h][0], rtol=2e-4)
    # every row recovered on the sync retry: nothing degraded
    assert "accel_rows_zero_filled" not in degraded.snapshot()


def test_per_dm_fallback_total_refusal_raises(monkeypatch):
    """When the runtime refuses EVERY row (twice each), the search
    must not return an all-zero result dressed as success."""
    import jax
    import pytest

    rng = np.random.default_rng(31)
    specs = jnp.asarray((rng.normal(size=(2, 4000))
                         + 1j * rng.normal(size=(2, 4000))
                         ).astype(np.complex64))
    bank = accel.build_template_bank(8.0, seg=1 << 11)
    monkeypatch.setattr(accel, "_BATCH_OK", False)
    monkeypatch.setattr(accel, "_native_cpu_path_usable",
                        lambda: False)

    def refuse(full, bf, i, **kw):
        raise jax.errors.JaxRuntimeError(
            "UNIMPLEMENTED: TPU backend error (Unimplemented).")

    monkeypatch.setattr(accel, "accel_row_topk", refuse)
    with pytest.raises(accel.AccelStageRefused):
        accel.accel_search_batch(specs, bank, max_numharm=2, topk=8)


# --- the tiled harmonic-sum kernel (accel._harmsum_zmax) --------------
# A program lowered for a TPU runs the kernel; lowered for anything else
# (these tests, the host rescue) it runs the strided form.  So the
# kernel's definition is held here directly, in Pallas's interpreter.

def _kernel_maxes(plane, stages, nz):
    """The kernel, interpreted, on one (nz, nr) plane or a block."""
    planes = plane if plane.ndim == 3 else plane[None]
    out = accel._harmsum_zmax(planes, tuple(stages), nz, interpret=True)
    if plane.ndim == 3:
        return out
    return {h: (m[0], a[0]) for h, (m, a) in out.items()}


def _oracle_stage(plane, h, nz):
    """(max over z, argmax over z) of the strided per-stage sum."""
    old = np.asarray(accel._harmonic_sum_plane(plane, h, nz))
    return old.max(axis=0), old.argmax(axis=0)


def _random_plane(shape, dtype, seed=5):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) ** 2
                       ).astype(dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("nz,nr,numharm", [
    (51, 4096, 16), (9, 1000, 8), (201, 2048, 16), (51, 777, 4),
    # 2 mod 8 x 128, like all three survey planes (3,932,162;
    # 1,966,082; 4,194,306): every stage ends in a ragged edge tile
    (51, 3074, 8),
])
def test_harmsum_kernel_bit_identical_to_strided_oracle(nz, nr, numharm,
                                                        dtype):
    """The tiled kernel's per-stage (max over z, argmax over z) are
    the strided oracle's bit for bit — same f32 addition order, same
    z clamping, same first-index argmax, same L = nr // h — for f32
    and bf16 planes; and what the program lowers to off the TPU
    (_harmonic_stage_maxes here) is the same bits again."""
    from tpulsar.kernels.fourier import harmonic_stages

    plane = _random_plane((nz, nr), dtype)
    stages = tuple(harmonic_stages(numharm))
    maxes = _kernel_maxes(plane, stages, nz)
    lowered_here = accel._harmonic_stage_maxes(plane, stages, nz)
    assert set(maxes) == set(lowered_here) == set(stages)
    for h in stages:
        want_max, want_arg = _oracle_stage(plane, h, nz)
        assert maxes[h][0].dtype == jnp.float32
        assert maxes[h][1].dtype == jnp.int32
        for got in (maxes, lowered_here):
            np.testing.assert_array_equal(np.asarray(got[h][0]), want_max)
            np.testing.assert_array_equal(np.asarray(got[h][1]), want_arg)


@pytest.mark.parametrize("nd", [1, 2, 3])
def test_harmsum_kernel_gives_block_topk_its_candidates(nd):
    """nd DM rows at once: the kernel on _correlate_block's plane,
    then the block-max top-k, is _accel_block_topk's result bit for
    bit (the chunk program's body, which lowers the strided form
    here); a stage the plane has no column for comes back empty."""
    from tpulsar.kernels.fourier import blockmax_topk, harmonic_stages

    rng = np.random.default_rng(nd)
    nbins = 3001
    specs = (rng.normal(size=(nd, nbins)) + 1j * rng.normal(size=(nd, nbins))
             ).astype(np.complex64)
    specs[:, 500] += 20.0
    bank = accel.build_template_bank(8.0, seg=1 << 11)
    nz = len(bank.zs)
    bank_fft = jnp.asarray(bank.bank_fft)
    kw = dict(seg=bank.seg, step=bank.step, width=bank.width, nz=nz)
    vals, rbins, zidx = accel._accel_block_topk(
        jnp.asarray(specs), bank_fft, max_numharm=8, topk=16, **kw)
    plane = accel._correlate_block(jnp.asarray(specs), bank_fft, **kw)
    # the stated invariant of the selection matmul: a finite plane
    assert bool(jnp.isfinite(plane.astype(jnp.float32)).all())
    stages = harmonic_stages(8)
    maxes = _kernel_maxes(plane, stages, nz)
    for si, h in enumerate(stages):
        zmax, zarg = maxes[h]
        assert zmax.shape == (nd, 2 * nbins // h)
        v, r = blockmax_topk(zmax, 16)
        np.testing.assert_array_equal(np.asarray(vals[:, si]),
                                      np.asarray(v))
        np.testing.assert_array_equal(np.asarray(rbins[:, si]),
                                      np.asarray(r))
        np.testing.assert_array_equal(
            np.asarray(zidx[:, si]),
            np.take_along_axis(np.asarray(zarg), np.asarray(r), axis=1))
    for narrow in (_kernel_maxes(plane[0][:, :3], (1, 2, 4), nz),
                   accel._harmonic_stage_maxes(plane[0][:, :3], (1, 2, 4),
                                               nz)):
        assert narrow[4][0].shape == narrow[4][1].shape == (0,)
    assert set(accel.harmsum_plan(nz, 3, (1, 2, 4), "float32").stages
               ) == {1, 2}


def test_harmsum_kernel_ties_take_first_z():
    """Equal sums at several z: argmax's first-index rule."""
    nz, nr = 9, 600
    base = _random_plane((1, nr), "float32", seed=2)
    plane = jnp.tile(base, (nz, 1))           # every z row the same
    plane = plane.at[3:6, 100:200].add(1.0)   # a three-way tie above
    maxes = _kernel_maxes(plane, (1, 2, 4), nz)
    for h in (1, 2, 4):
        want_max, want_arg = _oracle_stage(plane, h, nz)
        np.testing.assert_array_equal(np.asarray(maxes[h][0]), want_max)
        np.testing.assert_array_equal(np.asarray(maxes[h][1]), want_arg)
    arg1 = np.asarray(maxes[1][1])
    assert (arg1[100:200] == 3).all() and (arg1[:100] == 0).all()


def test_harmsum_kernel_nonfinite_stays_in_its_column_group():
    """What the docstring of _harmonic_stage_maxes states of a
    non-finite plane value: 0 x inf reaches only the 128 output
    columns of its selection matmul, at harmonics >= 2; stage 1 (a
    plain read) and every other group keep the oracle's bits."""
    nz, nr = 9, 4096
    plane = _random_plane((nz, nr), "float32", seed=3)
    c = 1801
    plane = plane.at[4, c].set(jnp.inf)
    maxes = _kernel_maxes(plane, (1, 2, 4), nz)
    want_max, want_arg = _oracle_stage(plane, 1, nz)
    np.testing.assert_array_equal(np.asarray(maxes[1][0]), want_max)
    np.testing.assert_array_equal(np.asarray(maxes[1][1]), want_arg)
    for h in (2, 4):
        want_max, want_arg = _oracle_stage(plane, h, nz)
        touched = np.zeros(nr // h, bool)
        if c < nr // h:
            touched[c] = True                 # hh = 1 reads it as r = c
        for hh in range(2, h + 1):
            g = c // (128 * hh)
            touched[g * 128:(g + 1) * 128] = True
        got = np.asarray(maxes[h][0])
        np.testing.assert_array_equal(got[~touched], want_max[~touched])
        np.testing.assert_array_equal(
            np.asarray(maxes[h][1])[~touched], want_arg[~touched])
        assert np.isfinite(got[~touched]).all()


def test_strided_form_is_lowered_only_off_the_tpu():
    """No knob chooses the form: lax.platform_dependent does, per
    lowering.  Here (CPU) the chunk program holds no Pallas call;
    tests/test_chip_compile.py sees the kernel in the same program
    lowered for a v5e."""
    bank = accel.build_template_bank(8.0, seg=1 << 11)
    text = accel._accel_block_topk.lower(
        jnp.zeros((2, 3001), jnp.complex64), jnp.asarray(bank.bank_fft),
        seg=bank.seg, step=bank.step, width=bank.width, nz=len(bank.zs),
        max_numharm=8, topk=16).compile().as_text()
    assert "harmsum_zmax" not in text


# the three survey planes (Mock ds=1, Mock ds=2, WAPP ds=1), the
# benchmark's nz and BASELINE config 3's
@pytest.mark.parametrize("nz,numharm", [(51, 8), (201, 8), (201, 16)])
@pytest.mark.parametrize("ncols", [3_932_162, 1_966_082, 4_194_306])
def test_harmsum_plan_covers_every_column_once_within_vmem(ncols, nz,
                                                           numharm):
    """No chip: tile, padding and VMEM bytes the kernel derives stay
    under the scoped-VMEM limit it requests (and that under a v5e's
    128 MiB), every output column of every stage is written by
    exactly one grid step, every source block starts inside the
    plane, and the strided z reads stay inside the scratch."""
    from tpulsar.kernels.fourier import harmonic_stages

    stages = tuple(harmonic_stages(numharm))
    p = accel.harmsum_plan(nz, ncols, stages, jnp.bfloat16)
    assert p.stages == stages and p.tile % 128 == 0
    assert p.nzb % 16 == 0 and 0 <= p.nzb - nz < 16
    assert p.vmem_bytes <= p.vmem_limit - (4 << 20)
    assert p.vmem_limit <= 100 << 20
    T = p.tile
    for si, h in enumerate(stages):
        L = ncols // h
        nt = p.ntiles[si]
        # tiles [j*T, (j+1)*T) for j < nt: disjoint, and they cover
        # [0, L) with the last one ragged, none wholly outside
        assert (nt - 1) * T < L <= nt * T
        assert nt <= p.ntiles[0]
    for hh in range(1, numharm + 1):
        si = p.stage_of(hh)
        assert stages[si] >= hh and (si == 0 or stages[si - 1] < hh)
        last = p.ntiles[si] - 1
        assert hh * T * last < ncols          # the block starts inside
        # every source column of a real output is inside the plane
        assert hh * (ncols // stages[si] - 1) < ncols
        center = (nz - 1) // 2
        for r0 in range(0, nz, 8):
            lo = p.margin + center + hh * (r0 - center)
            lo_zi = -(-(center * (hh - 1)) // hh)
            hi_zi = (nz - 1 + center * (hh - 1)) // hh
            if r0 + 7 >= lo_zi and r0 <= hi_zi:   # a strided read
                assert 0 <= lo and lo + 7 * hh < p.nzb + 2 * p.margin


@pytest.mark.parametrize("dtype", ["float16", "int8", "float64"])
def test_harmsum_plan_refuses_other_dtypes(dtype):
    """A dtype the selection matmul is not exact for is refused
    loudly, not routed around."""
    with pytest.raises(ValueError, match="dtype"):
        accel.harmsum_plan(51, 4096, (1, 2, 4, 8), np.dtype(dtype))


def test_harmsum_plan_refuses_what_vmem_cannot_hold():
    with pytest.raises(ValueError, match="VMEM"):
        accel.harmsum_plan(4001, 1 << 20, (1, 2, 4, 8, 16, 32),
                           jnp.float32)
