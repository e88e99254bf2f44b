"""The chunk program's correlation as a matrix product (accel.corr_plane,
what a TPU lowers) against the overlap-save FFT form it replaces there
(accel._correlate_block, what every other platform keeps): here on the
CPU, the kernel in Pallas's interpreter, toy sizes, float32 plane.
tests/test_chip_compile.py compiles the same kernel for a described
v5e at the survey's widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpulsar.kernels import accel

NBINS = 3001            # 23 blocks of 128 bins and 57 more
BANKS = [(8.0, 9, 64), (50.0, 51, 128), (200.0, 201, 256)]


@pytest.fixture(scope="module")
def banks():
    return {zmax: accel.build_template_bank(zmax, seg=1 << 11)
            for zmax, _, _ in BANKS}


def _spectra(rows, nbins=NBINS, seed=3, kind="complex"):
    """Noise and two tones.  The kinds other than "complex" have real
    and imaginary parts far from equal: where the componentwise error
    of a complex product made of three real ones (on the smaller of re
    and im) would show, if the power felt it."""
    rng = np.random.default_rng(seed)
    re, im = rng.standard_normal((2, rows, nbins)).astype(np.float32)
    re[:, nbins // 3] += 20.0
    re[:, nbins - 3] += 30.0       # a tone in the last `width` bins
    if kind == "im_1e-4_re":
        im = (1e-4 * np.abs(re) * np.sign(im)).astype(np.float32)
    elif kind != "complex":
        im = np.zeros_like(re)
    if kind == "imaginary":
        re, im = im, re
    return jnp.asarray((re + 1j * im).astype(np.complex64))


def _fft_plane(specs, bank):
    return np.asarray(accel._correlate_block(
        specs, jnp.asarray(bank.bank_fft), bank.seg, bank.step,
        bank.width, len(bank.zs)))


@pytest.mark.parametrize("rows,kind", [
    (1, "complex"), (2, "complex"), (3, "complex"),
    (1, "real"), (1, "imaginary"), (1, "im_1e-4_re")])
@pytest.mark.parametrize("zmax,nz,width", BANKS)
def test_corr_plane_is_the_fft_forms_plane(banks, zmax, nz, width, rows,
                                           kind):
    """The bound is on the POWER, which the larger of re and im sets:
    the lopsided kinds keep it too."""
    bank = banks[zmax]
    assert (len(bank.zs), bank.width) == (nz, width)
    assert NBINS % accel._CORR_B
    specs = _spectra(rows, kind=kind)
    want = _fft_plane(specs, bank)
    got = np.asarray(accel._corr_plane(
        *accel._split_block(specs), jnp.asarray(accel.corr_taps(bank)),
        width, nz, interpret=True))
    assert got.shape == want.shape == (rows, nz, 2 * NBINS)
    assert got.dtype == want.dtype == np.float32
    top = want.max()
    assert np.abs(got - want).max() <= 2e-6 * top
    # the FFT form's left pad, folded into the kernel's indices
    assert np.all(got[:, :, :width] == 0) and np.all(want[:, :, :width] == 0)
    assert np.any(got[:, :, width] > 0)
    # the last `width` bins are searched (zero overhang past nbins):
    # the tone 3 bins from the top peaks on its own column at z = 0
    tail = got[:, (nz - 1) // 2, -2 * width:]
    assert np.all(tail.argmax(axis=1) == 2 * width - 6)
    assert tail.max() > 0.5 * top


@pytest.mark.parametrize("zmax,nz,width", BANKS)
def test_corr_plane_over_several_grid_steps(banks, monkeypatch, zmax, nz,
                                            width):
    """The survey's spectra take tens of grid steps along the bins, the
    toy's one: with 8 blocks a step at most the toy takes three, the
    blocks shared evenly, and a step's last windows reach into the next
    step's first rows (the halo).  Same plane."""
    monkeypatch.setattr(accel, "_CORR_BLOCKS", 8)
    p = accel.corr_plan(NBINS, nz, width, 2)
    assert (p.ntiles, p.blocks) == (3, 8)
    bank = banks[zmax]
    specs = _spectra(2, seed=9)
    want = _fft_plane(specs, bank)
    got = np.asarray(accel._corr_plane.__wrapped__(
        *accel._split_block(specs), jnp.asarray(accel.corr_taps(bank)),
        width, nz, interpret=True))
    assert np.abs(got - want).max() <= 2e-6 * want.max()
    assert np.all(got[:, :, :width] == 0)


def _dots_of(jaxpr):
    """Every dot_general of a jaxpr, the ones inside its calls, loops
    and kernels included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _dots_of(inner)


@pytest.mark.parametrize("zmax,nz,width", BANKS)
def test_the_kernel_takes_three_float32_products_a_z(banks, zmax, nz, width):
    """Gauss's identity: three real products a (tile, z) where the
    complex product's four quadrants took four, each of float32
    operands at Precision.HIGHEST accumulated in float32 — no bf16
    operand, no lower precision, no hand split."""
    part = jax.ShapeDtypeStruct((2, NBINS), jnp.float32)
    taps = jax.ShapeDtypeStruct(accel.corr_taps_shape(nz, width), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda r, i, t: accel._corr_plane(r, i, t, width, nz,
                                          interpret=False))(part, part, taps)
    dots = list(_dots_of(jaxpr.jaxpr))
    p = accel.corr_plan(NBINS, nz, width, 2)
    assert len(dots) == 3
    for eqn in dots:
        lhs, rhs = (v.aval for v in eqn.invars)
        assert lhs.dtype == rhs.dtype == jnp.float32
        assert lhs.shape == (p.blocks, p.kdim)
        assert rhs.shape == (p.kdim, 2 * accel._CORR_B)
        assert eqn.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.fixture
def tpu_side_taken(monkeypatch):
    """What a program lowered for a TPU takes of _chunk_plane, here:
    the kernel, in Pallas's interpreter.  The harmonic sums keep this
    CPU's form.  Un-jitted _accel_block_topk: a cached trace would keep
    the form it was made with.  Returns the list of kernels taken."""
    taken = []

    def platform_dependent(*args, default, tpu):
        if len(args) == 4:              # _chunk_plane's operands
            taken.append("corr")
            return tpu(*args)
        return default(*args)           # the harmonic sums: this CPU's

    real = accel._corr_plane
    monkeypatch.setattr(jax.lax, "platform_dependent", platform_dependent)
    monkeypatch.setattr(
        accel, "_corr_plane",
        lambda r, i, t, w, n, interpret: real(r, i, t, w, n,
                                              interpret=True))
    monkeypatch.setattr(accel, "_accel_block_topk",
                        accel._accel_block_topk.__wrapped__)
    return taken


@pytest.mark.parametrize("zmax,nz,width", BANKS[:2])
def test_chunk_programs_topk_through_the_kernel(banks, tpu_side_taken,
                                                zmax, nz, width):
    """accel_chunk_topk -> _accel_block_topk with the TPU's side of
    _chunk_plane taken against the FFT form's block program: values
    within the plane's tolerance, r and z equal."""
    bank = banks[zmax]
    specs = _spectra(4, seed=5)
    kw = dict(seg=bank.seg, step=bank.step, width=width, nz=nz,
              max_numharm=8, topk=16)
    bank_fft = jnp.asarray(bank.bank_fft)
    want = [np.asarray(a) for a in
            accel._accel_block_topk(specs[1:3], bank_fft, **kw)]
    assert not tpu_side_taken           # no taps: the FFT form
    got = [np.asarray(a) for a in accel.accel_chunk_topk.__wrapped__(
        accel._split_block(specs), bank_fft,
        jnp.asarray(accel.corr_taps(bank)), np.int32(1), nrows=2, **kw)]
    assert tpu_side_taken == ["corr"]
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=0,
                               atol=8 * 2e-6 * want[0].max())
    assert want[0].max() > 100.0


def test_the_mesh_programs_hi_stage_through_the_kernel(tpu_side_taken,
                                                       monkeypatch):
    """The DM-sharded mesh pass as a TPU process runs it: the executor
    sends the taps to every device and sizes a device's rows by the
    direct form's count, and the one program's hi stage
    (_accel_block_topk on the shard's complex spectra) takes the
    kernel.  Same candidates as one device's FFT form."""
    from tpulsar.parallel import mesh as pmesh
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    rng = np.random.default_rng(11)
    nchan, T, dt = 16, 1 << 12, 1e-3
    freqs = np.linspace(1200.0, 1500.0, nchan)
    data = rng.standard_normal((nchan, T)).astype(np.float32)
    t = np.arange(T) * dt
    # drifting by 4 bins: a candidate only the hi stage reports
    drift = 0.5 * (4.0 / (T * dt) ** 2) * t * t
    data += ((t / 0.064 + drift) % 1.0 < 0.08) * 3.0
    plan = [ddplan.DedispStep(lodm=0.0, dmstep=4.0, dms_per_pass=8,
                              numpasses=1, numsub=8, downsamp=1)]
    params = executor.SearchParams(
        nsub=8, lo_accel_numharm=4, hi_accel_zmax=8, hi_accel_numharm=4,
        topk_per_stage=8, max_cands_to_fold=0, make_plots=False)
    block = jnp.asarray(data)
    single = executor.search_block(block, freqs, dt, plan, params)[0]
    assert not tpu_side_taken

    monkeypatch.setattr(accel, "corr_form", lambda: "direct")
    monkeypatch.setattr(executor, "_SHARDED_FN_CACHE", {})
    rows_asked = []
    real_rows = accel.plane_dm_chunk
    monkeypatch.setattr(
        accel, "plane_dm_chunk",
        lambda *a, **k: rows_asked.append(k) or real_rows(*a, **k))
    mesh = pmesh.make_mesh(n_beam=1, n_dm=4, devices=jax.devices()[:4])
    sharded = executor.search_block(block, freqs, dt, plan, params,
                                    mesh=mesh)[0]
    assert tpu_side_taken == ["corr"] and rows_asked == [{"max_chunk": 32}]

    def keys(cands):
        return sorted((round(c.r, 2), round(c.z, 2), c.numharm,
                       round(c.dm, 3)) for c in cands)

    assert keys(sharded) == keys(single)
    assert max(single, key=lambda c: c.sigma).z == 4.0
    by_key = dict(zip(keys(single), sorted(
        single, key=lambda c: (round(c.r, 2), round(c.z, 2), c.numharm,
                               round(c.dm, 3)))))
    for c in sharded:
        ref = by_key[(round(c.r, 2), round(c.z, 2), c.numharm,
                      round(c.dm, 3))]
        assert c.sigma == pytest.approx(ref.sigma, rel=1e-3)


def test_off_the_tpu_the_chunk_program_keeps_the_fft_form(banks):
    """No knob chooses the form.  A process off a TPU dispatches the
    complex block and no taps (chunk_operands): nothing is built or
    split for a kernel it cannot lower.  A program that is given the
    taps chooses where it is lowered (lax.platform_dependent): here
    (CPU) it holds no Pallas call either.  Both programs' planes are
    _correlate_block's and their top-k the FFT form's block program's,
    bit for bit."""
    bank = banks[8.0]
    nz = len(bank.zs)
    specs = _spectra(2)
    assert accel.corr_form() == "fft"
    full, none = accel.chunk_operands(specs, bank)
    assert full is specs and none is None
    bank_fft = jnp.asarray(bank.bank_fft)
    taps = jnp.asarray(accel.corr_taps(bank))
    kw = dict(seg=bank.seg, step=bank.step, width=bank.width, nz=nz)
    want = accel._accel_block_topk(specs, bank_fft, max_numharm=8,
                                   topk=16, **kw)
    for operands in ((specs, None), (accel._split_block(specs), taps),
                     (specs, taps)):
        plane = jax.jit(lambda s, b, t: accel._chunk_plane(s, b, t, **kw))(
            operands[0], bank_fft, operands[1])
        np.testing.assert_array_equal(np.asarray(plane),
                                      _fft_plane(specs, bank))
        chunk = accel.accel_chunk_topk.lower(
            operands[0], bank_fft, operands[1], np.int32(0), nrows=2,
            max_numharm=8, topk=16, **kw).compile()
        text = chunk.as_text()
        assert "tpu_custom_call" not in text and "fft_type=IFFT" in text
        for got, ref in zip(
                chunk(operands[0], bank_fft, operands[1], np.int32(0)),
                want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_the_taps_on_the_device_are_kept_for_one_bank(banks):
    """_corr_taps_on_device sends a bank's taps once and keeps the last
    bank's only (keyed on the device too: a backend made anew sends
    again)."""
    a = accel._corr_taps_on_device(banks[8.0])
    assert accel._corr_taps_on_device(banks[8.0]) is a
    np.testing.assert_array_equal(np.asarray(a),
                                  accel.corr_taps(banks[8.0]))
    b = accel._corr_taps_on_device(banks[50.0])
    assert b.shape == accel.corr_taps_shape(51, 128)
    assert accel._CORR_TAPS_ON_DEVICE[1] is b
    assert accel._corr_taps_on_device(banks[8.0]) is not a


@pytest.mark.parametrize("zmax,nz,width", BANKS)
def test_corr_taps_are_the_rows_bank_fft_transforms(banks, zmax, nz, width):
    bank = banks[zmax]
    assert bank.taps.shape == (nz, 2 * width)
    rows = np.zeros_like(bank.bank_fft)
    rows[:, :2 * width] = bank.taps
    np.testing.assert_array_equal(
        np.fft.fft(rows, axis=-1).astype(np.complex64), bank.bank_fft)
    a = accel.corr_taps(bank)
    assert a.shape == accel.corr_taps_shape(nz, width)
    assert a.dtype == np.float32
    ncol = a.shape[2] // 2
    rng = np.random.default_rng(0)
    for z, i, n in zip(rng.integers(0, nz, 200),
                       rng.integers(0, a.shape[1], 200),
                       rng.integers(0, ncol, 200)):
        m = n - 2 * i + 2 * width - 1
        tap = bank.taps[z, m] if 0 <= m < 2 * width else 0
        assert a[z, i, n] == np.real(tap) and a[z, i, ncol + n] == np.imag(tap)


@pytest.mark.parametrize("nbins,nz,width,rows", [
    (1_966_081, 51, 128, 6),        # Mock ds=1
    (2_097_153, 51, 128, 6),        # WAPP ds=1
    (1_966_081, 201, 256, 2),       # zmax 200
    (983_041, 51, 128, 6),          # Mock ds=2
    (737_281, 51, 128, 1),          # GBNCC's 120 s pointing at ds=1
    (3001, 9, 64, 2),               # a toy bank: a window of 1.5 blocks
    (2_097_153, 901, 1024, 1),      # the widest tiled: 8 blocks of reach,
                                    # the halo the kernel fetches
])
def test_corr_plan_covers_every_bin_within_vmem(nbins, nz, width, rows):
    p = accel.corr_plan(nbins, nz, width, rows)
    B = accel._CORR_B
    assert p.blocks % 8 == 0 and p.blocks <= accel._CORR_BLOCKS
    assert (p.ntiles - 1) * p.blocks * B < nbins <= p.ntiles * p.blocks * B
    # the steps share the blocks evenly, each an odd count of 8: under
    # 16 blocks a step overhang
    assert p.blocks // 8 % 2 == 1
    assert p.ntiles * p.blocks - -(-nbins // B) < 16 * p.ntiles
    # a block's window: its own bins and `width` more, inside S blocks
    assert (p.shifts - 1) * B >= width and p.kdim >= B + width
    # the padded spectrum: width/2 zeros, the bins, the last tile's halo
    assert p.rows_in * B >= width // 2 + nbins + width // 2
    assert p.rows_in == p.ntiles * p.blocks + accel._CORR_HALO
    # three window panels, the summed taps and three products of the
    # largest step: inside a v5e's 128 MiB at every width tiled
    assert p.shifts - 1 <= accel._CORR_HALO
    assert p.vmem_bytes < p.vmem_limit <= (100 if width <= 256 else 120) << 20


@pytest.mark.parametrize("nbins,nz,width,rows,why", [
    (0, 51, 128, 2, "nothing to tile"),
    (3001, 0, 128, 2, "nothing to tile"),
    (3001, 51, 128, 0, "nothing to tile"),
    (3001, 51, 127, 2, "must be even"),
    (3001, 51, 0, 2, "must be even"),
    (3001, 51, 2048, 2, "blocks of 128 bins past its own"),
    (3001, 51, 1026, 2, "blocks of 128 bins past its own"),
])
def test_corr_plan_refuses_what_it_cannot_tile(nbins, nz, width, rows, why):
    with pytest.raises(ValueError, match=why):
        accel.corr_plan(nbins, nz, width, rows)
