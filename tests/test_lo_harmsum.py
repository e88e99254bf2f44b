"""The lo stage's tiled harmonic-sum kernel (fourier._lo_block_maxima)
against the strided form it replaces on a TPU: the kernel runs here in
Pallas's interpreter, which fills what lies past an array's end with
NaN — so every ragged shape below also plants NaN past the end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.kernels import decimate
from tpulsar.kernels import fourier as fr

STAGES = (1, 2, 4, 8, 16)


def _powers(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.exponential(size=shape).astype(np.float32))


def _oracle(powers, h):
    bmax, barg = fr._block_maxima(fr.harmonic_sum(powers, h), fr.BLOCK_R)
    return np.asarray(bmax), np.asarray(barg)


def _assert_same_bits(got, powers, stages):
    for h in stages:
        want_max, want_arg = _oracle(powers, h)
        assert got[h][0].dtype == jnp.float32
        assert got[h][1].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got[h][0]), want_max)
        np.testing.assert_array_equal(np.asarray(got[h][1]), want_arg)


# rows: one series, a mesh device's 6, Mock's 32 and 38, GBNCC's 102
# (three row groups, the last ragged).  ncols: odd; 2 mod 128 like
# every survey grid (2 * nbins); shorter than the narrowest tile;
# some tiles past stage 16's last
@pytest.mark.parametrize("rows", [1, 6, 32, 38, 102])
@pytest.mark.parametrize("ncols", [3001, 2050, 100, 5121])
def test_kernel_bit_identical_to_strided_form(rows, ncols):
    """Values AND in-block argmax of every stage's block maxima are
    the strided form's bit for bit: same float32 addition order, same
    -inf padding of the last block, same first-index argmax."""
    powers = _powers((rows, ncols), seed=rows + ncols)
    got = fr._lo_block_maxima(powers, STAGES, interpret=True)
    assert set(got) == set(STAGES)
    _assert_same_bits(got, powers, STAGES)


@pytest.mark.parametrize("stages,ncols", [
    ((1, 2, 4, 8, 16), 10),    # no column for stage 16: cut short
    ((1, 2, 4, 8, 16), 3),     # ... nor for 4, 8, 16
    ((1, 2, 4), 777),
    ((1,), 300),
    ((1, 3), 1000),            # a stage list that skips 2
])
def test_kernel_stages(stages, ncols):
    """Other stage lists; a stage the array has no column for is
    answered empty, and its candidates are the strided form's zeros."""
    powers = _powers((5, ncols), seed=ncols)
    got = fr._lo_block_maxima(powers, stages, interpret=True)
    _assert_same_bits(got, powers, stages)
    for h in stages:
        v, b = fr._topk_blocks(*got[h], 8, fr.BLOCK_R)
        wv, wb = fr.blockmax_topk(fr.harmonic_sum(powers, h), 8)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(wv))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(wb))


def test_kernel_tie_inside_a_block_takes_the_first_index():
    powers = jnp.ones((3, 1000), jnp.float32)      # every block a tie
    powers = powers.at[1, 200:210].set(5.0)        # a ten-way tie above
    got = fr._lo_block_maxima(powers, (1, 2, 4), interpret=True)
    _assert_same_bits(got, powers, (1, 2, 4))
    arg1 = np.asarray(got[1][1])
    assert (arg1[0] == 0).all() and (arg1[2] == 0).all()
    assert arg1[1, 200 // 64] == 200 % 64 and (arg1[1, :3] == 0).all()


@pytest.mark.parametrize("hh,limit", [(2, 300), (3, 1), (16, 2047),
                                      (5, 640)])
def test_decimated_tile_masks_what_lies_past_the_end(hh, limit):
    """decimate.decimated_tile (both kernels' fetch): NaN in the
    block's columns at and past `limit` does not reach a real column
    (0 x NaN through the selection matmul would), and every hh-th
    column below it arrives exactly."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    groups, rows = 2, 8
    width = groups * hh * decimate.LANES
    x = np.asarray(_powers((rows, width), seed=hh)).copy()
    x[:, limit:] = np.nan

    def kernel(x_ref, o_ref, sel_ref):
        decimate.write_selection(sel_ref, hh, jnp.float32)
        o_ref[...] = decimate.decimated_tile(x_ref, sel_ref, hh, groups,
                                             jnp.int32(limit))

    got = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups * rows, decimate.LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((decimate.sel_row(hh + 1),
                                    decimate.LANES), jnp.float32)],
        interpret=True)(jnp.asarray(x))
    want = np.where(np.isnan(x), 0.0, x)[:, ::hh]      # (rows, groups*128)
    want = np.concatenate(np.split(want, groups, axis=1), axis=0)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_nonfinite_power_stays_in_its_column_group():
    """What kernels/decimate.py states of a non-finite source value: it
    reaches only the 128 output columns of its selection matmul, at
    harmonics >= 2; stage 1 (a plain read) and every other block keep
    the strided form's bits."""
    powers = _powers((6, 4096), seed=3)
    c = 1801
    powers = powers.at[4, c].set(jnp.inf)
    got = fr._lo_block_maxima(powers, (1, 2, 4), interpret=True)
    _assert_same_bits(got, powers, (1,))
    for h in (2, 4):
        want_max, want_arg = _oracle(powers, h)
        touched = np.zeros((6, -(-(4096 // h) // fr.BLOCK_R)), bool)
        if c < 4096 // h:
            touched[4, c // fr.BLOCK_R] = True         # hh = 1: r = c
        for hh in range(2, h + 1):
            g = c // (128 * hh)
            touched[4, 2 * g:2 * g + 2] = True
        np.testing.assert_array_equal(
            np.asarray(got[h][0])[~touched], want_max[~touched])
        np.testing.assert_array_equal(
            np.asarray(got[h][1])[~touched], want_arg[~touched])


@pytest.mark.parametrize("rows,nbins", [(38, 1500), (6, 700)])
def test_lo_stage_candidates_equals_the_parents(rows, nbins):
    """lo_stage_candidates here (the strided form: this is a CPU) and
    with the kernel in its place return what the parent's per-stage
    composition returned: interbin -> harmonic_sum -> blockmax_topk."""
    rng = np.random.default_rng(rows)
    wspec = jnp.asarray((rng.normal(size=(rows, nbins))
                         + 1j * rng.normal(size=(rows, nbins))
                         ).astype(np.complex64))
    powers = fr.interbin_powers(wspec)
    here = fr.lo_stage_candidates(wspec, STAGES, 16)
    maxima = fr._lo_block_maxima(powers, STAGES, interpret=True)
    for h in STAGES:
        want_v, want_b = fr.blockmax_topk(fr.harmonic_sum(powers, h), 16)
        tiled = fr._topk_blocks(*maxima[h], 16, fr.BLOCK_R)
        one = fr.all_stage_candidates(powers, (1, h)[h == 1:], 16)[h]
        for v, b in (here[h], tiled, one):
            np.testing.assert_array_equal(np.asarray(v), np.asarray(want_v))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(want_b))


def test_mesh_body_lo_output_equals_the_parents():
    """The mesh step's per-device body (parallel/mesh._local_search)
    calls the one entry point and returns the parent's candidates:
    its own loop of harmonic_sum + blockmax_topk a stage."""
    from tpulsar.parallel import mesh as pmesh

    nsub, T, ndms = 4, 2048, 3
    rng = np.random.default_rng(7)
    subb = jnp.asarray(rng.normal(size=(nsub, T)).astype(np.float32))
    shifts = jnp.asarray(rng.integers(0, 8, size=(ndms, nsub)), jnp.int32)
    nbins = T // 2 + 1
    spec = pmesh.SearchStepSpec(
        nsub=nsub, nfft=T, max_numharm=8, topk=8,
        whiten_edges=tuple(int(e) for e in fr._block_edges(nbins)),
        dd_pad=8)
    keep = jnp.ones((nbins,), jnp.float32)
    got = pmesh._local_search(subb, shifts, keep, spec)

    from tpulsar.kernels.dedisperse import _dedisperse_subbands_scan
    series = _dedisperse_subbands_scan(subb, shifts, 8)
    series = series - series.mean(axis=-1, keepdims=True)
    cspec = jnp.fft.rfft(series, axis=-1)
    powers = (jnp.abs(cspec) ** 2).at[..., 0].set(0.0)
    wpow = fr.whiten_powers(powers, spec.whiten_edges, estimator="median")
    p2 = fr.interbin_powers(fr.scale_spectrum(cspec, powers, wpow))
    assert set(got) == {1, 2, 4, 8}
    for h in (1, 2, 4, 8):
        want_v, want_b = fr.blockmax_topk(fr.harmonic_sum(p2, h), 8)
        np.testing.assert_array_equal(np.asarray(got[h][0]),
                                      np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(got[h][1]),
                                      np.asarray(want_b))


# every survey grid the cells run (2 * nbins): Mock ds 1, 2, 3, 5, 6,
# 10; WAPP ds 1, 5, 25; GBNCC ds 1, 2, 4, 8, 16 — at their row counts
@pytest.mark.parametrize("rows,ncols", [
    (38, 3_932_162), (64, 1_966_082), (38, 1_310_722), (38, 786_434),
    (38, 655_362), (38, 393_218), (38, 4_194_306), (38, 838_862),
    (38, 167_774), (102, 1_361_922), (102, 680_962), (102, 340_482),
    (102, 170_242), (102, 85_122), (6, 3_932_162), (1, 3_932_162),
    (4 * 38, 3_932_162),
])
def test_plan_covers_every_column_once_within_vmem(rows, ncols):
    p = fr.lo_harmsum_plan(rows, ncols, STAGES)
    assert p.stages == STAGES and p.tile % decimate.LANES == 0
    assert p.row_block % 8 == 0
    assert -(-rows // p.row_block) * p.row_block >= rows
    assert p.row_block <= fr._LO_ROWS_MAX
    for h, nt in zip(p.stages, p.ntiles):
        assert (nt - 1) * p.tile < ncols // h <= nt * p.tile
        # harmonic h's last block starts inside the array
        assert h * (nt - 1) * p.tile < ncols
    assert p.vmem_bytes <= fr._LO_VMEM_TARGET
    assert p.vmem_bytes < p.vmem_limit <= fr._LO_VMEM_MAX + (8 << 20)


@pytest.mark.parametrize("rows,ncols,stages,match", [
    (38, 1 << 20, (1, 2, 4, 8, 16, 32, 64), "VMEM"),   # S_2..S_64: 136 MB
    (38, 1 << 20, (2, 4), "start at 1"),
    (38, 1 << 20, (1, 4, 2), "increase"),
    (0, 1 << 20, (1, 2), "no rows"),
    (38, 0, (1, 2), "with a column"),
])
def test_plan_refuses_what_the_kernel_cannot_take(rows, ncols, stages,
                                                  match):
    with pytest.raises(ValueError, match=match):
        fr.lo_harmsum_plan(rows, ncols, stages)


def test_kernel_refuses_other_dtypes():
    with pytest.raises(ValueError, match="float32"):
        fr._lo_block_maxima(jnp.ones((4, 256), jnp.bfloat16), (1, 2),
                            interpret=True)


def test_strided_form_is_lowered_off_the_tpu():
    """No knob chooses the form: lax.platform_dependent does, per
    lowering.  Here (CPU) the program holds no Pallas call and the
    dispatch attributes say so; tests/test_chip_compile.py sees the
    kernel in the same program lowered for a v5e."""
    text = fr.lo_stage_candidates.lower(
        jnp.zeros((6, 3001), jnp.complex64), STAGES, 16
    ).compile().as_text()
    assert "tpu_custom_call" not in text
    assert fr.lo_dispatch_attrs(6, 3001, STAGES, "cpu") == {
        "lo_form": "strided", "lo_tile": 0}


@pytest.mark.parametrize("on", ["operand", "mesh"])
def test_a_chunks_span_carries_both_programs_forms(on):
    """dm_chunk / mesh_chunk get lo_form / lo_tile and the boxcar
    ladder's sp_form / sp_tile from one place, for the platform of the
    devices the chunk's operand (or the pass's mesh) lives on: a CPU
    here, so the strided and the plain form."""
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.search import executor

    where = (jnp.zeros(4) if on == "operand" else
             jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(1, 4),
                               ("beam", "dm")))
    assert executor._dispatch_attrs(
        (6, 9000), list(sp_k.DEFAULT_WIDTHS), (6, 3001), list(STAGES),
        where) == {"sp_form": "plain", "sp_tile": 0,
                   "lo_form": "strided", "lo_tile": 0}


@pytest.mark.parametrize("rows,platform,want", [
    (38, "tpu", "tiled"), (6, "tpu", "tiled"), (63, "tpu", "tiled"),
    (64, "tpu", "tiled"), (65, "tpu", "strided"), (76, "tpu", "strided"),
    (102, "tpu", "strided"), (4 * 38, "tpu", "strided"),
    (38, "cpu", "strided"), (102, "cpu", "strided"),
])
def test_dispatch_attrs_say_what_the_program_runs(rows, platform, want):
    """lo_form / lo_tile of a chunk's span: the kernel and its tile
    where the program was lowered for a TPU (the platform of its
    operands' devices, not this process's default) with at most 64
    rows a call (measured: PERF.md, PR 39), else the strided form and
    0 — by fr.lo_form, the rule _stage_block_maxima branches on."""
    assert fr.lo_form(rows, platform) == want
    got = fr.lo_dispatch_attrs(rows, 1_966_081, STAGES, platform)
    tile = fr.lo_harmsum_plan(rows, 3_932_162, STAGES).tile
    assert got == {"lo_form": want,
                   "lo_tile": tile if want == "tiled" else 0}
