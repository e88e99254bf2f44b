"""The boxcar ladder's tiled kernel (singlepulse._ladder_block_maxima)
against the plain chain form every other platform lowers: the kernel
runs here in Pallas's interpreter, which fills what lies past an
array's end with NaN — so every ragged shape below also plants NaN
past the end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulsar.kernels import fourier as fr
from tpulsar.kernels import singlepulse as sp

TILE = sp._SP_TILE


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """Every case compiles the interpreted kernel anew (its 32-chunk
    loop unrolled: a large CPU program); a worker that keeps all of
    them (--dist loadfile gives one worker the whole file) died in the
    CPU compiler after ~110.  Nothing here reuses a program."""
    yield
    jax.clear_caches()


def _series(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _assert_same_bits(got, want):
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.int32
    assert got[0].shape == want[0].shape == got[1].shape
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# rows: one series, the mesh's 6 and 13 a device, Mock's 38 and 64,
# GBNCC's 102 (three row groups, the last ragged).  T: whole tiles; a
# ragged last tile; shorter than the widest boxcar; shorter than a tile
@pytest.mark.parametrize("widths", [sp.DEFAULT_WIDTHS, (1,), (5, 7)])
@pytest.mark.parametrize("T", [2 * TILE, 2 * TILE + 808, 20, 3000])
@pytest.mark.parametrize("rows", [1, 6, 13, 38, 64, 102])
def test_kernel_bit_identical_to_plain_form(rows, T, widths):
    """Values AND in-block argmax of every width's block maxima are the
    plain form's bit for bit: the same chain of float32 additions, the
    same -inf past the last whole window, the same first-index
    argmax."""
    x = _series((rows, T), seed=rows + T)
    got = sp._ladder_block_maxima(x, widths, interpret=True)
    assert got[0].shape == (len(widths), rows, -(-T // sp.BLOCK))
    _assert_same_bits(got, sp._plain_block_maxima(x, widths))


@pytest.mark.parametrize("widths", [sp.DEFAULT_WIDTHS, (2, 30), (128,)])
def test_tie_inside_a_block_takes_the_first_index(widths):
    x = jnp.ones((3, TILE + 300), jnp.float32)      # every block a tie
    x = x.at[1, 200:280].set(5.0)                   # ties above, too
    got = sp._ladder_block_maxima(x, widths, interpret=True)
    _assert_same_bits(got, sp._plain_block_maxima(x, widths))
    arg = np.asarray(got[1])
    # whole blocks of equal sums (the series' last block is not one:
    # its windows past the end read -inf, so its first wins anyway)
    assert (arg[:, 0] == 0).all() and (arg[:, 2] == 0).all()
    wi = len(widths) - 1
    w = widths[wi]
    if w <= 32:
        # width w's sums are level over [200, 280 - w]: block 7's
        # (samples 224..255) tie from its first sample on
        assert arg[wi, 1, 7] == 0
        assert arg[wi, 1, 6] == 200 % 32            # first to reach 5 w


@pytest.mark.parametrize("w", [2, 9, 30])
@pytest.mark.parametrize("edge", [TILE, 2 * TILE, 128, TILE - 128])
def test_window_straddling_a_boundary_is_found_at_its_sample(w, edge):
    """A pulse whose window starts before a tile's (or a 128-lane
    chunk's) last sample and ends past it is found at its true first
    sample, with the whole pulse's sum: the shifted read takes its top
    lanes from the next chunk, the last chunk's from the halo."""
    T = 2 * TILE + 500
    start = edge - w // 2 - (w % 2)         # straddles `edge`
    x = np.zeros((2, T), np.float32)
    x[1, start:start + w] = 3.0
    got = sp._ladder_block_maxima(jnp.asarray(x), (1, w), interpret=True)
    _assert_same_bits(got, sp._plain_block_maxima(jnp.asarray(x), (1, w)))
    bmax, barg = np.asarray(got[0])[1, 1], np.asarray(got[1])[1, 1]
    b = int(np.argmax(bmax))
    assert b * sp.BLOCK + barg[b] == start
    assert bmax[b] == np.float32(3.0 * w) * sp._scale(w)


@pytest.mark.parametrize("T", [TILE, TILE + 5, 2 * TILE - 3, 700, 31])
def test_windows_past_the_end_never_win(T):
    """The largest samples are the series' last: every window that
    would run past the end reads -inf, whatever lies beyond (NaN, in
    the interpreter), so no sample index reaches past T - w."""
    x = np.zeros((5, T), np.float32)
    x[:, -3:] = 100.0
    widths = sp.DEFAULT_WIDTHS
    got = sp._ladder_block_maxima(jnp.asarray(x), widths, interpret=True)
    _assert_same_bits(got, sp._plain_block_maxima(jnp.asarray(x), widths))
    bmax, barg = (np.asarray(a) for a in got)
    assert not np.isnan(bmax).any()
    pos = np.arange(bmax.shape[-1]) * sp.BLOCK + barg
    for wi, w in enumerate(widths):
        live = np.isfinite(bmax[wi])
        assert (pos[wi][live] <= T - w).all()
        if T >= w:
            assert (bmax[wi][:, (T - w) // sp.BLOCK] > 0).all()
        else:
            assert not live.any()
    vals, idx = fr._topk_blocks(got[0][-1], got[1][-1], 4, sp.BLOCK)
    if T >= widths[-1]:
        # the widest window that still holds the three: the last whole
        assert (np.asarray(idx)[:, 0] == T - widths[-1]).all()


@pytest.mark.parametrize("w", sp.DEFAULT_WIDTHS + (5, 7, 100))
def test_plain_form_against_a_float64_direct_sum(w):
    """Every width's chain adds w float32 samples in a tree at most w
    deep: within 1e-5 * sqrt(w) of the float64 direct sum on a
    unit-variance series (the cumulative sums it replaces drifted with
    the prefix's size, not the window's)."""
    T = 5000
    x = np.asarray(_series((4, T), seed=w))
    x64 = x.astype(np.float64)
    cs = np.concatenate([np.zeros((4, 1)), np.cumsum(x64, axis=-1)], -1)
    snr = (cs[:, w:] - cs[:, :-w]) / np.sqrt(w)           # (4, T-w+1)
    pad = -(-T // sp.BLOCK) * sp.BLOCK - snr.shape[-1]
    blocks = np.pad(snr, ((0, 0), (0, pad)),
                    constant_values=-np.inf).reshape(4, -1, sp.BLOCK)
    bmax, barg = sp._plain_block_maxima(jnp.asarray(x), (w,))
    bmax, barg = np.asarray(bmax)[0], np.asarray(barg)[0]
    best = blocks.max(-1)
    live = np.isfinite(best)          # a block past the last window
    assert (np.isfinite(bmax) == live).all()
    assert np.abs(bmax[live] - best[live]).max() < 1e-5 * np.sqrt(w)
    # the float32 maximum sits at a sample whose float64 sum is within
    # the same distance of the block's best
    at = np.take_along_axis(blocks, barg[..., None], -1)[..., 0]
    assert np.abs(at[live] - best[live]).max() < 1e-5 * np.sqrt(w)


@pytest.mark.parametrize("widths,shifts", [
    (sp.DEFAULT_WIDTHS, 10), ((1,), 0), ((5, 7), 4), ((100,), 8),
    ((30, 2, 2, 9), 8),
])
def test_chain_builds_every_width_from_what_is_there(widths, shifts):
    have = {1}
    n = 0
    for w, parts in sp.boxcar_chain(widths):
        assert sum(parts) == w and set(parts) <= have
        have.add(w)
        n += len(parts) - 1
    assert set(widths) <= have and n == shifts


@pytest.mark.parametrize("rows,T,widths,match", [
    (0, 100, (1, 2), "nothing to search"),
    (4, 100, (), "nothing to search"),
    (4, 100, (1, 129), "halo"),
])
def test_plan_refuses_what_the_kernel_cannot_take(rows, T, widths, match):
    with pytest.raises(ValueError, match=match):
        sp.sp_boxcar_plan(rows, T, widths)


@pytest.mark.parametrize("rows,T,ntiles", [
    (1, 20, 1), (6, 3_932_160, 960), (38, 3_932_160, 960),
    (76, 167_772, 41), (102, 1_361_920, 333)])
def test_plan_follows_the_shape(rows, T, ntiles):
    """Eight rows a grid step whatever the rows (the kernel's time goes
    with the rows it computes: PERF.md, PR 44), tiles of 4096 samples
    over the series, the chain of the widths given."""
    p = sp.sp_boxcar_plan(rows, T, [1, 30, 9])
    assert (p.row_block, p.tile, p.ntiles) == (8, TILE, ntiles)
    assert p.widths == (1, 30, 9)
    assert p.chain == sp.boxcar_chain((1, 9, 30))


def test_kernel_refuses_other_dtypes():
    with pytest.raises(ValueError, match="float32"):
        sp._ladder_block_maxima(jnp.ones((4, 256), jnp.bfloat16), (1, 2),
                                interpret=True)


@pytest.mark.parametrize("T,topk", [(2 * TILE + 808, 128), (700, 128),
                                    (20, 8)])
def test_search_over_the_kernels_maxima_equals_the_plain_search(T, topk):
    """boxcar_search's top-k over the kernel's block maxima (what a TPU
    program runs) equals the program lowered here, shapes and padding
    included."""
    x = _series((6, T), seed=T)
    widths = sp.DEFAULT_WIDTHS
    want = sp.boxcar_search(x, widths, topk)
    bmax, barg = sp._ladder_block_maxima(x, widths, interpret=True)
    for wi in range(len(widths)):
        v, i = fr._topk_blocks(bmax[wi], barg[wi], topk, sp.BLOCK)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(want[0][wi]))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(want[1][wi]))
    assert want[0].shape == want[1].shape == (len(widths), 6, topk)
