"""RFI mask kernel tests."""

import jax.numpy as jnp
import numpy as np

from tpulsar.io import synth
from tpulsar.kernels import rfi


def test_clean_data_mostly_unmasked():
    rng = np.random.default_rng(0)
    data = rng.standard_normal((8192, 16)).astype(np.float32)
    mask = rfi.find_rfi(data, dt=1e-3, block_len=512)
    assert mask.masked_fraction < 0.05
    assert not mask.bad_channels.any()


def test_tone_channel_flagged():
    spec = synth.BeamSpec(nchan=16, nsamp=8192, nsblk=64)
    data = synth.make_dynamic_spectrum(
        spec, rfi=[synth.RFISpec(kind="tone", channel=5, amplitude=4.0)])
    mask = rfi.find_rfi(data, dt=spec.tsamp_s, block_len=512)
    assert mask.bad_channels[5]
    assert mask.bad_channels.sum() <= 2


def test_burst_blocks_flagged():
    spec = synth.BeamSpec(nchan=16, nsamp=8192, nsblk=64)
    t0 = 2000 * spec.tsamp_s
    data = synth.make_dynamic_spectrum(
        spec, rfi=[synth.RFISpec(kind="burst", t_start_s=t0,
                                 t_len_s=600 * spec.tsamp_s, amplitude=3.0)])
    mask = rfi.find_rfi(data, dt=spec.tsamp_s, block_len=512)
    burst_blocks = range(2000 // 512, (2000 + 600) // 512 + 1)
    assert any(mask.bad_blocks[b] for b in burst_blocks)


def test_apply_mask_replaces_bad_cells():
    rng = np.random.default_rng(1)
    data = rng.standard_normal((4096, 8)).astype(np.float32)
    data[1024:1536, 3] += 50.0
    mask = rfi.find_rfi(data, dt=1e-3, block_len=512)
    assert mask.cell_mask[2, 3] or mask.bad_channels[3]
    cleaned = np.asarray(rfi.apply_mask(
        jnp.asarray(data), jnp.asarray(mask.full_mask()), 512))
    assert abs(cleaned[1024:1536, 3].mean()) < 1.0  # spike removed
    # untouched cells unchanged
    np.testing.assert_allclose(cleaned[:512, 0], data[:512, 0])


def test_mask_roundtrip(tmp_path):
    rng = np.random.default_rng(2)
    data = rng.standard_normal((2048, 8)).astype(np.float32)
    mask = rfi.find_rfi(data, dt=1e-3, block_len=256)
    p = str(tmp_path / "beam_rfi.npz")
    mask.save(p)
    back = rfi.RFIMask.load(p)
    np.testing.assert_array_equal(back.cell_mask, mask.cell_mask)
    assert back.block_len == 256


def test_short_observation_mask_is_finite():
    """Observations shorter than one rfifind block must still produce
    a usable mask with a finite masked_fraction (a NaN fraction broke
    upload verification: NaN cannot round-trip SQLite)."""
    import math

    rng = np.random.default_rng(9)
    data = rng.standard_normal((100, 8)).astype(np.float32)  # T < 2048
    mask = rfi.find_rfi(data, dt=1e-3, block_len=2048)
    assert mask.block_len == 100
    assert mask.cell_mask.shape == (1, 8)
    assert math.isfinite(mask.masked_fraction)
    # apply_mask with the clamped block length round-trips the shape
    out = rfi.apply_mask(jnp.asarray(data),
                         jnp.asarray(mask.full_mask()), mask.block_len)
    assert out.shape == data.shape


def test_mask_quantization_roundtrip(tmp_path):
    """The per-channel dequantization affine saved with a quantized
    run's mask must load back exactly: a mask whose chan_fill is in
    quantized units is only re-applicable to float32 data through
    this map (round-2 advisor finding)."""
    import numpy as np

    from tpulsar.kernels.rfi import RFIMask

    nchan, nblocks = 8, 4
    mask = RFIMask(block_len=128, dt=1e-3,
                   cell_mask=np.zeros((nblocks, nchan), bool),
                   bad_channels=np.zeros(nchan, bool),
                   bad_blocks=np.zeros(nblocks, bool),
                   chan_fill=np.arange(nchan, dtype=np.float32))
    qscale = np.linspace(0.1, 2.0, nchan).astype(np.float32)
    qoff = np.linspace(-3.0, 3.0, nchan).astype(np.float32)
    p = str(tmp_path / "m.npz")
    mask.save(p, qscale=qscale, qoff=qoff)
    got = RFIMask.load_quantization(p)
    assert got is not None
    np.testing.assert_array_equal(got[0], qscale)
    np.testing.assert_array_equal(got[1], qoff)
    # float32 runs carry no map
    p2 = str(tmp_path / "m2.npz")
    mask.save(p2)
    assert RFIMask.load_quantization(p2) is None
    # the mask itself still round-trips
    m2 = RFIMask.load(p)
    np.testing.assert_array_equal(m2.chan_fill, mask.chan_fill)


import pytest  # noqa: E402


@pytest.mark.parametrize("dtype, T, block_len", [
    (np.uint8, 4096, 512),       # whole blocks, the beams' dtype
    (np.uint8, 4096 + 300, 512),  # samples past the last whole block
    (np.float32, 2048, 256),     # an unquantized beam
    (np.uint8, 700, 700),        # one block, clamped to the observation
    (np.uint8, 1536, 512),       # three blocks: not a power of two
])
def test_apply_mask_chan_is_the_select_block_by_block(dtype, T, block_len):
    """Channel-major masking against the plain NumPy select: masked
    cells take the channel's fill (rounded for integer data), every
    other sample and the tail past the last whole block stay to the
    bit, and the output is the input's dtype and shape."""
    rng = np.random.default_rng(5)
    nchan, nblocks = 12, T // block_len
    data = (rng.integers(0, 16, (nchan, T)).astype(dtype)
            if dtype == np.uint8
            else rng.standard_normal((nchan, T)).astype(dtype))
    cell_mask = rng.random((nblocks, nchan)) < 0.3
    cell_mask[0, 0] = True
    fill = rng.uniform(2.0, 12.0, nchan).astype(np.float32)
    want = data.copy()
    fillv = np.round(fill).astype(dtype) if dtype == np.uint8 \
        else fill.astype(dtype)
    for b in range(nblocks):
        for c in np.flatnonzero(cell_mask[b]):
            want[c, b * block_len:(b + 1) * block_len] = fillv[c]
    got = rfi.apply_mask_chan(jnp.asarray(data), jnp.asarray(cell_mask),
                              jnp.asarray(fill), block_len)
    assert got.dtype == dtype and got.shape == data.shape
    assert np.array_equal(np.asarray(got), want)
    assert not np.array_equal(want, data)
