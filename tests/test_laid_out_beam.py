"""A beam block laid over several devices by channels (ONE ``jax.Array``
sharded ``P("chan", None)``), searched share by share: RFI statistics,
stage 1 and the finish on each device's own channels, one exchange a
pass into the trial-sharded search (``SearchParams.dm_shards``), the
read-in that lays a beam out itself.  On four of the eight virtual CPU
devices ``tests/conftest.py`` forces; every comparison is with the same
bits whole on one device, to the bit where sums of bytes make it so.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpulsar.kernels import dedisperse as dd
from tpulsar.kernels import pallas_dd, rfi
from tpulsar.obs import telemetry, trace
from tpulsar.parallel import mesh as pmesh
from tpulsar.plan import ddplan
from tpulsar.search import executor

NCHAN, NSUB, T = 64, 16, 20000
FREQS = np.linspace(1000.0, 1500.0, NCHAN)
DT = 1e-3


def lay_out(host, devices=None, axis="chan"):
    devices = jax.devices()[:4] if devices is None else devices
    mesh = Mesh(np.asarray(devices), (axis,))
    return jax.device_put(host, NamedSharding(mesh, P(axis, None)))


@pytest.fixture(scope="module")
def bits():
    """Full-range bytes: a subband of 4 channels stays under 2^10, a
    trial's sum under 2^14, every float32 sum exact in any order."""
    return np.random.default_rng(43).integers(0, 256, (NCHAN, T),
                                              dtype=np.uint8)


# ----------------------------------------------- (a) stage 1 share by share

@pytest.mark.parametrize("downsamp", [1, 4])
@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_stage_1_of_a_laid_out_block_is_the_whole_blocks_to_the_bit(
        bits, form, downsamp):
    ch, _sub = dd.plan_pass_shifts(FREQS, NSUB, 300.0, np.array([300.0]),
                                   DT, downsamp)
    assert ch.max() > 40
    whole, laid = jnp.asarray(bits), lay_out(bits)
    if form == "xla":
        a = dd.form_subbands(whole, jnp.asarray(ch), NSUB, downsamp)
        b = dd.form_subbands(laid, jnp.asarray(ch), NSUB, downsamp)
    else:
        # three slabs, so the join and the ragged last slab run too
        kw = dict(interpret=True, slab_bytes=200_000)
        a = pallas_dd.form_subbands_pallas(whole, ch, NSUB, downsamp, **kw)
        b = pallas_dd.form_subbands_pallas(laid, ch, NSUB, downsamp, **kw)
    assert b.shape == (NSUB, T // downsamp) and b.dtype == jnp.float32
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # the subbands come out laid over the same devices, by subband
    cm = pmesh.channel_mesh(b)
    assert list(cm.devices.flat) == jax.devices()[:4]
    assert {s.data.shape for s in b.addressable_shards} == \
        {(NSUB // 4, T // downsamp)}
    assert pmesh.channel_mesh(a) is None


def test_a_share_takes_the_geometry_of_its_own_channels(bits):
    """The kernel's plan is a share's (nchan / N, nsub / N), the span
    says how many shares ran, and a subband over two devices is
    refused."""
    ch, _ = dd.plan_pass_shifts(FREQS, NSUB, 100.0, np.array([100.0]), DT, 1)
    trace.start()
    try:
        with trace.span("subbanding", _stage=True):
            pallas_dd.form_subbands_pallas(lay_out(bits), ch, NSUB, 1,
                                           interpret=True)
        (span,) = [e["args"] for e in trace.events()
                   if e["name"] == "subbanding"]
    finally:
        trace.reset()
    S = pallas_dd.stage_overhang(int(ch.max()))
    plan = pallas_dd.stage1_plan(NCHAN // 4, NSUB // 4, S, 1)
    assert span["shards"] == 4 and span["sb_block_t"] == plan.block_t
    assert span["sb_groups"] == NSUB // 4 // plan.group
    with pytest.raises(ValueError, match="straddle"):
        pallas_dd.form_subbands_pallas(lay_out(bits), np.zeros(NCHAN, int),
                                       2, 1, interpret=True)
    with pytest.raises(ValueError, match="straddle"):
        dd.form_subbands(lay_out(bits), jnp.zeros(NCHAN, jnp.int32), 2, 1)


def test_only_a_layout_by_equal_shares_of_the_first_axis_is_a_layout(bits):
    assert pmesh.channel_mesh(bits) is None
    assert pmesh.channel_mesh(jnp.asarray(bits)) is None
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("t",))
    by_time = jax.device_put(bits, NamedSharding(mesh, P(None, "t")))
    with pytest.raises(ValueError, match="no layout by equal shares"):
        pmesh.channel_mesh(by_time)
    copies = jax.device_put(bits, NamedSharding(mesh, P()))
    with pytest.raises(ValueError, match="no layout by equal shares"):
        pmesh.channel_mesh(copies)
    back = lay_out(bits, devices=jax.devices()[4:8][::-1])
    assert list(pmesh.channel_mesh(back).devices.flat) == \
        jax.devices()[4:8][::-1]


# ------------------------------------------------------- (b) the RFI mask

def test_the_rfi_mask_of_a_laid_out_block_is_the_whole_blocks(bits):
    host = bits.copy()
    host[37] = 250                      # a loud channel
    host[:, 6000:6700] //= 2            # a quiet interval
    whole, laid = jnp.asarray(host), lay_out(host)
    m1 = rfi.find_rfi_chan(whole, DT, block_len=512)
    trace.start()
    try:
        with trace.span("rfifind", _stage=True):
            m2 = rfi.find_rfi_chan(laid, DT, block_len=512)
        (span,) = [e["args"] for e in trace.events()
                   if e["name"] == "rfifind"]
    finally:
        trace.reset()
    assert span["shards"] == 4
    assert m1.cell_mask.any() and m1.bad_channels[37]
    assert np.array_equal(m1.cell_mask, m2.cell_mask)
    assert np.array_equal(m1.bad_channels, m2.bad_channels)
    assert np.array_equal(m1.bad_blocks, m2.bad_blocks)
    assert np.allclose(m1.chan_fill, m2.chan_fill, atol=1e-6, rtol=0)
    args = (jnp.asarray(m1.full_mask()), jnp.asarray(m1.chan_fill),
            m1.block_len)
    out = rfi.apply_mask_chan(laid, *args)
    assert np.array_equal(np.asarray(out),
                          np.asarray(rfi.apply_mask_chan(whole, *args)))
    # elementwise: it partitions as it stands, each share masked where
    # it lies
    assert out.sharding.is_equivalent_to(laid.sharding, 2)
    assert list(pmesh.channel_mesh(out).devices.flat) == jax.devices()[:4]


# ------------------------------------- (c) search_block on a laid-out block

@pytest.fixture(scope="module")
def beam():
    """A 4-bit-range noise block with a pulse train at DM 0, two
    passes (ds 1 and 2), the mesh of four."""
    rng = np.random.default_rng(7)
    n = 32768
    blk = rng.integers(0, 16, (NCHAN, n), dtype=np.uint8)
    blk += (3 * ((np.arange(n) % 100) < 3)).astype(np.uint8)[None, :]
    plan = [ddplan.DedispStep(0.0, 1.0, 10, 1, NSUB, 1),
            ddplan.DedispStep(10.0, 2.0, 10, 1, NSUB, 2)]
    params = executor.SearchParams(
        dm_shards=4, nsub=NSUB, run_hi_accel=False, make_plots=False,
        max_cands_to_fold=2)
    return blk, plan, params


def _search(block, plan, params):
    trace.start()
    try:
        res = executor.search_block(block, FREQS, DT, plan, params)
        events = trace.events()
    finally:
        trace.reset()
    return res, events


def _cand_key(c):
    return (round(c.r, 3), round(c.z, 2), c.numharm, round(c.dm, 4),
            round(c.sigma, 5))


@pytest.mark.parametrize("min_bytes, forms, hi", [
    (0, ["partial", "partial"], False),
    # both sides of the threshold in one call: ds=1 over the bytes,
    # ds=2 under; and with the hi stage behind the exchange
    (1 << 20, ["partial", "replicate"], False),
    (1 << 20, ["partial", "replicate"], True)])
def test_a_laid_out_block_searches_as_the_same_bits_on_one_device(
        beam, min_bytes, forms, hi):
    blk, plan, params = beam
    params = dataclasses.replace(
        params, seq_shard_min_bytes=min_bytes,
        run_hi_accel=hi, hi_accel_zmax=8, topk_per_stage=8)
    whole = jax.device_put(blk, jax.devices()[0])
    (c1, f1, e1, n1), ev1 = _search(whole, plan, params)
    (c2, f2, e2, n2), ev2 = _search(lay_out(blk), plan, params)
    assert n1 == n2 == 20 and len(c1) > 0
    assert sorted(map(_cand_key, c1)) == sorted(map(_cand_key, c2))
    assert np.array_equal(e1, e2) and len(e1) > 0
    assert len(f1) == len(f2) > 0
    for a, b in zip(f1, f2):
        assert (a.period_s, a.dm) == (b.period_s, b.dm)
        assert np.array_equal(a.profile, b.profile)
    # one exchange a pass, by name, only where the block is laid out
    assert not [e for e in ev1 if e["name"] == "mesh-exchange"]
    ex = [e["args"] for e in ev2 if e["name"] == "mesh-exchange"]
    assert [a["form"] for a in ex] == forms
    assert all(a["devices"] == 4 and a["bytes"] > 0 for a in ex)
    assert {e["args"]["block_shards"] for e in ev2
            if e["name"] == "pass"} == {4}
    assert {e["args"]["block_shards"] for e in ev1
            if e["name"] == "pass"} == {1}
    assert {e["args"].get("shards") for e in ev2
            if e["name"] == "subbanding"} == {4}
    # `mesh-place` keeps its name and its place; the subbands' bytes
    # are the exchange's now
    assert [e["args"]["parent"] for e in ev2
            if e["name"] == "mesh-exchange"] == ["pass", "pass"]


def test_the_exchange_counts_its_bytes_by_form(beam):
    blk, plan, params = beam

    def totals():
        snap = telemetry.metrics.REGISTRY.snapshot()
        return dict((snap.get("tpulsar_mesh_exchange_bytes_total")
                     or {}).get("series", {}))

    base = totals()
    params = dataclasses.replace(params, seq_shard_min_bytes=1 << 40)
    executor.search_block(lay_out(blk), FREQS, DT, plan[:1], params)
    got = {k: v - base.get(k, 0.0) for k, v in totals().items()}
    nbytes = NSUB * blk.shape[1] * 4
    assert got["replicate"] == 3 * nbytes      # a copy to each other chip
    params = dataclasses.replace(params, seq_shard_min_bytes=0)
    executor.search_block(lay_out(blk), FREQS, DT, plan[:1], params)
    got = {k: v - base.get(k, 0.0) for k, v in totals().items()}
    # the partial sums of the 12 padded rows, three quarters of each
    assert got["partial"] == 3 * 12 * blk.shape[1] * 4


def test_the_partial_form_keeps_a_groups_partial_sums_bounded():
    assert pmesh.partial_groups(13, 4, 6_103_040) == 13
    assert pmesh.partial_groups(26, 4, 6_103_040) == 13
    assert pmesh.partial_groups(26, 4, 3_051_520) == 26
    assert pmesh.partial_groups(7, 4, 1 << 30) == 1


def test_the_mesh_splits_a_pass_whose_rows_would_not_fit_a_call():
    """26 rows a device at nfft 6,144,000 is over the fused program's
    budget: two even calls of 13; the accepted mesh cell's 6 rows at
    Mock's length are far under theirs."""
    assert executor._mesh_rows_budget(6_144_000, 6 << 30) == 13
    assert executor._mesh_rows_budget(3_072_000, 6 << 30) == 26
    assert executor._mesh_rows_budget(3_932_160, 6 << 30) >= 6


# ------------------------------- (c') hi-accel behind the exchange, ds 2 first

def _hi_rows():
    snap = telemetry.metrics.REGISTRY.snapshot()
    return dict((snap.get("tpulsar_mesh_hi_rows_total")
                 or {}).get("series", {}))


def _hi_params(params, min_bytes=1 << 20):
    return dataclasses.replace(
        params, seq_shard_min_bytes=min_bytes, run_hi_accel=True,
        hi_accel_zmax=8, topk_per_stage=8)


def test_hi_accel_over_a_laid_out_beam_in_the_ds2_ds1_order(beam):
    """The order the benchmark's hi-accel cells list their steps in
    (the downsampled pass, the pulsar's, first): the laid-out beam's
    candidates are the whole block's on the same mesh, the exchange is
    `replicate` then `partial`, every call ran the hi stage in the
    fused program, and a traced `mesh_chunk` says by which form its
    subbands came and what its rows' planes hold."""
    from tpulsar.kernels import accel as accel_k

    blk, plan, params = beam
    plan, params = plan[::-1], _hi_params(params)
    base = _hi_rows()
    whole = jax.device_put(blk, jax.devices()[0])
    (c1, _f1, e1, n1), ev1 = _search(whole, plan, params)
    (c2, _f2, e2, n2), ev2 = _search(lay_out(blk), plan, params)
    assert n1 == n2 == 20
    assert any(c.z != 0 for c in c1)
    assert sorted(map(_cand_key, c1)) == sorted(map(_cand_key, c2))
    assert np.array_equal(e1, e2)
    assert [e["args"]["form"] for e in ev2
            if e["name"] == "mesh-exchange"] == ["replicate", "partial"]
    got = {k: v - base.get(k, 0.0) for k, v in _hi_rows().items()}
    assert got == {"fused": 40.0}           # both searches, no fallback
    for events, forms in ((ev1, ["none", "none"]),
                          (ev2, ["replicate", "partial"])):
        calls = [e["args"] for e in events if e["name"] == "mesh_chunk"]
        assert [a["form"] for a in calls] == forms
        assert all(a["hi"] for a in calls)
        for a, T_ds in zip(calls, (blk.shape[1] // 2, blk.shape[1])):
            nbins = ddplan.choose_n(T_ds) // 2 + 1
            assert a["plane_bytes"] == a["rows_per_device"] * \
                accel_k.plane_row_bytes(nbins, len(accel_k.z_grid(8)),
                                        accel_k.corr_z_pieces())


@pytest.mark.parametrize("min_bytes, outcome", [
    (1 << 40, "falls back"), (0, "refused")])
def test_the_hi_fallback_on_a_laid_out_beam_fits_or_is_refused(
        beam, monkeypatch, min_bytes, outcome):
    """The batched path pinned off.  Subbands every chip was given
    whole (`replicate`) go down the single-device route in the
    one-device loop's chunks and find what the fused program finds;
    subbands that stayed where they lie (`partial`: too large for a
    whole copy on any chip) are not gathered onto one: refused."""
    from tpulsar.kernels import accel as accel_k
    from tpulsar.search import degraded

    blk, plan, params = beam
    plan, params = plan[:1], _hi_params(params, min_bytes)
    if outcome == "falls back":
        (good, _f, _e, _n), _ = _search(lay_out(blk), plan, params)
    monkeypatch.setattr(accel_k, "_BATCH_OK", False)
    base = _hi_rows()
    sizes = []
    sound = executor._hi_accel_chunk
    monkeypatch.setattr(
        executor, "_hi_accel_chunk",
        lambda wspec, dm_chunk, *a: sizes.append(len(dm_chunk))
        or sound(wspec, dm_chunk, *a))
    monkeypatch.setattr(executor, "pass_chunk_size", lambda *a: 4)
    if outcome == "refused":
        with pytest.raises(ValueError, match="single-device route on a "
                           "laid-out beam.*seq_shard_min_bytes=0"):
            executor.search_block(lay_out(blk), FREQS, DT, plan, params)
        assert not sizes and _hi_rows() == base
        return
    (cands, _f, _e, n), events = _search(lay_out(blk), plan, params)
    assert n == 10 and sizes == [4, 4, 2]
    assert sorted(map(_cand_key, cands)) == sorted(map(_cand_key, good))
    got = {k: v - base.get(k, 0.0) for k, v in _hi_rows().items()}
    assert got.get("fallback") == 10.0 and not got.get("fused")
    assert "sharded_hi_fallback" in degraded.snapshot()
    assert not any(e["args"]["hi"] for e in events
                   if e["name"] == "mesh_chunk")


# --------------------------------------------- (d) devices that do not match

@pytest.mark.parametrize("pick, says", [
    (slice(1, 5), "same order"), (slice(3, None, -1), "same order"),
    (slice(0, 2), "laid over 2 devices")])
def test_a_block_on_other_devices_than_the_mesh_raises_before_a_pass(
        beam, pick, says):
    blk, plan, params = beam
    passes = []
    laid = lay_out(blk, devices=jax.devices()[pick])
    with pytest.raises(ValueError, match=says):
        executor.search_block(laid, FREQS, DT, plan, params,
                              progress_cb=passes.append)
    assert passes == []


def test_one_device_reads_a_laid_out_beams_subbands_never_its_block(beam):
    """dm_shards = 1 is no mesh: the one-device pass loop takes each
    pass's subbands whole on the block's first device (stage 1 still
    runs share by share) and finds what the whole block gives."""
    blk, plan, params = beam
    params = dataclasses.replace(params, dm_shards=1)
    (c1, _f, e1, n1), _ = _search(jax.device_put(blk, jax.devices()[0]),
                                  plan, params)
    (c2, _f, e2, n2), ev = _search(lay_out(blk), plan, params)
    assert n1 == n2 == 20
    assert sorted(map(_cand_key, c1)) == sorted(map(_cand_key, c2))
    assert np.array_equal(e1, e2)
    assert not [e for e in ev if e["name"] == "mesh-exchange"]
    assert {e["args"].get("shards") for e in ev
            if e["name"] == "subbanding"} == {4}


# ------------------------------------------------------- (e) the read-in

@pytest.mark.parametrize("native", [True, False])
def test_the_read_in_lays_a_beam_over_the_mesh_by_channels(
        tmp_path, monkeypatch, native):
    """A toy 4-bit file with dm_shards = 4 and the budget forced small:
    the block comes back laid over the mesh's devices by channels, equal
    to the one-device read-in's, with the mask found share by share;
    the native decode and the NumPy decode feed it unchanged."""
    from tpulsar import native as native_mod
    from tpulsar.io import synth
    from tpulsar.io.psrfits import SpectraInfo
    from tpulsar.search.report import StageTimers

    if not native:
        monkeypatch.setattr(native_mod, "load", lambda: None)
    spec = synth.BeamSpec(nchan=64, nsamp=1 << 14, nbits=4)
    paths = synth.synth_beam(str(tmp_path / "beam"), spec, merged=True)
    params = executor.SearchParams(dm_shards=4, nsub=16,
                                   block_quantize="on")

    def read(budget, sub):
        monkeypatch.setattr(executor, "READIN_WHOLE_MAX_BYTES", budget)
        out = tmp_path / sub
        out.mkdir()
        trace.start()
        try:
            data, mask = executor._read_and_mask(
                SpectraInfo(paths), params, "beam", str(out), None,
                StageTimers())
            (place,) = [e["args"] for e in trace.events()
                        if e["name"] == "readin-place"]
        finally:
            trace.reset()
        return data, mask, place

    whole, m1, p1 = read(6 << 30, "whole")
    laid, m2, p2 = read(1 << 10, "laid")
    assert p1["devices"] == 1 and pmesh.channel_mesh(whole) is None
    assert p2["devices"] == 4 and p2["bytes"] == p1["bytes"]
    assert list(pmesh.channel_mesh(laid).devices.flat) == \
        list(executor.dm_mesh(4).devices.flat)
    assert laid.shape == whole.shape == (64, 1 << 14)
    assert laid.dtype == whole.dtype == np.uint8
    assert np.array_equal(np.asarray(laid), np.asarray(whole))
    assert np.array_equal(m1.cell_mask, m2.cell_mask)
    assert np.allclose(m1.chan_fill, m2.chan_fill, atol=1e-6, rtol=0)
    # a subband that would straddle two devices: the beam stays whole
    odd = dataclasses.replace(params, nsub=2)
    assert executor._readin_devices(np.zeros((8, 64), np.uint8), odd) == \
        [None]
