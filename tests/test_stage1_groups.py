"""Stage 1 on full vector registers over subband groups, and stage 2
on its grouped branch, at the shapes a 4096-channel beam forces (the GBNCC survey's GUPPI
geometry): the Pallas kernels in interpret mode against the XLA twin
and the benchmark's plain reference, and `pallas_dd.stage1_plan` /
`stage2_plan` over every pass of the three survey plans.
"""

import functools
import json
import os

import numpy as np
import pytest

from benchmark.harness import reference as ref
from benchmark.harness.cells import ROOT
from tpulsar.kernels import dedisperse as dd
from tpulsar.kernels import pallas_dd
from tpulsar.plan import ddplan

# a toy that keeps the GBNCC ratios: 16 channels a subband, shifts past
# one time segment, a length no block divides
NCHAN, NSUB, T, BLOCK_T = 256, 16, 2900, 1024


def _block(dtype, seed, nchan=NCHAN, nsamp=T):
    rng = np.random.default_rng(seed)
    # whole numbers under 256: their sums are exact in float32 in any
    # order, so "equal" below means equal
    return rng.integers(0, 256, size=(nchan, nsamp)).astype(dtype)


def _shifts(smax, seed, nchan=NCHAN, nsub=NSUB):
    rng = np.random.default_rng(seed + 1)
    sh = rng.integers(0, smax + 1, size=nchan).astype(np.int32)
    sh[nchan // nsub - 1::nchan // nsub] = 0    # a subband's top channel
    sh[0], sh[-2] = smax, smax
    return sh


def _equals_the_twins(data, sh, nsub, smax, **geometry):
    """The kernel in the interpreter against the XLA map and the
    benchmark's plain reference, downsampled or not: equal."""
    import jax.numpy as jnp

    for downsamp in (1, 3):
        got = np.asarray(pallas_dd.form_subbands_pallas(
            data, sh, nsub, downsamp, interpret=True, **geometry))
        twin = np.asarray(dd._form_subbands_jit(
            jnp.asarray(data), jnp.asarray(sh), nsub, downsamp,
            dd._pad_bucket(smax)))
        plain = np.asarray(ref.form_subbands(jnp.asarray(data), sh, nsub,
                                             downsamp))
        assert got.shape == (nsub, data.shape[1] // downsamp)
        np.testing.assert_array_equal(got, twin)
        np.testing.assert_array_equal(got, plain)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("smax", [200, 1800])       # S buckets 256, 2048
@pytest.mark.parametrize("group", [NSUB, 4, 1])
def test_grouped_stage1_equals_the_plain_twins(group, smax, dtype):
    """out[b, t] = sum_c data[b*cps + c, min(t + sh[b,c], T-1)] whatever
    the group: all subbands a step, a proper divisor, one — at segments
    of 128 samples under an overhang of 256 and of 512 under 2048 (an
    overhang that spans 3 and 5 segments), the last block on the edge
    clamp."""
    data, sh = _block(dtype, smax + group), _shifts(smax, group)
    S = pallas_dd.stage_overhang(smax)
    block_t = BLOCK_T if smax == 200 else 4 * BLOCK_T
    assert S == (256 if smax == 200 else 2048) and S > block_t // 8
    assert T % block_t
    _equals_the_twins(data, sh, NSUB, smax, block_t=block_t, group=group)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("case,nchan,nsub,nsamp,smax,geometry", [
    # a shift of exactly S (and of 0), the last block on the edge clamp
    ("shift-of-S", 256, 16, 2900, 256, dict(block_t=1024, group=8)),
    # T a multiple of 8 * seg: no column of the last block is padding
    ("whole-blocks", 256, 16, 3072, 200, dict(block_t=1024, group=8)),
    # a series shorter than one block: nearly all of it the edge
    ("one-short-block", 256, 16, 700, 200, dict(block_t=1024, group=16)),
    # the channel counts a subband of the three surveys: WAPP, Mock, GBNCC
    ("cps-4", 64, 16, 2900, 200, dict(block_t=1024, group=8)),
    ("cps-10", 160, 16, 2900, 200, dict(block_t=1024, group=8)),
    ("cps-32", 256, 8, 2900, 300, dict(block_t=2048, group=8)),
    # slabs of one block: every block's overhang but the last is the
    # next slab's start, read through the slab's tail
    ("slabs-of-a-block", 256, 16, 2900, 200,
     dict(block_t=1024, group=8, slab_cols=1024)),
    # ... and two blocks a slab at an overhang of 5 segments
    ("two-slabs", 160, 16, 11000, 1800,
     dict(block_t=4096, group=16, slab_cols=8192)),
    # the geometry the plan itself chooses, its blocks of 32768 samples
    ("the-plans-own", 64, 16, 40000, 200, dict()),
])
def test_full_register_stage1_at_the_edges(case, nchan, nsub, nsamp, smax,
                                           geometry, dtype):
    data = _block(dtype, len(case), nchan, nsamp)
    sh = _shifts(smax, len(case), nchan, nsub)
    assert sh.min() == 0 and sh.max() == smax
    geometry = dict(geometry)
    if "slab_cols" in geometry:     # the budget is in the block's dtype
        geometry["slab_bytes"] = (geometry.pop("slab_cols") * nchan
                                  * data.dtype.itemsize)
    _equals_the_twins(data, sh, nsub, smax, **geometry)


def test_stage1_refuses_a_block_it_cannot_tile():
    """8 segments of whole registers, none shorter than a seventh of
    the overhang (the next block's 8 sublanes are all a step has)."""
    data, sh = _block(np.uint8, 5), _shifts(1800, 5)
    for block_t in (512, 1536, 1024):
        with pytest.raises(ValueError, match="cannot tile"):
            pallas_dd.form_subbands_pallas(data, sh, NSUB, 1,
                                           block_t=block_t, interpret=True)


def test_grouped_stage1_writes_its_geometry_on_the_stage_span():
    """`subbanding` carries what the wrapper dispatched: grid steps
    over the channel axis, block length, samples a sublane, overhang,
    slabs."""
    from tpulsar.obs import trace

    data, sh = _block(np.uint8, 3), _shifts(200, 3)
    trace.start()
    try:
        with trace.span("subbanding"):
            pallas_dd.form_subbands_pallas(
                data, sh, NSUB, 1, block_t=BLOCK_T, group=4,
                interpret=True, slab_bytes=NCHAN * 1024)
        ev = [e for e in trace.events() if e["name"] == "subbanding"]
    finally:
        trace.reset()
    assert ev[-1]["args"]["sb_groups"] == 4
    assert ev[-1]["args"]["sb_block_t"] == BLOCK_T
    assert ev[-1]["args"]["sb_seg"] == BLOCK_T // 8
    assert ev[-1]["args"]["sb_overhang"] == 256
    assert ev[-1]["args"]["sb_slabs"] == 3


# ---------------------------------------------------------------- plans

def _geometry(config):
    """(nchan, nsub, dt, freqs, plan name) of a benchmark configuration,
    from its file as the cells run it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as fh:
        c = json.load(fh)
    nchan, bw = int(c["nchan"]), float(c["bw_mhz"])
    freqs = (float(c["fctr_mhz"]) - bw / 2) \
        + (np.arange(nchan) + 0.5) * (bw / nchan)
    return nchan, int(c["nsub"]), float(c["dt_s"]), freqs, c["backend"]


@functools.lru_cache(maxsize=None)
def _pass_overhangs(config):
    """((downsamp, stage-1 S, stage-2 S), ...) of every pass of a
    configuration's plan, from the table."""
    nchan, nsub, dt, freqs, backend = _geometry(config)
    out = []
    for step in ddplan.survey_plan(backend):
        for p in step.passes():
            ch, sb = dd.plan_pass_shifts(freqs, nsub, p.subdm,
                                         np.asarray(p.dms), dt,
                                         step.downsamp)
            out.append((step.downsamp,
                        pallas_dd.stage_overhang(int(ch.max())),
                        pallas_dd.stage_overhang(int(sb.max()))))
    return tuple(out)


def _step_bytes(nchan, nsub, S, plan):
    """What a grid step holds in VMEM for a uint8 block: the staged
    block, the next block's head and the slab's tail, and the float32
    output block, each in the pipeline's two buffers; one subband's
    float32 slab."""
    cps, seg, over = nchan // nsub, plan.block_t // 8, S + 128
    return (2 * plan.group * cps * 8 * (seg + 2 * min(over, seg))
            + 2 * 4 * plan.group * 8 * seg + 4 * cps * 8 * (seg + over))


@pytest.mark.parametrize("config,npasses,group,vmem", [
    ("palfa_mock", 57, 48, 55_566_336), ("palfa_wapp", 15, 64, 41_467_904)])
def test_stage1_plan_takes_whole_registers_on_mock_and_wapp(
        config, npasses, group, vmem):
    """At every overhang the Mock and WAPP plans reach, a uint8 block
    goes 8 segments of 4096 samples a step: WAPP's 256 channels all 64
    subbands at a time, Mock's 960 in two groups of 48."""
    nchan, nsub = _geometry(config)[:2]
    passes = _pass_overhangs(config)
    assert len(passes) == npasses
    assert {S1 for _ds, S1, _S2 in passes} == {256}
    for _ds, S1, _S2 in passes:
        plan = pallas_dd.stage1_plan(nchan, nsub, S1, 1)
        assert plan.group == group and nsub % group == 0
        assert plan.window == plan.block_t + S1
        assert (plan.block_t, plan.seg, plan.lanes, plan.head) == (
            32768, 4096, 4096 + 256 + 128, 256 + 128)
        assert _step_bytes(nchan, nsub, S1, plan) \
            <= pallas_dd.STAGE1_VMEM_BUDGET
        assert plan.vmem_bytes == vmem \
            == _step_bytes(nchan, nsub, S1, plan) + (4 << 20)


@pytest.mark.parametrize("pass_idx", range(389))
def test_stage1_plan_fits_a_tile_at_every_gbncc_pass(pass_idx):
    """4096 channels never fit whole (157 MB at 4096 samples a
    segment, 15 MB at 128): at every pass of the GBNCC plan the plan
    gives a group of 8 or more whole subbands whose step fits the
    budget at the longest segment, 16 subbands to an overhang of 1024
    and 8 at 2048."""
    nchan, nsub = 4096, 128
    passes = _pass_overhangs("gbncc_guppi350")
    assert len(passes) == 389
    ds, S1, S2 = passes[pass_idx]
    assert S1 in (256, 512, 1024, 2048)
    plan = pallas_dd.stage1_plan(nchan, nsub, S1, 1)
    assert plan.group < nsub and nsub % plan.group == 0
    assert plan.group >= 8
    assert _step_bytes(nchan, nsub, S1, plan) \
        <= pallas_dd.STAGE1_VMEM_BUDGET
    assert (plan.block_t, plan.group) == (32768, 8 if S1 == 2048 else 16)
    # ... and stage 2 takes its grouped branch past the first DMs
    p2 = pallas_dd.stage2_plan(nsub, S2, 102, 1_361_920 // ds)
    assert p2.vmem_bytes <= pallas_dd.STAGE2_VMEM_BUDGET
    assert (p2.calls, p2.rows) == (4, 26)
    assert (p2.group < nsub) == (S2 >= 4096)


def test_gbncc_cell_passes_take_both_grouped_branches():
    """The first pass of each step (the benchmark cell's slice): stage
    1 in 8 groups everywhere, stage 2 in 2 groups at ds 2-16."""
    first = {}
    for ds, S1, S2 in _pass_overhangs("gbncc_guppi350"):
        first.setdefault(ds, (S1, S2))
    assert first == {1: (256, 256), 2: (256, 8192), 4: (256, 8192),
                     8: (512, 8192), 16: (1024, 8192)}
    for ds, (S1, S2) in first.items():
        assert 128 // pallas_dd.stage1_plan(4096, 128, S1, 1).group == 8
        p2 = pallas_dd.stage2_plan(128, S2, 102, 1_361_920 // ds)
        assert 128 // p2.group == (1 if ds == 1 else 2)


def test_stage1_plan_falls_back_to_a_stated_limit():
    """Where not even one group of 8 subbands fits at the shortest
    segment an overhang allows (4096 samples at S 16384: it may span 7
    segments, no more), the plan still answers, and states the scoped
    VMEM the call needs."""
    plan = pallas_dd.stage1_plan(4096, 128, 16384, 1)
    assert (plan.block_t, plan.group) == (32768, 8)
    assert _step_bytes(4096, 128, 16384, plan) \
        > pallas_dd.STAGE1_VMEM_BUDGET
    assert plan.vmem_bytes == _step_bytes(4096, 128, 16384, plan) \
        + (4 << 20)


# -------------------------------------------------- stage 2 in groups

def test_grouped_stage2_equals_the_reference_past_4096():
    """`group` 64 of 128 subbands at an overhang of 8192 (the GBNCC
    passes' geometry at ds 2-16, `stage2_plan`'s own answer): each row
    equals the plain reference's one-trial sum, bit for bit."""
    nsub, Tn, rows, smax = 128, 16500, 3, 4700
    plan = pallas_dd.stage2_plan(nsub, pallas_dd.stage_overhang(smax),
                                 rows, Tn)
    assert (plan.group, plan.window) == (64, 2048 + 8192 + 128)
    rng = np.random.default_rng(11)
    subb = rng.integers(0, 4096, size=(nsub, Tn)).astype(np.float32)
    shifts = np.sort(rng.integers(0, smax + 1, size=(rows, nsub)),
                     axis=1)[:, ::-1].astype(np.int32)
    shifts[0, 0] = smax
    got = np.asarray(pallas_dd.dedisperse_subbands_pallas(
        subb, shifts, interpret=True))
    for k in range(rows):
        want = np.asarray(ref.dedisperse_one(subb, shifts[k]))
        np.testing.assert_array_equal(got[k], want)
