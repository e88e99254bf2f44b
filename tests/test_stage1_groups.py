"""Stage 1 over subband groups, and stage 2 on its grouped branch, at
the shapes a 4096-channel beam forces (the GBNCC survey's GUPPI
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
# one time block, a length no block divides
NCHAN, NSUB, T, BLOCK_T = 256, 16, 2900, 512


def _block(dtype, seed):
    rng = np.random.default_rng(seed)
    # whole numbers under 256: their sums are exact in float32 in any
    # order, so "equal" below means equal
    return rng.integers(0, 256, size=(NCHAN, T)).astype(dtype)


def _shifts(smax, seed):
    rng = np.random.default_rng(seed + 1)
    sh = rng.integers(0, smax + 1, size=NCHAN).astype(np.int32)
    sh[NCHAN // NSUB - 1::NCHAN // NSUB] = 0    # a subband's top channel
    sh[0], sh[-2] = smax, smax
    return sh


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("smax", [200, 1800])       # S buckets 256, 2048
@pytest.mark.parametrize("group", [NSUB, 4, 1])
def test_grouped_stage1_equals_the_plain_twins(group, smax, dtype):
    """out[b, t] = sum_c data[b*cps + c, min(t + sh[b,c], T-1)] whatever
    the group: all subbands a step, a proper divisor, one — equal to the
    XLA map and to the benchmark's reference, downsampled or not."""
    import jax.numpy as jnp

    data, sh = _block(dtype, smax + group), _shifts(smax, group)
    S = pallas_dd.stage_overhang(smax)
    assert S == (256 if smax == 200 else 2048) and smax > BLOCK_T // 4
    for downsamp in (1, 3):
        got = np.asarray(pallas_dd.form_subbands_pallas(
            data, sh, NSUB, downsamp, block_t=BLOCK_T, group=group,
            interpret=True))
        twin = np.asarray(dd._form_subbands_jit(
            jnp.asarray(data), jnp.asarray(sh), NSUB, downsamp,
            dd._pad_bucket(smax)))
        plain = np.asarray(ref.form_subbands(jnp.asarray(data), sh, NSUB,
                                             downsamp))
        assert got.shape == (NSUB, T // downsamp)
        np.testing.assert_array_equal(got, twin)
        np.testing.assert_array_equal(got, plain)


def test_grouped_stage1_writes_its_geometry_on_the_stage_span():
    """`subbanding` carries what the wrapper dispatched: grid steps
    over the channel axis, block length, overhang, slabs."""
    from tpulsar.obs import trace

    data, sh = _block(np.uint8, 3), _shifts(200, 3)
    trace.start()
    try:
        with trace.span("subbanding"):
            pallas_dd.form_subbands_pallas(
                data, sh, NSUB, 1, block_t=BLOCK_T, group=4,
                interpret=True, slab_bytes=NCHAN * 2 * 1024)
        ev = [e for e in trace.events() if e["name"] == "subbanding"]
    finally:
        trace.reset()
    assert ev[-1]["args"]["sb_groups"] == 4
    assert ev[-1]["args"]["sb_block_t"] == BLOCK_T
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


def _tile_bytes(nchan, nsub, S, plan):
    return (6 * plan.group * (nchan // nsub) * (plan.block_t + S)
            + 4 * plan.group * plan.block_t)


@pytest.mark.parametrize("config,npasses,block_t", [
    ("palfa_mock", 57, 1024), ("palfa_wapp", 15, 4096)])
def test_stage1_plan_keeps_every_subband_a_step_on_mock_and_wapp(
        config, npasses, block_t):
    """At every overhang the Mock and WAPP plans reach, a uint8 block
    stages all its subbands in one grid step, at the block length the
    kernel has always run there (1024 and 4096)."""
    nchan, nsub = _geometry(config)[:2]
    passes = _pass_overhangs(config)
    assert len(passes) == npasses
    assert {S1 for _ds, S1, _S2 in passes} == {256}
    for _ds, S1, _S2 in passes:
        plan = pallas_dd.stage1_plan(nchan, nsub, S1, 1)
        assert plan.group == nsub and plan.window == plan.block_t + S1
        assert plan.block_t == block_t
        assert _tile_bytes(nchan, nsub, S1, plan) \
            <= pallas_dd.STAGE1_VMEM_BUDGET
        assert plan.vmem_bytes == 16 << 20


@pytest.mark.parametrize("pass_idx", range(389))
def test_stage1_plan_fits_a_tile_at_every_gbncc_pass(pass_idx):
    """4096 channels never fit whole (18.9 MB at the smallest tile):
    at every pass of the GBNCC plan the plan gives a group of whole
    subbands, a multiple of 8, whose tile fits the budget."""
    nchan, nsub = 4096, 128
    passes = _pass_overhangs("gbncc_guppi350")
    assert len(passes) == 389
    ds, S1, S2 = passes[pass_idx]
    assert S1 in (256, 512, 1024, 2048)
    plan = pallas_dd.stage1_plan(nchan, nsub, S1, 1)
    assert plan.group < nsub and nsub % plan.group == 0
    assert plan.group % 8 == 0
    assert _tile_bytes(nchan, nsub, S1, plan) \
        <= pallas_dd.STAGE1_VMEM_BUDGET
    assert (plan.block_t, plan.group) == (4096, 8)
    # ... and stage 2 takes its grouped branch past the first DMs
    p2 = pallas_dd.stage2_plan(nsub, S2, 102, 1_361_920 // ds)
    assert p2.vmem_bytes <= pallas_dd.STAGE2_VMEM_BUDGET
    assert (p2.calls, p2.rows) == (4, 26)
    assert (p2.group < nsub) == (S2 >= 4096)


def test_gbncc_cell_passes_take_both_grouped_branches():
    """The first pass of each step (the benchmark cell's slice): stage
    1 in 16 groups everywhere, stage 2 in 2 groups at ds 2-16."""
    first = {}
    for ds, S1, S2 in _pass_overhangs("gbncc_guppi350"):
        first.setdefault(ds, (S1, S2))
    assert first == {1: (256, 256), 2: (256, 8192), 4: (256, 8192),
                     8: (512, 8192), 16: (1024, 8192)}
    for ds, (S1, S2) in first.items():
        assert 128 // pallas_dd.stage1_plan(4096, 128, S1, 1).group == 16
        p2 = pallas_dd.stage2_plan(128, S2, 102, 1_361_920 // ds)
        assert 128 // p2.group == (1 if ds == 1 else 2)


def test_stage1_plan_falls_back_to_a_stated_limit():
    """Where not even one group of 8 subbands fits at 512 samples, the
    plan still answers, and states the scoped VMEM the call needs."""
    plan = pallas_dd.stage1_plan(4096, 128, 16384, 1)
    assert (plan.block_t, plan.group) == (512, 8)
    assert plan.vmem_bytes > 16 << 20


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
