"""The spans inside the stages: stage 1's device steps under the pass
loop's `subbanding`, and the finish's re-formed subbands under
`refinement` and `folding`, as a traced `search_block` leaves them in
its `StageTimers` — with the Pallas stage 1 in interpret mode, two
slabs and a downsampled pass."""

import functools

import numpy as np
import pytest

from tpulsar.obs import trace

SB_STEPS = ("sb-slice", "sb-layout", "sb-kernel", "sb-join",
            "sb-downsample")


def traced_two_slab_search(tmp_dir: str):
    """(events, timers) of one traced `search_block` over a toy beam of
    two stage-1 slabs (`slab_bytes` forced down to one block of 32768
    samples) and one pass at downsamp 3, its pulsar folded.  Plain
    function: tests/benchmark_tests/ runs it too."""
    import jax.numpy as jnp
    from tpulsar.io import synth
    from tpulsar.io.psrfits import SpectraInfo
    from tpulsar.kernels import pallas_dd
    from tpulsar.plan import ddplan
    from tpulsar.search import executor
    from tpulsar.search.report import StageTimers

    spec = synth.BeamSpec(nchan=32, nsamp=1 << 16, nbits=4,
                          tsamp_s=1.31072e-4)
    psr = synth.PulsarSpec(period_s=0.15, dm=60.0, snr_per_sample=0.6,
                           width_frac=0.05)
    si = SpectraInfo(synth.synth_beam(f"{tmp_dir}/beam", spec,
                                      pulsars=[psr], merged=True))
    block = jnp.asarray(np.ascontiguousarray(si.read_all().T))
    plan = [ddplan.DedispStep(lodm=50.0, dmstep=2.0, dms_per_pass=10,
                              numpasses=1, numsub=16, downsamp=3)]
    params = executor.SearchParams(
        nsub=16, hi_accel_zmax=8, topk_per_stage=8,
        max_cands_to_fold=1, make_plots=False)
    timers = StageTimers()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPULSAR_PALLAS_SB", "1")
        mp.setattr(pallas_dd, "form_subbands_pallas", functools.partial(
            pallas_dd.form_subbands_pallas, slab_bytes=32 * 32768))
        trace.reset()
        trace.start()
        try:
            _cands, folded, _sp, ntrials = executor.search_block(
                block, np.asarray(si.freqs), float(si.dt), plan, params,
                timers=timers)
            events = trace.events()
        finally:
            trace.reset()
    assert ntrials == 10 and len(folded) == 1
    return events, timers


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return traced_two_slab_search(str(tmp_path_factory.mktemp("sb")))


def _spans(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def test_stage_1_leaves_its_five_steps_under_subbanding(traced):
    _events, timers = traced
    for step in SB_STEPS:
        assert timers.times[f"subbanding/{step}"] > 0, step
    # one after another, never one inside another (`sb-wait`, from the
    # third slab on, is inside `sb-slice`): their sum is the stage's
    assert sum(timers.times[f"subbanding/{s}"] for s in SB_STEPS) \
        <= timers.times["subbanding"]


def test_the_steps_carry_their_attributes(traced):
    events, _timers = traced
    by_slab = {}
    for e in _spans(events, "sb-kernel"):
        if e["args"]["parent"] == "subbanding":
            by_slab[e["args"]["slab"]] = e
    assert sorted(by_slab) == [0, 1]
    sl = [e["args"] for e in _spans(events, "sb-slice")
          if e["args"]["parent"] == "subbanding"]
    assert [(a["slab"], a["cols"]) for a in sl] == [(0, 32768), (1, 32768)]
    assert {e["args"]["n_blocks"] for e in _spans(events, "sb-layout")} \
        == {1}
    join = [e["args"] for e in _spans(events, "sb-join")]
    assert join and all(a["slabs"] == 2 for a in join)
    down = [e["args"] for e in _spans(events, "sb-downsample")
            if e["args"]["parent"] == "subbanding"]
    assert [a["downsamp"] for a in down] == [3]
    # the stage span still says what ran
    (stage,) = [e for e in _spans(events, "subbanding")]
    assert stage["args"]["sb_slabs"] == 2


def test_the_finish_leaves_its_formings_beside_their_kernels(traced):
    events, timers = traced
    t = timers.times
    # refinement re-forms the block at full resolution for the
    # candidate's DM, the fold at its pass's downsampling
    assert t["refinement/refine-series"] > 0
    assert t["refinement/sb-kernel"] > 0
    assert "refinement/sb-downsample" not in t
    assert t["folding/fold-subbands"] > 0
    assert 0 < t["folding/sb-kernel"] <= t["folding/fold-subbands"]
    assert t["folding/sb-downsample"] > 0
    assert t["folding/fold-subbands"] <= t["folding/fold-device"] \
        <= t["folding"]
    assert t["refinement/refine-series"] <= t["refinement"]
    # one function, three stages: the bare name is their sum
    assert t["sb-kernel"] == pytest.approx(
        t["subbanding/sb-kernel"] + t["refinement/sb-kernel"]
        + t["folding/sb-kernel"])
    (series,) = _spans(events, "refine-series")
    assert series["args"]["parent"] == "refinement"
    assert series["args"]["dm"] == pytest.approx(60.0, abs=10.0)
    (fold,) = _spans(events, "fold-subbands")
    assert fold["args"]["parent"] == "fold-device"
    assert (fold["args"]["pass_idx"], fold["args"]["downsamp"]) == (0, 3)


def test_the_traced_report_shows_the_split(traced):
    _events, timers = traced
    lines = timers.report_text("toy").splitlines()
    at = next(i for i, ln in enumerate(lines)
              if ln.strip().startswith("subbanding:"))
    detail = [ln.split(":")[0].strip() for ln in lines[at + 1:at + 6]]
    assert detail == ["> " + s for s in SB_STEPS]


def test_the_hosts_wait_is_a_span_from_the_third_slab_on():
    """Three slabs: the 2-deep backpressure blocks once, inside the
    third slab's dispatch, and the result is the untraced call's."""
    import jax.numpy as jnp
    from tpulsar.kernels import pallas_dd

    rng = np.random.default_rng(7)
    data = jnp.asarray(rng.integers(0, 255, (32, 3 * 32768), np.uint8))
    shifts = rng.integers(0, 40, 32)
    kw = dict(nsub=16, downsamp=2, interpret=True, slab_bytes=32 * 32768)
    want = pallas_dd.form_subbands_pallas(data, shifts, **kw)
    trace.reset()
    trace.start()
    try:
        got = pallas_dd.form_subbands_pallas(data, shifts, **kw)
        events = trace.events()
    finally:
        trace.reset()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    (wait,) = _spans(events, "sb-wait")
    assert wait["args"]["slab"] == 2
    assert wait["args"]["parent"] == "sb-slice"
    assert [e["args"]["slab"] for e in _spans(events, "sb-kernel")] \
        == [0, 1, 2]
    assert [e["args"]["slabs"] for e in _spans(events, "sb-join")] == [3]
