"""Dedispersion plan tests."""

import dataclasses

import numpy as np
import pytest

from tpulsar.plan import ddplan


def test_survey_plan_mock_matches_reference_table():
    """The hardcoded Mock plan must reproduce the reference's DM
    coverage: 6 steps, 57 passes, DM 0 -> 1066.4."""
    steps = ddplan.survey_plan("pdev")
    assert len(steps) == 6
    assert sum(s.numpasses for s in steps) == 57
    assert steps[0].lodm == 0.0
    assert abs(steps[-1].hidm - 1066.4) < 1e-9
    # steps tile the DM range contiguously
    for a, b in zip(steps[:-1], steps[1:]):
        assert abs(a.hidm - b.lodm) < 1e-9
    # trial count: 28*76 + 12*64 + 4*76 + 9*76 + 3*76 + 1*76
    assert ddplan.total_dm_trials(steps) == 28 * 76 + 12 * 64 + (4 + 9 + 3 + 1) * 76


def test_survey_plan_wapp():
    steps = ddplan.survey_plan("wapp")
    assert len(steps) == 3
    assert sum(s.numpasses for s in steps) == 15
    assert abs(steps[-1].hidm - 1725.2) < 1e-9


GBNCC_OBS = ddplan.Observation(dt=81.92e-6, fctr=350.0, bw=100.0,
                               numchan=4096, blocklen=2048)


def test_survey_plan_gbncc_is_the_planners_own():
    """The frozen GBNCC rows are generate_ddplan's answer for the
    survey's geometry (GUPPI at 350 MHz), row for row: five steps at
    ds 1-16, 102 DMs a pass, 389 passes, 39,678 trials to DM 504.6."""
    steps = ddplan.survey_plan("gbncc")
    assert steps == ddplan.generate_ddplan(GBNCC_OBS, 0, 500, numsub=128)
    assert [s.downsamp for s in steps] == [1, 2, 4, 8, 16]
    assert [s.numpasses for s in steps] == [169, 60, 69, 55, 36]
    assert {s.dms_per_pass for s in steps} == {102}
    assert ddplan.total_dm_trials(steps) == 39678
    assert abs(steps[-1].hidm - 504.594) < 1e-9
    for a, b in zip(steps[:-1], steps[1:]):
        assert abs(a.hidm - b.lodm) < 1e-9


@pytest.mark.parametrize("survey", [None, "gbncc"])
def test_plan_for_a_gbncc_header_runs_the_frozen_passes(survey):
    """A header of this geometry with no survey named (a GUPPI back
    end has no table: plan_for generates) and the survey by name give
    the executor the same passes."""
    import types

    si = types.SimpleNamespace(num_channels=4096, dt=81.92e-6, fctr=350.0,
                               BW=100.0, spectra_per_subint=2048,
                               backend="GUPPI")
    steps, obs, nsub = ddplan.plan_for(si, 0.0, 500.0, numsub=128,
                                       survey=survey)
    assert (obs, nsub) == (GBNCC_OBS, 128)
    assert steps == ddplan.survey_plan("gbncc")


GPPS_OBS = ddplan.Observation(dt=49.152e-6, fctr=1250.0, bw=500.0,
                              numchan=2048, blocklen=2048)


def test_survey_plan_gpps_is_the_planners_own():
    """The frozen FAST GPPS rows are generate_ddplan's answer for the
    survey's geometry (the 19-beam L-band receiver, 2048 channels over
    1.0-1.5 GHz at 49.152 us), step by step: six steps at ds 1-32, 102
    DMs a pass, 229 passes, 23,358 trials to DM 3006.96."""
    steps = ddplan.survey_plan("gpps")
    made = ddplan.generate_ddplan(GPPS_OBS, 0, 3000, numsub=128)
    assert len(steps) == len(made) == 6
    for frozen, gen in zip(steps, made):
        # the planner's 0.3 is 0.30000000000000004: the same step, the
        # same DMs (a pass rounds them to 1e-6)
        assert frozen.dmstep == pytest.approx(gen.dmstep, abs=1e-12)
        assert dataclasses.replace(frozen, dmstep=gen.dmstep) == gen
        assert [p.dms for p in frozen.passes()] == \
            [p.dms for p in gen.passes()]
        assert [p.subdm for p in frozen.passes()] == \
            [p.subdm for p in gen.passes()]
    assert [s.downsamp for s in steps] == [1, 2, 4, 8, 16, 32]
    assert [s.numpasses for s in steps] == [71, 27, 37, 39, 30, 25]
    assert {s.dms_per_pass for s in steps} == {102}
    assert ddplan.total_dm_trials(steps) == 23358
    assert abs(steps[-1].hidm - 3006.96) < 1e-9
    for a, b in zip(steps[:-1], steps[1:]):
        assert abs(a.hidm - b.lodm) < 1e-9
    # the first pass lies under the sifter's low-DM cutoff, which is
    # why the benchmark's slice starts at step 1
    assert steps[0].passes()[0].dms[-1] < 2.04 < steps[0].passes()[1].dms[1]


def test_survey_plan_unknown_backend():
    with pytest.raises(ValueError):
        ddplan.survey_plan("guppi")


def test_passes_expand_correctly():
    step = ddplan.DedispStep(lodm=10.0, dmstep=0.5, dms_per_pass=4,
                             numpasses=3, numsub=8, downsamp=2)
    passes = step.passes()
    assert len(passes) == 3
    assert passes[0].dms == (10.0, 10.5, 11.0, 11.5)
    assert passes[1].lodm == 12.0
    assert abs(passes[0].subdm - 11.0) < 1e-9  # lodm + 0.5*sub_dmstep
    assert step.hidm == 16.0
    np.testing.assert_allclose(step.all_dms(), 10.0 + 0.5 * np.arange(12))


def test_dm_smear_consistency():
    """guess_dmstep inverts dm_smear at the same geometry."""
    dt, bw, fctr = 6.5e-4, 322.0, 1375.0
    ddm = ddplan.guess_dmstep(dt, bw, fctr)
    assert abs(ddplan.dm_smear(ddm, bw, fctr) - dt) < 1e-12


def test_generated_plan_covers_range_and_balances_smearing():
    obs = ddplan.Observation(dt=65.5e-6, fctr=1375.5, bw=322.6,
                             numchan=960, blocklen=2048)
    steps = ddplan.generate_ddplan(obs, 0.0, 1000.0, numsub=96)
    assert steps[0].lodm == 0.0
    assert steps[-1].hidm >= 1000.0
    for a, b in zip(steps[:-1], steps[1:]):
        assert abs(a.hidm - b.lodm) < 1e-9
        assert b.downsamp >= a.downsamp
        assert b.dmstep >= a.dmstep
    # downsampling factors must divide the block length
    for s in steps:
        assert obs.blocklen % s.downsamp == 0
    fr = ddplan.work_fractions(steps)
    assert abs(fr.sum() - 1.0) < 1e-12


def test_describe_and_plot_plan(tmp_path):
    from tpulsar.plan import ddplan
    steps = ddplan.survey_plan("pdev")
    obs = ddplan.Observation(dt=65.476e-6, fctr=1375.5, bw=322.617,
                             numchan=960, blocklen=2048)
    text = ddplan.describe_plan(steps, obs)
    assert "total DM trials" in text and "4188" in text
    png = str(tmp_path / "plan.png")
    assert ddplan.plot_plan(steps, obs, png) == png
    import os
    assert os.path.getsize(png) > 1000


def test_plan_cli(tmp_path, capsys):
    from tpulsar.cli import main as cli
    assert cli.main(["plan", "--survey", "pdev"]) == 0
    out = capsys.readouterr().out
    assert "total DM trials" in out


def test_choose_n_properties():
    from tpulsar.plan.ddplan import choose_n

    def is_smooth(n, factors=(2, 3, 5, 7)):
        for f in factors:
            while n % f == 0:
                n //= f
        return n == 1

    for n in (1, 63, 64, 65, 1000, 30000, 123457, 2 ** 20,
              2 ** 20 + 1, 9999991):
        N = choose_n(n)
        assert N >= n
        assert N % 64 == 0
        assert is_smooth(N)
        # padding overhead stays small (<= ~12% for awkward sizes)
        if n >= 1000:
            assert N / n < 1.13, (n, N)
    # already-smooth multiples of 64 are returned unchanged
    assert choose_n(1 << 15) == 1 << 15
    assert choose_n(30240 * 64) == 30240 * 64


def test_choose_n_exact_examples():
    from tpulsar.plan.ddplan import choose_n
    assert choose_n(30000) == 30720          # 64 * 480
    assert choose_n(100) == 128
    assert choose_n(0) == 64


# ------------------------------------------------------------ trim_plan

def test_trim_plan_default_window_is_noop():
    """The PALFA survey plans are untouched by the default [0, 1000)
    window: every pass STARTS below 1000 and trimming is whole-pass
    (a narrower window would desynchronize production runs from the
    reference's plan tables)."""
    from tpulsar.plan.ddplan import survey_plan, trim_plan

    for backend in ("mock", "wapp"):
        steps = survey_plan(backend)
        assert trim_plan(steps, 0.0, 1000.0) == steps


def test_trim_plan_low_window():
    """[0, 60] on the Mock plan keeps only whole passes of step 1
    that intersect the window."""
    from tpulsar.plan.ddplan import survey_plan, trim_plan

    steps = trim_plan(survey_plan("mock"), 0.0, 60.0)
    assert len(steps) == 1
    s = steps[0]
    assert s.lodm == 0.0
    # sub_dmstep = 7.6; passes start at 0, 7.6, ... -> last start
    # below 60 is 53.2 (index 7)
    assert s.numpasses == 8
    assert s.hidm == pytest.approx(60.8)
    # every requested DM inside the window is still searched
    dms = s.all_dms()
    assert dms.min() == 0.0 and dms.max() >= 60.0 - s.dmstep


def test_trim_plan_mid_window_spans_steps():
    from tpulsar.plan.ddplan import survey_plan, trim_plan

    steps = trim_plan(survey_plan("mock"), 300.0, 500.0)
    # steps 2 (212.8..443.2) and 3 (443.2..534.4) intersect
    assert len(steps) == 2
    s2, s3 = steps
    assert s2.lodm == pytest.approx(289.6)   # whole-pass: 212.8 + 4*19.2
    assert s2.hidm >= 443.2 - 1e-6
    assert s3.lodm == pytest.approx(443.2)
    assert s3.hidm >= 500.0
    # the window is fully covered, no gaps at the seam
    assert s2.hidm == pytest.approx(s3.lodm)


def test_trim_plan_empty_and_plan_for_raises():
    from tpulsar.plan.ddplan import plan_for, survey_plan, trim_plan

    assert trim_plan(survey_plan("mock"), 2000.0, 3000.0) == []

    # plan_for must RAISE (not return an empty plan) when the DM
    # window excludes every pass — an empty plan would send the
    # executor into a zero-pass search that "succeeds" with no trials
    class _Si:
        num_channels = 96
        dt = 6.4e-5
        fctr = 1400.0
        BW = 100.0
        spectra_per_subint = 2048
        backend = "mock"

    with pytest.raises(ValueError, match="no passes"):
        plan_for(_Si(), lodm=2000.0, hidm=3000.0)


def test_searching_dm_window_reaches_params():
    """config.searching.dm_min/dm_max flow into SearchParams (the
    worker's from_config path)."""
    from tpulsar.config import TpulsarConfig
    from tpulsar.search.executor import SearchParams

    cfg = TpulsarConfig()
    cfg.searching.dm_max = 60.0
    p = SearchParams.from_config(cfg.searching)
    assert p.dm_max == 60.0 and p.dm_min == 0.0


def test_trim_plan_default_no_cap():
    """The documented no-cap default (hidm=inf) keeps every pass."""
    from tpulsar.plan.ddplan import survey_plan, trim_plan

    steps = survey_plan("mock")
    assert trim_plan(steps) == steps
    assert trim_plan(steps, lodm=500.0)[-1] == steps[-1]
