"""Batch-of-beams tests: the host planner's ladder/budget/compat
arithmetic (jax-free), bit-exact per-beam parity of a group through
the pass loop against a solo search (candidates, SP events, checkpoint
artifacts; a toy pair in tier-1, three survey-planned beams `slow`),
and mid-batch kill + resume — a beam searched inside a batch must
leave byte-identical checkpoint artifacts and resume behaviour to the
same beam searched solo."""

import glob
import os
import subprocess
import sys
import types
import zipfile

import numpy as np
import pytest

from tpulsar.kernels import accel_batch as abp
from tpulsar.kernels import beam_batch as bb

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------
# planner (pure host arithmetic — no jax)
# --------------------------------------------------------------------

def test_plan_beam_groups_quantized_no_tails():
    plan = bb.plan_beam_groups(5)
    assert [len(g) for g in plan.groups] == [4, 1]
    # unlike the DM-batch planner there are NO clamped tails: ragged
    # remainders drop a rung (re-covering a beam would recompute and
    # re-checkpoint real per-beam science)
    flat = [i for g in plan.groups for i in g]
    assert flat == list(range(5))
    plan = bb.plan_beam_groups(7, cap=3)
    assert [len(g) for g in plan.groups] == [3, 3, 1]
    assert bb.plan_beam_groups(1).groups == ((0,),)


def test_plan_beam_groups_covers_each_beam_exactly_once():
    for n in range(1, 40):
        for cap in (0, 1, 3, 8):
            plan = bb.plan_beam_groups(n, cap=cap)
            flat = [i for g in plan.groups for i in g]
            assert sorted(flat) == list(range(n)), (n, cap)
            assert len(flat) == n
            for g in plan.groups:
                assert len(g) in abp.BATCH_QUANTA
                if cap:
                    assert len(g) <= cap


def test_plan_beam_groups_rejects_bad_args():
    with pytest.raises(ValueError):
        bb.plan_beam_groups(0)
    with pytest.raises(ValueError):
        bb.plan_beam_groups(4, cap=-1)


def test_beam_batch_cap_env(monkeypatch):
    monkeypatch.delenv("TPULSAR_BEAM_BATCH", raising=False)
    assert bb.beam_batch_cap() == 0
    monkeypatch.setenv("TPULSAR_BEAM_BATCH", "6")
    assert bb.beam_batch_cap() == 6
    monkeypatch.setenv("TPULSAR_BEAM_BATCH", "nope")
    with pytest.raises(ValueError):
        bb.beam_batch_cap()
    monkeypatch.setenv("TPULSAR_BEAM_BATCH", "-2")
    with pytest.raises(ValueError):
        bb.beam_batch_cap()


def test_beam_budget_bytes_env(monkeypatch):
    monkeypatch.delenv("TPULSAR_BEAM_BATCH_BYTES", raising=False)
    assert bb.beam_budget_bytes() == bb.DEFAULT_BEAM_BUDGET
    monkeypatch.setenv("TPULSAR_BEAM_BATCH_BYTES", "1e9")
    assert bb.beam_budget_bytes() == int(1e9)
    monkeypatch.setenv("TPULSAR_BEAM_BATCH_BYTES", "0")
    with pytest.raises(ValueError):
        bb.beam_budget_bytes()


def test_budget_beams_monotone():
    a = bb.budget_beams(1 << 20, 64, 1 << 14, budget=1 << 30)
    b = bb.budget_beams(1 << 24, 64, 1 << 14, budget=1 << 30)
    assert a >= b >= 1
    assert bb.budget_beams(1 << 30, 128, 1 << 20, budget=1) == 1


def _fake_step(**kw):
    base = dict(lodm=0.0, dmstep=0.5, dms_per_pass=76, numpasses=2,
                numsub=96, downsamp=1)
    base.update(kw)
    return types.SimpleNamespace(**base)


class _FakeParams:
    def __init__(self, tag="a"):
        self.tag = tag

    def provenance(self):
        return {"tag": self.tag}


def test_compat_key_sensitivity():
    plan = [_fake_step()]
    args = dict(nchan=960, nsamp=1 << 20, dt=6.4e-5, f_lo=1200.0,
                f_hi=1500.0, nsub=96)
    k0 = bb.compat_key(plan=plan, params=_FakeParams(), **args)
    assert k0 == bb.compat_key(plan=[_fake_step()],
                               params=_FakeParams(), **args)
    # every static device-program input keys; provenance keys too
    for field, val in (("nchan", 480), ("nsamp", 1 << 19),
                      ("dt", 1.28e-4), ("f_lo", 1100.0),
                      ("nsub", 48)):
        changed = dict(args, **{field: val})
        assert bb.compat_key(plan=plan, params=_FakeParams(),
                             **changed) != k0, field
    assert bb.compat_key(plan=[_fake_step(downsamp=2)],
                         params=_FakeParams(), **args) != k0
    assert bb.compat_key(plan=plan, params=_FakeParams("b"),
                         **args) != k0
    assert bb.compat_key(plan=plan, params=_FakeParams(),
                         zap_digest="deadbeef", **args) != k0


def test_zaplist_digest():
    assert bb.zaplist_digest(None) == ""
    z = np.asarray([[60.0, 0.5], [120.0, 1.0]])
    d = bb.zaplist_digest(z)
    assert d and d == bb.zaplist_digest(z.copy())
    assert d != bb.zaplist_digest(z[:1])


# --------------------------------------------------------------------
# coalesced executor path: bit-exact parity + kill/resume
# --------------------------------------------------------------------

_NB = 3
_PARAM_KW = dict(dm_max=40.0, run_hi_accel=True, max_cands_to_fold=1,
                 make_plots=False)
_CAND_FIELDS = ("r", "z", "sigma", "power", "numharm", "dm",
                "period_s", "freq_hz")


@pytest.fixture(scope="module")
def mini_beams(tmp_path_factory):
    """Three tiny compatible beams + the SOLO reference runs (with
    their checkpoint stores kept) every parity assertion compares
    against.  One shared persistent compile cache keeps the
    subprocess resume test warm."""
    from tpulsar.io import synth
    from tpulsar.search import executor

    base = tmp_path_factory.mktemp("beambatch")
    cache_was_unset = "JAX_COMPILATION_CACHE_DIR" not in os.environ
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(base / "jax_cache"))
    psr = synth.PulsarSpec(period_s=0.05, dm=20.0,
                           snr_per_sample=1.5)
    beams = []
    for i in range(_NB):
        spec = synth.BeamSpec(nchan=32, nsamp=2048, nsblk=64,
                              nbits=4, tsamp_s=5.24288e-4,
                              scan=100 + i)
        beams.append(synth.synth_beam(str(base / f"data{i}"), spec,
                                      pulsars=[psr], merged=True))
    params = executor.SearchParams(**_PARAM_KW)
    solo = []
    for i, fns in enumerate(beams):
        solo.append(executor.search_beam(
            fns, str(base / f"w_s{i}"), str(base / f"r_s{i}"),
            params, checkpoint_dir=str(base / f"ck_s{i}")))
    yield {"base": base, "beams": beams, "params": params,
           "solo": solo}
    if cache_was_unset:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


def _assert_outcome_parity(a, b, label=""):
    assert a.num_dm_trials == b.num_dm_trials, label
    assert len(a.candidates) == len(b.candidates), label
    for ca, cb in zip(a.candidates, b.candidates):
        for f in _CAND_FIELDS:
            assert getattr(ca, f) == getattr(cb, f), (label, f)
    assert a.sp_events.tobytes() == b.sp_events.tobytes(), label


def _assert_checkpoint_parity(dir_a, dir_b, label=""):
    """Checkpoint artifact payloads must be byte-identical: every
    npz member stream compared raw (the zip container's entry
    timestamps are the only bytes allowed to differ)."""
    a_files = sorted(os.path.basename(p)
                     for p in glob.glob(f"{dir_a}/*.npz"))
    b_files = sorted(os.path.basename(p)
                     for p in glob.glob(f"{dir_b}/*.npz"))
    assert a_files == b_files and a_files, (label, a_files, b_files)
    for nm in a_files:
        with zipfile.ZipFile(os.path.join(dir_a, nm)) as za, \
                zipfile.ZipFile(os.path.join(dir_b, nm)) as zb:
            assert za.namelist() == zb.namelist(), (label, nm)
            for member in za.namelist():
                assert za.read(member) == zb.read(member), \
                    (label, nm, member)


# ---- the group loop at a size tier-1 can pay: two DIFFERENT beams of
# one geometry over a toy plan, every branch a group adds to the one
# pass loop switched on (hi-accel, a zaplist with per-beam baryv so
# the 2-D keep rows differ, checkpoints, three chunks a pass so the
# two-in-flight bound engages)

_TOY_PARAM_KW = dict(nsub=16, hi_accel_zmax=8, topk_per_stage=8,
                     max_dms_per_chunk=4, max_cands_to_fold=1,
                     make_plots=False)


def _toy_plan():
    from tpulsar.plan import ddplan
    return [ddplan.DedispStep(lodm=40.0, dmstep=2.0, dms_per_pass=12,
                              numpasses=2, numsub=16, downsamp=1)]


@pytest.fixture(scope="module")
def toy_pair(tmp_path_factory):
    """Two toy beams searched each alone (search_beam) and together
    (search_beam_batch), checkpoint stores kept."""
    from tpulsar.io import synth
    from tpulsar.kernels.fourier import parse_zaplist
    from tpulsar.search import executor

    base = tmp_path_factory.mktemp("toypair")
    zap = parse_zaplist(os.path.join(
        os.path.dirname(executor.__file__), "..", "data",
        "default.zaplist"))
    psrs = [synth.PulsarSpec(period_s=0.15, dm=60.0,
                             snr_per_sample=0.6, width_frac=0.05),
            synth.PulsarSpec(period_s=0.09, dm=50.0,
                             snr_per_sample=0.7, width_frac=0.05)]
    # baryv apart by more than a Fourier bin at the zapped lines (far
    # beyond a real sky's 1e-4: the two keep masks must differ)
    beams, baryvs = [], [0.0, 5e-3]
    for i, psr in enumerate(psrs):
        spec = synth.BeamSpec(nchan=32, nsamp=1 << 13, nbits=4,
                              tsamp_s=5.24288e-4, scan=200 + i)
        beams.append(synth.synth_beam(str(base / f"data{i}"), spec,
                                      pulsars=[psr], merged=True))
    params = executor.SearchParams(**_TOY_PARAM_KW)
    with pytest.MonkeyPatch.context() as mp:
        # search_beam_batch plans from the header; give both entry
        # points the toy plan the same way
        mp.setattr(executor.ddplan, "plan_for",
                   lambda si, **kw: (_toy_plan(), None, 16))
        solo = [executor.search_beam(
            fns, str(base / f"w_s{i}"), str(base / f"r_s{i}"), params,
            zaplist=zap, baryv=baryvs[i],
            checkpoint_dir=str(base / f"ck_s{i}"))
            for i, fns in enumerate(beams)]
        progress = []
        batched = executor.search_beam_batch(
            [executor.BeamSpec(
                fns=fns, workdir=str(base / f"w_b{i}"),
                resultsdir=str(base / f"r_b{i}"), zaplist=zap,
                baryv=baryvs[i],
                checkpoint_dir=str(base / f"ck_b{i}"))
             for i, fns in enumerate(beams)],
            params, progress_cb=progress.append)
    return {"base": base, "solo": solo, "batched": batched,
            "progress": progress, "zap": zap, "baryvs": baryvs}


def test_group_of_two_rode_one_loop(toy_pair):
    res = toy_pair["batched"]
    assert [(r.path, r.group_size, r.fallout) for r in res] \
        == [("batched", 2, "")] * 2, [(r.path, r.error) for r in res]
    # the solo's progress keys, plus nbeams; trials are one beam's
    assert [p["pass_idx"] for p in toy_pair["progress"]] == [1, 2]
    assert set(toy_pair["progress"][-1]) == {
        "pass_idx", "npasses", "step_idx", "ntrials_done", "ncands",
        "stage_s", "nbeams"}
    assert toy_pair["progress"][-1]["ntrials_done"] == 24
    assert toy_pair["progress"][-1]["nbeams"] == 2
    # the beams differ, so a slice taken from the wrong rows shows
    a, b = (o.candidates for o in toy_pair["solo"])
    assert a and b and a[0].period_s != b[0].period_s
    # and so do their keep masks (per-row 2-D masks in the group)
    from tpulsar.kernels import fourier as fr
    nfft = 1 << 13
    masks = [fr.zap_mask(nfft // 2 + 1, nfft * 5.24288e-4,
                         toy_pair["zap"], bv)
             for bv in toy_pair["baryvs"]]
    assert not np.array_equal(*masks) and not masks[0].all()


@pytest.mark.parametrize("i", [0, 1])
def test_group_member_outcome_is_its_solo_outcome(toy_pair, i):
    _assert_outcome_parity(toy_pair["solo"][i],
                           toy_pair["batched"][i].outcome, f"beam{i}")
    assert toy_pair["solo"][i].num_dm_trials == 24


@pytest.mark.parametrize("i", [0, 1])
def test_group_member_checkpoints_are_its_solo_checkpoints(toy_pair,
                                                           i):
    base = toy_pair["base"]
    _assert_checkpoint_parity(str(base / f"ck_s{i}"),
                              str(base / f"ck_b{i}"), f"beam{i}")
    names = sorted(os.path.basename(p) for p in
                   glob.glob(str(base / f"ck_b{i}" / "*.npz")))
    assert {"pass_0000.npz", "pass_0001.npz", "sifted.npz"} \
        <= set(names)


@pytest.mark.slow
def test_batched_parity_bitexact(mini_beams):
    """The acceptance contract: a beam searched inside a coalesced
    batch yields bit-identical candidates, SP events, and checkpoint
    artifacts to the same beam searched solo.  (slow: ~3 min of real
    searches — the CI beambatch job runs this module explicitly.)"""
    from tpulsar.search import executor

    base = mini_beams["base"]
    specs = [executor.BeamSpec(
        fns=fns, workdir=str(base / f"w_b{i}"),
        resultsdir=str(base / f"r_b{i}"),
        checkpoint_dir=str(base / f"ck_b{i}"))
        for i, fns in enumerate(mini_beams["beams"])]
    results = executor.search_beam_batch(specs,
                                         mini_beams["params"])
    assert [r.path for r in results] == ["batched"] * _NB, \
        [(r.path, r.fallout, r.error) for r in results]
    assert all(r.group_size == _NB for r in results)
    for i, (s, r) in enumerate(zip(mini_beams["solo"], results)):
        assert r.error is None, r.error
        _assert_outcome_parity(s, r.outcome, f"beam{i}")
        _assert_checkpoint_parity(str(base / f"ck_s{i}"),
                                  str(base / f"ck_b{i}"),
                                  f"beam{i}")
    # per-beam metrics attribution: each batched beam's metrics.json
    # composes the SHARED plan-loop delta with only ITS OWN finish
    # phase — identical beams (all warm) must report identical
    # compile-hit totals; the pre-fix cumulative base made beam b's
    # artifact include beams 0..b-1's finish-phase counters, so the
    # totals grew strictly with b
    import json

    def _hits(d):
        rec = json.load(open(os.path.join(d, "metrics.json"))).get(
            "tpulsar_compile_cache_hits_total") or {"series": {}}
        return sum(rec["series"].values())

    hits = [_hits(str(base / f"r_b{i}")) for i in range(_NB)]
    assert len(set(hits)) == 1, hits


@pytest.mark.slow
def test_mid_batch_kill_resume_byte_identical(mini_beams):
    """Kill a batched search mid-batch (hard exit after the first
    pass's artifacts are durable for every member), then re-enter:
    each beam falls out of the batch to the solo path (resume state),
    resumes from the batched run's checkpoints WITHOUT recomputing
    completed passes, and finishes byte-identical to the pure-solo
    reference."""
    from tpulsar.search import executor

    base = mini_beams["base"]
    script = base / "kill_mid_batch.py"
    script.write_text(f"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {_REPO!r})
from tpulsar.search import executor

beams = {mini_beams["beams"]!r}
base = {str(base)!r}
params = executor.SearchParams(**{_PARAM_KW!r})
specs = [executor.BeamSpec(
    fns=fns, workdir=os.path.join(base, f"w_k{{i}}"),
    resultsdir=os.path.join(base, f"r_k{{i}}"),
    checkpoint_dir=os.path.join(base, f"ck_k{{i}}"))
    for i, fns in enumerate(beams)]


def kill_after_pass_1(progress):
    if progress["pass_idx"] >= 1:
        os._exit(70)      # SIGKILL footprint: no unwind, no cleanup


executor.search_beam_batch(specs, params,
                           progress_cb=kill_after_pass_1)
raise SystemExit("unreachable: the kill never fired")
""")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True,
                          timeout=600, env=dict(os.environ))
    assert proc.returncode == 70, (proc.returncode, proc.stderr[-800:])

    from tpulsar import checkpoint as ckpt
    for i in range(_NB):
        assert ckpt.progress_marker(str(base / f"ck_k{i}")) > 0, i

    # re-enter through the batch entry point: resume state forces
    # every member out of the batch onto the proven solo path
    events: list[tuple] = []
    specs = [executor.BeamSpec(
        fns=fns, workdir=str(base / f"w_k{i}"),
        resultsdir=str(base / f"r_k{i}"),
        checkpoint_dir=str(base / f"ck_k{i}"),
        checkpoint_journal=(lambda ev, _i=i, **kw:
                            events.append((_i, ev, kw))))
        for i, fns in enumerate(mini_beams["beams"])]
    results = executor.search_beam_batch(specs,
                                         mini_beams["params"])
    assert [r.path for r in results] == ["solo"] * _NB
    assert [r.fallout for r in results] == ["resume"] * _NB
    resumed = {i for i, ev, kw in events if ev == "resume"}
    assert resumed == set(range(_NB)), events
    for i, (s, r) in enumerate(zip(mini_beams["solo"], results)):
        assert r.error is None, r.error
        _assert_outcome_parity(s, r.outcome, f"resume beam{i}")
        _assert_checkpoint_parity(str(base / f"ck_s{i}"),
                                  str(base / f"ck_k{i}"),
                                  f"resume beam{i}")


def test_incompatible_declared_compat_is_admission_only():
    """A ticket's declared compat key is an admission optimization:
    the executor groups by the true header-derived key, so the unit
    of trust is compat_key itself — two geometry-identical beams key
    equal, and the grouping logic (exercised end-to-end above) only
    coalesces equal keys."""
    plan = [_fake_step()]
    args = dict(nchan=960, nsamp=1 << 20, dt=6.4e-5, f_lo=1200.0,
                f_hi=1500.0, nsub=96)
    assert bb.compat_key(plan=plan, params=_FakeParams(), **args) \
        == bb.compat_key(plan=plan, params=_FakeParams(), **args)
