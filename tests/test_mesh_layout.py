"""The DM-sharded mesh as configuration (``SearchParams.dm_shards``,
``SearchingConfig.dm_shards``): the north-star deployment's layout on
the normal path, on four of the eight virtual CPU devices
``tests/conftest.py`` forces.

``search_block(params.dm_shards=4)`` against ``dm_shards=1`` and
against the benchmark's plain reference on a toy beam, at a trial
count the mesh does not divide and at one where the clamped last chunk
call recomputes rows; the refusals (too few devices, a group of beams,
a worker's boot); the spans and counters of the three mesh stages.
"""

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from benchmark.harness import cells, check, runner, window
from tpulsar.obs import telemetry, trace
from tpulsar.search import executor

ROOT = cells.ROOT
TOY = os.path.join(ROOT, "tests", "benchmark_tests", "toy_mesh4")
SEED = 2 ** 31 + 3131
sys.path.insert(0, os.path.join(ROOT, "tools"))

#: (trials in the pass, SearchParams.max_dms_per_chunk) -> the chunk
#: calls the mesh of four makes:
#:   75, 128: the table is padded to 76 rows, one call computes them
#:            all (1 row recomputed: the padding);
#:   76, 32:  calls of 32 rows at 0, 32 and 64, the last clamped back
#:            to rows 44-75 (12 trials first searched, 20 recomputed)
CASES = {"not_divided": (75, 128, 76, 1), "clamped_tail": (76, 32, 96, 20)}


def _load(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def toy():
    """The toy mesh cell's block, pulsar, plan and parameters, made the
    way a benchmark run makes them (``runner.setup``)."""
    cell = cells.Cell(
        name="toy_mesh4", chips=4, config_name="toy_wapp_mesh4",
        traffic_name="toy_ds1_hiaccel_mesh",
        config=_load(TOY, "configs", "toy_wapp_mesh4.json"),
        traffic=_load(TOY, "traffic", "toy_ds1_hiaccel_mesh.json"),
        bench=_load(ROOT, "BENCHMARK.json"), root=ROOT,
        bench_dir=os.path.join(ROOT, "benchmark"))
    block, psr, plan, params = runner.setup(cell, SEED, {})
    assert params.dm_shards == 4 and params.run_hi_accel
    return cell, block, psr, plan, params


@pytest.fixture(scope="module", params=sorted(CASES))
def pair(request, toy):
    """One slice call with dm_shards=4 and one with dm_shards=1 on the
    same block, through the harness's own ``slice_call`` (which hands
    ``search_block`` no ``mesh=``), with the mesh call's spans and the
    counters' deltas over it."""
    cell, block, psr, plan, params = toy
    ntrials, max_chunk, rows, recomputed = CASES[request.param]
    plan = [dataclasses.replace(plan[0], dms_per_pass=ntrials)]
    params = dataclasses.replace(params, max_dms_per_chunk=max_chunk)

    def counters():
        snap = telemetry.metrics.REGISTRY.snapshot()
        rows_ = (snap.get("tpulsar_mesh_rows_total") or {}).get("series", {})
        placed = (snap.get("tpulsar_mesh_bytes_placed_total")
                  or {}).get("series", {})
        return {"searched": rows_.get("searched", 0.0),
                "recomputed": rows_.get("recomputed", 0.0),
                "placed": sum(placed.values())}

    base = counters()
    trace.start()
    try:
        mesh = window.slice_call(block, cell.freqs, cell.dt, plan, params)
        events = [e for e in trace.events() if e.get("ph") == "X"]
    finally:
        trace.reset()
    delta = {k: v - base[k] for k, v in counters().items()}
    solo = window.slice_call(
        block, cell.freqs, cell.dt, plan,
        dataclasses.replace(params, dm_shards=1))
    return {"cell": cell, "block": block, "psr": psr, "plan": plan,
            "mesh": mesh, "solo": solo, "events": events, "delta": delta,
            "ntrials": ntrials, "rows": rows, "recomputed": recomputed}


# ------------------------------------------ 4a: dm_shards=4 == dm_shards=1

def test_mesh_returns_what_one_device_returns(pair):
    """Candidates one-to-one both ways, as ``chip_smoke.py --chips 4``
    compares them (``compare_candlists.match``, frequency within 1e-4,
    DM within 0.5), with equal (r, z, numharm, DM) keys; sigma within
    1e-3 relative (the sharded program sums the same float32 terms in
    another order)."""
    import compare_candlists

    m_cands, _f, _e, m_n = pair["mesh"].result
    s_cands, _f, _e, s_n = pair["solo"].result
    assert m_n == s_n == pair["ntrials"]
    assert pair["mesh"].ntrials_done == pair["ntrials"]
    assert len(m_cands) == len(s_cands) > 0
    for ref, got in ((s_cands, m_cands), (m_cands, s_cands)):
        kinds = [k for _c, k, _g in compare_candlists.match(
            ref, got, freq_tol=1e-4, dm_tol=0.5)]
        assert kinds.count("exact") == len(ref)

    def key(c):
        return (round(c.r, 2), round(c.z, 2), c.numharm, round(c.dm, 3))

    by_key = {key(c): c for c in s_cands}
    assert set(by_key) == {key(c) for c in m_cands}
    for c in m_cands:
        assert c.sigma == pytest.approx(by_key[key(c)].sigma, rel=1e-3)
    assert not pair["mesh"].degraded and not pair["mesh"].rescued


def test_mesh_single_pulse_events_and_pass_dumps_match(pair):
    """The same events (DM, sample, width); sigma within 1e-3: the
    detrend's float32 sums round differently at other row counts (PR
    29 read <= 3.6e-4 on the chip between 8 rows and 4; the mesh runs
    19 or 8 rows a device here where one device runs up to 76).  The
    per-pass dump the harness's check reads: the same raw candidates,
    powers within 1e-5 relative, the same trial count."""
    m_ev, s_ev = pair["mesh"].result[2], pair["solo"].result[2]

    def by_key(ev):
        return {(round(float(e["dm"]), 3), int(e["sample"]),
                 int(e["downfact"])): float(e["sigma"]) for e in ev}

    m_by, s_by = by_key(m_ev), by_key(s_ev)
    assert set(m_by) == set(s_by) and len(m_by) > 0
    assert max(abs(m_by[k] - s_by[k]) for k in m_by) <= 1e-3

    (m_dump,), (s_dump,) = pair["mesh"].dumps, pair["solo"].dumps
    assert m_dump["ntrials"] == s_dump["ntrials"] == pair["ntrials"]

    def raw(d):
        c = d["cands"]
        return {(round(float(r), 2), float(z), int(h), round(float(dm), 3)):
                float(p) for r, z, h, dm, p in
                zip(c["r"], c["z"], c["numharm"], c["dm"], c["power"])}

    m_raw, s_raw = raw(m_dump), raw(s_dump)
    assert set(m_raw) == set(s_raw) and len(m_raw) > 20
    assert any(k[1] != 0.0 for k in m_raw)          # the hi stage's too
    for k, p in m_raw.items():
        assert p == pytest.approx(s_raw[k], rel=1e-5)
    assert len(m_dump["events"]) == len(s_dump["events"])


# --------------------------------------- 4b: against the plain reference

def test_mesh_powers_and_snrs_agree_with_the_plain_reference(pair):
    """``benchmark/harness/check.py`` on the mesh's own slice call: the
    injected pulsar recovered, raw hi powers and single-pulse SNRs
    within the toy configuration's limits of ``reference.py`` (which
    imports no tpulsar) — and far inside them, the plane being float32
    off a TPU."""
    verdict = check.check(pair["cell"], pair["plan"], pair["psr"],
                          pair["mesh"], pair["block"], SEED)
    got = {n["name"]: n for n in verdict["numbers"]}
    assert verdict["correct"], got
    assert got["pulsar_missing"]["value"] == 0
    assert got["pulsar_z_err_bins"]["value"] <= 2.0
    assert got["hi_power_gap"]["n"] >= 3
    assert got["hi_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["n"] >= 3
    assert got["sp_snr_gap"]["value"] < 1e-3
    assert got["lo_best_missing"]["value"] == 0


def test_mesh_lo_powers_agree_with_the_plain_reference(toy):
    """Hi-accel off: the check then compares the lo stage's raw powers
    (float32 end to end; the toy's limit is 1e-3)."""
    cell, block, psr, plan, params = toy
    cell = dataclasses.replace(
        cell, traffic={**cell.traffic, "run_hi_accel": False})
    plan = [dataclasses.replace(plan[0], dms_per_pass=75)]
    params = dataclasses.replace(params, run_hi_accel=False)
    call = window.slice_call(block, cell.freqs, cell.dt, plan, params)
    verdict = check.check(cell, plan, psr, call, block, SEED)
    got = {n["name"]: n for n in verdict["numbers"]}
    assert got["lo_power_gap"]["n"] >= 3
    assert got["lo_power_gap"]["ok"] and got["lo_power_gap"]["value"] < 1e-4
    assert got["sp_snr_gap"]["ok"] and got["trials_not_searched"]["ok"]


# ------------------------------------------------------- 4c: the refusals

def test_too_few_devices_raises_before_any_work(monkeypatch):
    monkeypatch.setattr(executor, "_DM_MESHES", {})
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: jax.devices()[:2])
    params = executor.SearchParams(dm_shards=4)
    with pytest.raises(RuntimeError, match="dm_shards=4 needs 4 local"):
        # no block, no plan: nothing was touched before the refusal
        executor.search_block(None, None, 1e-3, None, params)
    with pytest.raises(RuntimeError, match="this process has 2"):
        executor.search_beam(["/nonexistent.fits"], "/nonexistent/w",
                             "/nonexistent/r", params=params)
    assert not os.path.exists("/nonexistent")
    assert executor._DM_MESHES == {}


def test_the_mesh_is_built_once_a_process_over_the_first_devices():
    mesh = executor.dm_mesh(4)
    assert mesh is executor.dm_mesh(4)
    assert dict(mesh.shape) == {"beam": 1, "dm": 4}
    assert [d.id for d in mesh.devices.ravel()] == \
        [d.id for d in jax.local_devices()[:4]]


@pytest.mark.parametrize("bad", [0, -1, 2.5])
def test_dm_shards_must_be_a_whole_number_of_one_or_more(bad):
    with pytest.raises(ValueError, match="dm_shards"):
        executor.SearchParams(dm_shards=bad)


def test_from_config_carries_the_layout_and_the_config_validates_it(
        tmp_path):
    from tpulsar.config import core

    cfg = core.TpulsarConfig()
    assert cfg.searching.dm_shards == 1
    assert executor.SearchParams.from_config(cfg.searching).dm_shards == 1
    cfg.searching.dm_shards = 4
    params = executor.SearchParams.from_config(cfg.searching)
    assert params.dm_shards == 4
    assert params.provenance()["dm_shards"] == 4    # search_params.txt
    cfg.searching.dm_shards = 0
    with pytest.raises(core.InsaneConfigsError,
                       match="searching.dm_shards must be >= 1"):
        cfg.check_sanity(create_dirs=False)


def test_a_group_of_beams_refuses_the_mesh():
    specs = [executor.BeamSpec(fns=[f"/nonexistent/{i}.fits"],
                               workdir=f"/nonexistent/w{i}",
                               resultsdir=f"/nonexistent/r{i}")
             for i in range(2)]
    with pytest.raises(ValueError, match="ONE beam's DM trials"):
        executor.search_beam_batch(
            specs, executor.SearchParams(dm_shards=4))
    assert not os.path.exists("/nonexistent")


def test_a_worker_on_a_host_with_fewer_chips_refuses_to_start(
        tmp_path, monkeypatch):
    from tpulsar.config import core
    from tpulsar.serve.server import SearchServer

    monkeypatch.setattr(executor, "_DM_MESHES", {})
    cfg = core.TpulsarConfig()
    cfg.searching.dm_shards = 16          # the test host has 8
    srv = SearchServer(spool=str(tmp_path / "spool"), cfg=cfg,
                       warm_boot=False)
    with pytest.raises(RuntimeError, match="dm_shards=16 needs 16"):
        srv.boot()


# ------------------------------------------- 4d: spans and counters

def test_the_three_mesh_stages_are_siblings_under_the_pass(pair):
    ev = pair["events"]
    (pas,) = [e for e in ev if e["name"] == "pass"]
    stages = {e["name"]: e for e in ev
              if e["name"] in ("mesh-place", "sharded-search",
                               "mesh-candidates")}
    assert set(stages) == {"mesh-place", "sharded-search",
                           "mesh-candidates"}
    assert all(e["parent_id"] == pas["id"] for e in stages.values())
    place = stages["mesh-place"]["args"]
    assert place["devices"] == 4
    # four copies of the toy's (16, 65536) float32 subbands at least
    assert place["bytes"] >= 4 * 16 * 65536 * 4
    assert place["bytes"] == pair["delta"]["placed"]
    cands = stages["mesh-candidates"]["args"]
    assert cands["cands"] > 0 and cands["events"] > 0
    # none of the solo chunk loop's spans
    assert not [e for e in ev if e["name"] in ("dm_chunk",
                                               "hi-accelsearch")]


def test_mesh_chunk_spans_count_the_rows(pair):
    ev = pair["events"]
    (search,) = [e for e in ev if e["name"] == "sharded-search"]
    chunks = [e for e in ev if e["name"] == "mesh_chunk"]
    assert chunks and all(e["parent_id"] == search["id"] for e in chunks)
    args = [e["args"] for e in chunks]
    assert sum(a["n"] for a in args) == pair["ntrials"]
    assert sum(a["rows"] for a in args) == pair["rows"]
    assert sum(a["rows"] - a["n"] for a in args) == pair["recomputed"] \
        == pair["delta"]["recomputed"]
    assert pair["delta"]["searched"] == pair["ntrials"]
    for a in args:
        assert a["devices"] == 4 and a["hi"] is True
        assert a["rows_per_device"] * 4 == a["rows"]
        assert a["pass_idx"] == 0
    assert [a["lo"] for a in args] == sorted(a["lo"] for a in args)
    fetches = [e for e in ev if e["name"] == "mesh-fetch"]
    assert len(fetches) == len(chunks)
    assert {f["parent_id"] for f in fetches} == {c["id"] for c in chunks}
    assert all(f["args"]["bytes"] > 0 for f in fetches)


def test_mesh_chunk_says_the_lo_form(pair):
    """mesh_chunk carries the lo stage's harmonic sums as each device's
    rows got them: the strided form here (a CPU; `tiled` and the
    kernel's tile on a TPU), docs/operations.md."""
    chunks = [e["args"] for e in pair["events"]
              if e["name"] == "mesh_chunk"]
    assert chunks
    assert {(a["lo_form"], a["lo_tile"]) for a in chunks} == {
        ("strided", 0)}


def test_mesh_chunk_says_the_exchange_form_and_its_planes_bytes(pair):
    """... and how its subbands came (a whole block: no exchange,
    `mesh-place` replicated them) and what a device's rows hold of
    hi-accel planes, by the count plane_dm_chunk sized the rows with."""
    from tpulsar.kernels import accel
    from tpulsar.plan import ddplan

    chunks = [e["args"] for e in pair["events"]
              if e["name"] == "mesh_chunk"]
    assert {a["form"] for a in chunks} == {"none"}
    cell = pair["cell"]
    nbins = ddplan.choose_n(cell.nsamp) // 2 + 1
    row = accel.plane_row_bytes(nbins, len(accel.z_grid(50)),
                                accel.corr_z_pieces())
    assert all(a["hi"] and a["plane_bytes"] == a["rows_per_device"] * row
               for a in chunks)


def test_mesh_chunk_says_the_sp_form(pair):
    """... and the boxcar ladder's: the plain chain here (`tiled` and
    the kernel's tile on a TPU: singlepulse.sp_dispatch_attrs)."""
    chunks = [e["args"] for e in pair["events"]
              if e["name"] == "mesh_chunk"]
    assert chunks
    assert {(a["sp_form"], a["sp_tile"]) for a in chunks} == {("plain", 0)}


def test_the_solo_report_lists_no_mesh_stage():
    """``report.STAGES`` and a solo search's ``.report`` text stay as
    they are: ``StageTimers.timing`` takes a mesh stage when one is
    timed, and only then."""
    from tpulsar.search import report

    assert not [s for s in report.STAGES if "mesh" in s or "sharded" in s]
    timers = report.StageTimers()
    assert "mesh-place" not in timers.report_text("beam")
    with timers.timing("mesh-place"):
        pass
    assert "mesh-place" in timers.times
