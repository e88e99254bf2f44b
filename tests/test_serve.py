"""Resident warm-worker serving tests: spool protocol, admission
backpressure, drain-on-SIGTERM, poisoned-beam isolation, and the
warm queue backend's fallback to process-per-beam submission."""

import os
import signal
import stat
import threading
import time
import types

import pytest

from tpulsar.io import synth
from tpulsar.orchestrate.queue_managers.warm import WarmServerManager
from tpulsar.resilience import faults
from tpulsar.serve import protocol
from tpulsar.serve.server import SearchServer


@pytest.fixture()
def cfg(tmp_path):
    from tpulsar.config import TpulsarConfig, set_settings

    cfg = TpulsarConfig()
    cfg.basic.log_dir = str(tmp_path / "logs")
    cfg.background.jobtracker_db = str(tmp_path / "jt.db")
    cfg.download.datadir = str(tmp_path / "raw")
    cfg.processing.base_working_directory = str(tmp_path / "work")
    cfg.processing.base_results_directory = str(tmp_path / "res")
    cfg.resultsdb.url = str(tmp_path / "results.db")
    cfg.check_sanity(create_dirs=True)
    set_settings(cfg)
    yield cfg
    set_settings(TpulsarConfig())


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.reset()


def _beam_files(tmp_path, n=1):
    out = []
    for i in range(n):
        spec = synth.BeamSpec(nchan=16, nsamp=512, nsblk=64,
                              scan=100 + i)
        out.append(synth.synth_beam(str(tmp_path / f"data{i}"), spec,
                                    merged=True))
    return out


def _fake_outcome(misses=0):
    return types.SimpleNamespace(compile_misses=misses, compile_hits=3,
                                 candidates=[], num_dm_trials=8)


def _server(spool, cfg, **kw):
    kw.setdefault("warm_boot", False)
    kw.setdefault("poll_s", 0.05)
    return SearchServer(spool=str(spool), cfg=cfg, **kw)


# ------------------------------------------------------------- protocol

def test_spool_ticket_roundtrip(tmp_path):
    spool = str(tmp_path / "spool")
    protocol.write_ticket(spool, "t1", ["/a/x.fits"], "/out1",
                          job_id=7)
    time.sleep(0.01)
    protocol.write_ticket(spool, "t2", ["/a/y.fits"], "/out2",
                          job_id=8)
    assert protocol.pending_count(spool) == 2
    assert protocol.ticket_state(spool, "t1") == "incoming"

    rec = protocol.claim_next_ticket(spool)
    assert rec["ticket"] == "t1"            # FIFO by submitted_at
    assert rec["job_id"] == 7 and rec["datafiles"] == ["/a/x.fits"]
    assert protocol.ticket_state(spool, "t1") == "claimed"
    assert protocol.pending_count(spool) == 1

    protocol.write_result(spool, "t1", "done", beam_seconds=1.5,
                          warm=True, compile_misses=0)
    assert protocol.ticket_state(spool, "t1") == "done"
    out = protocol.read_result(spool, "t1")
    assert out["status"] == "done" and out["warm"] is True
    # the claim was released only after the result became durable
    assert not os.path.exists(
        protocol.ticket_path(spool, "t1", "claimed"))

    # boot recovery: a claimed-but-unfinished ticket is requeued, a
    # claimed-with-result one is just reconciled
    protocol.claim_next_ticket(spool)
    assert protocol.requeue_stale_claims(spool) == ["t2"]
    assert protocol.ticket_state(spool, "t2") == "incoming"


def test_requeue_skips_live_coserver_claims(tmp_path):
    """Boot recovery must not steal a beam a LIVE co-server on the
    same spool is mid-way through — only claims whose owner pid is
    gone (or our own, at drain) are requeued."""
    import json
    import subprocess

    spool = str(tmp_path / "spool")
    protocol.write_ticket(spool, "a", ["/x"], "/o", job_id=1)
    time.sleep(0.01)
    protocol.write_ticket(spool, "b", ["/y"], "/o2", job_id=2)
    protocol.claim_next_ticket(spool)
    protocol.claim_next_ticket(spool)
    p = subprocess.Popen(["true"])
    p.wait()                                  # reaped: pid is dead
    for tid, owner in (("a", 1), ("b", p.pid)):
        path = protocol.ticket_path(spool, tid, "claimed")
        rec = json.load(open(path))
        rec["claimed_by"] = owner
        protocol._atomic_write_json(path, rec)
    assert protocol.requeue_stale_claims(spool) == ["b"]
    assert protocol.ticket_state(spool, "a") == "claimed"
    assert protocol.ticket_state(spool, "b") == "incoming"


def test_heartbeat_freshness(tmp_path):
    spool = str(tmp_path / "spool")
    assert not protocol.heartbeat_fresh(spool)     # no server ever
    protocol.write_heartbeat(spool, status="running")
    assert protocol.heartbeat_fresh(spool)
    protocol.write_heartbeat(spool, status="draining")
    assert not protocol.heartbeat_fresh(spool)     # draining = closed
    protocol._atomic_write_json(                   # long-dead server
        protocol.heartbeat_path(spool),
        {"t": time.time() - 9999, "pid": 1, "status": "running"})
    assert not protocol.heartbeat_fresh(spool)


# ------------------------------------------------------------ the loop

def test_serve_once_processes_spool(tmp_path, cfg):
    """Two real synthetic beams through the loop (stubbed device
    work): stage-in runs for real, every ticket gets a result record,
    outdirs are created, the heartbeat ends 'stopped'."""
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 2)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"w{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    seen = []

    def stub(prepared):
        # the prefetch thread really staged the files into a scratch
        # workspace before the device loop saw the beam
        assert prepared.ppfns and all(
            os.path.exists(f) for f in prepared.ppfns)
        assert prepared.workdir != os.path.dirname(beams[0][0])
        seen.append(prepared.ticket_id)
        return _fake_outcome(misses=2 if not seen[:-1] else 0)

    srv = _server(spool, cfg, beam_fn=stub)
    assert srv.serve(once=True) == 0
    assert sorted(seen) == ["w0", "w1"]
    r0 = protocol.read_result(str(spool), "w0")
    r1 = protocol.read_result(str(spool), "w1")
    assert {r0["status"], r1["status"]} == {"done"}
    # first beam paid compiles (cold), second did not (warm)
    by_id = {r["ticket"]: r for r in (r0, r1)}
    first, second = seen
    assert by_id[first]["warm"] is False
    assert by_id[second]["warm"] is True
    assert protocol.read_heartbeat(str(spool))["status"] == "stopped"
    # every record says where it ran, as jax reports it in the worker,
    # and how the boot went (no gate ran here)
    import jax
    dev = jax.devices()
    assert r0["device"] == r1["device"] == {
        "platform": "cpu", "kind": dev[0].device_kind,
        "count": len(dev)}
    assert r0["boot_gate_rc"] is None and r0["boot_seconds"] >= 0
    assert srv.beams == {"done": 2, "failed": 0, "skipped": 0}


def test_backpressure_can_submit_false_when_queue_full(tmp_path):
    spool = str(tmp_path / "spool")
    protocol.write_heartbeat(spool, status="running")
    qm = WarmServerManager(spool=spool, max_queue_depth=2)
    assert qm.can_submit()
    qm.submit(["/a.fits"], str(tmp_path / "o1"), 1)
    assert qm.can_submit()
    qm.submit(["/b.fits"], str(tmp_path / "o2"), 2)
    assert not qm.can_submit()              # admission queue full
    assert qm.status()[0] == 2
    # a claim frees an admission slot
    protocol.claim_next_ticket(spool)
    assert qm.can_submit()


def test_drain_completes_inflight_beam(tmp_path, cfg):
    """SIGTERM mid-beam: the in-flight beam finishes and its result
    is durable; unstarted tickets go back to incoming; the final
    heartbeat says 'stopped' so clients fall back."""
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 3)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"d{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    started = threading.Event()

    def slow(prepared):
        started.set()
        time.sleep(0.8)
        return _fake_outcome()

    srv = _server(spool, cfg, beam_fn=slow)
    old_term = signal.getsignal(signal.SIGTERM)
    old_int = signal.getsignal(signal.SIGINT)
    srv.install_signal_handlers()
    try:
        th = threading.Thread(target=srv.serve, daemon=True)
        th.start()
        assert started.wait(timeout=20.0)
        signal.raise_signal(signal.SIGTERM)   # delivered to main thread
        th.join(timeout=30.0)
        assert not th.is_alive()
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
    done = protocol.list_tickets(str(spool), "done")
    assert "d0" in done                       # in-flight beam completed
    assert protocol.read_result(str(spool), "d0")["status"] == "done"
    # nothing left half-claimed; the unprocessed tail is resubmittable
    assert protocol.list_tickets(str(spool), "claimed") == []
    assert (len(done)
            + protocol.pending_count(str(spool))) == 3
    assert protocol.read_heartbeat(str(spool))["status"] == "stopped"


def test_poisoned_beam_isolation(tmp_path, cfg, monkeypatch):
    """A beam that raises a refusal-shaped error (TPULSAR_FAULTS
    point serve.beam) fails ITS ticket; the server and the following
    beams are unaffected.  Uses the real _search_one runner so the
    injection point in the production path is what fires."""
    from tpulsar.cli import search_job

    monkeypatch.setattr(search_job, "run_search",
                        lambda *a, **k: _fake_outcome())
    faults.configure("serve.beam:unimplemented:count=1")
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 2)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"p{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    srv = _server(spool, cfg)                 # default beam_fn
    assert srv.serve(once=True) == 0
    r0 = protocol.read_result(str(spool), "p0")
    r1 = protocol.read_result(str(spool), "p1")
    assert r0["status"] == "failed" and "UNIMPLEMENTED" in r0["error"]
    assert r1["status"] == "done"
    assert srv.beams["failed"] == 1 and srv.beams["done"] == 1
    assert faults.fired("serve.beam") == 1


def test_stagein_failure_fails_only_that_ticket(tmp_path, cfg):
    spool = tmp_path / "spool"
    protocol.write_ticket(str(spool), "bad", ["/nonexistent.fits"],
                          str(tmp_path / "outbad"), job_id=1)
    (good,) = _beam_files(tmp_path, 1)
    protocol.write_ticket(str(spool), "good", good,
                          str(tmp_path / "outgood"), job_id=2)
    srv = _server(spool, cfg, beam_fn=lambda p: _fake_outcome())
    assert srv.serve(once=True) == 0
    assert protocol.read_result(str(spool), "bad")["status"] == "failed"
    assert "stage-in failed" in protocol.read_result(
        str(spool), "bad")["error"]
    assert protocol.read_result(str(spool), "good")["status"] == "done"


def test_beam_deadline_fails_ticket_not_server(tmp_path, cfg):
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 2)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"t{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    calls = []

    def maybe_hang(prepared):
        calls.append(prepared.ticket_id)
        if len(calls) == 1:
            time.sleep(5.0)                  # a wedged dispatch
        return _fake_outcome()

    srv = _server(spool, cfg, beam_fn=maybe_hang, beam_deadline_s=0.3)
    assert srv.serve(once=True) == 0
    hung, ok = calls[0], calls[1]
    rec = protocol.read_result(str(spool), hung)
    assert rec["status"] == "failed" and "deadline" in rec["error"]
    assert protocol.read_result(str(spool), ok)["status"] == "done"


# ---------------------------------------------------- the warm backend

def _fake_worker_script(tmp_path, body="touch $OUTDIR/done.marker\n"):
    script = tmp_path / "worker.sh"
    script.write_text("#!/bin/sh\n" + body)
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    return str(script)


def test_warm_backend_submits_tickets_when_server_fresh(tmp_path):
    spool = str(tmp_path / "spool")
    protocol.write_heartbeat(spool, status="running")
    qm = WarmServerManager(spool=spool, max_queue_depth=4)
    qid = qm.submit(["/a.fits"], str(tmp_path / "o"), 11)
    assert qid.startswith("warm-")
    assert qm.is_running(qid)                # waiting for admission
    assert protocol.pending_count(spool) == 1
    # the server finishes it
    protocol.claim_next_ticket(spool)
    protocol.write_result(spool, qid, "done", beam_seconds=2.0,
                          warm=True)
    assert not qm.is_running(qid)
    assert not qm.had_errors(qid)
    # failed beams surface through the same contract
    qid2 = qm.submit(["/b.fits"], str(tmp_path / "o2"), 12)
    protocol.claim_next_ticket(spool)
    protocol.write_result(spool, qid2, "failed", rc=1,
                          error="UNIMPLEMENTED: boom")
    assert qm.had_errors(qid2)
    assert "boom" in qm.get_errors(qid2)


def test_warm_backend_falls_back_when_heartbeat_stale(tmp_path):
    """No fresh heartbeat: submission, capacity, and queries all go
    through the embedded LocalProcessManager — a warm deployment
    keeps searching when the server is down."""
    spool = protocol.ensure_spool(str(tmp_path / "spool"))
    protocol._atomic_write_json(               # stale server
        protocol.heartbeat_path(spool),
        {"t": time.time() - 9999, "pid": 1, "status": "running"})
    qm = WarmServerManager(
        spool=spool, max_queue_depth=4,
        fallback_kwargs={"max_jobs_running": 2,
                         "script": _fake_worker_script(tmp_path),
                         "state_dir": str(tmp_path / "localq")})
    try:
        assert not qm.server_available()
        assert qm.can_submit()
        qid = qm.submit(["/a.fits"], str(tmp_path / "out"), 21)
        assert not qid.startswith("warm-")     # a real subprocess
        assert protocol.pending_count(spool) == 0
        for _ in range(50):
            if not qm.is_running(qid):
                break
            time.sleep(0.1)
        assert not qm.had_errors(qid)
        assert os.path.exists(str(tmp_path / "out" / "done.marker"))
    finally:
        qm.shutdown()


def test_warm_backend_abandons_orphaned_ticket(tmp_path):
    """A ticket submitted to a server that then died must not be
    polled forever: once the heartbeat is stale, is_running() fails
    it (removing it from the spool so a restarted server cannot
    double-process it) and the pool's retry machinery takes over."""
    spool = str(tmp_path / "spool")
    protocol.write_heartbeat(spool, status="running")
    qm = WarmServerManager(spool=spool)
    qid = qm.submit(["/a.fits"], str(tmp_path / "o"), 31)
    # server dies without claiming the ticket
    protocol._atomic_write_json(
        protocol.heartbeat_path(spool),
        {"t": time.time() - 9999, "pid": 1, "status": "running"})
    assert not qm.is_running(qid)
    assert qm.had_errors(qid)
    assert "abandoned" in qm.get_errors(qid)
    assert protocol.pending_count(spool) == 0  # gone from the spool


def test_warm_backend_delete_contract(tmp_path):
    spool = str(tmp_path / "spool")
    protocol.write_heartbeat(spool, status="running")
    qm = WarmServerManager(spool=spool)
    qid = qm.submit(["/a.fits"], str(tmp_path / "o"), 41)
    assert qm.delete(qid)                      # waiting: cancellable
    assert protocol.pending_count(spool) == 0
    qid2 = qm.submit(["/b.fits"], str(tmp_path / "o2"), 42)
    protocol.claim_next_ticket(spool)
    assert not qm.delete(qid2)                 # in-flight: cannot abort


def test_warm_boot_verifies_before_recompiling(monkeypatch):
    """Server boot warm-start: with a manifest, a clean verify is the
    whole boot cost; misses (or no manifest) trigger the compile
    gate."""
    from tpulsar.aot import warmstart

    calls = []

    def gate(verify_rc):
        def fake(**kw):
            calls.append(bool(kw.get("verify", False)))
            return verify_rc if kw.get("verify") else 0
        return fake

    monkeypatch.setattr(warmstart, "load_manifest",
                        lambda *a, **k: {"programs": {}})
    monkeypatch.setattr(warmstart, "run_gate", gate(0))
    assert warmstart.warm_boot(echo=lambda s: None) == 0
    assert calls == [True]                 # verify only, no compile

    calls.clear()
    monkeypatch.setattr(warmstart, "run_gate", gate(1))
    assert warmstart.warm_boot(echo=lambda s: None) == 0
    assert calls == [True, False]          # misses -> compile follows

    calls.clear()
    monkeypatch.setattr(warmstart, "load_manifest", lambda *a, **k: None)
    assert warmstart.warm_boot(echo=lambda s: None) == 0
    assert calls == [False]                # no manifest -> compile


def test_get_queue_manager_registers_warm(tmp_path):
    from tpulsar.orchestrate.queue_managers import get_queue_manager

    qm = get_queue_manager("warm", spool=str(tmp_path / "spool"))
    assert isinstance(qm, WarmServerManager)
    for m in ("submit", "can_submit", "is_running", "delete",
              "status", "had_errors", "get_errors"):
        assert callable(getattr(qm, m))


# ------------------------------------------------------ batched admission

def test_serve_batch_mode_coalesces_and_finishes_each_ticket(
        tmp_path, cfg):
    """serve --batch N: one claim_batch admission pass, ONE
    batch_dispatch journal event naming the members, per-ticket
    search_start and durable results — per-beam discipline unchanged
    by coalesced dispatch."""
    from tpulsar.obs import journal

    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 3)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"b{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    batches = []

    def batch_stub(prepared_list):
        assert all(p.ppfns and os.path.exists(p.ppfns[0])
                   for p in prepared_list)     # really staged
        batches.append([p.ticket_id for p in prepared_list])
        return [("done", _fake_outcome(), "batched")
                for _ in prepared_list]

    srv = _server(spool, cfg, batch_size=3, batch_linger_s=0.2,
                  batch_fn=batch_stub)
    assert srv.serve(once=True) == 0
    assert sorted(t for b in batches for t in b) == ["b0", "b1", "b2"]
    for i in range(3):
        rec = protocol.read_result(str(spool), f"b{i}")
        assert rec["status"] == "done", rec
        assert rec["batch_path"] == "batched"
    evs = journal.read_events(str(spool))
    bd = [e for e in evs if e["event"] == "batch_dispatch"]
    assert bd and sum(e["beams"] for e in bd) == 3
    assert len([e for e in evs
                if e["event"] == "search_start"]) == 3


def test_serve_batch_partial_dispatches_after_linger(tmp_path, cfg):
    """A partial batch must dispatch after the bounded linger window
    instead of starving: 2 tickets, batch size 3."""
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 2)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"p{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)
    sizes = []

    def batch_stub(prepared_list):
        sizes.append(len(prepared_list))
        return [("done", _fake_outcome(), "batched")
                for _ in prepared_list]

    srv = _server(spool, cfg, batch_size=3, batch_linger_s=0.2,
                  batch_fn=batch_stub)
    assert srv.serve(once=True) == 0
    assert sizes == [2]
    assert all(protocol.read_result(str(spool), f"p{i}")["status"]
               == "done" for i in range(2))


def test_serve_batch_per_beam_failure_isolated(tmp_path, cfg):
    """A beam that fails inside the batch fails ITS ticket only —
    batchmates finish normally (the executor's per-beam degradation
    surfaces as a per-job failed tuple, never an exception)."""
    spool = tmp_path / "spool"
    beams = _beam_files(tmp_path, 2)
    for i, fns in enumerate(beams):
        protocol.write_ticket(str(spool), f"f{i}", fns,
                              str(tmp_path / f"out{i}"), job_id=i)

    def batch_stub(prepared_list):
        out = []
        for k, p in enumerate(sorted(prepared_list,
                                     key=lambda p: p.ticket_id)):
            out.append(("done", _fake_outcome(), "batched") if k == 0
                       else ("failed", RuntimeError("poisoned beam"),
                             "solo"))
        return out

    srv = _server(spool, cfg, batch_size=2, batch_linger_s=0.2,
                  batch_fn=batch_stub)
    assert srv.serve(once=True) == 0
    recs = {i: protocol.read_result(str(spool), f"f{i}")
            for i in range(2)}
    statuses = sorted(r["status"] for r in recs.values())
    assert statuses == ["done", "failed"]
    failed = next(r for r in recs.values() if r["status"] == "failed")
    assert "poisoned beam" in failed["error"]


# ------------------------------------------------------------- stream mode

def test_serve_stream_mode_runs_session_tickets(tmp_path, cfg):
    import numpy as np

    from tpulsar.stream import STREAM_PROFILE, ingest

    spool = tmp_path / "spool"
    sroot = str(tmp_path / "stream")
    geom = dict(STREAM_PROFILE, nchan=16, ndms=8, chunk_len=256)
    rng = np.random.default_rng(5)
    ingest.open_session(sroot, "sv", geom)
    for k in range(4):
        ingest.append_chunk(
            sroot, "sv",
            k, rng.normal(0, 1, (16, 256)).astype(np.float32),
            t_ingest=time.time())
    ingest.close_session(sroot, "sv", 4)

    server = _server(spool, cfg, worker_id="ws", stream=True,
                     poll_s=0.02)
    server.queue.submit("sv-t", [], str(tmp_path / "out"),
                        kind="stream", session="sv",
                        stream_root=sroot)
    # a beam ticket on the same spool is refused, not searched
    server.queue.submit("beam-t", ["/data/x.fits"],
                        str(tmp_path / "out2"))
    assert server.serve(once=True) == 0
    res = server.queue.read_result("sv-t")
    assert res["status"] == "done"
    assert res["chunks"] == 4 and res["gaps"] == 0
    assert server.queue.read_result("beam-t")["status"] == "failed"
    assert server.beams == {"done": 1, "failed": 0, "skipped": 1}
    from tpulsar.obs import journal
    names = [e["event"] for e in journal.read_events(
        server.jroot, ticket="sv-t")]
    assert names.count("chunk_received") == 4
    assert "stream_closed" in names
