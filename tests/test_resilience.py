"""Resilience layer: deterministic fault injection, the shared
retry/backoff/deadline/circuit-breaker policy, and host rescue of
device-refused accel work — including the end-to-end property the
subsystem exists for: a CPU run with 100% of accel row dispatches
refused produces the SAME candidate list as a clean run (all rows
host-rescued, zero rows zero-filled)."""

import os
import sqlite3
import time

import numpy as np
import pytest

from tpulsar.resilience import faults, policy, rescue


@pytest.fixture(autouse=True)
def _disarm():
    """No test's armed faults may leak into the next (or into the
    other test modules running in this process)."""
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------ fault specs

def test_parse_full_spec():
    specs = faults.parse_spec(
        "accel.row_dispatch:unimplemented:rate=0.25,seed=7,after=3;"
        "download.transfer:hang:seconds=5;"
        "queue.submit:unimplemented:count=2")
    s = specs["accel.row_dispatch"]
    assert (s.mode, s.rate, s.seed, s.after) == ("unimplemented",
                                                 0.25, 7, 3)
    assert specs["download.transfer"].seconds == 5.0
    assert specs["queue.submit"].count == 2


def test_parse_defaults():
    s = faults.parse_spec("upload.write:poison")["upload.write"]
    assert (s.rate, s.seed, s.after, s.count) == (1.0, 0, 0, 0)


@pytest.mark.parametrize("bad", [
    "nosuch.point:unimplemented",      # unknown point
    "accel.chunk:explode",             # unknown mode
    "accel.chunk:unimplemented:frobnicate=1",   # unknown option
    "accel.chunk:unimplemented:rate=1.5",       # rate outside [0,1]
    "accel.chunk",                     # missing mode
    "accel.chunk:hang:seconds",        # option not key=val
    "accel.chunk:hang;accel.chunk:hang",        # duplicate point
])
def test_parse_rejects_loudly(bad):
    """A typo'd spec that silently never fired would make a
    reproduction run meaningless — every malformed spec must raise at
    configure time."""
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


def test_fire_raises_refusal_shape():
    faults.configure("queue.submit:unimplemented")
    with pytest.raises(Exception, match="UNIMPLEMENTED.*queue.submit"):
        faults.fire("queue.submit")
    assert faults.fired("queue.submit") == 1
    faults.fire("upload.write")        # un-armed point: no-op


def test_fire_shapes_to_site_taxonomy():
    faults.configure("download.transfer:unimplemented")
    with pytest.raises(IOError):
        faults.fire("download.transfer", make_exc=IOError)


def test_rate_is_deterministic_per_seed():
    def pattern():
        faults.configure("accel.chunk:unimplemented:rate=0.4,seed=11")
        hits = []
        for i in range(40):
            try:
                faults.fire("accel.chunk")
            except Exception:
                hits.append(i)
        return hits

    first, second = pattern(), pattern()
    assert first == second            # a reproduction is a command line
    assert 0 < len(first) < 40        # rate actually thins the stream
    faults.configure("accel.chunk:unimplemented:rate=0.4,seed=12")
    third = []
    for i in range(40):
        try:
            faults.fire("accel.chunk")
        except Exception:
            third.append(i)
    assert third != first             # the seed is the stream


def test_after_and_count_windows():
    faults.configure("accel.chunk:unimplemented:after=2,count=3")
    outcomes = []
    for _ in range(8):
        try:
            faults.fire("accel.chunk")
            outcomes.append(False)
        except Exception:
            outcomes.append(True)
    # calls 1-2 spared (after), 3-5 fire (count=3), 6-8 spared
    assert outcomes == [False, False, True, True, True,
                        False, False, False]


def test_poison_refuses_everything_after():
    faults.configure("upload.write:poison")
    with pytest.raises(Exception):
        faults.fire("upload.write")
    # EVERY later fire at ANY point now raises — the wedged-chip mode
    with pytest.raises(faults.SessionPoisoned):
        faults.fire("accel.row_dispatch")
    with pytest.raises(faults.SessionPoisoned):
        faults.fire("download.transfer")
    faults.configure("")              # configure clears poisoned state
    faults.fire("accel.row_dispatch")


def test_snapshot_reports_counts():
    faults.configure("queue.submit:unimplemented:count=1")
    for _ in range(3):
        try:
            faults.fire("queue.submit")
        except Exception:
            pass
    snap = faults.snapshot()
    assert snap["queue.submit"]["calls"] == 3
    assert snap["queue.submit"]["fired"] == 1


# ---------------------------------------------------------- retry policy

def test_backoff_curve_matches_jobtracker_loop():
    p = policy.RetryPolicy(backoff_base_s=0.05, backoff_mult=2.0,
                           backoff_max_s=1.0)
    assert [p.backoff_s(k) for k in range(6)] == \
        [0.05, 0.1, 0.2, 0.4, 0.8, 1.0]


def test_backoff_jitter_bounds():
    p = policy.RetryPolicy(backoff_base_s=1.0, backoff_mult=1.0,
                           backoff_max_s=1.0, jitter=True)
    lo = p.backoff_s(0, rng=lambda: 0.0)
    hi = p.backoff_s(0, rng=lambda: 0.999)
    assert lo == pytest.approx(0.5) and hi == pytest.approx(1.499)


def test_should_retry_serves_db_counter_loops():
    p = policy.RetryPolicy(max_attempts=3)
    assert [p.should_retry(n) for n in (0, 2, 3, 4)] == \
        [True, True, False, False]


def test_call_retries_then_succeeds():
    sleeps, tries = [], []

    def flaky():
        tries.append(1)
        if len(tries) < 3:
            raise IOError("transient")
        return "ok"

    out = policy.call(flaky,
                      policy.RetryPolicy(max_attempts=4,
                                         backoff_base_s=2.0,
                                         retry_on=(IOError,)),
                      sleeper=sleeps.append)
    assert out == "ok" and len(tries) == 3
    assert sleeps == [2.0, 4.0]       # backoff between attempts only


def test_call_exhaustion_raises_last():
    with pytest.raises(IOError, match="always"):
        policy.call(lambda: (_ for _ in ()).throw(IOError("always")),
                    policy.RetryPolicy(max_attempts=3,
                                       retry_on=(IOError,)),
                    sleeper=lambda s: None)


def test_call_nonretryable_raises_immediately():
    tries = []

    def wrong_kind():
        tries.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        policy.call(wrong_kind,
                    policy.RetryPolicy(max_attempts=5,
                                       retry_on=(IOError,)),
                    sleeper=lambda s: None)
    assert len(tries) == 1


def test_retryable_predicate_refines_class_match():
    p = policy.RetryPolicy(
        retry_on=(sqlite3.OperationalError,),
        retryable=lambda e: "locked" in str(e) or "busy" in str(e))
    assert p._is_retryable(sqlite3.OperationalError("database is locked"))
    assert not p._is_retryable(sqlite3.OperationalError("syntax error"))
    assert not p._is_retryable(ValueError("locked"))


def test_on_retry_observes_each_failure():
    seen = []

    def flaky():
        if len(seen) < 2:
            raise IOError("x")
        return 1

    policy.call(flaky, policy.RetryPolicy(max_attempts=3,
                                          retry_on=(IOError,)),
                sleeper=lambda s: None,
                on_retry=lambda k, e: seen.append((k, str(e))))
    assert [k for k, _ in seen] == [0, 1]


def test_on_retry_never_fires_after_terminal_failure():
    """The hook means 'a retry WILL follow' (callers roll back / log
    'replaying...' in it) — it must not run after the last attempt."""
    seen = []
    with pytest.raises(IOError):
        policy.call(lambda: (_ for _ in ()).throw(IOError("x")),
                    policy.RetryPolicy(max_attempts=2,
                                       retry_on=(IOError,)),
                    sleeper=lambda s: None,
                    on_retry=lambda k, e: seen.append(k))
    assert seen == [0]                # not after attempt 1 (terminal)


def test_call_rejects_zero_attempts():
    with pytest.raises(ValueError, match="max_attempts"):
        policy.call(lambda: 1, policy.RetryPolicy(max_attempts=0))


# -------------------------------------------------------- circuit breaker

def test_breaker_opens_and_recovers():
    now = [0.0]
    br = policy.CircuitBreaker(failure_threshold=3, cooloff_s=10.0,
                               clock=lambda: now[0])
    assert br.allow()
    for _ in range(3):
        br.record_failure()
    assert not br.allow()             # open: skip the doomed call
    now[0] = 11.0
    assert br.allow()                 # half-open: one trial allowed
    br.record_success()
    assert br.allow() and br.state == "closed"


def test_breaker_reopen_on_halfopen_failure():
    now = [0.0]
    br = policy.CircuitBreaker(failure_threshold=2, cooloff_s=5.0,
                               clock=lambda: now[0])
    br.record_failure(); br.record_failure()
    now[0] = 6.0
    assert br.allow()
    br.record_failure()               # trial failed: re-open
    assert not br.allow()


def test_call_with_open_breaker_refuses():
    br = policy.CircuitBreaker(failure_threshold=1, cooloff_s=1e9)
    with pytest.raises(IOError):
        policy.call(lambda: (_ for _ in ()).throw(IOError("x")),
                    policy.RetryPolicy(max_attempts=1,
                                       retry_on=(IOError,)),
                    breaker=br, sleeper=lambda s: None)
    with pytest.raises(policy.CircuitOpenError):
        policy.call(lambda: "never runs",
                    policy.RetryPolicy(max_attempts=1), breaker=br,
                    sleeper=lambda s: None)


# ------------------------------------------------------ watchdog deadline

def test_run_with_deadline_passthrough():
    assert policy.run_with_deadline(lambda: 7, 0) == 7        # inline
    assert policy.run_with_deadline(lambda: 7, 5.0) == 7      # threaded


def test_run_with_deadline_propagates_exception():
    def boom():
        raise KeyError("inner")
    with pytest.raises(KeyError):
        policy.run_with_deadline(boom, 5.0)


def test_run_with_deadline_classifies_hang():
    t0 = time.monotonic()
    with pytest.raises(policy.DeadlineExceeded, match="deadline"):
        policy.run_with_deadline(lambda: time.sleep(5.0), 0.1,
                                 label="test hang")
    assert time.monotonic() - t0 < 2.0     # caller got control back


def test_hang_fault_converted_by_watchdog():
    """The session-poisoning hang, bounded: a `hang` fault sleeps past
    the watchdog deadline and the caller sees a CLASSIFIED failure
    instead of an unbounded stall."""
    faults.configure("download.transfer:hang:seconds=1.0")
    with pytest.raises(policy.DeadlineExceeded):
        policy.run_with_deadline(
            lambda: faults.fire("download.transfer"), 0.1)


# ------------------------------------------------- host rescue (unit)

def test_rescue_disabled_by_env(monkeypatch):
    monkeypatch.setenv("TPULSAR_HOST_RESCUE", "0")
    assert not rescue.enabled()
    assert rescue.rescue_accel_rows(None, None, [1], max_numharm=4,
                                    topk=8) == ({}, False)


def test_rescue_no_rows_is_noop():
    assert rescue.rescue_accel_rows(None, None, [], max_numharm=4,
                                    topk=8) == ({}, False)


def test_rescue_unfetchable_spectra_not_exhausted():
    """A rescue whose device fetch is refused reports
    recompute_ran=False: the caller's chunk-level retry (which
    re-fetches) is still a live second chance."""
    class _Unfetchable:
        def __array__(self, *a, **k):
            raise RuntimeError("UNIMPLEMENTED: poisoned session")
    out, ran = rescue.rescue_accel_rows(_Unfetchable(), None, [0, 1],
                                        max_numharm=4, topk=8)
    assert out == {} and ran is False


def test_rescue_fetch_bounded_by_watchdog(monkeypatch):
    """A fetch that HANGS (wedged session) is bounded by the same
    watchdog deadline as the dispatches — rescue reports the rows
    unrescued instead of stalling the beam."""
    monkeypatch.setenv("TPULSAR_ACCEL_DISPATCH_DEADLINE_S", "0.05")

    class _Hanging:
        def __array__(self, *a, **k):
            time.sleep(30)

    t0 = time.monotonic()
    out, ran = rescue.rescue_accel_rows(_Hanging(), None, [0],
                                        max_numharm=4, topk=8)
    assert out == {} and ran is False
    assert time.monotonic() - t0 < 10


def test_rescue_chunk_partial_keeps_recovered_rows(small_spectra,
                                                   monkeypatch):
    """One failed row in a chunk rescue must not discard the rows
    that DID recompute: they are returned, the failed row is
    zero-filled and reported in lost_rows."""
    from tpulsar.kernels import accel as ak
    spec, bank = small_spectra
    real = ak.accel_row_topk

    def flaky(block, bank_fft, i, **kw):
        if int(i) == 2:
            raise RuntimeError("transient host failure")
        return real(block, bank_fft, i, **kw)

    monkeypatch.setattr(ak, "accel_row_topk", flaky)
    out = rescue.rescue_accel_chunk(spec, bank, max_numharm=4, topk=8)
    assert out is not None
    res, lost = out
    assert lost == [2]
    monkeypatch.setattr(ak, "accel_row_topk", real)
    res2, lost2 = rescue.rescue_accel_chunk(spec, bank, max_numharm=4,
                                            topk=8)
    assert lost2 == []
    keep = [i for i in range(spec.shape[0]) if i != 2]
    for h in res:
        for a, b in zip(res[h], res2[h]):
            assert np.array_equal(np.asarray(a)[keep],
                                  np.asarray(b)[keep])
        assert np.all(np.asarray(res[h][0])[2] == 0.0)  # zero power


# ------------------------------------------- accel end-to-end (CPU)

@pytest.fixture(scope="module")
def small_spectra():
    from tpulsar.kernels import accel as ak
    bank = ak.build_template_bank(8.0, seg=1 << 10)
    rng = np.random.default_rng(0)
    nd, nb = 6, 4096
    spec = (rng.standard_normal((nd, nb))
            + 1j * rng.standard_normal((nd, nb))).astype(np.complex64)
    return spec, bank


def _accel_run(spec, bank):
    import jax.numpy as jnp

    from tpulsar.kernels import accel as ak
    return ak.accel_search_batch(jnp.asarray(spec), bank,
                                 max_numharm=4, topk=8)


@pytest.fixture
def perdm_path(monkeypatch):
    """Pin the per-DM accel path (the path the faults instrument) and
    clear the process-global batch verdict so the pin is honoured —
    and so the pinned verdict cannot leak into later tests."""
    import tpulsar.kernels.accel as ak
    monkeypatch.setenv("TPULSAR_ACCEL_BATCH", "0")
    monkeypatch.setattr(ak, "_BATCH_OK", None)


def test_all_rows_refused_rescued_bit_identical(small_spectra,
                                                perdm_path):
    """THE acceptance property: 100% refusal of accel row dispatches
    on a CPU run yields results bit-identical to a clean run of the
    same per-DM path — every row host-rescued, zero rows zero-filled,
    and the provenance ledger (not the loss ledger) records it."""
    from tpulsar.search import degraded
    spec, bank = small_spectra
    # per-DM path pinned for the clean comparator: the armed fault
    # pins it for the faulted run anyway, and the batched chunk
    # program's reduction order differs in the last ulp
    degraded.reset()
    clean = _accel_run(spec, bank)

    degraded.reset()
    faults.configure("accel.row_dispatch:unimplemented:rate=1.0")
    faulty = _accel_run(spec, bank)

    for h in clean:
        for a, b in zip(clean[h], faulty[h]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert faults.fired("accel.row_dispatch") > 0
    prov = degraded.provenance_snapshot()
    assert "accel_rows_rescued" in prov
    assert prov["accel_rows_rescued"].startswith("6/6")
    assert "accel_rows_zero_filled" not in degraded.snapshot()
    degraded.reset()


def test_poisoned_session_rescued(small_spectra, perdm_path):
    """A poison fault refuses the first dispatch AND everything after
    (the wedged-chip pattern); the breaker stops hammering it and the
    host rescue still completes the block."""
    from tpulsar.search import degraded
    spec, bank = small_spectra
    degraded.reset()
    clean = _accel_run(spec, bank)
    degraded.reset()
    faults.configure("accel.row_dispatch:poison")
    faulty = _accel_run(spec, bank)
    for h in clean:
        for a, b in zip(clean[h], faulty[h]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert "accel_rows_rescued" in degraded.provenance_snapshot()
    degraded.reset()


def test_hung_dispatch_retried_under_watchdog(small_spectra,
                                              perdm_path,
                                              monkeypatch):
    """One hung row dispatch + the watchdog deadline: the hang becomes
    a classified refusal, the synchronous retry succeeds (count=1
    exhausts the fault), and nothing needs rescue."""
    from tpulsar.search import degraded
    spec, bank = small_spectra
    monkeypatch.setenv("TPULSAR_ACCEL_DISPATCH_DEADLINE_S", "0.05")
    degraded.reset()
    clean = _accel_run(spec, bank)
    degraded.reset()
    faults.configure(
        "accel.row_dispatch:hang:seconds=0.5,count=1")
    faulty = _accel_run(spec, bank)
    for h in clean:
        for a, b in zip(clean[h], faulty[h]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert faults.fired("accel.row_dispatch") == 1
    assert "accel_rows_zero_filled" not in degraded.snapshot()
    degraded.reset()


def test_rescue_off_zero_fills_and_flags(small_spectra, perdm_path,
                                         monkeypatch):
    """TPULSAR_HOST_RESCUE=0 restores the pre-rescue degrade path:
    refused rows zero-fill, the LOSS ledger records them, and the
    whole-block refusal raises AccelStageRefused."""
    from tpulsar.kernels import accel as ak
    from tpulsar.search import degraded
    spec, bank = small_spectra
    monkeypatch.setenv("TPULSAR_HOST_RESCUE", "0")
    degraded.reset()
    faults.configure("accel.row_dispatch:unimplemented:rate=1.0")
    with pytest.raises(ak.AccelStageRefused):
        _accel_run(spec, bank)
    degraded.reset()


@pytest.mark.parametrize("persistent", [False, True])
def test_refused_stacked_hi_accel_degrades_per_beam(
        small_spectra, perdm_path, monkeypatch, persistent):
    """A group's stacked hi-accel dispatch that the runtime refuses is
    split: each beam's rows go down the one-beam ladder alone.  A
    refusal that ends with the stacked call costs nothing (each beam's
    candidates are those of a clean call on its rows); one that
    persists costs each beam its own chunk, loudly, in the ledger."""
    import jax.numpy as jnp

    from tpulsar.kernels import accel as ak
    from tpulsar.search import degraded, executor
    spec, bank = small_spectra           # 6 rows: 2 beams x 3 DMs
    monkeypatch.setitem(executor._BANK_CACHE, 8, bank)
    monkeypatch.setenv("TPULSAR_HOST_RESCUE", "0")
    params = executor.SearchParams(hi_accel_zmax=8, hi_accel_numharm=4,
                                   topk_per_stage=8)
    params.sifting.sigma_threshold = 1.0
    wspec, dms, T_s = jnp.asarray(spec), np.array([10., 12., 14.]), 2.0

    def ident(per_beam):
        return [[(c.r, c.z, c.power, c.numharm, c.dm) for c in cands]
                for cands in per_beam]

    clean = ident([executor._hi_accel_chunk(
        wspec[b * 3:(b + 1) * 3], dms, 1, T_s, params)[0]
        for b in range(2)])
    assert clean[0] and clean[1] and clean[0] != clean[1]
    assert ident(executor._hi_accel_chunk(wspec, dms, 2, T_s,
                                          params)) == clean

    # what the stacked call spends before it gives up
    faults.configure("accel.row_dispatch:unimplemented:rate=1.0")
    with pytest.raises(ak.AccelStageRefused):
        _accel_run(spec, bank)
    spent = faults.fired("accel.row_dispatch")
    assert spent > 0
    faults.configure("accel.row_dispatch:unimplemented:"
                     + ("rate=1.0" if persistent else f"count={spent}"))
    degraded.reset()
    if persistent:
        with pytest.warns(UserWarning, match="hi-accel chunk skipped"):
            out = executor._hi_accel_chunk(wspec, dms, 2, T_s, params)
        assert out == [[], []]
        lost = degraded.snapshot()["accel_hi_chunk_skipped"]
        assert lost.startswith("6/6 across 2 call(s)"), lost
    else:
        out = executor._hi_accel_chunk(wspec, dms, 2, T_s, params)
        assert faults.fired("accel.row_dispatch") == spent
        assert ident(out) == clean
        assert "accel_hi_chunk_skipped" not in degraded.snapshot()
    degraded.reset()


# ------------------------------------- dedisperse fault point (CPU)

def test_dedisperse_pallas_fault_falls_back():
    import jax.numpy as jnp

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.search import degraded
    rng = np.random.default_rng(3)
    subb = jnp.asarray(rng.standard_normal((8, 512)).astype(np.float32))
    shifts = jnp.asarray((np.arange(4)[:, None]
                          * np.ones((1, 8))).astype(np.int32))
    degraded.reset()
    clean = np.asarray(dd.dedisperse_subbands(subb, shifts))
    faults.configure("dedisperse.pallas:unimplemented:count=1")
    degraded.reset()
    out = np.asarray(dd.dedisperse_subbands(subb, shifts))
    assert np.array_equal(clean, out)      # XLA fallback, same science
    assert "pallas_dd_disabled" in degraded.snapshot()
    assert faults.fired("dedisperse.pallas") == 1
    degraded.reset()


def test_dedisperse_pallas_fault_is_loud_on_a_tpu_backend(monkeypatch):
    """On a TPU backend a kernel fault fails the beam instead of
    completing it on the XLA path, and nothing is disabled."""
    import jax.numpy as jnp

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.kernels import pallas_dd
    from tpulsar.search import degraded
    monkeypatch.setattr(pallas_dd, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(pallas_dd, "use_pallas", lambda: False)
    subb = jnp.zeros((8, 512), jnp.float32)
    shifts = jnp.zeros((4, 8), jnp.int32)
    faults.configure("dedisperse.pallas:unimplemented:count=1")
    degraded.reset()
    disabled = dict(pallas_dd._DISABLED_SIGS)
    with pytest.raises(Exception, match="dedisperse.pallas"):
        dd.dedisperse_subbands(subb, shifts)
    assert "pallas_dd_disabled" not in degraded.snapshot()
    assert pallas_dd._DISABLED_SIGS == disabled
    degraded.reset()


# ----------------------------- orchestrate fault points + policy routes

def test_downloader_transfer_fault_exercises_retry_ledger(tmp_path):
    """An injected transport failure takes the real failed ->
    retrying -> terminal_failure route, fully recorded in the
    download_attempts audit table."""
    from tpulsar.orchestrate.downloader import Downloader, LocalTransport
    from tpulsar.orchestrate.jobtracker import JobTracker

    remote = tmp_path / "remote" / "r1"
    remote.mkdir(parents=True)
    (remote / "beam0.fits").write_bytes(b"x" * 64)
    t = JobTracker(str(tmp_path / "jobs.db"))
    dl = Downloader(t, restore_service=None,
                    transport=LocalTransport(str(tmp_path / "remote")),
                    datadir=str(tmp_path / "data"), numretries=2)
    rid = t.insert("requests", guid="r1", numrequested=1, numbits=4,
                   file_type="mock", status="waiting", details="")
    assert dl.create_file_entries({"id": rid, "guid": "r1"}) == 1

    faults.configure("download.transfer:unimplemented")   # always fail
    for _ in range(4):
        dl.start_downloads()
        for th in dl._threads.values():
            th.join(5.0)
        dl.verify_files()
        dl.recover_failed_downloads()
    row = t.query("SELECT status FROM files", fetchone=True)
    assert row["status"] == "terminal_failure"
    assert t.count("download_attempts") == 2   # policy bound, not 4


def test_jobtracker_lock_retry_routes_through_policy(monkeypatch,
                                                     tmp_path):
    """The sqlite lock-contention loop is the shared primitive now:
    bounded attempts, then the real error surfaces."""
    from tpulsar.orchestrate import jobtracker as jt

    t = jt.JobTracker(str(tmp_path / "jobs.db"))
    calls = []

    def always_locked():
        calls.append(1)
        raise sqlite3.OperationalError("database is locked")

    monkeypatch.setattr(
        jt.JobTracker, "RETRY_POLICY",
        policy.RetryPolicy(
            max_attempts=3,
            retry_on=(sqlite3.OperationalError,),
            retryable=jt.JobTracker.RETRY_POLICY.retryable))
    monkeypatch.setattr(time, "sleep", lambda s: None)
    with pytest.raises(sqlite3.OperationalError):
        t._with_retries(always_locked)
    assert len(calls) == 3


def test_pool_submit_fault_defers_job(tmp_path):
    """queue.submit injection exercises the defer tier: the job stays
    queued and the next rotate resubmits it."""
    from tpulsar.orchestrate.jobtracker import JobTracker
    from tpulsar.orchestrate.pool import JobPool

    class NeverCalled:
        def can_submit(self):
            return True

        def submit(self, fns, outdir, job_id):   # pragma: no cover
            raise AssertionError("fault should fire first")

    t = JobTracker(str(tmp_path / "jobs.db"))
    pool = JobPool(t, NeverCalled(), str(tmp_path / "results"))
    job_id = t.insert("jobs", status="new", details="")
    faults.configure("queue.submit:unimplemented")
    pool.submit(job_id)
    row = t.query("SELECT status FROM jobs WHERE id=?", [job_id],
                  fetchone=True)
    assert row["status"] == "new"              # deferred, not failed
    assert t.count("job_submits") == 0


def test_uploader_deadlock_replays_transaction(tmp_path):
    """Writer contention replays the one-beam transaction in process
    (bounded by the shared policy) instead of waiting a full daemon
    cycle; the rollback between attempts keeps it all-or-nothing."""
    from tpulsar.orchestrate import uploader as up
    from tpulsar.orchestrate.results_db import DatabaseDeadlockError

    attempts, rollbacks = [], []

    def txn():
        attempts.append(1)
        if len(attempts) < 3:
            raise DatabaseDeadlockError("deadlock")

    policy.call(txn, up.DEADLOCK_RETRY, sleeper=lambda s: None,
                on_retry=lambda k, e: rollbacks.append(k))
    assert len(attempts) == 3 and rollbacks == [0, 1]
    assert up.DEADLOCK_RETRY.max_attempts == 3


def test_moab_lost_msub_recovery_via_policy(tmp_path):
    """The constant-wait recovery loop (lost msub reply, recover by
    job name) now runs through the shared primitive with the same
    bound and the same injected sleeper."""
    from tpulsar.orchestrate.queue_managers.moab import MoabManager

    class R:
        def __init__(self, out="", err=""):
            self.stdout, self.stderr = out, err
            self.returncode = 0

    showq_ok = R(out='<queue-root><queue option="active">'
                     '<job JobID="77" JobName="tpulsar5" State="Running"/>'
                     '</queue></queue-root>')
    replies = [R(err="COMMUNICATION ERROR: lost reply"),   # msub
               R(err="communication error"),               # showq 1
               showq_ok]                                   # showq 2
    sleeps = []
    qm = MoabManager(script="/bin/true", comm_retry_limit=5,
                     retry_wait_s=7.0,
                     runner=lambda cmd, **kw: (replies.pop(0) if replies
                                               else showq_ok),
                     sleeper=sleeps.append)
    qid = qm.submit([], str(tmp_path / "moab_out"), 5)
    assert qid == "77"
    assert sleeps == [7.0, 7.0]       # delay_first + one retry wait


# ------------------------------------------ executor end-to-end (CPU)

@pytest.mark.slow
def test_beam_with_total_accel_refusal_matches_clean(tmp_path,
                                                     monkeypatch):
    """Acceptance criterion end-to-end: a full CPU beam search with
    TPULSAR_FAULTS refusing 100% of accel row dispatches produces the
    same candidate list as the fault-free run, and search_params.txt
    records accel_rows_rescued provenance with NO loss flag."""
    from tpulsar.io import accelcands, synth
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    spec = synth.BeamSpec(nchan=24, nsamp=1 << 13, nbits=4,
                          tsamp_s=5.24288e-4)
    psr = synth.PulsarSpec(period_s=0.15, dm=6.0,
                           snr_per_sample=0.5, width_frac=0.05)
    fns = synth.synth_beam(str(tmp_path / "beam"), spec,
                           pulsars=[psr], merged=True)
    plan = [ddplan.DedispStep(lodm=0.0, dmstep=2.0, dms_per_pass=8,
                              numpasses=1, numsub=24, downsamp=1)]
    params = executor.SearchParams(nsub=24, hi_accel_zmax=8,
                                   topk_per_stage=8,
                                   max_cands_to_fold=0,
                                   make_plots=False)

    clean = executor.search_beam(fns, str(tmp_path / "w0"),
                                 str(tmp_path / "r0"), params=params,
                                 plan=plan)
    faults.configure("accel.row_dispatch:unimplemented:rate=1.0")
    rescued = executor.search_beam(fns, str(tmp_path / "w1"),
                                   str(tmp_path / "r1"), params=params,
                                   plan=plan)
    faults.reset()

    c0 = accelcands.parse_candlist(
        os.path.join(clean.resultsdir, f"{clean.basenm}.accelcands"))
    c1 = accelcands.parse_candlist(
        os.path.join(rescued.resultsdir,
                     f"{rescued.basenm}.accelcands"))
    assert len(c0) == len(c1) and len(c1) > 0
    for a, b in zip(c0, c1):
        assert (a.dm, a.numharm) == (b.dm, b.numharm)
        assert a.r == pytest.approx(b.r, rel=1e-9)
        assert a.z == pytest.approx(b.z, rel=1e-9)
        # powers may differ in the last ulp between the clean run's
        # batched/native program and the rescued rows' row program
        assert a.power == pytest.approx(b.power, rel=1e-5)
        assert a.sigma == pytest.approx(b.sigma, rel=1e-4)

    ns: dict = {}
    exec(open(os.path.join(rescued.resultsdir,
                           "search_params.txt")).read(), {}, ns)
    assert "accel_rows_rescued" in ns["rescued_modes"]
    assert "accel_rows_zero_filled" not in ns["degraded_modes"]
    assert "accel_hi_chunk_skipped" not in ns["degraded_modes"]
    rep = open(os.path.join(rescued.resultsdir,
                            f"{rescued.basenm}.report")).read()
    assert "Rescued work" in rep and "accel_rows_rescued" in rep
    # the clean run's artifacts carry NO rescue section
    ns0: dict = {}
    exec(open(os.path.join(clean.resultsdir,
                           "search_params.txt")).read(), {}, ns0)
    assert ns0["rescued_modes"] == {}
