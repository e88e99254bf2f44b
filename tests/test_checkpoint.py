"""Checkpointed beam search: pass-level crash resume with
checksummed artifact manifests.

Covers the tpulsar/checkpoint/ store contract (atomic writes, sha256
verification, torn/stale/mismatched manifests, ENOSPC degradation,
the checkpoint.write/load fault points), executor resume parity
(kill after pass k => resumed candidates identical to the golden
uninterrupted run), the fleet quarantine-fairness rule (checkpoint
progress resets the crash-loop budget), the chaos stub worker's
crash-after-pass resume e2e, and verifier mutation cases for the
resume_consistent / no_pass_rerun invariants.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from tpulsar import checkpoint as ckpt
from tpulsar.chaos import invariants
from tpulsar.chaos import worker as cworker
from tpulsar.checkpoint import hashing
from tpulsar.obs import journal
from tpulsar.resilience import faults
from tpulsar.serve import protocol


@pytest.fixture(autouse=True)
def _no_fault_leak():
    faults.reset()
    yield
    faults.reset()


class _Journal:
    """Captures a store's journal callback events."""

    def __init__(self):
        self.events: list[tuple[str, dict]] = []

    def __call__(self, event, **extra):
        self.events.append((event, extra))

    def names(self):
        return [e for e, _ in self.events]

    def of(self, name):
        return [kw for e, kw in self.events if e == name]


# ------------------------------------------------------------- store

def test_store_roundtrip_and_manifest(tmp_path):
    root = str(tmp_path / "ck")
    store = ckpt.CheckpointStore(root, "fp-1")
    assert store.save("pass_0000", b"alpha", kind="pass", pass_idx=0)
    assert store.save("rfi_mask", b"beta", kind="stage", ext=".npz")
    # the manifest carries schema + fingerprint + per-entry sha256
    doc = ckpt.read_manifest(root)
    assert doc["schema"] == ckpt.SCHEMA
    assert doc["fingerprint"] == "fp-1"
    ent = doc["entries"]["pass_0000"]
    assert ent["bytes"] == 5
    assert ent["sha256"] == hashing.sha256_bytes(b"alpha")
    assert ent["kind"] == "pass"
    # a re-opened store loads + verifies
    store2 = ckpt.CheckpointStore(root, "fp-1")
    assert store2.load("pass_0000") == b"alpha"
    assert store2.load("rfi_mask") == b"beta"
    assert store2.load("missing") is None
    assert set(store2.entries(kind="pass")) == {"pass_0000"}
    # no tmp litter after clean writes
    assert not [n for n in os.listdir(root) if n.endswith(".tmp")]


def test_overwritten_key_survives_a_kill_between_its_two_writes(
        tmp_path, monkeypatch):
    """The stream worker saves its carry under ONE key after every
    chunk.  A writer killed after the artifact and before the manifest
    must leave the previous checkpoint loadable (the manifest's hash
    still matches the file it names), not discard it as corrupt."""
    root = str(tmp_path / "ck")
    store = ckpt.CheckpointStore(root, "fp")
    assert store.save("stream_carry", b"after chunk 0", ext=".npz")
    assert store.save("stream_carry", b"after chunk 1", ext=".npz")

    class Killed(BaseException):
        pass

    def die():
        raise Killed

    monkeypatch.setattr(store, "_write_manifest", die)
    with pytest.raises(Killed):
        store.save("stream_carry", b"after chunk 2", ext=".npz")
    j = _Journal()
    again = ckpt.CheckpointStore(root, "fp", journal=j)
    assert again.load("stream_carry") == b"after chunk 1"
    assert not j.of("checkpoint_invalid")
    # the two names take turns: never more than two files for the key
    assert again.save("stream_carry", b"after chunk 2", ext=".npz")
    assert again.save("stream_carry", b"after chunk 3", ext=".npz")
    assert ckpt.CheckpointStore(root, "fp").load("stream_carry") == \
        b"after chunk 3"
    assert sorted(n for n in os.listdir(root) if "carry" in n) == [
        "stream_carry.alt.npz", "stream_carry.npz"]
    assert ckpt.verify_root(root)["ok"]


def test_corrupt_artifact_discarded_and_journaled(tmp_path):
    root = str(tmp_path / "ck")
    j = _Journal()
    store = ckpt.CheckpointStore(root, "fp", journal=j)
    store.save("pass_0000", b"payload")
    # flip bytes on disk: the sha256 check must refuse the entry
    path = os.path.join(root, "pass_0000.bin")
    with open(path, "wb") as fh:
        fh.write(b"garbage")         # same length: the sha must catch it
    store2 = ckpt.CheckpointStore(root, "fp", journal=j)
    assert store2.load("pass_0000") is None
    assert not store2.has("pass_0000")       # discarded, recompute
    bad = j.of("checkpoint_invalid")
    assert bad and bad[-1]["key"] == "pass_0000"
    assert "mismatch" in bad[-1]["reason"]
    # and the discard is durable: a THIRD open no longer lists it
    assert "pass_0000" not in ckpt.CheckpointStore(root, "fp").entries()


def test_torn_manifest_wipes_and_recomputes(tmp_path):
    root = str(tmp_path / "ck")
    store = ckpt.CheckpointStore(root, "fp")
    store.save("pass_0000", b"x")
    with open(ckpt.manifest_path(root), "w") as fh:
        fh.write('{"schema": "tpulsar-checkpo')      # torn mid-write
    j = _Journal()
    store2 = ckpt.CheckpointStore(root, "fp", journal=j)
    assert store2.entries() == {}
    assert j.of("checkpoint_invalid")[0]["scope"] == "manifest"
    # the dir is fresh + writable again
    assert store2.save("pass_0000", b"y")
    assert store2.load("pass_0000") == b"y"


def test_stale_schema_manifest_rejected(tmp_path):
    root = str(tmp_path / "ck")
    store = ckpt.CheckpointStore(root, "fp")
    store.save("pass_0000", b"x")
    doc = json.load(open(ckpt.manifest_path(root)))
    doc["schema"] = "tpulsar-checkpoint/0"
    json.dump(doc, open(ckpt.manifest_path(root), "w"))
    j = _Journal()
    store2 = ckpt.CheckpointStore(root, "fp", journal=j)
    assert store2.entries() == {}            # old-schema dumps unused
    assert "checkpoint_invalid" in j.names()


def test_fingerprint_mismatch_wipes(tmp_path):
    root = str(tmp_path / "ck")
    ckpt.CheckpointStore(root, "fp-A").save("pass_0000", b"x")
    store = ckpt.CheckpointStore(root, "fp-B")
    assert store.entries() == {}
    assert ckpt.read_manifest(root)["fingerprint"] == "fp-B"


def test_tmp_litter_swept_at_open(tmp_path):
    root = str(tmp_path / "ck")
    ckpt.CheckpointStore(root, "fp").save("pass_0000", b"x")
    litter = os.path.join(root, "pass_0001.bin.1234.tmp")
    with open(litter, "wb") as fh:
        fh.write(b"partial")
    ckpt.CheckpointStore(root, "fp")
    assert not os.path.exists(litter)


def test_enospc_disables_store_for_the_beam(tmp_path):
    root = str(tmp_path / "ck")
    j = _Journal()
    store = ckpt.CheckpointStore(root, "fp", journal=j)
    assert store.save("pass_0000", b"x")
    faults.configure("checkpoint.write:unimplemented:errno=ENOSPC")
    assert not store.save("pass_0001", b"y")
    assert store.disabled
    assert "checkpoint_disabled" in j.names()
    faults.reset()
    # disabled is sticky for the rest of the beam — even after the
    # volume 'recovers', no further writes are attempted
    assert not store.save("pass_0002", b"z")
    assert "pass_0001" not in store.entries()
    # the pre-failure artifact is still intact for the NEXT attempt
    assert ckpt.CheckpointStore(root, "fp").load("pass_0000") == b"x"


def test_transient_eio_skips_one_artifact_only(tmp_path):
    root = str(tmp_path / "ck")
    j = _Journal()
    store = ckpt.CheckpointStore(root, "fp", journal=j)
    faults.configure("checkpoint.write:unimplemented:count=1")
    assert not store.save("pass_0000", b"x")     # EIO-shaped default
    assert not store.disabled
    assert "checkpoint_write_failed" in j.names()
    assert store.save("pass_0001", b"y")         # later writes fine


def test_load_fault_treated_as_corruption(tmp_path):
    root = str(tmp_path / "ck")
    j = _Journal()
    store = ckpt.CheckpointStore(root, "fp", journal=j)
    store.save("pass_0000", b"x")
    faults.configure("checkpoint.load:unimplemented:count=1")
    assert store.load("pass_0000") is None       # discard + recompute
    assert j.of("checkpoint_invalid")[-1]["key"] == "pass_0000"


def test_verify_root_and_progress_marker(tmp_path):
    root = str(tmp_path / "ck")
    assert ckpt.progress_marker(root) == -1      # no manifest at all
    store = ckpt.CheckpointStore(root, "fp")
    assert ckpt.progress_marker(root) == 0
    store.save("pass_0000", b"a")
    store.save("pass_0001", b"b")
    assert ckpt.progress_marker(root) == 2
    rep = ckpt.verify_root(root)
    assert rep["ok"] and len(rep["entries"]) == 2
    with open(os.path.join(root, "pass_0001.bin"), "wb") as fh:
        fh.write(b"corrupt")
    rep = ckpt.verify_root(root)
    assert not rep["ok"]
    bad = [e for e in rep["entries"] if not e["ok"]]
    assert [e["key"] for e in bad] == ["pass_0001"]


def test_shared_sha256_helper(tmp_path):
    p = tmp_path / "blob"
    p.write_bytes(b"the one hashing helper")
    assert hashing.sha256_file(str(p)) \
        == hashing.sha256_bytes(b"the one hashing helper")


# ---------------------------------------------------- executor parity

def _small_beam():
    import jax.numpy as jnp
    from tpulsar.plan.ddplan import DedispStep
    rng = np.random.default_rng(21)
    data = jnp.asarray(
        rng.integers(0, 16, size=(24, 4096), dtype=np.uint8))
    freqs = 1214.2 + (np.arange(24) + 0.5) * (322.6 / 24)
    plan = [DedispStep(0.0, 1.0, 8, 2, 12, 1),
            DedispStep(16.0, 2.0, 8, 1, 12, 2)]   # 3 passes total
    return data, freqs, plan


def _ckey(c):
    return (c.r, c.z, c.sigma, c.power, c.numharm, c.dm, c.period_s,
            c.freq_hz, tuple(c.dm_hits))


def _truncate_to(ckdir: str, keep_passes: int) -> None:
    """Simulate a crash after pass ``keep_passes - 1``: drop every
    later pass artifact plus the downstream sifted/fold artifacts,
    exactly the state a SIGKILL mid-plan-loop leaves behind."""
    man_path = ckpt.manifest_path(ckdir)
    doc = json.load(open(man_path))
    for key in list(doc["entries"]):
        drop = (key == "sifted" or key.startswith("fold_")
                or (key.startswith("pass_")
                    and int(key[len("pass_"):]) >= keep_passes))
        if drop:
            os.unlink(os.path.join(ckdir, doc["entries"][key]["file"]))
            del doc["entries"][key]
    json.dump(doc, open(man_path, "w"))


@pytest.mark.parametrize("keep", [0, 1, 2])
def test_resume_parity_after_kill_at_pass_k(tmp_path, keep):
    """Kill after pass k => resumed candidates IDENTICAL (every field,
    including the DM-hit history) to the golden uninterrupted run,
    for k in {0, mid, last}."""
    from tpulsar.search import executor

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(run_hi_accel=False,
                                   max_cands_to_fold=0,
                                   make_plots=False)
    gold_c, _, gold_sp, gold_n = executor.search_block(
        data, freqs, 65e-6, plan, params)

    ck = str(tmp_path / f"ck{keep}")
    executor.search_block(data, freqs, 65e-6, plan, params,
                          checkpoint_dir=ck)
    _truncate_to(ck, keep)
    j = _Journal()
    res_c, _, res_sp, res_n = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    assert res_n == gold_n
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    assert np.array_equal(res_sp, gold_sp)
    # the journal shows the resume AND that only the missing tail of
    # passes was recomputed
    recomputed = [kw["pass_idx"] for kw in j.of("pass_complete")]
    assert recomputed == list(range(keep, 3))
    assert ("resume" in j.names()) == (keep > 0)


def test_resume_parity_after_torn_manifest(tmp_path):
    from tpulsar.search import executor

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(run_hi_accel=False,
                                   max_cands_to_fold=0,
                                   make_plots=False)
    gold_c, _, _, _ = executor.search_block(data, freqs, 65e-6, plan,
                                            params)
    ck = str(tmp_path / "ck")
    executor.search_block(data, freqs, 65e-6, plan, params,
                          checkpoint_dir=ck)
    with open(ckpt.manifest_path(ck), "w") as fh:
        fh.write("{torn")
    j = _Journal()
    res_c, _, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    assert j.of("checkpoint_invalid")[0]["scope"] == "manifest"
    assert "resume" not in j.names()         # nothing was resumable


def test_enospc_mid_search_finishes_unckeckpointed(tmp_path):
    """A sick checkpoint volume must never fail a healthy beam: the
    search completes with identical science, checkpointing disabled
    for the rest of the beam and the degradation journaled."""
    from tpulsar.search import executor

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(run_hi_accel=False,
                                   max_cands_to_fold=0,
                                   make_plots=False)
    gold_c, _, _, _ = executor.search_block(data, freqs, 65e-6, plan,
                                            params)
    ck = str(tmp_path / "ck")
    # first write (pass 0) lands; the second hits ENOSPC
    faults.configure(
        "checkpoint.write:unimplemented:errno=ENOSPC,after=1")
    j = _Journal()
    res_c, _, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    faults.reset()
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    assert "checkpoint_disabled" in j.names()
    # only the pre-failure pass is journaled durable
    assert [kw["pass_idx"] for kw in j.of("pass_complete")] == [0]


def test_sifted_and_fold_artifacts_resume(tmp_path):
    """A crash during folding resumes past the whole plan loop via
    the 'sifted' artifact and re-folds only the missing candidate."""
    from tpulsar.search import executor, sifting

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(
        run_hi_accel=False, make_plots=False, refine_cands=False,
        to_prepfold_sigma=0.0, max_cands_to_fold=2,
        fold_by_rules=False, fold_batched=False,
        # loosened sift: pure-noise inputs must still yield fold-worthy
        # candidates for the fold-artifact resume to exercise
        sifting=sifting.SiftParams(sigma_threshold=2.0,
                                   min_num_dms=1))
    ck = str(tmp_path / "ck")
    gold_c, gold_f, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck)
    assert len(gold_f) == 2
    doc = json.load(open(ckpt.manifest_path(ck)))
    assert "sifted" in doc["entries"]
    assert {"fold_0000", "fold_0001"} <= set(doc["entries"])
    # drop fold_0001: the resumed run must re-fold ONLY candidate 1
    os.unlink(os.path.join(ck, doc["entries"]["fold_0001"]["file"]))
    del doc["entries"]["fold_0001"]
    json.dump(doc, open(ckpt.manifest_path(ck), "w"))
    j = _Journal()
    res_c, res_f, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    assert len(res_f) == 2
    for a, b in zip(res_f, gold_f):
        assert np.array_equal(a.profile, b.profile)
        assert np.array_equal(a.subints, b.subints)
        assert a.reduced_chi2 == b.reduced_chi2
    # sifted short-circuit: no pass was recomputed or re-journaled
    assert j.of("pass_complete") == []
    assert "resume" in j.names()


def test_undecodable_pass_payload_discarded_with_excuse(tmp_path):
    """A payload whose bytes verify but whose layout no longer
    decodes must be discarded THROUGH the store (journaling the
    checkpoint_invalid excuse) — a silent recompute would journal a
    duplicate pass_complete and trip no_pass_rerun on a healthy
    beam."""
    from tpulsar.search import executor

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(run_hi_accel=False,
                                   max_cands_to_fold=0,
                                   make_plots=False)
    gold_c, _, _, _ = executor.search_block(data, freqs, 65e-6, plan,
                                            params)
    ck = str(tmp_path / "ck")
    executor.search_block(data, freqs, 65e-6, plan, params,
                          checkpoint_dir=ck)
    fp = ckpt.read_manifest(ck)["fingerprint"]
    store = ckpt.CheckpointStore(ck, fp)
    store.save("pass_0001", b"sha-valid but not an npz",
               kind="pass", ext=".npz")
    # downstream artifacts of the 'crash' are gone too
    store.discard("sifted", reason="test")
    j = _Journal()
    res_c, _, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    bad = [kw for kw in j.of("checkpoint_invalid")
           if kw.get("key") == "pass_0001"]
    assert bad and "undecodable" in bad[0]["reason"]
    assert [kw["pass_idx"] for kw in j.of("pass_complete")] == [1]


def test_stale_fold_artifact_identity_mismatch_discarded(tmp_path):
    """fold_NNNN artifacts are keyed by position: one bound to a
    different candidate's identity (the sifted list regenerated
    between attempts) must be discarded and re-folded, never
    attributed to candidate k."""
    from tpulsar.search import executor, sifting

    data, freqs, plan = _small_beam()
    params = executor.SearchParams(
        run_hi_accel=False, make_plots=False, refine_cands=False,
        to_prepfold_sigma=0.0, max_cands_to_fold=2,
        fold_by_rules=False, fold_batched=False,
        sifting=sifting.SiftParams(sigma_threshold=2.0,
                                   min_num_dms=1))
    ck = str(tmp_path / "ck")
    gold_c, gold_f, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck)
    # rebind fold_0000 to a candidate that does not exist: sha-valid,
    # decodable, wrong identity
    import types
    fp = ckpt.read_manifest(ck)["fingerprint"]
    store = ckpt.CheckpointStore(ck, fp)
    res, _ident = executor._decode_fold(store.load("fold_0000"))
    ghost = types.SimpleNamespace(period_s=123.456, dm=7.0)
    store.save("fold_0000", executor._encode_fold(res, ghost),
               kind="fold", ext=".npz")
    j = _Journal()
    res_c, res_f, _, _ = executor.search_block(
        data, freqs, 65e-6, plan, params, checkpoint_dir=ck,
        checkpoint_journal=j)
    assert [_ckey(c) for c in res_c] == [_ckey(c) for c in gold_c]
    for a, b in zip(res_f, gold_f):
        assert np.array_equal(a.profile, b.profile)
    bad = [kw for kw in j.of("checkpoint_invalid")
           if kw.get("key") == "fold_0000"]
    assert bad and "identity" in bad[0]["reason"]


# ----------------------------------------------- quarantine fairness

def _dead_pid() -> int:
    p = subprocess.Popen(["true"])
    p.wait()
    return p.pid


def _crash_claim(spool: str, tid: str) -> None:
    """Claim the ticket then forge a dead owner: the next janitor
    scan judges it a crash strike."""
    rec = protocol.claim_next_ticket(spool, "wX")
    assert rec is not None and rec["ticket"] == tid
    path = protocol.ticket_path(spool, tid, "claimed")
    data = json.load(open(path))
    data["claimed_by"] = _dead_pid()
    protocol._atomic_write_json(path, data)


def test_quarantine_fairness_progress_resets_budget(tmp_path):
    """A beam whose checkpoint advances between crashes is being
    PREEMPTED, not crash-looping: it must survive past max_attempts
    (attempts stay monotone for the journal contract) — and the
    moment progress stalls, the cap applies again."""
    spool = str(tmp_path / "spool")
    outdir = str(tmp_path / "out")
    protocol.write_ticket(spool, "b1", ["/x"], outdir)
    store = ckpt.CheckpointStore(ckpt.default_root(outdir), "fp")
    cap = 2
    for i in range(4):          # 4 strikes, each with fresh progress
        store.save(f"pass_{i:04d}", bytes([i]), kind="pass")
        _crash_claim(spool, "b1")
        assert protocol.requeue_stale_claims(spool, cap) == ["b1"], i
    rec = json.load(open(protocol.ticket_path(spool, "b1",
                                              "incoming")))
    assert rec["attempts"] == 4          # monotone, never reset
    assert rec["ckpt_progress"] == 4
    # progress stalls: cap strikes later the beam quarantines
    _crash_claim(spool, "b1")
    assert protocol.requeue_stale_claims(spool, cap) == ["b1"]
    _crash_claim(spool, "b1")
    assert protocol.requeue_stale_claims(spool, cap) == []
    assert protocol.list_tickets(spool, "quarantine") == ["b1"]
    done = protocol.read_result(spool, "b1")
    assert done is not None and done["status"] == "failed"
    # quarantine removed the (now useless) resume state + any litter
    assert not os.path.exists(ckpt.default_root(outdir))
    # the journal carries the fairness evidence
    evs = journal.read_events(spool, ticket="b1")
    resets = [e for e in evs if e.get("event") == "takeover"
              and e.get("budget_reset")]
    assert len(resets) == 4
    assert journal.validate_chain(evs) == [], evs


def test_empty_checkpoint_store_is_not_progress(tmp_path):
    """A just-opened store (manifest, zero artifacts) must not reset
    the crash-loop budget: a beam that kills its worker at search
    start still quarantines at exactly max_attempts."""
    spool = str(tmp_path / "spool")
    outdir = str(tmp_path / "out")
    protocol.write_ticket(spool, "b1", ["/x"], outdir)
    ckpt.CheckpointStore(ckpt.default_root(outdir), "fp")
    for _ in range(2):
        _crash_claim(spool, "b1")
        assert protocol.requeue_stale_claims(spool, 3) == ["b1"]
    _crash_claim(spool, "b1")
    assert protocol.requeue_stale_claims(spool, 3) == []
    assert protocol.list_tickets(spool, "quarantine") == ["b1"]


def test_quarantine_unchanged_without_checkpoints(tmp_path):
    """No manifest => exactly the pre-fairness behaviour: quarantine
    at max_attempts crash strikes."""
    spool = str(tmp_path / "spool")
    protocol.write_ticket(spool, "b1", ["/x"],
                          str(tmp_path / "out"))
    for _ in range(2):
        _crash_claim(spool, "b1")
        protocol.requeue_stale_claims(spool, 3)
    _crash_claim(spool, "b1")
    assert protocol.requeue_stale_claims(spool, 3) == []
    assert protocol.list_tickets(spool, "quarantine") == ["b1"]


# ------------------------------------------------ verifier mutations

def _resume_chain(spool, tid, npasses=4, digest=None, dup_pass=None,
                  excuse=None):
    """A crash-and-resume chain: attempt 0 completes half the passes,
    dies, a takeover hands the beam to attempt 1 which resumes and
    finishes.  ``dup_pass`` re-journals that pass on attempt 1 (the
    no_pass_rerun mutation); ``excuse`` injects the named event
    before the duplicate."""
    trace = f"tr-{tid}"
    outdir = os.path.join(spool, "outs", tid)

    def j(event, attempt, **kw):
        journal.record(spool, event, ticket=tid, worker="w0",
                       attempt=attempt, trace_id=trace, **kw)

    journal.record(spool, "submitted", ticket=tid, attempt=0,
                   trace_id=trace, outdir=outdir)
    j("claimed", 0)
    j("search_start", 0)
    half = npasses // 2
    for k in range(half):
        j("pass_complete", 0, pass_idx=k, npasses=npasses)
    j("takeover", 1, from_worker="w0")
    j("claimed", 1)
    j("search_start", 1)
    j("resume", 1, passes_done=half, npasses=npasses,
      salvaged_s=half * 0.1)
    if excuse == "invalid":
        j("checkpoint_invalid", 1, scope="entry",
          key=f"pass_{dup_pass:04d}", reason="sha256 mismatch")
    elif excuse == "disabled":
        j("checkpoint_disabled", 1, key="manifest", errno=28)
    if dup_pass is not None:
        j("pass_complete", 1, pass_idx=dup_pass, npasses=npasses)
    for k in range(half, npasses):
        j("pass_complete", 1, pass_idx=k, npasses=npasses)
    j("result", 1, status="done", rc=0)
    protocol.ensure_spool(spool)
    protocol._atomic_write_json(
        protocol.ticket_path(spool, tid, "done"),
        {"ticket": tid, "status": "done", "finished_at": time.time(),
         "trace_id": trace, "passes": npasses,
         "candidates_digest": (digest if digest is not None
                               else cworker.expected_digest(
                                   tid, npasses))})


def _named(spool, **kw):
    report = invariants.verify(spool, **kw)
    return {name for name, n in report["invariants"].items() if n}


def test_clean_resume_chain_passes_new_invariants(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a")
    report = invariants.verify(spool)
    assert report["ok"], report["violations"]
    assert report["checked"]["resumes"] == 1


def test_verifier_names_no_pass_rerun(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a", dup_pass=1)
    assert "no_pass_rerun" in _named(spool)


def test_checkpoint_invalid_excuses_exactly_that_pass(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a", dup_pass=1, excuse="invalid")
    report = invariants.verify(spool)
    assert report["ok"], report["violations"]
    # ...but the excuse names ONE pass: re-running a DIFFERENT one
    # is still a violation
    spool2 = str(tmp_path / "spool2")
    _resume_chain(spool2, "b", dup_pass=0, excuse=None)
    assert "no_pass_rerun" in _named(spool2)


def test_checkpoint_disabled_excuses_reruns(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a", dup_pass=1, excuse="disabled")
    report = invariants.verify(spool)
    assert report["ok"], report["violations"]


def test_verifier_names_resume_consistent(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a", digest="deadbeef" * 8)
    named = _named(spool)
    assert "resume_consistent" in named


def test_checkpoint_tmp_litter_named_orphan(tmp_path):
    spool = str(tmp_path / "spool")
    _resume_chain(spool, "a")
    root = ckpt.default_root(os.path.join(spool, "outs", "a"))
    os.makedirs(root, exist_ok=True)
    litter = os.path.join(root, "pass_0002.bin.999.tmp")
    with open(litter, "wb") as fh:
        fh.write(b"partial")
    assert "no_orphan_sidefiles" in _named(spool)
    os.unlink(litter)
    report = invariants.verify(spool)
    assert report["ok"], report["violations"]


# ----------------------------------------------- serve-path plumbing

def test_run_search_threads_journal_and_cleans(tmp_path, monkeypatch):
    """The serve worker resumes through search_job.run_search: the
    checkpoint dir is the outdir's (so a reclaimed ticket resumes on
    whichever worker steals it), the journal hook reaches the
    executor, and resume state is disposed only after results are
    durable."""
    import types

    from tpulsar.cli import search_job
    from tpulsar.search import executor as ex

    seen = {}

    def fake_search_beam(ppfns, workdir, resultsdir, params=None,
                         zaplist=None, checkpoint_dir=None,
                         checkpoint_journal=None, **kw):
        seen["ckdir"] = checkpoint_dir
        checkpoint_journal("resume", passes_done=2)
        os.makedirs(resultsdir, exist_ok=True)
        os.makedirs(checkpoint_dir, exist_ok=True)
        with open(os.path.join(checkpoint_dir, "pass.tmp"), "w"):
            pass
        with open(os.path.join(resultsdir, "b.report"), "w"):
            pass
        return types.SimpleNamespace(resultsdir=resultsdir,
                                     candidates=[], num_dm_trials=0)

    monkeypatch.setattr(ex, "search_beam", fake_search_beam)
    events = []
    out = str(tmp_path / "out")
    search_job.run_search(
        ["f"], str(tmp_path / "wk"), out, None, None,
        log=lambda m: None,
        journal=lambda e, **kw: events.append(e))
    assert seen["ckdir"] == ckpt.default_root(out)
    assert events == ["resume"]
    assert os.path.exists(os.path.join(out, "b.report"))
    # resume state (tmp litter included) gone once results are durable
    assert not os.path.exists(ckpt.default_root(out))


# --------------------------------------------- chaos stub worker e2e

_WORKER = [sys.executable, "-m", "tpulsar.chaos.worker"]


def test_worker_crash_after_pass_then_resume(tmp_path):
    """Deterministic kill-mid-beam: the stub worker dies after
    computing 3 of 6 passes, the janitor steals the claim, a second
    run resumes from the manifest and finishes with the digest of an
    uninterrupted run — audited end to end by the verifier."""
    spool = str(tmp_path / "spool")
    outdir = str(tmp_path / "out" / "b0")
    protocol.write_ticket(spool, "beam-0", ["chaos://x"], outdir,
                          passes=6, pass_s=0.02)
    rc = subprocess.run(
        [*_WORKER, "--spool", spool, "--worker-id", "w0", "--once",
         "--crash-after-pass", "3"],
        timeout=60).returncode
    assert rc == 70
    assert protocol.ticket_state(spool, "beam-0") == "claimed"
    assert ckpt.progress_marker(ckpt.default_root(outdir)) == 3
    assert protocol.requeue_stale_claims(spool) == ["beam-0"]
    rc = subprocess.run(
        [*_WORKER, "--spool", spool, "--worker-id", "w1", "--once"],
        timeout=60).returncode
    assert rc == 0
    rec = protocol.read_result(spool, "beam-0")
    assert rec["status"] == "done"
    assert rec["resumed_passes"] == 3
    assert rec["computed_passes"] == 3
    assert rec["candidates_digest"] \
        == cworker.expected_digest("beam-0", 6)
    names = [e.get("event")
             for e in journal.read_events(spool, ticket="beam-0")]
    assert "resume" in names
    # resume state cleaned once the result is durable
    assert not os.path.exists(ckpt.default_root(outdir))
    report = invariants.verify(spool, quiesced=True)
    assert report["ok"], report["violations"]


def test_worker_no_checkpoint_control_recomputes_from_zero(tmp_path):
    """The --no-checkpoint control: same crash, no salvage — the
    resumed attempt recomputes all 6 passes (and still matches the
    golden digest, so resume_consistent holds for from-zero runs)."""
    spool = str(tmp_path / "spool")
    outdir = str(tmp_path / "out" / "b0")
    protocol.write_ticket(spool, "beam-0", ["chaos://x"], outdir,
                          passes=6, pass_s=0.02)
    rc = subprocess.run(
        [*_WORKER, "--spool", spool, "--worker-id", "w0", "--once",
         "--no-checkpoint", "--crash-after-pass", "3"],
        timeout=60).returncode
    assert rc == 70
    assert ckpt.progress_marker(ckpt.default_root(outdir)) == -1
    protocol.requeue_stale_claims(spool)
    rc = subprocess.run(
        [*_WORKER, "--spool", spool, "--worker-id", "w1", "--once",
         "--no-checkpoint"],
        timeout=60).returncode
    assert rc == 0
    rec = protocol.read_result(spool, "beam-0")
    assert rec["status"] == "done"
    assert rec["resumed_passes"] == 0
    assert rec["computed_passes"] == 6
    assert rec["candidates_digest"] \
        == cworker.expected_digest("beam-0", 6)
    names = [e.get("event")
             for e in journal.read_events(spool, ticket="beam-0")]
    assert "resume" not in names
    report = invariants.verify(spool, quiesced=True)
    assert report["ok"], report["violations"]
