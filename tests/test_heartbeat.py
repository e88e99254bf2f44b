"""Stage-heartbeat attribution (round-4 verdict missing #2).

The supervising bench parent must be able to name the stage a killed
child was executing: the child's heartbeat file carries JSON
{t, t_stage, stage, event, info?} written at every stage begin/end and
at chunk drains, and the parent parses it into
{stalled_stage, stage_elapsed_s} on any kill.  Reference contract:
per-stage timing on every run (PALFA2_presto_search.py:95-139,336-372)
— here extended to runs that are KILLED, which is where four rounds of
TPU evidence actually died.
"""

import importlib.util
import json
import os
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def report(monkeypatch, tmp_path):
    from tpulsar.search import report as rep

    hb = str(tmp_path / "hb.json")
    monkeypatch.setattr(rep, "_HEARTBEAT", hb)
    monkeypatch.setattr(rep, "_CUR_STAGE", [])
    return rep, hb


def _read(hb):
    with open(hb) as fh:
        return json.load(fh)


def test_timing_scope_writes_stage_named_beats(report):
    rep, hb = report
    t = rep.StageTimers()
    with t.timing("dedispersing"):
        beat = _read(hb)
        assert beat["stage"] == "dedispersing"
        assert beat["event"] == "begin"
        # t_stage is the scope's begin time — the parent computes
        # total in-stage time from it for the per-stage budget kill
        assert abs(beat["t_stage"] - time.time()) < 5.0
    beat = _read(hb)
    assert beat["event"] == "end"
    assert beat["stage"] == "dedispersing"


def test_progress_beat_keeps_stage_begin_time(report):
    rep, hb = report
    t = rep.StageTimers()
    with t.timing("hi-accelsearch"):
        t0 = _read(hb)["t_stage"]
        rep.progress_beat("accel window dm 32/128")
        beat = _read(hb)
        assert beat["event"] == "progress"
        assert beat["stage"] == "hi-accelsearch"
        assert beat["info"] == "accel window dm 32/128"
        # progress must NOT reset the stage clock: the budget kill
        # measures the whole stage, the stall kill measures silence
        assert beat["t_stage"] == t0


def test_progress_beat_outside_scope_is_noop(report):
    rep, hb = report
    rep.progress_beat("orphan")
    assert not os.path.exists(hb)


def test_bench_parses_heartbeat_and_budgets(tmp_path, monkeypatch):
    bench = _load("bench_hb_test", os.path.join(_REPO, "bench.py"))
    hb = tmp_path / "hb.json"
    hb.write_text(json.dumps({"t": 1.0, "t_stage": 0.5,
                              "stage": "FFT", "event": "begin"}))
    rec = bench._read_heartbeat(str(hb))
    assert rec["stage"] == "FFT"
    # torn/pre-JSON content degrades to None, never raises
    hb.write_text("1234.5")
    assert bench._read_heartbeat(str(hb)) is None
    assert bench._read_heartbeat(str(tmp_path / "absent")) is None
    # budget table: known stage, default, and the env multiplier
    base = bench._stage_budget("hi-accelsearch")
    assert base == bench._STAGE_BUDGETS["hi-accelsearch"]
    assert bench._stage_budget("never-heard-of") \
        == bench._STAGE_BUDGET_DEFAULT
    monkeypatch.setenv("TPULSAR_STAGE_BUDGET_MULT", "2.5")
    assert bench._stage_budget("hi-accelsearch") == 2.5 * base
