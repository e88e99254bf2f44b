"""Tests for the repo tools (tools/ is not a package; load by path)."""

import importlib.util
import os
import subprocess
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _C:
    def __init__(self, freq_hz, dm, sigma):
        self.freq_hz, self.dm, self.sigma = freq_hz, dm, sigma


def test_compare_match_is_one_to_one():
    """A single got-candidate must not satisfy two reference
    candidates: a strong harmonic cannot mask a missing detection."""
    cmp_mod = _load("compare_candlists")
    ref = [_C(1.0, 20.0, 10.0), _C(2.0, 20.0, 9.0)]
    got = [_C(2.0, 20.0, 9.0)]
    res = cmp_mod.match(ref, got, freq_tol=1e-4, dm_tol=0.5)
    kinds = {rc.freq_hz: kind for rc, kind, _ in res}
    assert kinds[2.0] == "exact"
    assert kinds[1.0] == "missed"


def test_compare_harmonic_and_dm_tolerance():
    cmp_mod = _load("compare_candlists")
    ref = [_C(1.0, 20.0, 8.0), _C(5.0, 100.0, 7.0)]
    got = [_C(2.00001, 20.2, 8.0),    # 2nd harmonic of ref[0]
           _C(5.0, 103.0, 7.0)]       # DM too far from ref[1]
    res = cmp_mod.match(ref, got, freq_tol=1e-4, dm_tol=0.5)
    kinds = {rc.freq_hz: kind for rc, kind, _ in res}
    assert kinds[1.0] == "harmonic"
    assert kinds[5.0] == "missed"


def test_compare_exact_preferred_over_harmonic():
    cmp_mod = _load("compare_candlists")
    ref = [_C(2.0, 20.0, 9.0)]
    got = [_C(1.0, 20.0, 5.0), _C(2.0, 20.0, 9.0)]
    res = cmp_mod.match(ref, got, freq_tol=1e-4, dm_tol=0.5)
    assert res[0][1] == "exact"
    assert res[0][2].freq_hz == 2.0


def test_trace_compare_reports_a_stage_mismatch(tmp_path):
    """--compare-report's stage check: a trace whose rollup agrees
    with the .report's rows passes, a stage whose spans fall short of
    its row is a REAL mismatch that names the stage, and every span is
    compared with its own row only (nothing is counted twice)."""
    ts = _load("trace_summarize")
    report = tmp_path / "x.report"
    report.write_text(
        "Timing report for x\n"
        "   Total time: 10.00 s\n\n"
        "      dedispersing:      6.00 s  ( 60.0%)\n"
        "      single-pulse:      2.00 s  ( 20.0%)\n")
    summary = {"rollup": {
        "dedispersing": {"seconds": 6.0, "count": 3},
        "single-pulse": {"seconds": 2.0, "count": 3},
    }}
    assert ts.compare(summary, str(report)) == []
    # 4 s of spans against a 6 s row: over the 5% gate
    summary2 = {"rollup": {
        "dedispersing": {"seconds": 4.0, "count": 3},
        "detrend": {"seconds": 2.0, "count": 3},
        "single-pulse": {"seconds": 2.0, "count": 3},
    }}
    problems = ts.compare(summary2, str(report))
    assert len(problems) == 1 and "dedispersing" in problems[0]
    # a report that rows detrend itself is compared row-for-row
    report2 = tmp_path / "y.report"
    report2.write_text(
        "Timing report for y\n"
        "   Total time: 10.00 s\n\n"
        "      dedispersing:      4.00 s  ( 40.0%)\n"
        "           detrend:      2.00 s  ( 20.0%)\n")
    assert ts.compare(summary2, str(report2)) == []


def test_aot_check_cli_smoke():
    """The AOT memory checker compiles a tiny-scale program set and
    exits 0 (CPU; the tool's purpose is pre-validating full-scale
    programs without executing on the device)."""
    import tpulsar

    env = tpulsar.cpu_subprocess_env()
    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "aot_check.py"),
         "--scale", "0.02"],
        capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stdout[-800:] + out.stderr[-400:]
    assert "all programs compiled" in out.stdout


@pytest.mark.slow
def test_aot_check_deadline_defers_cleanly_and_resumes(tmp_path):
    """--deadline is checked BETWEEN compiles: a mid-run expiry
    compiles a prefix ([ok]), defers the tail ([defer], rc 3, never
    killed mid-compile), and a re-run
    against the same cache resumes the partially-warmed set to rc 0.

    Determinism: an ISOLATED cold cache dir makes the full ~27-program
    set take far longer than the deadline slack (defer guaranteed),
    while calibrating the deadline to this host's import time leaves
    room for the first compiles ([ok] guaranteed)."""
    import time as _time

    import tpulsar

    env = dict(tpulsar.cpu_subprocess_env())
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")

    t0 = _time.monotonic()
    subprocess.run([sys.executable, "-c", "import jax"],
                   capture_output=True, timeout=120, env=env)
    import_s = _time.monotonic() - t0

    first = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "aot_check.py"),
         "--scale", "0.02", "--deadline", str(import_s + 6.0)],
        capture_output=True, text=True, timeout=560, env=env)
    assert first.returncode == 3, first.stdout[-800:] + first.stderr[-400:]
    assert "[ok]" in first.stdout          # a prefix compiled...
    assert "[defer]" in first.stdout       # ...the tail deferred
    assert "deferred past deadline" in first.stdout
    assert "[FAIL]" not in first.stdout

    resumed = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "aot_check.py"),
         "--scale", "0.02"],
        capture_output=True, text=True, timeout=560, env=env)
    assert resumed.returncode == 0, (resumed.stdout[-800:]
                                     + resumed.stderr[-400:])
    assert "all programs compiled" in resumed.stdout


@pytest.mark.slow
def test_aot_check_fast_mode():
    """--fast (bench.py's headline pre-flight) gates the
    maximal-footprint subset: the ds=1 block programs and exactly one
    budget-capped sp/spectrum pair must be present, the ds>1 block
    variants absent."""
    import tpulsar

    env = tpulsar.cpu_subprocess_env()
    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "aot_check.py"),
         "--scale", "0.02", "--fast"],
        capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stdout[-800:] + out.stderr[-400:]
    assert "all programs compiled" in out.stdout
    assert "form_subbands ds=1" in out.stdout
    assert "form_subbands ds=2" not in out.stdout
    assert out.stdout.count("sp_boxcars") == 1
