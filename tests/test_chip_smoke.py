"""chip_smoke.py rehearsed on CPU: the same script end to end at toy
size (gateway + serve children), and its refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import tpulsar

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """One placed compile cache for the module: the second smoke run
    boots from the first one's warm-start manifest."""
    return str(tmp_path_factory.mktemp("smoke") / "jax_cache")


def _run(args, cwd=_REPO, script=_SMOKE, timeout=420, **env_extra):
    env = dict(tpulsar.cpu_subprocess_env())
    env.pop("TPULSAR_FAULTS", None)
    env.pop("XLA_FLAGS", None)      # one CPU device, like one chip
    env.update(env_extra)
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _cache_files(root):
    return [os.path.join(d, f) for d, _, files in os.walk(root)
            for f in files if f.endswith("-cache")]


def test_tiny_end_to_end(tmp_path, cache_dir):
    work = str(tmp_path / "work")
    out = _run(["--tiny", "--workdir", work, "--keep"],
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    # the last line is the contract's, and nothing else is in it
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"],
        "count": 1}}
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    assert by_phase["sizes"]["dm_trials"] == 152
    assert by_phase["search"]["dm_trials"] == 152
    assert by_phase["boot-gate"]["rc"] in (0, 3)
    assert by_phase["modes"] == {
        "phase": "modes", "degraded_modes": {}, "rescued_modes": {},
        "hi_accel_trials_by_path": {"batched": 152}}
    assert by_phase["candidates"]["source"] == "index"
    assert by_phase["candidates"]["recovered"]
    assert by_phase["stages"]["seconds"]["hi-accelsearch"] > 0
    assert by_phase["fsck"]["ok"] is True
    # the compile cache went where the variable says, and nowhere else
    assert _cache_files(cache_dir)
    assert os.path.isfile(os.path.join(cache_dir, "aot_manifest.json"))
    assert not _cache_files(work)
    shutil.rmtree(work)


def test_degraded_beam_fails_the_smoke(tmp_path, cache_dir):
    """A kernel fault that CPU CI may absorb into the XLA path (the
    dedisperse.pallas fault point) leaves a degraded mode on the beam:
    the search completes, the smoke does not."""
    out = _run(["--tiny", "--workdir", str(tmp_path / "work")],
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               TPULSAR_FAULTS="dedisperse.pallas:unimplemented:count=1")
    assert out.returncode == 1, out.stdout[-1500:] + out.stderr[-1500:]
    assert "degraded modes" in out.stderr
    assert "pallas_dd_disabled" in out.stderr
    assert '"ok"' not in out.stdout
    assert not os.path.exists(tmp_path / "work")     # cleaned up


def test_refuses_a_hidden_chip():
    """Without --tiny on CPU it exits non-zero and prints no result:
    no silent CPU pass."""
    out = _run([], timeout=60)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
    assert "JAX_PLATFORMS=cpu hides the accelerator" in out.stderr


def test_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails, whatever the platform."""
    shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["--tiny"], cwd=str(tmp_path),
               script=str(tmp_path / "chip_smoke.py"), timeout=60,
               PYTHONPATH="")
    assert out.returncode not in (0, None)
    assert '"ok"' not in out.stdout
