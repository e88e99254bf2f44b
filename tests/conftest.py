"""Test configuration: force JAX onto a virtual 8-device CPU platform
so multi-chip sharding is exercised without TPU hardware."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# jax freezes its platform config from the environment when it is
# first imported; a plugin that imported it earlier than this file
# would leave the env vars above too late, so set the live config as
# well (safe: backends are not instantiated until first use).
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute integration tests (skip with "
        "TPULSAR_FAST_TESTS=1 or -m 'not slow')")


def pytest_collection_modifyitems(config, items):
    """TPULSAR_FAST_TESTS=1 skips every slow-marked test — the env-var
    contract lives here once, not as per-test skipifs."""
    if os.environ.get("TPULSAR_FAST_TESTS") != "1":
        return
    skip = pytest.mark.skip(reason="TPULSAR_FAST_TESTS=1 skips "
                                   "slow integration tests")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


def _find_search_job_pids() -> list[int]:
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                cmd = fh.read().decode(errors="replace")
        except OSError:
            continue
        if "tpulsar.cli.search_job" in cmd.replace("\0", " "):
            pids.append(int(pid))
    return pids


@pytest.fixture(autouse=True, scope="session")
def _no_leaked_search_jobs():
    """Every test must reap the search subprocesses it submits (the
    LocalProcessManager.shutdown() teardown in test_cli does this);
    a leaked search_job outlived its test by 20+ minutes in round 1.
    This guard fails the suite if any survive — and still kills them
    so one failure doesn't poison the machine."""
    import signal
    import time

    before = set(_find_search_job_pids())
    yield
    leaked = [p for p in _find_search_job_pids() if p not in before]
    for pid in leaked:
        try:
            os.killpg(os.getpgid(pid), signal.SIGTERM)
        except OSError:
            try:
                os.kill(pid, signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + 10
    while time.time() < deadline and any(
            p in _find_search_job_pids() for p in leaked):
        time.sleep(0.2)
    assert not leaked, (
        f"search_job subprocesses leaked by the suite (killed now): "
        f"{leaked}")
