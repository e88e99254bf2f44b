"""Tests for the AOT subsystem (tpulsar/aot/): cache-dir resolution,
registry completeness against the package ASTs, program resolution,
the warm-start manifest, and the two-process zero-recompile contract.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from tpulsar.aot import cachedir, registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------
# cachedir: the one resolver
# ------------------------------------------------------------------

def test_cachedir_precedence(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> that directory, verbatim;
    unset -> <checkout>/.jax_cache, a fixed path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cachedir.resolve() == os.path.join(_REPO, ".jax_cache")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jaxpin"))
    assert cachedir.resolve() == str(tmp_path / "jaxpin")


@pytest.fixture
def jax_cache_config():
    """activate() pushes the path into the live jax config, which
    outlives monkeypatch's env restore: put it back."""
    import jax

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cachedir_activate_leaves_a_set_variable_alone(
        monkeypatch, tmp_path, jax_cache_config):
    """activate() uses the set variable and never overwrites it: the
    cache is placeable from outside, and no code sets another."""
    pinned = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", pinned)
    got = cachedir.activate()
    assert got == pinned
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == pinned
    assert os.path.isdir(pinned)
    import jax
    assert jax.config.jax_compilation_cache_dir == pinned


def test_scoped_kernels_salt_the_cache_key(monkeypatch):
    """jax leaves op metadata out of its compile-cache key, and the
    hot programs' named scopes are metadata: the same program with and
    without the salt has another key, so an executable cached under
    other scope names is never loaded.  The salt is a hash of the
    scope names, goes after an embedder's own hook, and is installed
    by importing the module that defines the scopes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    from tpulsar.kernels import scopes

    assert cache_key.custom_hook().endswith(scopes.KEY_SALT)   # at import
    monkeypatch.setattr(cache_key, "custom_hook", lambda: "theirs/")

    def key():
        lowered = jax.jit(lambda x: x + 1).lower(jnp.zeros(4))
        backend = jax.devices()[0].client
        return cache_key.get(
            lowered.compiler_ir("stablehlo"), np.array(jax.devices()[:1]),
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            backend)

    bare = key()
    assert scopes.salt_cache_key()
    assert cache_key.custom_hook() == "theirs/" + scopes.KEY_SALT
    assert scopes.salt_cache_key()                       # idempotent
    assert cache_key.custom_hook() == "theirs/" + scopes.KEY_SALT
    assert key() != bare
    assert key() == key()
    # the salt follows the names: no constant to bump after a rename
    assert all(s in "".join(scopes.SCOPES) for s in ("hiaccel", "sp/"))
    with pytest.raises(ValueError):
        scopes.scope("sp/not-a-scope")


def test_cachedir_activate_salts_or_refuses(
        monkeypatch, tmp_path, jax_cache_config):
    """activate() with jax imported puts the hook in itself, and a jax
    without the hook is an error once a cache directory is set: an
    unsalted cache would serve executables with other scope names."""
    from jax._src import cache_key

    from tpulsar.kernels import scopes

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    cachedir.activate()
    assert cache_key.custom_hook() == scopes.KEY_SALT
    monkeypatch.delattr(cache_key, "custom_hook")
    with pytest.raises(RuntimeError, match="custom_hook"):
        cachedir.activate()


def test_cachedir_activate_unset_uses_the_checkout(
        monkeypatch, jax_cache_config):
    """Unset, activate() exports the fixed checkout path, so a jax
    imported later — and every child process — lands in the same
    cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cachedir.activate()
        assert got == os.path.join(_REPO, ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got
    finally:
        # activate() set it behind monkeypatch's back
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


def test_cachedir_activate_if_configured_only_when_set(monkeypatch,
                                                       tmp_path):
    """The library entry (executor.search_beam) turns the persistent
    cache on only when the variable is set."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cachedir.activate_if_configured() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


@pytest.mark.parametrize("pinned", [False, True])
def test_two_processes_resolve_the_same_path(tmp_path, pinned):
    """No temp-, pid- or time-derived component: two fresh processes
    (different pids, different cwds, seconds apart) resolve one
    path."""
    import tpulsar

    env = dict(tpulsar.cpu_subprocess_env(), PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if pinned:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "pin")
    got = []
    for cwd in (str(tmp_path), _REPO):
        out = subprocess.run(
            [sys.executable, "-c",
             "from tpulsar.aot import cachedir; "
             "print(cachedir.resolve())"],
            capture_output=True, text=True, timeout=60, env=env,
            cwd=cwd)
        assert out.returncode == 0, out.stderr[-400:]
        got.append(out.stdout.strip())
    want = (str(tmp_path / "pin") if pinned
            else os.path.join(_REPO, ".jax_cache"))
    assert got == [want, want]


def test_manifest_path_lives_in_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cachedir.manifest_path() == str(
        tmp_path / cachedir.MANIFEST_NAME)


# ------------------------------------------------------------------
# registry completeness: every jax.jit site in the package is either
# registered or on the commented exemption list — the round-3
# lambda-wrapping pitfall cannot silently recur via a new unregistered
# program
# ------------------------------------------------------------------

def _is_jit_expr(node: ast.AST) -> bool:
    """True for `jax.jit` / `functools.partial(jax.jit, ...)` /
    `partial(jax.jit, ...)` expressions."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "jit":
            return True
        is_partial = ((isinstance(fn, ast.Name)
                       and fn.id == "partial")
                      or (isinstance(fn, ast.Attribute)
                          and fn.attr == "partial"))
        if is_partial:
            return any(_is_jit_expr(a) for a in node.args)
    return False


def _jit_sites(relpath: str) -> set[str]:
    """Every jit site in one file as '<relpath>::<function-name>':
    jit-decorated defs plus inline jax.jit(...) calls attributed to
    their enclosing function."""
    tree = ast.parse(open(os.path.join(_REPO, relpath)).read())
    sites: set[str] = set()

    class Visitor(ast.NodeVisitor):
        def __init__(self):
            self.stack: list[str] = []

        def _visit_def(self, node):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    sites.add(f"{relpath}::{node.name}")
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = _visit_def
        visit_AsyncFunctionDef = _visit_def

        def visit_Call(self, node):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "jit":
                encl = self.stack[-1] if self.stack else "<module>"
                sites.add(f"{relpath}::{encl}")
            self.generic_visit(node)

    Visitor().visit(tree)
    return sites


def test_every_jit_site_is_registered_or_exempt():
    all_sites: set[str] = set()
    for sub in ("kernels", "search", "parallel"):
        d = os.path.join(_REPO, "tpulsar", sub)
        for fname in sorted(os.listdir(d)):
            if fname.endswith(".py"):
                all_sites |= _jit_sites(f"tpulsar/{sub}/{fname}")
    assert all_sites, "AST walk found no jit sites — walker broken?"

    covered = registry.registered_sites() | set(registry.EXEMPT_SITES)
    unregistered = sorted(all_sites - covered)
    assert not unregistered, (
        "jax.jit sites neither registered in tpulsar/aot/registry.py "
        "nor on its EXEMPT_SITES list (register the module-level "
        f"callable, or exempt it WITH a reason): {unregistered}")

    # the inverse direction: a registered/exempt site that no longer
    # exists is stale registry state (e.g. a renamed kernel)
    stale = sorted(covered - all_sites)
    assert not stale, f"registry/exempt sites with no jit site: {stale}"


def test_registry_names_unique_and_resolvable():
    names = [p.name for p in registry.PROGRAMS]
    assert len(names) == len(set(names))
    # spot-resolve the round-5 victim + the round-3 pitfall programs:
    # each must be the jitted callable itself (lowerable), not a
    # wrapper
    for name in ("dedisperse._form_subbands_jit", "refine.gather",
                 "fourier.whitened_spectrum", "accel.accel_chunk_topk"):
        fn = registry.jitted(name)
        assert hasattr(fn, "lower"), name


def test_gate_groups_cover_only_registered_programs():
    """Every instance the shape-builders emit references a registered
    program, in every profile (headline/fast/config 1/3/4)."""
    ctx = registry.make_context(scale=0.01)
    known = {p.name for p in registry.PROGRAMS}
    seen: set[str] = set()
    for config in (0, 1, 3, 4):
        for fast in ((False, True) if config == 0 else (False,)):
            for _hdr, insts in registry.gate_groups(
                    ctx, config=config, fast=fast):
                for inst in insts:
                    assert inst.program in known, inst
                    seen.add(inst.program)
    # the gate set must include the known recompile victims
    assert "dedisperse._form_subbands_jit" in seen
    assert "refine.gather" in seen
    assert "bench.gen_block_chunk" in seen


def test_gate_compiles_the_stage_family_the_runtime_dispatches(
        monkeypatch):
    """Where the Pallas tiers are on (a TPU backend; forced here by
    env) the gate holds their slab / chunk programs at the wrappers'
    own geometry and none of the XLA formulations the runtime would
    never reach there."""
    from tpulsar.kernels import pallas_dd

    ctx = registry.make_context(scale=1.0)

    def programs():
        return {i.program: i for _h, g in registry.gate_groups(
            ctx, fast=True) for i in g}

    monkeypatch.delenv("TPULSAR_PALLAS", raising=False)
    xla = programs()
    assert "dedisperse._form_subbands_jit" in xla
    assert "pallas_dd._form_subbands_block" not in xla

    monkeypatch.setenv("TPULSAR_PALLAS", "1")
    tpu = programs()
    assert "dedisperse._form_subbands_jit" not in tpu
    assert "dedisperse._dedisperse_subbands_scan" not in tpu
    sb = tpu["pallas_dd._form_subbands_block"]
    assert sb.kwargs["interpret"] is False
    # 960 channels go 8 segments of 4096 samples a step, 48 subbands
    # at a time (the scoped-VMEM rule)
    plan1 = pallas_dd.stage1_plan(960, 96, 256, 1)
    assert (plan1.block_t, plan1.group) == (32768, 48)
    assert {k: sb.kwargs[k] for k in plan1._fields} == plan1._asdict()
    assert sb.args[0].dtype == "uint8"           # staged as it is
    assert sb.args[0].shape[1:] == (sb.args[0].shape[1], plan1.seg)
    assert sb.args[1].shape == (960, 8, plan1.head)
    # stage 2 in the wrapper's own split: the executor's chunk (76
    # trials run as 38 + 38) as 19-row programs and a fold's series as
    # one row, never a call padded up to 32
    dd2 = {i.args[2].shape[0]: i
           for _h, g in registry.gate_groups(ctx, fast=True)
           for i in g if i.program == "pallas_dd._dedisperse_chunk"}
    assert sorted(dd2) == [1, 19]
    for rows, n in ((19, 38), (1, 1)):
        kw = dd2[rows].kwargs
        seg = dd2[rows].args[0].shape[2]
        plan = pallas_dd.stage2_plan(96, kw["window"] - seg - 128, n,
                                     ctx.nsamp)
        assert (plan.rows, plan.seg, plan.window) == (rows, seg,
                                                      kw["window"])
        assert dd2[rows].args[0].shape == (96, ctx.nsamp // seg, seg)
        assert kw["vmem_bytes"] == plan.vmem_bytes
        assert kw["interpret"] is False
    assert tpu["pallas_dd._segment_layout"].kwargs["seg"] == plan.seg


def test_fingerprint_is_stable_and_shape_sensitive():
    from tpulsar.aot import warmstart

    ctx = registry.make_context(scale=0.01)
    groups = registry.gate_groups(ctx)
    insts = [i for _h, g in groups for i in g]
    a = insts[1]
    assert warmstart.fingerprint(a) == warmstart.fingerprint(a)
    fps = {warmstart.fingerprint(i) for i in insts}
    # distinct labels => distinct signatures (duplicate-label dense-
    # sweep entries legitimately collide)
    assert len(fps) >= len({i.label for i in insts})


# ------------------------------------------------------------------
# warm start: two processes, one cache — the second compiles nothing
# ------------------------------------------------------------------

def _run_gate(args: list[str], env: dict) -> subprocess.CompletedProcess:
    import tpulsar

    full_env = dict(tpulsar.cpu_subprocess_env())
    full_env.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "aot_check.py"),
         *args],
        capture_output=True, text=True, timeout=540, env=full_env)


def test_two_process_warm_start_zero_misses(tmp_path):
    """Process 1 gates a registered-program subset; process 2 verifies
    against the manifest and must report ZERO misses — the acceptance
    contract that a warm child search compiles nothing the gate
    already compiled."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    only = "refine.gather,rfi._cell_stats_chan"

    first = _run_gate(["--scale", "0.02", "--only", only], env)
    assert first.returncode == 0, (first.stdout[-800:]
                                   + first.stderr[-400:])
    assert "all programs compiled" in first.stdout

    manifest = json.load(open(tmp_path / "cache"
                              / cachedir.MANIFEST_NAME))
    assert manifest["schema"] == "tpulsar-aot-manifest/v1"
    progs = {rec["program"]
             for rec in manifest["programs"].values()}
    assert progs == {"refine.gather", "rfi._cell_stats_chan"}
    # the gate's compiles landed in the persistent cache...
    assert any(rec["entries"]
               for rec in manifest["programs"].values())

    second = _run_gate(["--scale", "0.02", "--only", only,
                        "--verify"], env)
    assert second.returncode == 0, (second.stdout[-800:]
                                    + second.stderr[-400:])
    assert "0 misses" in second.stdout
    assert "[MISS]" not in second.stdout


_SEARCH_ORDER = """
import jax                                  # the search imports jax first
from tpulsar.aot import cachedir, registry
cachedir.activate()                         # ... and activates after
ctx = registry.make_context(scale=0.02, accel=False, nbeams=0)
before = cachedir.cache_entries()
n = 0
for _header, insts in registry.gate_groups(ctx, config=0, fast=False):
    for inst in insts:
        if inst.program in {only!r}:
            registry.jitted(inst.program).lower(
                *inst.args, **inst.kwargs).compile()
            n += 1
print("compiled", n, "new", len(cachedir.cache_entries() - before))
"""


def test_gate_then_search_order_process_zero_misses(tmp_path):
    """The gate activates the cache BEFORE it imports jax, a search
    process after: the keys (salted with the scope names) must not
    depend on that order, or a cache the gate prebaked is never hit.
    One of the programs carries named scopes."""
    import tpulsar

    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    only = ("singlepulse.boxcar_search", "refine.gather")
    first = _run_gate(["--scale", "0.02", "--only", ",".join(only)], env)
    assert first.returncode == 0, (first.stdout[-800:]
                                   + first.stderr[-400:])
    second = subprocess.run(
        [sys.executable, "-c", _SEARCH_ORDER.format(only=only)],
        capture_output=True, text=True, timeout=540,
        env=dict(tpulsar.cpu_subprocess_env(), PYTHONPATH=_REPO, **env))
    assert second.returncode == 0, second.stderr[-800:]
    compiled, new = (int(x) for x in
                     second.stdout.split()[1::2])
    assert compiled >= 2 and new == 0, second.stdout


def test_verify_without_manifest_fails(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "nocache")}
    out = _run_gate(["--scale", "0.02", "--only", "refine.gather",
                     "--verify"], env)
    assert out.returncode == 1
    assert "no manifest" in out.stdout


def test_verify_flags_cold_cache_as_miss(tmp_path):
    """Manifest present but cache entries gone (e.g. cache GC'd):
    verify must MISS, not silently recompile — this is precisely the
    round-5 bench scenario as an exit code."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    only = "refine.gather"
    first = _run_gate(["--scale", "0.02", "--only", only], env)
    assert first.returncode == 0, first.stdout[-500:]

    # sweep the cache entries, keep the manifest
    cache = tmp_path / "cache"
    for f in cache.iterdir():
        if f.name.endswith("-cache"):
            f.unlink()

    out = _run_gate(["--scale", "0.02", "--only", only, "--verify"],
                    env)
    assert out.returncode == 1, out.stdout[-500:]
    assert "[MISS]" in out.stdout


# ------------------------------------------------------------------
# CLI surface
# ------------------------------------------------------------------

def test_cli_aot_ls(capsys):
    from tpulsar.cli import main as cli_main

    rc = cli_main.main(["aot", "ls"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "registered programs" in out
    assert "dedisperse._form_subbands_jit" in out
    assert "exempt jit sites" in out
    assert "tpulsar/parallel/mesh.py::sharded_search_step" in out


# ------------------------------------------------------------------
# runtime monitor + compile rollup
# ------------------------------------------------------------------

def test_runtime_monitor_emits_compile_telemetry(tmp_path):
    """install_runtime_monitor turns an in-line XLA compile into a
    backend_compile trace event and a labeled histogram observation —
    the instrumentation that makes a silent recompile visible."""
    import jax
    import jax.numpy as jnp

    from tpulsar.aot import warmstart
    from tpulsar.obs import telemetry, trace

    assert warmstart.install_runtime_monitor()
    trace.start(clear=True)
    try:
        # a fresh closure AND a constant from the clock => a fresh
        # compile whatever a persistent compile cache holds (the same
        # program twice is a load the second time, not a compile)
        import time
        salt = float(time.time_ns() % 1_000_003) + 17.0

        @jax.jit
        def _probe(x):
            return x * salt + 1.0

        _probe(jnp.ones((64, 64))).block_until_ready()
    finally:
        events = trace.events()
        trace.stop()
    compiles = [e for e in events if e["name"] == "backend_compile"]
    assert compiles, "no backend_compile event recorded"
    assert compiles[0]["args"]["program"] == "(inline)"
    assert compiles[0]["dur"] > 0
    hist = telemetry.backend_compile_seconds()
    snap = telemetry.metrics.REGISTRY.snapshot()
    rec = snap["tpulsar_backend_compile_seconds"]
    assert any(s.get("count", 0) > 0 for s in rec["series"].values())


def test_compile_rollup_from_trace_file(tmp_path):
    """tools/trace_summarize.compile_rollup groups aot_compile and
    backend_compile spans per program."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_summarize",
        os.path.join(_REPO, "tools", "trace_summarize.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)

    tracefile = tmp_path / "x_trace.json"
    tracefile.write_text(json.dumps({"traceEvents": [
        {"name": "aot_compile", "ph": "X", "dur": 2_000_000,
         "args": {"program": "dedisperse._form_subbands_jit"}},
        {"name": "aot_compile", "ph": "X", "dur": 1_000_000,
         "args": {"program": "dedisperse._form_subbands_jit"}},
        {"name": "backend_compile", "ph": "X", "dur": 500_000,
         "args": {"program": "(inline)"}},
        {"name": "dedispersing", "ph": "X", "dur": 9_000_000,
         "args": {}},
    ]}))
    roll = ts.compile_rollup(str(tracefile))
    assert roll["dedisperse._form_subbands_jit"]["seconds"] == 3.0
    assert roll["dedisperse._form_subbands_jit"]["count"] == 2
    assert roll["(inline)"]["count"] == 1
    assert "dedispersing" not in roll
    txt = ts.render_compile_rollup(roll)
    assert "compile rollup" in txt and "(inline)" in txt


def test_compile_rollup_dedupes_gate_event_pairs(tmp_path):
    """A gated compile emits aot_compile (wall span) ENCLOSING the
    monitor's backend_compile — the rollup must count the pair once,
    not sum it (which would double every gate compile time)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_summarize_d",
        os.path.join(_REPO, "tools", "trace_summarize.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)

    roll = ts.compile_rollup([
        {"name": "aot_compile", "ph": "X", "dur": 4_000_000,
         "args": {"program": "rfi._cell_stats_chan"}},
        {"name": "backend_compile", "ph": "X", "dur": 3_800_000,
         "args": {"program": "rfi._cell_stats_chan"}},
    ])
    rec = roll["rfi._cell_stats_chan"]
    assert rec["seconds"] == 4.0 and rec["count"] == 1
    assert rec["events"] == {"aot_compile": 1, "backend_compile": 1}


def test_only_matching_nothing_is_loud(tmp_path):
    """A typo'd --only must not green-light an unverified cache with
    a vacuous rc-0 (0/0 hits, 0 misses)."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    out = _run_gate(["--scale", "0.02", "--only", "refine.gahter"],
                    env)
    assert out.returncode == 1, out.stdout[-400:]
    assert "no gate programs matched" in out.stdout


def test_gate_saves_trace_when_enabled(tmp_path):
    """TPULSAR_TRACE=1 gate runs save their aot_compile spans next to
    the manifest so the compile rollup has a real artifact to read."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "TPULSAR_TRACE": "1"}
    out = _run_gate(["--scale", "0.02", "--only", "refine.gather"],
                    env)
    assert out.returncode == 0, out.stdout[-400:]
    tracefile = tmp_path / "cache" / "aot_gate_trace.json"
    assert tracefile.exists()
    evs = json.loads(tracefile.read_text())["traceEvents"]
    aot = [e for e in evs if e["name"] == "aot_compile"]
    assert len(aot) == 3        # one per refine_gather width bucket
    assert {e["args"]["program"] for e in aot} == {"refine.gather"}
