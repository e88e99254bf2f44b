"""The ONE resolver for the persistent compilation-cache location.

  1. ``JAX_COMPILATION_CACHE_DIR`` set -> that directory, verbatim;
     nothing in this package sets or overwrites the variable then.
  2. unset -> ``<checkout>/.jax_cache``, a fixed path (the cache's
     location is part of its key, so a directory that moves never
     hits; no temp-, pid- or time-derived component).

The same directory also holds the AOT warm-start manifest
(``aot_manifest.json``), so "where does the cache live" has exactly
one answer per process.

stdlib-only: imported by bench.py's parent process and the CLI before
(and instead of) any jax import.
"""

from __future__ import annotations

import os
import sys

#: the AOT warm-start manifest filename inside the cache dir
MANIFEST_NAME = "aot_manifest.json"


def repo_root() -> str:
    """The checkout root this package runs from (the directory that
    holds the ``tpulsar`` package)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def resolve() -> str:
    """The persistent compilation-cache directory for this process
    (not created; see :func:`ensured`)."""
    val = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if val:
        return val
    return os.path.join(repo_root(), ".jax_cache")


def ensured() -> str:
    """:func:`resolve`, with the directory created."""
    d = resolve()
    os.makedirs(d, exist_ok=True)
    return d


def activate() -> str:
    """Resolve the cache dir and hand it to jax: through the
    environment when the variable is unset (a jax imported later
    reads it there; a set variable is left exactly as it is), and
    through the live config when jax is already imported.

    The cache's keys are salted with the hot programs' scope names
    (``kernels/scopes.py``, which installs the hook when it is
    imported, so in every process that can compile such a program);
    with jax already here the hook goes in now, and a jax without one
    is an error rather than a cache that serves other scopes' names."""
    d = ensured()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", d)
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            jax.config.update("jax_compilation_cache_dir", d)
            # jax's default 1 s floor silently excludes every
            # fast-compiling program from the persistent cache, and
            # the warm-start manifest attributes cache entries to
            # programs.  Cache everything.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        except Exception:
            pass
        from tpulsar.kernels import scopes
        scopes.salt_cache_key()
    return d


def activate_if_configured() -> str | None:
    """:func:`activate`, but only when ``JAX_COMPILATION_CACHE_DIR``
    is set — the library entry points (executor.search_beam) call
    this so the variable works end-to-end WITHOUT turning the
    persistent cache on by default for every embedder."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        return activate()
    return None


def manifest_path() -> str:
    """Where the AOT warm-start manifest lives for this cache dir."""
    return os.path.join(resolve(), MANIFEST_NAME)


def cache_entries() -> frozenset[str]:
    """The persistent-cache entry filenames currently on disk (the
    ``*-cache`` payload files; ``-atime`` sidecars churn on every hit
    and are excluded).  The warm-start manifest attributes entries to
    programs by diffing this set around each compile."""
    d = resolve()
    try:
        names = os.listdir(d)
    except OSError:
        return frozenset()
    return frozenset(n for n in names if n.endswith("-cache"))
