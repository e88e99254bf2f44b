"""The named device scopes of the hot programs, and the compile-cache
salt derived from them.

``scope(name)`` is ``jax.named_scope`` for a name of :data:`SCOPES`:
trace-time metadata on the operations inside (the HLO ``op_name``), no
run-time cost and no change to what XLA compiles.  A profiler trace
shows it on every device operation; ``benchmark/harness/scopes.py``
sums device seconds by it, and its list (``benchmark/trace_scopes.json``)
is held to this one by a test.

jax leaves an operation's metadata out of its persistent-cache key
(``jax_compilation_cache_include_metadata_in_key``, off: every edited
line number would miss the cache).  So a cache filled by a commit with
other scopes, or none, would serve its executables to this one, and a
trace would show the old names.  :data:`KEY_SALT`, a hash of the scope
names, goes into the key through jax's own ``cache_key.custom_hook``.
The hook is installed when this module is imported, that is by every
process that can compile a scoped program, whatever it imported first:
the out-of-process AOT gate and the search process compute the same
keys.  A renamed, added or removed scope changes the salt by itself.
"""

from __future__ import annotations

import hashlib
import os

import jax

SCOPES = (
    "hiaccel/correlate", "hiaccel/harmsum", "hiaccel/topk",   # accel.py
    "spectra/fft", "spectra/whiten", "lo/harmsum", "lo/topk",  # fourier.py
    "sp/detrend", "sp/boxcar",                            # singlepulse.py
)

KEY_SALT = "tpulsar-scopes/" + hashlib.sha256(
    "\n".join(SCOPES).encode()).hexdigest()[:16]


def scope(name: str):
    """``jax.named_scope(name)`` (a context manager and a decorator)
    for one of :data:`SCOPES`; any other name is a fault, because the
    salt would not know of it."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in kernels.scopes.SCOPES")
    return jax.named_scope(name)


def _cache_dir_set() -> bool:
    return bool(os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
                or jax.config.jax_compilation_cache_dir)


def salt_cache_key() -> bool:
    """Make jax hash :data:`KEY_SALT` into every persistent-cache key
    (after whatever hook an embedder has set).  Idempotent.  True when
    the hook is in place; where this jax has no such hook, False with
    no persistent cache configured and an error with one: a cache that
    cannot be salted may hand out executables with other scope names."""
    try:
        from jax._src import cache_key
        prev = cache_key.custom_hook
    except (ImportError, AttributeError):
        if _cache_dir_set():
            raise RuntimeError(
                "this jax has no cache_key.custom_hook: the persistent "
                "compile cache cannot tell programs with other named "
                "scopes apart (unset JAX_COMPILATION_CACHE_DIR)")
        return False
    if getattr(prev, "tpulsar_salt", None) != KEY_SALT:
        def hook() -> str:
            return prev() + KEY_SALT
        hook.tpulsar_salt = KEY_SALT
        cache_key.custom_hook = hook
    return True


salt_cache_key()
