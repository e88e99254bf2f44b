"""AOT compile layer: program registry, persistent-cache warm-start,
and the compile manifest.

The round-5 campaign measured a child search spending 160.6 s of its
176.5 s wall-clock recompiling HLO the AOT gate had already compiled
— the gate and the runtime lowered programs through independently
maintained paths, and three call sites fought the drift by hand
(module-level jits to dodge the wrapper-lambda cache-key pitfall,
``refine._gather_jit`` exposed solely for the gate, ``tools/
aot_check.py`` rebuilding shapes from its own constants).  This
package makes the drift structurally impossible instead of
comment-enforced:

  ``registry``  — every jitted program in the pipeline, declared once
                  with its exact module-level callable and the
                  shape-builders that derive canonical compile shapes
                  from ``SearchParams``/``DDPlan``/scale.  Consumed by
                  the gate, the runtime, and the diagnostics.
  ``cachedir``  — the ONE resolver for the persistent compilation
                  cache location (``JAX_COMPILATION_CACHE_DIR``
                  when set, else ``<checkout>/.jax_cache``).
  ``warmstart`` — the gate driver: compiles the registered program
                  set, records each program's cache fingerprint in a
                  manifest, verifies warm runs against it, and
                  installs the runtime compile monitor that turns any
                  silent in-line recompile into ``compile_cache_miss``
                  counters and trace spans.

Operator surface: ``tpulsar aot compile|verify|ls`` (tpulsar/cli) and
the thin ``tools/aot_check.py`` wrapper (rc 0/1/3 contract).

``cachedir`` and ``registry``'s table are stdlib-only at import time:
jax and the kernels load lazily, so the CLI can list programs and
resolve cache paths without touching the accelerator.
"""

from tpulsar.aot import cachedir  # noqa: F401  (stdlib-only)

__all__ = ["cachedir", "registry", "warmstart"]
