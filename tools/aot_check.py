#!/usr/bin/env python
"""AOT-compile the full-scale search programs and report their HBM
footprints WITHOUT executing anything on the device.

Thin wrapper over the tpulsar.aot subsystem: the program set and its
canonical shapes live in tpulsar/aot/registry.py (the single source
of truth the gate, the runtime, and the diagnostics share), the
compile loop + warm-start manifest in tpulsar/aot/warmstart.py.
`tpulsar aot compile|verify|ls` is the same machinery as CLI
subcommands; this script survives for its operators.

Why this exists: a runtime HBM OOM is an expensive way to learn that
a program does not fit, while a compile-stage error is a clean error
before anything runs.  This tool lowers and
compiles every whole-beam program at headline benchmark shapes
(960 x 3.93M Mock beam, the survey plan's pass geometries) and prints
each executable's compiler-reported memory so an over-budget program
is caught before it ever runs.

Usage:
    python tools/aot_check.py [--scale 1.0] [--accel]

Exit 0 = every program compiled; 1 lists the failures; 3 = the
--deadline elapsed with programs still pending (no failures).  Rc 3
is a clean between-compiles exit: re-running resumes from the
persistent compilation cache, so callers should loop on rc 3 rather
than SIGTERM-kill a long gate.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tpulsar.aot import cachedir  # noqa: E402  (stdlib-only)

# the one cache-dir resolution (JAX_COMPILATION_CACHE_DIR when set,
# else <repo>/.jax_cache)
cachedir.activate()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--accel", action="store_true",
                    help="also compile the hi-accel correlation block")
    ap.add_argument("--config", type=int, default=0,
                    help="compile the focused bench config's programs "
                         "(1/3/4, matching bench.run_focused_config) "
                         "instead of the headline survey-plan set — "
                         "the gate must compile exactly what will "
                         "execute")
    ap.add_argument("--fast", action="store_true",
                    help="gate only the MAXIMAL-footprint programs: "
                         "the ds=1 step (whole-block shapes dominate "
                         "every higher-downsamp variant of the same "
                         "program) plus the largest budget-capped "
                         "sp/spectrum chunk across steps.  The "
                         "skipped ds>1 programs are the same code at "
                         "strictly smaller block shapes and "
                         "budget-capped chunk bytes, so an "
                         "over-budget program cannot hide among "
                         "them.  Used by bench.py's pre-flight so a "
                         "cold-cache gate cannot eat the measured "
                         "run's deadline (~7 compiles instead of "
                         "~26)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="soft time budget in seconds, checked BETWEEN "
                         "compiles: once elapsed, remaining programs "
                         "are deferred and the tool exits rc 3 so the "
                         "caller can re-run (warm cache makes the "
                         "finished prefix instant).  0 = no deadline")
    ap.add_argument("--verify", action="store_true",
                    help="verify instead of gate: compile the same "
                         "set against the existing warm-start "
                         "manifest and exit 1 if any program misses "
                         "the persistent cache (= would have "
                         "recompiled in-line during a measured run)")
    ap.add_argument("--only", default="",
                    help="comma-separated substrings; gate only the "
                         "registry programs / instance labels that "
                         "match (tests and targeted re-gates)")
    args = ap.parse_args()

    from tpulsar.aot import warmstart

    only = tuple(s for s in args.only.split(",") if s.strip())
    return warmstart.run_gate(
        scale=args.scale, accel=args.accel, config=args.config,
        fast=args.fast, deadline=args.deadline, only=only,
        verify=args.verify)


if __name__ == "__main__":
    sys.exit(main())
