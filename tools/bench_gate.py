#!/usr/bin/env python
"""bench/v2 regression gate: compare a fresh run against a baseline.

Usage:
    python tools/bench_gate.py <baseline.json> <candidate.json>
        [--default-tol 0.5] [--key PATH[:lower|higher][:TOL]] ...
        [--json]

The repo carries 20+ committed ``BENCH_*.json`` baselines but nothing
compares a new run against them automatically — this tool is that
gate.  Both files must be bench/v2 records (the one stdout JSON line
``bench.py`` emits).  Compared keys, each with a DIRECTION (which way
is worse) and a relative tolerance:

  * ``value`` — the headline; direction inferred from ``unit``
    (seconds-flavoured units: lower is better; rates/speedups:
    higher is better);
  * every ``stage_rollup.<span>.seconds`` present in both records
    (lower is better);
  * well-known serve/fleet sub-keys (``serve.warm_steady_state_s``,
    ``serve.cold_first_beam_s``, ``fleet.speedup_vs_one_worker_warm``,
    ``fleet.two_worker.aggregate_warm_beams_per_s``, ...);
  * any ``--key`` extras (dotted paths; ``:lower``/``:higher`` and a
    per-key tolerance override the defaults).

A key is a REGRESSION when the candidate is worse than the baseline
by more than the tolerance: for lower-is-better,
``cand > base * (1 + tol)``; for higher-is-better,
``cand < base / (1 + tol)``.  Improvements always pass (and are
listed).  Keys missing from either record are skipped with a note —
bench/v2 is additive, so an old baseline simply gates fewer keys.
Exit 0 = no regressions, 1 = at least one, 2 = unusable input.

CI runs this at CPU-smoke scale against a committed smoke baseline
with a generous tolerance (runner speeds vary; the gate is for
catastrophic regressions — a silent recompile, a serialized prefetch
— not single-digit drift).  JAX-free and numpy-free: runs anywhere.
"""

from __future__ import annotations

import argparse
import json
import sys

#: seconds-flavoured units (headline ``value`` direction inference)
_LOWER_UNITS = ("s", "seconds", "ms")
_HIGHER_UNITS = ("beams/s", "trials/s", "/s", "x", "ratio")

#: well-known bench/v2 sub-keys gated by default when present in both
#: records: (dotted path, direction)
DEFAULT_KEYS = (
    ("serve.warm_steady_state_s", "lower"),
    ("serve.cold_first_beam_s", "lower"),
    # serve.warm_vs_cold_process_speedup is deliberately absent: no
    # committed baseline carries it (the smoke baseline runs with
    # TPULSAR_SERVE_COLD=0, cold_process_beam_s null), and the lint
    # bench-keys checker fails any DEFAULT_KEYS row that resolves in
    # no baseline — re-add it together with a baseline that has it
    ("fleet.speedup_vs_one_worker_warm", "higher"),
    ("fleet.two_worker.aggregate_warm_beams_per_s", "higher"),
    ("fleet.scaling_efficiency_vs_host_ceiling", "higher"),
    ("accel.batched.dm_trials_per_sec", "higher"),
    ("accel.per_dm.dm_trials_per_sec", "higher"),
    ("accel.speedup", "higher"),
    ("beambatch.batched.beams_per_sec", "higher"),
    ("beambatch.solo.beams_per_sec", "higher"),
    ("beambatch.speedup", "higher"),
    ("gateway.submit_to_result_p50_s", "lower"),
    ("gateway.submit_to_result_warm_s", "lower"),
    ("gateway.status_http_ms", "lower"),
    ("chaos.mttr_s", "lower"),
    ("chaos.takeover_latency_s", "lower"),
    ("chaos.e2e_p95_chaos_s", "lower"),
    ("chaos.e2e_p95_clean_s", "lower"),
    ("resume.wasted_compute_s", "lower"),
    ("resume.wasted_reduction", "higher"),
    ("resume.mttr_s", "lower"),
    ("autoscale.cost_per_beam_ws", "lower"),
    ("autoscale.queue_wait_p95_s", "lower"),
    ("autoscale.cost_saving", "higher"),
    ("queue.spool.tickets_per_s", "higher"),
    ("queue.sqlite.tickets_per_s", "higher"),
    ("doctor.tick_overhead_s", "lower"),
    ("doctor.detection_latency_s", "lower"),
    ("dataplane.stagein_mb_per_s", "higher"),
    ("dataplane.candidates_query_ms", "lower"),
    # stream.parity_ok is a bool — lookup() excludes it, so CI
    # asserts it directly instead of gating it with a tolerance
    ("stream.chunk_latency_p95_s", "lower"),
    ("stream.chunks_per_sec", "higher"),
)


def lookup(rec: dict, path: str):
    cur = rec
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) \
        and not isinstance(cur, bool) else None


def value_direction(rec: dict) -> str | None:
    unit = str(rec.get("unit", "")).strip()
    if unit in _LOWER_UNITS:
        return "lower"
    if unit in _HIGHER_UNITS or unit.endswith("/s"):
        return "higher"
    return None


def gate_keys(base: dict, cand: dict,
              extra: list[tuple[str, str | None, float | None]] = ()
              ) -> list[tuple[str, str, float | None]]:
    """The (path, direction, tolerance-override) list to compare."""
    keys: list[tuple[str, str, float | None]] = []
    direction = value_direction(base)
    if direction is not None:
        keys.append(("value", direction, None))
    roll_b = base.get("stage_rollup") or {}
    roll_c = cand.get("stage_rollup") or {}
    for span in sorted(set(roll_b) & set(roll_c)):
        keys.append((f"stage_rollup.{span}.seconds", "lower", None))
    for path, d in DEFAULT_KEYS:
        keys.append((path, d, None))
    for path, d, tol in extra:
        if d is None:
            # a tolerance-only override must NOT reset a known key's
            # direction (flipping higher-is-better to lower would
            # turn a collapse into a reported improvement)
            d = next((kd for kp, kd, _ in keys if kp == path),
                     "lower")
        keys = [k for k in keys if k[0] != path]   # override wins
        keys.append((path, d, tol))
    return keys


def compare(base: dict, cand: dict, keys, default_tol: float
            ) -> dict:
    """{regressions: [...], improvements: [...], passed: [...],
    skipped: [...]} — each entry {key, base, cand, ratio, tol}."""
    out = {"regressions": [], "improvements": [], "passed": [],
           "skipped": []}
    for path, direction, tol in keys:
        tol = default_tol if tol is None else tol
        b, c = lookup(base, path), lookup(cand, path)
        if b is None or c is None or b <= 0 or c <= 0:
            # -1 sentinels, missing keys, additive-schema gaps
            out["skipped"].append({"key": path, "base": b, "cand": c})
            continue
        ratio = c / b
        entry = {"key": path, "direction": direction,
                 "base": round(b, 4), "cand": round(c, 4),
                 "ratio": round(ratio, 3), "tol": tol}
        if direction == "lower":
            worse, better = ratio > 1.0 + tol, ratio < 1.0
        else:
            worse, better = ratio < 1.0 / (1.0 + tol), ratio > 1.0
        if worse:
            out["regressions"].append(entry)
        elif better:
            out["improvements"].append(entry)
        else:
            out["passed"].append(entry)
    return out


def _parse_key_spec(spec: str):
    parts = spec.split(":")
    path = parts[0]
    direction = None
    tol = None
    for p in parts[1:]:
        if p in ("lower", "higher"):
            direction = p
        else:
            tol = float(p)
    return path, direction, tol


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="committed BENCH_*.json")
    ap.add_argument("candidate", help="fresh bench.py output (the "
                                      "one stdout JSON line)")
    ap.add_argument("--default-tol", type=float, default=0.5,
                    help="relative tolerance for keys without an "
                         "override (0.5 = fail past 1.5x worse)")
    ap.add_argument("--key", action="append", default=[],
                    metavar="PATH[:lower|higher][:TOL]",
                    help="extra (or overriding) dotted key to gate")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    args = ap.parse_args(argv)

    recs = []
    for path in (args.baseline, args.candidate):
        try:
            with open(path) as fh:
                recs.append(json.load(fh))
        except (OSError, ValueError) as e:
            print(f"bench_gate: cannot read {path}: {e}",
                  file=sys.stderr)
            return 2
    base, cand = recs
    for name, rec in (("baseline", base), ("candidate", cand)):
        if rec.get("schema") != "bench/v2":
            print(f"bench_gate: {name} is not a bench/v2 record "
                  f"(schema={rec.get('schema')!r})", file=sys.stderr)
            return 2
    if base.get("metric") != cand.get("metric"):
        print(f"bench_gate: metric mismatch: baseline "
              f"{base.get('metric')!r} vs candidate "
              f"{cand.get('metric')!r}", file=sys.stderr)
        return 2

    extra = [_parse_key_spec(s) for s in args.key]
    # an EXPLICITLY requested key that the baseline cannot resolve is
    # unusable input, not a skippable gap: the operator named the key,
    # so a typo'd path (or a baseline from before the key existed)
    # must fail loudly with the key's name instead of silently gating
    # nothing.  DEFAULT_KEYS stay additive-schema skips — an old
    # baseline simply gates fewer keys (the lint bench-keys checker
    # guards those against going dead repo-wide at commit time).
    missing = [path for path, _, _ in extra
               if lookup(base, path) is None]
    if missing:
        for path in missing:
            print(f"bench_gate: --key {path!r} does not resolve to "
                  f"a number in baseline {args.baseline}",
                  file=sys.stderr)
        return 2
    result = compare(base, cand, gate_keys(base, cand, extra),
                     args.default_tol)
    result["metric"] = base.get("metric")
    result["ok"] = not result["regressions"]
    if args.json:
        print(json.dumps(result, indent=1, sort_keys=True))
    else:
        print(f"bench gate: {result['metric']} "
              f"(default tol {args.default_tol:g})")
        for kind, mark in (("regressions", "REGRESSION"),
                           ("improvements", "better"),
                           ("passed", "ok")):
            for e in result[kind]:
                print(f"  [{mark:>10s}] {e['key']}: "
                      f"{e['base']} -> {e['cand']} "
                      f"({e['ratio']}x, {e['direction']} is better, "
                      f"tol {e['tol']:g})")
        for e in result["skipped"]:
            print(f"  [{'skip':>10s}] {e['key']}: "
                  f"base={e['base']} cand={e['cand']}")
        print("PASS" if result["ok"] else "FAIL: "
              f"{len(result['regressions'])} regression(s)")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
