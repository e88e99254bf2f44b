"""The central TPULSAR_* env-knob registry.

Every ``os.environ``/``os.getenv`` read of a ``TPULSAR_*`` name
inside the ``tpulsar/`` package must be declared here — the static
contract linter (``tpulsar lint --checker env-knobs``) fails an
undeclared read, a declared-but-never-read entry, and any drift
between this registry and the docs/configuration.md knob table.
Before this registry the knobs lived only at their ~30 scattered
read sites; an operator auditing a deployment had to grep.

The registry is data, not mechanism: read sites keep their local
parsing/validation (a knob like TPULSAR_ACCEL_Z_CHUNK validates
loudly at its site with kernel-specific context the registry cannot
know).  What the registry buys is the closed world: the name set,
types, defaults, and one-line docs in one table, and the docs table
rendered from it instead of maintained by hand:

    python -m tpulsar.config.knobs        # markdown rows to stdout

Bench/campaign harness knobs (TPULSAR_BENCH_*, TPULSAR_SERVE_* etc.
read only by bench.py / tools/) are deliberately out of scope: they
configure the measurement harness, not the pipeline, and are
documented in bench.py's docstring.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared env knob: ``type`` is the operator-facing value
    shape (flag / int / float / str / path / enum / spec), ``default``
    the effective value when unset, ``doc`` the one-line meaning."""
    name: str
    type: str
    default: str
    doc: str


def _k(name: str, type: str, default: str, doc: str) -> Knob:
    return Knob(name, type, default, doc)


#: the registry, alphabetical by name
KNOBS: dict[str, Knob] = {k.name: k for k in (
    _k("TPULSAR_ACCEL_BATCH", "enum(0|1)", "auto",
       "pin the hi-accel path: 0 = per-DM row dispatch, 1 = batched "
       "DM chunks; unset = batched until the batch breaker trips"),
    _k("TPULSAR_ACCEL_BATCH_BREAKER", "int", "4",
       "consecutive refused batched hi-accel chunk dispatches before "
       "the batched path is pinned off for the process; below it each "
       "refused batch degrades alone (retry, then its rows ride the "
       "per-trial ladder)"),
    _k("TPULSAR_ACCEL_BREAKER_THRESHOLD", "int", "8",
       "consecutive refused accel row dispatches before the circuit "
       "breaker opens and routes remaining rows to host rescue"),
    _k("TPULSAR_ACCEL_DISPATCH_DEADLINE_S", "float", "0 (off)",
       "per-dispatch watchdog for hi-accel row/chunk programs; a "
       "stalled call is classified as a refusal (retry -> rescue) "
       "instead of hanging the beam"),
    _k("TPULSAR_ACCEL_HBM_GB", "float", "4",
       "GiB one hi-accel chunk program may hold live (its planes "
       "and what its correlation holds beside them, "
       "accel.plane_row_bytes): bounds its DM rows; on a TPU a row "
       "over it is refused"),
    _k("TPULSAR_ACCEL_NATIVE", "enum(0)", "on",
       "0 disables the native host accel consumer (CPU backend), "
       "keeping the pure XLA dispatch path"),
    _k("TPULSAR_ACCEL_PLANE_DTYPE", "enum(auto|f32|bf16)", "auto",
       "storage dtype of the accel power plane: auto = bf16 on "
       "accelerators (half the HBM), f32 on CPU (PRESTO parity)"),
    _k("TPULSAR_ACCEL_SYNC_WINDOW", "int", "32",
       "hi-accel chunk programs enqueued before one blocking drain "
       "(1 serializes dispatch and fetch)"),
    _k("TPULSAR_ACCEL_Z_CHUNK", "int [1,64]", "auto",
       "forced z-axis chunk height of the accel correlation's FFT "
       "form (plane-memory / dispatch-count trade): the CPU's "
       "programs; a TPU's programs correlate directly "
       "(accel.corr_plane) and have no z pieces"),
    _k("TPULSAR_ALERT_INTERVAL_S", "float", "5",
       "health-doctor detector tick period inside the fleet "
       "controller and `tpulsar doctor --watch`; <= 0 disables the "
       "hosted detector"),
    _k("TPULSAR_ALERT_NOTIFY", "spec", "log",
       "alert notifier fan-out: log | webhook:<url> | "
       "command:<argv> (alert JSON POSTed / piped on stdin); "
       "unknown schemes fail loudly at configure"),
    _k("TPULSAR_ALERT_RULES", "path", "unset (built-in pack)",
       "JSON alert-rules file extending (or with replace=true, "
       "replacing) the built-in rule pack; load failures are loud"),
    _k("TPULSAR_BEAM_BATCH", "int", "0 (planner budget)",
       "pin the largest coalesced beam group of the batch-of-beams "
       "search (kernels/beam_batch.py): 1 = coalescing off (every "
       "beam runs the solo path), 0/unset = the working-set budget "
       "decides; group sizes snap to the BATCH_QUANTA ladder either "
       "way"),
    _k("TPULSAR_BEAM_BATCH_BYTES", "int (bytes)",
       "8589934592 (8 GiB)",
       "coalesced working-set budget the beam-batch planner sizes B "
       "against (B resident channel blocks + B*chunk spectral "
       "transients, x2 chunks in flight)"),
    _k("TPULSAR_BENCH_DTYPE", "str", "uint8",
       "synthetic-beam sample dtype the AOT registry's program "
       "signatures assume (shared by bench.py so the gate compiles "
       "what the measured run executes)"),
    _k("TPULSAR_BLACKBOX", "enum(0)", "on",
       "0 disables the per-worker flight recorder (the in-memory "
       "ring dumped to <spool>/blackbox/ on crash or abnormal "
       "exit)"),
    _k("TPULSAR_BLACKBOX_RING", "int", "256",
       "flight-recorder ring size: how many recent journal appends/"
       "heartbeats/claims a worker keeps in memory for its crash "
       "dump"),
    _k("TPULSAR_BLOB_ROOT", "path", "unset (<spool>/blobs when "
       "serving)",
       "content-addressed blob-store root the gateway mounts at "
       "/v1/blobs and workers push result artifacts into; a "
       "--blob-root flag beats it"),
    _k("TPULSAR_CHAOS_SCHEDULE", "path", "unset",
       "chaos fault-schedule file this process's faults layer "
       "polls (injected into workers by the chaos conductor)"),
    _k("TPULSAR_CHAOS_TENANTS", "str (JSON)", "unset",
       "tenant table for chaos stub workers (same shape as "
       "frontdoor.tenants), injected by the conductor"),
    _k("TPULSAR_CHAOS_WORKER", "str", "unset",
       "this process's worker id for chaos schedule matching "
       "('*' entries match everyone)"),
    _k("TPULSAR_CONFIG", "path", "unset (built-in defaults)",
       "config file path; the CLI exports it so queue-launched "
       "workers inherit the operator's settings"),
    _k("TPULSAR_DATA_URL", "str (URL)", "unset (shared-disk paths)",
       "gateway base URL workers fetch by-digest `blobs:` ticket "
       "refs from at stage-in and push result artifacts to — the "
       "spool-less data plane; unset keeps the shared-filesystem "
       "path contract"),
    _k("TPULSAR_FAULTS", "spec", "unset",
       "deterministic fault-injection spec: point:mode[:k=v,..] "
       "(';'-separated); unknown points/modes fail loudly at parse"),
    _k("TPULSAR_GATEWAY_TOKEN", "str", "unset (open gateway)",
       "shared-secret bearer token: when set, every mutating "
       "gateway route (beam POST, blob PUT) answers 401 without "
       "`Authorization: Bearer <token>`; clients and the CLI read "
       "the same knob to send it"),
    _k("TPULSAR_HEARTBEAT_MAX_AGE_S", "float", "120",
       "heartbeat staleness window for every serve/fleet freshness "
       "judgment (config jobpooler.heartbeat_max_age_s wins over "
       "this env override)"),
    _k("TPULSAR_HOST_RESCUE", "enum(0)", "on",
       "0 disables host-CPU recompute of refused accel rows, "
       "restoring the zero-fill degrade path"),
    _k("TPULSAR_PALLAS", "enum(0|1)", "auto",
       "0 disables the Pallas dedispersion kernels, 1 forbids the "
       "XLA fallback off the chip too (CI no-fallback mode); unset "
       "= on exactly on a TPU backend, where a kernel fault always "
       "fails the beam"),
    _k("TPULSAR_PALLAS_SB", "enum(0|1)", "auto",
       "stage-1 (subband) Pallas tier override, after "
       "TPULSAR_PALLAS gates both tiers"),
    _k("TPULSAR_PROFILE", "path", "unset",
       "directory for a JAX profiler trace of the search block"),
    _k("TPULSAR_QUEUE_BUSY_TIMEOUT_S", "float", "5 (resilience "
       "policy timeout_s when configured)",
       "SQLite ticket-queue lock-wait budget: connect timeout and "
       "PRAGMA busy_timeout of every queue.db connection (contended "
       "multi-worker claims wait this long before SQLITE_BUSY)"),
    _k("TPULSAR_QUEUE_URL", "str (URL)", "unset (the spool)",
       "deployment-wide default ticket-queue backend for serve/"
       "fleet/gateway: sqlite:<path> or spool:<dir>; a --queue flag "
       "beats it, the spool remains the scratch/log root either "
       "way"),
    _k("TPULSAR_SP_DETREND", "enum(median|clipped_mean)",
       "median (via params)",
       "single-pulse detrend estimator; the env beats SearchParams "
       "beats the default (the on-chip A/B knob)"),
    _k("TPULSAR_STAGE_HEARTBEAT", "path", "unset",
       "file touched at every stage boundary; bench.py's supervisor "
       "uses it to tell a hung dispatch from a slow run"),
    _k("TPULSAR_STAGE_TRACE", "enum(1)", "off",
       "1 prints a flushed begin/end line per search stage to "
       "stderr (hang localization)"),
    _k("TPULSAR_STREAM_CHUNK_DEADLINE_S", "float (seconds)", "30.0",
       "streaming per-chunk ingest->trigger latency SLO: the "
       "default a stream ticket inherits when it names no slo_s; "
       "breaches are journaled on chunk_received and judged by the "
       "trigger_latency_bounded chaos invariant"),
    _k("TPULSAR_STREAM_IDLE_TIMEOUT_S", "float (seconds)", "60.0",
       "session idle timeout: a stream worker abandons a session "
       "(failed result, releasing the ticket) when neither a new "
       "chunk frame nor the close marker lands within this window"),
    _k("TPULSAR_STREAM_RING_CHUNKS", "int (chunks)", "4",
       "trigger span depth: completed chunks accumulated per "
       "single-pulse search span (the stream ticket's span_chunks "
       "beats it); larger rings amortize the boxcar ladder, "
       "smaller rings tighten trigger latency"),
    _k("TPULSAR_TRACE", "enum(1)", "off",
       "1 enables the per-beam span tracer (writes "
       "<basenm>_trace.json Chrome-trace output)"),
    _k("TPULSAR_TRACE_SYNC", "enum(1)", "off",
       "1 fences chunk scopes with block_until_ready for device "
       "attribution (serializes the pipeline it measures)"),
    _k("TPULSAR_WHITEN_ESTIMATOR", "enum(median|clipped_mean)",
       "median",
       "FFT whitening noise estimator (clipped_mean is the "
       "sort-free on-chip variant, opt-in pending its candidate "
       "A/B)"),
    _k("TPULSAR_WORKDIR_BASE", "path", "system tempdir",
       "base directory for per-job scratch workspaces "
       "(tempfile.mkdtemp parent)"),
)}


def render_markdown() -> str:
    """The docs/configuration.md knob table body — regenerate with
    ``python -m tpulsar.config.knobs`` whenever KNOBS changes (the
    env-knobs lint checker fails on any drift)."""
    lines = ["| Variable | Type | Default | Effect |",
             "|---|---|---|---|"]
    for knob in sorted(KNOBS.values(), key=lambda k: k.name):
        typ = knob.type.replace("|", "\\|")   # keep cells intact
        lines.append(f"| `{knob.name}` | {typ} | "
                     f"{knob.default} | {knob.doc} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(render_markdown())
