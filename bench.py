#!/usr/bin/env python
"""tpulsar benchmark: full PALFA Mock survey-plan search of one beam.

Measures the headline metric from BASELINE.json: wall-clock to search
one Mock-spectrometer-scale beam (960 channels, ~4.3 min at 65.5 us)
over the full hardcoded survey dedispersion plan (6 steps, 57 passes,
1272 DM trials — reference: PALFA2_presto_search.py:319-326) including
RFI masking, subbanding, dedispersion, single-pulse search, rfft +
whitening + 16-harmonic summing, zmax=50 acceleration search, sifting,
and folding of the top candidates.

The reference's implicit baseline is hours per beam on one CPU core
(walltime heuristic 50 h/GB, moab.py:14); the driver-defined target is
60 s (BASELINE.md).  vs_baseline = target_seconds / measured_seconds
(>1 means faster than target).

Hang resistance (the TPU chip in this environment can wedge so hard
that jax.devices() never returns): the parent process never imports
jax.  It first health-probes the chip in a subprocess under a hard
timeout, then runs the measured search in a second subprocess under a
deadline, killing it if it stalls.  Per-pass progress goes to stderr
and to `bench_partial.jsonl`, so even a killed run leaves evidence.
The parent ALWAYS prints exactly one JSON line on stdout.

Environment knobs:
  TPULSAR_BENCH_SCALE     fraction of the full beam length (default 1.0)
  TPULSAR_BENCH_ACCEL     "0" to skip the zmax>0 acceleration stage
  TPULSAR_BENCH_DTYPE     device block dtype: uint8 (default) | bfloat16
  TPULSAR_BENCH_NBEAMS    search N beams back-to-back (default 1): the
                          first beam pays all compiles, the rest measure
                          the amortized steady-state rate (BASELINE
                          config 5, the 8-beam batch)
  TPULSAR_BENCH_PROBE_TIMEOUT  health-probe timeout, s (default 180)
  TPULSAR_BENCH_DEADLINE  measured-run hard deadline, s (default 900)
  TPULSAR_BENCH_TOTAL_BUDGET   target ceiling on the parent's TOTAL
                          wall-clock, s (default 900): every phase's
                          timeout is clamped to the remaining budget
                          so the one JSON line appears within roughly
                          the budget (kill/drain slop can add ~30 s;
                          set an outer driver timeout with margin —
                          round 1 was killed by an outer timeout
                          before it could print anything)
  TPULSAR_BENCH_AOT_BUDGET     internal AOT-gate time cap, s (default
                          600)
  TPULSAR_BENCH_STALL     seconds without a stage heartbeat (or a new
                          bench_partial pass record) before the
                          measured child is declared hung and killed
                          early (default 1200, floor 300); the hard
                          deadline still applies regardless
  TPULSAR_BENCH_AOT       "0" to skip the mandatory compile-only AOT
                          memory gate (tools/aot_check.py) that runs
                          between the health probe and any full-scale
                          execute.  The gate exists because a runtime
                          HBM OOM wedges this chip for hours while a
                          compile-stage error is clean — an over-budget
                          program must die in the compiler, never on
                          the device (round-2 lesson: one 70 GB
                          program cost the whole round's TPU access)
  TPULSAR_BENCH_LADDER    "0" to skip the measured scale ladder
                          (0.1 -> 0.5) that runs before the full-scale
                          beam on TPU, so even a failed full-scale run
                          leaves real TPU datapoints
  TPULSAR_BENCH_CONFIG    focused BASELINE.json config instead of the
                          headline full search:
                            1  rfifind + dedispersion only, 128 DM trials
                            3  accelsearch zmax=200 numharm=16
                            4  single-pulse boxcar search only
                          (config 2 IS the headline with ACCEL=0;
                           config 5 is NBEAMS=8)
"""

import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

# the one cache-dir resolution (tpulsar.aot.cachedir:
# JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache) — the
# gate and the measured child must warm the same cache
from tpulsar.aot import cachedir as _aot_cachedir  # noqa: E402

_aot_cachedir.activate()

TARGET_SECONDS = 60.0   # BASELINE.json north-star target (v5e-4)

#: every record bench.py emits (headline, focused configs, and error
#: records) carries this schema tag plus, for measured runs, a
#: "stage_rollup" {span: {seconds, count}} from the telemetry span
#: tracer — BENCH_*.json artifacts from different rounds become
#: comparable instead of bespoke one-offs.  The schema is documented
#: in docs/operations.md ("bench/v2 schema"); new keys only, so
#: consumers of the single stdout JSON line keep working.
BENCH_SCHEMA = "bench/v2"


def _emit(result: dict) -> None:
    """The one stdout JSON line, schema-tagged."""
    result.setdefault("schema", BENCH_SCHEMA)
    print(json.dumps(result), flush=True)

# beam geometry shared with the AOT gate's shape-builders — ONE
# declaration (tpulsar/aot/registry.py; stdlib-only import), so the
# gate compiles exactly the shapes the measured child executes.
# T_FULL (~257 s observation) is divisible by every plan downsamp
# (1,2,3,5,6,10) with a rich 2^k factor; NSAMP_QUANTUM preserves that
# divisibility under TPULSAR_BENCH_SCALE.
from tpulsar.aot.registry import (  # noqa: E402
    BW, FCTR, NCHAN, NSAMP_QUANTUM, T_FULL, TSAMP)

# DM 220 sits in the FIRST pass of the survey plan's second step, so
# the injected pulsar stays inside the searched DM range even when
# TPULSAR_BENCH_SCALE shrinks each step's pass count (the reduced-scale
# CPU fallback run was missing it at DM 250: its truncated step only
# reached DM ~236).
P_TRUE, DM_TRUE = 0.012345, 220.0

PARTIAL_PATH = os.path.join(_REPO, "bench_partial.jsonl")


def _log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# --------------------------------------------------------------- child: probe

_PROBE_SRC = (
    "import json, time\n"
    "t0 = time.time()\n"
    "import jax\n"
    "d = jax.devices()\n"
    "t_dev = time.time() - t0\n"
    "import jax.numpy as jnp\n"
    "t1 = time.time()\n"
    "(jnp.ones((256, 256)) @ jnp.ones((256, 256)))"
    ".block_until_ready()\n"
    "print(json.dumps({'ok': True, 'platform': d[0].platform,"
    " 'ndev': len(d), 'device': str(d[0]),"
    " 'devices_s': round(t_dev, 1),"
    " 'matmul_s': round(time.time() - t1, 1)}))\n")


def probe_device(timeout: float, force_cpu: bool = False) -> dict | None:
    """Run jax.devices() + a tiny matmul in a subprocess under a hard
    timeout, from this parent that never touches jax.  Returns the
    probe record, or None on a hang, crash or nonsense output."""
    from tpulsar import cpu_subprocess_env

    env = cpu_subprocess_env() if force_cpu else dict(os.environ)
    try:
        out = subprocess.run([sys.executable, "-c", _PROBE_SRC],
                             env=env, capture_output=True, text=True,
                             timeout=timeout)
    except (subprocess.TimeoutExpired, OSError) as e:
        _log(f"probe failed: {e}")
        return None
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("ok"):
            return rec
    _log(f"probe failed: rc={out.returncode}: "
         + (out.stderr or "").strip()[-300:])
    return None


# ---------------------------------------------------------- child: measured run

def _bench_dtype_name() -> str:
    """Validated TPULSAR_BENCH_DTYPE value, with NO jax import — the
    parent process must be able to fail fast on a misconfig without
    dialing the accelerator runtime (import jax hangs on a wedged
    chip).  Delegates to the AOT registry, the ONE place the knob is
    interpreted (the measured child, the focused configs, and the
    gate's shape-builders must all agree on the dtype or the gate
    compiles programs that never execute)."""
    from tpulsar.aot.registry import block_dtype_name

    return block_dtype_name()


def _bench_dtype():
    """Device block dtype as a jnp dtype (see _bench_dtype_name)."""
    from tpulsar.aot.registry import block_dtype

    return block_dtype()


def gen_block_chunk(key, delay_chunk, n: int, nc: int, dtype):
    """The jitted per-channel-chunk beam synthesizer (noise + one
    injected pulsar, quantized to the device dtype).  Module-level so
    tools/aot_check.py can compile-check the EXACT program the
    measured run executes."""
    import jax
    import jax.numpy as jnp

    t = jnp.arange(n, dtype=jnp.float32) * TSAMP
    noise = 8.0 + 2.0 * jax.random.normal(key, (nc, n), jnp.float32)
    phase = ((t[None, :] - delay_chunk[:, None]) / P_TRUE) % 1.0
    dph = jnp.minimum(phase, 1.0 - phase)
    x = noise + jnp.exp(-0.5 * (dph / 0.02) ** 2)
    return jnp.clip(jnp.round(x), 0, 15).astype(dtype)


def make_block_device(nsamp: int, seed: int = 42, chan_chunk: int = 120,
                      dtype=None):
    """(NCHAN, nsamp) beam on device in the bench dtype: noise + one
    injected pulsar.  Generated on-accelerator in float32 channel
    chunks so the host never materializes multi-GB float64 noise
    (round-1 weakness: the old NumPy path burned minutes of untimed
    wall-clock)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from tpulsar.constants import dispersion_delay_s

    if dtype is None:
        dtype = _bench_dtype()
    freqs = (FCTR - BW / 2) + (np.arange(NCHAN) + 0.5) * (BW / NCHAN)
    delays = dispersion_delay_s(DM_TRUE, freqs, freqs[-1]).astype(np.float32)

    gen = partial(jax.jit, static_argnames=("n", "nc", "dtype"))(
        gen_block_chunk)
    key = jax.random.PRNGKey(seed)
    parts = []
    for c0 in range(0, NCHAN, chan_chunk):
        nc = min(chan_chunk, NCHAN - c0)
        key, sub = jax.random.split(key)
        parts.append(gen(sub, jnp.asarray(delays[c0:c0 + nc]), n=nsamp,
                         nc=nc, dtype=dtype))
    return jnp.concatenate(parts, axis=0)


def run_focused_config(cfg: int) -> None:
    """Focused BASELINE.json configs 1/3/4: time one stage on the
    full-length beam (config 2 is the headline with the accel stage
    off; config 5 is the headline with TPULSAR_BENCH_NBEAMS=8)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    want = os.environ.get("JAX_PLATFORMS", "").strip()
    if want:
        jax.config.update("jax_platforms", want)

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.obs import telemetry
    from tpulsar.obs import trace as trace_lib
    from tpulsar.search.report import StageTimers

    # span recording on for the measured child: the bench/v2 record
    # embeds the per-stage span rollup
    trace_lib.start(clear=True)

    scale = float(os.environ.get("TPULSAR_BENCH_SCALE", "1.0"))
    nsamp = int(T_FULL * scale)
    nsamp -= nsamp % NSAMP_QUANTUM
    freqs = (FCTR - BW / 2) + (np.arange(NCHAN) + 0.5) * (BW / NCHAN)
    # reset the partial-evidence file so a timed-out focused run's
    # error record cannot absorb a previous headline run's passes
    # (record shape from the shared telemetry event helper — same
    # constructor as the executor's stage heartbeat)
    with open(PARTIAL_PATH, "w") as fh:
        fh.write(json.dumps(telemetry.event_record(
            "start", config=cfg, nsamp=nsamp)) + "\n")
    # Every phase runs in a StageTimers scope: the scopes feed the
    # stage heartbeat, so a focused-config child killed mid-phase
    # still tells the supervising parent WHICH phase it died in
    # (round-4 verdict #2 — the focused configs previously emitted no
    # heartbeats at all and a kill carried no attribution).
    timers = StageTimers()
    with timers.timing("generate"):
        data = make_block_device(nsamp)
        data.block_until_ready()
    dms = np.arange(128) * 2.0
    t0 = time.time()
    if cfg == 1:
        # rfifind + two-stage dedispersion, 128 DM trials
        with timers.timing("rfifind"):
            mask = rfi_k.find_rfi_chan(data, TSAMP, block_len=2048)
            data = rfi_k.apply_mask_chan(
                data, jnp.asarray(mask.full_mask()),
                jnp.asarray(mask.chan_fill), mask.block_len)
        with timers.timing("subbanding"):
            ch_sh, sub_sh = dd.plan_pass_shifts(freqs, 96, 140.0, dms,
                                                TSAMP, 1)
            subb = dd.form_subbands(data, jnp.asarray(ch_sh), 96, 1)
        with timers.timing("dedispersing"):
            out = dd.dedisperse_subbands(subb, jnp.asarray(sub_sh))
            jax.block_until_ready(out)
        metric, extra = "rfifind_dedisperse_128dm_wallclock", {
            "dm_trials": 128}
    elif cfg == 3:
        from tpulsar.kernels import accel as ak
        with timers.timing("dedispersing"):
            ch_sh, sub_sh = dd.plan_pass_shifts(freqs, 96, 140.0,
                                                dms[:32], TSAMP, 1)
            subb = dd.form_subbands(data, jnp.asarray(ch_sh), 96, 1)
            series = dd.dedisperse_subbands(subb, jnp.asarray(sub_sh))
        with timers.timing("FFT"):
            spec = fr.complex_spectrum(series)
            powers, wpow = fr.whitened_powers(spec)
            wspec = fr.scale_spectrum(spec, powers, wpow)
            jax.block_until_ready(wspec)  # upstream must not leak
        # Free the upstream buffers BEFORE timing: with the full
        # 3.8 GB beam + subbands + series resident, XLA:CPU's
        # allocator degrades ~4x on the accel program's multi-GB
        # buffers (measured 2026-07-31: 10.5 s/trial free vs ~53
        # s/trial with the beam block held).  The real executor
        # releases pass buffers the same way.
        del data, subb, series, spec, powers, wpow
        t0 = time.time()               # into the accel-only timing
        try:
            with timers.timing("hi-accelsearch"):
                bank = ak.build_template_bank(200.0)
                res = ak.accel_search_batch(wspec, bank,
                                            max_numharm=16, topk=64)
                jax.block_until_ready(jnp.asarray(res[1][0]))
        except jax.errors.JaxRuntimeError as exc:
            # A runtime has rejected the z200 programs at
            # execution (observed 2026-08-01, cfg3_quarter_f32: the
            # batched path AND the per-DM fallback both raised
            # UNIMPLEMENTED while the z50 survey shapes ran fine).
            # A crashed child records nothing — emit the rung record
            # with the failure named instead.
            _emit({
                "metric": "accelsearch_z200_h16_32dm_wallclock",
                "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                "error": "accel_z200_runtime_rejected",
                "detail": str(exc)[:300], "nsamp": nsamp,
                "device": str(jax.devices()[0]),
                "accel_plane_dtype": _plane_dtype_name(),
                "stage_s": {k: round(v, 2)
                            for k, v in timers.times.items()
                            if v >= 0.005},
            })
            return
        # Plane dtype + a digest of the strongest detections, so two
        # cfg-3 runs with different TPULSAR_ACCEL_PLANE_DTYPE settings
        # are a committed candidate-level A/B, not just a wall-clock
        # one (round-4 advisor: the bf16 'auto' default has never been
        # candidate-compared on chip).
        top_stage = max(res)
        pows, rbins, zvals = (np.asarray(x) for x in res[top_stage])
        order = np.argsort(pows, axis=None)[::-1][:16]
        di, ki = np.unravel_index(order, pows.shape)
        metric, extra = "accelsearch_z200_h16_32dm_wallclock", {
            "dm_trials": 32, "nz": len(bank.zs),
            "accel_plane_dtype": _plane_dtype_name(),
            "top_cands": [[int(d), int(rbins[d, k]),
                           float(zvals[d, k]),
                           round(float(pows[d, k]), 2)]
                          for d, k in zip(di, ki)]}
    elif cfg == 4:
        with timers.timing("dedispersing"):
            ch_sh, sub_sh = dd.plan_pass_shifts(freqs, 96, 140.0, dms,
                                                TSAMP, 1)
            subb = dd.form_subbands(data, jnp.asarray(ch_sh), 96, 1)
            series = dd.dedisperse_subbands(subb, jnp.asarray(sub_sh))
            series.block_until_ready()
        t0 = time.time()            # SP stage only
        with timers.timing("single-pulse"):
            ev = sp_k.single_pulse_search(series, dms, TSAMP)
        metric, extra = "single_pulse_128dm_wallclock", {
            "dm_trials": 128, "events": int(len(ev))}
    else:
        raise SystemExit(f"unknown TPULSAR_BENCH_CONFIG {cfg}")
    elapsed = time.time() - t0
    _emit({
        "metric": metric, "value": round(elapsed, 2), "unit": "s",
        "vs_baseline": round(TARGET_SECONDS / max(elapsed, 1e-9), 3),
        "nsamp": nsamp, "device": str(jax.devices()[0]),
        "stage_s": {k: round(v, 2) for k, v in timers.times.items()
                    if v >= 0.005},
        "stage_rollup": trace_lib.rollup(), **extra,
    })


def _plane_dtype_name() -> str:
    """Resolved hi-accel plane dtype as a record-friendly name."""
    import jax.numpy as jnp
    from tpulsar.kernels import accel as ak

    return str(jnp.dtype(ak.plane_dtype()).name)


def run_measured() -> None:
    """The measured search (runs inside the deadline-guarded child).
    Prints progress to stderr, appends per-pass records to
    bench_partial.jsonl, and prints the result JSON to stdout."""
    # The parent's kill sequence leads with SIGTERM + grace: convert
    # it into SystemExit so the stack unwinds and the device runtime
    # tears its session down instead of dying mid-RPC (the default
    # disposition is as abrupt as SIGKILL).  A child hung inside a C
    # call won't run this until the call returns — that case still
    # ends with the parent's SIGKILL.
    import signal

    def _on_sigterm(signum, frame):
        raise SystemExit("SIGTERM: parent deadline/stall")

    signal.signal(signal.SIGTERM, _on_sigterm)
    cfg_raw = os.environ.get("TPULSAR_BENCH_CONFIG", "").strip()
    if cfg_raw:
        try:
            cfg = int(cfg_raw)
        except ValueError:
            raise SystemExit(
                f"TPULSAR_BENCH_CONFIG must be 1-5, got {cfg_raw!r}")
        if cfg == 2:
            os.environ["TPULSAR_BENCH_ACCEL"] = "0"   # zero-accel search
        elif cfg == 5:
            os.environ.setdefault("TPULSAR_BENCH_NBEAMS", "8")
        elif cfg in (1, 3, 4):
            run_focused_config(cfg)
            return
        else:
            raise SystemExit(
                f"TPULSAR_BENCH_CONFIG must be 1-5, got {cfg_raw!r}")
    import numpy as np

    import jax
    import jax.numpy as jnp

    # pin the live config to the env var (tpulsar.apply_platform_env)
    want = os.environ.get("JAX_PLATFORMS", "").strip()
    if want:
        jax.config.update("jax_platforms", want)
    try:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
    except Exception:
        pass

    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.obs import telemetry
    from tpulsar.obs import trace as trace_lib
    from tpulsar.plan import ddplan
    from tpulsar.search import executor
    from tpulsar.search.report import StageTimers

    # span recording on: beam 0's per-stage rollup is embedded in the
    # bench/v2 record, so every BENCH artifact decomposes the same way
    trace_lib.start(clear=True)

    scale = float(os.environ.get("TPULSAR_BENCH_SCALE", "1.0"))
    run_accel = os.environ.get("TPULSAR_BENCH_ACCEL", "1") != "0"
    nbeams = max(1, int(os.environ.get("TPULSAR_BENCH_NBEAMS", "1")))

    nsamp = int(T_FULL * scale)
    nsamp -= nsamp % NSAMP_QUANTUM  # divisibility by all downsamps
    freqs = (FCTR - BW / 2) + (np.arange(NCHAN) + 0.5) * (BW / NCHAN)
    plan = ddplan.survey_plan("pdev")
    if scale < 0.999:
        # shrink passes proportionally for smoke runs
        plan = [ddplan.DedispStep(s.lodm, s.dmstep, s.dms_per_pass,
                                  max(1, int(s.numpasses * scale)),
                                  s.numsub, s.downsamp) for s in plan]
    params = executor.SearchParams(
        run_hi_accel=run_accel,
        max_cands_to_fold=int(os.environ.get("TPULSAR_BENCH_MAXFOLD",
                                             "20")))
    npasses = sum(s.numpasses for s in plan)

    with open(PARTIAL_PATH, "w") as fh:
        fh.write(json.dumps(telemetry.event_record(
            "start", nsamp=nsamp, npasses=npasses, nbeams=nbeams,
            backend=jax.default_backend())) + "\n")

    per_beam_s = []
    found = False
    for b in range(nbeams):
        _log(f"beam {b}: generating {NCHAN}x{nsamp} block on device")
        timers = StageTimers()
        if b == 0:
            timers0 = timers
        t_gen = time.time()
        # timed scope so a kill during generation attributes to
        # "generate" (untimed, it was a heartbeat blind spot)
        with timers.timing("generate"):
            data = make_block_device(nsamp, seed=42 + b)
            data.block_until_ready()
        _log(f"beam {b}: block ready in {time.time()-t_gen:.1f} s")

        t0 = time.time()
        with timers.timing("rfifind"):
            mask = rfi_k.find_rfi_chan(data, TSAMP, block_len=2048)
            data = rfi_k.apply_mask_chan(
                data, jnp.asarray(mask.full_mask()),
                jnp.asarray(mask.chan_fill), mask.block_len)
            data.block_until_ready()
        _log(f"beam {b}: rfifind done at +{time.time()-t0:.1f} s")

        def progress(rec, _b=b, _t0=t0):
            # shared event constructor: these lines and the stage
            # heartbeat are the two inputs to the parent's stall
            # detector, and one shape builder keeps them in step
            rec = telemetry.event_record(
                "pass", beam=_b,
                elapsed_s=round(time.time() - _t0, 2), **rec)
            with open(PARTIAL_PATH, "a") as fh:
                fh.write(json.dumps(rec) + "\n")
            _log(f"beam {_b}: pass {rec.get('pass_idx', '?')}/"
                 f"{rec.get('npasses', npasses)} "
                 f"(step {rec.get('step_idx', '?')}, "
                 f"{rec.get('ntrials_done', '?')} trials) "
                 f"+{rec['elapsed_s']} s")

        cands, folded, sp_events, ntrials = executor.search_block(
            data, freqs, TSAMP, plan, params, progress_cb=progress,
            timers=timers)
        per_beam_s.append(time.time() - t0)
        _log(f"beam {b}: search done in {per_beam_s[-1]:.1f} s, "
             f"{len(cands)} candidates")

        if b == 0:
            found = any(
                min(abs(c.period_s / P_TRUE - r)
                    for r in (1.0, 0.5, 2.0)) < 0.01
                and abs(c.dm - DM_TRUE) < 10.0
                for c in cands[:10])
            # beam-0 span rollup, captured before beam 1's spans land
            rollup0 = trace_lib.rollup()
        del data

    elapsed = per_beam_s[0]   # headline: one beam incl. compiles
    result = {
        "metric": "mock_beam_full_plan_search_wallclock",
        "value": round(elapsed, 2),
        "unit": "s",
        "vs_baseline": round(TARGET_SECONDS / elapsed, 3),
        "dm_trials": ntrials,
        "dm_trials_per_sec": round(ntrials / elapsed, 1),
        "candidates": len(cands),
        "injected_pulsar_recovered": bool(found),
        "accel_stage": run_accel,
        "nsamp": nsamp,
        "device": str(jax.devices()[0]),
        # dtype of the hi-accel correlation plane: bf16-vs-f32 records
        # are not bit-comparable, so every record names its plane
        # dtype (round-4 advisor finding on the 'auto' default)
        "accel_plane_dtype": _plane_dtype_name() if run_accel else None,
        # beam-0 per-stage wall-clock (the .report breakdown,
        # reference PALFA2_presto_search.py:336-372) so the headline
        # number is decomposable from the one JSON line
        "stage_s": {k: round(v, 2) for k, v in timers0.times.items()
                    if v >= 0.005},
        # beam-0 telemetry span rollup ({span: {seconds, count}}):
        # the same numbers as stage_s where names overlap, plus the
        # structural spans (search_block, dm_chunk) and per-scope
        # counts — the cross-round comparison surface of bench/v2
        "stage_rollup": rollup0,
    }
    if nbeams > 1:
        steady = sum(per_beam_s[1:]) / (nbeams - 1)
        result["nbeams"] = nbeams
        result["steady_state_beam_s"] = round(steady, 2)
        result["beams_per_hour"] = round(3600.0 / steady, 1)
    result.setdefault("schema", BENCH_SCHEMA)
    with open(PARTIAL_PATH, "a") as fh:
        fh.write(json.dumps(telemetry.event_record(
            "done", **result)) + "\n")
    _emit(result)


# ----------------------------------------------------------------- parent

def _read_partial() -> dict:
    """Summarize bench_partial.jsonl for a timed-out/killed run.
    Parsed line-by-line: a SIGKILL mid-append truncates the final line
    and must not discard the evidence before it."""
    info: dict = {}
    lines = []
    try:
        with open(PARTIAL_PATH) as fh:
            for ln in fh:
                try:
                    lines.append(json.loads(ln))
                except json.JSONDecodeError:
                    continue
    except OSError:
        return info
    passes = [r for r in lines if "pass_idx" in r]
    if passes:
        last = passes[-1]
        info["passes_done"] = len(passes)
        info["npasses"] = last.get("npasses")
        info["ntrials_done"] = last.get("ntrials_done")
        info["last_pass_elapsed_s"] = last.get("elapsed_s")
        stage_s = last.get("stage_s")
        if stage_s:
            info["stage_s"] = stage_s
    return info


# Per-stage wall-clock budgets for the TPU path, seconds at FULL
# scale with a warm compilation cache.  Sized as pathology detectors,
# not estimates: on a healthy chip no single stage should approach
# these (the <60 s target needs every stage in seconds), so a stage
# that does is the 2026-07-31 failure mode — one stage silently
# eating ~24 minutes until the global deadline killed the run with no
# attribution.  The budget kill fires in minutes AND names the stage.
# CPU children are exempt (no chip to protect; full-scale CPU stages
# legitimately run 10-20x longer).
_STAGE_BUDGETS = {
    "generate": 360.0, "rfifind": 240.0, "subbanding": 360.0,
    "dedispersing": 420.0, "single-pulse": 420.0, "FFT": 420.0,
    "lo-accelsearch": 600.0, "hi-accelsearch": 900.0,
    "pipeline-wait": 420.0, "pipeline-drain": 600.0,
    "sharded-search": 900.0, "sifting": 300.0, "folding": 600.0,
}
_STAGE_BUDGET_DEFAULT = 600.0


def _stage_budget(stage: str) -> float:
    mult = float(os.environ.get("TPULSAR_STAGE_BUDGET_MULT", "1.0"))
    return _STAGE_BUDGETS.get(stage, _STAGE_BUDGET_DEFAULT) * mult


def _read_heartbeat(hb_path: str) -> dict | None:
    """Parse the child's JSON stage heartbeat ({t, t_stage, stage,
    event, info?}).  Pre-JSON beats (a bare float) and torn reads
    return None — the supervisor then falls back to mtime-only
    staleness, losing attribution but never crashing."""
    try:
        with open(hb_path) as fh:
            rec = json.load(fh)
        return rec if isinstance(rec, dict) else None
    except (OSError, ValueError):
        return None


def _attempt_dir(label: str) -> str:
    """Fresh per-attempt evidence directory under bench_runs/attempts.
    Everything a killed run leaves behind (partial records, the
    child's stderr stage trace, the kill attribution) is archived
    here BEFORE the next attempt truncates the shared working files —
    round 4 destroyed its only on-chip evidence exactly that way."""
    ts = time.strftime("%Y%m%dT%H%M%S")
    d = os.path.join(_REPO, "bench_runs", "attempts",
                     f"{ts}_{os.getpid()}_{label}")
    os.makedirs(d, exist_ok=True)
    return d


def run_child(deadline: float, extra_env: dict | None = None,
              label: str = "run") -> tuple[str, dict | None, dict]:
    """Run the measured search in a subprocess under `deadline`.
    Returns (status, result, info): ("ok", json, info) on success;
    ("timeout"/"stall"/"stage_budget", None, info) when killed —
    at the deadline, after TPULSAR_BENCH_STALL s without any stage
    heartbeat (hung dispatch), or when ONE stage exceeded its
    _STAGE_BUDGETS entry (pathologically slow stage; TPU only);
    ("crash", None, info) on nonzero exit or unparseable output.
    The distinction matters for the evidence record (a 10 s
    ImportError is not a deadline overrun), and `info` always carries
    the attempt archive dir plus, for kills, the stage being executed
    ({stalled_stage, stage_elapsed_s, last_beat}) — a kill without
    attribution destroys the most expensive evidence there is
    (round-4 verdict missing #2)."""
    import shutil

    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    attempt = _attempt_dir(label)
    info: dict = {"attempt_dir": os.path.relpath(attempt, _REPO)}
    # a previous parent may have died before archiving its partials —
    # rescue whatever the shared file still holds before we truncate
    try:
        if os.path.getsize(PARTIAL_PATH) > 0:
            shutil.copy(PARTIAL_PATH,
                        os.path.join(attempt, "partial_inherited.jsonl"))
    except OSError:
        pass
    # Always stage-trace the measured child: when a pass blocks inside
    # a remote device dispatch, the per-pass progress callback never
    # fires, and the trace lines on stderr are the only record of
    # WHICH stage the deadline kill interrupted.
    env.setdefault("TPULSAR_STAGE_TRACE", "1")
    # Stage heartbeat: lets this parent tell a *stalled* child (hung
    # remote dispatch) from a slow but progressing one.  Killing a
    # progressing child mid-dispatch wedges the chip for hours (it
    # did at 04:14 on 2026-07-31), so elapsed time alone must never
    # trigger the kill before the hard deadline.
    env.setdefault(
        "TPULSAR_STAGE_HEARTBEAT",
        os.path.join(tempfile.gettempdir(), f"tpulsar_hb_{os.getpid()}"))
    # Monitor the path the CHILD will actually beat (setdefault keeps
    # a pre-existing env value — monitoring our own default then would
    # see a permanently missing heartbeat and false-stall-kill a
    # healthy run).
    hb_path = env["TPULSAR_STAGE_HEARTBEAT"]
    try:
        os.remove(hb_path)
    except OSError:
        pass
    # Truncate the partial-evidence file BEFORE the child spawns: the
    # child only truncates it after `import jax` completes, so a child
    # killed while importing (the sick-runtime hang) would otherwise
    # report the PREVIOUS child's pass records as its own.
    with open(PARTIAL_PATH, "w") as fh:
        fh.write(json.dumps({"event": "spawn", "t": time.time()}) + "\n")
    on_cpu_child = env.get("JAX_PLATFORMS", "").strip() == "cpu"
    if on_cpu_child:
        # CPU children must not touch the accelerator
        from tpulsar import cpu_subprocess_env
        env = cpu_subprocess_env(env)
    # Child stderr goes to a FILE in the attempt dir, not the parent's
    # stream: the stage-trace lines are kill-attribution evidence and
    # must survive even a SIGKILL of this parent (round 4: the one
    # on-chip run's trace lines never reached the campaign log).  The
    # tail is echoed to our stderr after the child ends so live logs
    # still show it.
    stderr_path = os.path.join(attempt, "child_stderr.log")
    stderr_fh = open(stderr_path, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--measured"],
        env=env, stdout=subprocess.PIPE, stderr=stderr_fh, text=True)

    # Supervise: poll instead of one blocking communicate().  Kill
    # early on a genuine STALL (no stage heartbeat for STALL_S — a
    # hung dispatch never heartbeats again, waiting out the full
    # deadline just delays recovery), and at the hard deadline
    # regardless.  Kill sequence is SIGTERM + grace, then SIGKILL:
    # the runtime gets a chance to tear the device session down
    # cleanly before the hard kill that wedges the chip.
    # Stall threshold: heartbeats land only at stage begin/end and at
    # pass boundaries (bench_partial records), so one long scope — a
    # whole-phase fold/sift, or an in-line compile after the begin
    # beat — is silent for its full duration.  The floor keeps a
    # mis-set env from killing through ordinary scope silence; in-line
    # CPU compiles of the lo-stage program have taken ~10 min on this
    # 1-core host, hence the 1200 s default.
    stall_s = max(300.0, float(os.environ.get("TPULSAR_BENCH_STALL",
                                              "1200")))
    if on_cpu_child:
        # The stall kill exists to protect the CHIP (a hung remote
        # dispatch wedges it for hours).  A CPU-pinned child has no
        # chip to protect, and its full-scale in-line compiles are
        # legitimately silent for 20-40 min on this 1-core host — a
        # stall kill there only destroys evidence (it killed two
        # full-scale config-3 runs on 2026-07-31 before this floor).
        stall_s = max(stall_s, 3600.0)
    t_start = time.time()

    def _hb_age() -> float:
        ages = []
        for p in (hb_path, PARTIAL_PATH):
            try:
                ages.append(time.time() - os.path.getmtime(p))
            except OSError:
                pass
        return min(ages) if ages else time.time() - t_start

    def _attribute_kill(now: float) -> None:
        """Record which stage the kill interrupted, from the JSON
        heartbeat — the field the round-4 on-chip timeout record was
        missing."""
        hb = _read_heartbeat(hb_path)
        if hb is None:
            return
        info["last_beat"] = hb
        stage = hb.get("stage") or "?"
        if hb.get("event") == "end":
            # between timed scopes: silence after a completed stage
            info["stalled_stage"] = f"after:{stage}"
            info["stage_elapsed_s"] = round(now - hb.get("t", now), 1)
        else:
            info["stalled_stage"] = stage
            t_st = hb.get("t_stage") or hb.get("t", now)
            info["stage_elapsed_s"] = round(now - t_st, 1)
        if hb.get("info"):
            info["stage_progress"] = hb["info"]

    def _finish_attempt(status: str, rc=None) -> None:
        """Archive this attempt's evidence before anything truncates
        it, and echo the child's stderr tail to ours for the live
        campaign log."""
        try:
            stderr_fh.close()
        except OSError:
            pass
        try:
            if os.path.getsize(PARTIAL_PATH) > 0:
                shutil.copy(PARTIAL_PATH,
                            os.path.join(attempt, "bench_partial.jsonl"))
        except OSError:
            pass
        rec = {"label": label, "status": status, "rc": rc,
               "deadline_s": deadline, "t_end": time.time(),
               # which backend the child targeted: CPU exploration
               # kills must never read as on-chip attempts in the
               # collected campaign evidence
               "platform": (env.get("JAX_PLATFORMS", "").strip()
                            or "accelerator"),
               "elapsed_s": round(time.time() - t_start, 1), **info}
        try:
            with open(os.path.join(attempt, "attempt.json"), "w") as fh:
                json.dump(rec, fh, indent=1, sort_keys=True)
                fh.write("\n")
        except OSError:
            pass
        try:
            with open(stderr_path) as fh:
                tail = fh.read().splitlines()[-80:]
            for ln in tail:
                print(ln, file=sys.stderr)
            sys.stderr.flush()
        except OSError:
            pass

    reason = None
    while True:
        try:
            out, _ = proc.communicate(timeout=15)
            break
        except subprocess.TimeoutExpired:
            now = time.time()
            elapsed = now - t_start
            hb = _read_heartbeat(hb_path)
            in_stage = None
            if (hb is not None and not on_cpu_child
                    and hb.get("event") in ("begin", "progress")
                    and hb.get("t_stage")):
                in_stage = (hb.get("stage") or "?",
                            now - float(hb["t_stage"]))
            if elapsed > deadline:
                reason, status = f"deadline {deadline:.0f} s", "timeout"
            elif _hb_age() > stall_s:
                reason = (f"stall: no stage heartbeat for "
                          f"{_hb_age():.0f} s (hung dispatch)")
                status = "stall"
            elif (in_stage and in_stage[1] > _stage_budget(in_stage[0])
                    and _hb_age() < 90.0):
                # One pathologically slow stage: kill in minutes WITH
                # attribution instead of waiting out the global
                # deadline (round-4 verdict weak #5).  The freshness
                # guard (_hb_age < 90) restricts this to a PROGRESSING
                # stage — one emitting chunk-drain beats.  A stage
                # silent in a single long scope may be an in-line
                # remote compile (>7 min/program observed) or one huge
                # dispatch, and SIGTERM-killing either wedges the chip
                # for hours (2026-07-31, twice); silence stays the
                # stall detector's job at its compile-safe 1200 s
                # threshold — which now also attributes, via the same
                # heartbeat.
                reason = (f"stage budget: {in_stage[0]} has run "
                          f"{in_stage[1]:.0f} s > "
                          f"{_stage_budget(in_stage[0]):.0f} s "
                          "while actively progressing")
                status = "stage_budget"
            else:
                continue
            _attribute_kill(now)
            _log(f"measured run exceeded {reason} — killing "
                 f"(SIGTERM, 30 s grace, then SIGKILL)")
            proc.terminate()
            try:
                proc.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                try:
                    proc.communicate(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            info["kill_reason"] = reason
            _finish_attempt(status, proc.returncode)
            return status, None, info
    if proc.returncode != 0:
        _log(f"measured run failed rc={proc.returncode}")
        _attribute_kill(time.time())
        _finish_attempt("crash", proc.returncode)
        return "crash", None, info
    for line in reversed((out or "").strip().splitlines()):
        try:
            result = json.loads(line)
        except json.JSONDecodeError:
            continue
        _finish_attempt("ok", 0)
        return "ok", result, info
    _finish_attempt("crash", proc.returncode)
    return "crash", None, info


def run_aot_gate(timeout: float, accel: bool, scale: float,
                 config: int = 0) -> dict:
    """Compile-only AOT memory gate (tools/aot_check.py) in a
    subprocess.  Returns a record {ok, seconds, failures, detail}.
    ok=False means the full-scale programs must NOT be executed on
    the chip this run: either a program failed to compile (likely
    over-budget — the exact failure mode that wedged the chip in
    round 2) or the gate itself hung/crashed, leaving the memory
    question unanswered.

    Headline runs gate with --fast (maximal-footprint programs only):
    the gated compiles land in the shared JAX_COMPILATION_CACHE_DIR,
    while the ~19 smaller skipped programs cold-compile INSIDE the
    measured window.  That is a deliberate tradeoff: a full cold gate
    risks timing out and aborting the whole run with no result,
    whereas fast-gate compile time merely inflates the (explicitly
    compile-inclusive) headline number."""
    cmd = [sys.executable, os.path.join(_REPO, "tools", "aot_check.py"),
           "--scale", str(scale),
           # the tool's own between-compiles deadline: on expiry it
           # exits rc 3 CLEANLY instead of being killed mid-compile
           "--deadline", str(timeout)]
    if config in (1, 3, 4):
        # focused configs compile their own exact program set
        cmd += ["--config", str(config)]
    else:
        # --fast: gate the maximal-footprint programs only, so a
        # cold-cache gate (~7 compiles, not ~26) cannot eat
        # the measured run's deadline
        cmd.append("--fast")
        if accel:
            cmd.append("--accel")
    t0 = time.time()
    try:
        # outer kill = catastrophic backstop only, sized so the one
        # compile in flight when the deadline strikes can still finish
        # and exit cleanly (accel compiles observed >7 min each)
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout + 900.0)
    except subprocess.TimeoutExpired:
        return {"ok": False, "seconds": round(time.time() - t0, 1),
                "detail": f"aot_check hung > {timeout + 900.0:.0f} s"}
    except OSError as e:
        return {"ok": False, "seconds": round(time.time() - t0, 1),
                "detail": f"aot_check failed to start: {e}"}
    out = proc.stdout or ""
    failures = [ln.strip()[7:].split(":")[0]
                for ln in out.splitlines() if "[FAIL]" in ln]
    rec = {"ok": proc.returncode == 0,
           "seconds": round(time.time() - t0, 1)}
    if failures:
        rec["failures"] = failures
    if proc.returncode == 3:
        rec["deferred"] = True
        rec["detail"] = ("gate incomplete: deferred past deadline "
                         "(clean exit; cache warmed, rerun resumes)")
    elif proc.returncode != 0 and not failures:
        tail = (out + (proc.stderr or "")).strip().splitlines()
        rec["detail"] = tail[-1][:200] if tail else f"rc={proc.returncode}"
    return rec


# --------------------------------------------------------------- accel bench

def run_accel_ab() -> None:
    """``bench.py --accel``: per-trial vs batched FDAS A/B on one
    block of whitened DM-trial spectra — the per-stage
    ``dm_trials_per_sec`` contrast that justifies the batched
    acceleration-search path (kernels/accel.py + the
    kernels/accel_batch.py planner + the native plane consumer).
    Emits one bench/v2 record with an additive ``accel`` key;
    tools/bench_gate.py gates ``accel.batched.dm_trials_per_sec``
    (and the per-DM rate, and the speedup) against the committed
    baseline.

    Sides of the A/B are both PRODUCTION paths, pinned by the same
    control an operator would use: per_dm = ``TPULSAR_ACCEL_BATCH=0``
    (per-trial row dispatch, the degrade target), batched = the
    default batched path (on CPU that routes through the native
    z-chunked consumer when the toolchain allows).  The batched
    side's plane-construction seconds are measured separately so the
    record carries the plane-vs-fused-top-k split.  Measurements
    interleave within each rep and medians are reported (shared-host
    capacity drift must not masquerade as the path contrast).

    Knobs: TPULSAR_ACCEL_AB_NBINS (spectrum bins, default 1<<15),
    TPULSAR_ACCEL_AB_NDMS (DM trials, default 24),
    TPULSAR_ACCEL_AB_ZMAX (default 50), TPULSAR_ACCEL_AB_NUMHARM
    (default 8), TPULSAR_ACCEL_AB_TOPK (default 32),
    TPULSAR_ACCEL_AB_REPS (default 3)."""
    import statistics

    import numpy as np

    import jax
    import jax.numpy as jnp

    want = os.environ.get("JAX_PLATFORMS", "").strip()
    if want:
        jax.config.update("jax_platforms", want)

    from tpulsar import native
    from tpulsar.kernels import accel as ak
    from tpulsar.kernels import accel_batch as abp

    nbins = int(os.environ.get("TPULSAR_ACCEL_AB_NBINS",
                               str(1 << 15)))
    ndms = int(os.environ.get("TPULSAR_ACCEL_AB_NDMS", "24"))
    zmax = float(os.environ.get("TPULSAR_ACCEL_AB_ZMAX", "50"))
    numharm = int(os.environ.get("TPULSAR_ACCEL_AB_NUMHARM", "8"))
    topk = int(os.environ.get("TPULSAR_ACCEL_AB_TOPK", "32"))
    reps = max(1, int(os.environ.get("TPULSAR_ACCEL_AB_REPS", "3")))

    bank = ak.build_template_bank(zmax)
    nz = len(bank.zs)
    rng = np.random.default_rng(13)
    host = (rng.normal(size=(ndms, nbins))
            + 1j * rng.normal(size=(ndms, nbins))).astype(np.complex64)
    # a strong drifting tone so the A/B's candidate parity is judged
    # on a real detection, not only on noise maxima
    host[:, nbins // 3] += 25.0
    specs = jnp.asarray(host)
    plan = abp.plan_batches(ndms, ak.plane_dm_chunk(nbins, nz))
    block = specs if plan.padded_rows == ndms else ak._pad_block(
        specs, rows=plan.padded_rows)
    bank_fft = jnp.asarray(bank.bank_fft)

    def _pin(mode: str | None):
        # the same knob an operator pins the path with; the cached
        # probe verdict must be re-derived after every flip
        if mode is None:
            os.environ.pop("TPULSAR_ACCEL_BATCH", None)
        else:
            os.environ["TPULSAR_ACCEL_BATCH"] = mode
        ak._reset_batch_state()

    def per_dm_fn():
        _pin("0")
        return ak.accel_search_batch(specs, bank,
                                     max_numharm=numharm, topk=topk)

    def batched_fn():
        _pin(None)
        return ak.accel_search_batch(specs, bank,
                                     max_numharm=numharm, topk=topk)

    use_z = native.has_accel_zsegs()

    def plane_fn():
        # the batched side's plane construction alone, at the exact
        # per-batch shapes the planner dispatches (the z-chunked
        # pieces program when the native consumer will eat them, the
        # assembled block otherwise).  Pieces are dropped per batch,
        # matching the real path's buffer lifetime — holding every
        # batch's GB-scale pieces alive would measure allocator
        # pressure the pipeline never creates.
        for s0 in plan.starts:
            sub = jax.lax.dynamic_slice_in_dim(
                block, np.int32(s0), plan.b, axis=0)
            if use_z:
                out = ak._correlate_zpieces(
                    sub, bank_fft, seg=bank.seg, step=bank.step,
                    width=bank.width, nz=nz)
            else:
                out = ak._correlate_block(
                    sub, bank_fft, bank.seg, bank.step, bank.width,
                    nz)
            jax.block_until_ready(out)
            del out
        return True

    measures = {"per_dm": per_dm_fn, "batched": batched_fn,
                "plane": plane_fn}
    outs: dict[str, object] = {}
    for name, fn in measures.items():
        outs[name] = fn()                      # warm (compiles)
    samples: dict[str, list] = {k: [] for k in measures}
    for _ in range(reps):
        for name, fn in measures.items():
            t0 = time.time()
            outs[name] = fn()
            samples[name].append(time.time() - t0)
    _pin(None)

    per_dm_s = statistics.median(samples["per_dm"])
    batched_s = statistics.median(samples["batched"])
    plane_s = statistics.median(samples["plane"])
    res_p, res_b = outs["per_dm"], outs["batched"]

    # candidate parity: same winning (r, z) cells on both paths, and
    # powers within FFT-batching tolerance (the two sides batch their
    # FFTs differently, so the last-ulp reduction order differs; bins
    # and z picks must not)
    parity_ok = True
    max_rel = 0.0
    for h in res_b:
        pv, pr, pz = res_p[h]
        bv, br, bz = res_b[h]
        if not (np.array_equal(pr, br) and np.array_equal(pz, bz)):
            parity_ok = False
        denom = np.maximum(np.abs(pv), 1e-6)
        rel = float(np.max(np.abs(bv - pv) / denom))
        max_rel = max(max_rel, rel)
        if rel > 2e-4:
            parity_ok = False

    rec = {
        "metric": "accel_ab_batched_dm_trials_per_sec",
        "value": round(ndms / batched_s, 2),
        "unit": "trials/s",
        "vs_baseline": round((ndms / batched_s)
                             / max(ndms / per_dm_s, 1e-9), 3),
        "device": str(jax.devices()[0]),
        "accel": {
            "nbins": nbins, "ndms": ndms, "zmax": zmax, "nz": nz,
            "numharm": numharm, "topk": topk, "reps": reps,
            "native": bool(native.load() is not None),
            "native_zsegs": bool(use_z),
            "quantized_batch": plan.b,
            "padded_rows": plan.padded_rows,
            "nbatches": plan.nbatches,
            "per_dm": {
                "seconds": round(per_dm_s, 4),
                "dm_trials_per_sec": round(ndms / per_dm_s, 2),
            },
            "batched": {
                "seconds": round(batched_s, 4),
                # the fused reduction's share is the batched total
                # minus its measured plane construction
                "plane_seconds": round(plane_s, 4),
                "topk_seconds": round(max(batched_s - plane_s, 0.0),
                                      4),
                "dm_trials_per_sec": round(ndms / batched_s, 2),
            },
            "speedup": round(per_dm_s / batched_s, 3),
            "parity_max_rel_err": max_rel,
            "parity_ok": parity_ok,
        },
    }
    _emit(rec)


# --------------------------------------------------------------- serve bench

def run_serve() -> None:
    """``bench.py --serve``: push N synthetic beams through ONE
    resident server (tpulsar/serve/) and report cold-first-beam vs
    warm-steady-state per-beam wall time — the number that justifies
    the warm-worker subsystem (PR 3 measured 160 s of a 176 s cold
    child spent off the hot path; residency pays it once).

    Also times one real process-per-beam child on the same beam with its
    own cold cache (``TPULSAR_SERVE_COLD=0`` skips it) so the serve
    payload carries the deployment-shaped comparison, not only the
    within-server contrast.  Emits one bench/v2 record with an
    additive ``serve`` key."""
    import shutil
    import statistics
    import subprocess
    import tempfile

    from tpulsar.config import TpulsarConfig, set_settings
    from tpulsar.io import synth
    from tpulsar.serve import protocol
    from tpulsar.serve.server import SearchServer

    nbeams = int(os.environ.get("TPULSAR_SERVE_NBEAMS", "3"))
    nchan = int(os.environ.get("TPULSAR_SERVE_NCHAN", "32"))
    nsamp = int(os.environ.get("TPULSAR_SERVE_NSAMP", str(1 << 13)))
    dm_max = float(os.environ.get("TPULSAR_SERVE_DM_MAX", "60"))
    accel = os.environ.get("TPULSAR_SERVE_ACCEL", "0") == "1"
    base = tempfile.mkdtemp(prefix="tpulsar_servebench_")

    cfg = TpulsarConfig()
    cfg.basic.log_dir = os.path.join(base, "logs")
    cfg.background.jobtracker_db = os.path.join(base, "jt.db")
    cfg.download.datadir = os.path.join(base, "raw")
    cfg.processing.base_working_directory = os.path.join(base, "work")
    cfg.processing.base_results_directory = os.path.join(base, "res")
    cfg.resultsdb.url = os.path.join(base, "results.db")
    cfg.searching.dm_max = dm_max
    cfg.searching.use_hi_accel = accel
    cfg.searching.max_cands_to_fold = 2
    cfg.check_sanity(create_dirs=True)
    set_settings(cfg)

    psr = synth.PulsarSpec(period_s=0.05, dm=20.0,
                           snr_per_sample=1.5)
    beams = []
    for i in range(nbeams):
        spec = synth.BeamSpec(nchan=nchan, nsamp=nsamp, nsblk=64,
                              nbits=4, tsamp_s=5.24288e-4,
                              scan=100 + i)
        beams.append(synth.synth_beam(
            os.path.join(base, f"data{i}"), spec, pulsars=[psr],
            merged=True))

    # deployment-shaped baseline: one fork-per-beam child on beam 0,
    # with its own empty compile cache — Python/JAX startup, cache
    # probing, serial stage-in all included, exactly what every beam
    # pays in the batch model
    cold_process_s = None
    if os.environ.get("TPULSAR_SERVE_COLD", "1") != "0":
        cfg_file = os.path.join(base, "worker_config.yaml")
        with open(cfg_file, "w") as fh:
            fh.write(
                "searching:\n"
                f"  dm_max: {dm_max}\n"
                f"  use_hi_accel: {str(accel).lower()}\n"
                "  max_cands_to_fold: 2\n"
                "processing:\n"
                f"  base_working_directory: "
                f"{cfg.processing.base_working_directory}\n"
                f"  base_results_directory: "
                f"{cfg.processing.base_results_directory}\n"
                f"basic:\n  log_dir: {cfg.basic.log_dir}\n")
        env = dict(os.environ)
        env["TPULSAR_CONFIG"] = cfg_file
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, "cache_cold")
        _log(f"cold process-per-beam child on beam 0 ...")
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "tpulsar.cli.search_job"]
            + beams[0] + ["--outdir", os.path.join(base, "out_cold")],
            env=env, capture_output=True, text=True)
        if proc.returncode == 0:
            cold_process_s = round(time.time() - t0, 3)
            _log(f"cold child: {cold_process_s:.1f} s")
        else:
            _log("cold child failed rc "
                 f"{proc.returncode}: "
                 f"{(proc.stderr or '').strip()[-200:]}")

    # the resident server: fresh cache of its own, every beam through
    # one process — beam 1 pays the compiles, the rest ride the jit
    # cache and the prefetch overlap
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, "cache_serve")
    _aot_cachedir.activate()
    spool = os.path.join(base, "spool")
    tickets = []
    for i, fns in enumerate(beams):
        tid = f"bench-{i}"
        protocol.write_ticket(spool, tid, fns,
                              os.path.join(base, f"out{i}"), job_id=i)
        tickets.append(tid)
    _log(f"serving {nbeams} beams from one warm worker ...")
    t0 = time.time()
    server = SearchServer(spool=spool, cfg=cfg, warm_boot=False,
                          poll_s=0.1)
    server.serve(once=True)
    serve_wall = round(time.time() - t0, 3)

    per_beam, misses, failed = [], [], []
    for tid in tickets:
        rec = protocol.read_result(spool, tid) or {}
        if rec.get("status") != "done":
            failed.append(tid)
            continue
        per_beam.append(round(rec.get("beam_seconds", 0.0), 3))
        misses.append(int(rec.get("compile_misses", -1)))
    result = {
        "metric": "serve_steady_state_beam_wallclock",
        "value": (round(statistics.median(per_beam[1:]), 3)
                  if len(per_beam) > 1 else -1.0),
        "unit": "s",
        "serve": {
            "nbeams": nbeams,
            "beams_done": len(per_beam),
            "beams_failed": failed,
            "per_beam_s": per_beam,
            "compile_misses_per_beam": misses,
            "cold_first_beam_s": per_beam[0] if per_beam else -1.0,
            "warm_steady_state_s": (
                round(statistics.median(per_beam[1:]), 3)
                if len(per_beam) > 1 else -1.0),
            "cold_process_beam_s": cold_process_s,
            "server_wallclock_s": serve_wall,
            "accel": accel, "dm_max": dm_max,
            "nchan": nchan, "nsamp": nsamp,
        },
    }
    if cold_process_s and len(per_beam) > 1:
        result["serve"]["warm_vs_cold_process_speedup"] = round(
            cold_process_s / max(1e-9,
                                 result["serve"]["warm_steady_state_s"]),
            2)
    _emit(result)
    if os.environ.get("TPULSAR_SERVE_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_beambatch() -> None:
    """``bench.py --beambatch``: B=1 serial vs B=N coalesced
    batch-of-beams throughput (executor.search_beam vs
    executor.search_beam_batch) on N identical-geometry synthetic
    beams — the number that justifies batched admission for
    small-beam surveys (per-dispatch overhead, not per-beam compute,
    dominates their wall clock; the hi-accel FDAS stage alone is ~80%
    of a warm tiny beam and coalesces across beams).

    Both sides run the FULL per-beam path (read + RFI + plan loop +
    sift/refine/fold + artifacts) warm: one untimed warmup cycle per
    side compiles both paths' programs, then ``reps`` interleaved
    measurements (order alternating per rep so shared-host capacity
    drift cannot masquerade as the contrast) and medians are
    reported.  Per-beam candidate parity between the paths is
    asserted BIT-EXACT (same candidates, same float bits, same SP
    events) — `parity_ok` rides the record and CI gates it
    un-toleranced.  Emits one bench/v2 record with an additive
    ``beambatch`` key."""
    import shutil
    import statistics
    import tempfile

    from tpulsar.io import synth
    from tpulsar.search import executor

    nbeams = int(os.environ.get("TPULSAR_BEAMBATCH_NBEAMS", "8"))
    nchan = int(os.environ.get("TPULSAR_BEAMBATCH_NCHAN", "32"))
    nsamp = int(os.environ.get("TPULSAR_BEAMBATCH_NSAMP",
                               str(1 << 11)))
    # a survey-realistic DM depth: the deeper the DM range, the more
    # small per-chunk dispatches each SOLO beam pays for (the batched
    # side coalesces them B-wide), so shallow dm_max UNDERSTATES the
    # coalescing win the admission batch exists for
    dm_max = float(os.environ.get("TPULSAR_BEAMBATCH_DM_MAX", "120"))
    accel = os.environ.get("TPULSAR_BEAMBATCH_ACCEL", "1") == "1"
    reps = int(os.environ.get("TPULSAR_BEAMBATCH_REPS", "3"))
    # the small-beam-survey device shape: a modest z range and a
    # tight per-chunk DM budget (the HBM-constrained regime batching
    # exists for) — solo dispatches are SMALL, which is exactly what
    # the coalesced path amortizes
    zmax = int(os.environ.get("TPULSAR_BEAMBATCH_ZMAX", "20"))
    dm_chunk = int(os.environ.get("TPULSAR_BEAMBATCH_CHUNK", "19"))
    base = tempfile.mkdtemp(prefix="tpulsar_beambatch_")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(base, "cache"))
    _aot_cachedir.activate()

    psr = synth.PulsarSpec(period_s=0.05, dm=20.0,
                           snr_per_sample=1.5)
    beams = []
    for i in range(nbeams):
        spec = synth.BeamSpec(nchan=nchan, nsamp=nsamp, nsblk=64,
                              nbits=4, tsamp_s=5.24288e-4,
                              scan=100 + i)
        beams.append(synth.synth_beam(
            os.path.join(base, f"data{i}"), spec, pulsars=[psr],
            merged=True))
    params = executor.SearchParams(dm_max=dm_max,
                                   run_hi_accel=accel,
                                   hi_accel_zmax=zmax,
                                   max_dms_per_chunk=dm_chunk,
                                   sp_threshold=float(os.environ.get(
                                       "TPULSAR_BEAMBATCH_SP_THRESH",
                                       "8")),
                                   max_cands_to_fold=1,
                                   make_plots=False)
    seq = [0]

    def run_solo():
        seq[0] += 1
        outs = []
        t0 = time.time()
        for i, fns in enumerate(beams):
            outs.append(executor.search_beam(
                fns, os.path.join(base, f"w{seq[0]}_{i}"),
                os.path.join(base, f"r{seq[0]}_{i}"), params))
        return time.time() - t0, outs

    def run_batched():
        seq[0] += 1
        specs = [executor.BeamSpec(
            fns=fns, workdir=os.path.join(base, f"w{seq[0]}_{i}"),
            resultsdir=os.path.join(base, f"r{seq[0]}_{i}"))
            for i, fns in enumerate(beams)]
        t0 = time.time()
        res = executor.search_beam_batch(specs, params)
        dt = time.time() - t0
        bad = [(r.path, r.fallout, str(r.error)[:120]) for r in res
               if r.path != "batched" or r.error is not None]
        if bad:
            raise RuntimeError(f"beams fell out of the batch: {bad}")
        return dt, [r.outcome for r in res], sorted(
            {r.group_size for r in res})

    _log(f"beambatch warmup: {nbeams} beams nchan={nchan} "
         f"nsamp={nsamp} dm_max={dm_max:g} accel={accel}")
    _, solo_ref = run_solo()
    _, bat_ref, group_sizes = run_batched()

    fields = ("r", "z", "sigma", "power", "numharm", "dm",
              "period_s", "freq_hz")
    parity_beams = 0
    parity_ok = True
    for s, b in zip(solo_ref, bat_ref):
        beam_ok = (s.num_dm_trials == b.num_dm_trials
                   and len(s.candidates) == len(b.candidates)
                   and all(getattr(cs, f) == getattr(cb, f)
                           for cs, cb in zip(s.candidates,
                                             b.candidates)
                           for f in fields)
                   and s.sp_events.tobytes() == b.sp_events.tobytes())
        parity_ok &= beam_ok
        parity_beams += int(beam_ok)

    solo_s: list[float] = []
    bat_s: list[float] = []
    for rep in range(reps):
        if rep % 2 == 0:
            tb, _, _ = run_batched()
            ts, _ = run_solo()
        else:
            ts, _ = run_solo()
            tb, _, _ = run_batched()
        solo_s.append(round(ts, 3))
        bat_s.append(round(tb, 3))
        _log(f"beambatch rep{rep}: solo {ts:.2f} s "
             f"batched {tb:.2f} s ({ts / max(tb, 1e-9):.2f}x)")

    solo_med = statistics.median(solo_s)
    bat_med = statistics.median(bat_s)
    result = {
        "metric": "beambatch_beams_per_sec",
        "value": round(nbeams / max(bat_med, 1e-9), 4),
        "unit": "beams/s",
        "beambatch": {
            "nbeams": nbeams, "nchan": nchan, "nsamp": nsamp,
            "dm_max": dm_max, "accel": accel, "reps": reps,
            "solo": {
                "seconds": solo_med,
                "seconds_reps": solo_s,
                "beams_per_sec": round(nbeams / max(solo_med, 1e-9),
                                       4),
            },
            "batched": {
                "seconds": bat_med,
                "seconds_reps": bat_s,
                "beams_per_sec": round(nbeams / max(bat_med, 1e-9),
                                       4),
                "group_sizes": group_sizes,
            },
            "speedup": round(solo_med / max(bat_med, 1e-9), 3),
            "parity_ok": parity_ok,
            "parity_beams": parity_beams,
        },
    }
    _emit(result)
    if os.environ.get("TPULSAR_BEAMBATCH_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_gateway() -> None:
    """``bench.py --gateway``: push N synthetic beams through the
    HTTP front door (tpulsar/frontdoor/) backed by one resident warm
    worker on a filesystem spool, and report submit→result latency —
    measured from the journal's gateway-edge ``received`` event (HTTP
    arrival) to the terminal ``result`` — plus the status-query
    overhead the HTTP hop adds over reading the spool directly.  The
    first beam pays the compiles (cold); the steady-state warm median
    is the number the front door must not regress.  Emits one
    bench/v2 record with an additive ``gateway`` key.

    Knobs: TPULSAR_GW_NBEAMS/NCHAN/NSAMP/DM_MAX (beam set, defaults
    3/16/4096/30), TPULSAR_GW_STATUS_REPS (status-overhead sample
    count, default 50), TPULSAR_GW_KEEP=1 keeps the scratch dir."""
    import shutil
    import statistics
    import tempfile
    import threading

    from tpulsar.config import TpulsarConfig, set_settings
    from tpulsar.frontdoor import client
    from tpulsar.frontdoor.gateway import GatewayServer
    from tpulsar.frontdoor.queue import FilesystemSpoolQueue
    from tpulsar.io import synth
    from tpulsar.obs import fleetview, journal
    from tpulsar.serve import protocol
    from tpulsar.serve.server import SearchServer

    nbeams = int(os.environ.get("TPULSAR_GW_NBEAMS", "3"))
    nchan = int(os.environ.get("TPULSAR_GW_NCHAN", "16"))
    nsamp = int(os.environ.get("TPULSAR_GW_NSAMP", "4096"))
    dm_max = float(os.environ.get("TPULSAR_GW_DM_MAX", "30"))
    status_reps = int(os.environ.get("TPULSAR_GW_STATUS_REPS", "50"))
    base = tempfile.mkdtemp(prefix="tpulsar_gwbench_")

    cfg = TpulsarConfig()
    cfg.basic.log_dir = os.path.join(base, "logs")
    cfg.background.jobtracker_db = os.path.join(base, "jt.db")
    cfg.download.datadir = os.path.join(base, "raw")
    cfg.processing.base_working_directory = os.path.join(base, "work")
    cfg.processing.base_results_directory = os.path.join(base, "res")
    cfg.resultsdb.url = os.path.join(base, "results.db")
    cfg.searching.dm_max = dm_max
    cfg.searching.use_hi_accel = False
    cfg.searching.max_cands_to_fold = 2
    cfg.check_sanity(create_dirs=True)
    set_settings(cfg)

    psr = synth.PulsarSpec(period_s=0.05, dm=20.0,
                           snr_per_sample=1.5)
    beams = []
    for i in range(nbeams):
        spec = synth.BeamSpec(nchan=nchan, nsamp=nsamp, nsblk=64,
                              nbits=4, tsamp_s=5.24288e-4,
                              scan=100 + i)
        beams.append(synth.synth_beam(
            os.path.join(base, f"data{i}"), spec, pulsars=[psr],
            merged=True))

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, "cache_gw")
    _aot_cachedir.activate()
    spool = os.path.join(base, "spool")
    server = SearchServer(spool=spool, cfg=cfg, worker_id="w0",
                          warm_boot=False, poll_s=0.05)
    th = threading.Thread(target=server.serve, name="gw-bench-serve",
                          daemon=True)
    th.start()
    # admission opens when the worker's heartbeat is fresh (the
    # gateway 503s until then — exactly what a deployment sees)
    deadline = time.time() + 60
    while protocol.fleet_capacity(spool) is None \
            and time.time() < deadline:
        time.sleep(0.05)
    gw = GatewayServer(queue=FilesystemSpoolQueue(spool),
                       outdir_base=os.path.join(base, "out")).start()
    _log(f"gateway {gw.url} over 1 warm worker; submitting "
         f"{nbeams} beams over HTTP ...")

    latency, failed, tickets = [], [], []
    for i, fns in enumerate(beams):
        rec = client.submit_beam(gw.url, fns, job_id=i)
        res = client.wait_for_result(gw.url, rec["ticket"],
                                     timeout_s=1200, poll_s=0.1)
        tickets.append(rec["ticket"])
        if res.get("status") != "done":
            failed.append(rec["ticket"])
            continue
        evs = journal.read_events(spool, ticket=rec["ticket"])
        t_recv = next(e["t"] for e in evs
                      if e["event"] == "received")
        t_term = next(e["t"] for e in evs
                      if e["event"] == journal.TERMINAL_EVENT)
        latency.append(round(t_term - t_recv, 3))
        _log(f"beam {i}: submit->result {latency[-1]:.2f} s")

    # the HTTP status hop vs reading the spool directly (what the
    # PR 4-6 clients do) — the overhead the front door charges a
    # poller per status check
    tid = tickets[-1]
    t0 = time.time()
    for _ in range(status_reps):
        client.ticket_status(gw.url, tid)
    status_http_ms = round((time.time() - t0) / status_reps * 1e3, 3)
    t0 = time.time()
    for _ in range(status_reps):
        protocol.read_result(spool, tid)
    status_direct_ms = round((time.time() - t0) / status_reps * 1e3,
                             3)

    server.request_drain()
    th.join(timeout=60)
    gw.stop()

    lat_sorted = sorted(latency)
    warm = latency[1:]
    result = {
        "metric": "gateway_submit_to_result_latency",
        "value": (round(statistics.median(lat_sorted), 3)
                  if latency else -1.0),
        "unit": "s",
        "gateway": {
            "nbeams": nbeams, "beams_done": len(latency),
            "beams_failed": failed,
            "submit_to_result_s": latency,
            "submit_to_result_p50_s": (
                round(fleetview._quantile(lat_sorted, 0.5), 3)
                if latency else -1.0),
            "submit_to_result_p95_s": (
                round(fleetview._quantile(lat_sorted, 0.95), 3)
                if latency else -1.0),
            "submit_to_result_warm_s": (
                round(statistics.median(warm), 3) if warm else -1.0),
            "cold_first_beam_s": latency[0] if latency else -1.0,
            "status_http_ms": status_http_ms,
            "status_direct_ms": status_direct_ms,
            "status_overhead_ms": round(
                status_http_ms - status_direct_ms, 3),
            "status_reps": status_reps,
            "nchan": nchan, "nsamp": nsamp, "dm_max": dm_max,
        },
    }
    _emit(result)
    if os.environ.get("TPULSAR_GW_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_chaos() -> None:
    """``bench.py --chaos``: the same synthetic stub-beam workload
    through a 2-worker fleet twice — once clean, once under a chaos
    scenario (worker SIGKILL mid-backlog + a spool I/O fault window)
    — and report recovery speed and the latency cost of the storm:
    MTTR (kill -> victim beam terminal), takeover latency (the
    janitor's share), and ticket e2e p95 under chaos vs clean.  The
    invariant verifier runs over both spools and its violation count
    is part of the record: the only acceptable value is 0 — this
    bench regressing CORRECTNESS is worse than it regressing speed.
    Emits one bench/v2 record with an additive ``chaos`` key.

    Stub workers (tpulsar/chaos/worker.py) speak the full spool
    protocol with millisecond beams, so the measured numbers isolate
    the RECOVERY machinery (janitor cadence, takeover renames,
    restart backoff), not device compute.  Knobs:
    TPULSAR_CHAOS_NBEAMS/BEAM_S/INTERVAL_S (default 14/0.3/0.1),
    TPULSAR_CHAOS_KEEP=1 keeps the scratch spools."""
    import shutil
    import tempfile

    from tpulsar.chaos import invariants, runner, scenario
    from tpulsar.obs import fleetview, journal

    nbeams = int(os.environ.get("TPULSAR_CHAOS_NBEAMS", "14"))
    beam_s = float(os.environ.get("TPULSAR_CHAOS_BEAM_S", "0.3"))
    interval = float(os.environ.get("TPULSAR_CHAOS_INTERVAL_S",
                                    "0.1"))
    base = tempfile.mkdtemp(prefix="tpulsar_chaosbench_")
    # the kill lands mid-backlog: submissions outpace two workers'
    # service rate, so the victim worker is holding a beam
    kill_t = round(nbeams * interval * 0.5, 2)

    def one(tag: str, timeline: list) -> dict:
        spool = os.path.join(base, f"spool_{tag}")
        sc = scenario.from_dict({
            "name": f"bench-{tag}", "seed": 7, "duration_s": 120.0,
            "workers": 2, "worker_kind": "stub", "beam_s": beam_s,
            "workload": {"beams": nbeams, "interval_s": interval},
            "timeline": timeline, "quiesce_timeout_s": 90.0,
        })
        _log(f"chaos bench [{tag}]: {nbeams} beams x {beam_s:g} s "
             f"through 2 stub workers"
             + (f", {len(timeline)} action(s)" if timeline else ""))
        manifest = runner.run_scenario(sc, spool)
        events = journal.read_events(spool)
        e2e = sorted(
            rec["e2e_s"]
            for rec in journal.summarize(spool)["tickets"].values()
            if rec.get("status") == "done" and "e2e_s" in rec)
        report = invariants.verify(spool,
                                   quiesced=manifest["quiesced"])
        rec_stats = invariants.recovery_stats(events)
        return {
            "quiesced": manifest["quiesced"],
            "beams_done": len(e2e),
            "e2e_p50_s": (round(fleetview._quantile(e2e, 0.5), 3)
                          if e2e else -1.0),
            "e2e_p95_s": (round(fleetview._quantile(e2e, 0.95), 3)
                          if e2e else -1.0),
            "mttr_s": rec_stats["mttr_s"],
            "takeover_latency_s": rec_stats["takeover_latency_s"],
            "invariant_violations": len(report["violations"]),
            "violations": report["violations"][:10],
        }

    clean = one("clean", [])
    chaos = one("chaos", [
        {"t": kill_t, "action": "kill_worker", "worker": "w0",
         "signal": "KILL"},
        {"t": kill_t + 0.2, "action": "set_faults", "worker": "w1",
         "until": kill_t + 4.0,
         "faults": "spool.io:unimplemented:count=1,errno=EIO"},
    ])
    _log(f"clean p95 {clean['e2e_p95_s']:.2f} s; chaos p95 "
         f"{chaos['e2e_p95_s']:.2f} s, mttr {chaos['mttr_s']} s, "
         f"violations {clean['invariant_violations']}"
         f"+{chaos['invariant_violations']}")
    result = {
        "metric": "chaos_recovery_mttr",
        "value": (chaos["mttr_s"] if chaos["mttr_s"] is not None
                  else -1.0),
        "unit": "s",
        "chaos": {
            "nbeams": nbeams, "beam_s": beam_s,
            "interval_s": interval, "kill_t_s": kill_t,
            "mttr_s": (chaos["mttr_s"]
                       if chaos["mttr_s"] is not None else -1.0),
            "takeover_latency_s": (
                chaos["takeover_latency_s"]
                if chaos["takeover_latency_s"] is not None
                else -1.0),
            "e2e_p50_clean_s": clean["e2e_p50_s"],
            "e2e_p95_clean_s": clean["e2e_p95_s"],
            "e2e_p50_chaos_s": chaos["e2e_p50_s"],
            "e2e_p95_chaos_s": chaos["e2e_p95_s"],
            "e2e_p95_degradation": (
                round(chaos["e2e_p95_s"] / clean["e2e_p95_s"], 3)
                if clean["e2e_p95_s"] > 0 and chaos["e2e_p95_s"] > 0
                else -1.0),
            "beams_done_clean": clean["beams_done"],
            "beams_done_chaos": chaos["beams_done"],
            "quiesced": clean["quiesced"] and chaos["quiesced"],
            # the correctness row: MUST be 0 — the bench gate skips
            # zero-valued keys, so CI asserts this one explicitly
            "invariant_violations": (
                clean["invariant_violations"]
                + chaos["invariant_violations"]),
        },
    }
    if clean["violations"] or chaos["violations"]:
        result["chaos"]["violation_sample"] = (
            clean["violations"] + chaos["violations"])[:10]
    _emit(result)
    if os.environ.get("TPULSAR_CHAOS_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_resume() -> None:
    """``bench.py --resume``: the recovery-cost contrast the
    checkpoint layer (tpulsar/checkpoint/) exists to win.  The SAME
    seeded kill-mid-beam scenario — multi-pass stub beams through a
    2-worker fleet, w0 SIGKILLed mid-beam — runs twice: once with
    pass-level checkpointing (the default) and once with
    ``--no-checkpoint`` workers (the from-zero control that models
    every release before this one).  The journal-derived
    ``wasted_compute_s`` (kill-destroyed compute minus what the
    resumed attempt salvaged from the manifest — see
    invariants.recovery_stats) is the headline: checkpointed recovery
    must waste only the in-flight pass, not the whole beam.  The
    invariant verifier (including the new ``resume_consistent`` /
    ``no_pass_rerun`` invariants) runs over BOTH spools and its
    violation count is part of the record — the only acceptable
    value is 0.  Emits one bench/v2 record with an additive
    ``resume`` key.  Knobs: TPULSAR_RESUME_NBEAMS/PASSES/PASS_S
    (default 3/8/0.15), TPULSAR_RESUME_KEEP=1 keeps the spools."""
    import shutil
    import tempfile

    from tpulsar.chaos import invariants, runner, scenario
    from tpulsar.obs import journal

    nbeams = int(os.environ.get("TPULSAR_RESUME_NBEAMS", "3"))
    passes = int(os.environ.get("TPULSAR_RESUME_PASSES", "8"))
    pass_s = float(os.environ.get("TPULSAR_RESUME_PASS_S", "0.15"))
    base = tempfile.mkdtemp(prefix="tpulsar_resumebench_")
    # the kill lands mid-first-beam, several passes in: late enough
    # that the checkpoint store holds real salvage, early enough that
    # the control run still has most of the beam left to waste
    kill_t = round(passes * pass_s * 0.6, 2)

    def one(tag: str, extra_args: tuple) -> dict:
        spool = os.path.join(base, f"spool_{tag}")
        sc = scenario.from_dict({
            "name": f"resume-{tag}", "seed": 11, "duration_s": 120.0,
            "workers": 2, "worker_kind": "stub", "max_attempts": 3,
            "workload": {"beams": nbeams, "interval_s": 0.1,
                         "passes": passes, "pass_s": pass_s},
            "timeline": [{"t": kill_t, "action": "kill_worker",
                          "worker": "w0", "signal": "KILL"}],
            "quiesce_timeout_s": 90.0,
        })
        _log(f"resume bench [{tag}]: {nbeams} beams x {passes} "
             f"passes x {pass_s:g} s, w0 killed at t+{kill_t:g} s"
             + (f" ({' '.join(extra_args)})" if extra_args else ""))
        manifest = runner.run_scenario(sc, spool,
                                       worker_extra_args=extra_args)
        events = journal.read_events(spool)
        report = invariants.verify(spool,
                                   quiesced=manifest["quiesced"])
        stats = invariants.recovery_stats(events)
        names = [e.get("event") for e in events]
        return {
            "quiesced": manifest["quiesced"],
            "wasted_compute_s": stats["wasted_compute_s"],
            "mttr_s": stats["mttr_s"],
            "resumes": names.count("resume"),
            "pass_completes": names.count("pass_complete"),
            "invariant_violations": len(report["violations"]),
            "violations": report["violations"][:10],
        }

    ck = one("ckpt", ())
    ctrl = one("control", ("--no-checkpoint",))
    w_ck = ck["wasted_compute_s"]
    w_ctrl = ctrl["wasted_compute_s"]
    reduction = (round(1.0 - w_ck / w_ctrl, 3)
                 if w_ck is not None and w_ctrl else -1.0)
    _log(f"wasted compute: checkpointed {w_ck} s vs control "
         f"{w_ctrl} s ({reduction if reduction >= 0 else '?'} "
         f"reduction); violations "
         f"{ck['invariant_violations']}+{ctrl['invariant_violations']}")
    result = {
        "metric": "resume_wasted_compute",
        "value": w_ck if w_ck is not None else -1.0,
        "unit": "s",
        "resume": {
            "nbeams": nbeams, "passes": passes, "pass_s": pass_s,
            "kill_t_s": kill_t,
            "wasted_compute_s": (w_ck if w_ck is not None else -1.0),
            "wasted_compute_control_s": (
                w_ctrl if w_ctrl is not None else -1.0),
            # fraction of the control run's waste the checkpoint
            # layer eliminated — the acceptance floor is 0.5
            "wasted_reduction": reduction,
            "mttr_s": (ck["mttr_s"] if ck["mttr_s"] is not None
                       else -1.0),
            "resumes": ck["resumes"],
            "pass_completes": ck["pass_completes"],
            "quiesced": ck["quiesced"] and ctrl["quiesced"],
            # the correctness row: MUST be 0 (CI asserts it
            # explicitly — the gate skips zero-valued keys)
            "invariant_violations": (ck["invariant_violations"]
                                     + ctrl["invariant_violations"]),
        },
    }
    if ck["violations"] or ctrl["violations"]:
        result["resume"]["violation_sample"] = (
            ck["violations"] + ctrl["violations"])[:10]
    _emit(result)
    if os.environ.get("TPULSAR_RESUME_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_autoscale() -> None:
    """``bench.py --autoscale``: the fleet-economics headline the
    elastic autoscaler (tpulsar/fleet/autoscale.py) exists to win —
    COST-PER-BEAM AT A FIXED QUEUE-WAIT SLO.  The same bursty
    synthetic workload (a thundering-herd burst, a lull, a second
    surge) runs through two stub fleets on scratch spools:

      * static — the pre-autoscaler answer: ``max_workers`` workers
        for the whole run, idle capacity burning worker-seconds
        through every lull;
      * elastic — one worker plus the autoscaler (min 1 / max
        ``max_workers``), scaling up on backlog pressure and back
        down through the lull, spot-class workers SIGKILLed on
        scale-down.

    Worker-seconds are integrated from the journal's own
    worker_spawn/worker_exit pairs (no side channel), so
    ``cost_per_beam_ws`` = worker-seconds per done beam.  The elastic
    fleet must BEAT the static one on cost while both hold the
    queue-wait p95 SLO — a cheaper fleet that starves its queue has
    not won anything, so ``slo_met`` and the invariant verifier's
    violation count (including scaling_bounded / no_elastic_strike)
    are part of the record and the only acceptable violation count
    is 0.  Emits one bench/v2 record with an additive ``autoscale``
    key.  Knobs: TPULSAR_AUTOSCALE_NBEAMS (per burst) / BEAM_S /
    SLO_S, TPULSAR_AUTOSCALE_KEEP=1 keeps the spools."""
    import shutil
    import tempfile

    from tpulsar.chaos import invariants, runner, scenario
    from tpulsar.obs import fleetview, journal

    burst = int(os.environ.get("TPULSAR_AUTOSCALE_NBEAMS", "10"))
    beam_s = float(os.environ.get("TPULSAR_AUTOSCALE_BEAM_S",
                                  "0.35"))
    slo_s = float(os.environ.get("TPULSAR_AUTOSCALE_SLO_S", "8.0"))
    max_workers = 3
    surge_t = 9.0            # the lull between bursts
    base = tempfile.mkdtemp(prefix="tpulsar_autoscalebench_")

    def one(tag: str, workers: int, autoscale: dict | None) -> dict:
        spool = os.path.join(base, f"spool_{tag}")
        doc = {
            "name": f"asbench-{tag}", "seed": 31,
            "duration_s": 180.0, "workers": workers,
            "worker_kind": "stub", "beam_s": beam_s,
            "poll_s": 0.25,
            "workload": {"beams": burst, "interval_s": 0.03},
            "timeline": [{"t": surge_t, "action": "surge_submit",
                          "beams": burst}],
            "quiesce_timeout_s": 120.0,
        }
        if autoscale:
            doc["autoscale"] = autoscale
        sc = scenario.from_dict(doc)
        _log(f"autoscale bench [{tag}]: 2 x {burst} beams x "
             f"{beam_s:g} s, {workers} worker(s)"
             + (f" elastic [{autoscale['min_workers']}, "
                f"{autoscale['max_workers']}]" if autoscale else
                " static"))
        manifest = runner.run_scenario(sc, spool)
        events = journal.read_events(spool)
        t_end = max((e["t"] for e in events), default=0.0)
        # worker-seconds from spawn/exit pairs (keyed by pid: each
        # incarnation is one interval; anything still up at the last
        # journal instant is charged to there)
        spawns: dict = {}
        ws = 0.0
        for e in events:
            if e.get("event") == "worker_spawn":
                spawns[e.get("pid")] = e["t"]
            elif e.get("event") == "worker_exit":
                t0 = spawns.pop(e.get("pid"), None)
                if t0 is not None:
                    ws += e["t"] - t0
        ws += sum(t_end - t0 for t0 in spawns.values())
        tickets = journal.summarize(spool)["tickets"]
        waits = sorted(rec["queue_wait_s"]
                       for rec in tickets.values()
                       if rec.get("queue_wait_s") is not None)
        names = [e.get("event") for e in events]
        report = invariants.verify(spool,
                                   quiesced=manifest["quiesced"])
        done = sum(1 for rec in tickets.values()
                   if rec.get("status") == "done")
        return {
            "quiesced": manifest["quiesced"],
            "beams_done": done,
            "worker_seconds": round(ws, 3),
            "cost_per_beam_ws": (round(ws / done, 3) if done
                                 else -1.0),
            "queue_wait_p95_s": (
                round(fleetview._quantile(waits, 0.95), 3)
                if waits else -1.0),
            "scale_ups": names.count("scale_up"),
            "scale_downs": names.count("scale_down"),
            "invariant_violations": len(report["violations"]),
            "violations": report["violations"][:10],
        }

    elastic_cfg = {
        "min_workers": 1, "max_workers": max_workers,
        "queue_wait_slo_s": slo_s, "backlog_per_worker": 2.0,
        "cooldown_s": 1.5, "idle_window_s": 1.2,
        "drain_deadline_s": 3.0, "worker_class": "spot",
        "slo_lookback_s": 4.0,
    }
    static = one("static", max_workers, None)
    elastic = one("elastic", 1, elastic_cfg)
    saving = (round(1.0 - elastic["cost_per_beam_ws"]
                    / static["cost_per_beam_ws"], 3)
              if static["cost_per_beam_ws"] > 0
              and elastic["cost_per_beam_ws"] > 0 else -1.0)
    slo_met = (0 <= elastic["queue_wait_p95_s"] <= slo_s
               and 0 <= static["queue_wait_p95_s"] <= slo_s)
    _log(f"cost/beam: elastic {elastic['cost_per_beam_ws']} ws vs "
         f"static {static['cost_per_beam_ws']} ws "
         f"({saving if saving >= 0 else '?'} saving); p95 "
         f"{elastic['queue_wait_p95_s']} s vs "
         f"{static['queue_wait_p95_s']} s (SLO {slo_s:g} s, "
         f"{'met' if slo_met else 'VIOLATED'}); "
         f"{elastic['scale_ups']} up(s)/"
         f"{elastic['scale_downs']} down(s); violations "
         f"{static['invariant_violations']}"
         f"+{elastic['invariant_violations']}")
    result = {
        "metric": "autoscale_cost_per_beam",
        "value": elastic["cost_per_beam_ws"],
        "unit": "s",
        "autoscale": {
            "nbeams": 2 * burst, "beam_s": beam_s, "slo_s": slo_s,
            "workers_min": 1, "workers_max": max_workers,
            "cost_per_beam_ws": elastic["cost_per_beam_ws"],
            "cost_per_beam_static_ws": static["cost_per_beam_ws"],
            # fraction of the static fleet's worker-seconds the
            # autoscaler saved per beam — the economics headline
            "cost_saving": saving,
            "queue_wait_p95_s": elastic["queue_wait_p95_s"],
            "queue_wait_p95_static_s": static["queue_wait_p95_s"],
            "slo_met": slo_met,
            "worker_seconds": elastic["worker_seconds"],
            "worker_seconds_static": static["worker_seconds"],
            "beams_done": elastic["beams_done"],
            "scale_ups": elastic["scale_ups"],
            "scale_downs": elastic["scale_downs"],
            "quiesced": (elastic["quiesced"]
                         and static["quiesced"]),
            # the correctness row: MUST be 0 (CI asserts it
            # explicitly — the gate skips zero-valued keys)
            "invariant_violations": (
                static["invariant_violations"]
                + elastic["invariant_violations"]),
        },
    }
    if static["violations"] or elastic["violations"]:
        result["autoscale"]["violation_sample"] = (
            static["violations"] + elastic["violations"])[:10]
    _emit(result)
    if os.environ.get("TPULSAR_AUTOSCALE_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_queue() -> None:
    """``bench.py --queue``: the spool vs sqlite TicketQueue A/B —
    claim/finish throughput under N contending worker processes.

    The same ticket set (zero-length stub beams: every worker-second
    is queue protocol, not science) drains through each backend in
    turn: N ``tpulsar.chaos.worker`` processes hammer
    claim→result→release until the queue is empty.  Throughput is
    measured from the journal's own evidence — first ``claimed`` to
    last ``result`` — so process startup does not pollute the rate,
    and exactly-once is asserted from the same stream (one terminal
    result per ticket, no losses; ``duplicate_results`` /
    ``lost_tickets`` must be 0).  Emits one bench/v2 record with an
    additive ``queue`` key; headline ``value`` is the sqlite
    backend's tickets/s under contention — the number the WAL +
    transactional-CAS design must not regress.  Knobs:
    TPULSAR_QBENCH_NTICKETS (default 120) / WORKERS (default 4) /
    KEEP=1 keeps the scratch spools."""
    import shutil
    import subprocess
    import tempfile

    from tpulsar.frontdoor.queue import get_ticket_queue
    from tpulsar.obs import journal

    nticks = int(os.environ.get("TPULSAR_QBENCH_NTICKETS", "120"))
    nworkers = int(os.environ.get("TPULSAR_QBENCH_WORKERS", "4"))
    base = tempfile.mkdtemp(prefix="tpulsar_queuebench_")

    def one(tag: str) -> dict:
        spool = os.path.join(base, f"spool_{tag}")
        os.makedirs(spool, exist_ok=True)
        url = (f"sqlite:{os.path.join(spool, 'queue.db')}"
               if tag == "sqlite" else f"spool:{spool}")
        q = get_ticket_queue(url)
        for i in range(nticks):
            q.submit(f"qb-{i:04d}", ["bench://synthetic"],
                     os.path.join(base, f"out_{tag}", f"{i:04d}"),
                     job_id=i, beam_s=0.0)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        logdir = os.path.join(base, f"logs_{tag}")
        os.makedirs(logdir, exist_ok=True)
        _log(f"queue bench [{tag}]: {nworkers} workers contending "
             f"for {nticks} tickets on {url} ...")
        procs = []
        for w in range(nworkers):
            logf = open(os.path.join(logdir, f"qb{w}.log"), "w")
            procs.append((subprocess.Popen(
                [sys.executable, "-m", "tpulsar.chaos.worker",
                 "--spool", spool, "--queue", url,
                 "--worker-id", f"qb{w}", "--beam-s", "0",
                 "--poll-s", "0.01", "--heartbeat-s", "5",
                 "--no-checkpoint", "--once"],
                env=env, stdout=logf, stderr=subprocess.STDOUT),
                logf))
        rcs = []
        for p, logf in procs:
            rcs.append(p.wait(timeout=600))
            logf.close()
        # rate from journal truth (first claim -> last result), so
        # interpreter startup is not charged to the backend
        events = journal.read_events(spool)
        claims = [e["t"] for e in events
                  if e.get("event") == "claimed"]
        res = [e for e in events if e.get("event") == "result"]
        per_ticket: dict = {}
        for e in res:
            per_ticket[e.get("ticket")] = \
                per_ticket.get(e.get("ticket"), 0) + 1
        wall = (max(e["t"] for e in res) - min(claims)
                if res and claims else -1.0)
        return {
            "url": url,
            "wall_s": round(wall, 3),
            "tickets_per_s": (round(nticks / wall, 3)
                              if wall > 0 else -1.0),
            "done": q.state_count("done"),
            "duplicate_results": sum(n - 1
                                     for n in per_ticket.values()
                                     if n > 1),
            "lost_tickets": nticks - len(per_ticket),
            "worker_rcs": rcs,
        }

    spool_side = one("spool")
    sqlite_side = one("sqlite")
    ratio = (round(sqlite_side["tickets_per_s"]
                   / spool_side["tickets_per_s"], 3)
             if spool_side["tickets_per_s"] > 0
             and sqlite_side["tickets_per_s"] > 0 else -1.0)
    clean = all(s["duplicate_results"] == 0 and s["lost_tickets"] == 0
                and s["done"] == nticks and not any(s["worker_rcs"])
                for s in (spool_side, sqlite_side))
    _log(f"queue throughput ({nworkers} workers, {nticks} tickets): "
         f"spool {spool_side['tickets_per_s']}/s, sqlite "
         f"{sqlite_side['tickets_per_s']}/s "
         f"({ratio if ratio >= 0 else '?'}x); exactly-once "
         f"{'clean' if clean else 'VIOLATED'}")
    _emit({
        "metric": "queue_sqlite_tickets_per_s",
        "value": sqlite_side["tickets_per_s"],
        "unit": "/s",
        "queue": {
            "tickets": nticks, "workers": nworkers,
            "spool": spool_side, "sqlite": sqlite_side,
            "sqlite_vs_spool": ratio,
            # the correctness rows: MUST be 0 (CI asserts them
            # un-toleranced; the gate skips zero-valued keys)
            "duplicate_results": (spool_side["duplicate_results"]
                                  + sqlite_side["duplicate_results"]),
            "lost_tickets": (spool_side["lost_tickets"]
                             + sqlite_side["lost_tickets"]),
            "exactly_once_ok": clean,
        },
    })
    if os.environ.get("TPULSAR_QBENCH_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def run_dataplane() -> None:
    """``bench.py --dataplane``: the data plane's two headline
    numbers — (a) by-digest stage-in bandwidth through a live
    gateway's blob routes (PUT then the stage-in GET, both
    digest-verified end to end: the MB/s a spool-less worker
    actually sees, hashing included), and (b) the candidate query
    cost, indexed vs legacy outdir parse, over the same rows — the
    read-path speedup that justifies the index's write-path tax.
    Correctness rides along: every staged byte re-hashes to its
    address and the indexed rows equal the parse exactly (asserted,
    not toleranced).  Knobs: TPULSAR_DPBENCH_BLOB_MB (default 4) /
    NBLOBS (default 8) / NTICKETS (default 40) / QUERY_ITERS
    (default 50) / KEEP=1 keeps the scratch dir."""
    import shutil
    import tempfile

    from tpulsar.dataplane import blobstore as dp_blobstore
    from tpulsar.dataplane import index as dp_index
    from tpulsar.dataplane import transfer
    from tpulsar.frontdoor import results
    from tpulsar.frontdoor.gateway import GatewayServer
    from tpulsar.frontdoor.queue import get_ticket_queue
    from tpulsar.io import accelcands
    from tpulsar.search.sifting import Candidate

    blob_mb = float(os.environ.get("TPULSAR_DPBENCH_BLOB_MB", "4"))
    nblobs = int(os.environ.get("TPULSAR_DPBENCH_NBLOBS", "8"))
    ntickets = int(os.environ.get("TPULSAR_DPBENCH_NTICKETS", "40"))
    iters = int(os.environ.get("TPULSAR_DPBENCH_QUERY_ITERS", "50"))
    base = tempfile.mkdtemp(prefix="tpulsar_dpbench_")
    spool = os.path.join(base, "spool")
    os.makedirs(spool, exist_ok=True)
    q = get_ticket_queue(spool)
    # a handler-less logger keeps stdout pure bench/v2 (the default
    # gateway logger echoes INFO to stdout, which would corrupt the
    # committed baseline — bench_gate json.load()s the whole file)
    quiet = __import__("logging").getLogger("tpulsar.bench.dpgw")
    quiet.addHandler(__import__("logging").NullHandler())
    quiet.propagate = False
    gw = GatewayServer(queue=q, outdir_base=os.path.join(base, "res"),
                       blob_root=os.path.join(base, "cas"),
                       logger=quiet).start()
    try:
        # ---- (a) stage-in bandwidth over the wire, verified ------
        payload = os.urandom(int(blob_mb * 1e6))
        total_mb = nblobs * len(payload) / 1e6
        _log(f"dataplane bench: staging {nblobs} x "
             f"{len(payload) / 1e6:.0f} MB blobs through {gw.url}")
        digests = []
        t0 = time.time()
        for i in range(nblobs):
            # vary one leading byte so every blob is a distinct
            # object (no dedup short-circuit flattering the rate)
            digests.append(transfer.put_bytes(
                gw.url, bytes([i % 256]) + payload[1:]))
        put_s = time.time() - t0
        stage_dir = os.path.join(base, "stagein")
        os.makedirs(stage_dir, exist_ok=True)
        t0 = time.time()
        fetched = 0
        for i, d in enumerate(digests):
            fetched += transfer.get_to_file(
                gw.url, d, os.path.join(stage_dir, f"b{i:03d}.dat"))
        get_s = time.time() - t0
        assert fetched == nblobs * len(payload), (fetched, nblobs)
        stagein_mb_per_s = round(total_mb / get_s, 2) \
            if get_s > 0 else -1.0
        put_mb_per_s = round(total_mb / put_s, 2) if put_s > 0 \
            else -1.0

        # ---- (b) candidate query: index vs outdir parse ----------
        rng = __import__("random").Random(18)
        idx = dp_index.CandidateIndex(dp_index.index_path(spool))
        rows = 0
        for i in range(ntickets):
            tid = f"dp-{i:04d}"
            outdir = os.path.join(base, "out", tid)
            os.makedirs(outdir, exist_ok=True)
            cands = []
            for k in range(10):
                sig = round(4.0 + rng.random() * 12.0, 2)
                freq = 1.0 + rng.random() * 50.0
                cands.append(Candidate(
                    r=round(100.0 + k, 2), z=round(rng.random(), 2),
                    sigma=sig, power=round(20.0 + sig, 4),
                    numharm=1 + k % 8, dm=round(10.0 * (k + 1), 2),
                    period_s=1.0 / freq, freq_hz=freq,
                    dm_hits=[(10.0 * (k + 1), sig)]))
            accelcands.write_candlist(
                cands, os.path.join(outdir, f"{tid}.accelcands"))
            q.submit(tid, ["bench://synthetic"], outdir, job_id=i)
            q.claim_next("dpbench")
            q.write_result(tid, "done", rc=0, outdir=outdir,
                           worker="dpbench")
            rows += idx.index_outdir(tid, outdir)
        for tid in (f"dp-{i:04d}" for i in range(ntickets)):
            got = idx.candidate_rows(tid)
            want = results._candidate_rows(
                os.path.join(base, "out", tid))
            assert got == want, f"index drift on {tid}"
        t0 = time.time()
        for _ in range(iters):
            indexed = idx.query(min_sigma=8.0, limit=50)
        query_ms = round((time.time() - t0) / iters * 1000.0, 3)
        t0 = time.time()
        for _ in range(iters):
            parsed = results.query_candidates(q, min_sigma=8.0,
                                              limit=50)
        parse_ms = round((time.time() - t0) / iters * 1000.0, 3)
        assert indexed["total"] == parsed["total"], \
            (indexed["total"], parsed["total"])
        idx.close()
        speedup = round(parse_ms / query_ms, 2) if query_ms > 0 \
            else -1.0
        # a store-side sweep proves every staged byte is durable
        store = dp_blobstore.BlobStore(os.path.join(base, "cas"))
        verified = all(store.verify(d) for d in digests)
        _log(f"dataplane: stage-in {stagein_mb_per_s} MB/s (put "
             f"{put_mb_per_s} MB/s), candidates {query_ms} ms "
             f"indexed vs {parse_ms} ms parse ({speedup}x), "
             f"verify {'clean' if verified else 'FAILED'}")
        _emit({
            "metric": "dataplane_stagein_mb_per_s",
            "value": stagein_mb_per_s,
            "unit": "MB/s",
            "dataplane": {
                "blobs": nblobs,
                "blob_mb": round(blob_mb, 2),
                "stagein_mb_per_s": stagein_mb_per_s,
                "put_mb_per_s": put_mb_per_s,
                "candidates_query_ms": query_ms,
                "candidates_parse_ms": parse_ms,
                "index_speedup": speedup,
                "tickets": ntickets,
                "rows": rows,
                "query_total": indexed["total"],
                # correctness rows: CI asserts these un-toleranced
                "all_blobs_verified": verified,
                "index_matches_parse": True,
            },
        })
    finally:
        gw.stop()
        if os.environ.get("TPULSAR_DPBENCH_KEEP", "") != "1":
            shutil.rmtree(base, ignore_errors=True)


def run_stream() -> None:
    """``bench.py --stream``: the streaming plane's headline numbers
    over the AOT-registered STREAM_PROFILE geometry — per-chunk
    ingest-to-searched latency p95 (dedisperse the chunk, search
    every span it completes) and sustained chunk throughput.
    Parity rides along un-toleranced: the streamed dedispersed
    series must be BIT-identical to the batch program over the same
    samples, the streamed trigger set must equal the batch
    span-partitioned search, and the injected dispersed pulse must
    be recovered.  Knobs: TPULSAR_STBENCH_CHUNKS (default 24) /
    TPULSAR_STBENCH_BACKEND (numpy|jax|auto, default numpy)."""
    import numpy as np

    from tpulsar.constants import dispersion_delay_s
    from tpulsar.stream import STREAM_PROFILE
    from tpulsar.stream import dedisp_state as dds
    from tpulsar.stream.dedisp_state import StreamDedisp
    from tpulsar.stream.trigger import SpanTrigger, trigger_digest

    n_chunks = int(os.environ.get("TPULSAR_STBENCH_CHUNKS", "24"))
    backend = dds.resolve_backend(
        os.environ.get("TPULSAR_STBENCH_BACKEND", "numpy"))
    geom = dict(STREAM_PROFILE)
    nchan, cl = int(geom["nchan"]), int(geom["chunk_len"])
    T = n_chunks * cl
    rng = np.random.default_rng(19)
    data = rng.normal(0, 1, (nchan, T)).astype(np.float32)
    freqs, _ = dds.geometry_freqs_dms(geom)
    pulse_dm, pulse_t = 12.0, 2 * cl + 17
    sh = np.round(
        dispersion_delay_s(pulse_dm, freqs, float(freqs[-1]))
        / geom["dt"]).astype(int)
    for c in range(nchan):
        s = pulse_t + sh[c]
        if s + 3 <= T:
            data[c, s:s + 3] += 8.0
    _log(f"stream bench: {n_chunks} x {nchan}x{cl} chunks, "
         f"backend {backend}")

    # one untimed warm lap: a jax backend's compile cost (absent on
    # a warm AOT worker) must never pollute the latency distribution
    for _ in StreamDedisp(geom, backend=backend).append(data[:, :cl]):
        pass
    sd = StreamDedisp(geom, backend=backend)
    trig = SpanTrigger(geom, session="bench", backend=backend)
    blocks, recs, lat = [], [], []
    t_start = time.time()
    for k in range(n_chunks):
        t0 = time.time()
        for blk in sd.append(data[:, k * cl:(k + 1) * cl]):
            blocks.append(blk)
            for _, r in trig.feed(blk):
                recs.extend(r)
        lat.append(time.time() - t0)
    t0 = time.time()
    for blk in sd.flush():
        blocks.append(blk)
        for _, r in trig.feed(blk):
            recs.extend(r)
    for _, r in trig.flush():
        recs.extend(r)
    drain_s = time.time() - t0
    total_s = time.time() - t_start
    stream_series = np.concatenate(blocks, axis=1)

    # ---- parity, asserted (bitwise, not toleranced) --------------
    if backend == "jax":
        from tpulsar.kernels import dedisperse as dd_k
        batch = np.asarray(
            dd_k.dedisperse_stream_batch(data, sd.shifts))
    else:
        pad = dds.pad_bucket(sd.maxshift)
        ext = np.concatenate(
            [data, np.broadcast_to(data[:, -1:], (nchan, pad))],
            axis=1)
        batch = dds._window_scan_numpy(ext, sd.shifts, T)
    series_ok = stream_series.shape == batch.shape \
        and np.array_equal(stream_series, batch)
    ctl = SpanTrigger(geom, session="bench", backend=backend)
    ctl_recs = []
    for _, r in ctl.feed(batch):
        ctl_recs.extend(r)
    for _, r in ctl.flush():
        ctl_recs.extend(r)
    trig_ok = trigger_digest(recs) == trigger_digest(ctl_recs)
    found = any(abs(r["dm"] - pulse_dm) < 2.0
                and abs(r["sample"] - pulse_t) < 8 for r in recs)
    parity_ok = series_ok and trig_ok and found
    assert series_ok, "streamed series differs from batch (bitwise)"
    assert trig_ok, "streamed trigger set differs from batch spans"
    assert found, "injected pulse not recovered by the trigger plane"

    p95 = round(float(np.percentile(lat, 95)), 6)
    mean = round(float(np.mean(lat)), 6)
    cps = round(n_chunks / total_s, 2) if total_s > 0 else -1.0
    _log(f"stream: chunk latency p95 {p95 * 1000:.2f} ms (mean "
         f"{mean * 1000:.2f} ms), {cps} chunks/s, {len(recs)} "
         f"trigger(s), parity {'ok' if parity_ok else 'FAILED'}")
    _emit({
        "metric": "stream_chunk_latency_p95_s",
        "value": p95,
        "unit": "s",
        "stream": {
            "chunks": n_chunks,
            "chunk_len": cl,
            "nchan": nchan,
            "ndms": int(geom["ndms"]),
            "span_chunks": int(geom["span_chunks"]),
            "backend": backend,
            "chunk_latency_p95_s": p95,
            "chunk_latency_mean_s": mean,
            "chunks_per_sec": cps,
            "drain_s": round(drain_s, 4),
            "triggers": len(recs),
            # correctness rows: CI asserts these un-toleranced
            "parity_ok": parity_ok,
            "series_bit_identical": series_ok,
            "trigger_parity": trig_ok,
            "pulse_found": found,
        },
    })


def run_doctor() -> None:
    """``bench.py --doctor``: the health doctor's cost and reflexes —
    (a) steady-state tick overhead over a populated journal (the tax
    every controller loop pays for free alerting; it must stay in
    the low milliseconds so hosting the doctor is never a reason to
    turn it off), and (b) detection latency: the wall time from the
    second crash-flavoured ``worker_exit`` landing in the journal to
    the first tick that reports ``worker_flap`` firing (incremental
    journal read + the full rule pack, excluding the configurable
    poll interval — the part the code owns, not the knob).  Emits
    one bench/v2 record with an additive ``doctor`` key; headline
    ``value`` is the steady-state tick overhead.  Knobs:
    TPULSAR_DOCTORBENCH_EVENTS (default 2000) / TICKS (default 50) /
    KEEP=1 keeps the scratch spool."""
    import shutil
    import statistics
    import tempfile

    from tpulsar.obs import health, journal

    nevents = int(os.environ.get("TPULSAR_DOCTORBENCH_EVENTS", "2000"))
    nticks = int(os.environ.get("TPULSAR_DOCTORBENCH_TICKS", "50"))
    base = tempfile.mkdtemp(prefix="tpulsar_doctorbench_")
    spool = os.path.join(base, "spool")
    os.makedirs(spool, exist_ok=True)
    # a believable steady-state journal: full submit->claim->result
    # cycles so the queue-wait SLO rule has real samples to digest
    _log(f"doctor bench: journaling {nevents} events ...")
    cycle = ("submitted", "claimed", "search_start", "result")
    for i in range(nevents // len(cycle)):
        tid = f"db-{i:05d}"
        journal.record(spool, "submitted", ticket=tid)
        journal.record(spool, "claimed", ticket=tid,
                       worker=f"w{i % 4}", queue_wait_s=0.05)
        journal.record(spool, "search_start", ticket=tid,
                       worker=f"w{i % 4}")
        journal.record(spool, "result", ticket=tid, status="done",
                       rc=0)
    det = health.HealthDetector(spool, persist=False,
                                journal_events=False, notify=False)
    det.tick()                      # absorb the cold full-journal read
    ticks = []
    for _ in range(nticks):
        t0 = time.time()
        det.tick()
        ticks.append(time.time() - t0)
    tick_overhead = statistics.mean(ticks)
    # reflex: crash storm -> first firing tick (poll interval is a
    # knob, so the measured latency is read+evaluate+transition only)
    t_inject = time.time()
    for _ in range(2):
        journal.record(spool, "worker_exit", worker="w9", rc=70,
                       kind="crash")
    latency = -1.0
    for _ in range(100):
        active = det.tick()
        if any(a["rule"] == "worker_flap" for a in active):
            latency = time.time() - t_inject
            break
    _log(f"doctor bench: tick {tick_overhead * 1e3:.2f} ms over "
         f"{nevents} events, detection latency "
         f"{latency * 1e3:.2f} ms")
    _emit({
        "metric": "doctor_tick_overhead",
        "value": round(tick_overhead, 6),
        "unit": "s",
        "doctor": {
            "events": nevents,
            "ticks": nticks,
            "rules": len(det.rules),
            "tick_overhead_s": round(tick_overhead, 6),
            "tick_p95_s": round(
                sorted(ticks)[int(0.95 * (len(ticks) - 1))], 6),
            "detection_latency_s": round(latency, 6),
            "fired": sorted(a["rule"] for a in active),
        },
    })
    if os.environ.get("TPULSAR_DOCTORBENCH_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def _usable_cpus() -> list:
    """The CPU ids this process may actually run on, for taskset
    pinning (a cgroup cpuset need not start at 0 or be contiguous)."""
    if hasattr(os, "sched_getaffinity"):
        return sorted(os.sched_getaffinity(0))
    return list(range(os.cpu_count() or 1))


def run_fleet() -> None:
    """``bench.py --fleet``: the same synthetic beam set through a
    1-worker and a 2-worker fleet (tpulsar/fleet/) on one spool, and
    report aggregate beams/s — the number that justifies horizontal
    scale-out on top of the warm path.  Workers share one persistent
    compile cache (scaling is the contrast being measured, not
    caching), and the aggregate rate is computed over the result
    records' own timestamps (first beam start -> last beam finish),
    so worker boot (JAX import, cache activation) is excluded exactly
    as the serve bench excludes it.

    Every worker (in BOTH configs) is pinned to its own CPU core
    (taskset) with a single-threaded XLA pool: in the deployment this
    models, a fleet worker owns one device — on CPU that means one
    core each, so the contrast measures horizontal scaling at fixed
    per-worker resources rather than letting the single worker's XLA
    thread pool absorb every core and calling that the baseline
    (override via TPULSAR_FLEET_PIN=0).  Emits one bench/v2 record
    with an additive ``fleet`` key."""
    import shutil
    import statistics
    import tempfile

    from tpulsar.fleet.controller import FleetController
    from tpulsar.io import synth
    from tpulsar.serve import protocol

    nbeams = int(os.environ.get("TPULSAR_FLEET_NBEAMS", "6"))
    nchan = int(os.environ.get("TPULSAR_FLEET_NCHAN", "16"))
    nsamp = int(os.environ.get("TPULSAR_FLEET_NSAMP", str(1 << 12)))
    dm_max = float(os.environ.get("TPULSAR_FLEET_DM_MAX", "30"))
    base = tempfile.mkdtemp(prefix="tpulsar_fleetbench_")

    cfg_file = os.path.join(base, "config.yaml")
    with open(cfg_file, "w") as fh:
        fh.write(
            "searching:\n"
            f"  dm_max: {dm_max}\n"
            "  use_hi_accel: false\n"
            "  max_cands_to_fold: 2\n"
            "processing:\n"
            f"  base_working_directory: {base}/work\n"
            f"  base_results_directory: {base}/res\n"
            f"basic:\n  log_dir: {base}/logs\n")
    # worker subprocesses read both of these from the environment
    os.environ["TPULSAR_CONFIG"] = cfg_file
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, "cache")

    psr = synth.PulsarSpec(period_s=0.05, dm=20.0,
                           snr_per_sample=1.5)
    beams = []
    for i in range(nbeams):
        spec = synth.BeamSpec(nchan=nchan, nsamp=nsamp, nsblk=64,
                              nbits=4, tsamp_s=5.24288e-4,
                              scan=100 + i)
        beams.append(synth.synth_beam(
            os.path.join(base, f"data{i}"), spec, pulsars=[psr],
            merged=True))

    def run_config(nworkers: int, tag: str) -> dict:
        spool = os.path.join(base, f"spool{tag}")
        tickets = []
        for i, fns in enumerate(beams):
            tid = f"fleet{tag}-{i}"
            protocol.write_ticket(
                spool, tid, fns,
                os.path.join(base, f"out{tag}_{i}"), job_id=i)
            tickets.append(tid)
        _log(f"fleet config: {nbeams} beams through {nworkers} "
             f"worker(s) ...")
        pin = os.environ.get("TPULSAR_FLEET_PIN", "1") != "0"
        cpus = _usable_cpus()
        worker_env = None
        if pin:
            env_pin = {
                "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                              " --xla_cpu_multi_thread_eigen=false"
                              ).strip(),
                "OMP_NUM_THREADS": "1",
                "OPENBLAS_NUM_THREADS": "1",
                "MKL_NUM_THREADS": "1",
            }
            worker_env = lambda wid: env_pin     # noqa: E731

        def worker_cmd(wid: str) -> list:
            argv = []
            if pin:
                # one core per worker, like one device per worker —
                # indexed into the ACTUAL affinity mask (a cgroup
                # cpuset need not start at cpu 0)
                argv += ["taskset", "-c",
                         str(cpus[int(wid[1:]) % len(cpus)])]
            argv += [sys.executable, "-m", "tpulsar.cli",
                     "--config", cfg_file,
                     "serve", "--spool", spool, "--worker-id", wid,
                     "--once", "--no-warmstart"]
            return argv

        t0 = time.time()
        ctrl = FleetController(
            spool, workers=nworkers, once=True, poll_s=0.2,
            max_worker_restarts=1, worker_env=worker_env,
            worker_cmd=worker_cmd)
        rc = ctrl.run()
        wall = round(time.time() - t0, 3)
        done = [r for r in (protocol.read_result(spool, t)
                            for t in tickets)
                if r and r.get("status") == "done"]
        rec: dict = {"nworkers": nworkers, "rc": rc,
                     "beams_done": len(done),
                     "controller_wallclock_s": wall}
        if done:
            def span_bps(recs):
                starts = [r["finished_at"]
                          - r.get("beam_seconds", 0.0) for r in recs]
                span = (max(r["finished_at"] for r in recs)
                        - min(starts))
                return round(span, 3), round(
                    len(recs) / max(1e-9, span), 4)

            rec["serving_span_s"], rec["aggregate_beams_per_s"] = \
                span_bps(done)
            by_worker: dict[str, list] = {}
            for r in sorted(done, key=lambda r: r["finished_at"]):
                by_worker.setdefault(r.get("worker", "?"),
                                     []).append(r)
            rec["per_worker_beam_s"] = {
                w: [round(r.get("beam_seconds", 0.0), 3) for r in rs]
                for w, rs in by_worker.items()}
            # the warm regime: drop each worker's FIRST beam — it
            # pays the per-process jit traces a resident fleet
            # amortizes over days; steady-state throughput is what
            # scale-out buys
            rec["per_worker_warm_steady_s"] = {
                w: round(statistics.median(
                    [r.get("beam_seconds", 0.0) for r in rs[1:]]), 3)
                for w, rs in by_worker.items() if len(rs) > 1}
            warm = [r for rs in by_worker.values() for r in rs[1:]]
            if warm:
                rec["warm_span_s"], \
                    rec["aggregate_warm_beams_per_s"] = span_bps(warm)
        return rec

    def host_ceiling() -> dict:
        """Measure what 2-process scaling THIS host can physically
        deliver for jax CPU work (one fixed FFT loop, single vs two
        pinned copies).  On a dedicated 2-core box this reads ~2.0;
        on a noisy/sandboxed host it can be ~1.0 — and no fleet can
        scale past it, so the fleet speedup below is reported
        alongside this ceiling rather than pretending the host is
        quiet."""
        probe = os.path.join(base, "probe.py")
        with open(probe, "w") as fh:
            fh.write(
                "import os, time\n"
                "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
                "import jax, jax.numpy as jnp\n"
                "f = jax.jit(lambda x: jnp.fft.rfft(x, axis=-1)"
                ".real.sum())\n"
                "x = jnp.ones((512, 4096), jnp.float32)\n"
                "f(x).block_until_ready()\n"
                "t0 = time.time(); n = 0\n"
                "while time.time() - t0 < 6.0:\n"
                "    f(x).block_until_ready(); n += 1\n"
                "print(n)\n")
        import subprocess as sp

        cpus = _usable_cpus()

        def spawn(slot):
            argv = ([]
                    if os.environ.get("TPULSAR_FLEET_PIN", "1") == "0"
                    else ["taskset", "-c",
                          str(cpus[slot % len(cpus)])])
            return sp.Popen(argv + [sys.executable, probe],
                            stdout=sp.PIPE, text=True)

        def iters(proc):
            out, _ = proc.communicate(timeout=120)
            return int(out.strip().splitlines()[-1])

        # bracket the dual measurement with two singles: host
        # capacity drifts minute-to-minute, and a capacity swing
        # between the single and dual phases would fake (or mask)
        # scaling in the probe exactly as it would in the fleet run
        single_a = iters(spawn(0))
        pair = [spawn(0), spawn(1)]
        dual = sum(iters(p) for p in pair)
        single_b = iters(spawn(0))
        import statistics as _st
        single = _st.median([single_a, single_b])
        return {"single_iters": [single_a, single_b],
                "dual_iters": dual,
                "scaling": round(dual / max(1, single), 2)}

    _log("probing the host's 2-process jax scaling ceiling ...")
    ceiling = host_ceiling()
    _log(f"host ceiling: {ceiling['scaling']}x")

    # the 1-worker baseline is measured BOTH before and after the
    # 2-worker run: this (noisy, shared) host's capacity drifts on
    # the minutes timescale, and bracketing the fleet run keeps a
    # capacity swing from masquerading as (or hiding) scaling
    one = run_config(1, "1a")
    two = run_config(2, "2")
    one_b = run_config(1, "1b")
    steadies = [s for r in (one, one_b)
                for s in (r.get("per_worker_warm_steady_s") or {}
                          ).values()]
    steady1 = statistics.median(steadies) if steadies else None
    two_warm = two.get("aggregate_warm_beams_per_s")
    result = {
        "metric": "fleet_aggregate_warm_beams_per_s",
        "value": two_warm if two_warm else -1.0,
        "unit": "beams/s",
        "fleet": {
            "nbeams": nbeams, "nchan": nchan, "nsamp": nsamp,
            "dm_max": dm_max,
            "one_worker": one, "two_worker": two,
            "one_worker_post": one_b,
            "host_parallel_ceiling": ceiling,
        },
    }
    if steady1 and two_warm:
        # the headline contrast: 2-worker warm aggregate throughput
        # vs the 1-worker warm steady state expressed as beams/s
        result["fleet"]["one_worker_warm_beams_per_s"] = round(
            1.0 / steady1, 4)
        speedup = round(two_warm * steady1, 2)
        result["fleet"]["speedup_vs_one_worker_warm"] = speedup
        if ceiling.get("scaling"):
            # ~1.0 means the fleet layer added no overhead on top of
            # whatever parallelism the host could physically give
            result["fleet"]["scaling_efficiency_vs_host_ceiling"] = \
                round(speedup / ceiling["scaling"], 2)
    one_aggs = [r["aggregate_warm_beams_per_s"]
                for r in (one, one_b)
                if r.get("aggregate_warm_beams_per_s")]
    if one_aggs and two_warm:
        result["fleet"]["speedup_vs_one_worker_aggregate"] = round(
            two_warm / statistics.median(one_aggs), 2)
    _emit(result)
    if os.environ.get("TPULSAR_FLEET_KEEP", "") != "1":
        shutil.rmtree(base, ignore_errors=True)


def _acquire_campaign_lock() -> "object | None":
    """Serialize chip access between benches via the
    .campaign.lock flock.  Two clients of the single chip corrupt
    both measurements (a chip belongs to one process: the second
    presents as a FALSE tpu_unhealthy record), so when a campaign holds
    the lock this bench WAITS — up to TPULSAR_BENCH_LOCK_WAIT s
    (default 10800) — rather than racing it; a finished campaign also
    leaves the compilation cache warm, making the wait a net win.
    Returns the held file object (keep a reference until exit).  If
    the wait times out, running anyway would contend with the active
    campaign — corrupting BOTH measurements and possibly recording a
    false tpu_unhealthy — so this emits an explicit error record and
    exits instead.  Benches spawned BY the campaign set
    TPULSAR_CAMPAIGN_LOCK_HELD=1 to skip this (their parent already
    holds the lock; a fresh flock here would deadlock on it)."""
    if os.environ.get("TPULSAR_CAMPAIGN_LOCK_HELD", "") == "1":
        return None
    import fcntl
    path = os.path.join(_REPO, ".campaign.lock")
    fh = open(path, "w")
    wait_s = float(os.environ.get("TPULSAR_BENCH_LOCK_WAIT", "10800"))
    t0 = time.time()
    logged = False
    while True:
        try:
            fcntl.flock(fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
            return fh
        except OSError:
            if time.time() - t0 > wait_s:
                _log(f"campaign lock still held after {wait_s:.0f} s")
                _emit({
                    "metric": "mock_beam_full_plan_search_wallclock",
                    "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                    "error": "campaign_lock_timeout",
                    "detail": "a measurement campaign held "
                              ".campaign.lock for the whole wait; "
                              "refusing to contend for the single "
                              "chip (see bench_runs/ for the "
                              "campaign's own records)"})
                raise SystemExit(0)
            if not logged:
                _log("a measurement campaign holds .campaign.lock — "
                     f"waiting up to {wait_s:.0f} s for it to finish")
                logged = True
            time.sleep(30)


def main() -> None:
    if "--measured" in sys.argv:
        run_measured()
        return
    if "--serve" in sys.argv:
        run_serve()
        return
    if "--accel" in sys.argv:
        run_accel_ab()
        return
    if "--beambatch" in sys.argv:
        run_beambatch()
        return
    if "--fleet" in sys.argv:
        run_fleet()
        return
    if "--gateway" in sys.argv:
        run_gateway()
        return
    if "--chaos" in sys.argv:
        run_chaos()
        return
    if "--resume" in sys.argv:
        run_resume()
        return
    if "--autoscale" in sys.argv:
        run_autoscale()
        return
    if "--queue" in sys.argv:
        run_queue()
        return
    if "--dataplane" in sys.argv:
        run_dataplane()
        return
    if "--doctor" in sys.argv:
        run_doctor()
        return
    if "--stream" in sys.argv:
        run_stream()
        return
    if "--probe" in sys.argv:
        rec = probe_device(
            float(os.environ.get("TPULSAR_BENCH_PROBE_TIMEOUT", "180")))
        print(json.dumps(rec if rec else {"ok": False}))
        return
    _campaign_lock = _acquire_campaign_lock()  # noqa: F841 — held till exit

    try:
        _bench_dtype_name()   # fail fast, before any TPU spend
    except SystemExit as e:
        _emit({
            "metric": "mock_beam_full_plan_search_wallclock",
            "value": -1.0, "unit": "s", "vs_baseline": 0.0,
            "error": str(e)})
        return

    cfg_raw = os.environ.get("TPULSAR_BENCH_CONFIG", "").strip()
    bench_cfg = 0
    if cfg_raw:
        # Fail fast on a misconfig — before this check the harness
        # would spend the AOT gate + smoke probes (most of the budget)
        # only for the child to SystemExit on the same parse.  The
        # parsed value is THE config for the rest of main (one parse;
        # a second, different parse is how '+3' passes validation but
        # gates the wrong program set).
        try:
            bench_cfg = int(cfg_raw)
            if bench_cfg not in (1, 2, 3, 4, 5):
                raise ValueError
        except ValueError:
            _emit({
                "metric": "mock_beam_full_plan_search_wallclock",
                "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                "error": f"invalid TPULSAR_BENCH_CONFIG {cfg_raw!r} "
                         "(must be 1-5)"})
            return

    probe_timeout = float(os.environ.get("TPULSAR_BENCH_PROBE_TIMEOUT",
                                         "180"))
    deadline = float(os.environ.get("TPULSAR_BENCH_DEADLINE", "900"))
    total_budget = float(os.environ.get("TPULSAR_BENCH_TOTAL_BUDGET",
                                        "900"))

    result: dict | None = None
    t_start = time.time()

    def remaining(reserve: float = 60.0) -> float:
        """Seconds left in the total budget, keeping `reserve` for
        kill/drain slop and the final JSON emission."""
        return max(5.0, total_budget - (time.time() - t_start) - reserve)

    # Deadline floor reserved for the full-scale measured run: the
    # gate, smoke probes, and ladder are aids — they must never starve
    # the headline measurement into a guaranteed timeout record.
    full_reserve = float(os.environ.get("TPULSAR_BENCH_FULL_RESERVE",
                                        "300"))

    def spendable(cap: float, floor: float = 30.0) -> float:
        """Budget a pre-flight phase: at most `cap`, never dipping
        into the full-run reserve, but at least `floor` so the phase
        can do SOMETHING (a sub-floor budget means the total budget is
        already blown and the run will be a timeout record anyway)."""
        return max(floor, min(cap, remaining() - full_reserve))

    try:
        _log(f"health-probing accelerator (timeout {probe_timeout:.0f} s)")
        probe = probe_device(min(probe_timeout, remaining()))
        want_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
        if probe is not None and not want_cpu \
                and probe.get("platform") == "cpu":
            # The TPU plugin failed to register and jax silently fell
            # back to CPU: running the full-scale search there would
            # blow the deadline and be misreported as a timeout.
            _log(f"probe came back on CPU, not TPU: {probe}")
            probe = None
        if probe is not None:
            _log(f"probe OK: {probe}")
            on_tpu = probe.get("platform") not in (None, "cpu")
            bench_scale = float(os.environ.get("TPULSAR_BENCH_SCALE",
                                               "1.0"))
            # config 2 is the headline with the accel stage forced off
            # (run_measured sets ACCEL=0 in the child); the gate must
            # see the accel setting the child will actually use
            run_accel = (os.environ.get("TPULSAR_BENCH_ACCEL", "1")
                         != "0") and bench_cfg != 2
            aot_rec = None
            if on_tpu and os.environ.get("TPULSAR_BENCH_AOT", "1") != "0":
                # Mandatory compile-only gate before ANY full-scale
                # execute: an over-budget program must die in the
                # compiler (clean HTTP error), never at runtime (hours
                # -long chip wedge — the round-2 failure mode).
                _log("AOT compile-only memory gate "
                     "(full-scale programs, no execution)")
                # accel programs compile in ~10 min EACH on this
                # 1-core host, so the default cap can defer a cold
                # gate; callers that can afford it (the campaign's
                # quick-datapoint step) raise the cap and loop on the
                # aot_gate_deferred record, resuming from cache
                aot_cap = float(os.environ.get(
                    "TPULSAR_BENCH_AOT_BUDGET", "600"))
                aot_rec = run_aot_gate(spendable(aot_cap, floor=60.0),
                                       accel=run_accel,
                                       scale=bench_scale,
                                       config=bench_cfg)
                _log(f"AOT gate: {aot_rec}")
                if not aot_rec["ok"]:
                    result = {
                        "metric": "mock_beam_full_plan_search_wallclock",
                        "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                        # a clean deadline deferral is NOT the
                        # over-budget-compile signature — label it
                        # distinctly so triage reads the record right
                        "error": ("aot_gate_deferred"
                                  if aot_rec.get("deferred")
                                  else "aot_gate_failed"),
                        "aot_check": aot_rec, "probe": probe,
                    }
                    _emit(result)
                    return
            # Measured scale ladder (TPU, full-scale headline only):
            # short runs at 0.1 / 0.5 scale before committing the
            # budget to the full beam.  Even if the full-scale run
            # fails, the rungs are real TPU wall-clock datapoints.
            ladder: list[dict] = []
            anomaly = False
            if (on_tpu and bench_scale >= 0.999 and bench_cfg == 0
                    and os.environ.get("TPULSAR_BENCH_LADDER",
                                       "1") != "0"):
                for rung in (0.1, 0.5):
                    rung_cap = min(300.0, remaining() * 0.3)
                    if remaining() - rung_cap < full_reserve \
                            or rung_cap < 60.0:
                        _log(f"ladder rung {rung} skipped (budget: "
                             "reserving the full-scale deadline)")
                        break
                    _log(f"ladder rung: scale={rung} "
                         f"(cap {rung_cap:.0f} s)")
                    st, rr, rinfo = run_child(
                        rung_cap, label=f"ladder{rung}", extra_env={
                            "TPULSAR_BENCH_SCALE": str(rung),
                            "TPULSAR_BENCH_NBEAMS": "1"})
                    if rr is not None:
                        ladder.append({
                            "scale": rung, "value_s": rr["value"],
                            "dm_trials": rr.get("dm_trials"),
                            "injected_pulsar_recovered":
                                rr.get("injected_pulsar_recovered"),
                            "stage_s": rr.get("stage_s")})
                        _log(f"rung {rung}: {rr['value']} s, "
                             f"{rr.get('dm_trials')} trials")
                    elif st in ("timeout", "stall", "stage_budget"):
                        # Rung shapes are NOT warmed by the AOT gate
                        # (it compiles full-scale programs), so a rung
                        # overrun is most likely cold-compile cost,
                        # not a chip anomaly: skip remaining rungs but
                        # still attempt the gated full-scale run.
                        ladder.append({"scale": rung, "error": st,
                                       **rinfo, **_read_partial()})
                        if rinfo.get("stalled_stage") \
                                == "hi-accelsearch":
                            # exact match: 'after:hi-accelsearch'
                            # means the stage FINISHED and the hang
                            # is in the next scope — not an accel
                            # stall
                            # The hi stage has hung its first window
                            # drain (2026-08-01: every configuration
                            # at every scale except one) — a rung
                            # killed THERE predicts the full-scale
                            # attempt dying the same way.  Degrade to
                            # accel-off for the rest of this bench,
                            # recorded loudly: a completed beam with
                            # accel_stage=false beats a -1 record.
                            os.environ["TPULSAR_BENCH_ACCEL"] = "0"
                            _log("rung stalled IN hi-accelsearch — "
                                 "disabling the accel stage for the "
                                 "remaining attempts (recorded)")
                        _log(f"rung {rung} exceeded its cap — "
                             "skipping remaining rungs, proceeding "
                             "to the AOT-gated full-scale run")
                        break
                    else:
                        ladder.append({"scale": rung, "error": st,
                                       **rinfo, **_read_partial()})
                        anomaly = True
                        _log(f"rung {rung} CRASHED — stopping the "
                             "ladder, skipping full scale")
                        break
            if anomaly:
                result = {
                    "metric": "mock_beam_full_plan_search_wallclock",
                    "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                    "error": "ladder_anomaly", "ladder": ladder,
                    "probe": probe,
                }
                if aot_rec is not None:
                    result["aot_check"] = aot_rec
                _emit(result)
                return
            eff_deadline = min(deadline, remaining())
            status, result, kinfo = run_child(
                eff_deadline,
                label=f"cfg{bench_cfg}" if bench_cfg else "headline")
            hi_stall = None
            if (result is None and bench_cfg == 0
                    and status in ("timeout", "stall", "stage_budget")
                    and kinfo.get("stalled_stage") == "hi-accelsearch"
                    and os.environ.get("TPULSAR_BENCH_ACCEL") != "0"
                    and remaining() > 700.0):
                # Same hi-stage hang at full scale: retry ONCE with
                # the accel stage disabled so the record is a
                # completed beam with accel_stage=false and the stall
                # attribution attached, not a bare -1 (the complete
                # no-accel full-scale beam measures 641 s warm,
                # BENCH_fullscale_noaccel_r05.json).  hi_stall rides
                # to the FINAL record below — median sampling can
                # replace `result`, and a failed retry must still
                # carry the original accel attribution.
                _log("full-scale run stalled IN hi-accelsearch — "
                     "one retry with the accel stage disabled")
                hi_stall = {k: kinfo[k] for k in
                            ("stalled_stage", "stage_elapsed_s",
                             "kill_reason") if k in kinfo}
                os.environ["TPULSAR_BENCH_ACCEL"] = "0"
                eff_deadline = min(deadline, remaining())
                status, result, kinfo = run_child(
                    eff_deadline, label="headline_noaccel")
            # TPULSAR_BENCH_SAMPLES=N (default 1): repeat the measured
            # run and make the MEDIAN the headline, samples listed —
            # full-scale CPU wall-clock varies ±40% run-to-run on this
            # host (BENCH_cfg3_ab_r04.json), and a best-draw headline
            # overstates the claim (round-4 verdict weak #3 / next #7)
            try:
                nsamples = int(os.environ.get("TPULSAR_BENCH_SAMPLES",
                                              "1"))
            except ValueError:
                # never let a malformed knob discard the measured
                # result we already hold
                _log("ignoring unparseable TPULSAR_BENCH_SAMPLES "
                     f"{os.environ.get('TPULSAR_BENCH_SAMPLES')!r}")
                nsamples = 1
            if status == "ok" and result is not None and nsamples > 1:
                runs = [result]
                for i in range(1, nsamples):
                    cap = min(deadline, remaining())
                    if cap < 60.0:
                        _log(f"sample {i} skipped: budget exhausted "
                             f"({len(runs)}/{nsamples} collected)")
                        break
                    st_i, r_i, _ = run_child(cap, label=f"sample{i}")
                    if r_i is None:
                        _log(f"sample {i} failed ({st_i}); keeping "
                             f"the {len(runs)} collected")
                        break
                    runs.append(r_i)
                chron = [r["value"] for r in runs]
                # upper median on even counts: never headline the
                # faster of two middles
                med = sorted(chron)[len(chron) // 2]
                result = next(r for r in runs if r["value"] == med)
                result["samples"] = chron
                result["sample_policy"] = f"median_of_{len(runs)}"
            if result is None:
                partial = _read_partial()
                elapsed = round(time.time() - t_start, 2)
                err = {"timeout": f"timed_out_after_{eff_deadline:.0f}s",
                       "stall": "stalled_no_stage_heartbeat",
                       "stage_budget": "stage_budget_exceeded",
                       }.get(status, "measured_run_crashed")
                killed = status in ("timeout", "stall", "stage_budget")
                result = {
                    "metric": "mock_beam_full_plan_search_wallclock",
                    "value": elapsed if killed else -1.0,
                    "unit": "s",
                    "vs_baseline": 0.0,
                    "error": err,
                    # WHICH stage the kill interrupted and how long it
                    # had been running — the attribution the round-4
                    # on-chip timeout record was missing
                    "probe": probe, **kinfo, **partial,
                }
            if hi_stall:
                # attach on WHATEVER record survived (median pick,
                # completed retry, or the retry's own error record)
                result["accel_stage_disabled_after_stall"] = hi_stall
            if aot_rec is not None:
                result.setdefault("aot_check", aot_rec)
            if ladder:
                result.setdefault("ladder", ladder)
                with open(PARTIAL_PATH, "a") as fh:
                    for r in ladder:
                        fh.write(json.dumps(
                            {"event": "ladder_rung", **r}) + "\n")
        else:
            _log("accelerator UNHEALTHY (probe hung/crashed/fell back "
                 "to CPU)")
            result = {
                "metric": "mock_beam_full_plan_search_wallclock",
                "value": -1.0, "unit": "s", "vs_baseline": 0.0,
                "error": "tpu_unhealthy",
                "probe": f"TPU jax.devices()+matmul did not complete in "
                         f"{probe_timeout:.0f} s (or fell back to CPU)",
            }
    except Exception as e:  # the one JSON line must still appear
        result = {
            "metric": "mock_beam_full_plan_search_wallclock",
            "value": -1.0, "unit": "s", "vs_baseline": 0.0,
            "error": f"bench_harness_error: {type(e).__name__}: {e}",
        }
    _emit(result)
    if result.get("error") == "tpu_unhealthy":
        # a device run that finds no chip fails
        sys.exit(1)


if __name__ == "__main__":
    main()
