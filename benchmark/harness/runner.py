"""One run of one cell: set-up, warm-up, window, check, result line."""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time

from benchmark.harness import cells, check as check_mod, generate, layers
from benchmark.harness import tracered, window

#: the program's counters the harness reads around the window:
#: result-line name -> (counter, {label: value} or None for all series)
COUNTERS = {
    "inline_compiles": ("tpulsar_compile_cache_misses_total", None),
    "cache_hits": ("tpulsar_compile_cache_hits_total", None),
    "hi_trials_per_dm": ("tpulsar_accel_batch_trials_total", "per_dm"),
    "hi_trials_rescued": ("tpulsar_accel_batch_trials_total", "rescued"),
    "rescue_rows": ("tpulsar_rescue_rows_total", None),
}


class NoChip(SystemExit):
    """No accelerator this cell can run on: exit non-zero, no result."""


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def require_chip(chips: int, peaks: dict):
    """The devices this run measures on, or NoChip.  There is no CPU
    fallback and no flag for one."""
    import jax

    devs = jax.devices()
    plat, kind = devs[0].platform, devs[0].device_kind
    if plat != "tpu":
        raise NoChip(f"benchmark: platform is {plat!r}, not 'tpu': a "
                     "number from a CPU run is never a device metric")
    if kind not in peaks:
        raise NoChip(f"benchmark: device kind {kind!r} is not in "
                     "benchmark/peaks.json (an unknown chip has no "
                     "roofline; add its peaks with their source)")
    if len(devs) < chips:
        raise NoChip(f"benchmark: cell asks for {chips} chip(s), jax "
                     f"finds {len(devs)}")
    return devs


def counter_totals() -> dict:
    from tpulsar.obs import telemetry

    snap = telemetry.metrics.REGISTRY.snapshot()
    out = {}
    for name, (counter, label) in COUNTERS.items():
        series = (snap.get(counter) or {}).get("series", {})
        out[name] = float(sum(v for k, v in series.items()
                              if label is None or k == label))
    return out


def cost_shapes(cell, plan, params) -> dict:
    """Shapes the cost functions take, at the slice's first pass.  The
    rows per program call are the program's own blocking, read from it
    the way a kernel's name is."""
    from tpulsar.kernels import accel as accel_k
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    step = plan[0]
    T = cell.nsamp // step.downsamp
    nfft = ddplan.choose_n(T)
    nbins = nfft // 2 + 1
    nz = len(accel_k.z_grid(params.hi_accel_zmax))
    chunk = executor.pass_chunk_size(step.dms_per_pass, nfft, params)
    nsub = (params.nsub if cell.nchan % params.nsub == 0
            else ddplan.largest_divisor_leq(cell.nchan, params.nsub))
    return {"T": T, "nfft": nfft, "nbins": nbins, "nsub": nsub,
            "nz": nz, "zmax": params.hi_accel_zmax,
            "numharm": params.hi_accel_numharm,
            "topk": params.topk_per_stage,
            "hi_rows": accel_k.plane_dm_chunk(nbins, nz),
            "dd_rows": chunk / math.ceil(chunk / 32)}


class GcWatch:
    """Full (generation 2) collections of the Python heap and the
    seconds each took, by ``gc.callbacks``: a run's stderr says how
    many fell inside the window."""

    def __init__(self) -> None:
        self.seconds: list[float] = []
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.time()
        elif self._t0 is not None:
            self.seconds.append(time.time() - self._t0)
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


class Tracer:
    """The profiler around (the start of) the window.  Stops at the
    first stage boundary after `seconds`, or at the first slice call's
    last pass, whichever comes first — always on the main thread,
    between stages, so no stage's fenced time includes the dump."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.time()

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def maybe_stop(self, force: bool = False) -> None:
        if self.running and (force
                             or time.time() - self.t0 >= self.seconds):
            import jax

            self.t1 = time.time()
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def setup(cell, seed: int, host: dict):
    """The cell's masked block on the device from the seed, its plan
    slice and parameters.  -> (block, psr, plan, params)."""
    import jax
    import jax.numpy as jnp
    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.plan import ddplan

    plan = cells.plan_slice(cell)
    T_s = ddplan.choose_n(cell.nsamp) * cell.dt
    psr = generate.draw_pulsar(seed, cell.traffic["pulsar"],
                               cells.first_pass_dms(plan), T_s)
    block = generate.make_block(seed, psr, cell.freqs, cell.dt,
                                cell.nsamp, T_s)
    block.block_until_ready()
    t0 = time.time()
    sp = cell.config["search_params"]
    mask = rfi_k.find_rfi_chan(block, cell.dt,
                               block_len=int(sp["rfifind_blocklen"]),
                               threshold=float(sp["rfi_threshold"]))
    block = rfi_k.apply_mask_chan(
        block, jnp.asarray(mask.full_mask()), jnp.asarray(mask.chan_fill),
        mask.block_len)
    jax.block_until_ready(block)
    host["rfifind_s"] = time.time() - t0
    return block, psr, plan, cells.search_params(cell)


def measure(cell, seed: int, seconds: float, trace: bool, *,
            t_process: float, warm: bool = True, control: bool = False,
            log=print, search_block=None, dump_trace: str = "") -> dict:
    """Everything after the look for a chip.  -> the result line."""
    import jax
    from tpulsar.aot import warmstart
    from tpulsar.obs import trace as obs_trace

    warmstart.install_runtime_monitor()
    if trace:
        os.environ["TPULSAR_TRACE_SYNC"] = "1"
        obs_trace.start()
    host: dict = {}
    block, psr, plan, params = setup(cell, seed, host)
    log(f"setup: block {tuple(block.shape)} {block.dtype}, pulsar {psr}, "
        f"rfifind {host['rfifind_s']:.2f} s, "
        f"{sum(s.numdms for s in plan)} trials in "
        f"{sum(s.numpasses for s in plan)} pass(es)")

    tracer = Tracer(os.path.join(cell.root, ".bench_trace"),
                    float(cell.traffic.get("trace_seconds", 5.0)))
    npasses = sum(s.numpasses for s in plan)

    def one_call(keep=True, annotate=False):
        return window.slice_call(block, cell.freqs, cell.dt, plan, params,
                                 annotate=annotate, keep=keep,
                                 search_block=search_block,
                                 on_stage=tracer.maybe_stop if annotate
                                 else None,
                                 on_pass=(lambda k: tracer.maybe_stop(
                                     force=k >= npasses)) if annotate
                                 else None)

    if warm:
        # every program of the window compiled or loaded, every lazy
        # table built: the slice once, unmeasured
        w = one_call(keep=False)
        base = counter_totals()
        log(f"warm-up slice: {w.t_end - w.t_start:.2f} s; since process "
            f"start {base['cache_hits']:.0f} programs loaded from the "
            f"compile cache, {base['inline_compiles']:.0f} compiled")
    base = counter_totals()
    # The heap that imports, tracing and the warm-up left (objects that
    # live as long as the process) goes to the permanent generation, as
    # a long-running server does after start-up: a full collection in
    # the window then walks what the window made, not all of that, and
    # a pause of the collector's cannot land in the 1-2 s of finish_s.
    # With it no run had a full collection inside its window (PERF.md).
    gc.collect()
    gc.freeze()
    if trace:
        tracer.start()
    t_open = time.time()
    with GcWatch() as gcw:
        calls = window.run_window(lambda: one_call(annotate=trace), seconds)
    tracer.maybe_stop(force=True)
    gc.unfreeze()
    log(f"gc: {len(gcw.seconds)} full collection(s) inside the window, "
        f"{sum(gcw.seconds):.3f} s together, longest "
        f"{max(gcw.seconds, default=0.0):.3f} s")
    counters = {k: v - base[k] for k, v in counter_totals().items()}
    setup_s = t_open - t_process

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(peak) if peak is not None else 0}

    lost = int(counters["hi_trials_per_dm"] + counters["hi_trials_rescued"]
               + counters["rescue_rows"])
    attempted, failed = window.attempted_failed(calls, lost)
    e2e = window.end_to_end(calls)
    e2e["setup_s"] = setup_s
    ncands = len(calls[-1].result[0])
    for c in calls[-1].result[0][:12]:
        log(f"candidate: sigma {c.sigma:.2f} P {c.period_s:.6f} s DM "
            f"{c.dm:.2f} z {c.z:.2f} numharm {c.numharm} "
            f"power {c.power:.1f}")
    log(f"window: {len(calls)} slice call(s), {ncands} candidate(s) "
        f"returned, "
        + ", ".join(f"loop {c.loop_s:.3f} s + finish {c.finish_s:.3f} s"
                    for c in calls))

    result: dict = {"attempted": attempted, "failed": failed}
    if trace:
        layout = _load_json(cell.bench_dir, "trace_layout.json")
        stage_names = set().union(*(c.stage_s for c in calls))
        red = tracered.load_xplane(tracer.dir, layout, stage_names)
        busy = tracered.device_busy(red, layout)
        device["busy_s"] = tracered.busy_seconds(busy)
        device["window_s"] = tracer.window_s
        ctx = {"calls": calls, "trials": sum(c.ntrials_done for c in calls),
               "passes": npasses * len(calls), "ncalls": len(calls),
               "counters": counters, "host": host,
               "memory_peak_bytes": peak, "trace": red, "layout": layout,
               "device_trace": device, "bench_dir": cell.bench_dir,
               "peaks": _load_json(cell.bench_dir,
                                   "peaks.json").get(dev.device_kind),
               "shapes": cost_shapes(cell, plan, params)}
        result["metrics"] = layers.read_all(cell.per_layer(), ctx)
        result["breakdown"] = {
            "device_ops": tracered.top_ops(red, layout),
            "idle_gaps": tracered.idle_gaps(red, layout, busy)}
        if dump_trace:
            with open(dump_trace, "w") as fh:
                json.dump(tracered.sample(red), fh)
        log("trace inventory: " + json.dumps(red["inventory"]))
        log("trace notes: " + json.dumps(ctx.get("notes", {})))
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {k: {"value": float(e2e[k]), "unit": u}
                             for k, u in units.items()}

    # the comparison with the plain reference: after the window, after
    # the program's peak has been read, outside set-up
    t_chk = time.time()
    verdict = check_mod.check(cell, plan, psr, calls[-1], block, seed,
                              control=control)
    for n in verdict["numbers"]:
        log(f"check: {n['name']} = {n['value']!r} (limit {n['limit']!r}, "
            f"n {n['n']}) {'ok' if n['ok'] else 'FAIL'}")
    for n in verdict.get("control", []):
        log(f"control: {n['name']} = {n['value']!r} (limit {n['limit']!r}, "
            f"n {n['n']}) "
            f"{'NOT CAUGHT' if n['ok'] else 'caught'}")
    log(f"check took {time.time() - t_chk:.2f} s; inline compiles in the "
        f"window: {counters['inline_compiles']:.0f}")
    if counters["inline_compiles"]:
        log("check: a program compiled inside the window (warm-up fault)")
    result = {"correct": bool(verdict["correct"]
                              and failed == 0
                              and counters["inline_compiles"] == 0),
              **result, "device": device, "check": verdict["numbers"],
              "counters": counters, "seed": seed, "ncands": ncands,
              "calls": [{"loop_s": c.loop_s, "finish_s": c.finish_s,
                         "stage_s": {k: v for k, v in c.stage_s.items()
                                     if v}} for c in calls]}
    if "control" in verdict:
        result["control"] = verdict["control"]
    return result


def unit_measure(cell):
    """The unit of work the cell's traffic names: the slice call
    (`measure`, where it names none), or ``measure`` of the file
    ``units/<unit>.py`` beside the configurations, which takes the same
    arguments and gives the same result line."""
    import importlib.util

    unit = cell.traffic.get("unit")
    if unit is None:
        return measure
    name = "_bench_unit_" + unit
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(cell.bench_dir, "units", unit + ".py"))
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.measure


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = t_process or time.time()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--more-seeds", default="",
                    help="comma-separated further seeds measured in this "
                         "process without a second warm-up (limit-"
                         "setting runs; the driver never passes it)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control")
    ap.add_argument("--dump-trace", default="",
                    help="write a cut-down copy of the reduced trace "
                         "here (for the tests' recorded trace)")
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    from tpulsar.aot import cachedir
    cachedir.activate()           # before jax is imported: the env
    import jax  # noqa: F401
    cachedir.activate()           # and the live config
    peaks = _load_json(cell.bench_dir, "peaks.json")
    require_chip(cell.chips, peaks)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    seeds = [args.seed] + [int(s) for s in args.more_seeds.split(",") if s]
    run = unit_measure(cell)
    for i, seed in enumerate(seeds):
        res = run(cell, seed, args.seconds, bool(args.trace),
                      t_process=t_process if i == 0 else time.time(),
                      warm=(i == 0), control=bool(args.control), log=log,
                      dump_trace=args.dump_trace)
        sys.stdout.flush()
        print(json.dumps(res), flush=True)
    return 0
