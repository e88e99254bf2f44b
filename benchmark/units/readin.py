"""The read-in unit of work: a beam from the staged file to the masked
block resident on the chip, through ``executor.search_beam`` itself.

A read-in call is ``search_beam([file], workdir, resultsdir, params,
plan=<the cell's slice>, baryv=0.0, checkpoint_dir=<fresh>)`` — the
served worker's own entry — on ONE merged 4-bit PSRFITS file that lies
on the local disk, its pages warm.  The harness stamps the call's
start (a), the entry of ``search_block`` (b) and its return (c) by
wrapping the module attribute ``executor.search_block`` that
``search_beam`` resolves when it is called, and keeps the ``data``
argument it saw there.  ``readin_s`` is (b) - (a) with the block
waited for: header, plan, the nibbles decoded, the transpose, the
transfer, the RFI mask found, written (to the results directory and
the checkpoint) and applied.  Nothing of that prelude is copied here:
a later PR that moves any of it to the device moves this number.

A closed loop of one client: one unmeasured warm-up read-in of the
same file (a worker lives for many beams), then read-ins while fewer
than ``--seconds`` have passed, at least one.  The block of the call
before is dropped before the next starts.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import statistics
import time

from benchmark.harness import cells, check_readin, generate, layers
from benchmark.harness import psrfits4, readin_trace, runner, tracered, window


@dataclasses.dataclass
class Beam:
    """What set-up made: the file and what the check needs of it."""
    path: str
    psr: generate.Pulsar
    plan: list
    params: object
    rfi: dict
    T_s: float
    native_unpacker: bool


@dataclasses.dataclass
class ReadinCall:
    t_start: float                    # (a)
    t_block: float                    # (b)
    t_return: float                   # (c)
    t_end: float
    ntrials_given: int
    ntrials_done: int
    stage_s: dict
    degraded: dict
    rescued: dict
    resultsdir: str
    basenm: str
    masked_fraction: float
    ncands: int
    data: object = None               # the block search_block was given

    @property
    def readin_s(self) -> float:
        return self.t_block - self.t_start


class NoBlockStamp(SystemExit):
    """``search_beam`` returned without reaching ``search_block``
    through the module attribute: there is no ``readin_s``."""


def host_free_gib() -> float:
    with open("/proc/meminfo") as fh:
        for line in fh:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) / 2 ** 20
    return -1.0


def work_dir(cell) -> str:
    return os.path.join(cell.root, ".bench_readin")


def setup(cell, seed: int, host: dict, log) -> Beam:
    """The cell's block on the device from the seed, as every cell
    makes it, with the traffic's interference laid over it; then the
    file: packed on the device, fetched and written by the harness's
    own writer.  The block is dropped: the program reads the file."""
    from tpulsar import native
    from tpulsar.plan import ddplan

    spec = cell.traffic["input"]
    if (spec["format"], spec["nbits"], spec["npol"], spec["files"]) != (
            "psrfits", 4, 1, 1):
        raise SystemExit(f"benchmark: the read-in unit writes one "
                         f"one-polarisation 4-bit PSRFITS file, not {spec}")
    plan = cells.plan_slice(cell)
    T_s = ddplan.choose_n(cell.nsamp) * cell.dt
    psr = generate.draw_pulsar(seed, cell.traffic["pulsar"],
                               cells.first_pass_dms(plan), T_s)
    t0 = time.time()
    block = generate.make_block(seed, psr, cell.freqs, cell.dt,
                                cell.nsamp, T_s)
    block, rfi = psrfits4.rfi_overlay(
        block, seed, cell.traffic["rfi"],
        int(cell.config["search_params"]["rfifind_blocklen"]))
    block.block_until_ready()
    host["make_block_s"] = time.time() - t0
    scl, offs, wts = psrfits4.draw_calibration(seed, cell.nchan, spec)
    beam4 = psrfits4.Beam4(
        nchan=cell.nchan, nsamp=cell.nsamp, nsblk=int(spec["nsblk"]),
        dt=cell.dt, fctr_mhz=float(cell.config["fctr_mhz"]),
        bw_mhz=float(cell.config["bw_mhz"]), scl=scl, offs=offs, wts=wts)
    root = work_dir(cell)
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    path = os.path.join(root, psrfits4.FILE_NAME)
    t0 = time.time()
    nbytes = psrfits4.write_beam(path, beam4, block)
    host["write_file_s"] = time.time() - t0
    del block
    t0 = time.time()
    built = native.load() is not None
    host["native_load_s"] = time.time() - t0
    log(f"setup: block made in {host['make_block_s']:.2f} s, {nbytes} "
        f"bytes written in {host['write_file_s']:.2f} s to {path}; "
        f"pulsar {psr}; rfi {rfi}; native unpacker "
        f"{'built' if built else 'MISSING'}; host free "
        f"{host_free_gib():.1f} GiB")
    over = dict(cell.traffic.get("search_params", {}))
    params = dataclasses.replace(cells.search_params(cell), **over)
    return Beam(path=path, psr=psr, plan=plan, params=params, rfi=rfi,
                T_s=T_s, native_unpacker=built)


def readin_call(beam: Beam, callroot: str, *, annotate: bool = False,
                keep: bool = True, clock=time.time,
                search_block=None) -> ReadinCall:
    """One read-in call, stamped.  `search_block` stands in for the
    program's (a test breaks the timed path with it); the stamps wrap
    whichever runs."""
    import jax
    from tpulsar.search import degraded, executor

    inner = search_block or executor.search_block
    seen: dict = {}
    note = (jax.profiler.TraceAnnotation("readin") if annotate else None)

    def stamped(data, *args, **kw):
        jax.block_until_ready(data)
        seen["t_block"] = clock()
        if note is not None:
            note.__exit__(None, None, None)
        seen["data"] = data if keep else None
        try:
            return inner(data, *args, **kw)
        finally:
            seen["t_return"] = clock()

    shutil.rmtree(callroot, ignore_errors=True)
    resultsdir = os.path.join(callroot, "results")
    real = executor.search_block
    executor.search_block = stamped
    try:
        t0 = clock()
        if note is not None:
            note.__enter__()
        outcome = executor.search_beam(
            [beam.path], os.path.join(callroot, "work"), resultsdir,
            beam.params, plan=beam.plan, baryv=0.0,
            checkpoint_dir=os.path.join(callroot, "checkpoint"))
        t1 = clock()
    finally:
        executor.search_block = real
        if note is not None and "t_block" not in seen:
            note.__exit__(None, None, None)
    if "t_block" not in seen:
        raise NoBlockStamp(
            "benchmark: search_beam returned without calling "
            "executor.search_block: no readin_s, no result")
    return ReadinCall(
        t_start=t0, t_block=seen["t_block"], t_return=seen["t_return"],
        t_end=t1, ntrials_given=sum(s.numdms for s in beam.plan),
        ntrials_done=int(outcome.num_dm_trials),
        stage_s=dict(outcome.timers.times), degraded=degraded.snapshot(),
        rescued=degraded.provenance_snapshot(), resultsdir=resultsdir,
        basenm=outcome.basenm,
        masked_fraction=float(outcome.masked_fraction),
        ncands=len(outcome.candidates), data=seen["data"])


def run_window(one_call, seconds: float, clock=time.time) -> list:
    """Read-ins while fewer than `seconds` have passed since the
    window opened, at least one; only the last keeps its block and its
    results directory."""
    calls: list[ReadinCall] = []
    t_open = clock()
    while not calls or clock() - t_open < seconds:
        if calls:
            calls[-1].data = None
            shutil.rmtree(os.path.dirname(calls[-1].resultsdir),
                          ignore_errors=True)
        calls.append(one_call(len(calls)))
    return calls


def end_to_end(calls: list) -> dict:
    """readin_s: the median over the window's read-in calls of the
    seconds from the call's start to the entry of ``search_block``
    with the block resident."""
    return {"readin_s": statistics.median(c.readin_s for c in calls)}


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
    beam = setup(cell, seed, host, log)
    root = work_dir(cell)
    # stopped by hand at the end of the window's first call, not by time
    tracer = runner.Tracer(os.path.join(cell.root, ".bench_trace"),
                           float("inf"))

    def one_call(k, keep=True, annotate=False):
        return readin_call(beam, os.path.join(root, f"call{k}"),
                           annotate=annotate, keep=keep,
                           search_block=search_block)

    if warm:
        w = one_call("_warm", keep=False)
        base = runner.counter_totals()
        log(f"warm-up read-in: {w.readin_s:.2f} s to the block, "
            f"{w.t_end - w.t_start:.2f} s whole; since process start "
            f"{base['cache_hits']:.0f} programs loaded from the compile "
            f"cache, {base['inline_compiles']:.0f} compiled; host free "
            f"{host_free_gib():.1f} GiB")
        shutil.rmtree(os.path.join(root, "call_warm"), ignore_errors=True)
    base = runner.counter_totals()
    gc.collect()
    gc.freeze()
    if trace:
        tracer.start()
    t_open = time.time()

    def window_call(k):
        call = one_call(k, annotate=trace and k == 0)
        tracer.maybe_stop(force=True)   # the traced span: the window's
        return call                     # first read-in, whole

    with runner.GcWatch() as gcw:
        calls = run_window(window_call, seconds)
    gc.unfreeze()
    log(f"gc: {len(gcw.seconds)} full collection(s) inside the window, "
        f"{sum(gcw.seconds):.3f} s together")
    counters = {k: v - base[k] for k, v in runner.counter_totals().items()}
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
    e2e = end_to_end(calls)
    e2e["setup_s"] = setup_s
    for c in calls:
        log(f"window: read-in {c.readin_s:.3f} s (rfifind stage "
            f"{c.stage_s.get('rfifind', 0.0):.3f} s), search_block "
            f"{c.t_return - c.t_block:.3f} s, after it "
            f"{c.t_end - c.t_return:.3f} s; masked fraction "
            f"{c.masked_fraction:.5f}; {c.ncands} candidate(s); host "
            f"free {host_free_gib():.1f} GiB")

    result: dict = {"attempted": attempted, "failed": failed}
    if trace:
        layout = runner._load_json(cell.bench_dir, "trace_layout.json")
        names = {"readin"}.union(*(c.stage_s for c in calls))
        red = tracered.load_xplane(tracer.dir, layout, names)
        busy = tracered.device_busy(red, layout)
        device["busy_s"] = tracered.busy_seconds(busy)
        device["window_s"] = tracer.window_s
        ctx = {"calls": calls, "ncalls": len(calls), "trials": attempted,
               "passes": len(calls), "counters": counters, "host": host,
               "memory_peak_bytes": peak, "trace": red, "layout": layout,
               "busy": busy, "device_trace": device,
               "bench_dir": cell.bench_dir}
        result["metrics"] = layers.read_all(cell.per_layer(), ctx)
        result["breakdown"] = {
            "device_ops": tracered.top_ops(red, layout),
            "idle_gaps": readin_trace.idle_gaps(red, layout, busy)}
        if dump_trace:
            with open(dump_trace, "w") as fh:
                json.dump(tracered.sample(red), fh)
        log("trace inventory: " + json.dumps(red["inventory"]))
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {k: {"value": float(e2e[k]), "unit": u}
                             for k, u in units.items()}

    # the comparison with the plain reference: after the window, after
    # the program's peak has been read, outside set-up
    t_chk = time.time()
    verdict = check_readin.check(cell, beam, calls[-1], seed,
                                 control=control)
    for n in verdict["numbers"]:
        log(f"check: {n['name']} = {n['value']!r} (limit {n['limit']!r}, "
            f"n {n['n']}) {'ok' if n['ok'] else 'FAIL'}")
    for n in verdict.get("control", []):
        log(f"control: {n['name']} = {n['value']!r} (limit {n['limit']!r}, "
            f"n {n['n']}) {'NOT CAUGHT' if n['ok'] else 'caught'}")
    log(f"check took {time.time() - t_chk:.2f} s; inline compiles in the "
        f"window: {counters['inline_compiles']:.0f}; host free "
        f"{host_free_gib():.1f} GiB")
    if counters["inline_compiles"]:
        log("check: a program compiled inside the window (warm-up fault)")
    shutil.rmtree(root, ignore_errors=True)
    result = {"correct": bool(verdict["correct"] and failed == 0
                              and counters["inline_compiles"] == 0),
              **result, "device": device,
              "native_unpacker": beam.native_unpacker,
              "counters": counters, "seed": seed,
              "ncands": calls[-1].ncands, "host": host,
              "calls": [{"readin_s": c.readin_s,
                         "search_block_s": c.t_return - c.t_block,
                         "after_s": c.t_end - c.t_return,
                         "stage_s": {k: v for k, v in c.stage_s.items()
                                     if v}} for c in calls],
              "check": verdict["numbers"]}
    if "control" in verdict:
        result["control"] = verdict["control"]
    return result
