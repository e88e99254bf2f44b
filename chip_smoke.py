#!/usr/bin/env python3
"""One full-width Mock beam through gateway -> serve on the attached
chip: the quickest proof that the system still starts there.

    python chip_smoke.py             # one chip, the served path
    python chip_smoke.py --chips 4   # the DM-sharded mesh vs one device
    python chip_smoke.py --tiny      # the same script at toy size on
                                     # CPU (rehearsal, tier-1 test)

Default phase.  This process never imports jax (a chip belongs to one
process).  It writes one synthetic Mock beam from ``--seed`` (960
channels x 3,932,160 samples of 65.476 us, 4-bit PSRFITS, one pulsar
at DM 11), starts a jax-free ``tpulsar gateway`` on a sqlite queue
with a blob store, ``tpulsar blob put``s the beam, submits one ticket
by digest, and starts ONE ``tpulsar serve`` worker with the
default boot (AOT warm-start gate on) under a config that sets only
``searching.dm_max = 15`` beside the survey defaults: the survey's
first two ds=1 passes, 152 DM trials, every device program at its
production shape.  Width is the survey's; only depth is cut.

It exits non-zero unless the result is ``done`` on a TPU, the injected
pulsar comes back from ``/v1/candidates`` out of the index, the beam
recorded no degraded or rescued mode, every hi-accel trial ran on the
batched path, the boot gate returned 0 or 3, and ``tpulsar index
fsck`` is clean.  Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` with the device as the serve worker
stamped it on the result record.  Nothing is caught into an exit 0.

``--chips 4`` runs, in this one process, ``executor.search_beam`` on
the same beam and DM window with ``SearchParams(dm_shards=4)`` (the
layout a served worker takes from ``searching.dm_shards``) and again
with ``dm_shards=1``, checks that the sharded pass really spans four
devices and that the two candidate lists match one-to-one, prints
both wall-clocks, and nothing else.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

#: the survey's Mock beam (tpulsar.aot.registry declares the same
#: geometry for the warm-start gate) and the toy rehearsal size
FULL = dict(nchan=960, nsamp=3_932_160, tsamp_s=65.476e-6, nsblk=4096,
            period_s=0.1237, dm=11.0, snr_per_sample=0.01,
            warmstart_scale=1.0)
TINY = dict(nchan=32, nsamp=1 << 14, tsamp_s=6.5476e-4, nsblk=64,
            period_s=0.05, dm=11.0, snr_per_sample=1.0,
            warmstart_scale=0.008)
DM_MAX = 15.0          # passes 0-1 of the survey plan's 0.1 step
EXPECT_TRIALS = 152
MIN_SIGMA = 6.0


class SmokeFailure(Exception):
    pass


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def host_free_gib() -> float:
    """MemAvailable: on a sealed machine, memory a process allocated
    and freed may never come back, and the run dies at the limit."""
    with open("/proc/meminfo") as fh:
        for line in fh:
            if line.startswith("MemAvailable:"):
                return round(int(line.split()[1]) / 2**20, 1)
    return -1.0


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ the beam

def write_beam(outdir: str, size: dict, seed: int) -> tuple[str, dict]:
    """The synthetic Mock beam (numpy only)."""
    from tpulsar.io import synth

    spec = synth.BeamSpec(
        nchan=size["nchan"], nsamp=size["nsamp"],
        tsamp_s=size["tsamp_s"], nsblk=size["nsblk"], nbits=4,
        seed=seed)
    psr = synth.PulsarSpec(period_s=size["period_s"], dm=size["dm"],
                           snr_per_sample=size["snr_per_sample"])
    t0 = time.time()
    path, = synth.synth_beam(outdir, spec, pulsars=[psr], merged=True)
    return path, {"phase": "generate",
                  "seconds": round(time.time() - t0, 2),
                  "bytes": os.path.getsize(path),
                  "host_free_gib": host_free_gib()}


def write_config(root: str) -> str:
    """Survey defaults (hi-accel ON, zmax 50, refine and fold on) and
    the DM window; every directory the pipeline writes is under
    ``root``."""
    path = os.path.join(root, "config.py")
    with open(path, "w") as fh:
        fh.write(
            f"searching = {{'dm_max': {DM_MAX}}}\n"
            f"processing = {{'base_working_directory': "
            f"{os.path.join(root, 'work')!r}, "
            f"'base_results_directory': "
            f"{os.path.join(root, 'results')!r}}}\n"
            f"basic = {{'log_dir': {os.path.join(root, 'logs')!r}}}\n"
            f"background = {{'jobtracker_db': "
            f"{os.path.join(root, 'jobtracker.db')!r}}}\n"
            f"download = {{'datadir': "
            f"{os.path.join(root, 'rawdata')!r}}}\n"
            f"resultsdb = {{'url': "
            f"{os.path.join(root, 'results.db')!r}}}\n")
    return path


# ----------------------------------------------------- child processes

def cli(*argv: str) -> list[str]:
    return [sys.executable, "-m", "tpulsar.cli", *argv]


def run_cli(argv: list[str], env: dict, what: str,
            timeout: float) -> str:
    proc = subprocess.run(argv, env=env, cwd=HERE, text=True,
                          capture_output=True, timeout=timeout)
    check(proc.returncode == 0,
          f"{what} exited {proc.returncode}: "
          f"{(proc.stderr or proc.stdout).strip()[-2000:]}")
    return proc.stdout


class Children:
    """Every process this script starts, stopped on the way out."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []

    def start(self, argv, env, log_path) -> subprocess.Popen:
        log = open(log_path, "w")
        proc = subprocess.Popen(argv, env=env, cwd=HERE, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        log.close()
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass
        for proc in self.procs:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()


def wait_for_line(path: str, pattern: str, proc: subprocess.Popen,
                  timeout: float) -> re.Match:
    deadline = time.time() + timeout
    while time.time() < deadline:
        with open(path) as fh:
            m = re.search(pattern, fh.read())
        if m:
            return m
        check(proc.poll() is None,
              f"process exited {proc.returncode} before printing "
              f"{pattern!r}; see {path}")
        time.sleep(0.2)
    raise SmokeFailure(f"no {pattern!r} in {path} after {timeout} s")


# ------------------------------------------------- reading the outdir

def literal_line(path: str, key: str):
    """``key = <python literal>`` out of search_params.txt."""
    with open(path) as fh:
        for line in fh:
            if line.startswith(key + " = "):
                return ast.literal_eval(line.split(" = ", 1)[1])
    raise SmokeFailure(f"no {key!r} in {path}")


def stage_seconds(outdir: str) -> dict:
    """Per-stage seconds from the beam's .report."""
    reports = [f for f in os.listdir(outdir) if f.endswith(".report")]
    check(len(reports) == 1, f"expected one .report in {outdir}")
    out = {}
    with open(os.path.join(outdir, reports[0])) as fh:
        for line in fh:
            m = re.match(r"\s*([\w -]+?):\s+([\d.]+) s", line)
            if m:
                out[m.group(1).strip()] = float(m.group(2))
    return out


def trials_by_path(outdir: str) -> dict:
    """Hi-accel DM trials by dispatch path, from the beam's metrics
    delta."""
    with open(os.path.join(outdir, "metrics.json")) as fh:
        series = (json.load(fh).get(
            "tpulsar_accel_batch_trials_total") or {}).get(
                "series", {})
    out: dict = {}
    for labels, n in series.items():
        m = re.search(r"path=\"?(\w+)", labels)
        out[m.group(1) if m else labels] = int(n)
    return out


def keep_evidence(root: str, outdir: str | None) -> None:
    """Logs and the beam's small provenance files, where the chip
    tool brings them back from."""
    dest = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(dest, exist_ok=True)
    names = [os.path.join(root, n) for n in os.listdir(root)
             if n.endswith(".log")]
    if outdir and os.path.isdir(outdir):
        names += [os.path.join(outdir, n) for n in os.listdir(outdir)
                  if n.endswith((".report", ".txt", "metrics.json",
                                 ".accelcands"))]
    for path in names:
        shutil.copy(path, dest)


# ------------------------------------------------------ default phase

def served_beam(args, size: dict, root: str) -> dict:
    from tpulsar.frontdoor import client

    from tpulsar import native
    from tpulsar.plan import ddplan

    planned = ddplan.total_dm_trials(ddplan.trim_plan(
        ddplan.survey_plan("pdev"), 0.0, DM_MAX))
    check(planned == EXPECT_TRIALS,
          f"the DM window keeps {planned} trials, not {EXPECT_TRIALS}")

    emit(phase="sizes", seed=args.seed, tiny=args.tiny,
         nchan=size["nchan"], nsamp=size["nsamp"],
         tsamp_s=size["tsamp_s"], nbits=4, dm_max=DM_MAX,
         dm_trials=EXPECT_TRIALS, period_s=size["period_s"],
         dm=size["dm"], warmstart_scale=size["warmstart_scale"],
         native_host_library_built=native.load() is not None)

    beam, rec = write_beam(os.path.join(root, "data"), size,
                           args.seed)
    emit(**rec)

    cfg = write_config(root)
    env = dict(os.environ, TPULSAR_CONFIG=cfg, PYTHONPATH=HERE)
    queue = "sqlite:" + os.path.join(root, "queue.db")
    spool = os.path.join(root, "spool")
    children = Children()
    outdir = None
    try:
        gw_log = os.path.join(root, "gateway.log")
        gw = children.start(
            cli("gateway", "--queue", queue, "--port", "0",
                "--blob-root", os.path.join(root, "cas"),
                "--outdir-base", os.path.join(root, "results")),
            env, gw_log)
        url = wait_for_line(gw_log, r"gateway: (http://\S+)", gw,
                            60.0).group(1)

        t0 = time.time()
        digest = run_cli(cli("blob", "put", beam, "--url", url), env,
                         "blob put", 600.0).split()[0]
        os.remove(beam)           # the store holds it now
        emit(phase="stage-in", seconds=round(time.time() - t0, 2),
             digest=digest, host_free_gib=host_free_gib())

        # the one process that touches the chip (the gateway sheds
        # submissions until its first heartbeat, written before the
        # boot; the ticket then waits out the warm-start gate)
        serve_log = os.path.join(root, "serve.log")
        worker = children.start(
            cli("serve", "--queue", queue, "--spool", spool,
                "--warmstart-scale", str(size["warmstart_scale"])),
            dict(env, TPULSAR_DATA_URL=url), serve_log)

        def wait_until(ready, what: str, poll_s: float):
            """Poll ``ready()`` while the worker lives and the
            deadline holds."""
            while True:
                got = ready()
                if got:
                    return got
                check(worker.poll() is None,
                      f"serve worker exited {worker.returncode} "
                      f"waiting for {what}; see {serve_log}")
                check(time.time() - T_START < args.deadline,
                      f"no {what} by the {args.deadline} s deadline")
                time.sleep(poll_s)

        wait_until(lambda: client.capacity(url).get("capacity", -1) > 0,
                   "the worker's first heartbeat", 0.5)
        tid = client.submit_beam(
            url, [os.path.basename(beam)],
            blobs={os.path.basename(beam): digest})["ticket"]
        result = wait_until(
            lambda: client.ticket_status(url, tid).get("result"),
            f"the result of ticket {tid}", 1.0)
        outdir = result.get("outdir")
        check(result.get("status") == "done",
              f"result is {result.get('status')!r}: "
              f"{str(result.get('error'))[:2000]}")
        emit(phase="boot-gate", rc=result.get("boot_gate_rc"),
             seconds=result.get("boot_seconds"))
        emit(phase="search", ticket=tid,
             seconds=round(float(result["beam_seconds"]), 2),
             dm_trials=result.get("dm_trials"),
             candidates=result.get("candidates"),
             compile_misses=result.get("compile_misses"),
             compile_hits=result.get("compile_hits"),
             host_free_gib=host_free_gib())
        check(result.get("boot_gate_rc") in (0, 3),
              f"boot gate rc {result.get('boot_gate_rc')!r}")
        check(result.get("dm_trials") == EXPECT_TRIALS,
              f"{result.get('dm_trials')} DM trials searched")

        emit(phase="stages", seconds=stage_seconds(outdir))
        params = os.path.join(outdir, "search_params.txt")
        degraded = literal_line(params, "degraded_modes")
        rescued = literal_line(params, "rescued_modes")
        paths = trials_by_path(outdir)
        emit(phase="modes", degraded_modes=degraded,
             rescued_modes=rescued, hi_accel_trials_by_path=paths)
        check(not degraded, f"degraded modes: {degraded}")
        check(not rescued, f"rescued modes: {rescued}")
        check(paths == {"batched": EXPECT_TRIALS},
              f"hi-accel trials by path: {paths}")

        cands = client.query_candidates(url, ticket=tid,
                                        min_sigma=MIN_SIGMA)
        check(cands.get("source") == "index",
              f"/v1/candidates answered from {cands.get('source')!r}")
        hits = [c for c in cands["candidates"]
                if abs(c["period_s"] / size["period_s"] - 1) < 2e-3
                and abs(c["dm"] - size["dm"]) <= 1.0]
        emit(phase="candidates", total=cands["total"],
             source=cands["source"],
             recovered=[{k: c[k] for k in
                         ("period_s", "dm", "sigma", "numharm")}
                        for c in hits[:3]])
        check(bool(hits), "the injected pulsar is not among the "
                          f"{cands['total']} indexed candidates")

        fsck = json.loads(run_cli(
            cli("index", "fsck", "--spool", spool, "--queue", queue),
            env, "index fsck", 120.0).strip().splitlines()[-1])
        emit(phase="fsck", **fsck)

        device = result.get("device") or {}
        check(device.get("platform") == "tpu" or args.tiny,
              f"the worker ran on {device}: no accelerator")
        return device
    finally:
        children.stop_all()
        keep_evidence(root, outdir)


# -------------------------------------------------------- --chips N

def sharded_beam(args, size: dict, root: str) -> dict:
    """The DM-sharded mesh against one device, in this one process."""
    if args.tiny:
        # rehearsal: N virtual CPU devices
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    emit(phase="sizes", seed=args.seed, tiny=args.tiny,
         chips=args.chips, nchan=size["nchan"], nsamp=size["nsamp"],
         dm_max=DM_MAX, dm_trials=EXPECT_TRIALS)
    beam, rec = write_beam(os.path.join(root, "data"), size,
                           args.seed)
    emit(**rec)

    import jax

    from tpulsar.io.accelcands import parse_candlist
    from tpulsar.parallel import mesh as pmesh
    from tpulsar.search import executor

    sys.path.insert(0, os.path.join(HERE, "tools"))
    import compare_candlists

    devs = jax.devices()
    check(len(devs) == args.chips,
          f"jax reports {len(devs)} devices, not {args.chips}")
    check(devs[0].platform == "tpu" or args.tiny,
          f"jax reports {devs[0].platform}: no accelerator")

    # code that has never met a second chip may put everything on the
    # first: look at where each sharded pass program takes its inputs
    # and leaves its outputs
    spans = {"calls": 0, "in": set(), "out": set(), "dm_split": True}
    make_fn = pmesh.sharded_pass_fn

    def spying(mesh, spec):
        fn = make_fn(mesh, spec)
        compiled = []

        def call(*a):
            if not compiled:
                compiled.append(fn.lower(*a).compile())
                ins = compiled[0].input_shardings[0]
                # (the taps are None off a TPU: no sharding to read)
                spans["in"].update(
                    frozenset(d.id for d in sh.device_set)
                    for sh in jax.tree_util.tree_leaves(ins))
                # the DM table (argument 1) is split, not replicated
                spans["dm_split"] &= not ins[1].is_fully_replicated
            out = fn(*a)
            spans["calls"] += 1
            spans["out"].update(
                frozenset(sh.device.id for sh in x.addressable_shards)
                for x in jax.tree_util.tree_leaves(out))
            return out
        return call

    walls, lists = {}, {}
    for name, shards in (("mesh", args.chips), ("single", 1)):
        params = executor.SearchParams(dm_max=DM_MAX, make_plots=False,
                                       dm_shards=shards)
        out = os.path.join(root, f"out_{name}")
        pmesh.sharded_pass_fn = spying
        t0 = time.time()
        try:
            res = executor.search_beam(
                [beam], os.path.join(root, f"work_{name}"), out,
                params=params)
        finally:
            pmesh.sharded_pass_fn = make_fn
        walls[name] = round(time.time() - t0, 2)
        lists[name] = parse_candlist(
            os.path.join(out, f"{res.basenm}.accelcands"))
        degraded = literal_line(
            os.path.join(out, "search_params.txt"), "degraded_modes")
        emit(phase=f"search-{name}", seconds=walls[name],
             dm_trials=res.num_dm_trials,
             candidates=len(res.candidates), degraded_modes=degraded,
             stages={k: round(v, 2)
                     for k, v in res.timers.times.items() if v})
        check(res.num_dm_trials == EXPECT_TRIALS,
              f"{name}: {res.num_dm_trials} DM trials")
        check(not degraded, f"{name}: degraded modes {degraded}")

    want = frozenset(d.id for d in devs)
    emit(phase="placement", sharded_calls=spans["calls"],
         input_device_sets=sorted(map(sorted, spans["in"])),
         output_device_sets=sorted(map(sorted, spans["out"])),
         dm_table_split=spans["dm_split"])
    check(spans["calls"] > 0, "the sharded pass program never ran")
    check(spans["in"] == {want} and spans["out"] == {want}
          and spans["dm_split"],
          f"the sharded pass does not span the {args.chips} devices")

    # one-to-one, both ways
    report = {}
    for ref, got in (("single", "mesh"), ("mesh", "single")):
        kinds = [k for _c, k, _g in compare_candlists.match(
            lists[ref], lists[got], freq_tol=1e-4, dm_tol=0.5)]
        report[f"{ref}_in_{got}"] = {
            k: kinds.count(k) for k in ("exact", "harmonic", "missed")}
    emit(phase="compare", n_mesh=len(lists["mesh"]),
         n_single=len(lists["single"]), **report)
    check(len(lists["mesh"]) == len(lists["single"]) > 0
          and all(r["exact"] == len(lists["mesh"])
                  for r in report.values()),
          f"candidate lists are not one-to-one: {report}")
    hits = [c for c in lists["mesh"]
            if abs(c.period_s / size["period_s"] - 1) < 2e-3
            and abs(c.dm - size["dm"]) <= 1.0]
    check(bool(hits), "the mesh did not recover the injected pulsar")
    emit(phase="wallclock", mesh_s=walls["mesh"],
         single_s=walls["single"],
         note="first search pays every compile; not a speed claim")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -------------------------------------------------------------- main

T_START = time.time()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy size on CPU (rehearsal; the last line "
                         "then truthfully says cpu)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the DM-sharded mesh against one device, "
                         "and no other phase")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--deadline", type=float, default=1140.0,
                    help="seconds from start by which the serve "
                         "worker must have exited")
    ap.add_argument("--workdir", default=os.path.join(HERE,
                                                      ".chip_smoke"),
                    help="scratch root (removed afterwards)")
    ap.add_argument("--keep", action="store_true",
                    help="leave the scratch root in place")
    args = ap.parse_args()

    if not args.tiny and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("chip_smoke: JAX_PLATFORMS=cpu hides the accelerator; "
              "this run needs a TPU (--tiny rehearses on CPU)",
              file=sys.stderr)
        return 2

    size = TINY if args.tiny else FULL
    root = os.path.abspath(args.workdir)
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    try:
        if args.chips == 1:
            device = served_beam(args, size, root)
            # a chip belongs to one process, and it was the worker
            check("jax" not in sys.modules,
                  "the smoke's parent process imported jax")
        else:
            device = sharded_beam(args, size, root)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        if not args.keep:
            shutil.rmtree(root, ignore_errors=True)
    emit(ok=True, device={"platform": device.get("platform"),
                          "kind": device.get("kind"),
                          "count": device.get("count")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
