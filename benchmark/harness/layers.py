"""Per-layer metrics: each is a small file of its own under
``benchmark/layer_metrics/``, found by the metric's name in
BENCHMARK.json.

``<metric>.json`` names one of the general readers below and its
parameters; ``<metric>.py`` (for a reading none of them gives) holds
``read(ctx) -> float | None``.  A reader that finds nothing to read
returns None and the harness leaves the metric out of the line.

The context a reader gets:

  calls       the window's SliceCalls (fenced stage seconds in each)
  trials      DM trials the calls searched; passes, ncalls likewise
  counters    {name: delta over the window} of the program's counters
  host        host-clock readings of the set-up ({"rfifind_s": ...})
  memory_peak_bytes
  trace       the reduced profiler trace, or None when not traced
  layout, peaks, shapes   trace_layout.json, this chip's peaks, the
              shapes the cost functions take
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

from benchmark.harness import tracered


def _stage_timers(spec: dict, ctx: dict):
    total = sum(c.stage_s.get(s, 0.0) for c in ctx["calls"]
                for s in spec["stages"])
    per = {"trial": ctx["trials"], "pass": ctx["passes"],
           "call": ctx["ncalls"]}[spec["per"]]
    if not per:
        return None
    return float(spec.get("scale", 1.0)) * total / per


def _counter(spec: dict, ctx: dict):
    return ctx["counters"].get(spec["counter"])


def _host_clock(spec: dict, ctx: dict):
    return ctx["host"].get(spec["key"])


def _memory_peak(spec: dict, ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    return None if peak is None else peak * float(spec.get("scale", 1.0))


def _device_idle(spec: dict, ctx: dict):
    dev = ctx.get("device_trace")
    if not dev or not dev.get("window_s"):
        return None
    return tracered.idle_pct(dev["busy_s"], dev["window_s"])


def _load_module(path: str):
    name = "_bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cost(bench_dir: str, name: str):
    return _load_module(os.path.join(bench_dir, "costs", name + ".py")).cost


def _module_roofline(spec: dict, ctx: dict):
    if ctx.get("trace") is None:
        return None
    durs = tracered.slowest_variant(tracered.module_durations(
        ctx["trace"], ctx["layout"], spec["module"]))
    if not durs:
        return None
    ops, nbytes = load_cost(ctx["bench_dir"], spec["cost"])(ctx["shapes"])
    pct, bound = tracered.roofline_pct(ops, nbytes,
                                       statistics.median(durs),
                                       ctx["peaks"])
    ctx.setdefault("notes", {})[spec["cost"]] = {
        "bound": bound, "calls": len(durs),
        "median_s": statistics.median(durs), "ops": ops, "bytes": nbytes}
    return pct


READERS = {"stage_timers": _stage_timers, "counter": _counter,
           "host_clock": _host_clock, "memory_peak": _memory_peak,
           "device_idle": _device_idle, "module_roofline": _module_roofline}


def read_metric(name: str, ctx: dict):
    base = os.path.join(ctx["bench_dir"], "layer_metrics", name)
    if os.path.exists(base + ".py"):
        return _load_module(base + ".py").read(ctx)
    with open(base + ".json") as fh:
        spec = json.load(fh)
    return READERS[spec["reader"]](spec, ctx)


def read_all(metrics: list[dict], ctx: dict) -> dict:
    out = {}
    for m in metrics:
        value = read_metric(m["name"], ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
