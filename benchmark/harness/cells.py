"""A cell, found by its name: BENCHMARK.json's workload entry, the
configuration's file of sizes and the traffic mix's file of
parameters.  Everything a later PR adds is a new file plus a new
entry; nothing here names a cell, a configuration or a mix.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict        # benchmark/configs/<config>.json as it is run
    traffic: dict       # benchmark/traffic/<traffic>.json
    bench: dict         # BENCHMARK.json
    root: str           # the checkout BENCHMARK.json lies in
    bench_dir: str      # the tree the configuration's file lies in

    # ---- geometry of the deployment
    @property
    def nchan(self) -> int:
        return int(self.config["nchan"])

    @property
    def nsamp(self) -> int:
        return int(self.config["nsamp"])

    @property
    def dt(self) -> float:
        return float(self.config["dt_s"])

    @property
    def freqs(self) -> np.ndarray:
        from benchmark.harness.generate import channel_freqs
        return channel_freqs(float(self.config["fctr_mhz"]),
                             float(self.config["bw_mhz"]), self.nchan)

    @property
    def run_hi_accel(self) -> bool:
        return bool(self.traffic["run_hi_accel"])

    # ---- what BENCHMARK.json says this cell reports
    def _reported(self, group: str) -> list[dict]:
        out = []
        for m in self.bench[group]:
            cells = m.get("workloads")
            if cells is None or self.name in cells:
                out.append(m)
        return out

    def end_to_end(self) -> list[dict]:
        return self._reported("end_to_end")

    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self._reported("per_layer")
                if m["moves"] in e2e]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(by_name)})")
    w = by_name[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg_path = os.path.join(root, cfgs[w["config"]]["file"])
    bench_dir = os.path.dirname(os.path.dirname(cfg_path))
    traffic = _load(os.path.join(bench_dir, "traffic",
                                 w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=_load(cfg_path),
                traffic=traffic, bench=bench, root=root,
                bench_dir=bench_dir)


def plan_slice(cell: Cell):
    """The cell's slice of the survey plan, through public ``ddplan``
    API only: of each step the traffic names (``"all"`` or a list of
    step indices), its first ``passes_per_step`` passes.  A pass stays
    whole — it is the plan's atomic unit (``ddplan.trim_plan``)."""
    from tpulsar.plan import ddplan

    plan = ddplan.survey_plan(cell.config["backend"])
    steps = cell.traffic["steps"]
    idx = range(len(plan)) if steps == "all" else [int(i) for i in steps]
    npass = int(cell.traffic["passes_per_step"])
    return [dataclasses.replace(plan[i],
                                numpasses=min(npass, plan[i].numpasses))
            for i in idx]


def first_pass_dms(plan) -> tuple[float, float]:
    """(lowest DM of the slice's first pass, lowest DM of the pass
    after it): the span the traffic's pulsar is drawn in."""
    first = next(iter(plan[0].passes()))
    return float(first.lodm), float(first.lodm + plan[0].sub_dmstep)


def search_params(cell: Cell):
    """SearchParams as the configuration file states them (the
    reference's searching defaults), with the traffic's hi-accel
    switch."""
    from tpulsar.search import executor

    sp = dict(cell.config["search_params"])
    return executor.SearchParams(run_hi_accel=cell.run_hi_accel, **sp)
