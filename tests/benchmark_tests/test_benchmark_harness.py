"""The benchmark harness's own parts, at no size at all: the window
rule and its arithmetic, the seed's draws, the trace reduction on a
small recorded trace, the cost functions against hand counts, the
look for a chip, and BENCHMARK.json against its contract."""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import cells, generate, layers, runner, tracered, window
from tpulsar.plan import ddplan

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------ the window

class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def fake_call(clock, loop_s, finish_s, given=76, done=None, flags=None):
    def one():
        t0 = clock.t
        clock.t += loop_s
        stamps = [t0 + loop_s / 2, clock.t]
        clock.t += finish_s
        return window.SliceCall(
            t_start=t0, t_passes=stamps, t_end=clock.t, ntrials_given=given,
            ntrials_done=given if done is None else done, stage_s={},
            degraded=dict(flags or {}), rescued={}, result=("r",), dumps=[])
    return one


@pytest.mark.parametrize("seconds, loop_s, want_calls", [
    (10, 48.0, 1),       # one pass outlasts the window: exactly one call
    (10, 4.0, 2),        # 0 and 5 s started, 10 s not
    (30, 12.0, 3),       # 0, 13 and 26 s started, 39 s not
    (0.0, 3.0, 1),       # at least one, whatever the window
])
def test_window_starts_calls_only_before_the_deadline(seconds, loop_s,
                                                      want_calls):
    clock = FakeClock()
    t_open = clock.t
    calls = window.run_window(fake_call(clock, loop_s, 1.0), seconds,
                              clock=clock)
    assert len(calls) == want_calls
    assert all(c.t_start - t_open < max(seconds, 1e-9) or i == 0
               for i, c in enumerate(calls))
    # only the last call keeps its answers for the check
    assert calls[-1].result is not None
    assert all(c.result is None for c in calls[:-1])


def test_end_to_end_arithmetic_from_the_stamps():
    clock = FakeClock()
    calls = window.run_window(fake_call(clock, 4.0, 1.0), 10, clock=clock)
    calls += [fake_call(clock, 6.0, 3.0)()]
    e2e = window.end_to_end(calls)
    # 3 calls of 76 trials over 4 + 4 + 6 s of pass loop
    assert e2e["trials_per_s"] == pytest.approx(3 * 76 / 14.0)
    # median of the finishes 1, 1, 3
    assert e2e["finish_s"] == pytest.approx(1.0)


@pytest.mark.parametrize("kw, lost, want", [
    ({}, 0, 0),
    ({"done": 70}, 0, 6),                           # trials not searched
    ({"flags": {"accel_batch_pinned": "x"}}, 0, 76),  # a degraded call
    ({}, 4, 4),                                     # rescued / per-DM rows
])
def test_failed_counts_unsearched_degraded_and_rescued(kw, lost, want):
    clock = FakeClock()
    calls = [fake_call(clock, 1.0, 1.0, **kw)()]
    assert window.attempted_failed(calls, lost) == (76, want)


def test_pass_dump_store_keeps_passes_and_nothing_else():
    import io
    buf = io.BytesIO()
    fields = {f: np.arange(3.0) for f in window.CAND_FIELDS}
    np.savez_compressed(buf, events=np.zeros(2), ntrials=np.int64(76),
                        period_s=np.ones(3), **fields)
    store = window.PassDumpStore()
    assert store.load("pass_0000") is None
    assert store.save("pass_0000", buf.getvalue(), kind="pass",
                      ext=".npz", pass_idx=0) is False
    assert store.save("sifted", b"x", kind="stage", ext=".npz") is False
    store.journal("pass_complete", pass_idx=0)
    store.discard("fold_0000", reason="x")
    (only,) = store.decoded()
    assert only["ntrials"] == 76 and len(only["cands"]["r"]) == 3


# ---------------------------------------------------- draws from the seed

TRAFFIC = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "benchmark", "traffic")))


def _cells_of(traffic):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [w["name"] for w in bench["workloads"] if w["traffic"] == traffic]


@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("seed", [0, 1, 7, 2 ** 31 + 11, 4123456789])
def test_pulsar_draw_stays_inside_the_traffics_ranges(traffic, seed):
    ranges = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", traffic + ".json")))["pulsar"]
    for name in _cells_of(traffic):
        plan = cells.plan_slice(cells.load_cell(name))
        lo, hi = cells.first_pass_dms(plan)
        cell = cells.load_cell(name)
        span = ddplan.choose_n(cell.nsamp) * cell.dt
        psr = generate.draw_pulsar(seed, ranges, (lo, hi), span)
        # snapped to the search's Fourier grid: the mean frequency is a
        # whole number of bins of the padded span, inside the range
        r = psr.mean_freq_hz(span) * span
        assert abs(r - round(r)) < 1e-6
        assert span / ranges["period_s"][1] - 1 <= r \
            <= span / ranges["period_s"][0] + 1
        f_lo, f_hi = ranges["dm_frac_of_first_pass"]
        assert lo + f_lo * (hi - lo) <= psr.dm <= lo + f_hi * (hi - lo)
        assert ranges["duty"][0] <= psr.duty <= ranges["duty"][1]
        assert ranges["abs_z"][0] <= abs(psr.z) <= ranges["abs_z"][1]
        assert psr == generate.draw_pulsar(seed, ranges, (lo, hi), span)
        # The sifter drops a candidate whose BEST DM is under 2, and the
        # best DM of a 10 ms pulsar wanders from the truth by as much as
        # a DM error that smears the pulse by its own width: 0.3 ms is
        # 0.3 units at Mock's band and 1.1 at WAPP's.  Three times that
        # clear of the cutoff, and of the pass's end.
        freqs = cell.freqs
        per_unit = generate.KDM * (freqs[0] ** -2.0 - freqs[-1] ** -2.0)
        wander = 0.03 * psr.period_s / per_unit
        assert psr.dm - 3 * wander >= 2.0
        assert psr.dm + 3 * wander <= hi


def test_snap_moves_the_period_by_under_a_bin_and_keeps_the_drift():
    base = {"period_s": [0.01, 0.0101], "dm": [3, 4], "duty": [0.03, 0.03],
            "abs_z": [20, 20], "amp": 1.0}
    span = 257.4
    for seed in (3, 2 ** 31 + 9):
        free = generate.draw_pulsar(seed, base)
        snap = generate.draw_pulsar(
            seed, {**base, "snap_to_fourier_grid": True}, span_s=span)
        assert abs(snap.z) == 20 and snap.z == free.z and snap.dm == free.dm
        r_free = free.mean_freq_hz(span) * span
        r_snap = snap.mean_freq_hz(span) * span
        assert abs(r_snap - round(r_snap)) < 1e-6
        assert abs(r_snap - r_free) <= 0.5 * abs(free.z) + 0.5 + 1e-6


def test_pulsar_dm_may_be_absolute_or_a_share_of_the_first_pass():
    base = {"period_s": [0.02, 0.03], "duty": [0.03, 0.05],
            "abs_z": [0, 0], "amp": 1.0}
    a = generate.draw_pulsar(5, {**base, "dm": [6.0, 16.0]})
    b = generate.draw_pulsar(5, {**base, "dm_frac_of_first_pass": [0.3, 0.8]},
                             (0.0, 20.0))
    assert 6.0 <= a.dm <= 16.0 and a.dm == pytest.approx(b.dm)
    assert (a.period_s, a.duty, a.z) == (b.period_s, b.duty, b.z)


def test_block_is_the_same_for_the_same_seed_and_carries_the_drift():
    ranges = {"period_s": [0.02, 0.03], "dm": [6, 16], "duty": [0.03, 0.05],
              "abs_z": [8, 16], "amp": 4.0}
    psr = generate.draw_pulsar(2 ** 31 + 5, ranges)
    freqs = generate.channel_freqs(400.0, 100.0, 8)
    T_s = 4096 * 6.4e-5
    a = generate.make_block(2 ** 31 + 5, psr, freqs, 6.4e-5, 4096, T_s)
    b = generate.make_block(2 ** 31 + 5, psr, freqs, 6.4e-5, 4096, T_s)
    c = generate.make_block(2 ** 31 + 6, psr, freqs, 6.4e-5, 4096, T_s)
    assert a.dtype == np.uint8 and a.shape == (8, 4096)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert int(np.asarray(a).max()) <= 15
    # z bins of drift over T_s is a frequency derivative of z / T_s^2
    assert psr.fdot(T_s) == pytest.approx(psr.z / T_s ** 2)
    assert psr.mean_freq_hz(T_s) == pytest.approx(
        1 / psr.period_s + 0.5 * psr.z / T_s)


# ------------------------------------------------------- trace reduction

LAYOUT = json.load(open(os.path.join(ROOT, "benchmark",
                                     "trace_layout.json")))
RECORDED = json.load(open(os.path.join(HERE, "recorded_trace.json")))
PEAKS = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))


def test_recorded_trace_has_the_planes_the_layout_names():
    names = [p["name"] for p in RECORDED["planes"]]
    assert any(re.search(LAYOUT["device_plane"], n) for n in names)
    assert any(re.search(LAYOUT["host_plane"], n) for n in names)
    busy = tracered.device_busy(RECORDED, LAYOUT)
    assert busy and all(iv for iv in busy.values())


def test_recorded_trace_reduces_to_the_numbers_read_off_it_by_hand():
    """The cut of a real v5e trace (mock_ds1_hiaccel, my chip run, PR 24):
    its first 200 operations end in the first DM chunk's lo-accel."""
    busy = tracered.device_busy(RECORDED, LAYOUT)
    (iv,) = busy.values()
    span_s = (iv[-1][1] - iv[0][0]) / 1e9
    assert tracered.busy_seconds(busy) == pytest.approx(1.578797008)
    assert tracered.idle_pct(tracered.busy_seconds(busy), span_s) \
        == pytest.approx(0.5798178, rel=1e-5)
    # stage 2: two calls of one program, 0.5487 s each
    dd = tracered.module_durations(RECORDED, LAYOUT, "^jit__dedisperse_chunk")
    assert list(dd.values()) == [[0.548685598, 0.548677861]]
    # by hand: 19 useful rows of 96 subbands x 3,932,160 samples move
    # 4 x (96 + 19) x 3,932,160 = 1,808,793,600 bytes, 2.2085 ms at
    # 819 GB/s, of the 548.68 ms median: 0.4025 %, memory-bound
    cost = layers.load_cost(os.path.join(ROOT, "benchmark"), "dedisp_chunk")
    ops, nbytes = cost({"dd_rows": 19, "nsub": 96, "T": 3932160})
    assert nbytes == 1808793600
    pct, bound = tracered.roofline_pct(
        ops, nbytes, 0.5486817295, PEAKS["TPU v5 lite"])
    assert bound == "memory" and pct == pytest.approx(0.40252, rel=1e-4)
    # stage 1 runs in slabs of two lengths: the longer is the variant
    sb = tracered.module_durations(RECORDED, LAYOUT,
                                   "^jit__form_subbands_block")
    assert sorted(len(v) for v in sb.values()) == [1, 3]
    assert tracered.slowest_variant(sb)[0] == pytest.approx(0.1125, rel=1e-3)
    # the host's stage names are on the device's clock
    gaps = dict(tracered.idle_gaps(RECORDED, LAYOUT, busy))
    assert set(gaps) <= {"subbanding", "dedispersing", "single-pulse", "FFT",
                         "lo-accelsearch", "hi-accelsearch",
                         "(between stages)"}
    top = tracered.top_ops(RECORDED, LAYOUT, 1)[0]
    assert top[0].startswith("%_dedisperse_chunk.1 custom-call")
    assert top[1] == pytest.approx(1.097363452)


def test_an_operations_name_is_cut_to_name_opcode_and_shape():
    hlo = ("%fusion.2 = bf16[1966081,2,51]{0,1,2:T(2,128)(2,1)} fusion(bf16"
           "[2,51,3932162]{2,0,1} %pad_maximum_fusion.4), kind=kCustom")
    assert tracered.short_name(hlo) == \
        "%fusion.2 fusion bf16[1966081,2,51]{0,1,2:T(2,128)(2,1)}"
    assert tracered.short_name("jit_f(12)") == "jit_f(12)"


def test_busy_is_the_union_of_op_intervals():
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["a", 0, 4e9], ["b", 1e9, 1e9],        # nested in a
            ["c", 3e9, 3e9],                       # overlaps a: to 6
            ["d", 8e9, 1e9]]},                     # after a 2 s gap
        {"name": "XLA Modules", "events": [["jit_f(1)", 0, 6e9]]}]}]}
    busy = tracered.device_busy(trace, LAYOUT)
    assert busy == {"/device:TPU:0": [(0, 6e9), (8e9, 9e9)]}
    assert tracered.busy_seconds(busy) == pytest.approx(7.0)
    assert tracered.idle_pct(7.0, 10.0) == pytest.approx(30.0)


def test_module_time_and_roofline_share_by_hand():
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["x", 0, 1]]},
        {"name": "XLA Modules", "events": [
            ["jit_k(11)", 0, 2e9], ["jit_k(11)", 3e9, 4e9],
            ["jit_k(12)", 8e9, 1e8], ["jit_other(3)", 9e9, 5e9]]}]}]}
    durs = tracered.module_durations(trace, LAYOUT, "^jit_k")
    assert durs == {"jit_k(11)": [2.0, 4.0], "jit_k(12)": [0.1]}
    # of one function at two shapes, the one that runs longest
    assert tracered.slowest_variant(durs) == [2.0, 4.0]
    peak = {"flops_per_s": 100e12, "bytes_per_s": 1e12}
    # 30 TFLOP take 0.3 s at the peak, 0.5 TB take 0.5 s: memory-bound,
    # 0.5 s of the 3 s median
    pct, bound = tracered.roofline_pct(30e12, 0.5e12, 3.0, peak)
    assert bound == "memory" and pct == pytest.approx(100 * 0.5 / 3.0)
    pct, bound = tracered.roofline_pct(90e12, 0.5e12, 3.0, peak)
    assert bound == "compute" and pct == pytest.approx(30.0)


def test_idle_gaps_go_to_the_stage_the_host_was_in():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0, 1e9], ["b", 3e9, 1e9], ["c", 9e9, 1e9]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["FFT", 0, 5e9], ["pipeline-drain", 5e9, 5e9]]}]}]}
    busy = tracered.device_busy(trace, LAYOUT)
    gaps = dict(tracered.idle_gaps(trace, LAYOUT, busy))
    assert gaps == {"pipeline-drain": pytest.approx(5.0),
                    "FFT": pytest.approx(2.0)}
    assert tracered.top_ops(trace, LAYOUT, n=2) == [["a", 1.0], ["b", 1.0]]


# ---------------------------------------------------------- cost functions

def test_hiaccel_cost_against_a_hand_count():
    cost = layers.load_cost(os.path.join(ROOT, "benchmark"),
                            "hiaccel_chunk")
    shapes = {"hi_rows": 2, "nbins": 1966081, "nz": 51, "zmax": 50,
              "numharm": 8, "topk": 32}
    ops, nbytes = cost(shapes)
    # width 128, step 8064, 244 segments of 16384-point FFTs
    nsegs, L = 244, 16384
    fft = 5 * L * 14
    seg = fft + 51 * (6 * L + fft + 3 * 2 * 8064)
    plane = 51 * 2 * 1966081
    harm = plane * (1 / 2 + 2 / 4 + 4 / 8) + plane * (1 + 1 / 2 + 1 / 4 + 1 / 8)
    assert ops == pytest.approx(2 * (nsegs * seg + harm), rel=1e-12)
    assert 15e9 < ops / 2 < 18e9          # ~17 GFLOP a row
    assert nbytes == pytest.approx(2 * 1966081 * 8 + 51 * L * 8
                                   + 2 * 4 * 32 * 12)


def test_dedisp_cost_against_a_hand_count():
    cost = layers.load_cost(os.path.join(ROOT, "benchmark"), "dedisp_chunk")
    ops, nbytes = cost({"dd_rows": 19, "nsub": 96, "T": 1000})
    assert ops == 19 * 96 * 1000
    assert nbytes == 4 * 96 * 1000 + 4 * 19 * 1000


# ----------------------------------------------------- the look for a chip

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs, ok", [
    ([_Dev("tpu", "TPU v5 lite")], True),
    ([_Dev("cpu", "cpu")], False),                 # no accelerator
    ([_Dev("gpu", "TPU v5 lite")], False),         # not a TPU
    ([_Dev("tpu", "TPU v9 imaginary")], False),    # no peaks for it
    ([], False),
])
def test_only_a_known_tpu_passes_the_look_for_a_chip(monkeypatch, devs, ok):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: devs or [_Dev("cpu", "cpu")])
    if ok:
        assert runner.require_chip(1, PEAKS) == devs
    else:
        with pytest.raises(SystemExit) as exc:
            runner.require_chip(1, PEAKS)
        assert exc.value.code not in (0, None)


def test_a_cell_that_asks_for_more_chips_than_there_are_fails(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("tpu", "TPU v5 lite")])
    with pytest.raises(SystemExit):
        runner.require_chip(4, PEAKS)


def test_peaks_name_their_source_and_the_v5e_numbers():
    assert "Google Cloud" in PEAKS["_source"]
    assert PEAKS["TPU v5 lite"]["flops_per_s"] == 197e12
    assert PEAKS["TPU v5 lite"]["bytes_per_s"] == 819e9


def test_the_command_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
        "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "correct" not in out.stdout and "metrics" not in out.stdout
    assert "not 'tpu'" in out.stderr


# ------------------------------------------- BENCHMARK.json's own contract

def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


ENTRIES = ([("config", c) for c in BENCH["configs"]]
           + [("workload", w) for w in BENCH["workloads"]]
           + [("end_to_end", m) for m in BENCH["end_to_end"]]
           + [("per_layer", m) for m in BENCH["per_layer"]])
KEYS = {"config": {"name", "source", "file", "reduced", "why"},
        "workload": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


@pytest.mark.parametrize("kind, entry", ENTRIES,
                         ids=[f"{k}:{e['name']}" for k, e in ENTRIES])
def test_every_entry_keeps_to_the_allowed_keys_names_and_units(kind, entry):
    assert set(entry) - {"workloads"} == KEYS[kind]
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    for text in ("why", "layer", "source"):
        if text in entry:
            assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text] \
                and "\t" not in entry[text]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    if kind == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    if kind == "workload":
        assert entry["chips"] in (1, 4)
    if kind == "config":
        assert all(NAME.match(k) for k in entry["reduced"])
        assert entry["file"].startswith(tuple(BENCH["paths"]))


def test_names_are_unique_and_every_reference_resolves():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cfgs = {c["name"] for c in BENCH["configs"]}
    cells_ = {w["name"] for w in BENCH["workloads"]}
    assert {w["config"] for w in BENCH["workloads"]} == cfgs
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells_)) <= cells_
        assert m["name"].endswith("_roofline") == (
            "roofline" in m["name"]) and (
            "roofline" not in m["name"] or m["unit"] == "%")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_reports_something(workload):
    cell = cells.load_cell(workload)
    assert cell.config["name"] == cell.config_name
    assert {"source", "reduced", "assumed", "precisions", "tolerances"} \
        <= set(cell.config)
    assert len(cell.config["source"]) <= 200
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell.per_layer()
    assert per_layer
    bench_dir = os.path.join(ROOT, "benchmark")
    for m in per_layer:
        base = os.path.join(bench_dir, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
    # the uint8 block of the deployment, resident on the device
    assert cell.nchan * cell.nsamp >= 1 << 30
    assert cell.traffic["run_hi_accel"] == ("hiaccel" in workload)


@pytest.mark.parametrize("metric", sorted(
    f for f in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))))
def test_every_layer_metric_file_names_a_reader_and_a_benchmark_entry(metric):
    name, ext = os.path.splitext(metric)
    assert name in {m["name"] for m in BENCH["per_layer"]}
    if ext == ".json":
        spec = json.load(open(os.path.join(ROOT, "benchmark",
                                           "layer_metrics", metric)))
        assert spec["reader"] in layers.READERS
        if spec["reader"] == "module_roofline":
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "costs", spec["cost"] + ".py"))


def test_the_plan_slices_are_the_ones_the_cells_name():
    mock = cells.plan_slice(cells.load_cell("mock_ds1_hiaccel"))
    assert [(s.numpasses, s.dms_per_pass, s.downsamp) for s in mock] \
        == [(1, 76, 1)]
    assert mock[0].passes()[0].dms[-1] == pytest.approx(7.5)
    wapp = cells.plan_slice(cells.load_cell("wapp_steps_noaccel"))
    assert [(s.numpasses, s.downsamp) for s in wapp] \
        == [(1, 1), (1, 5), (1, 25)]
    assert sum(s.numdms for s in wapp) == 228
    params = cells.search_params(cells.load_cell("wapp_steps_noaccel"))
    assert params.run_hi_accel is False and params.lo_accel_numharm == 16
    assert math.isclose(params.to_prepfold_sigma, 6.0)
