"""The read-in unit of work, on a toy cell ADDED AS FILES ONLY (a
configuration and a traffic mix under ``toy_readin/``, entries in a
temporary copy of BENCHMARK.json): the harness's own PSRFITS writer
against the program's reader, the plain decode and its three wrong
forms, the plain mask statistics against a hand-made block, a whole
run past the look for a chip with ``readin_s`` in its result line, and
the timed path broken underneath.
"""

import dataclasses
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import (cells, check_readin, psrfits4, readin_trace,
                               reference_readin as ref, runner)

ROOT = cells.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_readin")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
READIN = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
          if m["name"].startswith("readin")]


# ------------------------------------------------- the file, both ways

def small_beam(tmp_path, nchan=16, nsamp=8192, nsblk=512, seed=3,
               wts=None):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    nib = rng.integers(0, 16, (nchan, nsamp)).astype(np.uint8)
    spec = {"dat_scl": [0.5, 2.0], "dat_offs": [-3.0, 3.0], "dat_wts": 1.0}
    scl, offs, w = psrfits4.draw_calibration(seed, nchan, spec)
    beam = psrfits4.Beam4(nchan=nchan, nsamp=nsamp, nsblk=nsblk,
                          dt=6.4e-5, fctr_mhz=1375.5, bw_mhz=322.617,
                          scl=scl, offs=offs,
                          wts=w if wts is None else wts.astype(np.float32))
    path = str(tmp_path / psrfits4.FILE_NAME)
    psrfits4.write_beam(path, beam, jnp.asarray(nib), rows_per_chunk=5)
    return path, beam, nib


def calibrated(beam, nib):
    return ((nib.astype(np.float64) * beam.scl[:, None].astype(np.float64)
             + beam.offs[:, None]) * beam.wts[:, None])


def test_the_programs_reader_gets_the_harness_files_samples_back(tmp_path):
    from tpulsar.io import datafile
    from tpulsar.io.psrfits import SpectraInfo

    wts = np.ones(16)
    wts[[2, 9]] = [0.0, 0.5]
    path, beam, nib = small_beam(tmp_path, wts=wts)
    si = SpectraInfo([path])
    assert (si.num_channels, int(si.N), si.bits_per_sample) == (16, 8192, 4)
    assert si.need_flipband and si.dt == beam.dt
    np.testing.assert_allclose(si.freqs, beam.freqs, rtol=0, atol=1e-9)
    got = si.read_all()                       # (T, nchan), ascending
    np.testing.assert_allclose(got.T, calibrated(beam, nib), rtol=2e-6,
                               atol=1e-6)
    obj = datafile.autogen_dataobj([path])    # the merged-Mock name
    assert type(obj).__name__ == "MergedMockPsrfitsData"


def test_the_plain_decode_is_nibble_times_scale_plus_offset(tmp_path):
    wts = np.ones(16)
    wts[5] = 0.25
    path, beam, nib = small_beam(tmp_path, wts=wts)
    f = ref.open_psrfits4(path)
    assert (f["nchan"], f["nsblk"], f["nrows"]) == (16, 512, 16)
    want = calibrated(beam, nib)
    # float32 columns read back as float64: exact to their rounding
    np.testing.assert_allclose(ref.decode_psrfits4(f, 0, 8192), want,
                               rtol=1e-7, atol=1e-7)
    # a stretch that straddles three rows
    np.testing.assert_allclose(ref.decode_psrfits4(f, 500, 1600),
                               want[:, 500:1600], rtol=1e-7, atol=1e-7)


@pytest.mark.parametrize("wrong, least", [
    ({"drop_low_bit": True}, 0.5), ({"swap_nibbles": True}, 1.0),
    ({"file_band_order": True}, 1.0)])
def test_each_wrong_decode_lies_far_from_the_plain_one(tmp_path, wrong,
                                                       least):
    """In units of the smallest DAT_SCL: a dropped low bit is one
    nibble off on half the samples, a swapped pair or a band left
    descending many."""
    path, beam, _nib = small_beam(tmp_path)
    f = ref.open_psrfits4(path)
    gap = np.abs(ref.decode_psrfits4(f, 0, 8192, **wrong)
                 - ref.decode_psrfits4(f, 0, 8192))
    assert gap.max() >= 1.0 * beam.scl.min()
    assert (gap > 0).mean() >= 0.4 * least


# ------------------------------------------- the plain mask statistics

def test_cell_statistics_of_a_hand_made_block(tmp_path):
    """Four channels of 4 cells of 256: a constant, a square wave at
    the Nyquist bin (power N a cell's variance: the largest a cell can
    show), a step between cells, and the scale applied."""
    import jax.numpy as jnp

    N, ncell = 256, 4
    nib = np.zeros((4, N * ncell), np.uint8)
    nib[0] = 7
    nib[1] = np.tile([4, 12], N * ncell // 2)
    nib[2] = np.repeat([2, 2, 10, 2], N)
    nib[3] = np.tile(np.repeat([5, 9], 8), N * ncell // 16)
    beam = psrfits4.Beam4(nchan=4, nsamp=N * ncell, nsblk=128, dt=1e-4,
                          fctr_mhz=1400.0, bw_mhz=4.0,
                          scl=np.asarray([1, 1, 2, 1], np.float32),
                          offs=np.asarray([0, 0, 1, 0], np.float32),
                          wts=np.ones(4, np.float32))
    path = str(tmp_path / "hand.fits")
    psrfits4.write_beam(path, beam, jnp.asarray(nib))
    f = ref.open_psrfits4(path)
    mean, std, maxpow = ref.cell_stats(f, np.ones(4), np.zeros(4), N)
    assert mean.shape == (ncell, 4)
    np.testing.assert_allclose(mean[:, 0], 7.0)
    np.testing.assert_allclose(std[:, 0], 0.0, atol=1e-6)
    np.testing.assert_allclose(maxpow[:, 0], 0.0, atol=1e-3)
    np.testing.assert_allclose(mean[:, 1], 8.0)
    np.testing.assert_allclose(std[:, 1], 4.0, rtol=1e-6)
    np.testing.assert_allclose(maxpow[:, 1], N, rtol=1e-5)
    np.testing.assert_allclose(mean[:, 2], [5.0, 5.0, 21.0, 5.0])
    # a square wave of period 16: most of its variance in one bin
    k = np.fft.rfft(np.tile(np.repeat([-2.0, 2.0], 8), N // 16))
    want = (np.abs(k[1:]) ** 2).max() / (N * 4.0)
    np.testing.assert_allclose(maxpow[:, 3], want, rtol=1e-5)
    # on the program's quantised scale: (x - qoff) / qscale
    m2, s2, _p = ref.cell_stats(f, np.full(4, 0.5), np.full(4, 1.0), N)
    np.testing.assert_allclose(m2[:, 1], 14.0)
    np.testing.assert_allclose(s2[:, 1], 8.0, rtol=1e-6)
    # the control's file: every low bit gone
    m3, _s, _p = ref.cell_stats(f, np.ones(4), np.zeros(4), N,
                                drop_low_bit=True)
    np.testing.assert_allclose(m3[:, 0], 6.0)


def test_robust_z_and_the_largest_of_six():
    x = np.asarray([[1.0, 2.0, 3.0, 4.0, 100.0]]).T        # (5, 1)
    z = ref.robust_z(x, axis=0)
    np.testing.assert_allclose(z[:, 0], (x[:, 0] - 3.0) / 1.4826)
    flat = np.ones((6, 5))
    spike = flat.copy()
    spike[4, 2] = 9.0
    zmax = ref.cell_zmax(flat + np.arange(6)[:, None] * 1e-3, flat, spike)
    assert np.argmax(zmax) == 4 * 5 + 2 and zmax[4, 2] > 1e6


def test_a_candidate_list_is_read_back(tmp_path):
    from tpulsar.io import accelcands
    from tpulsar.search.sifting import Candidate

    c = Candidate(r=1234.5, z=0.0, sigma=12.34, power=99.5, numharm=8,
                  dm=5.25, period_s=0.0100123, freq_hz=99.877151,
                  dm_hits=[(5.2, 11.0), (5.3, 12.34)])
    path = str(tmp_path / "x.accelcands")
    accelcands.write_candlist([c, c], path)
    got = ref.read_accelcands(path)
    assert len(got) == 2 and got[0].numharm == 8
    assert got[0].dm == 5.25 and got[0].sigma == 12.34
    assert got[0].freq_hz == pytest.approx(99.877151)
    assert got[0].period_s == pytest.approx(0.0100123)


# -------------------------------------------------- draws from the seed

@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 11, 4123456789])
def test_the_seed_draws_the_rfi_the_calibration_and_the_tiles(seed):
    where = psrfits4.draw_rfi(seed, 960, 1920)
    assert 0 <= where["channel"] < 960 and 0 <= where["interval"] < 1920
    assert where == psrfits4.draw_rfi(seed, 960, 1920)
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "readin_psrfits4.json")))["input"]
    scl, offs, wts = psrfits4.draw_calibration(seed, 960, spec)
    assert spec["dat_scl"][0] <= scl.min() and scl.max() <= spec["dat_scl"][1]
    assert spec["dat_offs"][0] <= offs.min() \
        and offs.max() <= spec["dat_offs"][1]
    assert np.all(wts == 1.0) and scl.std() > 0.03
    tiles = check_readin.pick_tiles(seed, 1920, 64, where["interval"])
    assert {0, 1919, where["interval"]} <= set(tiles)
    assert 64 <= len(tiles) <= 67 and tiles == sorted(set(tiles))


def test_the_rfi_lies_where_it_was_drawn():
    import jax.numpy as jnp

    block = jnp.full((8, 4096), 8, jnp.uint8)
    spec = {"channel_sigma_factor": 3.0, "interval_raise_quanta": 4}
    out, where = psrfits4.rfi_overlay(block, 17, spec, 512)
    out = np.asarray(out)
    c, i = where["channel"], where["interval"]
    quiet = np.delete(out, c, axis=0)
    assert np.all(quiet[:, i * 512:(i + 1) * 512] == 12)
    assert np.all(np.delete(quiet, np.s_[i * 512:(i + 1) * 512], 1) == 8)
    # sigma 6 about 8, clipped to the 4-bit range: 4.85
    assert 4.5 < out[c].astype(float).std() < 5.2 and out.max() <= 15


# ------------------------------------------ device time inside a read-in

def test_busy_time_is_cut_to_the_readin_spans():
    layout = json.load(open(os.path.join(ROOT, "benchmark",
                                         "trace_layout.json")))
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["readin", 1_000_000_000, 4_000_000_000],
            ["rfifind", 3_000_000_000, 2_000_000_000]]}]},
        {"name": "/device:TPU:0", "lines": []}]}
    busy = {"/device:TPU:0": [(500_000_000, 1_500_000_000),
                              (4_000_000_000, 4_250_000_000),
                              (4_900_000_000, 6_000_000_000)]}
    ctx = {"trace": trace, "layout": layout, "busy": busy}
    assert readin_trace.readin_spans(ctx) == [(1_000_000_000,
                                               5_000_000_000)]
    got = readin_trace.busy_inside(ctx)
    assert got == pytest.approx((0.5 + 0.25 + 0.1, 4.0))
    assert readin_trace.busy_inside({"trace": None}) is None
    from benchmark.harness import layers
    ctx["bench_dir"] = os.path.join(ROOT, "benchmark")
    assert layers.read_metric("readin_mask_device_s", ctx) \
        == pytest.approx(0.85)
    assert layers.read_metric("readin_idle_pct", ctx) \
        == pytest.approx(100 * (1 - 0.85 / 4.0))
    # the call's idle time by what the host was doing: 2 s before the
    # rfifind stage less 0.5 busy, 2 s of the stage less 0.35, and what
    # `tracered` finds after the block (a gap named by its middle)
    gaps = dict(readin_trace.idle_gaps(trace, layout, busy))
    assert gaps["readin, before the rfifind stage"] == pytest.approx(1.5)
    assert gaps["readin, rfifind stage"] == pytest.approx(1.65)
    assert set(gaps) <= {"readin, before the rfifind stage",
                         "readin, rfifind stage", "(between stages)"}
    # nothing ran on the device, or nothing was traced: no reading,
    # never a 0
    ctx["busy"] = {"/device:TPU:0": []}
    assert layers.read_metric("readin_mask_device_s", ctx) is None
    ctx["trace"] = None
    assert layers.read_metric("readin_idle_pct", ctx) is None


# ------------------------------------------------------- BENCHMARK.json

def test_the_standing_cells_report_what_they_did_and_the_new_one_its_own():
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end()]
        layer = {m["name"] for m in cell.per_layer()}
        if w["name"] == "mock_readin":
            assert e2e == ["readin_s", "setup_s"]
            assert layer == {"readin_rfifind_s", "readin_decode_s",
                             "readin_mask_device_s", "readin_idle_pct"}
            assert cell.traffic["unit"] == "readin"
        else:
            assert e2e == ["trials_per_s", "finish_s", "setup_s"]
            assert not layer & set(READIN) and "unit" not in cell.traffic
            assert runner.unit_measure(cell) is runner.measure


# ------------------------------------------------- the toy cell, whole

@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_readin_checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TOY, sub)):
            dst = os.path.join(root, "benchmark", sub, f)
            assert not os.path.exists(dst)           # new files only
            shutil.copy(os.path.join(TOY, sub, f), dst)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "toy_mock_readin", "source": "tests", "reduced": ["passes"],
        "file": "benchmark/configs/toy_mock_readin.json", "why": "toy"})
    bench["workloads"].append({
        "name": "toy_readin", "config": "toy_mock_readin",
        "traffic": "toy_readin_psrfits4", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in READIN:
            m["workloads"] = m["workloads"] + ["toy_readin"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def run(toy_root, seed, warm=False, **kw):
    cell = cells.load_cell("toy_readin", root=toy_root)
    measure = runner.unit_measure(cell)
    assert measure is not runner.measure
    return cell, measure(cell, seed, 0.5, kw.pop("trace", False),
                         t_process=time.time(), warm=warm,
                         log=lambda m: None, **kw)


@pytest.fixture(scope="module")
def sound(toy_root):
    return run(toy_root, 2 ** 31 + 4321, warm=True, control=True)


def numbers(res, key="check"):
    return {n["name"]: n for n in res[key]}


def test_the_toy_readin_cell_runs_whole_and_is_correct(sound, toy_root):
    cell, res = sound
    assert res["correct"] is True, numbers(res)
    assert res["attempted"] % 76 == 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()} \
        == {"readin_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["metrics"]["readin_s"]["unit"] == "s"
    assert res["native_unpacker"] is True
    assert list(res)[-2:] == ["check", "control"]     # the numbers last
    for c in res["calls"]:
        # the stamps: the block was resident before search_block ran
        assert 0 < c["stage_s"]["rfifind"] < c["readin_s"]
        assert c["search_block_s"] > 0 and c["after_s"] > 0
    json.dumps(res)
    # what a run leaves behind: nothing (the file is 1.9 GB a run)
    assert not os.path.exists(os.path.join(toy_root, ".bench_readin"))


def test_every_readin_number_is_printed_beside_its_limit(sound):
    _cell, res = sound
    got = numbers(res)
    assert list(got) == [
        "trials_not_searched", "degraded_or_rescued_flags",
        "native_unpacker_missing", "block_step_gap", "fill_mismatch",
        "reference_stats_gap", "block_mean_gap", "chan_fill_gap", "mask_z_gap",
        "rfi_channel_unflagged", "rfi_interval_unflagged",
        "masked_fraction", "masked_fraction_short", "pulsar_missing",
        "pulsar_period_frac_err"]
    assert all(n["ok"] and n["value"] <= n["limit"] for n in got.values())
    # half a step is reached (a rounding) and not passed
    assert 0.45 < got["block_step_gap"]["value"] <= 0.5001
    assert got["block_step_gap"]["n"] > 5 * 2048 * 50
    assert got["fill_mismatch"]["n"] >= 64 + 32 - 1
    assert got["block_mean_gap"]["n"] > 1500


def test_the_low_bit_control_comes_out_not_correct(sound):
    _cell, res = sound
    ctrl = numbers(res, "control")
    assert set(ctrl) == {"block_step_gap", "block_mean_gap",
                         "chan_fill_gap", "mask_z_gap"}
    for name in ("block_step_gap", "block_mean_gap", "chan_fill_gap"):
        assert not ctrl[name]["ok"]
        assert ctrl[name]["value"] >= 2 * ctrl[name]["limit"]


def test_a_traced_toy_run_reads_the_host_side_layer_metrics(toy_root):
    cell, res = run(toy_root, 77, warm=True, trace=True)
    assert res["correct"] is True, numbers(res)
    want = {m["name"] for m in cell.per_layer()}
    assert want == {"readin_rfifind_s", "readin_decode_s",
                    "readin_mask_device_s", "readin_idle_pct"}
    # no device plane on a CPU: the two device readers leave theirs out
    assert set(res["metrics"]) == {"readin_rfifind_s", "readin_decode_s"}
    call = res["calls"][0]
    assert res["metrics"]["readin_rfifind_s"]["value"] > 0
    assert res["metrics"]["readin_decode_s"]["value"] > 0
    assert call["stage_s"]["rfifind"] < call["readin_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    # the traced span is one read-in, whole
    assert res["device"]["window_s"] >= call["readin_s"] \
        + call["search_block_s"]


def test_no_stamp_at_search_block_is_no_result(toy_root, monkeypatch):
    """``search_beam`` has to reach ``search_block`` through the module
    attribute the harness wraps: one that does not gives no readin_s,
    and the run ends non-zero with no result line."""
    from tpulsar.search import executor

    real = executor.search_block

    def sidestep(fns, workdir, resultsdir, params, **kw):
        return None                    # returns without the attribute

    monkeypatch.setattr(executor, "search_beam", sidestep)
    with pytest.raises(SystemExit) as err:
        run(toy_root, 5)
    assert "search_block" in str(err.value.code)
    assert err.value.code not in (0, None)
    assert executor.search_block is real          # the wrapper is gone


def test_search_beam_reaches_search_block_through_the_attribute(toy_root,
                                                                monkeypatch):
    """The program's side of that contract, held on the real entry."""
    from tpulsar.search import executor

    seen = []
    real = executor.search_block

    def spy(data, *a, **kw):
        seen.append(tuple(data.shape))
        return real(data, *a, **kw)

    _cell, res = run(toy_root, 6, search_block=spy)
    assert seen == [(64, 65536)] * len(res["calls"])
    assert executor.search_block is real


@pytest.mark.parametrize("fault, fails", [
    ("samples_altered", "block_step_gap"),
    ("stretch_misplaced", "block_mean_gap"),
    ("mask_not_applied", "fill_mismatch"),
    ("threshold_raised", "mask_z_gap"),
    ("pass_left_out", "trials_not_searched")])
def test_correct_is_false_with_the_timed_path_broken(toy_root, monkeypatch,
                                                     fault, fails):
    from tpulsar.io.psrfits import SpectraInfo
    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.search import executor

    kw = {}
    if fault == "samples_altered":
        real = SpectraInfo.read_all_uint8

        def off_by_two(self, *a, **k):
            block, scale, off = real(self, *a, **k)
            block[1000:1200, 3] += 2          # inside one interval
            return block, scale, off

        monkeypatch.setattr(SpectraInfo, "read_all_uint8", off_by_two)
    elif fault == "stretch_misplaced":
        real = SpectraInfo.read_all_uint8

        def rolled(self, *a, **k):
            block, scale, off = real(self, *a, **k)
            return np.roll(block, 2048, axis=0), scale, off

        monkeypatch.setattr(SpectraInfo, "read_all_uint8", rolled)
    elif fault == "mask_not_applied":
        monkeypatch.setattr(rfi_k, "apply_mask_chan",
                            lambda data, mask, fill, block_len: data)
    elif fault == "threshold_raised":
        real_find = rfi_k.find_rfi_chan
        monkeypatch.setattr(
            rfi_k, "find_rfi_chan",
            lambda data, dt, block_len, threshold: real_find(
                data, dt, block_len=block_len, threshold=threshold + 2))
    else:
        real_block = executor.search_block

        def lazy(data, freqs, dt, plan, params, **k):
            none = [dataclasses.replace(s, numpasses=0) for s in plan]
            return real_block(data, freqs, dt, none, params, **k)

        kw["search_block"] = lazy
    _cell, res = run(toy_root, 4242, **kw)
    assert res["correct"] is False
    assert not numbers(res)[fails]["ok"], numbers(res)
    if fault == "pass_left_out":
        assert res["failed"] == res["attempted"] > 0
