"""The read-in touches each sample once (PR 42): `read_all_uint8`'s
block, scale and offset against the code it replaced (kept HERE as the
oracle), the medians from nibble counts against `np.median` over the
pool, the files that keep the pool and the NumPy decode, and
`_read_and_mask`'s block transposed on the device."""

import dataclasses
import os
import re
import warnings

import numpy as np
import pytest

from tpulsar import native
from tpulsar.io import psrfits, synth
from tpulsar.io.psrfits import SpectraInfo, median_from_counts
from tpulsar.obs import trace

@pytest.fixture(scope="module")
def lib():
    """The native library, or a skip where no toolchain builds it."""
    lib = native.load()
    if lib is None:
        pytest.skip("no native toolchain")
    return lib


# ------------------------------------------------------------------
# the oracle: read_all_uint8 as it stood before PR 42
# ------------------------------------------------------------------

def _old_read_quantized_4bit(si, ii, lo, hi, qscale, qoffset, out_slice):
    from tpulsar.io import fitscore

    if not si._fast4_applicable():
        return False
    finfo = si._files[ii]
    rows = fitscore.get_hdu(finfo.hdus, "SUBINT").data[lo:hi]
    raw = np.asarray(rows["DATA"])
    nrows, nsblk, nchan = hi - lo, si.spectra_per_subint, si.num_channels
    packed = np.ascontiguousarray(raw.reshape(nrows, nsblk, nchan // 2))
    qs = float(qscale[0])
    qoff_file = qoffset[::-1] if si.need_flipband else qoffset
    for r in range(nrows):
        eff_scl, eff_off = si._row_effective_affine(rows, r, nchan)
        res = native.unpack4_quantize(packed[r], eff_scl / qs,
                                      (eff_off - qoff_file) / qs)
        out_slice[r * nsblk:(r + 1) * nsblk] = \
            res[:, ::-1] if si.need_flipband else res
    return True


def old_read_all_uint8(si, target_std_lsb=18.0, chunk_subints=16):
    """The parent's loop: the affine from the pool, one group after
    another, each row decoded to `res` and copied (flipped) into out."""
    nchan, nsblk = si.num_channels, si.spectra_per_subint
    total = int(sum(f.num_subint * nsblk + f.num_pad for f in si._files))
    out = np.empty((total, nchan), np.uint8)
    scale, offset = si._quantize_affine(target_std_lsb, chunk_subints)
    pos = 0
    for ii, finfo in enumerate(si._files):
        file_start = pos
        for r0 in range(0, finfo.num_subint, chunk_subints):
            hi = min(r0 + chunk_subints, finfo.num_subint)
            n = (hi - r0) * nsblk
            if _old_read_quantized_4bit(si, ii, r0, hi, scale, offset,
                                        out[pos: pos + n]):
                pos += n
                continue
            blockf = si.read_subints(ii, r0, hi)
            q = np.rint((blockf - offset) / scale)
            out[pos: pos + len(blockf)] = np.clip(q, 0, 255).astype(
                np.uint8)
            pos += len(blockf)
        if finfo.num_pad:
            tail = out[max(file_start, pos - 1024): pos]
            medq = (np.median(tail, axis=0).astype(np.uint8)
                    if len(tail) else np.full(nchan, 128, np.uint8))
            out[pos: pos + finfo.num_pad] = medq[None, :]
            pos += finfo.num_pad
    return out[:pos], scale, offset


# ------------------------------------------------------------------
# files
# ------------------------------------------------------------------

def _write(path, seed=3, **kw):
    spec = synth.BeamSpec(**{**dict(nchan=32, nsamp=40 * 64, nsblk=64,
                                    nbits=4, seed=seed), **kw})
    psr = synth.PulsarSpec(period_s=0.05, dm=30.0, snr_per_sample=1.0)
    synth.write_psrfits(str(path), spec,
                        synth.make_dynamic_spectrum(spec, pulsars=[psr]))
    return str(path), spec


def _vary_rows(path, seed=11):
    """DAT_SCL, DAT_OFFS and DAT_WTS redrawn for every row (the first
    among them, so that the reader's need_* flags come up)."""
    from tpulsar.io import fitscore

    rng = np.random.default_rng(seed)
    table = fitscore.get_hdu(fitscore.read_fits(path), "SUBINT").data
    rows = np.memmap(path, dtype=table.dtype, mode="r+",
                     offset=table.offset, shape=table.shape)
    del table
    rows["DAT_SCL"] *= rng.uniform(0.8, 1.25, rows["DAT_SCL"].shape)
    rows["DAT_OFFS"] += rng.uniform(-0.5, 0.5, rows["DAT_OFFS"].shape)
    rows["DAT_WTS"] = rng.choice([0.0, 0.5, 1.0, 1.5],
                                 rows["DAT_WTS"].shape,
                                 p=[0.05, 0.15, 0.6, 0.2])
    rows.flush()
    del rows


def _beam(tmp_path, case):
    """-> SpectraInfo of the named 4-bit beam."""
    if case == "two_files_padded":
        p1, spec = _write(tmp_path / "a.fits", descending_band=True)
        gap = 200
        spec2 = dataclasses.replace(
            spec, seed=9, mjd=spec.mjd
            + (spec.nsamp + gap) * spec.tsamp_s / 86400.0)
        p2 = str(tmp_path / "b.fits")
        synth.write_psrfits(p2, spec2, synth.make_dynamic_spectrum(spec2))
        _vary_rows(p2)
        si = SpectraInfo([p1, p2])
        assert si.num_pad[0] == gap
        return si
    kw = {"descending": dict(descending_band=True),
          "ascending": {},
          "rows_vary": dict(descending_band=True),
          "zero_off": dict(descending_band=True),
          # 37 rows: 16 does not divide them, the sampled groups overlap
          "ragged": dict(descending_band=True, nsamp=37 * 64),
          # fewer rows than one group: every sampled row counts twice
          "short": dict(nsamp=5 * 64)}[case]
    path, spec = _write(tmp_path / "beam.fits", **kw)
    if case in ("rows_vary", "zero_off", "ragged"):
        _vary_rows(path)
    si = SpectraInfo([path])
    if case == "zero_off":
        si.zero_off = 2.5
    assert si.need_flipband == bool(kw.get("descending_band"))
    return si


CASES = ["descending", "ascending", "rows_vary", "zero_off", "ragged",
         "short", "two_files_padded"]


@pytest.mark.parametrize("threads", [1, 5])
@pytest.mark.parametrize("case", CASES)
def test_read_all_uint8_equals_the_parents_to_the_bit(
        lib, tmp_path, monkeypatch, case, threads):
    monkeypatch.setattr(psrfits, "DECODE_THREADS", threads)
    si = _beam(tmp_path, case)
    want, wscale, woff = old_read_all_uint8(si)
    trace.start()
    try:
        got, scale, offset = si.read_all_uint8()
        spans = {e["name"]: e["args"] for e in trace.events()}
    finally:
        trace.reset()
    assert got.dtype == np.uint8 and got.shape == want.shape == (
        int(si.N), si.num_channels)
    assert got.flags.c_contiguous
    np.testing.assert_array_equal(got, want)
    assert scale.tobytes() == wscale.tobytes()
    assert offset.tobytes() == woff.tobytes()
    assert spans["readin-affine"]["form"] == "counts"
    assert spans["readin-decode"]["form"] == "native4"
    assert spans["readin-decode"]["threads"] == min(
        threads, len(os.sched_getaffinity(0)))
    assert spans["readin-decode"]["groups"] == sum(
        -(-f.num_subint // 16) for f in si._files)


def test_a_group_that_fails_fails_the_call(lib, tmp_path, monkeypatch):
    """A group's exception is not left in its future."""
    si = _beam(tmp_path, "descending")

    def broken(*a, **kw):
        raise RuntimeError("group lost")

    monkeypatch.setattr(native, "unpack4_quantize_rows", broken)
    with pytest.raises(RuntimeError, match="group lost"):
        si.read_all_uint8()


# ------------------------------------------------------------------
# files the native 4-bit path does not take
# ------------------------------------------------------------------

def _other_beam(tmp_path, case):
    if case == "8bit":
        path, _ = _write(tmp_path / "b.fits", nbits=8,
                         descending_band=True)
    elif case == "two_pol_aabb":
        # the writer makes one polarisation: 64 channels read as AA
        # and BB of 32 (a row's nibbles, DAT_SCL and DAT_OFFS are laid
        # out polarisation-major either way)
        path, _ = _write(tmp_path / "b.fits", nchan=64)
    else:
        path, _ = _write(tmp_path / "b.fits", descending_band=True)
    si = SpectraInfo([path])
    if case == "two_pol_aabb":
        si.num_polns, si.num_channels, si.poln_order = 2, 32, "AABB"
    elif case == "signed":
        si.signed_ints = True
    elif case == "no_native_library":
        si._fast4_applicable = lambda: False
    return si


@pytest.mark.parametrize("case", ["8bit", "two_pol_aabb", "signed",
                                  "no_native_library"])
def test_other_files_keep_the_pool_and_the_numpy_decode(tmp_path, case):
    si = _other_beam(tmp_path, case)
    want, wscale, woff = old_read_all_uint8(si)
    trace.start()
    try:
        got, scale, offset = si.read_all_uint8()
        spans = {e["name"]: e["args"] for e in trace.events()}
    finally:
        trace.reset()
    np.testing.assert_array_equal(got, want)
    assert scale.tobytes() == wscale.tobytes()
    assert offset.tobytes() == woff.tobytes()
    assert spans["readin-affine"]["form"] == "pool"
    assert spans["readin-decode"]["form"] == "numpy"
    assert spans["readin-decode"]["threads"] == 1


def test_the_counter_says_which_form_decoded(tmp_path):
    from tpulsar.obs import telemetry

    si = _beam(tmp_path, "ascending")
    form = "native4" if si._fast4_applicable() else "numpy"
    ctr = telemetry.readin_bytes_total()
    before = ctr.value(form=form), ctr.value(form="float32")
    block, _, _ = si.read_all_uint8()
    blockf = si.read_all()
    assert ctr.value(form=form) - before[0] == block.nbytes
    assert ctr.value(form="float32") - before[1] == blockf.nbytes


# ------------------------------------------------------------------
# the medians from counts
# ------------------------------------------------------------------

def _pool_of(values, counts):
    return np.stack([np.repeat(values[:, c], counts[:, c])
                     for c in range(values.shape[1])], axis=1)


@pytest.mark.parametrize("case", ["odd", "even", "ties", "unused_values",
                                  "nan_held", "nan_unused"])
def test_median_from_counts_is_np_median_over_the_pool(case):
    rng = np.random.default_rng(5)
    nchan, nval, total = 7, 48, {"odd": 1001, "even": 1000}.get(case, 600)
    values = rng.normal(20.0, 4.0, (nval, nchan)).astype(np.float32)
    if case == "ties":
        # few distinct values, most of them several times
        values = rng.integers(0, 6, (nval, nchan)).astype(np.float32) / 3
    # every column the same total (the pool is a rectangle), split by a
    # multinomial so that some values never occur
    counts = np.stack([rng.multinomial(total, rng.dirichlet(
        np.full(nval, 0.3))) for _ in range(nchan)], axis=1)
    if case == "unused_values":
        counts[rng.random(counts.shape) < 0.5] = 0
        counts[0] += total - counts.sum(axis=0)
    if case == "nan_held":
        values[3, 2] = np.nan
        counts[counts[:, 2].argmax(), 2] -= 1
        counts[3, 2] += 1
    if case == "nan_unused":
        values[3, 2] = np.nan
        counts[0, 2] += counts[3, 2]
        counts[3, 2] = 0
    pool = _pool_of(values, counts)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # NaN's
        want = np.median(pool, axis=0)
        wmad = np.median(np.abs(pool - want), axis=0)
    got = median_from_counts(values, counts)
    assert got.dtype == np.float32
    assert got.tobytes() == want.tobytes()
    assert np.isnan(got[2]) == (case == "nan_held")
    with np.errstate(invalid="ignore"):
        gmad = median_from_counts(np.abs(values - got), counts)
    assert gmad.tobytes() == wmad.tobytes()


def test_count4_counts_every_nibble_of_a_row(lib, tmp_path):
    si = _beam(tmp_path, "descending")
    _, raw = si._packed_rows(0, 0, 3)
    nsblk, nchan = si.spectra_per_subint, si.num_channels
    counts = native.count4(raw[1], nsblk, nchan)
    samples = psrfits.unpack_samples(
        np.ascontiguousarray(raw[1]).reshape(nsblk, nchan // 2), 4)
    want = np.stack([(samples == x).sum(axis=0) for x in range(16)],
                    axis=1)
    np.testing.assert_array_equal(counts, want)
    assert counts.sum() == nsblk * nchan
    with pytest.raises(ValueError, match="not packed 4-bit rows"):
        native.count4(raw[1], nsblk + 1, nchan)


# ------------------------------------------------------------------
# _read_and_mask: the transpose on the device
# ------------------------------------------------------------------

def _search_setup(tmp_path, quantize):
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    spec = synth.BeamSpec(nchan=96, nsamp=1 << 15, nbits=4,
                          tsamp_s=5.24288e-4, descending_band=True)
    psr = synth.PulsarSpec(period_s=0.15, dm=60.0, snr_per_sample=0.5,
                           width_frac=0.05)
    fns = synth.synth_beam(str(tmp_path / "data"), spec, pulsars=[psr])
    plan = [ddplan.DedispStep(lodm=52.0, dmstep=2.0, dms_per_pass=8,
                              numpasses=1, numsub=24, downsamp=1)]
    params = executor.SearchParams(
        nsub=24, hi_accel_zmax=0, topk_per_stage=16, max_cands_to_fold=1,
        fold_nbin=32, fold_npart=8, block_quantize=quantize,
        make_plots=False)
    return fns, plan, params


@pytest.mark.parametrize("quantize,dtype", [("on", np.uint8),
                                            ("off", np.float32)])
def test_read_and_masks_block_is_the_host_transpose_uncommitted(
        tmp_path, monkeypatch, quantize, dtype):
    import jax

    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.search import executor
    from tpulsar.search.report import StageTimers

    fns, _, params = _search_setup(tmp_path, quantize)
    si = SpectraInfo(fns)
    block = (si.read_all_uint8()[0] if quantize == "on"
             else si.read_all())
    seen = {}
    real = rfi_k.find_rfi_chan

    def find(data, *a, **kw):
        seen["data"] = data
        return real(data, *a, **kw)

    monkeypatch.setattr(rfi_k, "find_rfi_chan", find)
    os.makedirs(tmp_path / "results")
    trace.start()
    try:
        data, mask = executor._read_and_mask(
            si, params, "beam", str(tmp_path / "results"), None,
            StageTimers())
        spans = {e["name"]: e["args"] for e in trace.events()}
    finally:
        trace.reset()
    placed = seen["data"]
    assert placed.dtype == dtype and placed.shape == block.shape[::-1]
    np.testing.assert_array_equal(np.asarray(placed),
                                  np.ascontiguousarray(block.T))
    # as jnp.asarray leaves an array: on the default device and free to
    # follow its consumers (the mesh path places its operands from it)
    for arr in (placed, data):
        assert not arr.committed
        assert arr.devices() == {jax.devices()[0]}
    assert spans["readin-place"] == {
        **spans["readin-place"], "bytes": block.nbytes,
        "transposed": "device", "parent": "rfifind"}
    assert mask.cell_mask.shape[1] == si.num_channels


def test_search_beam_writes_what_the_parent_wrote(lib, tmp_path,
                                                  monkeypatch):
    """A small quantised 4-bit beam through `search_beam`, with the
    read-in as it is and with the parent's (the oracle's block, the
    transpose on the host): the mask artifact's arrays (an .npz's
    entries carry the clock), the .accelcands, and the .report's rows
    and the timers' keys with tracing off."""
    import jax.numpy as jnp

    from tpulsar.kernels import rfi as rfi_k
    from tpulsar.search import executor

    fns, plan, params = _search_setup(tmp_path, "on")

    def run(tag):
        out = executor.search_beam(
            fns, str(tmp_path / tag / "work"),
            str(tmp_path / tag / "results"), params=params, plan=plan,
            baryv=0.0)
        rd = out.resultsdir
        with np.load(os.path.join(rd, f"{out.basenm}_rfifind.npz")) as z:
            arrays = {k: (z[k].dtype, z[k].shape, z[k].tobytes())
                      for k in z.files}
        cands = open(os.path.join(rd, f"{out.basenm}.accelcands")).read()
        report = open(os.path.join(rd, f"{out.basenm}.report")).read()
        return arrays, cands, report, set(out.timers.times)

    new = run("new")
    with monkeypatch.context() as mp:
        mp.setattr(SpectraInfo, "read_all_uint8", old_read_all_uint8)
        mp.setattr(rfi_k, "channel_major", lambda dev: jnp.asarray(
            np.ascontiguousarray(np.asarray(dev).T)))
        old = run("old")
    assert new[0].keys() == old[0].keys() and new[0]["qscale"][1] == (96,)
    for key in new[0]:
        assert new[0][key] == old[0][key], key
    assert new[1] == old[1] and "DM" in new[1]
    # the same rows in the same order, no stage for the read-in; the
    # seconds are the clock's

    def rows(text):
        return [re.sub(r" *[0-9.]+ s| *[0-9.]+%", "", ln)
                for ln in text.splitlines()]

    assert rows(new[2]) == rows(old[2])
    assert new[3] == old[3]
