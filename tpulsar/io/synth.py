"""Synthetic PSRFITS beam generator with injected pulsars.

The reference has no offline test fixture at all — its tests hit live
servers (SURVEY.md section 4).  This module closes that gap: it writes
search-mode PSRFITS files (single merged-band beams, or PALFA
Mock-spectrometer s0/s1 subband pairs) containing Gaussian radio
noise, optional injected dispersed pulsars, and optional injected RFI,
so every layer from the FITS reader to the full search executor can be
tested hermetically and candidate recovery can be asserted against
ground truth.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from tpulsar.astro import angles, times
from tpulsar.constants import dispersion_delay_s
from tpulsar.io import fitscore


@dataclasses.dataclass
class PulsarSpec:
    """Ground truth for one injected pulsar."""
    period_s: float
    dm: float
    width_frac: float = 0.05      # FWHM as a fraction of the period
    snr_per_sample: float = 0.1   # peak amplitude in units of noise sigma
    pdot: float = 0.0             # period derivative (s/s)


@dataclasses.dataclass
class RFISpec:
    """Ground truth for injected interference."""
    kind: str = "tone"            # 'tone' (narrowband) or 'burst' (broadband)
    channel: int = 0              # for tones
    t_start_s: float = 0.0        # for bursts
    t_len_s: float = 0.1
    amplitude: float = 5.0


@dataclasses.dataclass
class BeamSpec:
    """Observation geometry for a synthetic beam (PALFA-Mock-like
    defaults, scaled down; real Mock: 960 chan, 65.5 us, ~4 min)."""
    nchan: int = 96
    nsamp: int = 1 << 16
    tsamp_s: float = 655.36e-6
    fctr_mhz: float = 1375.5
    bw_mhz: float = 322.617
    nbits: int = 4
    npol: int = 1
    nsblk: int = 64
    source: str = "G0000+00"
    ra_str: str = "18:53:00.0"
    dec_str: str = "+13:04:00.0"
    projid: str = "P2030"
    beam_id: int = 3
    scan: int = 100
    mjd: float = 55555.5
    backend: str = "pdev"
    descending_band: bool = False  # write channels in descending freq order
    seed: int = 42


def channel_freqs(spec: BeamSpec) -> np.ndarray:
    """Ascending channel center frequencies in MHz."""
    df = spec.bw_mhz / spec.nchan
    lo = spec.fctr_mhz - spec.bw_mhz / 2 + df / 2
    return lo + np.arange(spec.nchan) * df


def dispersion_delays(dm: float, freqs_mhz: np.ndarray,
                      ref_freq_mhz: float) -> np.ndarray:
    """Dispersion delay (s) of each channel relative to ref_freq."""
    return dispersion_delay_s(dm, freqs_mhz, ref_freq_mhz)


#: elements of the dynamic spectrum generated, digitized and written
#: at a time.  A full Mock beam (3.9 M samples x 960 channels, 15 GB
#: as float32) is 480 such blocks; every beam the tests write is one.
BLOCK_ELEMS = 1 << 23

#: the quantisation levels come from (at least) this many leading
#: elements — the whole beam at every size the tests write
LEVEL_ELEMS = 1 << 25

_scratch = threading.local()


def _buf(name: str, shape: tuple, dtype) -> np.ndarray:
    """A per-thread work buffer, reused from block to block.  A fresh
    multi-MB temporary per operation is page-faulted anew every time,
    and a sandboxed host may never hand the freed pages back: a full
    beam written with plain expressions churns through ~50 GB."""
    arr = getattr(_scratch, name, None)
    if arr is None or arr.shape != shape or arr.dtype != dtype:
        arr = np.empty(shape, dtype)
        setattr(_scratch, name, arr)
    return arr


def block_rows(spec: BeamSpec) -> int:
    """Rows per generated block: a whole number of subints."""
    rows = (BLOCK_ELEMS // spec.nchan) // spec.nsblk * spec.nsblk
    return min(spec.nsamp, max(spec.nsblk, rows))


def _block(spec: BeamSpec, pulsars, rfi, k: int, rows: int
           ) -> np.ndarray:
    """Block ``k`` (rows [k*rows, (k+1)*rows)) of the dynamic
    spectrum, float32 with channels ascending in frequency:
    unit-variance noise plus the injected signals.  Each block draws
    its noise from a stream of its own, (seed, k) — block 0 from
    ``seed`` alone — so blocks are made in parallel.  The result is
    this thread's work buffer: use it before the next call."""
    r0 = k * rows
    shape = (min(rows, spec.nsamp - r0), spec.nchan)
    rng = np.random.default_rng([spec.seed, k] if k else spec.seed)
    f64 = _buf("f64", shape, np.float64)
    rng.standard_normal(out=f64)
    data = _buf("data", shape, np.float32)
    data[...] = f64
    freqs = channel_freqs(spec)
    ref = freqs[-1]
    t = (r0 + np.arange(shape[0])) * spec.tsamp_s

    for psr in pulsars:
        delays = dispersion_delays(psr.dm, freqs, ref)
        # Gaussian pulse profile in phase, per channel with its
        # delay:  snr * exp(-0.5 * (min(ph, 1 - ph) / sigma) ** 2),
        # ph = ((t - delay) / p_inst) % 1  — those operations in that
        # order, on the work buffers
        sigma_phase = psr.width_frac / 2.35482
        p_inst = (psr.period_s + psr.pdot * t)[:, None]
        ph, tmp = f64, _buf("tmp", shape, np.float64)
        np.subtract(t[:, None], delays[None, :], out=ph)
        ph /= p_inst
        np.mod(ph, 1.0, out=ph)
        np.subtract(1.0, ph, out=tmp)
        np.minimum(ph, tmp, out=ph)
        ph /= sigma_phase
        np.square(ph, out=ph)
        ph *= -0.5
        np.exp(ph, out=ph)
        ph *= psr.snr_per_sample
        f32 = _buf("cast", shape, np.float32)
        f32[...] = ph
        data += f32

    for r in rfi:
        if r.kind == "tone":
            data[:, r.channel] += r.amplitude * np.sin(
                2 * np.pi * 60.0 * t).astype(np.float32)
        elif r.kind == "burst":
            i0 = int(r.t_start_s / spec.tsamp_s)
            i1 = min(spec.nsamp, i0 + max(1, int(r.t_len_s / spec.tsamp_s)))
            lo, hi = max(i0 - r0, 0), min(i1 - r0, shape[0])
            if hi > lo:
                data[lo:hi, :] += r.amplitude
    return data


def make_dynamic_spectrum(spec: BeamSpec,
                          pulsars: list[PulsarSpec] = (),
                          rfi: list[RFISpec] = ()) -> np.ndarray:
    """Float32 (nsamp, nchan) dynamic spectrum in one piece."""
    rows = block_rows(spec)
    return _stacked(lambda k: _block(spec, pulsars, rfi, k, rows),
                    -(-spec.nsamp // rows))


def _stacked(block, n: int) -> np.ndarray:
    """Blocks 0..n-1 in one array (copied: a block may be a work
    buffer that the next call overwrites)."""
    out = np.concatenate([np.array(block(k)) for k in range(n)])
    _scratch.__dict__.clear()     # the calling thread outlives the call
    return out


def _levels(data: np.ndarray, nbits: int):
    """Per-channel scale/offset mapping float data onto unsigned
    nbits ints so that decode(scale*x+offset) ~= data."""
    lo = np.percentile(data, 0.5, axis=0)
    hi = np.percentile(data, 99.5, axis=0)
    nlev = (1 << nbits) - 1
    scale = np.maximum((hi - lo) / nlev, 1e-6).astype(np.float32)
    return scale, lo.astype(np.float32)


def _subint_dtype(spec: BeamSpec) -> np.dtype:
    nchan, npol = spec.nchan, spec.npol
    bytes_per_blk = spec.nsblk * npol * nchan * spec.nbits // 8
    return np.dtype([
        ("TSUBINT", ">f8"), ("OFFS_SUB", ">f8"), ("LST_SUB", ">f8"),
        ("RA_SUB", ">f8"), ("DEC_SUB", ">f8"), ("GLON_SUB", ">f8"),
        ("GLAT_SUB", ">f8"), ("FD_ANG", ">f4"), ("POS_ANG", ">f4"),
        ("PAR_ANG", ">f4"), ("TEL_AZ", ">f4"), ("TEL_ZEN", ">f4"),
        ("DAT_FREQ", ">f8", (nchan,)), ("DAT_WTS", ">f4", (nchan,)),
        ("DAT_OFFS", ">f4", (nchan * npol,)), ("DAT_SCL", ">f4", (nchan * npol,)),
        ("DATA", ">u1", (bytes_per_blk,)),
    ])


def _subint_rows(spec: BeamSpec, sub0: int, data: np.ndarray,
                 freqs: np.ndarray, scale: np.ndarray,
                 offset: np.ndarray) -> np.ndarray:
    """The SUBINT table rows for one block of the spectrum starting
    at subint ``sub0``; freqs/scale/offset are in file channel order.
    The result is this thread's work buffer."""
    from tpulsar.io.psrfits import pack_samples

    nlev = (1 << spec.nbits) - 1
    if spec.descending_band:
        data = data[:, ::-1]
    # q = clip(round((data - offset) / scale), 0, nlev)
    lev = _buf("cast", data.shape, np.result_type(data, np.float32))
    np.subtract(data, offset, out=lev)
    lev /= scale
    np.round(lev, out=lev)
    np.clip(lev, 0, nlev, out=lev)
    q = _buf("q", data.shape, np.uint16)
    q[...] = lev
    nsub = data.shape[0] // spec.nsblk
    rows = _buf("rows", (nsub,), _subint_dtype(spec))
    rows[...] = np.zeros((), rows.dtype)
    tsub = spec.nsblk * spec.tsamp_s
    rows["TSUBINT"] = tsub
    rows["OFFS_SUB"] = (sub0 + np.arange(nsub) + 0.5) * tsub
    rows["RA_SUB"] = angles.hms_str_to_deg(spec.ra_str)
    rows["DEC_SUB"] = angles.dms_str_to_deg(spec.dec_str)
    rows["TEL_AZ"] = 180.0
    rows["TEL_ZEN"] = 10.0
    rows["DAT_FREQ"] = freqs
    rows["DAT_WTS"] = 1.0
    rows["DAT_OFFS"] = np.tile(offset, spec.npol)
    rows["DAT_SCL"] = np.tile(scale, spec.npol)
    rows["DATA"] = pack_samples(
        q.reshape(nsub, spec.nsblk * spec.npol * spec.nchan),
        spec.nbits).reshape(nsub, -1)
    return rows


def write_psrfits(path: str, spec: BeamSpec, data: np.ndarray) -> str:
    """Write (nsamp, nchan) float data as a search-mode PSRFITS file."""
    rows = block_rows(spec)
    return _write_psrfits(path, spec, rows,
                          lambda k: data[k * rows:(k + 1) * rows])


def _write_psrfits(path: str, spec: BeamSpec, rows: int, block) -> str:
    """Write the file whose spectrum is ``block(k)`` for k = 0, 1, ...
    (float (rows, nchan) arrays, rows a whole number of subints,
    channels ascending).  Blocks are digitized and written on a
    thread pool, each at its own file offset; the quantisation levels
    come from the leading LEVEL_ELEMS elements."""
    nsub = spec.nsamp // spec.nsblk
    if nsub * spec.nsblk != spec.nsamp:
        raise ValueError("nsamp must be a multiple of nsblk")
    nblocks = -(-spec.nsamp // rows)
    scale, offset = _levels(_stacked(block, min(
        nblocks, -(-LEVEL_ELEMS // (rows * spec.nchan)))), spec.nbits)

    freqs = channel_freqs(spec)
    if spec.descending_band:
        freqs = freqs[::-1]
        scale = scale[::-1]
        offset = offset[::-1]

    nchan, npol, nsblk = spec.nchan, spec.npol, spec.nsblk

    mjd_i = int(spec.mjd)
    secs = (spec.mjd - mjd_i) * 86400.0
    stt_smjd = int(secs)
    stt_offs = secs - stt_smjd

    primary = fitscore.primary_header()
    for k, v in [
        ("FITSTYPE", "PSRFITS"), ("HDRVER", "3.4"),
        ("TELESCOP", "Arecibo"), ("OBSERVER", "tpulsar-synth"),
        ("PROJID", spec.projid), ("FRONTEND", "alfa"),
        ("BACKEND", spec.backend), ("IBEAM", spec.beam_id),
        ("NRCVR", 1), ("FD_POLN", "LIN"),
        ("OBS_MODE", "SEARCH"), ("DATE-OBS", times.mjd_to_datestr(spec.mjd)),
        ("OBSFREQ", spec.fctr_mhz), ("OBSBW", spec.bw_mhz),
        ("OBSNCHAN", spec.nchan), ("CHAN_DM", 0.0),
        ("SRC_NAME", spec.source), ("TRK_MODE", "TRACK"),
        ("RA", spec.ra_str), ("DEC", spec.dec_str),
        ("BMIN", 0.05667), ("BMAJ", 0.05667),
        ("STT_IMJD", mjd_i), ("STT_SMJD", stt_smjd), ("STT_OFFS", stt_offs),
        ("STT_LST", times.lmst_seconds(spec.mjd, -66.7528)),
    ]:
        primary.set(k, v)

    subhdr_cards = dict(
        INT_TYPE="TIME", INT_UNIT="SEC", SCALE="FluxDen",
        NPOL=npol, POL_TYPE="AA+BB" if npol == 1 else "AABB",
        TBIN=spec.tsamp_s, NBIN=1, NBITS=spec.nbits,
        NCH_FILE=nchan, NCHAN=nchan, CHAN_BW=(freqs[1] - freqs[0]),
        NCHNOFFS=0, NSBLK=nsblk, NSUBOFFS=0,
        ZERO_OFF=0.0, SIGNINT=0, NUMIFS=1, BEAM=spec.beam_id,
    )
    # TDIM fastest axis is the packed channel byte count (nchan*nbits/8),
    # valid for 4-, 8- and 16-bit data alike.
    rowdt = _subint_dtype(spec)
    subhdr = fitscore.bintable_header(
        "SUBINT", np.zeros(0, dtype=rowdt),
        tdims={"DATA": (nsblk, npol, nchan * spec.nbits // 8)},
        **subhdr_cards)
    subhdr.set("NAXIS2", nsub, "number of rows")
    fitscore.write_fits(path, [fitscore.HDU(primary, None),
                               fitscore.HDU(subhdr, None)])

    def write_block(k: int) -> None:
        sub0 = k * rows // nsblk
        rec = _subint_rows(spec, sub0, block(k), freqs, scale, offset)
        os.pwrite(fd, rec.view(np.uint8), start + sub0 * rowdt.itemsize)

    start = os.path.getsize(path)
    nbytes = nsub * rowdt.itemsize
    fd = os.open(path, os.O_WRONLY)
    try:
        # the table zero-padded to a whole FITS block
        os.ftruncate(fd, start + nbytes + (-nbytes) % fitscore.BLOCK)
        with ThreadPoolExecutor(_workers()) as pool:
            list(pool.map(write_block, range(nblocks)))
    finally:
        os.close(fd)
    return path


def _workers() -> int:
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def mock_filename(spec: BeamSpec, subband: int | None = None) -> str:
    """PALFA filename conventions (reference: lib/python/datafile.py:398,514).

    subband None -> merged-Mock name '{projid}.{date}.{src}.b{beam}.{scan}.fits';
    else raw Mock '4bit-{projid}.{date}.{src}.b{beam}s{sb}g0.{scan}.fits'.
    """
    y, m, d = times.mjd_to_date(spec.mjd)
    date = f"{y:04d}{m:02d}{int(d):02d}"
    if subband is None:
        return f"{spec.projid}.{date}.{spec.source}.b{spec.beam_id}.{spec.scan:05d}.fits"
    return (f"4bit-{spec.projid}.{date}.{spec.source}."
            f"b{spec.beam_id}s{subband}g0.{spec.scan:05d}.fits")


def synth_beam(outdir: str, spec: BeamSpec | None = None,
               pulsars: list[PulsarSpec] = (), rfi: list[RFISpec] = (),
               merged: bool = True) -> list[str]:
    """Generate a synthetic beam on disk.

    merged=True  -> one merged-band file (MergedMock-style name).
    merged=False -> a Mock s0/s1 subband pair splitting the band, with
                    a small overlap region, to exercise subband merging.
    Returns the list of file paths written.
    """
    spec = spec or BeamSpec()
    os.makedirs(outdir, exist_ok=True)
    rows = block_rows(spec)
    if merged:
        path = os.path.join(outdir, mock_filename(spec))
        return [_write_psrfits(
            path, spec, rows,
            lambda k: _block(spec, pulsars, rfi, k, rows))]

    # Split into two overlapping halves like the Mock spectrometer:
    # s1 = low half, s0 = high half (PALFA convention), with overlap.
    overlap = max(2, spec.nchan // 16)
    half = spec.nchan // 2
    df = spec.bw_mhz / spec.nchan
    freqs = channel_freqs(spec)
    out = []
    for sb, sl in (("1", slice(0, half + overlap)),
                   ("0", slice(half - overlap, spec.nchan))):
        fsub = freqs[sl]
        subspec = dataclasses.replace(
            spec, nchan=len(fsub),
            fctr_mhz=float(fsub.mean()),
            bw_mhz=float(df * len(fsub)))
        path = os.path.join(outdir, mock_filename(spec, subband=int(sb)))
        _write_psrfits(
            path, subspec, rows,
            lambda k: _block(spec, pulsars, rfi, k, rows)[:, sl])
        out.append(path)
    return out
