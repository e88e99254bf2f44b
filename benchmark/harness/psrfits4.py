"""The harness's own 4-bit PSRFITS file: the beam as the telescope's
pipeline leaves it for a read-in cell.

A search-mode PSRFITS file is a primary header and one SUBINT binary
table.  Each table row holds ``NSBLK`` spectra of ``NCHAN`` samples,
two 4-bit samples a byte with the EARLIER channel in the high nibble,
and that row's per-channel ``DAT_FREQ``, ``DAT_WTS``, ``DAT_OFFS`` and
``DAT_SCL``: a sample's calibrated value is ``(nibble * DAT_SCL +
DAT_OFFS) * DAT_WTS``.  The Mock spectrometer writes its band
descending, so file channel j is the block's channel nchan - 1 - j.

Written here from the FITS standard's card and table layout alone, and
importing nothing of ``tpulsar``: the program's reader is held to a
file that its own writer (``tpulsar/io/synth.py``) did not make.  The
samples come from the device, where the cell's block is made: packed
there, fetched a time chunk at a time, and written row by row.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import numpy as np

CARD, BLOCK = 80, 2880

#: what `generate._gen_block_chunk` draws a clean channel from, in
#: 4-bit quanta (its literals 8.0 and 2.0)
NOISE_MEAN, NOISE_SIGMA = 8.0, 2.0

#: the merged-Mock file name the survey's pipeline leaves
#: (<projid>.<date>.<source>.b<beam>.<scan>.fits)
FILE_NAME = "P2030.20100101.G4500+00.b0.00001.fits"


@dataclasses.dataclass(frozen=True)
class Beam4:
    """What the file states, channels in ASCENDING frequency."""
    nchan: int
    nsamp: int
    nsblk: int
    dt: float
    fctr_mhz: float
    bw_mhz: float
    scl: np.ndarray          # (nchan,) float32 DAT_SCL
    offs: np.ndarray         # (nchan,) float32 DAT_OFFS
    wts: np.ndarray          # (nchan,) float32 DAT_WTS

    @property
    def nrows(self) -> int:
        return self.nsamp // self.nsblk

    @property
    def freqs(self) -> np.ndarray:
        df = self.bw_mhz / self.nchan
        return (self.fctr_mhz - self.bw_mhz / 2) + (np.arange(self.nchan)
                                                    + 0.5) * df


def draw_calibration(seed: int, nchan: int, spec: dict):
    """(scl, offs, wts) of this seed, (nchan,) float32 each: every
    channel's scale and offset drawn uniformly inside the traffic
    file's ranges (constant over the rows), every weight as it states."""
    rng = np.random.default_rng([int(seed), 4])
    scl = rng.uniform(*spec["dat_scl"], nchan).astype(np.float32)
    offs = rng.uniform(*spec["dat_offs"], nchan).astype(np.float32)
    wts = np.full(nchan, float(spec["dat_wts"]), np.float32)
    return scl, offs, wts


# ------------------------------------------------------------ the RFI

def draw_rfi(seed: int, nchan: int, nint: int) -> dict:
    """Which channel and which mask interval carry this seed's RFI."""
    rng = np.random.default_rng([int(seed), 5])
    return {"channel": int(rng.integers(0, nchan)),
            "interval": int(rng.integers(0, nint))}


def _overlay(block, key, chan, t0, n: int, sigma: float, raise_q: int):
    import jax
    import jax.numpy as jnp

    T = block.shape[1]
    row = NOISE_MEAN + sigma * jax.random.normal(key, (T,), jnp.float32)
    row = jnp.clip(jnp.round(row), 0, 15).astype(block.dtype)
    block = jax.lax.dynamic_update_slice(block, row[None, :], (chan, 0))
    cols = jax.lax.dynamic_slice(block, (0, t0), (block.shape[0], n))
    cols = jnp.minimum(cols + raise_q, 15).astype(block.dtype)
    return jax.lax.dynamic_update_slice(block, cols, (0, t0))


def rfi_overlay(block, seed: int, spec: dict, interval_len: int):
    """The block with this seed's interference, made on the device in
    place: one channel redrawn as noise of `channel_sigma_factor`
    times the clean sigma over the whole beam (a persistent broadband
    -noisy channel), and one mask interval with every channel raised
    by `interval_raise_quanta` (a broadband burst).  -> (block, where)."""
    import jax

    nchan, T = block.shape
    where = draw_rfi(seed, nchan, T // interval_len)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             (int(seed) >> 31) + 7)
    overlay = partial(jax.jit, donate_argnums=0,
                      static_argnames=("n", "sigma", "raise_q"))(_overlay)
    block = overlay(block, key, where["channel"],
                    where["interval"] * interval_len, n=interval_len,
                    sigma=float(spec["channel_sigma_factor"]) * NOISE_SIGMA,
                    raise_q=int(spec["interval_raise_quanta"]))
    return block, where


# ----------------------------------------------------------- the file

def _card(key: str, value, comment: str = "") -> bytes:
    if isinstance(value, bool):
        body = ("T" if value else "F").rjust(20)
    elif isinstance(value, (int, np.integer)):
        body = str(int(value)).rjust(20)
    elif isinstance(value, (float, np.floating)):
        body = repr(float(value)).upper().rjust(20)
    else:
        body = ("'" + str(value).ljust(8) + "'").ljust(20)
    text = f"{key:<8}= {body}"
    if comment:
        text += f" / {comment}"
    if len(text) > CARD and not comment:
        raise ValueError(f"card {key} does not fit: {value!r}")
    return text[:CARD].ljust(CARD).encode("ascii")


def _header(cards: list[tuple]) -> bytes:
    buf = b"".join(_card(*c) for c in cards) + b"END".ljust(CARD)
    return buf + b" " * ((-len(buf)) % BLOCK)


def row_dtype(nchan: int, nsblk: int) -> np.dtype:
    return np.dtype([
        ("TSUBINT", ">f8"), ("OFFS_SUB", ">f8"),
        ("TEL_AZ", ">f4"), ("TEL_ZEN", ">f4"),
        ("DAT_FREQ", ">f8", (nchan,)), ("DAT_WTS", ">f4", (nchan,)),
        ("DAT_OFFS", ">f4", (nchan,)), ("DAT_SCL", ">f4", (nchan,)),
        ("DATA", ">u1", (nsblk * nchan // 2,))])


_TFORM = {"f8": "D", "f4": "E", "u1": "B"}


def _headers(beam: Beam4, rowdt: np.dtype) -> bytes:
    primary = [
        ("SIMPLE", True, "file conforms to FITS standard"),
        ("BITPIX", 8), ("NAXIS", 0), ("EXTEND", True),
        ("FITSTYPE", "PSRFITS"), ("HDRVER", "3.4"),
        ("TELESCOP", "Arecibo"), ("OBSERVER", "benchmark"),
        ("PROJID", "P2030"), ("FRONTEND", "alfa"), ("BACKEND", "pdev"),
        ("IBEAM", 0), ("NRCVR", 1), ("FD_POLN", "LIN"),
        ("OBS_MODE", "SEARCH"), ("DATE-OBS", "2010-01-01T00:00:00"),
        ("OBSFREQ", float(beam.fctr_mhz)), ("OBSBW", float(beam.bw_mhz)),
        ("OBSNCHAN", beam.nchan), ("CHAN_DM", 0.0),
        ("SRC_NAME", "G4500+00"), ("TRK_MODE", "TRACK"),
        ("RA", "19:00:00.0"), ("DEC", "+10:00:00.0"),
        ("BMIN", 0.05667), ("BMAJ", 0.05667),
        ("STT_IMJD", 55197), ("STT_SMJD", 0), ("STT_OFFS", 0.0),
        ("STT_LST", 0.0)]
    table = [
        ("XTENSION", "BINTABLE", "binary table extension"),
        ("BITPIX", 8), ("NAXIS", 2), ("NAXIS1", rowdt.itemsize),
        ("NAXIS2", beam.nrows), ("PCOUNT", 0), ("GCOUNT", 1),
        ("TFIELDS", len(rowdt.names))]
    for n, name in enumerate(rowdt.names, start=1):
        base, shape = rowdt[name].base, rowdt[name].shape
        repeat = int(np.prod(shape)) if shape else 1
        code = _TFORM[base.str[1:]]
        table += [(f"TTYPE{n}", name),
                  (f"TFORM{n}", f"{repeat}{code}" if repeat > 1 else code)]
        if name == "DATA":
            table.append((f"TDIM{n}",
                          f"({beam.nchan // 2},1,{beam.nsblk})"))
    df = beam.bw_mhz / beam.nchan
    table += [
        ("EXTNAME", "SUBINT"), ("INT_TYPE", "TIME"), ("INT_UNIT", "SEC"),
        ("SCALE", "FluxDen"), ("NPOL", 1), ("POL_TYPE", "AA+BB"),
        ("TBIN", float(beam.dt)), ("NBIN", 1), ("NBITS", 4),
        ("NCH_FILE", beam.nchan), ("NCHAN", beam.nchan),
        ("CHAN_BW", -df), ("NCHNOFFS", 0), ("NSBLK", beam.nsblk),
        ("NSUBOFFS", 0), ("ZERO_OFF", 0.0), ("SIGNINT", 0),
        ("NUMIFS", 1), ("BEAM", 0)]
    return _header(primary) + _header(table)


def _pack_chunk(block, t0, n: int):
    """Samples [t0, t0 + n) of the (nchan, T) block in the file's
    order: time-major, channels descending, two a byte."""
    import jax

    x = jax.lax.dynamic_slice(block, (0, t0), (block.shape[0], n))
    x = x[::-1, :].T                      # (n, nchan), band descending
    return (x[:, 0::2] << 4) | (x[:, 1::2] & 0x0F)


def write_beam(path: str, beam: Beam4, block, rows_per_chunk: int = 64):
    """Write `block` ((nchan, nsamp) uint8 on the device, values 0-15,
    ascending frequency) as the file `beam` describes."""
    import jax

    pack = partial(jax.jit, static_argnames=("n",))(_pack_chunk)
    rowdt = row_dtype(beam.nchan, beam.nsblk)
    head = _headers(beam, rowdt)
    tsub = beam.nsblk * beam.dt
    rows = np.zeros(min(rows_per_chunk, beam.nrows), rowdt)
    rows["TSUBINT"] = tsub
    rows["TEL_AZ"], rows["TEL_ZEN"] = 180.0, 10.0
    rows["DAT_FREQ"] = beam.freqs[::-1]
    rows["DAT_WTS"] = beam.wts[::-1]
    rows["DAT_OFFS"] = beam.offs[::-1]
    rows["DAT_SCL"] = beam.scl[::-1]
    nbytes = beam.nrows * rowdt.itemsize
    with open(path, "wb") as fh:
        fh.write(head)
        for r0 in range(0, beam.nrows, len(rows)):
            n = min(len(rows), beam.nrows - r0)
            packed = np.asarray(pack(block, r0 * beam.nsblk,
                                     n=n * beam.nsblk))
            rows["OFFS_SUB"][:n] = (r0 + np.arange(n) + 0.5) * tsub
            rows["DATA"][:n] = packed.reshape(n, -1)
            fh.write(rows[:n].view(np.uint8))
        fh.write(b"\x00" * ((-nbytes) % BLOCK))
    return os.path.getsize(path)
