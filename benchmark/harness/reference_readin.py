"""The plain reference of the read-in: what a 4-bit PSRFITS file says,
and the rfifind statistics of it.

It imports nothing of ``tpulsar`` and nothing of the harness's writer
(``psrfits4.py``): the file is parsed here from its own cards.

  * ``decode_psrfits4``: NumPy, float64.  Nibbles in file order (the
    earlier channel in a byte's high nibble), times ``DAT_SCL`` plus
    ``DAT_OFFS``, times ``DAT_WTS``, each row by its own columns, the
    band turned ascending.
  * ``cell_stats``: the statistics rfifind takes of every (interval,
    channel) cell — mean, standard deviation, largest Fourier power
    over the cell's variance — of the decoded samples mapped onto the
    quantised scale the program says its block has, ``(x - qoff) /
    qscale`` WITHOUT the rounding, in plain ``jax.numpy`` float32 a
    chunk of rows at a time (1.8 million transforms of 2048 samples:
    NumPy would take longer than the window).
  * ``robust_z`` / ``cell_zmax``: median and MAD z-scores of each
    statistic along time and along the band, NumPy float64.

``drop_low_bit`` is the control: every nibble with its low bit cleared,
the 3-bit file a later PR might be tempted to read.  ``swap_nibbles``
and ``file_band_order`` are the two decodes a reader most easily gets
wrong; the tests show the check failing each.
"""

from __future__ import annotations

import math

import numpy as np

CARD, BLOCK = 80, 2880
_CODES = {"D": ">f8", "E": ">f4", "B": ">u1", "J": ">i4", "I": ">i2"}


def _read_header(fh) -> dict:
    cards: dict = {}
    while True:
        block = fh.read(BLOCK)
        if len(block) < BLOCK:
            raise ValueError("truncated FITS header")
        for i in range(0, BLOCK, CARD):
            text = block[i:i + CARD].decode("ascii")
            key = text[:8].strip()
            if key == "END":
                return cards
            if text[8:10] != "= ":
                continue
            body = text[10:]
            if body.lstrip().startswith("'"):
                value = body.lstrip()[1:].split("'")[0].rstrip()
            else:
                word = body.split("/")[0].strip()
                if word in ("T", "F"):
                    value = word == "T"
                else:
                    try:
                        value = int(word)
                    except ValueError:
                        value = float(word)
            cards[key] = value


def open_psrfits4(path: str) -> dict:
    """The file's geometry and its table as a read-only memory map."""
    with open(path, "rb") as fh:
        _read_header(fh)                        # primary: no data
        sub = _read_header(fh)
        start = fh.tell()
    if sub.get("EXTNAME") != "SUBINT" or int(sub["NBITS"]) != 4 \
            or int(sub["NPOL"]) != 1:
        raise ValueError(f"{path}: not a one-polarisation 4-bit SUBINT "
                         "table")
    fields = []
    for n in range(1, int(sub["TFIELDS"]) + 1):
        form = str(sub[f"TFORM{n}"])
        repeat = int(form[:-1] or 1)
        fields.append((str(sub[f"TTYPE{n}"]), _CODES[form[-1]], (repeat,))
                      if repeat > 1 else
                      (str(sub[f"TTYPE{n}"]), _CODES[form[-1]]))
    rowdt = np.dtype(fields)
    if rowdt.itemsize != int(sub["NAXIS1"]):
        raise ValueError("row width disagrees with NAXIS1")
    table = np.memmap(path, dtype=rowdt, mode="r", offset=start,
                      shape=(int(sub["NAXIS2"]),))
    return {"nchan": int(sub["NCHAN"]), "nsblk": int(sub["NSBLK"]),
            "nrows": int(sub["NAXIS2"]), "dt": float(sub["TBIN"]),
            "table": table}


def _row_affine(f: dict, r: int):
    """(a, b) of row r in FILE channel order, float64: a sample's
    calibrated value is nibble * a + b."""
    scl, offs, wts = (np.asarray(f["table"][col][r], np.float64)
                      for col in ("DAT_SCL", "DAT_OFFS", "DAT_WTS"))
    return scl * wts, offs * wts


def _band_descends(f: dict) -> bool:
    freqs = f["table"]["DAT_FREQ"][0]
    return bool(freqs[0] > freqs[-1])


def _nibbles(raw: np.ndarray, drop_low_bit: bool, swap_nibbles: bool):
    """(n, nchan/2) bytes -> (n, nchan) nibbles in file order."""
    first, second = raw >> 4, raw & 0x0F
    if swap_nibbles:
        first, second = second, first
    out = np.empty((raw.shape[0], raw.shape[1] * 2), np.uint8)
    out[:, 0::2], out[:, 1::2] = first, second
    if drop_low_bit:
        out &= 0x0E
    return out


def decode_psrfits4(f: dict, t0: int, t1: int, *, drop_low_bit=False,
                    swap_nibbles=False, file_band_order=False):
    """Samples [t0, t1) of every channel, (nchan, t1 - t0) float64,
    ascending frequency."""
    nchan, nsblk = f["nchan"], f["nsblk"]
    desc = _band_descends(f)
    out = np.empty((nchan, t1 - t0), np.float64)
    for r in range(t0 // nsblk, -(-t1 // nsblk)):
        lo, hi = max(t0, r * nsblk), min(t1, (r + 1) * nsblk)
        raw = np.asarray(f["table"]["DATA"][r]).reshape(nsblk, nchan // 2)
        nib = _nibbles(raw[lo - r * nsblk: hi - r * nsblk],
                       drop_low_bit, swap_nibbles)
        a, b = _row_affine(f, r)
        x = nib.astype(np.float64) * a + b
        if desc and not file_band_order:
            x = x[:, ::-1]
        out[:, lo - t0: hi - t0] = x.T
    return out


# ------------------------------------------------------ cell statistics

def _stats_chunk(raw, a, b, qscale, qoff, block_len: int,
                 drop_low_bit: bool):
    """raw (rows, nsblk, nchan/2) uint8; a, b (2, rows, nchan/2) and
    qscale, qoff (2, nchan/2): the file's even and odd channels (a
    byte's high and low nibble) -> mean, std, maxpow, each (2, rows *
    nsblk / block_len, nchan/2).  The nibbles are taken as int32 and
    the two planes never interleaved on the device: the TPU compiler
    was seen to read whole bytes out of a fused uint8 shift, mask,
    stack and reshape (my chip runs, PR 40)."""
    import jax.numpy as jnp

    rows, nsblk, half = raw.shape
    word = raw.astype(jnp.int32)
    out = []
    for k, nib in enumerate(((word >> 4) & 0x0F, word & 0x0F)):
        if drop_low_bit:
            nib = nib & 0x0E
        x = nib.astype(jnp.float32) * a[k][:, None, :] + b[k][:, None, :]
        x = ((x - qoff[k]) / qscale[k]).reshape(rows * nsblk, half)
        ncell = rows * nsblk // block_len   # a ragged tail is no cell
        x = x[: ncell * block_len].reshape(ncell, block_len, half)
        mean = x.mean(axis=1)
        d = x - mean[:, None, :]
        var = (d * d).mean(axis=1)
        spec = jnp.fft.rfft(d, axis=1)[:, 1:, :]
        power = spec.real ** 2 + spec.imag ** 2
        maxpow = power.max(axis=1) / jnp.maximum(block_len * var, 1e-9)
        out.append((mean, jnp.sqrt(var), maxpow))
    return tuple(jnp.stack([o[s] for o in out]) for s in range(3))


def cell_stats(f: dict, qscale, qoff, block_len: int, *,
               drop_low_bit: bool = False, rows_per_chunk: int = 16):
    """(mean, std, maxpow), each (nint, nchan) float64 on the host and
    ascending in frequency, of the whole file on the program's
    quantised scale (`qscale`, `qoff` ascending too)."""
    import jax
    import jax.numpy as jnp

    nchan, nsblk, nrows = f["nchan"], f["nsblk"], f["nrows"]
    # every chunk but the last holds whole cells
    step = math.lcm(nsblk, block_len) // nsblk
    rows_per_chunk = max(step, rows_per_chunk // step * step)
    table = f["table"]
    desc = _band_descends(f)

    def planes(v):          # (..., nchan) in file order -> (2, ..., nchan/2)
        v = np.asarray(v, np.float32)
        return jnp.asarray(np.stack([v[..., 0::2], v[..., 1::2]]))

    fn = jax.jit(_stats_chunk, static_argnames=("block_len",
                                                "drop_low_bit"))
    qs, qo = (planes(np.asarray(v)[::-1] if desc else v)
              for v in (qscale, qoff))
    parts = []
    for r0 in range(0, nrows, rows_per_chunk):
        r1 = min(nrows, r0 + rows_per_chunk)
        raw = np.ascontiguousarray(table["DATA"][r0:r1]).reshape(
            r1 - r0, nsblk, nchan // 2)
        ab = [_row_affine(f, r) for r in range(r0, r1)]
        parts.append(fn(jnp.asarray(raw),
                        planes(np.stack([p[0] for p in ab])),
                        planes(np.stack([p[1] for p in ab])), qs, qo,
                        block_len=block_len, drop_low_bit=drop_low_bit))
    parts = jax.device_get(parts)
    out = []
    for k in range(3):
        both = np.concatenate([p[k] for p in parts], axis=1)
        stat = np.empty((both.shape[1], nchan), np.float64)
        stat[:, 0::2], stat[:, 1::2] = both[0], both[1]
        out.append(stat[:, ::-1] if desc else stat)
    return tuple(out)


def robust_z(x: np.ndarray, axis: int) -> np.ndarray:
    """z-scores from the median and the median absolute deviation."""
    med = np.median(x, axis=axis, keepdims=True)
    mad = np.median(np.abs(x - med), axis=axis, keepdims=True)
    return (x - med) / np.maximum(1.4826 * mad, 1e-9)


def cell_zmax(mean, std, maxpow) -> np.ndarray:
    """The largest |z| of a cell over the three statistics, each
    standardised along time (a burst against its channel's history)
    and along the band (a channel against the band in that interval):
    rfifind flags the cell where it passes the threshold."""
    return np.max([np.abs(robust_z(s, axis=ax))
                   for s in (mean, std, maxpow) for ax in (0, 1)], axis=0)


# ------------------------------------------------------- the candidates

def read_accelcands(path: str) -> list:
    """The candidate rows of a PRESTO-style ``.accelcands`` list (the
    columns its header line names), as objects with the fields the
    harness's pulsar look takes."""
    import types

    out = []
    with open(path) as fh:
        for line in fh:
            w = line.split()
            if line.startswith("#") or len(w) != 9 or "=" in line:
                continue
            out.append(types.SimpleNamespace(
                sigma=float(w[1]), numharm=int(w[2]), power=float(w[3]),
                dm=float(w[4]), r=float(w[5]), z=float(w[6]),
                period_s=float(w[7]) / 1e3, freq_hz=float(w[8])))
    return out
