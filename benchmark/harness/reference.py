"""The plain reference the benchmark holds the program to.

Frozen copies of the repo's own plain definitions, written out in
``jax.numpy`` float32 (matmul precision "highest") and NumPy float64:
the integer shift rule of the two-stage dedispersion, the mean pad and
block-median whitening of the spectrum, interbinning, the harmonic
sums, the z-response template of the acceleration search, and the
single-pulse detrend and boxcar.  It imports nothing of ``tpulsar``,
uses no Pallas kernel and no batching, and takes nothing the program
made: only the masked input block, the observation's geometry and the
coordinates of the answers it is asked about.

Everything here is a POINT evaluation: one DM trial is dedispersed,
its spectrum whitened, and the power read at a candidate's own
(r, z, numharm) — never a search plane.

``precision="lower"`` is the control: the same chain with the float32
parts rounded to bfloat16 and the acceleration plane's powers rounded
to float8 (e4m3), the nearest precisions below the ones the
configuration states.  A sound check has to fail it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: dispersion constant, MHz^2 s per (pc cm^-3) (tpulsar/constants.py)
KDM = 1.0 / 2.41e-4
MAX_WHITEN_BLOCK = 8192
SP_DETREND_BLOCK = 1000


def _bf16(x):
    """Round float32 to bfloat16's 8 exponent and 7 mantissa bits.  (A
    convert to bfloat16 and back is removed by the TPU compiler as excess
    precision; `reduce_precision` is not.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# ------------------------------------------------------- padded length

def choose_n(n: int, factors=(2, 3, 5, 7), multiple_of: int = 64) -> int:
    """Smallest length >= n that is a product of small primes and a
    multiple of 64 — the length every dedispersed series is padded to
    before its FFT (PRESTO's choose_N; plan/ddplan.py choose_n)."""
    if n <= multiple_of:
        return multiple_of
    target = -(-n // multiple_of)
    best = None
    stack = [(1, 0)]
    while stack:
        prod, i = stack.pop()
        if prod >= target:
            if best is None or prod < best:
                best = prod
            continue
        for j in range(i, len(factors)):
            nxt = prod * factors[j]
            if best is None or nxt < best or nxt >= target:
                stack.append((nxt, j))
    return best * multiple_of


# ------------------------------------------------------------ shift rule

def pass_shifts(freqs_mhz, nsub: int, subdm: float, dms, dt: float,
                downsamp: int):
    """(chan_shifts[nchan], sub_shifts[ndms, nsub]) of one pass: each
    channel's integer delay at the pass's sub-DM relative to its own
    subband's top channel, and each subband's integer delay at each
    DM relative to the band's top channel, at the downsampled rate
    (kernels/dedisperse.py plan_pass_shifts)."""
    f = np.asarray(freqs_mhz, np.float64)
    nchan = len(f)
    subrefs = f.reshape(nsub, nchan // nsub)[:, -1]
    chan_ref = np.repeat(subrefs, nchan // nsub)
    chan_shifts = np.round(
        KDM * subdm * (f ** -2.0 - chan_ref ** -2.0) / dt).astype(np.int32)
    dms = np.atleast_1d(np.asarray(dms, np.float64))
    sub_shifts = np.stack([
        np.round(KDM * dm * (subrefs ** -2.0 - f[-1] ** -2.0)
                 / (dt * downsamp)).astype(np.int32) for dm in dms])
    return chan_shifts, sub_shifts


@partial(jax.jit, static_argnames=("nrows", "pad", "downsamp"))
def _shift_sum(block, row0, shifts, nrows: int, pad: int, downsamp: int):
    """sum over rows i of block[row0:row0+nrows] of
    row_i[min(t + shifts[i], T-1)], then sum-downsampled (a length the
    factor does not divide is truncated)."""
    T = block.shape[1]
    rows = jax.lax.dynamic_slice_in_dim(block, row0, nrows, axis=0)
    tail = jnp.broadcast_to(rows[:, -1:], (nrows, pad))
    padded = jnp.concatenate([rows, tail], axis=1)

    def body(i, acc):
        row = jax.lax.dynamic_index_in_dim(padded, i, 0, keepdims=False)
        sl = jax.lax.dynamic_slice_in_dim(row, shifts[i], T)
        return acc + sl.astype(jnp.float32)

    acc = jax.lax.fori_loop(0, nrows, body, jnp.zeros((T,), jnp.float32))
    if downsamp > 1:
        n = (T // downsamp) * downsamp
        acc = jax.lax.reduce_window(acc[:n], 0.0, jax.lax.add,
                                    (downsamp,), (downsamp,), "VALID")
    return acc


def form_subbands(data, chan_shifts, nsub: int, downsamp: int):
    """Stage 1: (nchan, T) block -> (nsub, T // downsamp) float32."""
    cps = data.shape[0] // nsub
    pad = max(1, int(np.max(chan_shifts)))
    shifts = np.asarray(chan_shifts, np.int32).reshape(nsub, cps)
    return jnp.stack([
        _shift_sum(data, s * cps, jnp.asarray(shifts[s]), cps, pad,
                   downsamp) for s in range(nsub)])


def dedisperse_one(subb, sub_shifts_dm):
    """Stage 2 for ONE trial: (nsub, T') -> (T',) float32."""
    pad = max(1, int(np.max(sub_shifts_dm)))
    return _shift_sum(subb, 0, jnp.asarray(sub_shifts_dm, jnp.int32),
                      subb.shape[0], pad, 1)


# -------------------------------------------------------------- spectrum

def _block_edges(nbins: int, first_block: int = 6, growth: float = 1.5):
    edges = [1]
    size = first_block
    while edges[-1] < nbins and size < MAX_WHITEN_BLOCK:
        edges.append(min(nbins, edges[-1] + int(size)))
        size = size * growth
    return tuple(int(e) for e in edges)


@partial(jax.jit, static_argnames=("nfft", "edges", "lower"))
def _whitened_spectrum(series, nfft: int, edges: tuple, lower: bool):
    """mean-pad -> rfft -> zero DC -> divide by the block-median noise
    level, linearly interpolated between block centres.  Returns the
    whitened spectrum as (real, imag) float32."""
    T = series.shape[0]
    x = series.astype(jnp.float32)
    if lower:
        x = _bf16(x)
    if T > nfft:
        x = x[:nfft]
    elif T < nfft:
        x = jnp.concatenate([x, jnp.full((nfft - T,), jnp.mean(x))])
    spec = jnp.fft.rfft(x)
    spec = spec.at[0].set(0.0)
    if lower:
        spec = jax.lax.complex(_bf16(spec.real), _bf16(spec.imag))
    powers = spec.real ** 2 + spec.imag ** 2
    nbins = powers.shape[0]
    ln2 = float(np.log(2.0))
    centers, levels = [], []
    for lo, hi in zip(edges[:-1], edges[1:]):
        centers.append(0.5 * (lo + hi))
        levels.append(jnp.median(powers[lo:hi])[None] / ln2)
    tail_start = edges[-1]
    m = (nbins - tail_start) // MAX_WHITEN_BLOCK
    if m > 0:
        tail = powers[tail_start: tail_start + m * MAX_WHITEN_BLOCK]
        levels.append(jnp.median(
            tail.reshape(m, MAX_WHITEN_BLOCK), axis=-1) / ln2)
        centers.extend(tail_start + (j + 0.5) * MAX_WHITEN_BLOCK
                       for j in range(m))
    rem = nbins - tail_start - m * MAX_WHITEN_BLOCK
    if rem > 16:
        lo = nbins - rem
        centers.append(0.5 * (lo + nbins))
        levels.append(jnp.median(powers[lo:])[None] / ln2)
    level = jnp.maximum(jnp.concatenate(levels), 1e-30)
    bins = jnp.arange(nbins, dtype=jnp.float32)
    level_at = jnp.interp(bins, jnp.asarray(centers, jnp.float32), level)
    scale = jnp.sqrt((powers / level_at) / jnp.maximum(powers, 1e-30))
    wre, wim = spec.real * scale, spec.imag * scale
    if lower:
        wre, wim = _bf16(wre), _bf16(wim)
    return wre, wim


def whitened_spectrum(series, nfft: int, lower: bool = False):
    """Host complex128 whitened spectrum of one dedispersed series
    (fetched as two float32 planes)."""
    nbins = nfft // 2 + 1
    with jax.default_matmul_precision("highest"):
        wre, wim = _whitened_spectrum(series, nfft, _block_edges(nbins),
                                      lower)
    wre, wim = jax.device_get((wre, wim))
    return np.asarray(wre, np.float64) + 1j * np.asarray(wim, np.float64)


# ------------------------------------------------------------- lo stage

def interbin_power(X: np.ndarray, q) -> np.ndarray:
    """Power on the half-bin grid at index q (r = q / 2): |X_k|^2 on
    the bins, (pi^2/16) |X_k - X_{k+1}|^2 between them."""
    q = np.asarray(q, np.int64)
    k = np.clip(q // 2, 0, len(X) - 1)
    k1 = np.clip(k + 1, 0, len(X) - 1)
    on = np.abs(X[k]) ** 2
    between = (np.pi ** 2 / 16.0) * np.abs(X[k] - X[k1]) ** 2
    between = np.where(k + 1 < len(X), between, 0.0)
    return np.where(q % 2 == 0, on, between)


def lo_power(X: np.ndarray, q: int, numharm: int) -> float:
    """Incoherent harmonic sum at half-bin index q: sum_h P(h q)."""
    hs = np.arange(1, numharm + 1)
    return float(np.sum(interbin_power(X, hs * int(q))))


def lo_stage_best(X: np.ndarray, stages=(1, 2, 4, 8, 16)) -> dict:
    """{numharm: (q, power)} of the strongest zero-drift candidate of
    each harmonic stage over the whole half-bin grid, r >= 1."""
    p2 = interbin_power(X, np.arange(2 * len(X)))
    out = {}
    for h in stages:
        L = len(p2) // h
        acc = p2[:L].copy()
        for hh in range(2, h + 1):
            acc += p2[::hh][:L]
        acc[:2] = -np.inf
        q = int(np.argmax(acc))
        out[h] = (q, float(acc[q]))
    return out


# ------------------------------------------------------------- hi stage

def template_width(zmax: float) -> int:
    w = int(2 * np.ceil(abs(zmax) / 2) + 32)
    return int(2 ** np.ceil(np.log2(w)))


def z_response(z: float, width: int) -> np.ndarray:
    """Half-bin-sampled response of a unit tone drifting z bins: the
    DFT of a discrete chirp, 2*width samples over `width` bins, centred
    on the tone's MEAN frequency (kernels/accel.py gen_z_response with
    numbetween=2), in complex128."""
    N = 1 << 14
    c = N // 4
    n = np.arange(N)
    chirp = np.exp(2j * np.pi * (c * n / N + 0.5 * z * (n / N) ** 2))
    spec = np.fft.fft(chirp, 2 * N) / N
    center = int(round(2 * (c + z / 2)))
    lo = center - width
    return spec[lo: lo + 2 * width]


class HiStage:
    """Point evaluation of the acceleration search's harmonic-summed
    matched-filter power on the half-bin grid."""

    def __init__(self, zmax: float, lower: bool = False):
        self.zmax = float(zmax)
        self.width = template_width(zmax)
        self.lower = lower
        self._resp: dict[float, np.ndarray] = {}

    def _template(self, z: float) -> np.ndarray:
        if z not in self._resp:
            self._resp[z] = z_response(z, self.width)
        return self._resp[z]

    def plane_power(self, X: np.ndarray, q: int, z: float) -> float:
        """|sum_m S2[q - width + m] conj(resp_z[m])|^2 with S2 the
        spectrum zero-interleaved onto the half-bin grid.  The searched
        plane starts where the template first fits: below half-bin
        index `width` its power is 0 by definition."""
        w = self.width
        if q < w:
            return 0.0
        j = q - w + np.arange(2 * w)
        even = (j % 2 == 0) & (j >= 0) & (j // 2 < len(X))
        vals = np.where(even, X[np.clip(j // 2, 0, len(X) - 1)], 0.0)
        p = float(np.abs(np.sum(vals * np.conj(self._template(z)))) ** 2)
        if self.lower and p > 0.0:
            # float8 e4m3: four significant bits (the range is the
            # scale's affair, the precision is what a plane would lose)
            m, e = math.frexp(p)
            p = math.ldexp(round(m * 16.0) / 16.0, e)
        return p

    def power(self, X: np.ndarray, q: int, z: float, numharm: int) -> float:
        """sum_h P(h q, clip(h z)) — harmonic h of a signal at (r, z)
        sits at (h r, h z); z is clamped to the searched grid."""
        return sum(
            self.plane_power(X, h * int(q),
                             float(np.clip(h * z, -self.zmax, self.zmax)))
            for h in range(1, numharm + 1))


# ---------------------------------------------------------- single pulse

@partial(jax.jit, static_argnames=("lower",))
def _normalize_series(series, lower: bool):
    """Block-median (1000 samples; a shorter tail by its own median)
    baseline removed, scaled to unit standard deviation."""
    x = series.astype(jnp.float32)
    if lower:
        x = _bf16(x)
    T = x.shape[0]
    blk = min(SP_DETREND_BLOCK, T)
    nblk = max(1, T // blk)
    usable = nblk * blk
    med = jnp.median(x[:usable].reshape(nblk, blk), axis=-1)
    base = jnp.repeat(med, blk)
    if T > usable:
        base = jnp.concatenate(
            [base, jnp.full((T - usable,), jnp.median(x[usable:]))])
    d = x - base
    return d / jnp.maximum(jnp.std(d), 1e-9)


def normalized_series(series, lower: bool = False) -> np.ndarray:
    return np.asarray(jax.device_get(_normalize_series(series, lower)),
                      np.float64)


def boxcar_snr(norm: np.ndarray, sample: int, width: int) -> float:
    """sum of `width` normalised samples from `sample`, over sqrt(width)."""
    return float(np.sum(norm[sample: sample + width]) / np.sqrt(width))
