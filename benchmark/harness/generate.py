"""Inputs from the seed: the injected pulsar's parameters and the
uint8 block on the device.

A copy of ``bench.py``'s on-device synthesizer (noise + one injected
pulsar, quantized to the 4-bit range and held as uint8), kept here so
that no later PR can change what the benchmark feeds the program.  Two
things differ from the original: the pulsar's period, DM, duty cycle
and Fourier drift are drawn from ``--seed`` inside the ranges the
traffic file gives, and the phase carries a quadratic term — a
constant frequency derivative, the way ``io/synth.PulsarSpec.pdot``
models a drifting pulsar on the host.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

KDM = 1.0 / 2.41e-4


@dataclasses.dataclass(frozen=True)
class Pulsar:
    period_s: float
    dm: float
    duty: float        # Gaussian pulse sigma, in turns
    z: float           # Fourier drift over the padded span, in bins
    amp: float         # pulse peak, in units of the 4-bit quantum

    def fdot(self, T_s: float) -> float:
        """Frequency derivative (Hz/s) that drifts z bins over T_s."""
        return self.z / (T_s * T_s)

    def mean_freq_hz(self, T_s: float) -> float:
        """The tone's mean frequency over the span, the coordinate the
        search reports a drifting candidate at."""
        return 1.0 / self.period_s + 0.5 * self.fdot(T_s) * T_s


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def draw_pulsar(seed: int, ranges: dict,
                first_pass: tuple[float, float] | None = None,
                span_s: float | None = None) -> Pulsar:
    """The injected pulsar of this seed.  Every seed gives the same
    amount of work — one pulsar, the same shapes — at other
    parameters: period log-uniform, DM, duty and |z| uniform, the
    drift's sign a coin toss.  The DM's range is absolute (``dm``) or
    a share of the slice's first pass (``dm_frac_of_first_pass``, with
    `first_pass` = that pass's lowest DM and the next pass's), so that
    one mix puts the pulsar inside the first pass of any plan.  With
    ``snap_to_fourier_grid`` the period drawn is moved, by under a
    bin, so that the tone's mean frequency over `span_s` (the padded
    series the search transforms) is a whole number of Fourier bins:
    every harmonic then lies on the search's grid, and the search
    finds the same harmonics at every seed."""
    rng = seed_rng(seed)
    p_lo, p_hi = ranges["period_s"]
    period = float(np.exp(rng.uniform(np.log(p_lo), np.log(p_hi))))
    if "dm" in ranges:
        dm = float(rng.uniform(*ranges["dm"]))
    else:
        lo, hi = first_pass
        dm = lo + float(rng.uniform(*ranges["dm_frac_of_first_pass"])) \
            * (hi - lo)
    duty = float(rng.uniform(*ranges["duty"]))
    z_lo, z_hi = ranges["abs_z"]
    z = float(rng.uniform(z_lo, z_hi))
    if z and rng.random() < 0.5:
        z = -z
    if ranges.get("snap_to_fourier_grid"):
        # mean frequency r / span; the tone starts z / 2 bins under it
        period = span_s / (round(span_s / period) - 0.5 * z)
    return Pulsar(period_s=period, dm=dm, duty=duty, z=z,
                  amp=float(ranges["amp"]))


def channel_freqs(fctr: float, bw: float, nchan: int) -> np.ndarray:
    return (fctr - bw / 2) + (np.arange(nchan) + 0.5) * (bw / nchan)


def _gen_block_chunk(key, delay_chunk, f0, fdot, duty, amp, dt,
                     n: int, nc: int):
    import jax
    import jax.numpy as jnp

    t = jnp.arange(n, dtype=jnp.float32) * dt
    noise = 8.0 + 2.0 * jax.random.normal(key, (nc, n), jnp.float32)
    tau = t[None, :] - delay_chunk[:, None]
    phase = (tau * f0 + 0.5 * fdot * tau * tau) % 1.0
    dph = jnp.minimum(phase, 1.0 - phase)
    x = noise + amp * jnp.exp(-0.5 * (dph / duty) ** 2)
    return jnp.clip(jnp.round(x), 0, 15).astype(jnp.uint8)


def make_block(seed: int, psr: Pulsar, freqs: np.ndarray, dt: float,
               nsamp: int, T_s: float, chan_chunk: int = 120):
    """(nchan, nsamp) uint8 on the device, made there in channel
    chunks of one compiled program."""
    import jax
    import jax.numpy as jnp

    nchan = len(freqs)
    delays = (KDM * psr.dm * (freqs ** -2.0 - freqs[-1] ** -2.0)
              ).astype(np.float32)
    gen = partial(jax.jit, static_argnames=("n", "nc"))(_gen_block_chunk)
    # --seed runs past 2**31: fold the high bits in separately
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    scal = [jnp.float32(v) for v in (1.0 / psr.period_s, psr.fdot(T_s),
                                     psr.duty, psr.amp, dt)]
    parts = []
    for c0 in range(0, nchan, chan_chunk):
        nc = min(chan_chunk, nchan - c0)
        key, sub = jax.random.split(key)
        parts.append(gen(sub, jnp.asarray(delays[c0:c0 + nc]), *scal,
                         n=nsamp, nc=nc))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
