"""Sub-bin candidate refinement — the harmpolish equivalent.

PRESTO's accelsearch optimizes each candidate's (r, z) to sub-bin
precision before reporting (the -harmpolish stage; the reference
invokes it for every search, lib/python/PALFA2_presto_search.py:561
and :579 via config.searching accel flags).  Bin-quantized candidates
lose up to half a Fourier bin of frequency accuracy and up to ~30% of
peak power (scalloping), which shifts both the reported frequency and
the significance ordering — the "candidate list identical to PRESTO"
goal (BASELINE.md) is unreachable without this stage.

Method: the power of a frequency-drifting tone at CONTINUOUS Fourier
coordinates (r, z) is evaluated by correlating the complex spectrum
against an analytically generated fractional-offset z-response
(the same discrete-chirp construction as the search templates in
kernels/accel.py, but sampled at non-integer bin offsets), and a
Nelder-Mead simplex maximizes it within +-1 bin in r and +-DZ in z.
Each harmonic h is refined at (h*r, h*z) and the summed power is
re-assembled, mirroring harmpolish's per-harmonic optimization.
"""

from __future__ import annotations

import numpy as np

from tpulsar.kernels.accel import DZ
from tpulsar.obs import trace

def _response_at(z: float, offsets: np.ndarray) -> np.ndarray:
    """Complex response values of a unit tone drifting z bins,
    sampled at (possibly fractional) bin offsets from the tone's MEAN
    frequency.

    Closed form via Fresnel integrals (the continuous limit of the
    discrete-chirp DFT that builds the search templates in
    kernels/accel.py):
      S(u) = e^{-i pi u^2 / z} / sqrt(2 z) * [F(t2) - F(t1)],
      t1 = -u sqrt(2/z), t2 = (1 - u/z) sqrt(2 z),
    with u the offset from the START frequency and F = C + iS the
    Fresnel integral; z < 0 follows from S_{-z}(u) = conj(S_z(-u)),
    and z -> 0 degenerates to the Dirichlet kernel
    e^{-i pi u} sinc(u).  O(width) per call instead of the O(N*width)
    arbitrary-frequency DFT."""
    offsets = np.asarray(offsets, np.float64)
    u = offsets + z / 2.0              # offsets from the START freq
    if abs(z) < 1e-4:
        return (np.exp(-1j * np.pi * u) * np.sinc(u)).astype(complex)
    if z < 0:
        return np.conj(_response_at(-z, -offsets))
    s1, c1 = _fresnel(-u * np.sqrt(2.0 / z))
    s2, c2 = _fresnel((1.0 - u / z) * np.sqrt(2.0 * z))
    f21 = (c2 - c1) + 1j * (s2 - s1)
    return np.exp(-1j * np.pi * u * u / z) / np.sqrt(2.0 * z) * f21


def _fresnel(x):
    from scipy import special
    return special.fresnel(x)


def power_at(spec: np.ndarray, r: float, z: float,
             width: int | None = None) -> float:
    """Normalized power of the whitened complex spectrum `spec` at
    continuous coordinates (r, z): |matched filter|^2 with the
    fractional z-response, so a unit-mean-noise spectrum gives
    Gamma(1,1)-distributed values, same scale as the on-grid search.

    r is the signal's MEAN Fourier frequency in bins — the convention
    of the search plane (kernels/accel.py aligns plane index with the
    response center, which gen_z_response puts at the mean frequency)
    and therefore of every Candidate's r/freq fields.

    width defaults to the search templates' sizing rule
    (kernels/accel.py template_width: the drift extent plus Fresnel
    ringing) — a fixed window would truncate high-|z| responses and
    deflate the refined power."""
    from tpulsar.kernels.accel import template_width

    if width is None:
        width = template_width(abs(z))
    nbins = spec.shape[-1]
    center = r
    k0 = int(round(center)) - width // 2
    k0 = max(1, min(k0, max(1, nbins - width - 1)))
    kend = min(k0 + width, nbins)
    ks = np.arange(k0, kend)
    resp = _response_at(z, ks - center)
    seg = np.asarray(spec[k0: kend])
    norm = float(np.sum(np.abs(resp) ** 2))
    if norm <= 0:
        return 0.0
    return float(np.abs(np.vdot(resp, seg)) ** 2 / norm)


def refine_peak(spec: np.ndarray, r0: float, z0: float,
                numharm: int = 1, width: int | None = None,
                max_dr: float = 1.0, max_dz: float = DZ
                ) -> tuple[float, float, float]:
    """Maximize the harmonic-summed power around (r0, z0).

    Returns (r, z, summed_power) with r the refined FUNDAMENTAL bin
    (possibly fractional) and summed_power = sum_h P(h*r, h*z) —
    the quantity PRESTO's harmpolish reports.  The simplex search is
    bounded to +-max_dr / +-max_dz around the grid detection (the
    true peak of a detected signal is within half a grid cell).
    """
    from scipy import optimize

    def neg_summed(x):
        r, z = x
        if abs(r - r0) > max_dr or abs(z - z0) > max_dz:
            return 0.0        # outside the trust region: no credit
        return -sum(power_at(spec, h * r, h * z, width=width)
                    for h in range(1, numharm + 1))

    res = optimize.minimize(
        neg_summed, x0=[r0, z0], method="Nelder-Mead",
        options={"xatol": 1e-3, "fatol": 1e-4, "maxfev": 120})
    r, z = float(res.x[0]), float(res.x[1])
    best = -float(res.fun)
    grid = sum(power_at(spec, h * r0, h * z0, width=width)
               for h in range(1, numharm + 1))
    if grid > best:           # optimizer wandered; keep the grid point
        return r0, z0, grid
    return r, z, best


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


# The gather program set must be CLOSED so tools/aot_check.py can
# compile-gate every member before a measured on-chip run (an in-line
# remote compile inside the measured child is this project's
# documented wedge hazard): window count is always _NWIN (callers
# chunk + pad), width comes from _WIDTH_BUCKETS.  512 covers typical
# lo-stage candidates (template_width <= 256 plus slack); 8192 covers
# the worst survey case (h=16 at z0=zmax=200 -> template_width 4096
# plus harmonic slack); 2048 keeps the common hi-z cases off the
# 8192 transfer size.
_NWIN = 64
_WIDTH_BUCKETS = (512, 2048, 8192)


def _width_bucket(span: int) -> int:
    for w in _WIDTH_BUCKETS:
        if span <= w:
            return w
    # Beyond-survey fallback: correct, but the resulting gather
    # program is OUTSIDE the AOT-gated set — a silent in-line
    # compile inside the measured run.  Shout so the log can
    # localize it.
    import logging

    logging.getLogger("tpulsar.refine").warning(
        "refine window span %d exceeds every gated width bucket %s; "
        "this gather will compile in-line (ungated program)",
        span, _WIDTH_BUCKETS)
    return _pow2(span)


def _gather_jit():
    """The (lazily created) jitted window gather, registered as
    ``refine.gather`` in tpulsar/aot/registry.py so the AOT gate
    lowers the exact runtime callable (the lambda-wrapping pitfall of
    round 3 produced different persistent-cache keys than the
    runtime's own calls).  Lazy factory because this module must
    import jax-free."""
    import jax
    import jax.numpy as jnp

    global _GATHER_JIT
    if _GATHER_JIT is None:
        def _gather(spec, lo_arr, width):
            idx = lo_arr[:, None] + jnp.arange(width)[None, :]
            idx = jnp.clip(idx, 0, spec.shape[0] - 1)
            # Gather on the complex spectrum, then SHIP float32
            # real/imag planes: a TPU runtime has raised
            # UNIMPLEMENTED on a complex64 host fetch, and this would
            # be the only complex host transfer in the whole search
            # path.
            # Every other fetch in the pipeline is f32 and works; the
            # host side recombines.
            win = jnp.take(spec, idx, axis=0)
            return jnp.stack([win.real, win.imag],
                             axis=-1)          # (NWIN, width, 2) f32

        _GATHER_JIT = jax.jit(_gather, static_argnames=("width",))
    return _GATHER_JIT


_GATHER_JIT = None


class _WindowedSpectrum:
    """Host view of selected [lo, hi) windows of a device-resident
    spectrum.  Supports exactly the access pattern power_at uses —
    ``spec[k0:kend]`` with the slice fully inside one prefetched
    window, plus ``.shape`` — so refinement transfers a few hundred
    bins per candidate harmonic instead of the full whitened spectrum
    (~17 MB per DM group at survey scale; with up to
    max_cands_to_fold groups that was hundreds of MB of device-to-host
    transfer per beam)."""

    def __init__(self, nbins: int,
                 windows: list[tuple[int, np.ndarray]]) -> None:
        self.shape = (nbins,)
        self._wins = windows

    def __getitem__(self, sl: slice) -> np.ndarray:
        for lo, arr in self._wins:
            if lo <= sl.start and sl.stop <= lo + len(arr):
                return arr[sl.start - lo: sl.stop - lo]
        raise IndexError(
            f"slice [{sl.start}:{sl.stop}) outside prefetched windows")


def _harmonic_windows(r0: float, z0: float, numharm: int,
                      nbins: int) -> list[tuple[int, int]]:
    """[lo, hi) bin ranges covering every slice power_at can request
    while refine_peak explores |r - r0| <= 1, |z - z0| <= DZ at
    harmonics 1..numharm, including power_at's edge clamps."""
    from tpulsar.kernels.accel import template_width

    out = []
    for h in range(1, numharm + 1):
        w_max = template_width(abs(h * (abs(z0) + DZ)))
        raw_lo = int(round(h * (r0 - 1))) - w_max // 2 - 2
        # power_at's upper clamp can relocate k0 down to
        # nbins - w - 1 for centers near the top edge
        lo = min(raw_lo, nbins - w_max - 2)
        hi = int(round(h * (r0 + 1))) + w_max // 2 + 2
        if raw_lo < 1:
            # ... and its LOWER clamp (k0 = max(1, ...)) relocates k0
            # up to 1 for low-frequency candidates, stretching the
            # slice to [1, 1 + w): the window must reach that far
            # even though the nominal center sits below w/2
            hi = max(hi, 1 + w_max + 1)
        out.append((max(0, lo), min(nbins, max(hi, lo + w_max + 2))))
    return out


def refine_candidates(cands, series_by_dm, dt: float, nfft: int,
                      keep_mask=None) -> None:
    """Refine a list of sifting.Candidate IN PLACE.

    series_by_dm: {dm: (T,) float array} at FULL time resolution —
    candidates are grouped by DM so each series is FFT'd and whitened
    once.  A candidate's r is in its detection pass's (downsampled,
    padded) bin units, so the invariant freq_hz maps it onto this
    series' scale: r0 = freq_hz * T_s.  Power, r, z, freq and period
    fields are updated; sigma itself is the caller's to recompute
    (it owns the trials correction).

    Device traffic: the whitened spectrum stays on device; only the
    harmonic windows around each candidate (a few hundred bins each)
    are fetched, in ONE device_get per DM group.
    """
    import jax.numpy as jnp

    from tpulsar.kernels import fourier as fr

    by_dm: dict[float, list] = {}
    for c in cands:
        by_dm.setdefault(c.dm, []).append(c)
    T_s = nfft * dt
    for dm, group in by_dm.items():
        if dm not in series_by_dm:
            continue
        # the device half: spectrum, window gathers, the ONE
        # device_get; then the host half, the per-harmonic simplex
        with trace.span("refine-device", dm=float(dm), n=len(group)):
            series = jnp.asarray(series_by_dm[dm])[None, :]
            if keep_mask is not None:
                wspec_dev = fr.whitened_spectrum_masked(
                    series, jnp.asarray(keep_mask), nfft=nfft)[0]
            else:
                wspec_dev = fr.whitened_spectrum(series, nfft=nfft)[0]
            nbins = int(wspec_dev.shape[0])
            ranges: list[tuple[int, int]] = []
            cand_spans: list[list[tuple[int, int]]] = []
            for c in group:
                spans = _harmonic_windows(c.freq_hz * T_s, c.z,
                                          c.numharm, nbins)
                cand_spans.append(spans)
                ranges.extend(spans)
            # Jitted gathers in fixed _NWIN chunks at a bucketed width:
            # eager per-window slicing of a complex device array is
            # rejected by some TPU runtimes (see accel.accel_row_topk),
            # and per-window slice programs would be unbounded
            # data-dependent compiles — the fixed (count, width) buckets
            # keep the program set closed so the AOT gate covers it.
            # All chunks are dispatched async, then ONE device_get drains
            # them together (a blocking get per chunk would serialize
            # ceil(n/64) round-trips).
            import jax

            width = _width_bucket(max(hi - lo for lo, hi in ranges))
            lows_all = np.fromiter((lo for lo, _ in ranges), np.int32,
                                   len(ranges))
            gather = _gather_jit()
            chunks_dev = []
            for s in range(0, len(ranges), _NWIN):
                lows = lows_all[s: s + _NWIN]
                lows = np.pad(lows, (0, _NWIN - len(lows)))
                chunks_dev.append(gather(wspec_dev,
                                         jnp.asarray(lows, np.int32),
                                         width=width))
            fetched = np.concatenate(
                [np.asarray(c[..., 0] + 1j * c[..., 1])
                 for c in jax.device_get(chunks_dev)],
                axis=0)
        with trace.span("refine-host", dm=float(dm), n=len(group),
                        nharm=sum(c.numharm for c in group)):
            windows = [(lo, fetched[i][: min(width, nbins - lo)])
                       for i, (lo, _hi) in enumerate(ranges)]
            i = 0
            for c, spans in zip(group, cand_spans):
                view = _WindowedSpectrum(
                    nbins, windows[i: i + len(spans)])
                i += len(spans)
                r0 = c.freq_hz * T_s
                r, z, power = refine_peak(view, r0, c.z,
                                          numharm=c.numharm)
                c.r, c.z, c.power = r, z, power
                c.freq_hz = r / T_s
                c.period_s = T_s / r
