"""The least work of one hi-accel chunk program: `hi_rows` DM trials of
the overlap-save matched filter on the half-bin grid, the harmonic sums
and the top-k.

Counted is only what no implementation can avoid.  Bytes: the chunk's
complex64 spectra and the template bank in, the top-k out — not the
(rows x nz x 2 nbins) plane, which a fused kernel never writes.
Operations: per row and segment, one forward FFT of the interleaved
segment, and per z one complex product and one inverse FFT (5 L log2 L
each), the squared magnitude, and the harmonic sums over the plane.
The segment is fixed here at 8192 bins, the bank's, so that a PR which
changes the program's segment is measured against the same count.
"""

import math

SEG = 8192


def template_width(zmax: float) -> int:
    w = int(2 * math.ceil(abs(zmax) / 2) + 32)
    return int(2 ** math.ceil(math.log2(w)))


def cost(shapes: dict) -> tuple[float, float]:
    rows, nbins, nz = shapes["hi_rows"], shapes["nbins"], shapes["nz"]
    numharm, topk = shapes["numharm"], shapes["topk"]
    width = template_width(shapes["zmax"])
    step = SEG - width
    nsegs = -(-nbins // step)
    L = 2 * SEG
    fft = 5.0 * L * math.log2(L)
    per_seg = fft + nz * (6.0 * L + fft + 3.0 * 2 * step)
    stages = [h for h in (1, 2, 4, 8, 16, 32) if h <= numharm]
    plane = nz * 2.0 * nbins
    harm = sum(plane * (h - prev) / h
               for prev, h in zip([0] + stages, stages) if h > 1)
    zmax_reduce = sum(plane / h for h in stages)
    ops = rows * (nsegs * per_seg + harm + zmax_reduce)
    nbytes = (rows * nbins * 8.0 + nz * L * 8.0
              + rows * len(stages) * topk * 12.0)
    return ops, nbytes
