"""The least work of one stage-2 dedispersion program call: `dd_rows`
DM trials, each the sum of `nsub` shifted subband rows of `T` samples.

Bytes: the subband block read once, the rows written once (float32).
Operations: one add per row, subband and sample.  `dd_rows` is the
useful rows per call (a call padded up to a fixed row count does no
more useful work for it).
"""


def cost(shapes: dict) -> tuple[float, float]:
    rows, nsub, T = shapes["dd_rows"], shapes["nsub"], shapes["T"]
    ops = float(rows) * nsub * T
    nbytes = 4.0 * nsub * T + 4.0 * rows * T
    return ops, nbytes
