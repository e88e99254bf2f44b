"""The least work of one hi-accel chunk program at the configuration's
FULL length: `hiaccel_chunk`'s count (the sibling file, unchanged) with
`nbins` taken from `shapes["nsamp"]` by the program's own
`ddplan.choose_n`, read from it the way a kernel's name is, and the
rows a chunk program is given at that width.

`runner.cost_shapes` counts at the slice's FIRST pass.  In a slice
that lists a downsampled pass before the ds=1 pass (steps 1, 0: the
only order in which a plan whose own first pass lies under the
sifter's DM cutoff holds a recoverable pulsar) that is the ds=2 shape,
while `tracered.slowest_variant` times the ds=1 program: this file
sets the ds=1 program's seconds against the ds=1 program's work.
"""

import os


def cost(shapes: dict) -> tuple[float, float]:
    from benchmark.harness import layers
    from tpulsar.kernels import accel
    from tpulsar.plan import ddplan

    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    nbins = ddplan.choose_n(int(shapes["nsamp"])) // 2 + 1
    rows = accel.plane_dm_chunk(nbins, int(shapes["nz"]))
    return layers.load_cost(bench_dir, "hiaccel_chunk")(
        {**shapes, "nbins": nbins, "hi_rows": rows})
