"""The chip's idle share of a traced read-in, in per cent: 1 - busy /
span over the harness's `readin` annotations."""

from benchmark.harness import readin_trace


def read(ctx):
    got = readin_trace.busy_inside(ctx)
    if got is None or got[1] <= 0.0:
        return None
    return 100.0 * (1.0 - got[0] / got[1])
