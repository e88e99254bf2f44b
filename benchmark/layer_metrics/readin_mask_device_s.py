"""Device-busy seconds a traced read-in: the union of the operations
that ran on the chip between the call's start and the entry of
`search_block` (the RFI statistics, the mask's select, the transfer's
device side), a read-in."""

from benchmark.harness import readin_trace


def read(ctx):
    got = readin_trace.busy_inside(ctx)
    if got is None or got[0] <= 0.0:
        return None
    return got[0] / len(readin_trace.readin_spans(ctx))
