"""DM rows a device of the mesh took in one call of the fused pass
program with the hi stage inside it: the median `rows_per_device` of
the program's `mesh_chunk` spans whose `hi` is true, over the window's
slice calls.  `accel.plane_dm_chunk` gives the rows by the plane's
bytes, so a long series takes few (4 at FAST GPPS's ds=1, 8 at its
ds=2; 6 at Mock's), and a pass of 102 trials then takes many calls:
the number that says why.  None where no call ran the hi stage on the
mesh (hi-accel off, or the single-device fallback)."""

import statistics

from benchmark.harness import scopes


def read(ctx):
    per_call = scopes.call_events(ctx)
    if per_call is None:
        return None
    rows = [e["args"]["rows_per_device"]
            for events in per_call for e in events
            if e["name"] == "mesh_chunk" and e["args"].get("hi")
            and "rows_per_device" in e["args"]]
    return float(statistics.median(rows)) if rows else None
