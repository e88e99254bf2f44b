"""Host seconds a slice call under `refine-host`: the simplex of
scipy.optimize.minimize per candidate harmonic, after refinement's one
device_get (the device part is refine_s less this)."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.per(ctx, scopes.span_seconds(ctx, ("refine-host",)),
                      "call")
