"""Set-up: how many times the process built the coefficient grids, the
count of the program's ``build-coeffs`` span (``telemetry.span_totals``):
``Simulation`` once, and each kernel factory it tries once more."""

from metrics import _scopes


def read(ctx):
    tot = _scopes.span_total("build-coeffs")
    return None if tot is None else tot["count"]
