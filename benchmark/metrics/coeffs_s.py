"""Set-up: seconds in the program's ``build-coeffs`` span
(``solver.build_coeffs``, the coefficient and material grids built on
the host), summed over every call the process made
(``telemetry.span_totals``)."""

from metrics import _scopes


def read(ctx):
    tot = _scopes.span_total("build-coeffs")
    return None if tot is None else tot["seconds"]
