"""TFSF: device milliseconds per simulated step of the chunk executable's
ops whose named scope is the program's ``tfsf`` (``telemetry.op_scopes``):
the incident line, its gathers at the TFSF planes and the plane
corrections with their H fixes, whatever helper runs them. Fusions that
mix ``tfsf`` with another scope are left out."""

from metrics import _scopes


def read(ctx):
    if not ctx["steps"]:
        return None
    s = _scopes.class_s(ctx, ("tfsf",))
    return None if s is None else s / ctx["steps"] * 1e3
