"""Chunk executor: device milliseconds per chunk of the work a chunk does
once, whatever its length: the chunk executable's ops in the program's
``prepare`` scope (the coefficient packing hoisted out of the scan) and
``health`` scope (the in-graph unpack and the health reduction over the
final state), from ``telemetry.op_scopes``. One-step chunks pay it every
step."""

from metrics import _scopes


def read(ctx):
    if not ctx["chunks"]:
        return None
    s = _scopes.class_s(ctx, ("prepare", "health"))
    return None if s is None else s / ctx["chunks"] * 1e3
