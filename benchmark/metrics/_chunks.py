"""Shared by the readers: the window's device ops, by program and by
kernel.

The chunk executable is the program's scan over ``n`` steps
(``sim.Simulation._chunk_fn``). The program compiles it from a
``functools.partial``, so its runs show on the device's ``XLA Modules``
line as ``jit__unknown(<fingerprint>)``: no stable name. It is found as
the executable with the most device time in the window, which in every
cell is the solver's chunk (the NTFF sample, the unpack and the norms
are each a small fraction of it). The step kernel is found by the name
its kind's byte file gives (``bytes/<step_kind>.py`` ``OP``), the
``jax.named_scope`` the program puts on its ``pallas_call``."""

import importlib
import re


def device(ctx):
    """The device whose chunk program has the most device time: with
    several, the one that sets the pace."""
    return ctx["trace"].pace_device()


def runs(ctx):
    dev = device(ctx)
    if dev is None:
        return []
    tr = ctx["trace"]
    name = tr.busiest_module(dev)
    return sorted(tr.module_runs(dev, name)) if name else []


def step_s(ctx):
    """Device seconds in which the chunk program's ops ran (the union of
    their intervals: the scan's ``while`` op contains its body's ops),
    per step, or None."""
    r = runs(ctx)
    if not r or not ctx["steps"]:
        return None
    tr = ctx["trace"]
    ops = tr.ops_within(device(ctx), r)
    if not ops:
        return None
    return tr.union_s([(o[0], o[1]) for o in ops]) / ctx["steps"]


def ops_s(ctx, keep):
    """Device seconds per step of the window's ops for which
    ``keep(name, source)`` holds, or None where there are none."""
    dev = device(ctx)
    if dev is None or not ctx["steps"]:
        return None
    iv = ctx["trace"].ops_where(dev, keep)
    if not iv:
        return None
    return ctx["trace"].union_s(iv) / ctx["steps"]


def kind_module(kind):
    """``bytes/<kind>.py``; a step kind with no byte file is an error."""
    try:
        return importlib.import_module(f"bytes.{kind}")
    except ModuleNotFoundError:
        raise RuntimeError(f"no byte count for step kind {kind!r}: add "
                           f"benchmark/bytes/{kind}.py") from None


def kernel_s(ctx, kind):
    """Device seconds per step of the kind's own kernel ops, or None."""
    if device(ctx) is None:
        return None
    pat = re.compile(re.escape(kind_module(kind).OP) + r"(\.\d+)?$")
    return ops_s(ctx, lambda name, src: pat.match(name) is not None)


def peak_bytes_per_s(ctx):
    kind = ctx["device_kind"]
    if kind not in ctx["peaks"]:
        raise RuntimeError(f"device kind {kind!r} is not in peaks.json")
    return ctx["peaks"][kind]["hbm_bytes_per_s"]


def roofline(ctx, kind, seconds_per_step):
    """100 x least time per step over the measured seconds per step.
    The least time is the kind's bytes per step of the whole grid at
    the published HBM bandwidth of the chips the program split the grid
    over (``shards``, from the topology it resolved) together: each
    moves its part while the device that sets the pace is timed. FDTD is bandwidth-bound: a few flops per byte,
    far under the v5e's 240 flop/byte ridge."""
    if seconds_per_step is None:
        return None
    least = kind_module(kind).bytes_per_step(ctx) / (
        peak_bytes_per_s(ctx) * ctx["shards"])
    return 100.0 * least / seconds_per_step
