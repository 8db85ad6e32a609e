"""Shared by the readers of the program's own names: its device ops
joined with the named scopes of the chunk executable, and its host span
counters.

The program names its chunk executable ``fdtd3d_chunk``: its runs show
on the device's ``XLA Modules`` line as ``jit_fdtd3d_chunk(<id>)`` and
its ops on the ``XLA Ops`` line by their HLO instruction names.
``telemetry.op_scopes()`` maps each instruction of that module to the
innermost ``fdtd3d/<name>`` scope of its HLO metadata and, for a fusion,
to the scopes of the ops fused into it. ``telemetry.span_totals()``
holds the count and seconds of every host span the process closed, set-up
included. A program without them, or a trace without the module, gives
nothing here, and the readers then return None."""

CHUNK = "jit_fdtd3d_chunk"
MIXED = "mixed"


def _telemetry():
    try:
        from fdtd3d_tpu import telemetry
    except ImportError:
        return None
    return telemetry


def span_total(name):
    """``{"count", "seconds"}`` of the program's host span, or None."""
    tel = _telemetry()
    totals = getattr(tel, "span_totals", None)
    if totals is None:
        return None
    return totals().get(name)


def table():
    """The chunk module's ``{instruction: (scope, fused)}``, or None."""
    tel = _telemetry()
    scopes = getattr(tel, "op_scopes", None)
    if scopes is None:
        return None
    return scopes().get(CHUNK) or None


def classify(entry):
    """The one scope an op's time belongs to: the names of its own scope
    and of the ops fused into it, ``mixed`` where they are several,
    None (unscoped) where there is none."""
    if entry is None:
        return None
    scope, fused = entry
    names = set(fused) | ({scope} - {None})
    if len(names) > 1:
        return MIXED
    return next(iter(names), None)


def chunk_ops(ctx):
    """[(start, end, class)] of the chunk module's ops in the window on
    the device where that module ran longest, container ops (a scan's
    ``while``) left out; None where the program gives no scope map or
    the trace has no such module."""
    tab = table()
    tr = ctx["trace"]

    def chunk(name):
        return name.split("(")[0] == CHUNK

    dev = tr.pace_device(chunk)
    if tab is None or dev is None:
        return None
    runs = [m for m in tr.module_runs(dev) if chunk(m[2])]
    if not runs:
        return None
    lo, hi = tr.win
    return [(max(s, lo), min(e, hi), classify(tab.get(name)))
            for s, e, name, label, _ in tr.ops_within(dev, runs)
            if label is not None and e > lo and s < hi]


def class_s(ctx, classes):
    """Device seconds (the union of the op intervals) of the chunk
    module's ops whose class is in ``classes``, or None."""
    ops = chunk_ops(ctx)
    if ops is None:
        return None
    iv = [(s, e) for s, e, c in ops if c in classes]
    if not iv:
        return None
    return ctx["trace"].union_s(iv)
