"""NTFF: milliseconds per sample, from the start of the program's
``fdtd3d/ntff-sample`` span to the later of its end and the end of the
last device operation launched inside it on the device that sets the
pace (program span + device trace)."""


def read(ctx):
    tr = ctx["trace"]
    spans = tr.host_spans("fdtd3d/ntff-sample")
    if not spans:
        return None
    dev = tr.pace_device()
    ops = tr.ops.get(dev, []) if dev is not None else []
    tot = 0.0
    for s, e in spans:
        end = max([e] + [o[1] for o in ops if s <= o[0] <= e])
        tot += end - s
    return tot / len(spans) / 1e3
