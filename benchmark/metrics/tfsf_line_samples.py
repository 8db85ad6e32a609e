"""TFSF: incident-line positions the chunk program samples per
simulated step, over every TFSF correction and, under temporal
blocking, every generation of a call: the program's
``tfsf-line-samples`` gauge (``telemetry.gauges()``), set when the
chunk program is traced. None where the program has no such gauge."""


def read(ctx):
    try:
        from fdtd3d_tpu import telemetry
    except ImportError:
        return None
    gauges = getattr(telemetry, "gauges", None)
    if gauges is None:
        return None
    return gauges().get("tfsf-line-samples")
