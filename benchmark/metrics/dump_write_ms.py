"""Output: milliseconds per field dump spent writing the files, the
program's ``fdtd3d/io-write`` span inside ``io.write_outputs`` (the
format writers, each ``.dat`` fsynced), without the device-to-host
gather (``fdtd3d/io-gather``) that ``dump_ms`` also holds."""


def read(ctx):
    spans = ctx["trace"].host_spans("fdtd3d/io-write")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e3
