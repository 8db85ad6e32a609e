"""Four virtual CPU devices for this process, set before JAX starts, so
that the tests of a sharded cell's layout run in process; ``cpu_env(n)``
is the environment of a child process with ``n``."""

import os

_COUNT = "--xla_force_host_platform_device_count"


def cpu_env(n):
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_COUNT)]
    if n > 1:
        flags.append(f"{_COUNT}={n}")
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags))


os.environ.update(cpu_env(4))
