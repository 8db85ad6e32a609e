"""Drive benchmark/run.py in-process with the timed path broken
underneath (CPU rehearsal sizes): the harness's chip check is skipped
by --rehearse, everything else runs as in a real run.

    python3 benchmark/tests/fault_driver.py FAULT [--bench PATH] \
        -- <run.py args>

FAULT is ``unchanged`` (every chunk returns its state as it got it,
the step counter still advancing), ``altered`` (one field value of
every chunk's result is changed where the chunk produces it) or
``none``. ``--bench`` reads the cells from another BENCHMARK.json (a
test's scratch cells)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run  # noqa: E402


def patch(fault):
    import jax.numpy as jnp

    from fdtd3d_tpu.sim import Simulation
    orig = Simulation.advance

    def advance(self, n_steps):
        if fault == "unchanged":
            st = self.state
            t = st["t"]
            self.state = dict(st, t=t + n_steps)
            return self
        orig(self, n_steps)
        st = self.state
        ex = st["E"]["Ex"]
        mid = tuple(s // 2 for s in ex.shape)
        scale = jnp.max(jnp.abs(ex))
        st["E"]["Ex"] = ex.at[mid].add(1e-3 * scale)
        self.state = st
        return self

    Simulation.advance = advance


if __name__ == "__main__":
    fault = sys.argv[1]
    sep = sys.argv.index("--")
    if sys.argv[2:sep]:
        assert sys.argv[2] == "--bench" and sep == 4
        bench_file = sys.argv[3]
        load_cell = run.load_cell
        run.load_cell = lambda workload: load_cell(workload, bench_file)
    opts = run.parse(sys.argv[sep + 1:])
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax  # noqa: F401
    if fault != "none":
        patch(fault)
    sys.exit(run.run(opts))
