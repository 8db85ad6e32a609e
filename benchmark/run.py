"""The benchmark: one cell, one run, one JSON line.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs in ONE process, which holds the chip. It loads the cell's files
(BENCHMARK.json names the configuration and the traffic; each lives
in a file of its own under benchmark/), builds the program's
``Simulation`` from the configuration's command-line arguments the way
``fdtd3d_tpu.cli`` does, warms up every chunk length and output path
the window uses, advances the simulation for ``--seconds`` through
``Simulation.run`` with an ``on_interval`` that is a copy of the CLI's
own loop (cadences, gcd chunking, NTFF samples, norms, dumps), and
then checks what the window produced against the plain reference that
the configuration names (benchmark/reference/). On a cell of several
chips, everything the harness holds is sharded as the program's state
is. The last stdout line is the result.

``--trace 1`` captures a profiler trace of the window and reports the
cell's per-layer metrics (benchmark/metrics/<name>.py) instead of its
end-to-end ones. ``--rehearse`` (builder only) runs the configuration's
tiny ``rehearse`` variant on the CPU; a run without it that finds no
TPU, or fewer chips than the cell asks for, exits 2 and prints no
result. ``--dtype`` (builder only) overrides the storage precision: the
bfloat16 control.
"""

import time

T_START = time.perf_counter()   # process start, for setup_s

import argparse
import glob
import importlib.util
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")
BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(RuntimeError):
    """A file the benchmark names is missing or malformed."""


def _load_json(path, what):
    if not os.path.isfile(path):
        raise SpecError(f"{what}: missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path, what):
    if not os.path.isfile(path):
        raise SpecError(f"{what}: missing file {os.path.relpath(path, ROOT)}")
    name = "bench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(name):
    """A per-layer metric's reader, ``metrics/<name>.py``; a metric split
    by the end-to-end metric it moves (``<base>.<part>``) uses its
    base's reader unless it has one of its own."""
    own = os.path.join(HERE, "metrics", name + ".py")
    if os.path.isfile(own) or "." not in name:
        return own
    return os.path.join(HERE, "metrics", name.split(".")[0] + ".py")


def load_cell(workload, bench_file=BENCH_FILE):
    """Everything one cell needs, found by name; raises SpecError."""
    bench = _load_json(bench_file, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; "
                        f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SpecError(f"workload {workload}: no config {cell['config']!r}")
    config = _load_json(os.path.join(ROOT, configs[cell["config"]]["file"]),
                        f"config {cell['config']}")
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"),
                         f"traffic {cell['traffic']}")
    limits = _load_json(os.path.join(HERE, "limits", workload + ".json"),
                        f"limits of {workload}")

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    per_layer = [m for m in bench["per_layer"] if listed(m)]
    readers = {m["name"]: load_module(reader_path(m["name"]),
                                      f"metric {m['name']}")
               for m in per_layer}
    peaks = _load_json(os.path.join(HERE, "peaks.json"), "peak table")
    if "reference" not in config:
        raise SpecError(f"config {cell['config']}: no reference key")
    reference = load_module(os.path.join(HERE, config["reference"]),
                            f"reference of config {cell['config']}")
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": per_layer,
            "readers": readers, "peaks": peaks, "reference": reference}


def cell_args(spec, rehearse, dtype=None, save_dir=None):
    """The program's command line for this cell."""
    cfg, tr = spec["config"], spec["traffic"]
    args = list(cfg["args"]) + list(tr["args"])
    if rehearse:
        args += list(cfg["rehearse"]["args"]) + list(
            tr.get("rehearse", {}).get("args", []))
    if save_dir:
        args += ["--save-dir", save_dir]
    if dtype:
        args += ["--dtype", dtype]
    return args


def cell_physics(spec, rehearse):
    phys = dict(spec["config"]["physics"])
    if rehearse:
        phys.update(spec["config"]["rehearse"]["physics"])
    return phys


def cache_dir(rehearse):
    """JAX's persistent compilation cache: a fixed path inside the
    checkout that only the benchmark writes (a machine may bring
    entries of its own to ``.jax_cache``); CPU rehearsals keep theirs
    apart from it."""
    return os.path.join(OUT, "jax_cache_rehearse" if rehearse
                        else "jax_cache")


def use_cache(rehearse):
    """Point JAX (and through the environment the program's
    exec_cache.use_compile_cache()) at cache_dir(), caching every
    program. Eviction stays off: with it on, JAX refuses every write
    once the directory holds an entry without its ``-atime`` file."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir(rehearse)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir(rehearse))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def dump_dir():
    """Where the window's dumps go until the harness removes them; one
    per process, so rehearsals may run side by side."""
    return os.path.join(OUT, f"dumps.{os.getpid()}")


def _platform_check(jax, chips, rehearse):
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devs[0].platform!r}); refusing to report", file=sys.stderr)
        sys.exit(2)
    if not rehearse and len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)


class Window:
    """The simulation, its CLI-style interval loop, and the window."""

    def __init__(self, spec, args_list, seed, rehearse):
        import numpy as np

        from fdtd3d_tpu import cli
        from fdtd3d_tpu.sim import Simulation

        self.spec = spec
        self.rehearse = rehearse
        parser = cli.build_parser()
        self.args = parser.parse_args(args_list)
        self.cfg = cli.args_to_config(self.args)
        t0 = time.perf_counter()
        self.sim = Simulation(self.cfg)
        self.phys = cell_physics(spec, rehearse)
        self.reseed(seed)
        self.build_s = time.perf_counter() - t0
        self.cells = float(np.prod(self.cfg.grid_shape))
        self._cadences()

    def reseed(self, seed):
        """Start over at step 0 from the seeded initial fields: the data
        the comparison sees, made on the device in one jitted call and
        stored in the program's storage type; every other state leaf
        (CPML psi, the incident line) zero. On a mesh every leaf is
        made with the sharding the program gave it, and ``layout``
        places the harness's own arrays alike."""
        import jax
        import jax.numpy as jnp
        import layout
        import seeding
        st = self.sim.state
        sharded = self.sim.mesh is not None
        self.layout = layout.Layout(next(iter(st["E"].values())).sharding,
                                    self.cfg.grid_shape)
        fields = self.layout.make(*seeding.maker(self.phys, seed))

        def zero(x):
            return jnp.zeros(x.shape, x.dtype,
                             device=x.sharding if sharded else None)

        new = {k: ({c: fields[c].astype(v[c].dtype) for c in v}
                   if k in ("E", "H") else jax.tree.map(zero, v))
               for k, v in st.items()}
        del st, fields
        self.sim.state = new
        jax.block_until_ready(self.sim.state)
        self.seed = seed
        self.dumps = []          # steps of every dump written
        self.keep_dumps = set()  # steps whose dump files stay on disk
        self.dump_hook = None
        self._ntff_setup()

    # -- a copy of fdtd3d_tpu.cli.main's interval loop ---------------------

    def _ntff_setup(self):
        from fdtd3d_tpu import cli
        self.ntff = None
        self.ntff_every = self.ntff_start = 0
        if self.cfg.ntff.enabled:
            from fdtd3d_tpu.ntff import NtffCollector
            freq, self.ntff_every, self.ntff_start = \
                cli.resolve_ntff_cadence(self.cfg)
            self.ntff_freq = freq
            self.ntff = NtffCollector(self.sim, frequency=freq,
                                      margin=self.cfg.ntff.margin)

    def _cadences(self):
        out = self.cfg.output
        interval = 0
        for v in (out.save_res, out.norms_every, out.checkpoint_every,
                  out.metrics_every, self.ntff_every):
            if v:
                interval = math.gcd(interval, v)
        if not interval:
            raise SpecError("the traffic sets no cadence, so the CLI would "
                            "run one chunk: give it --norms-every")
        self.interval = interval

    def on_interval(self, s):
        from fdtd3d_tpu import diag, io
        from fdtd3d_tpu import telemetry as _telemetry
        from fdtd3d_tpu.log import log
        cfg = self.cfg
        if self.ntff is not None and s.t >= self.ntff_start and \
                s.t % self.ntff_every == 0:
            with _telemetry.span("ntff-sample"):
                self.ntff.sample()
        if cfg.output.metrics_every and \
                s.t % cfg.output.metrics_every == 0:
            os.makedirs(cfg.output.save_dir, exist_ok=True)
            with open(os.path.join(cfg.output.save_dir, "metrics.jsonl"),
                      "a") as f:
                f.write(json.dumps(diag.metrics(s)) + "\n")
        if cfg.output.norms_every and s.t % cfg.output.norms_every == 0:
            norms = diag.field_norms(s)
            txt = " ".join(f"{k}={v:.4e}" for k, v in sorted(norms.items()))
            log(f"[t={s.t}] {txt}")
        if cfg.output.save_res and s.t % cfg.output.save_res == 0:
            with _telemetry.span("io-dump"):
                io.write_outputs(s, s.t)
            self._dump_written(s.t)

    # -- dumps: the harness keeps the disk empty ----------------------------

    def dump_paths(self, step):
        return sorted(glob.glob(os.path.join(
            self.cfg.output.save_dir, f"*_t{step:06d}.dat*")))

    def _dump_written(self, step):
        self.dumps.append(step)
        if self.dump_hook is not None:
            self.dump_hook(self, step)
        if step not in self.keep_dumps:
            for p in self.dump_paths(step):
                os.unlink(p)

    # -- driving ----------------------------------------------------------

    def advance_interval(self):
        self.sim.run(time_steps=self.interval, on_interval=self.on_interval,
                     interval=self.interval)

    def t(self):
        return self.sim.t


def _device_info(jax, chips, rehearse):
    devs = jax.devices()[:max(chips, 1)]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def window_loop(w, seconds, unit):
    """The measured window: whole intervals until ``seconds`` have
    passed and the step count is a multiple of ``unit`` (the traffic's
    whole cycle, so every run ends on the same kind of boundary), ending
    in block_until_ready; -> (chunks, steps, elapsed, error). An
    exception of the program ends the window."""
    import tracing
    t = w.t()
    chunks = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    try:
        with tracing.annotation("bench/window"):
            while True:
                w.advance_interval()
                t += w.interval
                chunks += 1
                if t % unit == 0 and time.perf_counter() >= deadline:
                    break
            w.sim.block_until_ready()
    except Exception as exc:  # the program failed inside the window
        return chunks, 0, time.perf_counter() - t0, exc
    elapsed = time.perf_counter() - t0
    return chunks, chunks * w.interval, elapsed, None


def measure(w, spec, seed, seconds, tracer=None):
    """One seed on a built Window: warm every program the window runs,
    then run the window. Shared by run() and calibrate.py."""
    import checks
    check = checks.make(spec["traffic"]["check"], seed, spec["reference"])
    t0 = time.perf_counter()
    check.warm_up(w)
    w.sim.block_until_ready()
    r = {"check": check, "warmup_s": time.perf_counter() - t0}
    if tracer is not None:
        tracer.start()
    r["window_start"] = time.perf_counter()
    r["chunks"], r["steps"], r["elapsed"], r["error"] = window_loop(
        w, seconds, int(spec["traffic"]["window_unit_steps"]))
    if tracer is not None:
        tracer.stop()
    return r


def compare(check, w, limits, workload):
    """Let the check read what it compares, now that the window's clock
    has stopped and the device's peak memory has been read, and compare:
    -> (correct, {name: {value, limit}}); prints the traceback of a
    comparison that could not be made."""
    try:
        t0 = time.perf_counter()
        check.after_window(w)
        numbers = check.compare(w)
        print(f"benchmark: the check took {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
    except Exception as exc:   # what the window left cannot be read
        import traceback
        traceback.print_exc()
        print(f"benchmark: the comparison failed: {exc!r}", file=sys.stderr)
        return False, {}
    correct = bool(numbers)
    compared = {}
    for name, value in numbers.items():
        if name not in limits:
            raise SpecError(f"limits of {workload}: no limit for {name!r}")
        ok = value is not None and math.isfinite(value) \
            and value <= limits[name]
        correct = correct and ok
        compared[name] = {"value": value, "limit": limits[name]}
    return correct, compared


def prepare(workload, seed, rehearse, dtype=None):
    """Load the cell, point JAX at the compile cache, refuse a run with
    no TPU, and build the Window: -> (spec, jax, w)."""
    spec = load_cell(workload)
    os.makedirs(OUT, exist_ok=True)
    jax = use_cache(rehearse)
    _platform_check(jax, int(spec["cell"]["chips"]), rehearse)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from fdtd3d_tpu import exec_cache
    exec_cache.use_compile_cache()
    args_list = cell_args(spec, rehearse, dtype, dump_dir())
    return spec, jax, Window(spec, args_list, seed, rehearse)


def run(opts):
    spec, jax, w = prepare(opts.workload, opts.seed, opts.rehearse,
                           opts.dtype)
    import tracing as trace_mod
    chips = int(spec["cell"]["chips"])
    tracer = trace_mod.Capture(os.path.join(OUT, "trace")) \
        if opts.trace else None
    r = measure(w, spec, opts.seed, opts.seconds, tracer)
    setup_s = r["window_start"] - T_START
    steps, error = r["steps"], r["error"]

    device = _device_info(jax, chips, opts.rehearse)
    info = {"step_kind": w.sim.step_kind, "step_diag": w.sim.step_diag,
            "ntff_samples": (w.ntff.n_samples if w.ntff else 0),
            "dumps": len(w.dumps)}
    correct, compared = False, {}
    if error is None:
        correct, compared = compare(r["check"], w, spec["limits"]["limits"],
                                    opts.workload)

    metrics = {}
    if not opts.trace:
        e2e = {"setup_s": setup_s}
        rate = w.cells * steps / r["elapsed"] / 1e6 if steps else None
        for m in spec["end_to_end"]:
            if m["name"].startswith("mcells_per_s"):
                e2e[m["name"]] = rate
        for m in spec["end_to_end"]:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if opts.trace and error is None:
        tr = tracer.load()
        ctx = {"trace": tr, "steps": steps, "chunks": r["chunks"],
               "elapsed_s": r["elapsed"], "cells": w.cells,
               "build_s": w.build_s, "warmup_s": r["warmup_s"],
               "grid_shape": tuple(w.cfg.grid_shape),
               "dtype": w.cfg.dtype, "physics": w.phys,
               "device_kind": device["kind"],
               "shards": math.prod(w.sim.topology),
               "peaks": spec["peaks"],
               "rehearse": opts.rehearse, **info}
        for m in spec["per_layer"]:
            v = spec["readers"][m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
        if opts.keep_trace:
            tracer.keep(opts.keep_trace)
        tracer.cleanup()
    result = {"correct": bool(correct), "attempted": r["chunks"],
              "failed": int(error is not None), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    if error is not None:
        print(f"benchmark: the window failed: {error!r}", file=sys.stderr)
    shutil.rmtree(dump_dir(), ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0 if error is None else 1


def parse(argv=None):
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="builder only: tiny sizes on the CPU")
    p.add_argument("--keep-trace", default=None, metavar="PATH",
                   help="builder only: copy the trace's JSON there (how "
                        "benchmark/tests/fixture.trace.json.gz was "
                        "recorded, then cut to two chunks)")
    p.add_argument("--dtype", default=None,
                   help="builder only: storage precision override "
                        "(the bfloat16 control)")
    return p.parse_args(argv)


if __name__ == "__main__":
    sys.exit(run(parse()))
