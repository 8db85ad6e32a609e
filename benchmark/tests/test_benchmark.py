"""Self-checks of the benchmark (run them with
``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``; the repo's
tier-1 suite does not collect this directory). This process has four
CPU devices (conftest.py); each rehearsal runs in a child process with
one, or with four where its cell asks for four chips."""

import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import tracing  # noqa: E402
from conftest import cpu_env  # noqa: E402

CELLS = ("vacuum256.long", "mie512.ntff", "vacuum256.snap")
ENV = cpu_env(1)
FAULT_DRIVER = os.path.join(HERE, "fault_driver.py")


def _last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def _run(args, timeout=900, driver=None, env=ENV):
    cmd = [sys.executable] + ([driver] if driver else []) + args
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=timeout)
    return p


# -- the trace reduction -------------------------------------------------

def _fixture_trace(path):
    with open(path) as f:
        d = json.load(f)
    return d["traceEvents"] if isinstance(d, dict) else d


def test_reduction_on_the_repos_fixture():
    """tests/fixtures/fixture.trace.json: one device op line of 7 ops
    (100+50+30+80+200+10+5 us from ts 1000, back to back except a gap
    of 0 after each), with a window from 900 to 2000 us."""
    ev = _fixture_trace(os.path.join(ROOT, "tests", "fixtures",
                                     "fixture.trace.json"))
    ev = ev + [{"ph": "X", "pid": 1, "tid": 9, "ts": 900.0,
                "dur": 1100.0, "name": tracing.WINDOW}]
    tr = tracing.Trace(ev)
    assert tr.devices() == ["/device:TPU:0"]
    assert tr.busy_s() == pytest.approx(475e-6)
    assert tr.window_s() == pytest.approx(1100e-6)
    gaps = tr.idle_gaps("/device:TPU:0")
    assert sum(e - s for s, e in gaps) == pytest.approx(1100 - 475)
    # the kernel (custom-call) and its time
    ops = dict((n, v) for n, v in tr.breakdown()["device_ops"])
    assert ops["custom-call.1"] == pytest.approx(200e-6)
    assert sum(ops.values()) == pytest.approx(475e-6)


def test_reduction_on_a_recorded_chip_trace():
    path = os.path.join(HERE, "fixture.trace.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded chip trace")
    tr = tracing.Trace.from_file(path)
    exp = json.load(open(os.path.join(HERE, "fixture.expected.json")))
    dev = tr.devices()[0]
    assert tr.busy_s() == pytest.approx(exp["busy_s"], rel=1e-9)
    assert tr.window_s() == pytest.approx(exp["window_s"], rel=1e-9)
    assert tr.busiest_module(dev) == exp["chunk_module"]
    runs = tr.module_runs(dev, exp["chunk_module"])
    assert len(runs) == exp["chunk_runs"]
    ops = tr.ops_within(dev, runs)
    assert tr.union_s([o[:2] for o in ops]) == pytest.approx(
        exp["chunk_busy_s"], rel=1e-9)
    # the readers of the kernel and TFSF layers, on the same trace (a
    # two-step window of mie512.ntff: one packed-kernel call a step)
    ctx = _ctx("mie512", "pallas_packed")
    ctx.update(trace=tr, steps=exp["chunk_runs"])
    read = {m: run.load_module(run.reader_path(m), m).read(ctx)
            for m in ("kernel_ms_per_step", "tfsf_ms_per_step", "step_ms")}
    steps = exp["chunk_runs"]
    assert read["kernel_ms_per_step"] == pytest.approx(
        exp["kernel_busy_s"] / steps * 1e3, rel=1e-9)
    assert read["tfsf_ms_per_step"] == pytest.approx(
        exp["tfsf_busy_s"] / steps * 1e3, rel=1e-9)
    assert read["step_ms"] == pytest.approx(
        exp["chunk_busy_s"] / steps * 1e3, rel=1e-9)


# -- byte counts -----------------------------------------------------------

def _ctx(cfg_name, kind, k=None, rehearse=False):
    spec = {"config": json.load(open(os.path.join(
        BENCH, "configs", cfg_name + ".json")))}
    phys = run.cell_physics(spec, rehearse)
    return {"grid_shape": tuple(phys["size"]), "physics": phys,
            "dtype": "float32", "step_kind": kind, "shards": 1,
            "step_diag": {"temporal_block": k} if k else {}}


def _bytes(kind, ctx):
    mod = run.load_module(os.path.join(BENCH, "bytes", kind + ".py"), kind)
    return mod.bytes_per_step(ctx)


def test_bytes_as_perf_md_states():
    # 256^3 vacuum, tb k=4: (12 x 4 B x 256^3 + psi) / 4
    n = 256 ** 3
    psi = 3 * 4 * 2 * 18 * 256 * 256 * 4
    assert _bytes("pallas_packed_tb", _ctx("vacuum256", "pallas_packed_tb",
                                           4)) == (48 * n + psi) / 4
    assert (48 * n + psi) / 4 == 229_638_144
    # 512^3 sphere, single step: fields + 3 cb grids + psi
    n = 512 ** 3
    psi = 3 * 4 * 2 * 22 * 512 * 512 * 4
    assert _bytes("pallas_packed", _ctx("mie512", "pallas_packed")) == \
        60 * n + psi
    assert 60 * n + psi == 8_606_711_808


# -- the run, rehearsed on the CPU -----------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_an_accepted_line(cell):
    p = _run(["benchmark/run.py", "--workload", cell, "--seed",
              "3000000017", "--seconds", "2", "--trace", "0",
              "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    res = _last_json(p.stdout)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # each compared number is printed beside its limit on stderr too
    for name in res["checks"]:
        assert f"check {name}: " in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    p = _run(["benchmark/run.py", "--workload", cell, "--seed", "11",
              "--seconds", "2", "--trace", "0", "--rehearse", "--dtype",
              "bfloat16"])
    res = _last_json(p.stdout)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ("unchanged", "altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    p = _run([fault, "--", "--workload", cell, "--seed",
              "12", "--seconds", "2", "--trace", "0", "--rehearse"],
             driver=FAULT_DRIVER)
    res = _last_json(p.stdout)
    assert res["correct"] is False, (res["checks"], p.stderr[-2000:])


def test_traced_rehearsal_reports_per_layer_metrics_it_can_read():
    p = _run(["benchmark/run.py", "--workload", "vacuum256.long", "--seed",
              "5", "--seconds", "2", "--trace", "1", "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    res = _last_json(p.stdout)
    # the CPU has no device line: every metric the cell lists that the
    # host clock, the program's spans or its counters give is read,
    # and none that the device trace gives
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["per_layer"]
            if "vacuum256.long" in m.get("workloads", ["vacuum256.long"])
            and m["source"] in ("host_clock", "program_span",
                                "program_counter")}
    assert set(res["metrics"]) == want
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


# -- four-chip cells, rehearsed on four CPU devices --------------------------

def _four_chip_bench(tmp_path):
    """Scratch four-chip cells: ``vacuum256`` plus ``--topology auto``
    under ``long`` (segment check) and ``snap`` (dump check), and
    ``mie512`` (already ``--topology auto``) under ``ntff`` (trajectory
    check). At the rehearsal sizes the program resolves (1,2,2)."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "vacuum256.json")))
    cfg["args"] += ["--topology", "auto"]
    cfg_path = tmp_path / "vacuum256x4.json"
    cfg_path.write_text(json.dumps(cfg))

    def edit(b):
        b["configs"].append(dict(b["configs"][0], name="vacuum256x4",
                                 file=str(cfg_path)))
        for w in b["workloads"]:
            w["chips"] = 4
            if w["config"] == "vacuum256":
                w["config"] = "vacuum256x4"
    return _bench_copy(tmp_path, edit)


@pytest.mark.parametrize("mode", ("sound", "bfloat16", "unchanged",
                                  "altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_four_chip_rehearsal(tmp_path, cell, mode):
    """The checks of a sharded cell: a sound run is correct, the
    bfloat16 control and each planted fault are not."""
    fault = mode if mode in ("unchanged", "altered") else "none"
    args = [fault, "--bench", _four_chip_bench(tmp_path), "--",
            "--workload", cell, "--seed", "3000000041", "--seconds", "2",
            "--trace", "0", "--rehearse"]
    if mode == "bfloat16":
        args += ["--dtype", "bfloat16"]
    p = _run(args, driver=FAULT_DRIVER, env=cpu_env(4))
    res = _last_json(p.stdout)
    assert res["device"]["count"] == 4
    assert res["correct"] is (mode == "sound"), (res["checks"],
                                                 p.stderr[-3000:])
    if mode == "sound":
        assert res["checks"], p.stderr[-3000:]


def test_no_accelerator_no_result():
    p = _run(["benchmark/run.py", "--workload", "vacuum256.long", "--seed",
              "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().endswith("}")


def test_without_the_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and benchmark/ runs nothing."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "vacuum256.long", "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=tmp_path,
                       env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")


# -- a spec that names what is not there fails loudly -----------------------

def _bench_copy(tmp_path, edit):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = copy.deepcopy(bench)
    edit(bench)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_missing_traffic_file_fails(tmp_path):
    def edit(b):
        b["workloads"][0]["traffic"] = "no_such_traffic"
    with pytest.raises(run.SpecError, match="traffic"):
        run.load_cell("vacuum256.long", _bench_copy(tmp_path, edit))


def test_missing_metric_reader_fails(tmp_path):
    def edit(b):
        b["per_layer"].append({"name": "no_such_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "kernels", "moves": "mcells_per_s"})
    with pytest.raises(run.SpecError, match="no_such_metric"):
        run.load_cell("vacuum256.long", _bench_copy(tmp_path, edit))


def test_missing_reference_fails(tmp_path):
    cfg = json.load(open(os.path.join(BENCH, "configs", "vacuum256.json")))
    cfg["reference"] = "reference/no_such_reference.py"
    path = tmp_path / "vacuum256.json"
    path.write_text(json.dumps(cfg))

    def edit(b):
        b["configs"][0]["file"] = str(path)
    with pytest.raises(run.SpecError, match="no_such_reference"):
        run.load_cell("vacuum256.long", _bench_copy(tmp_path, edit))


def test_the_reference_is_the_one_the_config_names():
    spec = run.load_cell("vacuum256.long")
    assert spec["reference"].__file__ == os.path.join(
        BENCH, "reference", "yee.py")
    import re

    import checks
    with open(checks.__file__) as f:
        assert not re.search(r"^\s*(from|import)\s+reference", f.read(),
                             re.M)


def test_missing_config_fails(tmp_path):
    def edit(b):
        b["configs"][0]["file"] = "benchmark/configs/nothing.json"
    with pytest.raises(run.SpecError, match="config"):
        run.load_cell("vacuum256.long", _bench_copy(tmp_path, edit))


class _OneOpTrace:
    """A trace with one 10 us op, which is the chunk and the kernel."""

    def devices(self):
        return ["/device:TPU:0"]

    def pace_device(self, keep=None):
        return "/device:TPU:0"

    def busiest_module(self, dev):
        return "m"

    def module_runs(self, dev, name=None):
        return [(0.0, 10.0, "m")]

    def ops_within(self, dev, runs):
        return [(0.0, 10.0, "packed-kernel-tb.1", "k", "")]

    def ops_where(self, dev, keep):
        return [(0.0, 10.0)] if keep("packed-kernel-tb.1", "") else []

    union_s = staticmethod(tracing.Trace.union_s)


def _roofline_ctx(device_kind, step_kind):
    ctx = _ctx("vacuum256", "pallas_packed_tb", 4)
    ctx.update(trace=_OneOpTrace(), steps=1, device_kind=device_kind,
               peaks=json.load(open(os.path.join(BENCH, "peaks.json"))))
    ctx["step_kind"] = step_kind
    return ctx


@pytest.mark.parametrize("metric", ("step_roofline",
                                    "pallas_packed_tb_roofline"))
def test_unknown_device_kind_is_an_error(metric):
    mod = run.load_module(run.reader_path(metric), metric)
    with pytest.raises(RuntimeError, match="peaks.json"):
        mod.read(_roofline_ctx("TPU v99", "pallas_packed_tb"))
    assert mod.read(_roofline_ctx("TPU v5 lite", "pallas_packed_tb")) > 0


@pytest.mark.parametrize("metric", ("step_roofline", "kernel_ms_per_step"))
def test_unknown_step_kind_is_an_error(metric):
    mod = run.load_module(run.reader_path(metric), metric)
    with pytest.raises(RuntimeError, match="byte count"):
        mod.read(_roofline_ctx("TPU v5 lite", "no_such_kind"))


@pytest.mark.parametrize("metric", ("step_roofline",
                                    "pallas_packed_tb_roofline"))
def test_chips_share_the_grids_bytes(metric):
    """Split over four shards, each chip moves a quarter of the grid's
    bytes in the time the trace gives: the same trace reads a quarter
    of the share."""
    mod = run.load_module(run.reader_path(metric), metric)
    one = mod.read(_roofline_ctx("TPU v5 lite", "pallas_packed_tb"))
    four = mod.read(dict(_roofline_ctx("TPU v5 lite", "pallas_packed_tb"),
                         shards=4))
    assert four == pytest.approx(one / 4, rel=1e-12)


def test_a_kernel_roofline_is_silent_without_its_kernel():
    mod = run.load_module(run.reader_path("pallas_packed_roofline"), "r")
    assert mod.read(_roofline_ctx("TPU v5 lite", "pallas_packed_tb")) is None


def test_a_split_metric_uses_its_base_reader():
    assert run.reader_path("device_idle_share.io") == os.path.join(
        BENCH, "metrics", "device_idle_share.py")
    assert run.reader_path("no_such.metric").endswith("no_such.py")


def _two_device_events():
    """A window from 0 to 10,000 us on two devices. Each runs the chunk
    module ``jit_fdtd3d_chunk(7)`` once, device 0 for 1,000 us and
    device 1 for 3,000 us, and a small executable (400 us on device 0,
    more than device 1 spends in it). An NTFF sample span covers the
    start of the chunk on both."""
    ev = [{"ph": "M", "name": "process_name", "pid": 9,
           "args": {"name": "/host:CPU"}},
          {"ph": "X", "pid": 9, "tid": 1, "ts": 0.0, "dur": 10000.0,
           "name": tracing.WINDOW},
          {"ph": "X", "pid": 9, "tid": 1, "ts": 1000.0, "dur": 100.0,
           "name": "fdtd3d/ntff-sample"}]
    for pid, chunk_us, small_us in ((1, 1000.0, 400.0),
                                    (2, 3000.0, 200.0)):
        ev += [{"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": f"/device:TPU:{pid - 1}"}},
               {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
                "args": {"name": "XLA Modules"}},
               {"ph": "M", "name": "thread_name", "pid": pid, "tid": 2,
                "args": {"name": "XLA Ops"}},
               {"ph": "X", "pid": pid, "tid": 1, "ts": 1000.0,
                "dur": chunk_us, "name": "jit_fdtd3d_chunk(7)"},
               {"ph": "X", "pid": pid, "tid": 2, "ts": 1000.0,
                "dur": chunk_us, "name": "fusion.1",
                "args": {"hlo_category": "loop fusion"}},
               {"ph": "X", "pid": pid, "tid": 1, "ts": 6000.0,
                "dur": small_us, "name": "jit_norms(3)"},
               {"ph": "X", "pid": pid, "tid": 2, "ts": 6000.0,
                "dur": small_us, "name": "reduce.2",
                "args": {"hlo_category": "reduce"}}]
    return ev


def test_readers_on_a_two_device_trace(monkeypatch):
    """With several devices the readers read the one whose chunk
    program ran longest (device 1 here), not the first."""
    from metrics import _chunks, _scopes
    tr = tracing.Trace(_two_device_events())
    assert tr.devices() == ["/device:TPU:0", "/device:TPU:1"]
    assert tr.pace_device() == "/device:TPU:1"
    assert tr.pace_device(lambda n: n.startswith("jit_norms")) == \
        "/device:TPU:0"
    ctx = {"trace": tr, "steps": 2, "chunks": 1}
    assert _chunks.device(ctx) == "/device:TPU:1"
    step_ms = run.load_module(run.reader_path("step_ms"), "step_ms")
    assert step_ms.read(ctx) == pytest.approx(3000e-3 / 2)
    ntff = run.load_module(run.reader_path("ntff_ms_per_sample"), "ntff")
    assert ntff.read(ctx) == pytest.approx(3.0)
    monkeypatch.setattr(_scopes, "table",
                        lambda: {"fusion.1": ("tfsf", frozenset())})
    assert _scopes.class_s(ctx, ("tfsf",)) == pytest.approx(3000e-6)
    ops = dict(tr.breakdown()["device_ops"])
    assert ops["fusion.1"] == pytest.approx(3000e-6)
    # one device: the readers read it, as before
    one = tracing.Trace([e for e in _two_device_events()
                         if e.get("pid") != 2])
    assert one.pace_device() == "/device:TPU:0"
    assert step_ms.read(dict(ctx, trace=one)) == pytest.approx(0.5)


def test_missing_limit_file_fails(tmp_path):
    def edit(b):
        b["workloads"][0]["name"] = "vacuum256.other"
    with pytest.raises(run.SpecError, match="limits"):
        run.load_cell("vacuum256.other", _bench_copy(tmp_path, edit))


# -- BENCHMARK.json keeps to the contract's shape ---------------------------

def test_benchmark_json_names_units_and_links():
    import re
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in cells.values():
        assert name.match(w["name"]) and w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        # every cell reports setup_s, another e2e metric, a per-layer one
        rep = [m for m in b["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(rep) >= 2
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmark/")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert os.path.isfile(run.reader_path(m["name"]))
    for w in cells:
        assert os.path.isfile(os.path.join(BENCH, "limits", w + ".json"))
    assert len(json.dumps(b)) < 64 * 1024
