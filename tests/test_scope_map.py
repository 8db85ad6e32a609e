"""The program's own names for device ops and set-up time: the host span
counters (telemetry.span_totals), the scope map of the chunk executable
(telemetry.op_scopes), the program's gauges (telemetry.gauges) and the
benchmark readers built on them (benchmark/metrics/)."""

import os
import sys

import jax
import pytest

from fdtd3d_tpu import cli, telemetry
from fdtd3d_tpu.sim import Simulation

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import tracing  # noqa: E402
from metrics import (_scopes, coeffs_builds, coeffs_s,  # noqa: E402
                     dump_write_ms, per_chunk_ms, tfsf_line_samples,
                     tfsf_scope_ms_per_step)

ARGS = ["--3d", "--same-size", "16", "--time-steps", "4", "--use-pml",
        "--pml-size", "3", "--use-tfsf", "--tfsf-margin", "3",
        "--angle-teta", "0", "--angle-phi", "0", "--angle-psi", "0",
        "--check-finite"]


def _sim():
    cfg = cli.args_to_config(cli.build_parser().parse_args(ARGS))
    return Simulation(cfg)


@pytest.fixture(scope="module")
def stepped():
    """A small TFSF+CPML sim built from fresh counters and advanced one
    chunk: -> (sim, span totals of the build, op_scopes()). The
    registry of chunk executables starts empty, so that op_scopes()
    holds this sim's chunk alone and not the executables that other
    tests of the same process compiled under the same module name."""
    telemetry.reset_span_totals()
    telemetry._EXECUTABLES.clear()
    telemetry._PARSED.clear()
    sim = _sim()
    built = telemetry.span_totals()
    sim.advance(1)
    return sim, built, telemetry.op_scopes()


# -- span counters -------------------------------------------------------

def test_span_totals_count_nested_and_repeated_spans():
    telemetry.reset_span_totals()
    with telemetry.span("outer-test"):
        with telemetry.span("inner-test"):
            pass
        with telemetry.span("inner-test"):
            pass
    with telemetry.span("outer-test"):
        pass
    tot = telemetry.span_totals()
    assert tot["outer-test"]["count"] == 2
    assert tot["inner-test"]["count"] == 2
    assert tot["outer-test"]["seconds"] >= tot["inner-test"]["seconds"] > 0
    telemetry.reset_span_totals()
    assert telemetry.span_totals() == {}


def test_span_counts_when_its_body_raises():
    telemetry.reset_span_totals()
    with pytest.raises(ValueError):
        with telemetry.span("raising-test"):
            raise ValueError("inside")
    assert telemetry.span_totals()["raising-test"]["count"] == 1


def test_span_totals_is_a_copy():
    telemetry.reset_span_totals()
    with telemetry.span("copy-test"):
        pass
    snap = telemetry.span_totals()
    snap["copy-test"]["count"] = 99
    assert telemetry.span_totals()["copy-test"]["count"] == 1


def test_setup_spans_of_a_simulation_build(stepped):
    _, built, _ = stepped
    assert built["build-coeffs"]["count"] >= 1
    assert built["build-coeffs"]["seconds"] > 0
    for name in ("sim-init", "build-static", "coeffs-put", "init-state",
                 "make-runner"):
        assert built[name]["count"] == 1, name
    # the top-level span holds its children
    parts = sum(built[n]["seconds"] for n in
                ("coeffs-put", "init-state", "make-runner"))
    assert built["sim-init"]["seconds"] >= parts
    assert set(built) <= set(telemetry.HOST_SPANS)


def test_norms_and_io_spans(stepped, tmp_path):
    from fdtd3d_tpu import diag, io
    sim = stepped[0]
    telemetry.reset_span_totals()
    diag.field_norms(sim)
    sim.cfg.output.save_dir = str(tmp_path)
    sim.cfg.output.formats = ("dat",)
    io.write_outputs(sim, sim.t)
    tot = telemetry.span_totals()
    for name in ("norms", "io-gather", "io-write"):
        assert tot[name]["count"] == 1, name
    assert os.listdir(tmp_path)


def test_run_loop_spans(stepped):
    sim = stepped[0]
    seen = []
    telemetry.reset_span_totals()
    sim.run(time_steps=2, on_interval=lambda s: seen.append(s.t),
            interval=1)
    tot = telemetry.span_totals()
    assert seen == [2, 3]
    assert tot["advance"]["count"] == tot["on-interval"]["count"] == 2
    assert tot["advance"]["seconds"] >= tot["chunk"]["seconds"]


# -- scope map -----------------------------------------------------------

def test_op_scopes_names_the_chunk_module(stepped):
    scopes = stepped[2]
    assert any(m.startswith("jit_fdtd3d_chunk") for m in scopes)
    assert _scopes.CHUNK in scopes


def test_op_scopes_maps_tfsf_instructions_to_tfsf(stepped):
    sim, _, scopes = stepped
    text = sim._compiled[1].as_text()
    table = scopes[_scopes.CHUNK]
    tfsf_ops = [line.split("=")[0].split()[-1].lstrip("%")
                for line in text.splitlines()
                if "fdtd3d/tfsf/" in line and "=" in line
                and line.startswith("  ")]
    assert tfsf_ops
    for name in tfsf_ops:
        assert table[name][0] == "tfsf", (name, table[name])


def test_op_scopes_marks_a_fusion_of_two_scopes_mixed(stepped):
    table = stepped[2][_scopes.CHUNK]
    mixed = {n: e for n, e in table.items()
             if _scopes.classify(e) == _scopes.MIXED}
    assert mixed
    assert all(len(set(e[1]) | ({e[0]} - {None})) > 1
               for e in mixed.values())
    assert any("fusion" in n for n in mixed)


def test_parse_hlo_scopes_on_a_two_scope_fusion():
    def f(x):
        with telemetry.named("tfsf"):
            a = x * 2.0
        with telemetry.named("health"):
            return a + 1.0
    text = jax.jit(f).lower(jax.numpy.ones((64,))).compile().as_text()
    table = telemetry.parse_hlo_scopes(text)["jit_f"]
    fusions = [e for n, e in table.items() if "fusion" in n]
    assert fusions
    assert any(e[1] == frozenset({"tfsf", "health"}) for e in fusions)
    assert _scopes.classify(fusions[0]) == _scopes.MIXED


def test_op_scopes_registry_is_bounded():
    before = dict(telemetry._EXECUTABLES)
    try:
        for i in range(telemetry.EXECUTABLES_CAP + 3):
            telemetry.remember_executable(f"test-{i}", object())
        assert len(telemetry._EXECUTABLES) == telemetry.EXECUTABLES_CAP
        assert "test-0" not in telemetry._EXECUTABLES
    finally:
        telemetry._EXECUTABLES.clear()
        telemetry._EXECUTABLES.update(before)


class _Executable:
    """An executable whose HLO text is given, or cannot be had."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        if self.text is None:
            raise NotImplementedError("no HLO kept")
        return self.text


def test_op_scopes_skips_an_executable_without_hlo(monkeypatch):
    monkeypatch.setattr(telemetry, "_EXECUTABLES", {})
    monkeypatch.setattr(telemetry, "_PARSED", {})
    text = ("HloModule jit_fake, is_scheduled=true\n\n"
            "ENTRY %main (p: f32[4]) -> f32[4] {\n"
            "  %p = f32[4]{0} parameter(0)\n"
            "  ROOT %neg.1 = f32[4]{0} negate(%p), "
            'metadata={op_name="jit(f)/fdtd3d/tfsf/neg"}\n'
            "}\n")
    telemetry.remember_executable("text", _Executable(text))
    telemetry.remember_executable("none", _Executable(None))
    telemetry.remember_executable("empty", _Executable(""))
    assert telemetry.op_scopes() == {
        "jit_fake": {"p": (None, frozenset()),
                     "neg.1": ("tfsf", frozenset())}}


def test_op_scopes_merges_executables_of_one_module(monkeypatch):
    monkeypatch.setattr(telemetry, "_EXECUTABLES", {})
    monkeypatch.setattr(telemetry, "_PARSED", {})

    def text(scope, extra=""):
        return ("HloModule jit_fake, is_scheduled=true\n\n"
                "ENTRY %main (p: f32[4]) -> f32[4] {\n"
                "  %p = f32[4]{0} parameter(0)\n" + extra +
                "  ROOT %neg.1 = f32[4]{0} negate(%p), "
                f'metadata={{op_name="jit(f)/fdtd3d/{scope}/neg"}}\n'
                "}\n")
    telemetry.remember_executable("n1", _Executable(text("tfsf")))
    telemetry.remember_executable("n2", _Executable(text(
        "health", '  %abs.2 = f32[4]{0} abs(%p), '
                  'metadata={op_name="jit(f)/fdtd3d/health/abs"}\n')))
    table = telemetry.op_scopes()["jit_fake"]
    assert table["neg.1"] == (None, frozenset({"tfsf", "health"}))
    assert _scopes.classify(table["neg.1"]) == _scopes.MIXED
    assert table["abs.2"] == ("health", frozenset())


def test_classify():
    assert _scopes.classify(None) is None
    assert _scopes.classify((None, frozenset())) is None
    assert _scopes.classify(("tfsf", frozenset())) == "tfsf"
    assert _scopes.classify(("tfsf", frozenset({"tfsf"}))) == "tfsf"
    assert _scopes.classify((None, frozenset({"health"}))) == "health"
    assert _scopes.classify(("cpml", frozenset({"E-update"}))) == "mixed"


# -- the readers on a synthetic trace and scope map ----------------------

DEV, HOST = 1, 2
TABLE = {"fusion.1": ("tfsf", frozenset({"tfsf"})),
         "gather.2": ("tfsf", frozenset()),
         "fusion.3": ("health", frozenset()),
         "fusion.4": ("prepare", frozenset()),
         "fusion.5": ("tfsf", frozenset({"tfsf", "health"})),
         "copy.6": (None, frozenset())}


def _events(module="jit_fdtd3d_chunk(42)", io_write=True):
    ev = [{"ph": "M", "name": "process_name", "pid": DEV,
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "name": "thread_name", "pid": DEV, "tid": 1,
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "name": "thread_name", "pid": DEV, "tid": 2,
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "name": "process_name", "pid": HOST,
           "args": {"name": "/host:CPU"}},
          {"ph": "X", "pid": HOST, "tid": 9, "ts": 0.0, "dur": 10000.0,
           "name": tracing.WINDOW},
          {"ph": "X", "pid": DEV, "tid": 1, "ts": 1000.0, "dur": 2000.0,
           "name": module}]

    def op(name, ts, dur, cat="loop fusion"):
        ev.append({"ph": "X", "pid": DEV, "tid": 2, "ts": ts, "dur": dur,
                   "name": name, "args": {"hlo_category": cat}})
    op("while.9", 1000.0, 1900.0, "while")
    op("fusion.4", 1000.0, 100.0)     # prepare
    op("fusion.1", 1100.0, 300.0)     # tfsf
    op("gather.2", 1400.0, 100.0)     # tfsf
    op("fusion.5", 1500.0, 50.0)      # mixed
    op("copy.6", 1550.0, 50.0)        # unscoped
    op("fusion.3", 2900.0, 100.0)     # health
    op("fusion.1", 5000.0, 300.0)     # outside the chunk module
    if io_write:
        for ts in (4000.0, 6000.0):
            ev.append({"ph": "X", "pid": HOST, "tid": 9, "ts": ts,
                       "dur": 500.0 if ts < 5000 else 700.0,
                       "name": "fdtd3d/io-write"})
    return ev


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(telemetry, "op_scopes",
                        lambda: {"jit_fdtd3d_chunk": dict(TABLE)})
    monkeypatch.setattr(telemetry, "span_totals", lambda: {
        "build-coeffs": {"count": 3, "seconds": 2.5}})


def _ctx(**kw):
    return {"trace": tracing.Trace(_events(**kw)), "steps": 2,
            "chunks": 1}


def test_readers_on_a_synthetic_trace(program):
    ctx = _ctx()
    assert tfsf_scope_ms_per_step.read(ctx) == pytest.approx(0.4 / 2)
    assert per_chunk_ms.read(ctx) == pytest.approx(0.2)
    assert coeffs_s.read(ctx) == 2.5
    assert coeffs_builds.read(ctx) == 3
    assert dump_write_ms.read(ctx) == pytest.approx(0.6)
    classes = {c for _, _, c in _scopes.chunk_ops(ctx)}
    assert classes == {"tfsf", "prepare", "health", "mixed", None}


def test_readers_without_the_chunk_module(program):
    ctx = _ctx(module="jit__unknown(42)")
    assert tfsf_scope_ms_per_step.read(ctx) is None
    assert per_chunk_ms.read(ctx) is None


def test_readers_without_the_programs_names(monkeypatch):
    monkeypatch.delattr(telemetry, "op_scopes")
    monkeypatch.delattr(telemetry, "span_totals")
    ctx = _ctx(io_write=False)
    for reader in (tfsf_scope_ms_per_step, per_chunk_ms, coeffs_s,
                   coeffs_builds, dump_write_ms):
        assert reader.read(ctx) is None, reader.__name__


def test_readers_without_their_ops_or_spans(monkeypatch):
    monkeypatch.setattr(telemetry, "op_scopes",
                        lambda: {"jit_fdtd3d_chunk": {
                            "copy.6": (None, frozenset())}})
    monkeypatch.setattr(telemetry, "span_totals", lambda: {})
    ctx = _ctx(io_write=False)
    for reader in (tfsf_scope_ms_per_step, per_chunk_ms, coeffs_s,
                   coeffs_builds, dump_write_ms):
        assert reader.read(ctx) is None, reader.__name__


@pytest.mark.parametrize("gauges,want", [
    (None, None),                             # a program without gauges
    ({}, None),                               # the gauge never set
    ({"tfsf-line-samples": 1028.0}, 1028.0),
])
def test_line_samples_reader(monkeypatch, gauges, want):
    if gauges is None:
        monkeypatch.delattr(telemetry, "gauges")
    else:
        monkeypatch.setattr(telemetry, "gauges", lambda: dict(gauges))
    assert tfsf_line_samples.read(_ctx()) == want


def test_gauges_are_set_not_summed(monkeypatch):
    monkeypatch.setattr(telemetry, "_GAUGES", {})
    telemetry.set_gauge("tfsf-line-samples", 100)
    telemetry.set_gauge("tfsf-line-samples", 100)
    snap = telemetry.gauges()
    assert snap == {"tfsf-line-samples": 100.0}
    snap["tfsf-line-samples"] = 0.0
    assert telemetry.gauges()["tfsf-line-samples"] == 100.0
