"""TFSF plane-wave injection tests (3D containment, oblique incidence).

The scattered-field region outside the TFSF box must stay clean in vacuum:
machine-precision clean for axis-aligned incidence (the 1D line and the
grid share the same discrete dispersion along an axis), and below the
standard interpolation/dispersion floor (~-45 dB) for oblique incidence.
The incident line is sampled only along the axes the plane wave varies
on, bit for bit as the full face plane was, and the chunk program's
``tfsf-line-samples`` gauge counts the positions.
"""

import numpy as np
import pytest

from fdtd3d_tpu.config import SimConfig, TfsfConfig
from fdtd3d_tpu.sim import Simulation


def _scattered_max(field, shell):
    return max(
        np.abs(field[:shell]).max(), np.abs(field[-shell:]).max(),
        np.abs(field[:, :shell]).max(), np.abs(field[:, -shell:]).max(),
        np.abs(field[:, :, :shell]).max(), np.abs(field[:, :, -shell:]).max())


def test_3d_normal_incidence_containment():
    cfg = SimConfig(
        scheme="3D", size=(40, 40, 40), time_steps=60, dx=1e-3,
        courant_factor=0.5, wavelength=15e-3,
        tfsf=TfsfConfig(enabled=True, margin=(10, 10, 10),
                        angle_teta=0.0, angle_phi=0.0, angle_psi=0.0))
    sim = Simulation(cfg)
    sim.run()
    ex = sim.field("Ex")
    inside = np.abs(ex[12:28, 12:28, 12:28]).max()
    assert inside > 0.1, "incident wave did not enter the box"
    leak = _scattered_max(ex, 8)
    assert leak < 1e-6 * inside, f"leak {leak} vs inside {inside}"


def test_3d_oblique_incidence_contained_below_dispersion_floor():
    cfg = SimConfig(
        scheme="3D", size=(40, 40, 40), time_steps=80, dx=1e-3,
        courant_factor=0.5, wavelength=15e-3,
        tfsf=TfsfConfig(enabled=True, margin=(10, 10, 10),
                        angle_teta=45.0, angle_phi=30.0, angle_psi=20.0))
    sim = Simulation(cfg)
    sim.run()
    leak, inside = 0.0, 0.0
    for comp in ("Ex", "Ey", "Ez"):
        f = sim.field(comp)
        inside = max(inside, np.abs(f[12:28, 12:28, 12:28]).max())
        leak = max(leak, _scattered_max(f, 8))
    assert inside > 0.05
    assert leak < 2e-2 * inside, f"oblique leak {leak} vs inside {inside}"


def test_2d_tmz_tfsf_containment():
    cfg = SimConfig(
        scheme="2D_TMz", size=(48, 48, 1), time_steps=70, dx=1e-3,
        courant_factor=0.5, wavelength=15e-3,
        tfsf=TfsfConfig(enabled=True, margin=(12, 12, 0),
                        angle_teta=90.0, angle_phi=0.0, angle_psi=180.0))
    sim = Simulation(cfg)
    sim.run()
    ez = sim.field("Ez")[:, :, 0]
    inside = np.abs(ez[14:34, 14:34]).max()
    leak = max(np.abs(ez[:10]).max(), np.abs(ez[-10:]).max(),
               np.abs(ez[:, :10]).max(), np.abs(ez[:, -10:]).max())
    assert inside > 0.1
    assert leak < 1e-5 * inside, f"leak {leak} vs inside {inside}"


# -- the incident-line sampler -------------------------------------------

ANGLES = ((0.0, 0.0, 0.0), (90.0, 90.0, 0.0), (30.0, 40.0, 15.0))


def _setup(angles, n=24, **kw):
    """(static, coeffs as device arrays, a random f32 incident line)."""
    import jax.numpy as jnp

    from fdtd3d_tpu import solver
    from fdtd3d_tpu.config import PmlConfig
    cfg = SimConfig(
        scheme="3D", size=(n, n, n), time_steps=4, dx=1e-3,
        courant_factor=0.5, wavelength=15e-3, pml=PmlConfig(size=(4, 4, 4)),
        tfsf=TfsfConfig(enabled=True, margin=(3, 3, 3),
                        angle_teta=angles[0], angle_phi=angles[1],
                        angle_psi=angles[2]), **kw)
    static = solver.build_static(cfg)
    coeffs = {k: jnp.asarray(v) for k, v in
              solver.build_coeffs(static).items()}
    rng = np.random.default_rng(7)
    n_inc = static.tfsf_setup.n_inc
    inc = {k: jnp.asarray(rng.standard_normal(n_inc), jnp.float32)
           for k in ("Einc", "Hinc")}
    return static, coeffs, inc


def _full_plane_term(corr, setup, coeffs, inc, active_axes, dx):
    """The sampling as it was before the sampler followed khat: zeta
    summed over every transverse active axis, so every position of the
    face plane is interpolated."""
    import jax.numpy as jnp

    from fdtd3d_tpu.layout import YEE_OFFSETS, component_axis
    from fdtd3d_tpu.ops import tfsf
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    rdt = jnp.real(inc["Einc"]).dtype
    off = YEE_OFFSETS[corr.src]
    zeta = setup.zeta0 + setup.khat[corr.axis] * (
        corr.pos_a - setup.origin[corr.axis])
    zeta = jnp.asarray(zeta, dtype=rdt)
    for b in range(3):
        if b == corr.axis or b not in active_axes:
            continue
        pb = gs[b].astype(rdt) + off[b]
        shape = [1, 1, 1]
        shape[b] = pb.shape[0]
        zeta = zeta + jnp.asarray(setup.khat[b], rdt) * (
            pb - jnp.asarray(setup.origin[b], rdt)).reshape(shape)
    if corr.src[0] == "E":
        val = tfsf._interp_line(inc["Einc"], zeta)
        pol = setup.ehat[component_axis(corr.src)]
    else:
        val = tfsf._interp_line(inc["Hinc"], zeta - 0.5)
        pol = setup.hhat[component_axis(corr.src)]
    if abs(pol) < tfsf.POL_EPS:
        return None
    gate = tfsf._corr_gate_transverse(corr, setup, gs, active_axes,
                                      val.dtype)
    term = jnp.asarray(corr.sign * pol / dx, rdt) * val
    return term if gate is None else term * gate


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    shape = np.broadcast_shapes(a.shape, b.shape)
    a, b = np.broadcast_to(a, shape), np.broadcast_to(b, shape)
    return a.dtype == b.dtype and np.array_equal(
        a.view(np.uint32), b.view(np.uint32))


def _transverse_extents(val):
    return sum(1 for d in np.shape(val) if d > 1)


@pytest.mark.parametrize("angles", ANGLES)
def test_sampler_matches_the_full_plane_sampling_bit_for_bit(angles):
    from fdtd3d_tpu.ops import pallas3d, tfsf
    static, coeffs, inc = _setup(angles)
    setup, mode = static.tfsf_setup, static.mode
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    live = 0
    for corr in setup.corrections:
        want = _full_plane_term(corr, setup, coeffs, inc,
                                mode.active_axes, static.dx)
        got = tfsf.corr_plane_term(corr, setup, coeffs, inc,
                                   mode.active_axes, static.dx)
        assert (got is None) == (want is None), corr
        if got is None:
            continue
        live += 1
        assert got.shape == want.shape, corr
        assert _same_bits(got, want), corr
        extents = _transverse_extents(tfsf._sample_incident(
            corr, setup, gs, inc, mode.active_axes))
        varying = [b for b in mode.active_axes if b != corr.axis
                   and abs(setup.khat[b]) >= tfsf.POL_EPS]
        assert extents == len(varying), corr
        if angles == (0.0, 0.0, 0.0):
            # z-normal faces sample one value, x/y-normal faces a z row
            assert extents == (0 if corr.axis == 2 else 1), corr
        elif angles == (30.0, 40.0, 15.0):
            assert extents == 2, corr
    assert live == {ANGLES[0]: 8, ANGLES[1]: 8, ANGLES[2]: 24}[angles]
    # the packed kernels' patches are the same terms, face by face
    for field, comps in (("E", mode.e_components),
                         ("H", mode.h_components)):
        for comp in comps:
            want = [(c.axis, c.plane, _full_plane_term(
                c, setup, coeffs, inc, mode.active_axes, static.dx))
                for c in setup.corrections
                if c.field == field and c.comp == comp]
            want = [w for w in want if w[2] is not None]
            got = pallas3d.plane_corrections(field, comp, setup, coeffs,
                                             inc, mode.active_axes,
                                             static.dx)
            assert [g[:2] for g in got] == [w[:2] for w in want]
            for g, w in zip(got, want):
                assert _same_bits(g[2], w[2]), (field, comp, g[:2])


# -- the tfsf-line-samples gauge -------------------------------------------

@pytest.mark.parametrize("angles,kind", [
    ((0.0, 0.0, 0.0), "jnp"),
    ((0.0, 0.0, 0.0), "pallas_packed"),
    ((0.0, 0.0, 0.0), "pallas_packed_tb"),
    ((30.0, 40.0, 15.0), "jnp"),
    ((30.0, 40.0, 15.0), "pallas_packed"),
])
def test_line_samples_gauge(angles, kind, monkeypatch):
    import functools

    import jax

    from fdtd3d_tpu import solver, telemetry
    n = 24
    if kind == "pallas_packed":
        monkeypatch.setenv("FDTD3D_NO_TEMPORAL", "1")
    static, coeffs, _ = _setup(angles, n=n,
                               use_pallas=kind != "jnp")
    runner = solver.make_chunk_runner(static)
    assert runner.kind == kind
    state = solver.init_state(static)
    if getattr(runner, "packed", False):
        state = runner.pack(state)
    want = 4 + 4 * n if angles == (0.0, 0.0, 0.0) else 24 * n * n
    telemetry.set_gauge("tfsf-line-samples", -1.0)
    for _ in range(2):
        # a re-trace of the same step overwrites the gauge, never adds
        jax.eval_shape(functools.partial(runner, n=8), state, coeffs)
        assert telemetry.gauges()["tfsf-line-samples"] == want
    assert set(telemetry.gauges()) <= set(telemetry.GAUGES)
