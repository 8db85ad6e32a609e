"""Total-field / scattered-field (TFSF) plane-wave injection.

Reference parity: the TFSF source with 1D auxiliary incident grids and
oblique incidence (SURVEY.md §3.4 — ``performPlaneWave{E,H}Steps`` +
``YeeGridLayout``'s 3D-border-point -> 1D-line interpolation;
``--angle-teta/phi/psi``).

Mechanism (standard Taflove ch.5 consistency corrections, re-derived for
this codebase's accumulator formulation):

* A 1D incident line (Einc at integer positions, Hinc at half positions,
  spacing = dx) is leapfrogged each step with a hard source at cell 0 and a
  graded matched-loss absorbing tail at the far end.
* The total-field box is [lo_a, hi_a] per active axis in E-integer
  coordinates. Stored fields inside are total, outside scattered. Every
  curl difference that straddles the border is corrected by the incident
  value of the missing field, interpolated off the line at the straddling
  sample's own staggered position:

    E-update of comp c, curl term (axis a, H comp d, sign s):
      at g_a == lo_a : acc -= s * Hinc_d(pos_a = lo_a - 0.5) / dx
      at g_a == hi_a : acc += s * Hinc_d(pos_a = hi_a + 0.5) / dx
    H-update of comp c, curl term (axis a, E comp d, sign s):
      at g_a == lo_a - 1 : acc -= s * Einc_d(pos_a = lo_a) / dx
      at g_a == hi_a     : acc += s * Einc_d(pos_a = hi_a) / dx

  (acc is the curl accumulator later multiplied by +cb for E and -db for H.)

Shard-safety: every correction is (one-hot 1D mask along a) x (transverse
slab of interpolated incident values). Both are computed from the SHARDED
1D global-coordinate arrays in the coeffs pytree, so the same code runs
single-chip and under shard_map; the incident line itself is replicated.
The slab is sampled off the line only along the transverse axes khat
varies on (``_sample_incident``) and broadcast to the face plane by the
transverse box gate: at normal incidence a face samples one value or one
row of n, not n^2 positions.

Time alignment: Einc is advanced to t^{n+1} BEFORE the main E update (which
consumes Hinc at t^{n+1/2}); Hinc advances after the E update. This mirrors
the reference's performPlaneWaveESteps-before-field-steps ordering
(SURVEY.md §3.1).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from fdtd3d_tpu import physics
from fdtd3d_tpu.layout import (CURL_TERMS, YEE_OFFSETS, component_axis)
from fdtd3d_tpu.ops.sources import waveform

_TAIL = 24  # absorbing-tail length on the incident line, cells

# Polarization-projection cutoff: a correction whose ehat/hhat projection
# is below this is identically zero physics (an exact geometric zero
# blurred by the basis construction's f64 rounding) and is dropped. The
# SINGLE authority for that threshold — corr_plane_term/record_term_ds
# here (pallas3d.plane_corrections goes through corr_plane_term), and
# the packed-ds kernel's static record filter
# (pallas_packed_ds._corr_records) must all agree, or a
# record could be pre-filtered by one layer and then crash or silently
# vanish in another (advisor finding r5-2). A khat component below it
# is the same kind of zero: the f32 sampler skips its axis
# (_sample_incident).
POL_EPS = 1e-14


@dataclasses.dataclass(frozen=True)
class Correction:
    """One face-plane consistency correction (static descriptor)."""

    field: str        # "E" | "H": which update this correction belongs to
    comp: str         # component being updated (e.g. "Ez")
    axis: int         # derivative axis a
    plane: int        # global integer coordinate g_a of the corrected cells
    src: str          # incident component sampled (e.g. "Hy")
    sign: float       # +-s/dx premultiplied sign (without 1/dx)
    pos_a: float      # position along `axis` at which src is sampled (cells)
    mask_comp: str    # component whose TRANSVERSE box membership gates the
                      # correction: the updated comp for E-side (it must be
                      # total-field), the sampled E comp for H-side (the
                      # straddling sample must be total-field)


@dataclasses.dataclass(frozen=True)
class TfsfSetup:
    """Static TFSF geometry: box, incidence basis, line length, corrections."""

    lo: Tuple[int, int, int]
    hi: Tuple[int, int, int]
    khat: Tuple[float, float, float]
    ehat: Tuple[float, float, float]
    hhat: Tuple[float, float, float]
    origin: Tuple[float, float, float]
    zeta0: float            # guard offset added to projections (cells)
    n_inc: int              # incident-line length
    corrections: Tuple[Correction, ...]
    waveform: str
    amplitude: float


def _incidence_basis(teta_deg, phi_deg, psi_deg):
    """k/E/H unit vectors from the reference's teta/phi/psi angles."""
    th, ph, ps = (math.radians(v) for v in (teta_deg, phi_deg, psi_deg))
    khat = np.array([math.sin(th) * math.cos(ph),
                     math.sin(th) * math.sin(ph),
                     math.cos(th)])
    # Spherical unit vectors at (th, ph); for th == 0 they default to (x, y).
    theta_hat = np.array([math.cos(th) * math.cos(ph),
                          math.cos(th) * math.sin(ph),
                          -math.sin(th)])
    phi_hat = np.array([-math.sin(ph), math.cos(ph), 0.0])
    ehat = math.cos(ps) * theta_hat + math.sin(ps) * phi_hat
    hhat = np.cross(khat, ehat)
    return tuple(khat), tuple(ehat), tuple(hhat)


def build_setup(cfg, static) -> TfsfSetup:
    mode = static.mode
    shape = static.grid_shape
    lo, hi = [0, 0, 0], [0, 0, 0]
    for a in range(3):
        if a in mode.active_axes:
            pad = cfg.pml.size[a] + cfg.tfsf.margin[a]
            lo[a], hi[a] = pad, shape[a] - 1 - pad
            if hi[a] - lo[a] < 2:
                raise ValueError(f"TFSF box empty on axis {a}")
    khat, ehat, hhat = _incidence_basis(
        cfg.tfsf.angle_teta, cfg.tfsf.angle_phi, cfg.tfsf.angle_psi)
    # Wave must not propagate along an inactive axis component-wise:
    for a in range(3):
        if a not in mode.active_axes and abs(khat[a]) > 1e-12:
            raise ValueError(
                f"incidence direction has a component along inactive axis "
                f"{a} for scheme {mode.name}")
    origin = tuple(
        float(lo[a]) if khat[a] >= 0.0 else float(hi[a]) for a in range(3))
    zeta0 = 2.0  # guard so slightly-negative projections stay in range
    span = sum(abs(khat[a]) * (hi[a] - lo[a]) for a in mode.active_axes)
    n_inc = int(math.ceil(span + zeta0)) + 8 + _TAIL

    corrections: List[Correction] = []
    # E-update corrections (incident H sampled at half positions).
    for c in mode.e_components:
        ca = component_axis(c)
        for (a, d_axis, s) in CURL_TERMS[ca]:
            d = "H" + "xyz"[d_axis]
            if a not in mode.active_axes or d not in mode.h_components:
                continue
            corrections.append(Correction("E", c, a, lo[a], d, -s,
                                          lo[a] - 0.5, c))
            corrections.append(Correction("E", c, a, hi[a], d, +s,
                                          hi[a] + 0.5, c))
    # H-update corrections (incident E sampled at integer positions).
    for c in mode.h_components:
        ca = component_axis(c)
        for (a, d_axis, s) in CURL_TERMS[ca]:
            d = "E" + "xyz"[d_axis]
            if a not in mode.active_axes or d not in mode.e_components:
                continue
            corrections.append(Correction("H", c, a, lo[a] - 1, d, -s,
                                          float(lo[a]), d))
            corrections.append(Correction("H", c, a, hi[a], d, +s,
                                          float(hi[a]), d))
    return TfsfSetup(tuple(lo), tuple(hi), khat, ehat, hhat, origin, zeta0,
                     n_inc, tuple(corrections), cfg.tfsf.waveform,
                     cfg.tfsf.amplitude)


def line_loss_profiles(n_inc: int, dt: float, dx: float, dtype):
    """Matched graded-loss absorbing tail for the 1D incident line.

    In 1D a layer with sigma_m/mu0 == sigma_e/eps0 is perfectly matched at
    the continuous level; cubic grading keeps the discrete reflection tiny.
    Returns (ae, be, ah, bh): Einc = ae*Einc - be*dHinc ; likewise H.
    """
    sigma = np.zeros(n_inc, dtype=np.float64)
    d = (np.arange(n_inc) - (n_inc - 1 - _TAIL)) / _TAIL
    d = np.clip(d, 0.0, 1.0)
    smax = 4.0 / (physics.ETA0 * _TAIL * dx)  # ~R0 1e-5 at normal incidence
    sigma = smax * d ** 3
    se = sigma * dt / (2.0 * physics.EPS0)
    ae = ((1.0 - se) / (1.0 + se)).astype(dtype)
    be = ((dt / (physics.EPS0 * dx)) / (1.0 + se)).astype(dtype)
    # matched magnetic loss at half positions
    d_h = (np.arange(n_inc) + 0.5 - (n_inc - 1 - _TAIL)) / _TAIL
    d_h = np.clip(d_h, 0.0, 1.0)
    sh = (smax * d_h ** 3) * dt / (2.0 * physics.EPS0)  # sigma_m/mu = sig/eps
    ah = ((1.0 - sh) / (1.0 + sh)).astype(dtype)
    bh = ((dt / (physics.MU0 * dx)) / (1.0 + sh)).astype(dtype)
    return ae, be, ah, bh


def advance_einc(inc: Dict[str, jnp.ndarray], coeffs, t, dt, omega,
                 setup: TfsfSetup):
    """Einc^{n} -> Einc^{n+1} using Hinc^{n+1/2}; hard source at cell 0."""
    if "Einc_lo" in inc:
        return _advance_einc_ds(inc, coeffs, t, dt, omega, setup)
    einc, hinc = inc["Einc"], inc["Hinc"]
    dh = hinc - jnp.concatenate([jnp.zeros_like(hinc[:1]), hinc[:-1]])
    einc = coeffs["inc_ae"] * einc - coeffs["inc_be"] * dh
    # waveform time is REAL even in complex_fields mode
    src = setup.amplitude * waveform(
        setup.waveform, t, 1.0, omega, dt,
        np.dtype(jnp.real(einc).dtype).type)
    einc = einc.at[0].set(src.astype(einc.dtype))
    return dict(inc, Einc=einc)


def advance_hinc(inc: Dict[str, jnp.ndarray], coeffs, setup: TfsfSetup):
    """Hinc^{n+1/2} -> Hinc^{n+3/2} using Einc^{n+1}."""
    if "Einc_lo" in inc:
        return _advance_hinc_ds(inc, coeffs, setup)
    einc, hinc = inc["Einc"], inc["Hinc"]
    de = jnp.concatenate([einc[1:], jnp.zeros_like(einc[:1])]) - einc
    hinc = coeffs["inc_ah"] * hinc - coeffs["inc_bh"] * de
    return dict(inc, Hinc=hinc)


def _ds_line_diff(fh, fl, forward: bool):
    """Double-single neighbor difference on the 1D line (PEC ghost)."""
    from fdtd3d_tpu.ops import ds
    z = jnp.zeros_like(fh[:1])
    if forward:
        sh = jnp.concatenate([fh[1:], z])
        sl = jnp.concatenate([fl[1:], z])
        dh, de = ds.two_diff(sh, fh)
        dl = sl - fl
    else:
        sh = jnp.concatenate([z, fh[:-1]])
        sl = jnp.concatenate([z, fl[:-1]])
        dh, de = ds.two_diff(fh, sh)
        dl = fl - sl
    return ds.two_sum(dh, de + dl)


def _advance_einc_ds(inc, coeffs, t, dt, omega, setup: TfsfSetup):
    """float32x2 incident line: the line's own leapfrog must hold the
    same ~2^-47 accumulation class as the 3D fields it forces — its f32
    coefficient rounding was a measured linear-in-t drift source
    (BASELINE.md round-4 accuracy section)."""
    from fdtd3d_tpu.ops import ds
    eh, el = inc["Einc"], inc["Einc_lo"]
    dh_h, dh_l = _ds_line_diff(inc["Hinc"], inc["Hinc_lo"], forward=False)
    t1 = ds.mul_ff(eh, el, coeffs["inc_ae"], coeffs["inc_ae_lo"])
    t2 = ds.mul_ff(dh_h, dh_l, coeffs["inc_be"], coeffs["inc_be_lo"])
    eh, el = ds.sub_ff(*t1, *t2)
    from fdtd3d_tpu.ops.sources import waveform_ds
    sh, sl = waveform_ds(setup.waveform, t, 1.0, omega, dt)
    ah, al = ds.from_f64(np.float64(setup.amplitude))
    sh, sl = ds.mul_ff(sh, sl, jnp.float32(ah), jnp.float32(al))
    eh = eh.at[0].set(sh)
    el = el.at[0].set(sl)
    return dict(inc, Einc=eh, Einc_lo=el)


def _advance_hinc_ds(inc, coeffs, setup: TfsfSetup):
    from fdtd3d_tpu.ops import ds
    hh, hl = inc["Hinc"], inc["Hinc_lo"]
    de_h, de_l = _ds_line_diff(inc["Einc"], inc["Einc_lo"], forward=True)
    t1 = ds.mul_ff(hh, hl, coeffs["inc_ah"], coeffs["inc_ah_lo"])
    t2 = ds.mul_ff(de_h, de_l, coeffs["inc_bh"], coeffs["inc_bh_lo"])
    hh, hl = ds.sub_ff(*t1, *t2)
    return dict(inc, Hinc=hh, Hinc_lo=hl)


# Trace-time tallies of incident-line positions (count_line_samples).
_SAMPLE_TALLIES: List[List[int]] = []


@contextlib.contextmanager
def count_line_samples():
    """Count the incident-line positions sampled by the code traced in
    the block: yields a one-element list that holds the count. The
    chunk runner reads it per traced step (the ``tfsf-line-samples``
    gauge)."""
    tally = [0]
    _SAMPLE_TALLIES.append(tally)
    try:
        yield tally
    finally:
        _SAMPLE_TALLIES.remove(tally)


def _tally_samples(u) -> None:
    for tally in _SAMPLE_TALLIES:
        tally[0] += int(np.size(u))


def _interp_line(line: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Linear interpolation of the 1D line at fractional index u (clipped)."""
    _tally_samples(u)
    u = jnp.clip(u, 0.0, line.shape[0] - 1.001)
    i0 = jnp.floor(u).astype(jnp.int32)
    w = (u - i0.astype(u.dtype))
    return (1.0 - w) * jnp.take(line, i0) + w * jnp.take(line, i0 + 1)


def _corr_gate_transverse(corr: Correction, setup: TfsfSetup, gs,
                          active_axes, dtype):
    """Staggered transverse box membership (no normal-axis onehot) as a
    broadcastable 0/1 mask, or None when no transverse axis is active.
    THE single authority for which cells a correction touches
    transversely — consumed by corr_plane_term (f32: the jnp step AND
    the temporal-blocked kernel's plane-value operands and
    pallas3d.plane_corrections' patches), by record_term_ds
    (float32x2), and by consumers that carry the normal plane index
    statically (the packed-ds kernel's per-plane records) — so the
    box-membership rule (half-offset components occupy [lo, hi-1])
    can never drift between paths."""
    gate = None
    m_off = YEE_OFFSETS[corr.mask_comp]
    for b in range(3):
        if b == corr.axis or b not in active_axes:
            continue
        hi_b = setup.hi[b] - 1 if m_off[b] == 0.5 else setup.hi[b]
        ind = (gs[b] >= setup.lo[b]) & (gs[b] <= hi_b)
        shape_b = [1, 1, 1]
        shape_b[b] = ind.shape[0]
        ind = ind.reshape(shape_b).astype(dtype)
        gate = ind if gate is None else gate * ind
    return gate


def _projection(corr: Correction, setup: TfsfSetup) -> float:
    """The incidence polarization's projection on ``corr``'s sampled
    component (a correction below POL_EPS is dropped)."""
    pol = setup.ehat if corr.src[0] == "E" else setup.hhat
    return pol[component_axis(corr.src)]


def _sample_incident(corr: Correction, setup: TfsfSetup, gs,
                     inc: Dict[str, jnp.ndarray], active_axes):
    """The incident value ``corr`` reads off the f32 line at its
    staggered sample positions, sampled only along the transverse
    axes the plane wave varies on: a scalar, or an array with a
    non-unit extent on each transverse active axis b where
    |khat[b]| >= POL_EPS, broadcastable to the face plane.

    Skipping a smaller khat[b] leaves the f32 line coordinate zeta
    unchanged bit for bit: khat[b] * (p_b - origin_b) is then below
    half an ulp of zeta >= 1 (the zeta0 guard), so the sum rounds back
    to zeta."""
    # zeta is a REAL line coordinate even when the fields are complex
    # (complex_fields mode): interpolation clips/floors it.
    rdt = jnp.real(inc["Einc"]).dtype
    off = YEE_OFFSETS[corr.src]
    zeta = setup.zeta0 + setup.khat[corr.axis] * (
        corr.pos_a - setup.origin[corr.axis])
    zeta = jnp.asarray(zeta, dtype=rdt)
    for b in range(3):
        if b == corr.axis or b not in active_axes \
                or abs(setup.khat[b]) < POL_EPS:
            continue
        pb = gs[b].astype(rdt) + off[b]
        shape = [1, 1, 1]
        shape[b] = pb.shape[0]
        # khat/origin are strong-typed f64 scalars: cast to rdt so
        # an f32 run stays f32 even with jax_enable_x64 on
        zeta = zeta + jnp.asarray(setup.khat[b], rdt) * (
            pb - jnp.asarray(setup.origin[b], rdt)).reshape(shape)
    if corr.src[0] == "E":
        return _interp_line(inc["Einc"], zeta)
    # Hinc samples live at half positions on the line.
    return _interp_line(inc["Hinc"], zeta - 0.5)


def corr_plane_term(corr: Correction, setup: TfsfSetup, coeffs,
                    inc: Dict[str, jnp.ndarray], active_axes,
                    dx: float) -> Optional[jnp.ndarray]:
    """ONE correction's accumulator term on its face plane — the
    transverse box gate applied but WITHOUT the normal-axis onehot —
    or None when the polarization projection vanishes (POL_EPS).

    The single authority for the per-correction f32 math (the ds twin
    is record_term_ds): corrections_for consumes it through the
    normal-axis onehot, pallas3d.plane_corrections as plane patches,
    and the temporal-blocked kernel (ops/pallas_packed_tb.py) directly
    as per-generation plane-value operands, carrying the plane index
    statically — so the jnp and in-kernel paths cannot drift."""
    pol = _projection(corr, setup)
    if abs(pol) < POL_EPS:
        return None
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    val = _sample_incident(corr, setup, gs, inc, active_axes)
    gate = _corr_gate_transverse(corr, setup, gs, active_axes,
                                 val.dtype)
    term = jnp.asarray(corr.sign * pol / dx,
                       jnp.real(inc["Einc"]).dtype) * val
    return term if gate is None else term * gate


def corrections_for(field: str, comp: str, setup: TfsfSetup, coeffs,
                    inc: Dict[str, jnp.ndarray], active_axes,
                    dx: float) -> Optional[jnp.ndarray]:
    """Sum of this component's TFSF curl-accumulator corrections (or None).

    Built as sum over face planes of onehot_1d(axis) * slab(transverse),
    everything derived from the sharded coordinate arrays gx/gy/gz
    (corr_plane_term supplies each face's transverse value plane).
    """
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    total = None
    for corr in setup.corrections:
        if corr.field != field or corr.comp != comp:
            continue
        term = corr_plane_term(corr, setup, coeffs, inc, active_axes,
                               dx)
        if term is None:
            continue
        onehot_shape = [1, 1, 1]
        onehot_shape[corr.axis] = gs[corr.axis].shape[0]
        onehot = (gs[corr.axis] == corr.plane).reshape(onehot_shape)
        term = term * onehot.astype(term.dtype)
        total = term if total is None else total + term
    return total


def corrections_for_ds(field: str, comp: str, setup: TfsfSetup, coeffs,
                       inc: Dict[str, jnp.ndarray], active_axes,
                       dx: float):
    """corrections_for in double-single: returns an (hi, lo) pair.

    The per-step corrections are a boundary forcing whose f32 rounding
    would accumulate ~eps32*sqrt(steps) in the field — above the 1e-6
    bar by ~1000 steps — so the line samples, the sign*pol/dx
    coefficient, AND the line coordinate zeta are all carried as pairs:
    zeta grows to O(line length), so a single-f32 zeta has an ABSOLUTE
    sampling-position error of eps32*|zeta| ~ 1e-6 cells, which times
    the line's O(1/cell) gradient was measured as the dominant ~1e-6
    residual. The ds zeta keeps the FRACTIONAL interpolation weight
    accurate to ~2^-24 absolute.
    """
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    from fdtd3d_tpu.ops import ds
    tot = None
    for corr in setup.corrections:
        if corr.field != field or corr.comp != comp:
            continue
        term = record_term_ds(corr, setup, coeffs, inc, active_axes, dx)
        if term is None:
            continue
        th, tl = term
        onehot_shape = [1, 1, 1]
        onehot_shape[corr.axis] = gs[corr.axis].shape[0]
        onehot = (gs[corr.axis] == corr.plane) \
            .reshape(onehot_shape).astype(th.dtype)
        th, tl = th * onehot, tl * onehot  # 0/1 mask: exact
        tot = (th, tl) if tot is None else ds.add_ff(*tot, th, tl)
    return tot


def record_term_ds(corr: Correction, setup: TfsfSetup, coeffs, inc,
                   active_axes, dx: float):
    """ONE correction's ds accumulator term on its plane (hi, lo), with
    the TRANSVERSE box gate applied but WITHOUT the normal-axis onehot
    — or None when the polarization projection vanishes.

    The single authority for the per-correction ds math: the jnp-ds
    step consumes it through corrections_for_ds (which adds the onehot)
    and the packed-ds kernel (ops/pallas_packed_ds.py) consumes it
    directly, carrying the plane index statically, so the two paths
    cannot drift.
    """
    from fdtd3d_tpu.ops import ds
    pol = _projection(corr, setup)
    if abs(pol) < POL_EPS:
        return None
    gs = (coeffs["gx"], coeffs["gy"], coeffs["gz"])
    rdt = inc["Einc"].dtype
    off = YEE_OFFSETS[corr.src]
    z0 = np.float64(setup.zeta0) + np.float64(
        setup.khat[corr.axis]) * (corr.pos_a
                                  - setup.origin[corr.axis])
    zh, zl = ds.from_f64(z0)
    zh = jnp.asarray(zh, rdt)
    zl = jnp.asarray(zl, rdt)
    for b in range(3):
        if b == corr.axis or b not in active_axes:
            continue
        # pb values are integers + 0.5: exact in f32
        pb = gs[b].astype(rdt) + off[b]
        shape = [1, 1, 1]
        shape[b] = pb.shape[0]
        oh, ol = ds.from_f64(np.float64(setup.origin[b]))
        dh_, dl_ = ds.add_f(-oh, -ol, pb)
        th_, tl_ = ds.mul_ff(dh_, dl_,
                             *ds.from_f64(np.float64(setup.khat[b])))
        zh, zl = ds.add_ff(zh, zl, th_.reshape(shape),
                           tl_.reshape(shape))
    if corr.src[0] == "E":
        vh, vl = _interp_line_ds(inc["Einc"], inc["Einc_lo"],
                                 (zh, zl))
    else:
        vh, vl = _interp_line_ds(inc["Hinc"], inc["Hinc_lo"],
                                 ds.add_f(zh, zl, np.float32(-0.5)))
    ch, cl = ds.from_f64(np.float64(corr.sign) * pol / dx)
    th, tl = ds.mul_ff(vh, vl, ch, cl)
    gate = _corr_gate_transverse(corr, setup, gs, active_axes, th.dtype)
    if gate is not None:
        th, tl = th * gate, tl * gate      # 0/1 mask: exact
    return th, tl


def _interp_line_ds(line_h, line_l, u_pair):
    """Double-single linear interpolation of the (hi, lo) line.

    ``u_pair`` is the ds line coordinate; the fractional weight is
    extracted with an exact two_diff against the floored index so its
    absolute error is ~2^-24 regardless of |u|. A near-integer u whose
    collapsed floor differs from the pair's true floor yields w just
    outside [0, 1] — the linear form extrapolates the same segment, so
    the result stays continuous and correct to the same order.
    """
    from fdtd3d_tpu.ops import ds
    uh, ul = u_pair
    _tally_samples(uh)
    u = jnp.clip(uh + ul, 0.0, line_h.shape[0] - 1.001)
    i0 = jnp.floor(u).astype(jnp.int32)
    wh, we = ds.two_diff(uh, i0.astype(uh.dtype))
    wh, wl = ds.two_sum(wh, we + ul)
    # (1 - w) in ds too: a single-f32 weight's ~2^-24 error is FIXED
    # per cell while the line values slide past it — a coherent forcing
    # error at the wave frequency that accumulates ~linearly in t
    owh, owl = ds.add_f(-wh, -wl, jnp.float32(1.0))
    v0 = (jnp.take(line_h, i0), jnp.take(line_l, i0))
    v1 = (jnp.take(line_h, i0 + 1), jnp.take(line_l, i0 + 1))
    return ds.add_ff(*ds.mul_ff(*v0, owh, owl),
                     *ds.mul_ff(*v1, wh, wl))
