"""Plain reference: 3D Yee leapfrog with CPML, TFSF and a dielectric sphere.

Written from the published methods, not from the program under test,
and imports nothing of it:

* Yee (1966) staggering, E_CENTERED: Ex at (i+1/2, j, k), Ey at
  (i, j+1/2, k), Ez at (i, j, k+1/2), Hx at (i, j+1/2, k+1/2), Hy at
  (i+1/2, j, k+1/2), Hz at (i+1/2, j+1/2, k). E is advanced first from
  H^{n+1/2}, then H from E^{n+1}. Ghost values outside the grid are 0;
  tangential E is held at 0 on the outermost planes (PEC walls).
* CPML after Roden & Gedney (2000): polynomial grading of order m,
  sigma_max = -(m+1) ln(R0) / (2 eta0 npml dx), kappa graded the same
  way, alpha linear; psi' = b psi + c dF/da, term = dF/da / kappa + psi'.
  The e-profile is sampled at integer positions, the h-profile at
  half-integer ones; psi lives only on the planes where c != 0.
* TFSF after Taflove & Hagness ch. 5: a 1D incident line (E at
  integer, H at half-integer positions, spacing dx, hard sine source
  with a two-period smoothstep ramp at cell 0, graded matched-loss tail
  of 24 cells) drives consistency corrections on the faces of the total
  field box, the incident value linearly interpolated at the straddling
  sample's own staggered position.
* A dielectric sphere: eps_r = value where the component's staggered
  position lies within the radius.
* NTFF: the running DFT of tangential E (on the face plane) and of
  tangential H (mean of the two planes around the face) over the six
  faces of a closed box, E at phase -w t dt, H at -w (t + 1/2) dt.

All arithmetic is float32 on the device, as the configuration states;
host-side constants (profiles, source samples, phases) are float64 and
rounded once. Every number it needs comes from the configuration's
``physics`` block, so the program's defaults are stated there, not
looked up.

The contract of a reference module (a configuration names its own in
its ``reference`` key; benchmark/checks.py uses nothing else of it):

* ``E_COMPS`` and ``H_COMPS``, the component names of the two families
  the checks compare;
* ``Reference(phys)``, built from the configuration's ``physics``
  block, with ``dt`` and ``omega`` (s, rad/s) and
  ``advance(E, H, aux, t0, n, ntff=None) -> (E, H, aux, acc)``, which
  consumes its inputs; ``zero_aux()``, the state beside E and H at step
  0, whose ``psi`` holds ``<comp>_<axis>`` slabs as lo ++ hi planes of
  the whole grid along <axis>; ``ntff_box(margin)``;
  ``zero_ntff_acc(box)`` keyed ``(axis, side, comp)`` as the program's
  NTFF accumulators; and ``from_program(state) -> (E, H, aux, t)``,
  which takes the program's state dict with psi in that global layout,
  returns float32 copies of every family it models, and raises on any
  other family, never dropping one.

Given inputs sharded over a mesh, the arithmetic below stays plain
``jax.numpy``: the compiler partitions it, and E and H come out of
``advance`` sharded as they went in.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

C0 = 299_792_458.0
EPS0 = 8.854_187_8128e-12
MU0 = 1.256_637_062_12e-6
ETA0 = math.sqrt(MU0 / EPS0)

E_COMPS = ("Ex", "Ey", "Ez")
H_COMPS = ("Hx", "Hy", "Hz")
OFFSET = {"Ex": (0.5, 0.0, 0.0), "Ey": (0.0, 0.5, 0.0),
          "Ez": (0.0, 0.0, 0.5), "Hx": (0.0, 0.5, 0.5),
          "Hy": (0.5, 0.0, 0.5), "Hz": (0.5, 0.5, 0.0)}
# curl_c F = +dF_{c+2}/d_{c+1} - dF_{c+1}/d_{c+2}: (derivative axis,
# differentiated component axis, sign) for each component axis c
CURL = {0: ((1, 2, 1.0), (2, 1, -1.0)),
        1: ((2, 0, 1.0), (0, 2, -1.0)),
        2: ((0, 1, 1.0), (1, 0, -1.0))}
LINE_TAIL = 24
RAMP_PERIODS = 2.0


def _axis(comp: str) -> int:
    return "xyz".index(comp[1])


def _bshape(n: int, axis: int) -> Tuple[int, int, int]:
    s = [1, 1, 1]
    s[axis] = n
    return tuple(s)


def _cut(f, lo, hi, axis):
    return lax.slice_in_dim(f, lo, hi, axis=axis)


def _back_diff(f, axis):
    """f[i] - f[i-1], f[-1] = 0."""
    n = f.shape[axis]
    prev = jnp.concatenate(
        [jnp.zeros(_shape_with(f.shape, axis, 1), f.dtype),
         _cut(f, 0, n - 1, axis)], axis=axis)
    return f - prev


def _fwd_diff(f, axis):
    """f[i+1] - f[i], f[n] = 0."""
    n = f.shape[axis]
    nxt = jnp.concatenate(
        [_cut(f, 1, n, axis),
         jnp.zeros(_shape_with(f.shape, axis, 1), f.dtype)], axis=axis)
    return nxt - f


def _shape_with(shape, axis, n):
    s = list(shape)
    s[axis] = n
    return tuple(s)


def _add_plane(f, plane_val, index, axis):
    """f with plane_val added to its plane ``index`` along ``axis``."""
    cur = lax.dynamic_slice_in_dim(f, index, 1, axis=axis)
    return lax.dynamic_update_slice_in_dim(f, cur + plane_val, index,
                                           axis=axis)


class YeeReference:
    """The reference solver for one configuration's ``physics`` block."""

    def __init__(self, phys: Dict):
        self.phys = phys
        self.shape = tuple(int(v) for v in phys["size"])
        self.dx = float(phys["dx"])
        self.dt = float(phys["courant_factor"]) * self.dx / (
            C0 * math.sqrt(3.0))
        self.omega = 2.0 * math.pi * C0 / float(phys["wavelength"])
        self.npml = int(phys["pml_size"])
        self.m = self.npml + 1           # planes per CPML slab
        self.inv_dx = 1.0 / self.dx
        sph = phys.get("eps_sphere")
        self.sphere = None if sph is None else (
            tuple(float(v) for v in sph["center"]), float(sph["radius"]),
            float(sph["value"]))
        self.cb_vac = np.float32(self.dt / EPS0)
        self.cb_in = None if self.sphere is None else np.float32(
            self.dt / (EPS0 * self.sphere[2]))
        self.db = np.float32(self.dt / MU0)
        self._profiles()
        self._tfsf()
        self._fns = {}

    # -- host-side constants ------------------------------------------------

    def _profiles(self):
        p = self.phys["pml"]
        npml, dx, dt = self.npml, self.dx, self.dt
        order = float(p["grading_order"])
        smax = (float(p["sigma_scale"]) * (-(order + 1.0)
                * math.log(float(p["r0"]))) / (2.0 * ETA0 * npml * dx))
        self.prof = {}
        for tag, off in (("e", 0.0), ("h", 0.5)):
            per_axis = []
            for a in range(3):
                n = self.shape[a]
                pos = np.arange(n, dtype=np.float64) + off
                depth = np.clip(np.maximum((npml - pos) / npml,
                                           (pos - (n - 1 - npml)) / npml),
                                0.0, 1.0)
                sigma = smax * depth ** order
                kappa = 1.0 + (float(p["kappa_max"]) - 1.0) * depth ** order
                alpha = float(p["alpha_max"]) * (1.0 - depth)
                b = np.exp(-(sigma / kappa + alpha) * dt / EPS0)
                den = sigma * kappa + kappa * kappa * alpha
                with np.errstate(divide="ignore", invalid="ignore"):
                    c = np.where(den > 0.0, sigma * (b - 1.0) / den, 0.0)
                inside = depth > 0.0
                b = np.where(inside, b, 0.0)
                c = np.where(inside, c, 0.0)
                m = self.m
                idx = np.concatenate([np.arange(m), np.arange(n - m, n)])
                # nothing may lie between the slabs: the profile must
                # be the identity on every plane the slabs leave out
                mid = np.ones(n, bool)
                mid[idx] = False
                assert not (c[mid] != 0).any() and (kappa[mid] == 1).all()
                per_axis.append({
                    "b": b[idx].astype(np.float32),
                    "c": c[idx].astype(np.float32),
                    "ik": (1.0 / kappa[idx]).astype(np.float32)})
            self.prof[tag] = per_axis

    def _tfsf(self):
        ph = self.phys
        lo, hi = [], []
        for a in range(3):
            pad = self.npml + int(ph["tfsf_margin"])
            lo.append(pad)
            hi.append(self.shape[a] - 1 - pad)
        th, phi, psi = (math.radians(float(ph[k]))
                        for k in ("angle_teta", "angle_phi", "angle_psi"))
        khat = np.array([math.sin(th) * math.cos(phi),
                         math.sin(th) * math.sin(phi), math.cos(th)])
        that = np.array([math.cos(th) * math.cos(phi),
                         math.cos(th) * math.sin(phi), -math.sin(th)])
        phat = np.array([-math.sin(phi), math.cos(phi), 0.0])
        ehat = math.cos(psi) * that + math.sin(psi) * phat
        hhat = np.cross(khat, ehat)
        self.box = (tuple(lo), tuple(hi))
        self.khat, self.ehat, self.hhat = khat, ehat, hhat
        self.origin = tuple(float(lo[a]) if khat[a] >= 0 else float(hi[a])
                            for a in range(3))
        self.zeta0 = 2.0
        span = sum(abs(khat[a]) * (hi[a] - lo[a]) for a in range(3))
        self.n_inc = int(math.ceil(span + self.zeta0)) + 8 + LINE_TAIL
        n = self.n_inc
        smax = 4.0 / (ETA0 * LINE_TAIL * self.dx)
        d_e = np.clip((np.arange(n) - (n - 1 - LINE_TAIL)) / LINE_TAIL,
                      0.0, 1.0)
        d_h = np.clip((np.arange(n) + 0.5 - (n - 1 - LINE_TAIL))
                      / LINE_TAIL, 0.0, 1.0)
        se = smax * d_e ** 3 * self.dt / (2.0 * EPS0)
        sh = smax * d_h ** 3 * self.dt / (2.0 * EPS0)
        self.line = {
            "ae": ((1 - se) / (1 + se)).astype(np.float32),
            "be": ((self.dt / (EPS0 * self.dx)) / (1 + se)).astype(
                np.float32),
            "ah": ((1 - sh) / (1 + sh)).astype(np.float32),
            "bh": ((self.dt / (MU0 * self.dx)) / (1 + sh)).astype(
                np.float32)}
        # face corrections: (updated comp, axis, plane, sampled comp,
        # sign, sampled position along axis, comp gating transversely)
        corr: Dict[str, List] = {c: [] for c in E_COMPS + H_COMPS}
        for c in E_COMPS:
            for (a, d, s) in CURL[_axis(c)]:
                src = "H" + "xyz"[d]
                corr[c].append((a, lo[a], src, -s, lo[a] - 0.5, c))
                corr[c].append((a, hi[a], src, s, hi[a] + 0.5, c))
        for c in H_COMPS:
            for (a, d, s) in CURL[_axis(c)]:
                src = "E" + "xyz"[d]
                corr[c].append((a, lo[a] - 1, src, -s, float(lo[a]), src))
                corr[c].append((a, hi[a], src, s, float(hi[a]), src))
        self.corr = {}
        for c, lst in corr.items():
            keep = []
            for (a, plane, src, sign, pos, gate) in lst:
                hat = self.ehat if src[0] == "E" else self.hhat
                pol = float(hat[_axis(src)])
                if abs(pol) < 1e-14:
                    continue
                keep.append((a, plane, src, sign * pol, pos, gate))
            self.corr[c] = keep

    def source_values(self, t0: int, n: int) -> np.ndarray:
        """Einc[0] after steps t0 .. t0+n-1: amp * ramp * sin(w t)."""
        t = (np.arange(t0, t0 + n, dtype=np.float64) + 1.0) * self.dt
        period = 2.0 * math.pi / self.omega
        r = np.clip(t / (RAMP_PERIODS * period), 0.0, 1.0)
        r = r * r * (3.0 - 2.0 * r)
        amp = float(self.phys["tfsf_amplitude"])
        return (amp * r * np.sin(self.omega * t)).astype(np.float32)

    # -- state ------------------------------------------------------------

    def zero_aux(self) -> Dict:
        """psi (lo ++ hi slabs along each own axis) and the line, zero."""
        psi = {}
        for fam, comps in (("E", E_COMPS), ("H", H_COMPS)):
            for c in comps:
                for (a, _, _) in CURL[_axis(c)]:
                    psi[f"{c}_{'xyz'[a]}"] = jnp.zeros(
                        _shape_with(self.shape, a, 2 * self.m),
                        jnp.float32)
        return {"psi": psi,
                "Einc": jnp.zeros(self.n_inc, jnp.float32),
                "Hinc": jnp.zeros(self.n_inc, jnp.float32)}

    FAMILIES = ("E", "H", "psi_E", "psi_H", "inc", "t")

    def from_program(self, state: Dict):
        """(E, H, aux, t) from the program's state dict (components of
        E and H, ``psi_E``/``psi_H`` in the global lo ++ hi layout, the
        incident line, t): float32 copies, since ``advance`` consumes
        its inputs. A family this reference does not model raises."""
        extra = sorted(set(state) - set(self.FAMILIES))
        if extra:
            raise ValueError(f"the program's state holds {extra}, which "
                             f"this reference does not model")
        for fam, comps in (("E", E_COMPS), ("H", H_COMPS)):
            if set(state[fam]) != set(comps):
                raise ValueError(f"the program's {fam} holds "
                                 f"{sorted(state[fam])}, not {comps}")
        psi = {**state.get("psi_E", {}), **state.get("psi_H", {})}
        want = jax.eval_shape(self.zero_aux)["psi"]
        if set(psi) != set(want) or any(
                psi[k].shape != want[k].shape for k in want):
            raise ValueError(
                "the program's psi is not this reference's: "
                f"{ {k: tuple(v.shape) for k, v in sorted(psi.items())} }")

        line = {k: state["inc"][k] for k in ("Einc", "Hinc")}
        if any(v.shape != (self.n_inc,) for v in line.values()):
            raise ValueError("the program's incident line is not "
                             f"{self.n_inc} samples long")

        def own(x):
            return jnp.array(x, dtype=jnp.float32, copy=True)

        E = {c: own(state["E"][c]) for c in E_COMPS}
        H = {c: own(state["H"][c]) for c in H_COMPS}
        aux = {"psi": {k: own(v) for k, v in psi.items()},
               **{k: own(v) for k, v in line.items()}}
        return E, H, aux, int(state["t"])

    # -- one step -----------------------------------------------------------

    def _eps_mask(self, comp):
        """True where comp's staggered position is inside the sphere
        (exact integer arithmetic in doubled coordinates)."""
        (cx, cy, cz), r, _ = self.sphere
        d2 = None
        for a, cen in enumerate((cx, cy, cz)):
            n = self.shape[a]
            two_p = (2 * lax.broadcasted_iota(jnp.int32, _bshape(n, a), a)
                     + int(round(2 * OFFSET[comp][a])))
            q = two_p - int(round(2 * cen))
            d2 = q * q if d2 is None else d2 + q * q
        return d2 <= int(round(4 * r * r))

    def _gate(self, comp, axis):
        """Transverse total-field-box membership of ``comp`` on a face
        plane normal to ``axis`` (a broadcastable 0/1 plane)."""
        lo, hi = self.box
        g = None
        for b in range(3):
            if b == axis:
                continue
            top = hi[b] - 1 if OFFSET[comp][b] == 0.5 else hi[b]
            i = lax.broadcasted_iota(jnp.int32, _bshape(self.shape[b], b),
                                     b)
            ind = ((i >= lo[b]) & (i <= top)).astype(jnp.float32)
            g = ind if g is None else g * ind
        return g

    def _zeta(self, src, axis, pos):
        z = self.zeta0 + self.khat[axis] * (pos - self.origin[axis])
        zeta = jnp.float32(z)
        for b in range(3):
            if b == axis:
                continue
            pb = (lax.broadcasted_iota(jnp.int32, _bshape(self.shape[b], b),
                                       b).astype(jnp.float32)
                  + jnp.float32(OFFSET[src][b]))
            zeta = zeta + jnp.float32(self.khat[b]) * (
                pb - jnp.float32(self.origin[b]))
        return zeta

    def _interp(self, line, u):
        u = jnp.clip(u, 0.0, line.shape[0] - 1.001)
        i0 = jnp.floor(u).astype(jnp.int32)
        w = u - i0.astype(jnp.float32)
        return (1.0 - w) * jnp.take(line, i0) + w * jnp.take(line, i0 + 1)

    def _corrections(self, comp, acc, line):
        for (a, plane, src, coef, pos, gate) in self.corr[comp]:
            u = self._zeta(src, a, pos)
            val = (self._interp(line["Einc"], u) if src[0] == "E"
                   else self._interp(line["Hinc"], u - 0.5))
            term = jnp.float32(coef / self.dx) * val * self._gate(gate, a)
            term = jnp.broadcast_to(term, _shape_with(self.shape, a, 1))
            acc = _add_plane(acc, term, plane, a)
        return acc

    def _curl(self, comp, src_fields, psi, tag, diff):
        """Curl accumulator of ``comp`` with the CPML terms; -> (acc,
        new psi entries)."""
        acc = None
        new_psi = {}
        m = self.m
        for (a, d, s) in CURL[_axis(comp)]:
            f = src_fields[("H" if tag == "e" else "E") + "xyz"[d]]
            dfa = diff(f, a) * jnp.float32(self.inv_dx)
            n = dfa.shape[a]
            key = f"{comp}_{'xyz'[a]}"
            pr = self.prof[tag][a]
            b = jnp.asarray(pr["b"]).reshape(_bshape(2 * m, a))
            c = jnp.asarray(pr["c"]).reshape(_bshape(2 * m, a))
            ik = jnp.asarray(pr["ik"]).reshape(_bshape(2 * m, a))
            edge = jnp.concatenate([_cut(dfa, 0, m, a),
                                    _cut(dfa, n - m, n, a)], axis=a)
            p_new = b * psi[key] + c * edge
            new_psi[key] = p_new
            t_edge = ik * edge + p_new
            term = jnp.concatenate([_cut(t_edge, 0, m, a),
                                    _cut(dfa, m, n - m, a),
                                    _cut(t_edge, m, 2 * m, a)], axis=a)
            acc = s * term if acc is None else acc + s * term
        return acc, new_psi

    def step(self, E, H, aux, src_val):
        """One leapfrog step; -> (E, H, aux)."""
        ln = self.line
        einc, hinc = aux["Einc"], aux["Hinc"]
        dh = hinc - jnp.concatenate([jnp.zeros(1, jnp.float32), hinc[:-1]])
        einc = ln["ae"] * einc - ln["be"] * dh
        einc = einc.at[0].set(src_val)
        line = {"Einc": einc, "Hinc": hinc}
        psi = dict(aux["psi"])
        new_E = {}
        for c in E_COMPS:
            acc, p = self._curl(c, H, aux["psi"], "e", _back_diff)
            psi.update(p)
            acc = self._corrections(c, acc, line)
            if self.sphere is None:
                cb = self.cb_vac
            else:
                cb = jnp.where(self._eps_mask(c), self.cb_in, self.cb_vac)
            e = E[c] + cb * acc
            for a in range(3):
                if a == _axis(c):
                    continue
                i = lax.broadcasted_iota(jnp.int32, _bshape(self.shape[a],
                                                            a), a)
                wall = (i > 0) & (i < self.shape[a] - 1)
                e = jnp.where(wall, e, 0.0)
            new_E[c] = e
        de = jnp.concatenate([einc[1:], jnp.zeros(1, jnp.float32)]) - einc
        hinc = ln["ah"] * hinc - ln["bh"] * de
        line = {"Einc": einc, "Hinc": hinc}
        new_H = {}
        for c in H_COMPS:
            acc, p = self._curl(c, new_E, aux["psi"], "h", _fwd_diff)
            psi.update(p)
            acc = self._corrections(c, acc, line)
            new_H[c] = H[c] - self.db * acc
        return new_E, new_H, {"psi": psi, "Einc": einc, "Hinc": hinc}

    # -- NTFF -------------------------------------------------------------

    def ntff_box(self, margin: int):
        lo = tuple(self.npml + margin for _ in range(3))
        hi = tuple(self.shape[a] - 1 - self.npml - margin for a in range(3))
        return lo, hi

    def ntff_keys(self):
        keys = []
        for axis in range(3):
            tang = [c for c in E_COMPS + H_COMPS if _axis(c) != axis]
            for side in (0, 1):
                for c in tang:
                    keys.append((axis, side, c))
        return keys

    def _faces(self, E, H, box):
        lo, hi = box
        out = {}
        for (axis, side, c) in self.ntff_keys():
            idx = lo[axis] if side == 0 else hi[axis]
            sl = [slice(lo[b], hi[b] + 1) for b in range(3)]
            if c[0] == "E":
                sl[axis] = idx
                out[(axis, side, c)] = E[c][tuple(sl)]
            else:
                s0, s1 = list(sl), list(sl)
                s0[axis], s1[axis] = idx, idx - 1
                out[(axis, side, c)] = 0.5 * (H[c][tuple(s0)]
                                              + H[c][tuple(s1)])
        return out

    # -- drivers ----------------------------------------------------------

    def _chunk_fn(self, n: int, ntff_box):
        key = (n, ntff_box)
        if key in self._fns:
            return self._fns[key]

        def run(E, H, aux, acc, tables):
            def body(i, carry):
                E, H, aux, acc = carry
                E, H, aux = self.step(E, H, aux, tables["src"][i])
                if ntff_box is not None:
                    faces = self._faces(E, H, ntff_box)
                    new = {}
                    for k, f in faces.items():
                        w = tables["w"][i]
                        if k[2][0] == "E":
                            cs, sn = tables["ce"][i], tables["se"][i]
                        else:
                            cs, sn = tables["ch"][i], tables["sh"][i]
                        re, im = acc[k]
                        new[k] = (re + w * cs * f, im + w * sn * f)
                    acc = new
                return E, H, aux, acc
            return lax.fori_loop(0, n, body, (E, H, aux, acc))

        fn = jax.jit(run, donate_argnums=(0, 1, 2, 3))
        self._fns[key] = fn
        return fn

    def advance(self, E, H, aux, t0: int, n: int, ntff=None, chunk=10):
        """Advance n steps from step t0. ``ntff`` = dict(box, every,
        start, omega, acc) or None; -> (E, H, aux, acc)."""
        box = None if ntff is None else ntff["box"]
        acc = {} if ntff is None else ntff["acc"]
        t = t0
        done = 0
        while done < n:
            k = chunk if n - done >= chunk else 1
            ts = np.arange(t, t + k)
            tables = {"src": jnp.asarray(self.source_values(t, k))}
            if ntff is not None:
                after = ts + 1      # the step count at the sample
                w = ((after >= ntff["start"])
                     & (after % ntff["every"] == 0)).astype(np.float32)
                ang_e = -ntff["omega"] * after * self.dt
                ang_h = -ntff["omega"] * (after + 0.5) * self.dt
                tables.update(
                    w=jnp.asarray(w),
                    ce=jnp.asarray(np.cos(ang_e).astype(np.float32)),
                    se=jnp.asarray(np.sin(ang_e).astype(np.float32)),
                    ch=jnp.asarray(np.cos(ang_h).astype(np.float32)),
                    sh=jnp.asarray(np.sin(ang_h).astype(np.float32)))
            E, H, aux, acc = self._chunk_fn(k, box)(E, H, aux, acc, tables)
            t += k
            done += k
        return E, H, aux, acc

    def zero_ntff_acc(self, box):
        lo, hi = box
        acc = {}
        for k in self.ntff_keys():
            shape = tuple(hi[b] - lo[b] + 1 for b in range(3) if b != k[0])
            acc[k] = (jnp.zeros(shape, jnp.float32),
                      jnp.zeros(shape, jnp.float32))
        return acc


Reference = YeeReference
