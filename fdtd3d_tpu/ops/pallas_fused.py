"""Single-pass fused E+H Pallas kernel.

The two-pass kernels (ops/pallas3d.py) move ~18 field volumes per step
(72 B/cell f32); fusing both family updates into ONE pass cuts that to
12 (48 B/cell) — see docs/PERFORMANCE.md. The mechanics: each x-tile
computes new_E for its T planes PLUS one redundant extra plane
((i+1)·T, recomputed by the next tile), so the tile's H update — whose
forward x-difference needs new_E one plane ahead — never waits on a
neighbor tile.

Scope (everything else falls back to the two-pass kernels): 3D, real
f32/bf16 storage, UNSHARDED, slab-fitting CPML on any axes, Drude J/K,
TFSF, point source. The post-kernel E modifications (x-slab CPML
deltas, TFSF face corrections, point source) are thin plane patches;
the kernel's H update — computed from the PRE-patch E — is corrected
afterwards by the curl of those patches (``apply_patch_h_corrections``):
every patch contributes forward differences along each curl axis, all
plane-local, so the correction traffic is O(slab/tfsf planes), not a
full pass. The H-side x-slab CPML post-pass then runs on the CORRECTED
E (exact by construction), mirroring ops/pallas3d's two-pass ordering.

The extra plane needs one-plane "forward halos" of everything the E
update reads there: old E, psi_E, J, and any 3D E-side coefficient
grids — fetched as single-plane blocks of the same HBM arrays via
clamped index maps (the pattern the two-pass kernels already use for
the x halo).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fdtd3d_tpu.layout import CURL_TERMS, component_axis
from fdtd3d_tpu import ops
from fdtd3d_tpu.ops.pallas3d import (COMPILER_PARAMS, _VMEM_LIMIT,
                                     _pick_tile)
from fdtd3d_tpu.telemetry import named as _named

AXES = "xyz"


def eligible(static, mesh_axes=None) -> bool:
    if static.mode.name != "3D":
        return False
    if static.field_dtype not in (np.float32, jnp.bfloat16):
        return False
    if static.topology != (1, 1, 1):
        return False
    if mesh_axes and any(v is not None for v in mesh_axes.values()):
        return False
    if static.cfg.compensated:
        return False  # Kahan residuals live in the packed kernel only
    if static.cfg.ds_fields:
        return False  # double-single pairs: jnp_ds / packed-ds only
    return True


def _shift_lo(v: jnp.ndarray, axis: int) -> jnp.ndarray:
    """v shifted one plane toward lo along axis, zero-filled at hi."""
    pad = [(0, 0)] * v.ndim
    pad[axis] = (0, 1)
    return jnp.pad(lax.slice_in_dim(v, 1, v.shape[axis], axis=axis),
                   pad)


def _traced_patch_fix(static, out_H, c, p, a, s, db, coeffs,
                      mesh_axes, mesh_shape, inv_dx, cdt, h_dtype):
    """H correction for one traced (sharded-normal-axis) plane patch.

    The packed kernel admits sharded TFSF/point-source runs only when
    the patch support sits strictly inside the CPML identity region
    (pallas_packed._sources_interior), so F == identity and no psi term
    arises — the correction is the plain curl of the one-plane delta.
    Two pieces cross shards and ride ppermute: the P-1 plane of an
    a == b term when the patch sits at a shard's first plane (the
    correction lands on the LOWER b-neighbor's last plane), and the
    transverse forward-diff's hi-edge ghost on a sharded a.
    """
    from fdtd3d_tpu.ops import pallas3d as _p3

    b, loc, own, gplane = p.axis, p.start, p.own, p.gstart
    delta = p.delta.astype(cdt)          # one owner-gated plane along b
    name_b = mesh_axes[b]
    n_b = static.grid_shape[b] // static.topology[b]

    def db_plane(loc_b):
        if jnp.ndim(db) != 3:
            return db
        return lax.dynamic_slice_in_dim(db, loc_b, 1, b)

    def add_plane(H, loc_b, val):
        sl: list = [slice(None)] * 3
        sl[b] = loc_b            # traced int -> dynamic one-plane add
        return _p3.fields_add(H, c, sl,
                              jnp.squeeze(val, b).astype(h_dtype))

    if a == b:
        # forward diff along the patch normal: +delta/dx at P-1,
        # -delta/dx at P (dH = -db * s * that)
        here = -db_plane(loc) * (s * inv_dx) * (-delta)
        out_H = add_plane(out_H, loc, here)
        locm = jnp.maximum(loc - 1, 0)
        prev = -db_plane(locm) * (s * inv_dx) * delta
        prev = jnp.where(own & (loc > 0), prev, 0.0)
        out_H = add_plane(out_H, locm, prev)
        # cross-shard: when the owner holds P at its first plane, P-1
        # is the lower b-neighbor's LAST plane — ship the delta down
        n_sh_b = mesh_shape[name_b]
        with _named("halo-exchange"):
            recv = lax.ppermute(delta, name_b,
                                [(r + 1, r) for r in range(n_sh_b - 1)])
        gate = coeffs[f"g{AXES[b]}"][0] + n_b == gplane
        last = -db_plane(n_b - 1) * (s * inv_dx) * recv
        last = jnp.where(gate, last, 0.0)
        out_H = add_plane(out_H, n_b - 1, last)
    else:
        w = (_shift_lo(delta, a) - delta) * inv_dx
        if mesh_axes.get(a):
            # sharded transverse axis: the local hi plane's forward
            # neighbor is the upper a-shard's first patch plane
            name_a = mesh_axes[a]
            n_sh_a = mesh_shape[name_a]
            first = lax.slice_in_dim(delta, 0, 1, axis=a)
            with _named("halo-exchange"):
                nxt = lax.ppermute(first, name_a,
                                   [(r + 1, r)
                                    for r in range(n_sh_a - 1)])
            n_a_loc = delta.shape[a]
            hi_sl = [slice(None)] * 3
            hi_sl[a] = slice(n_a_loc - 1, n_a_loc)
            w = w.at[tuple(hi_sl)].add(nxt * inv_dx)
        out_H = add_plane(out_H, loc, -db_plane(loc) * (s * w))
    return out_H


def _patch_scope(p):
    """The named scope of the work a patch's H correction does: its
    origin's (``Patch.scope``), so TFSF work is charged to ``tfsf``."""
    return _named(p.scope) if p.scope else contextlib.nullcontext()


def apply_patch_h_corrections(static, new_H, psi_H, patches, coeffs,
                              slabs, mesh_axes=None, mesh_shape=None):
    """Correct the kernel's H update for post-kernel E patches.

    The kernel computed H from E' (pre-patch). The exact H uses
    E = E' + sum(patches); since the update is linear, the fix is
    dH_c = -db_c * sum_terms s * F_a(D_a(dE_d)/dx), applied at the
    patches' planes only. F_a encodes the CPML handling the kernel used
    for axis a:

      * a == 0 ("post" axis): the kernel term was the plain curl, so
        F = identity — the x-slab psi delta itself is added later by
        x_slab_post over the corrected E.
      * a in slabs (in-kernel slab psi): term = s*(ik*dfa + psi') with
        psi' = b*psi + c*dfa, so F = (ik + c) at the patch planes, and
        the stored psi' needs +c * D_a(dE)/dx at the slab overlap.
      * else: plain curl, F = identity.

    ``patches``: list of pallas3d.Patch. Static patches (own None)
    carry a shard-local int start and a delta spanning `k` planes along
    b (full extents elsewhere); traced patches (sharded patch axis,
    round 5) carry a traced local index + ownership and take the
    _traced_patch_fix branch, which assumes the CPML-identity-region
    precondition (pallas_packed._sources_interior). Runs unsharded and
    under shard_map.
    """
    from fdtd3d_tpu.ops import pallas3d as _p3

    mode = static.mode
    inv_dx = 1.0 / static.dx
    cdt = static.compute_dtype
    out_H = _p3.fields_copy(new_H)
    out_psi = _p3.fields_copy(psi_H)

    def slab_f(a: int, lo: int, hi: int) -> jnp.ndarray:
        """F = ik + c at ABSOLUTE planes [lo, hi) of axis a, from the
        FULL-length "h" profiles (ik=1, c=0 outside the absorbing
        region, so F is the identity exactly where the kernel used the
        plain curl)."""
        v = (coeffs[f"pml_ikh_{AXES[a]}"]
             + coeffs[f"pml_ch_{AXES[a]}"])[lo:hi]
        shape = [1, 1, 1]
        shape[a] = hi - lo
        return v.reshape(shape)

    for c in mode.h_components:
        h_dtype = out_H[c].dtype
        db = coeffs[f"db_{c}"]
        for (a, d_axis, s) in CURL_TERMS[component_axis(c)]:
            d = "E" + AXES[d_axis]
            if d not in mode.e_components:
                continue
            for p in patches:
                with _patch_scope(p):
                    if p.comp != d:
                        continue
                    if p.own is not None:
                        # sharded patch axis: traced local index + ownership
                        out_H = _traced_patch_fix(
                            static, out_H, c, p, a, s, db, coeffs,
                            mesh_axes, mesh_shape, inv_dx, cdt, h_dtype)
                        continue
                    b, start, delta = p.axis, p.start, p.delta
                    delta = delta.astype(cdt)
                    k = delta.shape[b]
                    # LOCAL extent: patches carry shard-local plane starts
                    # (identical to global when unsharded; the packed
                    # kernel also runs this under shard_map)
                    n_a = static.grid_shape[a] // static.topology[a]
                    if a == b:
                        # forward diff along the patch normal: k+1 planes
                        # starting at start-1 (zero ghost beyond the patch)
                        pad = [(0, 0)] * 3
                        pad[a] = (1, 1)
                        vpad = jnp.pad(delta, pad)
                        w = (lax.slice_in_dim(vpad, 1, k + 2, axis=a)
                             - lax.slice_in_dim(vpad, 0, k + 1, axis=a)) \
                            * inv_dx
                        pstart = start - 1
                        lo_clip = max(0, -pstart)
                        hi_clip = min(k + 1, n_a - pstart)
                        if hi_clip <= lo_clip:
                            continue
                        w = lax.slice_in_dim(w, lo_clip, hi_clip, axis=a)
                        pstart += lo_clip
                        plen = hi_clip - lo_clip
                    else:
                        # in-patch forward diff along a (zero ghost at the
                        # global hi edge — the kernel's PEC convention)
                        w = (_shift_lo(delta, a) - delta) * inv_dx
                        if mesh_axes and mesh_axes.get(a):
                            # sharded axis: the local hi plane's forward
                            # neighbor is the UPPER shard's first patch
                            # plane (zeros arrive at the global edge)
                            name = mesh_axes[a]
                            n_sh = mesh_shape[name]
                            first = lax.slice_in_dim(delta, 0, 1, axis=a)
                            with _named("halo-exchange"):
                                nxt = lax.ppermute(
                                    first, name,
                                    [(r + 1, r) for r in range(n_sh - 1)])
                            n_loc = delta.shape[a]
                            hi_sl = [slice(None)] * 3
                            hi_sl[a] = slice(n_loc - 1, n_loc)
                            w = w.at[tuple(hi_sl)].add(nxt * inv_dx)
                        pstart, plen = start, k

                    # position of the correction along the patch-extent axis
                    pa = a if a == b else b
                    sl = [slice(None)] * 3
                    sl[pa] = slice(pstart, pstart + plen)
                    sl = tuple(sl)

                    if a in slabs and a != 0:
                        if a == b:
                            dacc = s * slab_f(a, pstart, pstart + plen) * w
                        else:
                            dacc = s * slab_f(a, 0, n_a) * w
                        # stored psi' correction at the slab overlap
                        key = f"{c}_{AXES[a]}"
                        m = slabs[a]
                        ca_prof = coeffs[f"pml_slab_ch_{AXES[a]}"]
                        if a == b:
                            # patch planes [pstart, pstart+plen) vs slabs
                            # [0, m) and [n_a-m, n_a) -> compact [0,m)/[m,2m)
                            for (s_lo, s_hi, c_off) in ((0, m, 0),
                                                        (n_a - m, n_a, m)):
                                o_lo = max(pstart, s_lo)
                                o_hi = min(pstart + plen, s_hi)
                                if o_hi <= o_lo:
                                    continue
                                wsl = [slice(None)] * 3
                                wsl[a] = slice(o_lo - pstart, o_hi - pstart)
                                psl = [slice(None)] * 3
                                psl[a] = slice(c_off + o_lo - s_lo,
                                               c_off + o_hi - s_lo)
                                cp = ca_prof[c_off + o_lo - s_lo:
                                             c_off + o_hi - s_lo]
                                shape = [1, 1, 1]
                                shape[a] = o_hi - o_lo
                                _p3.fields_add(
                                    out_psi, key, tuple(psl),
                                    cp.reshape(shape) * w[tuple(wsl)])
                        else:
                            # w spans full a; slice its slab planes, add at
                            # the patch's b-location in the compact array
                            wsl_lo = [slice(None)] * 3
                            wsl_lo[a] = slice(0, m)
                            wsl_hi = [slice(None)] * 3
                            wsl_hi[a] = slice(n_a - m, n_a)
                            shape = [1, 1, 1]
                            shape[a] = m
                            add = jnp.concatenate(
                                [ca_prof[:m].reshape(shape)
                                 * w[tuple(wsl_lo)],
                                 ca_prof[m:].reshape(shape)
                                 * w[tuple(wsl_hi)]], axis=a)
                            bsl = [slice(None)] * 3
                            bsl[b] = slice(pstart, pstart + plen)
                            _p3.fields_add(out_psi, key, tuple(bsl), add)
                    else:
                        # plain curl term (x "post" axis or no PML on a)
                        dacc = s * w

                    db_sl = db[sl] if jnp.ndim(db) == 3 else db
                    _p3.fields_add(out_H, c, sl,
                                   (-db_sl * dacc).astype(h_dtype))
    return out_H, out_psi


def make_fused_eh_step(static, mesh_axes=None, mesh_shape=None):
    """One-pallas-call leapfrog step, or None if out of scope."""
    from fdtd3d_tpu import solver as solver_mod

    if not eligible(static, mesh_axes):
        return None
    slabs = solver_mod.slab_axes(static)
    # every PML axis must be slab-compacted (thin grids fall back):
    # y/z slabs run in-kernel, the x slab via the jnp post-pass
    for a in static.pml_axes:
        if a not in slabs:
            return None
    np_coeffs = solver_mod.build_coeffs(static)
    interpret = not ops.on_tpu()
    setup = static.tfsf_setup
    x_pml = 0 in static.pml_axes

    mode = static.mode
    n1, n2, n3 = static.grid_shape
    inv_dx = np.float32(1.0 / static.dx)
    fdt = jnp.float32
    fst = static.field_dtype
    # VMEM accounting at f32 width even for bf16 STORAGE: the kernel
    # casts every load to the f32 compute dtype, so Mosaic's scratch
    # holds f32 temporaries per block — sizing tiles by the 2-byte
    # storage width overflows scoped VMEM (measured: bf16 256^3 picked
    # T=16 from 2-byte accounting and failed compile at 120.4M/100M).
    fbytes = max(np.dtype(fst).itemsize, 4)
    e_comps = list(mode.e_components)
    h_comps = list(mode.h_components)
    drude_e = static.use_drude
    drude_m = static.use_drude_m

    # psi terms per family comp: (axis, src, sign) for slab y/z axes
    def terms_of(c: str, fam: str):
        out = []
        for (a, d_axis, s) in CURL_TERMS[component_axis(c)]:
            d = ("H" if fam == "E" else "E") + AXES[d_axis]
            out.append((a, d, s))
        return out

    # in-kernel (y/z) psi only; axis-0 psi lives in the x_slab_post pass
    psi_e_names = [f"{c}_{AXES[a]}" for c in e_comps
                   for (a, d, s) in terms_of(c, "E")
                   if a in slabs and a != 0]
    psi_h_names = [f"{c}_{AXES[a]}" for c in h_comps
                   for (a, d, s) in terms_of(c, "H")
                   if a in slabs and a != 0]

    pairs_e = ["ca", "cb"] + (["kj", "bj"] if drude_e else [])
    pairs_h = ["da", "db"] + (["km", "bm"] if drude_m else [])
    coeff_is_array = {}
    for c in e_comps:
        for p in pairs_e:
            coeff_is_array[f"{p}_{c}"] = np.ndim(np_coeffs[f"{p}_{c}"]) == 3
    for c in h_comps:
        for p in pairs_h:
            coeff_is_array[f"{p}_{c}"] = np.ndim(np_coeffs[f"{p}_{c}"]) == 3
    arr_e = [k for k, v in coeff_is_array.items()
             if v and k.split("_")[0] in pairs_e]
    arr_h = [k for k, v in coeff_is_array.items()
             if v and k.split("_")[0] in pairs_h]

    # CPML profile vectors per family tag and in-kernel slab axis
    prof_specs: List[Tuple[str, str, int]] = []   # (ref, coeffs key, axis)
    for tag in ("e", "h"):
        for a in sorted(slabs):
            if a == 0:
                continue
            for p in ("b", "c", "ik"):
                prof_specs.append((f"pf_{p}{tag}_{AXES[a]}",
                                   f"pml_slab_{p}{tag}_{AXES[a]}", a))

    def _psi_shape(name):
        a = AXES.index(name[-1])
        s = [n1, n2, n3]
        s[a] = 2 * slabs[a]
        return tuple(s)

    def _block_bytes(t: int) -> int:
        plane = n2 * n3
        total = 0
        # E in (t) + extra (1) + E out (t)
        total += len(e_comps) * (2 * t + 1) * plane * fbytes
        # H in (t) + bwd halo + fwd halo + H out (t)
        total += len(h_comps) * (2 * t + 2) * plane * fbytes
        # psi_E in (t) + extra (1) + out (t); psi_H in/out (t)
        for nm in psi_e_names:
            s = _psi_shape(nm)
            total += (2 * t + 1) * s[1] * s[2] * 4
        for nm in psi_h_names:
            s = _psi_shape(nm)
            total += 2 * t * s[1] * s[2] * 4
        if drude_e:   # J in (t) + extra + out (t)
            total += len(e_comps) * (2 * t + 1) * plane * 4
        if drude_m:   # K in/out
            total += len(h_comps) * 2 * t * plane * 4
        total += len(arr_e) * (t + 1) * plane * 4
        total += len(arr_h) * t * plane * 4
        for (_, key, a) in prof_specs:
            total += 2 * slabs[a] * 4
        total += n2 * 4 + n3 * 4 + t * 4     # walls
        return total

    T = _pick_tile(n1, _block_bytes)
    ntiles = n1 // T

    # ---- operand order --------------------------------------------------
    # E in | E extra | H in | H bhalo | H fhalo | psiE in | psiE extra |
    # psiH in | J in | J extra | K in | profiles | walls(x,y,z) |
    # arrE | arrE extra | arrH
    # outs: E | H | psiE | psiH | J | K

    def kernel(*refs):
        idx = {}
        pos = 0

        def take(names, prefix):
            nonlocal pos
            for nm in names:
                idx[f"{prefix}{nm}"] = refs[pos]
                pos += 1

        take(e_comps, "ein_")
        take(e_comps, "eex_")
        take(h_comps, "hin_")
        take(h_comps, "hbh_")
        take(h_comps, "hfh_")
        take(psi_e_names, "pe_")
        take(psi_e_names, "pex_")
        take(psi_h_names, "ph_")
        if drude_e:
            take(e_comps, "jin_")
            take(e_comps, "jex_")
        if drude_m:
            take(h_comps, "kin_")
        take([r for (r, _, _) in prof_specs], "")
        take(["wall_y", "wall_z"], "")
        take(arr_e, "ce_")
        take(arr_e, "cex_")
        take(arr_h, "ch_")
        take(e_comps, "eout_")
        take(h_comps, "hout_")
        take(psi_e_names, "peo_")
        take(psi_h_names, "pho_")
        if drude_e:
            take(e_comps, "jout_")
        if drude_m:
            take(h_comps, "kout_")

        i = pl.program_id(0)

        def cat0(a, b):
            return jnp.concatenate([a, b], axis=0)

        # extended (T+1 plane) loads for the E update
        e_old = {c: cat0(idx[f"ein_{c}"][:], idx[f"eex_{c}"][:]).astype(fdt)
                 for c in e_comps}
        h_old = {c: idx[f"hin_{c}"][:].astype(fdt) for c in h_comps}
        h_ext = {c: cat0(h_old[c], idx[f"hfh_{c}"][:].astype(fdt))
                 for c in h_comps}

        def coef_e(key, ext):
            if coeff_is_array[key]:
                v = idx[f"ce_{key}"][:]
                if ext:
                    v = cat0(v, idx[f"cex_{key}"][:])
                return v.astype(fdt)
            return fdt(float(np_coeffs[key]))

        def coef_h(key):
            if coeff_is_array[key]:
                return idx[f"ch_{key}"][:].astype(fdt)
            return fdt(float(np_coeffs[key]))

        def yz_diff(f, axis, backward):
            zero = jnp.zeros_like(lax.slice_in_dim(f, 0, 1, axis=axis))
            if backward:
                body = lax.slice_in_dim(f, 0, f.shape[axis] - 1, axis=axis)
                return (f - jnp.concatenate([zero, body], axis=axis)) \
                    * inv_dx
            body = lax.slice_in_dim(f, 1, f.shape[axis], axis=axis)
            return (jnp.concatenate([body, zero], axis=axis) - f) * inv_dx

        def slab_term(dfa, psi, tag, a, s, out_ref, owned):
            """CPML slab psi recursion + term for derivative axis a.

            dfa/psi span `owned+?` planes along x; psi written to out_ref
            for the owned T planes only when out_ref is not None.
            """
            m = slabs[a]
            b = idx[f"pf_b{tag}_{AXES[a]}"][:]
            cc = idx[f"pf_c{tag}_{AXES[a]}"][:]
            ik = idx[f"pf_ik{tag}_{AXES[a]}"][:]
            cut = lambda f, lo, hi: lax.slice_in_dim(f, lo, hi, axis=a)  # noqa: E731
            nloc = dfa.shape[a]
            d_lo, d_hi = cut(dfa, 0, m), cut(dfa, nloc - m, nloc)
            p_lo = cut(b, 0, m) * cut(psi, 0, m) + cut(cc, 0, m) * d_lo
            p_hi = (cut(b, m, 2 * m) * cut(psi, m, 2 * m)
                    + cut(cc, m, 2 * m) * d_hi)
            if out_ref is not None:
                out_ref[:] = jnp.concatenate(
                    [p_lo, p_hi], axis=a)[:owned].astype(fdt)
            dl = s * ((cut(ik, 0, m) - 1.0) * d_lo + p_lo)
            dh = s * ((cut(ik, m, 2 * m) - 1.0) * d_hi + p_hi)
            mid = list(dfa.shape)
            mid[a] = nloc - 2 * m
            delta = jnp.concatenate([dl, jnp.zeros(mid, fdt), dh], axis=a)
            return s * dfa + delta

        # global x indices of the extended range, for the PEC x wall
        gx = (i * T + lax.broadcasted_iota(jnp.int32, (T + 1, 1, 1), 0))
        wall_x_ext = ((gx != 0) & (gx != n1 - 1)).astype(fdt)

        # ---- E update over T+1 planes --------------------------------
        new_e = {}
        for c in e_comps:
            acc = None
            for (a, d, s) in terms_of(c, "E"):
                if d not in h_comps:
                    continue
                if a == 0:
                    # backward diff over the extended range: needs
                    # H[iT-1 .. iT+T] = bhalo ++ tile ++ fhalo
                    bh = idx[f"hbh_{d}"][:].astype(fdt)
                    ghost = jnp.where(i > 0, bh, jnp.zeros_like(bh))
                    full = cat0(ghost, h_ext[d])         # T+2 planes
                    dfa = (full[1:] - full[:-1]) * inv_dx  # T+1
                    term = s * dfa                        # no x-PML here
                else:
                    dfa = yz_diff(h_ext[d], a, backward=True)
                    if a in slabs:
                        key = f"{c}_{AXES[a]}"
                        psi = cat0(idx[f"pe_{key}"][:],
                                   idx[f"pex_{key}"][:]).astype(fdt)
                        term = slab_term(dfa, psi, "e", a, s,
                                         idx[f"peo_{key}"], T)
                    else:
                        term = s * dfa
                acc = term if acc is None else acc + term
            old = e_old[c]
            if drude_e:
                j_old = cat0(idx[f"jin_{c}"][:],
                             idx[f"jex_{c}"][:]).astype(fdt)
                j_new = (coef_e(f"kj_{c}", True) * j_old
                         + coef_e(f"bj_{c}", True) * old)
                idx[f"jout_{c}"][:] = j_new[:T].astype(fdt)
                acc = acc - j_new
            e = coef_e(f"ca_{c}", True) * old \
                + coef_e(f"cb_{c}", True) * acc
            ca_ax = component_axis(c)
            if ca_ax != 0:
                e = e * wall_x_ext
            for a2 in (1, 2):
                if a2 != ca_ax:
                    e = e * idx[f"wall_{AXES[a2]}"][:].astype(fdt)
            new_e[c] = e
            idx[f"eout_{c}"][:] = e[:T].astype(fst)

        # ---- H update over the owned T planes ------------------------
        for c in h_comps:
            acc = None
            for (a, d, s) in terms_of(c, "H"):
                if d not in e_comps:
                    continue
                if a == 0:
                    # forward diff: new_e has T+1 planes; at the global
                    # edge the shifted plane is the PEC zero ghost
                    f = new_e[d][:T]
                    nxt = new_e[d][1:T + 1]
                    edge = jnp.where(
                        (i * T + lax.broadcasted_iota(
                            jnp.int32, (T, 1, 1), 0)) < n1 - 1,
                        nxt, jnp.zeros_like(nxt))
                    dfa = (edge - f) * inv_dx
                    term = s * dfa
                else:
                    dfa = yz_diff(new_e[d][:T], a, backward=False)
                    if a in slabs:
                        key = f"{c}_{AXES[a]}"
                        psi = idx[f"ph_{key}"][:].astype(fdt)
                        term = slab_term(dfa, psi, "h", a, s,
                                         idx[f"pho_{key}"], T)
                    else:
                        term = s * dfa
                acc = term if acc is None else acc + term
            old = h_old[c]
            if drude_m:
                k_new = (coef_h(f"km_{c}") * idx[f"kin_{c}"][:].astype(fdt)
                         + coef_h(f"bm_{c}") * old)
                idx[f"kout_{c}"][:] = k_new.astype(fdt)
                acc = acc + k_new
            h = coef_h(f"da_{c}") * old - coef_h(f"db_{c}") * acc
            idx[f"hout_{c}"][:] = h.astype(fst)

    # ---- specs ---------------------------------------------------------
    def tile_spec(last2=(n2, n3)):
        return pl.BlockSpec((T, last2[0], last2[1]), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def fwd_halo_spec(last2=(n2, n3)):
        return pl.BlockSpec(
            (1, last2[0], last2[1]),
            lambda i: (jnp.minimum((i + 1) * T, n1 - 1), 0, 0),
            memory_space=pltpu.VMEM)

    def bwd_halo_spec():
        return pl.BlockSpec(
            (1, n2, n3), lambda i: (jnp.maximum(i * T - 1, 0), 0, 0),
            memory_space=pltpu.VMEM)

    def psi_last2(nm):
        s = _psi_shape(nm)
        return (s[1], s[2])

    def vec_spec(a, length):
        if a == 0:
            return pl.BlockSpec((T, 1, 1), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM)
        s = [1, 1, 1]
        s[a] = length
        return pl.BlockSpec(tuple(s), lambda i: (0, 0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = (
        [tile_spec() for _ in e_comps]
        + [fwd_halo_spec() for _ in e_comps]
        + [tile_spec() for _ in h_comps]
        + [bwd_halo_spec() for _ in h_comps]
        + [fwd_halo_spec() for _ in h_comps]
        + [tile_spec(psi_last2(nm)) for nm in psi_e_names]
        + [fwd_halo_spec(psi_last2(nm)) for nm in psi_e_names]
        + [tile_spec(psi_last2(nm)) for nm in psi_h_names])
    if drude_e:
        in_specs += ([tile_spec() for _ in e_comps]
                     + [fwd_halo_spec() for _ in e_comps])
    if drude_m:
        in_specs += [tile_spec() for _ in h_comps]
    in_specs += [vec_spec(a, 2 * slabs[a]) for (_, _, a) in prof_specs]
    in_specs += [vec_spec(1, n2), vec_spec(2, n3)]
    in_specs += [tile_spec() for _ in arr_e]
    in_specs += [fwd_halo_spec() for _ in arr_e]
    in_specs += [tile_spec() for _ in arr_h]

    out_specs = ([tile_spec() for _ in e_comps]
                 + [tile_spec() for _ in h_comps]
                 + [tile_spec(psi_last2(nm)) for nm in psi_e_names]
                 + [tile_spec(psi_last2(nm)) for nm in psi_h_names])
    out_shape = ([jax.ShapeDtypeStruct((n1, n2, n3), fst)
                  for _ in e_comps + h_comps]
                 + [jax.ShapeDtypeStruct(_psi_shape(nm), np.float32)
                    for nm in psi_e_names + psi_h_names])
    if drude_e:
        out_specs += [tile_spec() for _ in e_comps]
        out_shape += [jax.ShapeDtypeStruct((n1, n2, n3), np.float32)
                      for _ in e_comps]
    if drude_m:
        out_specs += [tile_spec() for _ in h_comps]
        out_shape += [jax.ShapeDtypeStruct((n1, n2, n3), np.float32)
                      for _ in h_comps]

    # Input/output aliasing. SAFETY RULE: an aliased (donated) array may
    # only be read at its OWN tile's planes or FORWARD of them (a later
    # tile's region, still unwritten under the sequential grid order).
    # E/psi_E/J extra planes are forward reads -> safe to alias. H is
    # read BACKWARD (the bwd halo plane belongs to the previous tile,
    # which would already have overwritten it) -> H is NOT aliased.
    ne, nh = len(e_comps), len(h_comps)
    npe, nph = len(psi_e_names), len(psi_h_names)
    pos_in = {}
    p = 0
    pos_in["E"] = p; p += ne          # E in
    p += ne                           # E extra
    pos_in["H"] = p; p += nh
    p += 2 * nh                       # halos
    pos_in["psiE"] = p; p += npe
    p += npe                          # psi extra
    pos_in["psiH"] = p; p += nph
    if drude_e:
        pos_in["J"] = p; p += ne
        p += ne
    if drude_m:
        pos_in["K"] = p; p += nh
    aliases = {}
    for j in range(ne):
        aliases[pos_in["E"] + j] = j
    for j in range(npe):
        aliases[pos_in["psiE"] + j] = ne + nh + j
    for j in range(nph):
        aliases[pos_in["psiH"] + j] = ne + nh + npe + j
    out_p = ne + nh + npe + nph
    if drude_e:
        for j in range(ne):
            aliases[pos_in["J"] + j] = out_p + j
        out_p += ne
    if drude_m:
        for j in range(nh):
            aliases[pos_in["K"] + j] = out_p + j

    call = ops.x32(pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        compiler_params=COMPILER_PARAMS(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    ))

    def _vec3(v, a):
        s = [1, 1, 1]
        s[a] = v.shape[0]
        return v.astype(fdt).reshape(s)

    from fdtd3d_tpu.ops import pallas3d
    from fdtd3d_tpu.ops import tfsf as tfsf_mod

    def step(state, coeffs):
        t = state["t"]
        new_state = dict(state)
        if setup is not None:
            new_state["inc"] = tfsf_mod.advance_einc(
                state["inc"], coeffs, t, static.dt, static.omega, setup)
        args = [state["E"][c] for c in e_comps]
        args += [state["E"][c] for c in e_comps]       # extra (same array)
        args += [state["H"][c] for c in h_comps]
        args += [state["H"][c] for c in h_comps]       # bwd halo
        args += [state["H"][c] for c in h_comps]       # fwd halo
        args += [state["psi_E"][nm] for nm in psi_e_names]
        args += [state["psi_E"][nm] for nm in psi_e_names]
        args += [state["psi_H"][nm] for nm in psi_h_names]
        if drude_e:
            args += [state["J"][c] for c in e_comps]
            args += [state["J"][c] for c in e_comps]
        if drude_m:
            args += [state["K"][c] for c in h_comps]
        args += [_vec3(coeffs[key], a) for (_, key, a) in prof_specs]
        args += [_vec3(coeffs["wall_y"], 1), _vec3(coeffs["wall_z"], 2)]
        args += [coeffs[k] for k in arr_e]
        args += [coeffs[k] for k in arr_e]
        args += [coeffs[k] for k in arr_h]
        outs = call(*args)
        p = 0
        new_E = {c: outs[p + j] for j, c in enumerate(e_comps)}
        p += ne
        new_H = {c: outs[p + j] for j, c in enumerate(h_comps)}
        p += nh
        psi_E = dict(state.get("psi_E", {}))
        psi_H = dict(state.get("psi_H", {}))
        if psi_e_names or psi_h_names:
            psi_E.update({nm: outs[p + j]
                          for j, nm in enumerate(psi_e_names)})
            p += npe
            psi_H.update({nm: outs[p + j]
                          for j, nm in enumerate(psi_h_names)})
            p += nph
        if drude_e:
            new_state["J"] = {c: outs[p + j]
                              for j, c in enumerate(e_comps)}
            p += ne
        if drude_m:
            new_state["K"] = {c: outs[p + j]
                              for j, c in enumerate(h_comps)}
            p += nh

        # ---- E post-passes, collecting the applied thin patches ------
        patches: list = []
        if x_pml:
            px = {k: v for k, v in psi_E.items() if k.endswith("_x")}
            new_E, px_new = pallas3d.x_slab_post(
                static, "E", new_E, state["H"], px, coeffs, slabs,
                collect=patches)
            psi_E.update(px_new)
        if setup is not None:
            new_E = pallas3d.tfsf_patch(static, "E", new_E, coeffs,
                                        new_state["inc"],
                                        collect=patches)
        if static.cfg.point_source.enabled:
            new_E = pallas3d.point_source_patch(static, new_E, coeffs, t,
                                                collect=patches)

        # ---- H corrections: curl of the E patches --------------------
        if patches:
            new_H, psi_H = apply_patch_h_corrections(
                static, new_H, psi_H, patches, coeffs, slabs)
        if setup is not None:
            new_state["inc"] = tfsf_mod.advance_hinc(
                new_state["inc"], coeffs, setup)
        if x_pml:
            px = {k: v for k, v in psi_H.items() if k.endswith("_x")}
            new_H, px_new = pallas3d.x_slab_post(
                static, "H", new_H, new_E, px, coeffs, slabs)
            psi_H.update(px_new)
        if setup is not None:
            # H-side consistency corrections (sampling Einc at t^{n+1})
            new_H = pallas3d.tfsf_patch(static, "H", new_H, coeffs,
                                        new_state["inc"])

        new_state["E"] = new_E
        new_state["H"] = new_H
        if psi_E or psi_H:
            new_state["psi_E"] = psi_E
            new_state["psi_H"] = psi_H
        new_state["t"] = t + 1
        return new_state

    step.diag = {"tile": {"EH": T},
                 "vmem_block_bytes": {"EH": _block_bytes(T)}}
    return step
