"""Fused Pallas TPU kernels for the 3D leapfrog hot path.

Reference parity: this is the TPU twin of the reference's CUDA
``InternalScheme`` kernels (SURVEY.md §2 CudaGrid/InternalScheme rows,
§3.3) — one fused device kernel per field family per step instead of the
XLA op-graph the pure-jnp path compiles to. Like the reference's hybrid
MPI+CUDA mode (SURVEY.md §2.9 item 6), the fused kernels compose with the
domain decomposition: the SAME kernels run inside ``shard_map``, with the
y/z ghost planes fetched by ``lax.ppermute`` outside the kernel and
streamed in as thin extra blocks.

Why it exists (measured on v5e, 256^3 + 10-cell CPML, f32): XLA's compiled
step moves ~743 bytes/cell/step of HBM traffic vs ~72 ideal — the CPML
slab deltas and curl intermediates each materialize full arrays. The fused
kernel computes each family update in ONE pass over tiles resident in
VMEM: curl + material update + CPML psi recursion + Drude ADE current +
PEC walls, reading each field once and writing each output once.

Design:

* Grid over x-slabs of ``tile`` planes of the LOCAL shard; blocks span
  the full LOCAL (y, z) extent.
* The one-plane x halo (backward diff for E, forward for H) is fetched as
  a SEPARATE single-plane block of the same HBM array via an index map
  (``i*T - 1`` clamped / ``(i+1)*T`` clamped); the shard-edge tile's
  ghost plane is zero (the PEC ghost, matching ops/stencil.py) on an
  unsharded x axis, or the x neighbor's ppermuted boundary plane when
  the x axis is sharded (zeros arrive at the global mesh edge).
* On a sharded y/z axis the one-plane halo comes from the neighbor shard:
  the step function ppermutes the boundary plane per source component
  (exactly ``ParallelGrid::share()``'s ghost exchange, SURVEY.md §3.2) and
  the kernel reads it as a (T, 1, nz)/(T, ny, 1) block instead of the
  zero plane. At the global mesh edge ppermute delivers zeros — again the
  PEC ghost.
* CPML profile (b, c, 1/kappa) vectors, PEC wall masks, and 3D material
  coefficient grids stream as kernel inputs taken from the coeffs pytree,
  so under shard_map every rank reads its OWN slice (interior ranks see
  identity profiles and all-ones walls — one SPMD program, like the
  reference's sigma grids being zero outside the PML).
* y/z-axis CPML psi slabs are block-aligned along x, so they stream
  through the same grid; their recursions + curl-accumulator deltas run
  in-kernel on VMEM data.
* x-axis CPML psi (compact along the grid axis — NOT block-aligned) is
  corrected by a thin jnp post-pass on the 2(npml+1) boundary planes
  (`x_slab_post`), exactly the solver.py slab-delta algebra restricted to
  the slabs. TFSF face corrections and point sources are jnp patches on
  single planes/cells (`tfsf_patch`, `point_source_patch`); on a sharded
  axis the patch index is ownership-gated per shard.
* The Drude ADE current recursion (J' = kj J + bj E; E -= cb J') runs
  in-kernel on the same VMEM-resident data — two extra FMAs per E
  component (reference: dispersive update with prev-prev layers,
  SURVEY.md §2 InternalScheme row).

Eligibility (everything else falls back to the identical-semantics jnp
path in solver.py): 3D scheme, real f32/bf16 storage; any decomposition
topology. The kernels run in interpreter mode on CPU so the same code
path is testable without a TPU (tests/test_pallas.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fdtd3d_tpu.layout import CURL_TERMS, component_axis
from fdtd3d_tpu import ops
from fdtd3d_tpu.ops import tfsf as tfsf_mod
from fdtd3d_tpu.ops.sources import waveform
from fdtd3d_tpu.telemetry import named as _named

AXES = "xyz"

# jax renamed pltpu.TPUCompilerParams -> pltpu.CompilerParams between
# releases; take whichever this jax exposes (shared by every kernel
# module) so the kernels run on both sides of the rename.
COMPILER_PARAMS = getattr(pltpu, "CompilerParams", None) \
    or getattr(pltpu, "TPUCompilerParams")


def eligible(static, mesh_axes=None) -> bool:
    """True when the fused kernels cover this configuration.

    Any axis may shard — y/z halos ride ppermute outside the kernel and
    stream in as thin ghost blocks; an x (tiling-axis) halo plane is
    likewise ppermuted and fed to the shard-edge tiles where the kernel
    would otherwise use the PEC zero ghost. Drude and sharded meshes are
    in-scope; complex fields and non-3D modes fall back to jnp.
    """
    if static.mode.name != "3D":
        return False
    if static.field_dtype not in (np.float32, jnp.bfloat16):
        return False
    if static.cfg.compensated:
        return False  # Kahan residuals live in the packed kernel only
    if static.cfg.ds_fields:
        return False  # double-single pairs: jnp_ds / packed-ds only
    return True


# Mosaic's default scoped-VMEM limit is 16 MiB; v5e/v5p have 128 MiB of
# physical VMEM. Raise the limit and budget the double-buffered working
# set well under it (measured: 256^3 at T=8 needs ~38 MiB). The default
# budget is conservative because Mosaic's own scratch (kernel
# temporaries) measured 40-47 MiB on top of the blocks and does not
# model cleanly across sizes: 512^3 two-pass at T=4 (2x43 MiB blocks)
# compiles and runs 18% faster than T=2, while 256^3 at T=16 (2x41 MiB)
# overflows by 0.7 MiB. FDTD3D_VMEM_BUDGET_MB overrides for callers
# prepared to catch the (loud, compile-time) OOM and retry — bench.py
# does this for its 512^3 stage.
_VMEM_LIMIT = 100 << 20
_VMEM_BUDGET = 64 << 20


def _vmem_budget() -> int:
    import os
    v = os.environ.get("FDTD3D_VMEM_BUDGET_MB")
    if v:
        try:
            return int(v) << 20
        except ValueError:
            pass
    return _VMEM_BUDGET


def _pick_tile(n1: int, block_bytes_at) -> int:
    """Largest divisor T of n1 whose double-buffered VMEM use fits budget.

    ``block_bytes_at(t)`` returns the summed bytes of every kernel operand
    block (inputs + outputs) at x-tile size t; Mosaic double-buffers each
    block for grid pipelining, hence the factor 2.
    """
    budget = _vmem_budget()
    for t in (32, 16, 8, 4, 2, 1):
        if n1 % t == 0 and 2 * block_bytes_at(t) <= budget:
            return t
    for t in (8, 4, 2, 1):
        if n1 % t == 0:
            return t
    return 1


# ---------------------------------------------------------------------------
# kernel builder (one per family)
# ---------------------------------------------------------------------------

# Term classification for CPML handling per (component, derivative axis):
#   "plain" — no PML on this axis: acc += s * dfa
#   "slab"  — in-kernel compact psi (axis 1 or 2)
#   "full"  — in-kernel full-length psi (axis 1 or 2, thin-grid fallback)
#   "post"  — axis 0: pure curl in-kernel, psi delta patched by x_slab_post


def _classify(static, slabs: Dict[int, int], axis: int) -> str:
    if axis not in static.pml_axes:
        return "plain"
    if axis == 0:
        return "post"
    return "slab" if axis in slabs else "full"


def make_family_kernel(static, np_coeffs, family: str, local_shape,
                       tile: Optional[int], slabs: Dict[int, int],
                       sharded_axes: Tuple[int, ...], interpret: bool):
    """Build the fused pallas update for one family ('E' or 'H').

    ``local_shape`` is the per-shard extent (globals divided by the
    topology); ``sharded_axes`` lists which axes have >1 shards (their
    halos arrive as ghost-plane inputs; the axis-0 ghost feeds the
    shard-edge tiles of the x tiling).

    Returns (run, psi_names, ghost_pairs) where
    run(fields_in, src, psi, coeffs, ghosts) ->
        (new_fields, new_psi_inkernel[, new_J])
    with psi holding only the in-kernel (y/z-axis) psi arrays and ghosts
    keyed by (src_comp, axis).
    """
    mode = static.mode
    n1, n2, n3 = local_shape
    inv_dx = np.float32(1.0 / static.dx)
    upd = mode.e_components if family == "E" else mode.h_components
    tag = "e" if family == "E" else "h"
    backward = family == "E"
    # ADE dispersion: electric Drude (J) on the E family, magnetic Drude
    # (K, metamaterial mode) on the H family — same recursion, dual sign
    drude = static.use_drude if family == "E" else static.use_drude_m
    ade = ("kj", "bj") if family == "E" else ("km", "bm")

    # ---- static layout of kernel operands ------------------------------
    src_names = list(mode.h_components if family == "E"
                     else mode.e_components)
    # halo planes for the axis-0 derivative (same-array index-map blocks)
    halo_names: List[str] = []
    # neighbor ghost planes for sharded y/z derivatives: (src_comp, axis)
    ghost_pairs: List[Tuple[str, int]] = []
    # in-kernel psi terms: comp -> [(axis, src, sign, kind)]
    terms: Dict[str, List[Tuple[int, str, int, str]]] = {}
    psi_names: List[str] = []
    for c in upd:
        terms[c] = []
        for (a, d_axis, s) in CURL_TERMS[component_axis(c)]:
            d = ("H" if family == "E" else "E") + AXES[d_axis]
            if d not in src_names:
                continue
            if a == 0 and d not in halo_names:
                halo_names.append(d)
            if a in sharded_axes and (d, a) not in ghost_pairs:
                ghost_pairs.append((d, a))
            kind = _classify(static, slabs, a)
            terms[c].append((a, d, s, kind))
            if kind in ("slab", "full"):
                psi_names.append(f"{c}_{AXES[a]}")

    # material coefficient layout: scalar -> embedded; array -> streamed
    pairs = (("ca", "cb") if family == "E" else ("da", "db"))
    coeff_keys = [f"{p}_{c}" for c in upd for p in pairs]
    if drude:
        coeff_keys += [f"{p}_{c}" for c in upd for p in ade]
    coeff_is_array = {k: np.ndim(np_coeffs[k]) == 3 for k in coeff_keys}
    array_coeff_names = [k for k, v in coeff_is_array.items() if v]

    # CPML profile vectors: one (b, c, ik) triple per distinct in-kernel
    # psi axis, streamed from the coeffs pytree (key, axis, slab?).
    profile_srcs: List[Tuple[str, str, int, bool]] = []  # (ref, key, axis)
    seen_axes = set()
    for c in upd:
        for (a, d, s, kind) in terms[c]:
            if kind in ("slab", "full") and a not in seen_axes:
                seen_axes.add(a)
                ax = AXES[a]
                for p in ("b", "c", "ik"):
                    key = (f"pml_slab_{p}{tag}_{ax}" if kind == "slab"
                           else f"pml_{p}{tag}_{ax}")
                    profile_srcs.append((f"pf_{p}_{ax}", key, a,
                                         kind == "slab"))

    def _prof_len(a: int, slab: bool) -> int:
        return 2 * slabs[a] if slab else local_shape[a]

    # PEC wall masks (E family only): 1D arrays from coeffs, one per axis.
    wall_axes = [a for a in range(3)] if family == "E" else []

    def _ghost_shape(a: int) -> Tuple[int, int, int]:
        s = [n1, n2, n3]
        s[a] = 1
        return tuple(s)

    def _psi_shape(name: str) -> Tuple[int, int, int]:
        a = AXES.index(name[-1])
        s = [n1, n2, n3]
        if a in slabs:
            s[a] = 2 * slabs[a]
        return tuple(s)

    # f32-width accounting even for bf16 storage: in-kernel compute is
    # f32, so Mosaic scratch scales with the f32 temporaries, not the
    # storage bytes (see ops/pallas_fused.py for the measured overflow)
    fbytes = max(np.dtype(static.field_dtype).itemsize, 4)

    def _block_bytes(t: int) -> int:
        """Summed operand-block bytes at x-tile size t (see _pick_tile)."""
        plane = n2 * n3
        n_field = len(upd) + len(src_names) + len(upd)  # in + src + out
        total = n_field * t * plane * fbytes
        total += len(array_coeff_names) * t * plane * 4
        if drude:
            total += 2 * len(upd) * t * plane * 4       # J in + J out
        total += len(halo_names) * plane * fbytes
        for (_, a) in ghost_pairs:
            gs = _ghost_shape(a)
            total += (1 if a == 0 else t) * gs[1] * gs[2] * fbytes
        for nm in psi_names:  # psi in + psi out
            s = _psi_shape(nm)
            total += 2 * t * s[1] * s[2] * 4
        for (_, _, a, slab) in profile_srcs:
            total += _prof_len(a, slab) * 4
        for a in wall_axes:
            total += (t if a == 0 else local_shape[a]) * 4
        return total

    T = tile if tile is not None else _pick_tile(n1, _block_bytes)
    ntiles = n1 // T

    fdt = jnp.float32                 # in-kernel compute dtype
    fst = static.field_dtype          # field STORAGE dtype (f32 or bf16)

    # ---- the kernel ----------------------------------------------------
    def kernel(*refs):
        idx = {}
        pos = 0
        for name in upd:
            idx[f"in_{name}"] = refs[pos]; pos += 1
        if drude:
            for name in upd:
                idx[f"jin_{name}"] = refs[pos]; pos += 1
        for name in src_names:
            idx[f"src_{name}"] = refs[pos]; pos += 1
        for name in halo_names:
            idx[f"halo_{name}"] = refs[pos]; pos += 1
        for (d, a) in ghost_pairs:
            idx[f"gh_{d}_{a}"] = refs[pos]; pos += 1
        for name in psi_names:
            idx[f"psi_{name}"] = refs[pos]; pos += 1
        for (ref, _, _, _) in profile_srcs:
            idx[ref] = refs[pos]; pos += 1
        for a in wall_axes:
            idx[f"wl_{AXES[a]}"] = refs[pos]; pos += 1
        for name in array_coeff_names:
            idx[f"coef_{name}"] = refs[pos]; pos += 1
        for name in upd:
            idx[f"out_{name}"] = refs[pos]; pos += 1
        if drude:
            for name in upd:
                idx[f"jout_{name}"] = refs[pos]; pos += 1
        for name in psi_names:
            idx[f"pso_{name}"] = refs[pos]; pos += 1

        i = pl.program_id(0)

        # loads cast to the f32 compute dtype (no-op for f32 storage)
        src_vals = {name: idx[f"src_{name}"][:].astype(fdt)
                    for name in src_names}

        def diff(name: str, axis: int) -> jnp.ndarray:
            f = src_vals[name]
            if axis == 0:
                h = idx[f"halo_{name}"][:].astype(fdt)
                # shard-edge plane: the x neighbor's boundary plane when
                # axis 0 is sharded (ppermute delivers zeros at the
                # global mesh edge = the PEC ghost), else the PEC zero.
                if (name, 0) in ghost_pairs:
                    edge = idx[f"gh_{name}_0"][:].astype(fdt)
                else:
                    edge = jnp.zeros_like(h)
                if backward:
                    ghost = jnp.where(i > 0, h, edge)
                    # T == 1: the shifted tile IS the ghost plane (a
                    # zero-size f[:-1] slice is rejected by Mosaic)
                    sh = ghost if T == 1 else jnp.concatenate(
                        [ghost, f[:-1]], axis=0)
                    return (f - sh) * inv_dx
                ghost = jnp.where(i < ntiles - 1, h, edge)
                sh = ghost if T == 1 else jnp.concatenate(
                    [f[1:], ghost], axis=0)
                return (sh - f) * inv_dx
            if axis in sharded_axes:
                # neighbor plane (zeros at the global mesh edge = PEC ghost)
                gh = idx[f"gh_{name}_{axis}"][:].astype(fdt)
                if backward:
                    body = lax.slice_in_dim(f, 0, f.shape[axis] - 1,
                                            axis=axis)
                    sh = jnp.concatenate([gh, body], axis=axis)
                    return (f - sh) * inv_dx
                body = lax.slice_in_dim(f, 1, f.shape[axis], axis=axis)
                sh = jnp.concatenate([body, gh], axis=axis)
                return (sh - f) * inv_dx
            zero = jnp.zeros_like(
                lax.slice_in_dim(f, 0, 1, axis=axis))
            if backward:
                body = lax.slice_in_dim(f, 0, f.shape[axis] - 1, axis=axis)
                sh = jnp.concatenate([zero, body], axis=axis)
                return (f - sh) * inv_dx
            body = lax.slice_in_dim(f, 1, f.shape[axis], axis=axis)
            sh = jnp.concatenate([body, zero], axis=axis)
            return (sh - f) * inv_dx

        def coef(key: str):
            if coeff_is_array[key]:
                return idx[f"coef_{key}"][:]
            return fdt(float(np_coeffs[key]))

        for c in upd:
            acc = None
            for (a, d, s, kind) in terms[c]:
                dfa = diff(d, a)
                if kind in ("slab", "full"):
                    key = f"{c}_{AXES[a]}"
                    psi = idx[f"psi_{key}"][:]
                    ax = AXES[a]
                    b = idx[f"pf_b_{ax}"][:]
                    cc = idx[f"pf_c_{ax}"][:]
                    ik = idx[f"pf_ik_{ax}"][:]
                    if kind == "slab":
                        m = slabs[a]
                        nloc = dfa.shape[a]
                        cut = functools.partial(lax.slice_in_dim, axis=a)
                        d_lo = cut(dfa, 0, m)
                        d_hi = cut(dfa, nloc - m, nloc)
                        p_lo = (cut(b, 0, m) * cut(psi, 0, m)
                                + cut(cc, 0, m) * d_lo)
                        p_hi = (cut(b, m, 2 * m) * cut(psi, m, 2 * m)
                                + cut(cc, m, 2 * m) * d_hi)
                        idx[f"pso_{key}"][:] = jnp.concatenate(
                            [p_lo, p_hi], axis=a)
                        dl = s * ((cut(ik, 0, m) - 1.0) * d_lo + p_lo)
                        dh = s * ((cut(ik, m, 2 * m) - 1.0) * d_hi + p_hi)
                        mid_shape = list(dfa.shape)
                        mid_shape[a] = nloc - 2 * m
                        delta = jnp.concatenate(
                            [dl, jnp.zeros(mid_shape, fdt), dh], axis=a)
                        term = s * dfa + delta
                    else:
                        p_new = b * psi + cc * dfa
                        idx[f"pso_{key}"][:] = p_new
                        term = s * (ik * dfa + p_new)
                else:
                    term = s * dfa
                acc = term if acc is None else acc + term

            old = idx[f"in_{c}"][:].astype(fdt)
            if drude:
                ade_new = (coef(f"{ade[0]}_{c}") * idx[f"jin_{c}"][:]
                           + coef(f"{ade[1]}_{c}") * old)
                idx[f"jout_{c}"][:] = ade_new.astype(fdt)
                # J is driven by +curl (subtract); K opposes -curl (add)
                acc = acc - ade_new if family == "E" else acc + ade_new
            if family == "E":
                new = coef(f"ca_{c}") * old + coef(f"cb_{c}") * acc
                for a in range(3):
                    if a != component_axis(c):
                        new = new * idx[f"wl_{AXES[a]}"][:]
            else:
                new = coef(f"da_{c}") * old - coef(f"db_{c}") * acc
            idx[f"out_{c}"][:] = new.astype(fst)

    # ---- specs ---------------------------------------------------------
    def field_spec():
        return pl.BlockSpec((T, n2, n3), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def halo_spec():
        if backward:
            return pl.BlockSpec(
                (1, n2, n3), lambda i: (jnp.maximum(i * T - 1, 0), 0, 0),
                memory_space=pltpu.VMEM)
        return pl.BlockSpec(
            (1, n2, n3),
            lambda i: (jnp.minimum((i + 1) * T, n1 - 1), 0, 0),
            memory_space=pltpu.VMEM)

    def ghost_spec(a: int):
        gs = _ghost_shape(a)
        if a == 0:
            # one full (n2, n3) plane, shared by every tile
            return pl.BlockSpec((1, gs[1], gs[2]), lambda i: (0, 0, 0),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((T, gs[1], gs[2]), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def psi_spec(name: str):
        s = _psi_shape(name)
        return pl.BlockSpec((T, s[1], s[2]), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def vec_spec(a: int, length: int):
        """1D profile/wall broadcast block along axis a."""
        s = [1, 1, 1]
        s[a] = length
        if a == 0:
            return pl.BlockSpec((T, 1, 1), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec(tuple(s), lambda i: (0, 0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [field_spec() for _ in upd]
    if drude:
        in_specs += [field_spec() for _ in upd]
    in_specs += [field_spec() for _ in src_names]
    in_specs += [halo_spec() for _ in halo_names]
    in_specs += [ghost_spec(a) for (_, a) in ghost_pairs]
    in_specs += [psi_spec(nm) for nm in psi_names]
    in_specs += [vec_spec(a, _prof_len(a, slab))
                 for (_, _, a, slab) in profile_srcs]
    in_specs += [vec_spec(a, local_shape[a]) for a in wall_axes]
    in_specs += [field_spec() for _ in array_coeff_names]

    out_specs = [field_spec() for _ in upd]
    if drude:
        out_specs += [field_spec() for _ in upd]
    out_specs += [psi_spec(nm) for nm in psi_names]

    out_shape = [jax.ShapeDtypeStruct((n1, n2, n3), static.field_dtype)
                 for _ in upd]
    if drude:
        out_shape += [jax.ShapeDtypeStruct((n1, n2, n3), np.float32)
                      for _ in upd]
    out_shape += [jax.ShapeDtypeStruct(_psi_shape(nm), np.float32)
                  for nm in psi_names]

    # donate the updated family's buffers (+J, +psi) into the outputs
    n_upd = len(upd)
    aliases = {j: j for j in range(n_upd)}
    if drude:
        for j in range(n_upd):
            aliases[n_upd + j] = n_upd + j
    psi_in_start = ((2 if drude else 1) * n_upd + len(src_names)
                    + len(halo_names) + len(ghost_pairs))
    psi_out_start = (2 if drude else 1) * n_upd
    for j in range(len(psi_names)):
        aliases[psi_in_start + j] = psi_out_start + j

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

    def _vec3(v: jnp.ndarray, a: int) -> jnp.ndarray:
        s = [1, 1, 1]
        s[a] = v.shape[0]
        return v.astype(fdt).reshape(s)

    def run(fields: Dict[str, jnp.ndarray], src: Dict[str, jnp.ndarray],
            psi: Dict[str, jnp.ndarray], coeffs: Dict[str, jnp.ndarray],
            ghosts: Dict[Tuple[str, int], jnp.ndarray], J=None):
        """Invoke the built pallas_call (see make_family_kernel)."""
        args = [fields[c] for c in upd]
        if drude:
            args += [J[c] for c in upd]
        args += [src[c] for c in src_names]
        args += [src[c] for c in halo_names]
        args += [ghosts[(d, a)] for (d, a) in ghost_pairs]
        args += [psi[nm] for nm in psi_names]
        args += [_vec3(coeffs[key], a) for (_, key, a, _) in profile_srcs]
        args += [_vec3(coeffs[f"wall_{AXES[a]}"], a) for a in wall_axes]
        args += [coeffs[k] for k in array_coeff_names]
        outs = call(*args)
        new_fields = {c: outs[j] for j, c in enumerate(upd)}
        k = n_upd
        new_j = None
        if drude:
            new_j = {c: outs[k + j] for j, c in enumerate(upd)}
            k += n_upd
        new_psi = {nm: outs[k + j] for j, nm in enumerate(psi_names)}
        return new_fields, new_psi, new_j

    # startup observability (VERDICT r2 item 7): surfaced via step.diag
    run.tile = T
    run.block_bytes = _block_bytes(T)
    return run, psi_names, ghost_pairs


# ---------------------------------------------------------------------------
# halo exchange for the sharded case (outside the kernel)
# ---------------------------------------------------------------------------


def gather_ghosts(src: Dict[str, jnp.ndarray],
                  ghost_pairs: List[Tuple[str, int]],
                  mesh_axes, mesh_shape, backward: bool):
    """ppermute the one-plane halos the kernel needs (any sharded axis).

    backward=True (E family): each shard receives the LAST plane of its
    lower neighbor; False (H family): the FIRST plane of its upper
    neighbor. Non-periodic, so edge shards receive zeros (PEC ghost) —
    identical to ops/stencil.py's _neighbor_plane convention. Axis-0
    ghosts feed the kernel's shard-edge tiles; y/z ghosts are read as
    thin blocks by every tile.
    """
    out = {}
    for (d, a) in ghost_pairs:
        name = mesh_axes[a]
        n_sh = mesh_shape[name]
        f = src[d]
        n = f.shape[a]
        if backward:
            plane = lax.slice_in_dim(f, n - 1, n, axis=a)
            perm = [(i, i + 1) for i in range(n_sh - 1)]
        else:
            plane = lax.slice_in_dim(f, 0, 1, axis=a)
            perm = [(i + 1, i) for i in range(n_sh - 1)]
        out[(d, a)] = lax.ppermute(plane, name, perm)
    return out


# ---------------------------------------------------------------------------
# component views: dict-of-arrays or packed (ncomp, n1, n2, n3) stacks
# ---------------------------------------------------------------------------


class PackedView:
    """Mutable per-component view over a packed ``(ncomp, n1, n2, n3)`` array.

    Duck-types the ``Dict[str, array]`` interface the thin post-passes
    consume: component reads are lazy leading-index slices (XLA fuses the
    slice into the thin consumers), writes go through ``add_at`` as
    scatter updates on the packed array — never a full per-component
    materialization (which would cost a full HBM pass per step and negate
    the packed kernel's traffic win; ops/pallas_packed.py).
    """

    __slots__ = ("arr", "comps", "_idx")

    def __init__(self, arr, comps):
        self.arr = arr
        self.comps = tuple(comps)
        self._idx = {c: j for j, c in enumerate(self.comps)}

    def clone(self) -> "PackedView":
        return PackedView(self.arr, self.comps)

    def __contains__(self, c) -> bool:
        return c in self._idx

    def __getitem__(self, c):
        return self.arr[self._idx[c]]

    def keys(self):
        return self._idx.keys()

    def add_at(self, c, sl, val):
        self.arr = self.arr.at[(self._idx[c],) + tuple(sl)].add(val)


class PackedPsiView:
    """CPML psi view: per-axis packed stacks + plain entries for the rest.

    ``stacks[a]`` is a ``(k, ...)`` stack of the compact psi arrays whose
    slab axis is ``a``; ``rows[key] = (a, j)`` maps a psi name to its row.
    Keys not in ``rows`` (the x-axis psi of the packed kernel, which only
    the jnp post-pass touches) live in ``extra`` as ordinary arrays.
    """

    __slots__ = ("stacks", "rows", "extra")

    def __init__(self, stacks, rows, extra=None):
        self.stacks = dict(stacks)
        self.rows = rows
        self.extra = dict(extra or {})

    def clone(self) -> "PackedPsiView":
        return PackedPsiView(self.stacks, self.rows, self.extra)

    def __contains__(self, key) -> bool:
        return key in self.rows or key in self.extra

    def __getitem__(self, key):
        if key in self.rows:
            a, j = self.rows[key]
            return self.stacks[a][j]
        return self.extra[key]

    def add_at(self, key, sl, val):
        if key in self.rows:
            a, j = self.rows[key]
            self.stacks[a] = self.stacks[a].at[(j,) + tuple(sl)].add(val)
        else:
            self.extra[key] = self.extra[key].at[tuple(sl)].add(val)

    def set_full(self, key, val):
        if key in self.rows:
            a, j = self.rows[key]
            self.stacks[a] = self.stacks[a].at[j].set(val)
        else:
            self.extra[key] = val


class Patch(NamedTuple):
    """One applied E-side field delta, for post-hoc H correction.

    The single-pass fused kernels (ops/pallas_fused.py,
    ops/pallas_packed.py) compute H from the PRE-patch E; the linearity
    of the update lets them re-add the curl of each patch afterwards
    (pallas_fused.apply_patch_h_corrections). Two flavors:

    * static (``own is None``): ``start`` is a shard-local int — slab
      patches (always at local planes 0 / n-m on every shard) and
      TFSF/point patches on an unsharded axis.
    * traced (``own`` is a traced bool): the patch normal axis is
      SHARDED, so the local index of the global plane ``gstart`` is the
      traced ``start`` (ownership-clamped, pallas3d._local_index) and
      ``delta`` is owner-gated (zero on non-owner shards). Always one
      plane (k == 1).
    """

    comp: str
    axis: int
    start: Any            # local plane index (static int or traced i32)
    delta: Any            # thin local delta array, owner-gated
    own: Any = None       # traced ownership bool (sharded axis) or None
    gstart: int = -1      # static GLOBAL plane (traced patches only)
    scope: Optional[str] = None   # named scope of its H correction


def fields_copy(fields):
    """Shallow copy of a component container (dict or PackedView)."""
    return dict(fields) if isinstance(fields, dict) else fields.clone()


def fields_add(fields, c, sl, val):
    """fields[c].at[sl].add(val) for either container; mutates and returns."""
    if isinstance(fields, dict):
        fields[c] = fields[c].at[tuple(sl)].add(val)
    else:
        fields.add_at(c, sl, val)
    return fields


def psi_set(psi, key, val):
    if isinstance(psi, dict):
        psi[key] = val
    else:
        psi.set_full(key, val)
    return psi


# ---------------------------------------------------------------------------
# jnp post-passes (thin patches on kernel output)
# ---------------------------------------------------------------------------


def slab_post(static, family: str, fields, src, psi_ax, coeffs,
              slabs, axis: int, collect=None, src_slabs=None):
    """Apply one axis's CPML psi recursion + delta onto kernel output.

    The kernel computed plain s*dfa for this axis's curl terms; the
    exact CPML term differs only on the two slabs of `axis` by
    s*((ik-1)*dfa + psi') (solver.py's _slab_delta restricted to one
    axis). ``collect``, when a list, receives the APPLIED field deltas
    as thin patches (comp, axis, start, delta_array) — the single-pass
    fused kernels (ops/pallas_fused.py, ops/pallas_packed.py) consume
    them to correct the H update they computed from the pre-patch E.

    ``src_slabs``, when given, maps each source component to its two
    pre-sliced boundary regions ``(f_lo, f_hi)`` — the m+1 planes
    [0, m+1) and [n1-m-1, n1) along `axis` — and `src` is not read.
    The packed kernel donates its source arrays into the pallas call,
    so reading them afterwards would force XLA to defensively copy the
    whole family (+2 volumes/step); the thin regions are sliced off
    BEFORE the call instead.

    All slices are shard-local: under a sharded topology the slab
    profile / wall / cb slices are per-shard (identity on interior
    shards, so their deltas are exactly zero — and the one edge plane
    whose local derivative lacks the true neighbor value only ever
    multiplies those identity profiles).
    """
    mode = static.mode
    upd = mode.e_components if family == "E" else mode.h_components
    tag = "e" if family == "E" else "h"
    ax = AXES[axis]
    inv_dx = 1.0 / static.dx
    n1 = static.grid_shape[axis] // static.topology[axis]
    m = slabs[axis]
    b = coeffs[f"pml_slab_b{tag}_{ax}"]
    cc = coeffs[f"pml_slab_c{tag}_{ax}"]
    ik = coeffs[f"pml_slab_ik{tag}_{ax}"]

    def r3(v, lo, hi):
        shape = [1, 1, 1]
        shape[axis] = hi - lo
        return v[lo:hi].reshape(shape)

    def cut(f, lo, hi):
        return lax.slice_in_dim(f, lo, hi, axis=axis)

    def pad1(f, lo_side: bool):
        pad = [(0, 0)] * 3
        pad[axis] = (1, 0) if lo_side else (0, 1)
        return jnp.pad(f, pad)

    def slab_slice(lo, hi):
        sl = [slice(None)] * 3
        sl[axis] = slice(lo, hi)
        return tuple(sl)

    new_fields = fields_copy(fields)
    new_psi = fields_copy(psi_ax)
    for c in upd:
        for (a, d_axis, s) in CURL_TERMS[component_axis(c)]:
            if a != axis:
                continue
            d = ("H" if family == "E" else "E") + AXES[d_axis]
            if src_slabs is not None:
                if d not in src_slabs:
                    continue
                f_lo, f_hi = src_slabs[d]
                f_lo = f_lo.astype(static.compute_dtype)
                f_hi = f_hi.astype(static.compute_dtype)
            else:
                if d not in src:
                    continue
                # slice FIRST, convert the thin regions after: astype on
                # the full array risks a full-volume materialization if
                # XLA does not fuse the convert into the slices
                # (measured as a ~35% step tax on bf16 at 768^3)
                f = src[d]
                f_lo = cut(f, 0, m + 1).astype(static.compute_dtype)
                f_hi = cut(f, n1 - m - 1, n1).astype(static.compute_dtype)
            if family == "E":  # backward diff, slabs [0,m) / [n1-m,n1)
                d_lo = (cut(f_lo, 0, m) - pad1(cut(f_lo, 0, m - 1), True)) \
                    * inv_dx
                d_hi = (cut(f_hi, 1, m + 1) - cut(f_hi, 0, m)) * inv_dx
            else:              # forward diff
                d_lo = (cut(f_lo, 1, m + 1) - cut(f_lo, 0, m)) * inv_dx
                d_hi = (pad1(cut(f_hi, 2, m + 1), False)
                        - cut(f_hi, 1, m + 1)) * inv_dx
            key = f"{c}_{ax}"
            psi = psi_ax[key]
            p_lo = r3(b, 0, m) * cut(psi, 0, m) + r3(cc, 0, m) * d_lo
            p_hi = (r3(b, m, 2 * m) * cut(psi, m, 2 * m)
                    + r3(cc, m, 2 * m) * d_hi)
            psi_set(new_psi, key, jnp.concatenate([p_lo, p_hi], axis=axis))
            dl = s * ((r3(ik, 0, m) - 1.0) * d_lo + p_lo)
            dh = s * ((r3(ik, m, 2 * m) - 1.0) * d_hi + p_hi)
            cb = coeffs[("cb_" if family == "E" else "db_") + c]
            sign = 1.0 if family == "E" else -1.0
            if jnp.ndim(cb) == 3:
                cb_lo = cb[slab_slice(0, m)]
                cb_hi = cb[slab_slice(n1 - m, n1)]
            else:
                cb_lo = cb_hi = cb
            if family == "E":
                # respect PEC walls (kernel already zeroed the field)
                wx = coeffs[f"wall_{ax}"]
                dl = dl * r3(wx, 0, m)
                dh = dh * r3(wx, n1 - m, n1)
                ca_ax = component_axis(c)
                for a2 in range(3):
                    if a2 != ca_ax and a2 != axis:
                        w = coeffs[f"wall_{AXES[a2]}"]
                        shape = [1, 1, 1]
                        shape[a2] = w.shape[0]
                        dl = dl * w.reshape(shape)
                        dh = dh * w.reshape(shape)
            fdt = new_fields[c].dtype
            fshape = new_fields[c].shape
            add_lo = (sign * cb_lo * dl).astype(fdt)
            add_hi = (sign * cb_hi * dh).astype(fdt)
            fields_add(new_fields, c, slab_slice(0, m), add_lo)
            fields_add(new_fields, c, slab_slice(n1 - m, n1), add_hi)
            if collect is not None:
                lo_shape = list(fshape)
                lo_shape[axis] = m
                collect.append(Patch(c, axis, 0, jnp.broadcast_to(
                    add_lo, lo_shape)))
                collect.append(Patch(c, axis, n1 - m, jnp.broadcast_to(
                    add_hi, lo_shape)))
    return new_fields, new_psi


def x_slab_post(static, family, fields, src, psi_x, coeffs, slabs,
                collect=None, src_slabs=None):
    """Axis-0 wrapper of slab_post (the two-pass kernels' post-pass)."""
    return slab_post(static, family, fields, src, psi_x, coeffs, slabs,
                     0, collect, src_slabs)


def plane_corrections(field: str, comp: str, setup, coeffs, inc,
                      active_axes, dx: float):
    """TFSF corrections as (axis, plane, broadcastable term) patches.

    ``tfsf.corr_plane_term``'s face-plane terms (the one authority for
    the f32 sampling and the transverse gate) with the plane index for
    an .at[plane].add patch, in place of corrections_for's full-size
    onehot. All coordinates come from the gx/gy/gz global-index
    arrays, so the terms are correct per shard.
    """
    out = []
    for corr in setup.corrections:
        if corr.field != field or corr.comp != comp:
            continue
        term = tfsf_mod.corr_plane_term(corr, setup, coeffs, inc,
                                        active_axes, dx)
        if term is not None:
            out.append((corr.axis, corr.plane, term))
    return out


def _local_index(static, coeffs, axis: int, pos: int):
    """(local index, ownership mask) of global cell `pos` along `axis`.

    The local index is pos - first-owned-global-index (gx/gy/gz hold each
    shard's global coordinates), clamped into range so every rank runs the
    same program; `own` is None on an unsharded axis (always owner) and a
    traced bool on a sharded one.
    """
    if static.topology[axis] <= 1:
        return pos, None
    g = coeffs[f"g{AXES[axis]}"]
    n_loc = static.grid_shape[axis] // static.topology[axis]
    loc = pos - g[0]
    own = (loc >= 0) & (loc < n_loc)
    return jnp.clip(loc, 0, n_loc - 1), own


def _plane_add(static, fields, c, axis: int, plane: int, val, coeffs):
    """fields[c][..., plane, ...] += val, ownership-gated on a sharded axis.

    Unsharded axis: static index (XLA folds to an in-place slice update).
    Sharded axis: the add is zeroed on non-owner shards. Returns
    (fields, loc, own, gated_val) so callers that collect Patch records
    (tfsf_patch) reuse the same gating/indexing — the sharded-plane-add
    convention lives in exactly one place.
    """
    loc, own = _local_index(static, coeffs, axis, plane)
    sl: List[Any] = [slice(None)] * 3
    sl[axis] = loc
    if own is not None:
        val = jnp.where(own, val, 0.0).astype(fields[c].dtype)
    return fields_add(fields, c, sl, val), loc, own, val


def _plane_coef(static, cb, axis: int, plane: int, coeffs):
    """cb sliced at a (possibly sharded-axis) plane; scalar cb passes through."""
    if jnp.ndim(cb) != 3:
        return cb
    loc, own = _local_index(static, coeffs, axis, plane)
    if own is None:
        sl = [slice(None)] * 3
        sl[axis] = loc
        return cb[tuple(sl)]
    return lax.dynamic_index_in_dim(cb, loc, axis, keepdims=False)


def tfsf_patch(static, family: str, fields: Dict[str, jnp.ndarray],
               coeffs, inc, collect=None) -> Dict[str, jnp.ndarray]:
    """Add the TFSF face corrections onto the kernel output planes.

    ``collect`` (list or None): receives the applied deltas as Patch
    records — static local-plane patches on unsharded axes, traced
    ownership-gated ones on sharded axes (round 5) — see x_slab_post
    and pallas_fused.apply_patch_h_corrections. The two-pass path
    passes None.
    """
    setup = static.tfsf_setup
    mode = static.mode
    upd = mode.e_components if family == "E" else mode.h_components
    out = fields_copy(fields)
    for c in upd:
        patches = plane_corrections(family, c, setup, coeffs, inc,
                                    mode.active_axes, static.dx)
        if not patches:
            continue
        cb = coeffs[("cb_" if family == "E" else "db_") + c]
        sign = 1.0 if family == "E" else -1.0
        fdt = out[c].dtype
        fshape = out[c].shape
        for (axis, plane, term) in patches:
            if plane < 0 or plane >= static.grid_shape[axis]:
                continue
            scale = _plane_coef(static, cb, axis, plane, coeffs)
            t2 = jnp.squeeze(term, axis=axis)
            if family == "E":
                # PEC wall zeroing must survive the patch
                ca_ax = component_axis(c)
                for a2 in mode.active_axes:
                    if a2 != ca_ax and a2 != axis:
                        w = coeffs[f"wall_{AXES[a2]}"]
                        shp = [1, 1, 1]
                        shp[a2] = w.shape[0]
                        t2 = t2 * jnp.squeeze(
                            w.reshape(shp), axis=axis)
            val = (sign * scale * t2).astype(fdt)
            out, loc, own, val = _plane_add(static, out, c, axis, plane,
                                            val, coeffs)
            if collect is not None:
                pshape = list(fshape)
                pshape[axis] = 1
                collect.append(Patch(
                    c, axis, plane if own is None else loc,
                    jnp.broadcast_to(jnp.expand_dims(val, axis), pshape),
                    own, plane, "tfsf"))
    return out


def point_source_patch(static, fields, coeffs, t, collect=None):
    """Soft point source as a single-cell add, ownership-gated per shard.

    ``collect``: receives the applied delta as a one-x-plane Patch with
    a single nonzero cell — static on an unsharded x axis, traced
    (local index + x-ownership) on a sharded one.
    """
    ps = static.cfg.point_source
    c = ps.component
    if c not in fields:
        return fields
    wf = waveform(ps.waveform, t, 0.5, static.omega, static.dt,
                  static.real_dtype)
    out = fields_copy(fields)
    fdt = out[c].dtype
    fshape = out[c].shape
    cb = coeffs[f"cb_{c}"]
    idxs = []
    owns = []
    own = None
    for a in range(3):
        loc, o = _local_index(static, coeffs, a, ps.position[a])
        idxs.append(loc)
        owns.append(o)
        if o is not None:
            own = o if own is None else own & o
    scale = cb
    if jnp.ndim(cb) == 3:
        scale = cb[tuple(idxs)]
    # amplitude from the TRACED ps_amp coefficient (build_coeffs), not
    # the static config float: per-lane amplitudes must reach a
    # vmap-batched kernel step through the operand tree. Bit-identical
    # to the old static multiply — ps_amp is the f32 round of
    # cfg.point_source.amplitude, exactly what weak-type promotion of
    # the python float produced here before.
    amp = coeffs["ps_amp"] if "ps_amp" in coeffs else ps.amplitude
    val = amp * scale * wf
    if own is not None:
        val = jnp.where(own, val, 0.0)
    val = val.astype(fdt)
    if collect is not None:
        plane = jnp.zeros((1,) + tuple(fshape[1:]), fdt)
        plane = plane.at[0, idxs[1], idxs[2]].add(val)
        if owns[0] is None:
            collect.append(Patch(c, 0, ps.position[0], plane,
                                 scope="source"))
        else:
            collect.append(Patch(c, 0, idxs[0], plane, owns[0],
                                 ps.position[0], "source"))
    return fields_add(out, c, tuple(idxs), val)


# ---------------------------------------------------------------------------
# the fused step
# ---------------------------------------------------------------------------


def make_pallas_step(static, mesh_axes=None, mesh_shape=None):
    """Full leapfrog step via fused kernels. Same signature/state layout as
    solver.make_step's jnp step; returns None if the config is ineligible."""
    from fdtd3d_tpu import solver as solver_mod

    if not eligible(static, mesh_axes):
        return None
    topo = static.topology
    local_shape = tuple(static.grid_shape[a] // topo[a] for a in range(3))
    if any(topo[a] > 1 and not (mesh_axes or {}).get(a) for a in range(3)):
        return None  # sharded axis without a mesh axis name to permute on
    sharded_axes = tuple(a for a in range(3)
                         if topo[a] > 1 and (mesh_axes or {}).get(a))
    mesh_axes = mesh_axes or {}
    mesh_shape = mesh_shape or {}
    slabs = solver_mod.slab_axes(static)
    np_coeffs = solver_mod.build_coeffs(static)
    tile = None  # per-family auto pick (VMEM-budgeted, _pick_tile)
    interpret = not ops.on_tpu()

    run_e, psi_e_names, ghosts_e = make_family_kernel(
        static, np_coeffs, "E", local_shape, tile, slabs, sharded_axes,
        interpret)
    run_h, psi_h_names, ghosts_h = make_family_kernel(
        static, np_coeffs, "H", local_shape, tile, slabs, sharded_axes,
        interpret)
    setup = static.tfsf_setup
    x_active = 0 in static.pml_axes
    x_slab = 0 in slabs
    if x_active and not x_slab:
        # thin-grid full-length x psi: not covered by the fused path
        return None

    def step(state, coeffs):
        t = state["t"]
        new_state = dict(state)

        if setup is not None:
            with _named("tfsf"):
                new_state["inc"] = tfsf_mod.advance_einc(
                    state["inc"], coeffs, t, static.dt, static.omega,
                    setup)

        # E family ------------------------------------------------------
        # named scopes mirror the jnp step's so the cost ledger
        # (fdtd3d_tpu/costs.py) attributes the two-pass kernels to the
        # same sections: the family kernel call is the E/H-update, the
        # x-slab post-pass is cpml, patches are tfsf/source.
        with _named("E-update"):
            psi_e_in = {k: state["psi_E"][k] for k in psi_e_names} \
                if psi_e_names else {}
            with _named("halo-exchange"):
                gh_e = gather_ghosts(state["H"], ghosts_e, mesh_axes,
                                     mesh_shape, backward=True)
            new_E, psi_e_out, new_J = run_e(state["E"], state["H"],
                                            psi_e_in, coeffs, gh_e,
                                            J=state.get("J"))
            if new_J is not None:
                new_state["J"] = new_J
            psi_E = dict(state.get("psi_E", {}), **psi_e_out)
            if x_active:
                with _named("cpml"):
                    px = {k: v for k, v in psi_E.items()
                          if k.endswith("_x")}
                    new_E, px_new = x_slab_post(static, "E", new_E,
                                                state["H"], px, coeffs,
                                                slabs)
                    psi_E.update(px_new)
            if setup is not None:
                with _named("tfsf"):
                    new_E = tfsf_patch(static, "E", new_E, coeffs,
                                       new_state["inc"])
            if static.cfg.point_source.enabled:
                with _named("source"):
                    new_E = point_source_patch(static, new_E, coeffs, t)
        new_state["E"] = new_E

        if setup is not None:
            with _named("tfsf"):
                new_state["inc"] = tfsf_mod.advance_hinc(
                    new_state["inc"], coeffs, setup)

        # H family ------------------------------------------------------
        with _named("H-update"):
            psi_h_in = {k: state["psi_H"][k] for k in psi_h_names} \
                if psi_h_names else {}
            with _named("halo-exchange"):
                gh_h = gather_ghosts(new_E, ghosts_h, mesh_axes,
                                     mesh_shape, backward=False)
            new_H, psi_h_out, new_K = run_h(state["H"], new_E, psi_h_in,
                                            coeffs, gh_h,
                                            J=state.get("K"))
            if new_K is not None:
                new_state["K"] = new_K
            psi_H = dict(state.get("psi_H", {}), **psi_h_out)
            if x_active:
                with _named("cpml"):
                    px = {k: v for k, v in psi_H.items()
                          if k.endswith("_x")}
                    new_H, px_new = x_slab_post(static, "H", new_H,
                                                new_E, px, coeffs, slabs)
                    psi_H.update(px_new)
            if setup is not None:
                # H-side consistency corrections (Einc at t^{n+1})
                with _named("tfsf"):
                    new_H = tfsf_patch(static, "H", new_H, coeffs,
                                       new_state["inc"])
        new_state["H"] = new_H

        if psi_E:
            new_state["psi_E"] = psi_E
            new_state["psi_H"] = psi_H
        new_state["t"] = t + 1
        return new_state

    step.diag = {"tile": {"E": run_e.tile, "H": run_h.tile},
                 "vmem_block_bytes": {"E": run_e.block_bytes,
                                      "H": run_h.block_bytes}}
    return step
