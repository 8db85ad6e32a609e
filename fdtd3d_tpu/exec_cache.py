"""AOT executable cache: repeat scenarios skip compile (ROADMAP item 2a).

The production-service posture: for service-sized grids the compile
wall and the per-dispatch floor — not the kernels — dominate
time-to-first-field, so the compiled chunk executable is treated as a
first-class ARTIFACT, separable from the scenario spec it was compiled
for and from the state pytree it runs on (the three-object split of
``docs/SERVICE.md``; SNIPPETS.md's pjit shard/gather-fn pattern is the
template). Every chunk compile in the repo routes through
:func:`get_or_compile`, keyed by a canonical :class:`ExecKey`:

* **in-process layer** — a bounded digest -> ``jax.stages.Compiled``
  map: a second ``Simulation`` with an identical key performs ZERO
  traces (counter-asserted in tests/test_exec_cache.py);
* **on-disk layer** (``FDTD3D_AOT_CACHE_DIR``) — executables
  serialized via ``jax.experimental.serialize_executable`` (the AOT
  ``compile()`` product), published atomically (io.atomic_open), meta
  sidecar last so a half-written entry can never read as committed.
  A corrupt/truncated/stale-provenance entry is a NAMED miss, never a
  crash — the compile just happens again.

The key is deliberately WIDE: grid/scheme/dtype, the engaged step
kind + its tile + temporal-block ghost depth, topology + the planned
communication strategy, the health/per-chip telemetry lanes, the
donation posture, the batch width, argument avals, and jax+source
provenance (a content hash of the package's own files, so the key is
the same on a copy that is not a git checkout), plus a hash of the
full physics config. A collision would
silently reuse the wrong physics, so every axis that changes the
compiled graph is in the key; per-scenario VALUES (material
coefficient arrays, source amplitudes, the state itself) are traced
arguments and deliberately NOT in it — that separation is what makes
the cache useful.

Cache hit/miss counters surface in telemetry ``run_start``
(``aot_cache``) and ``run_end`` (``aot_cache`` + ``compile_ms``);
``FDTD3D_AOT_CACHE=0`` switches the whole layer off.

Trust note: the on-disk payload is a pickle (the same class of
artifact as jax's own persistent compilation cache) — point
``FDTD3D_AOT_CACHE_DIR`` only at directories you trust.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import time
from typing import Any, Callable, Dict, Optional, Tuple

from fdtd3d_tpu import log as _log

# bump when the on-disk payload layout changes: old entries then read
# as named stale-provenance misses instead of unpickling garbage
DISK_FORMAT = 1

# In-process layer bound: compiled executables are small (programs,
# not buffers) but a long test session builds hundreds of distinct
# keys; FIFO-evict beyond this. Sims keep their own reference
# (sim._compiled), so eviction never invalidates a live run.
MEM_CAP = 64


def enabled() -> bool:
    """The whole cache layer's off-switch: ``FDTD3D_AOT_CACHE=0`` (or
    ``off``/``no``) disables both layers — every compile then behaves
    exactly as the pre-cache build (still counted in the stats)."""
    return os.environ.get("FDTD3D_AOT_CACHE", "").lower() \
        not in ("0", "off", "no")


def cache_dir() -> Optional[str]:
    """On-disk layer root (``FDTD3D_AOT_CACHE_DIR``); None = memory
    only."""
    return os.environ.get("FDTD3D_AOT_CACHE_DIR") or None


_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(_PKG_DIR)


def use_compile_cache(min_compile_time_s: float = 1.0) -> str:
    """Turn on JAX's persistent compilation cache; -> its directory.

    The one place every entry point (CLI, queue ``serve``, bench.py,
    chip_smoke.py, the measure tools, the test conftest) places it;
    the first call in a process wins.
    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own
    and nothing is set here. Otherwise the cache lives at the fixed
    in-checkout path ``<repo>/.jax_cache`` (gitignored): the path is
    part of the cache's key, so a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir:
        # placed already (tests/conftest.py, an outer entry point):
        # the first placement in a process wins
        return jax.config.jax_compilation_cache_dir
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_s))
    return path


_source_fp: Optional[str] = None


def source_fingerprint() -> str:
    """sha256 over the package's ``.py`` files (relative path +
    bytes, sorted): the code-version axis of the key. Unlike a git sha
    it is defined on any copy of the tree, and it changes with an
    uncommitted edit."""
    global _source_fp
    if _source_fp is None:
        h = hashlib.sha256()
        for root, dirs, files in os.walk(_PKG_DIR):
            dirs[:] = sorted(d for d in dirs if d != "__pycache__")
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(root, name)
                    h.update(os.path.relpath(path, _PKG_DIR).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
        _source_fp = h.hexdigest()[:16]
    return _source_fp


# --------------------------------------------------------------------------
# the key
# --------------------------------------------------------------------------


def config_fingerprint(cfg) -> str:
    """Canonical hash of the PHYSICS configuration — everything that
    can change the traced graph except the axes the key carries
    explicitly. ``output`` (telemetry paths, cadences — the health/
    per-chip lanes are explicit key fields), ``time_steps`` (the chunk
    length ``n_steps`` is the compiled quantity) and ``require_pallas``
    (a constructor guard, not graph state) are excluded; everything
    else — sources, TFSF angles, PML grading, material STRUCTURE,
    courant factor — is in. Material/source VALUES that are traced
    arguments (coefficient arrays) still land in the fingerprint via
    cfg; that only narrows sharing, never corrupts it."""
    d = dataclasses.asdict(cfg)
    for k in ("output", "time_steps", "require_pallas"):
        d.pop(k, None)
    blob = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


def avals_fingerprint(*trees) -> str:
    """Hash of the (path-ordered) shapes+dtypes of the executable's
    argument pytrees — the defense-in-depth axis: a compiled artifact
    must never be invoked on avals it was not compiled for, even if
    every config-level key field collides."""
    import jax

    parts = []
    for tree in trees:
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
            parts.append(f"{jax.tree_util.keystr(path)}:{shape}:{dtype}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


@dataclasses.dataclass(frozen=True)
class ExecKey:
    """Canonical identity of one compiled chunk executable.

    Two runs with equal keys may share the artifact; ANY axis that
    changes the compiled graph must appear here (a collision = wrong
    physics silently reused — tests/test_exec_cache.py asserts the
    comm-strategy / ghost-depth / health-lane axes separate)."""

    scheme: str
    grid: Tuple[int, int, int]
    dtype: str
    step_kind: str
    tile: Optional[str]              # canonical json of step_diag tile
    ghost_depth: Optional[int]       # temporal-block pipeline depth k
    topology: Tuple[int, int, int]
    comm_strategy: Optional[str]     # canonical json of the record
    n_steps: int                     # compiled chunk length
    health: bool                     # in-graph health counters wired
    per_chip: bool                   # per-chip telemetry lane wired
    batch: int                       # vmap lanes (0 = unbatched)
    backend: str                     # jax backend / AOT topology tag
    donate: bool                     # carry-donation posture
    jax_version: str
    source_fp: str                   # source_fingerprint()
    config_fp: str                   # config_fingerprint(cfg)
    avals_fp: str                    # avals_fingerprint(args)
    # The mesh's device ids, in mesh order (None = the backend's
    # default placement). A compiled executable is DEVICE-PINNED: two
    # sims on the same topology but different device subsets (a
    # fleet/supervisor factory avoiding a faulted chip) must never
    # share one.
    devices: Optional[Tuple[int, ...]] = None

    def record(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["grid"] = list(self.grid)
        d["topology"] = list(self.topology)
        d["devices"] = list(self.devices) if self.devices else None
        return d

    @property
    def digest(self) -> str:
        blob = json.dumps(self.record(), sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()

    @property
    def comparable_digest(self) -> str:
        """Digest WITHOUT the jax/source provenance axes: equal across
        commits whenever nothing graph-shaping changed. A provenance
        bump legitimately invalidates the CACHE entry (the full
        digest), but must not excuse a compile-TIME regression —
        tools/perf_sentinel.py's compile lane gates cold compile_ms
        "at equal key" using this form."""
        rec = self.record()
        for k in ("jax_version", "source_fp"):
            rec.pop(k, None)
        blob = json.dumps(rec, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()


def registry_identity(key: ExecKey) -> Dict[str, Any]:
    """The run registry's executable-identity block
    (fdtd3d_tpu/registry.py): the provenance-free
    :attr:`ExecKey.comparable_digest` (stable across commits when
    nothing graph-shaping changed — the axis fleet_report and the SLO
    compile-budget rule join runs on) plus the human-readable axes a
    fleet table prints. Computed at the ``n_steps=0`` sentinel so two
    runs of one scenario share the digest regardless of chunking."""
    return {
        "exec_key_comparable": key.comparable_digest,
        "config_fp": key.config_fp,
        "step_kind": key.step_kind,
        "ghost_depth": key.ghost_depth,
    }


def mesh_device_ids(mesh) -> Optional[Tuple[int, ...]]:
    """The key's device-identity axis from a Mesh (None for no mesh:
    unsharded runs use the backend's default placement)."""
    if mesh is None:
        return None
    import numpy as _np
    return tuple(int(d.id) for d in _np.asarray(mesh.devices).flat)


def make_key(cfg, *, step_kind: str, topology, n_steps: int,
             health: bool = False, per_chip: bool = False,
             step_diag: Optional[Dict] = None, batch: int = 0,
             backend: Optional[str] = None,
             donate: Optional[bool] = None,
             avals_fp: str = "",
             devices: Optional[Tuple[int, ...]] = None) -> ExecKey:
    """Build the canonical ExecKey for one chunk compile.

    The tile / ghost depth / comm strategy come from the ENGAGED
    step's ``step_diag`` when the caller has one (the record the
    kernel actually consumed at build wins — the telemetry run_start
    convention); otherwise they are derived deterministically from the
    planner (plan.comm_strategy re-scores for the pinned kind, so an
    ``FDTD3D_COMM_STRATEGY``/``FDTD3D_TB_DEPTH`` override lands in the
    key even before any kernel is built)."""
    import jax

    topology = tuple(int(p) for p in topology)
    diag = step_diag or {}
    tile = diag.get("tile")
    depth = diag.get("temporal_block")
    if depth is None and step_kind == "pallas_packed_tb":
        from fdtd3d_tpu import solver as _solver
        from fdtd3d_tpu.ops import pallas_packed_tb
        static = dataclasses.replace(_solver.build_static(cfg),
                                     topology=topology)
        depth = pallas_packed_tb.planned_depth(static)
    strat = diag.get("comm_strategy")
    if strat is None and any(p > 1 for p in topology):
        from fdtd3d_tpu import plan as _plan
        s = _plan.comm_strategy(cfg, topology, step_kind=step_kind)
        strat = s.as_record() if s is not None else None
    if backend is None:
        backend = jax.default_backend()
    if donate is None:
        donate = backend == "tpu"
    return ExecKey(
        scheme=cfg.scheme, grid=tuple(cfg.grid_shape), dtype=cfg.dtype,
        step_kind=step_kind,
        tile=json.dumps(tile, sort_keys=True) if tile else None,
        ghost_depth=int(depth) if depth is not None else None,
        topology=topology,
        comm_strategy=json.dumps(strat, sort_keys=True)
        if strat else None,
        n_steps=int(n_steps), health=bool(health),
        per_chip=bool(per_chip), batch=int(batch), backend=str(backend),
        donate=bool(donate), jax_version=jax.__version__,
        source_fp=source_fingerprint(),
        config_fp=config_fingerprint(cfg), avals_fp=avals_fp,
        devices=tuple(int(d) for d in devices) if devices else None)


# --------------------------------------------------------------------------
# stats (surfaced in telemetry run_start/run_end `aot_cache`)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    hits: int = 0            # in-process layer hits
    misses: int = 0          # neither layer had it
    disk_hits: int = 0       # deserialized from FDTD3D_AOT_CACHE_DIR
    disk_load_failures: int = 0   # corrupt/stale entries read as misses
    traces: int = 0          # lower() calls actually performed
    compiles: int = 0        # compile() calls actually performed
    compile_ms: float = 0.0  # wall spent in lower+compile


STATS = CacheStats()


def stats() -> Dict[str, Any]:
    """Process-wide counter snapshot (JSON-ready): the assertion
    surface for the zero-trace guarantee and the ``aot_cache`` record
    telemetry run_start/run_end carry."""
    d = dataclasses.asdict(STATS)
    d["compile_ms"] = round(d["compile_ms"], 3)
    d["mem_entries"] = len(_MEM)
    d["disk_dir"] = cache_dir()
    d["enabled"] = enabled()
    return d


_MEM: Dict[str, Any] = {}


def clear_memory() -> None:
    """Drop the in-process layer (tests / bench's cold-compile stage).
    Live sims keep their own references; the disk layer is untouched."""
    _MEM.clear()


# --------------------------------------------------------------------------
# disk layer
# --------------------------------------------------------------------------


def _entry_paths(key: ExecKey) -> Tuple[str, str]:
    d = cache_dir() or ""
    dig = key.digest
    return (os.path.join(d, f"{dig}.json"),
            os.path.join(d, f"{dig}.aotx"))


def _disk_load(key: ExecKey):
    """-> Compiled or None. EVERY failure mode — missing, truncated,
    unpicklable, stale provenance, backend mismatch — is a named miss
    (warned), never an exception: a damaged cache must cost one
    recompile, not a run."""
    if cache_dir() is None:
        return None
    meta_path, bin_path = _entry_paths(key)
    if not os.path.exists(meta_path):
        return None
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except Exception as exc:
        STATS.disk_load_failures += 1
        _log.warn(f"aot cache: unreadable meta {meta_path} ({exc}); "
                  f"treating as a miss")
        return None
    # Provenance double-check (defense in depth beyond the digest): a
    # hand-copied or forged entry from another build must read as a
    # stale miss, not execute.
    for field, want in (("format", DISK_FORMAT),
                        ("jax_version", key.jax_version),
                        ("source_fp", key.source_fp),
                        ("backend", key.backend)):
        if meta.get(field) != want:
            STATS.disk_load_failures += 1
            _log.warn(f"aot cache: stale entry {meta_path} "
                      f"({field}={meta.get(field)!r} != {want!r}); "
                      f"treating as a miss")
            return None
    try:
        import jax
        from jax.experimental import serialize_executable as _se
        with open(bin_path, "rb") as f:
            payload, in_tree, out_tree = pickle.loads(f.read())
        # bind to the devices it was compiled for: without
        # execution_devices a reloaded executable binds to EVERY local
        # device and a one-device program then wants one shard each
        if key.devices:
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in key.devices]
        else:
            devices = jax.devices()[:1]
        return _se.deserialize_and_load(payload, in_tree, out_tree,
                                        execution_devices=devices)
    except Exception as exc:
        STATS.disk_load_failures += 1
        _log.warn(f"aot cache: entry {bin_path} failed to load "
                  f"({type(exc).__name__}: {exc}); treating as a miss")
        return None


def _disk_store(key: ExecKey, compiled) -> None:
    """Best-effort publish (rank 0): payload first, meta sidecar LAST
    — the meta is the commit marker, so a crash mid-publish leaves an
    orphan payload the loader never consults. Serialization support
    varies by backend (abstract-AOT executables serialize; some
    interpreters do not) — an unserializable executable is a logged
    skip, never an error."""
    d = cache_dir()
    if d is None:
        return
    try:
        import jax
        if jax.process_index() != 0:
            return
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
    except Exception as exc:
        _log.warn(f"aot cache: executable not serializable on this "
                  f"backend ({type(exc).__name__}: {exc}); entry not "
                  f"written")
        return
    from fdtd3d_tpu.io import atomic_open
    meta_path, bin_path = _entry_paths(key)
    try:
        os.makedirs(d, exist_ok=True)
        with atomic_open(bin_path, "wb") as f:
            f.write(blob)
        meta = dict(key.record(), format=DISK_FORMAT)
        with atomic_open(meta_path, "w") as f:
            f.write(json.dumps(meta, indent=1) + "\n")
    except OSError as exc:
        _log.warn(f"aot cache: could not publish {bin_path} ({exc})")


# --------------------------------------------------------------------------
# the cache
# --------------------------------------------------------------------------


def get_or_compile(key: ExecKey, lower_fn: Callable[[], Any]
                   ) -> Tuple[Any, Dict[str, Any]]:
    """The one compile gateway: ``lower_fn()`` -> ``jax.stages.Lowered``
    is invoked ONLY on a full miss (it is the trace). Returns
    ``(compiled, info)`` with ``info`` carrying ``source`` (one of
    ``memory``/``disk``/``compiled``) and ``compile_ms`` (0.0 on any
    hit). Compile/lower failures propagate untouched — the VMEM
    fallback ladder (sim._vmem_fallback) owns them — and are never
    cached."""
    if not enabled():
        t0 = time.perf_counter()
        lowered = lower_fn()
        STATS.traces += 1
        compiled = lowered.compile()
        STATS.compiles += 1
        ms = (time.perf_counter() - t0) * 1e3
        STATS.compile_ms += ms
        return compiled, {"source": "compiled", "compile_ms": ms,
                          "digest": key.digest}
    dig = key.digest
    hit = _MEM.get(dig)
    if hit is not None:
        STATS.hits += 1
        return hit, {"source": "memory", "compile_ms": 0.0,
                     "digest": dig}
    compiled = _disk_load(key)
    if compiled is not None:
        STATS.disk_hits += 1
        _remember(dig, compiled)
        return compiled, {"source": "disk", "compile_ms": 0.0,
                          "digest": dig}
    STATS.misses += 1
    t0 = time.perf_counter()
    lowered = lower_fn()
    STATS.traces += 1
    compiled = lowered.compile()
    STATS.compiles += 1
    ms = (time.perf_counter() - t0) * 1e3
    STATS.compile_ms += ms
    _remember(dig, compiled)
    _disk_store(key, compiled)
    return compiled, {"source": "compiled", "compile_ms": ms,
                      "digest": dig}


def jit_compile(key: ExecKey, fn, args_fn, donate: bool
                ) -> Tuple[Any, Dict[str, Any]]:
    """The ONE jit+lower+compile gateway both chunk executors use
    (Simulation._chunk_fn and BatchSimulation._chunk_fn): donate-jit
    ``fn`` (argument 0 when ``donate``), then compile through the
    cache. ``args_fn()`` supplies the lower-time arguments LAZILY —
    a sim's carry may be re-packed between VMEM-ladder attempts, so
    it must be re-read at lower time, not captured at call time.
    Keeping this in one place means a new ExecKey axis or donation
    rule cannot be threaded into one executor and missed in the
    other. The executable is named ``jit_fdtd3d_chunk`` (its HLO
    module, and the device trace's ``XLA Modules`` line) and kept for
    ``telemetry.op_scopes()``."""
    import jax

    from fdtd3d_tpu import telemetry
    jitted = jax.jit(chunk_named(fn), donate_argnums=0 if donate else ())
    compiled, info = get_or_compile(key, lambda: jitted.lower(*args_fn()))
    telemetry.remember_executable(key.digest, compiled)
    return compiled, info


def chunk_named(fn):
    """``fn`` under the stable name ``fdtd3d_chunk``: jit names the
    module after the function, and a ``functools.partial`` reads as
    ``_unknown``."""
    def fdtd3d_chunk(*args):
        return fn(*args)
    return fdtd3d_chunk


def _remember(dig: str, compiled) -> None:
    if len(_MEM) >= MEM_CAP:
        # FIFO eviction: drop the oldest insertion (dict preserves
        # insertion order); live sims hold their own references
        _MEM.pop(next(iter(_MEM)))
    _MEM[dig] = compiled


# --------------------------------------------------------------------------
# the shared AOT build (tools/aot_overlap.py + abstract-topology compiles)
# --------------------------------------------------------------------------


class WrongStepKind(RuntimeError):
    """The AOT build engaged a different kernel than the caller
    required (``aot_compile_sharded(require_kinds=...)``) — raised
    BEFORE any lowering, so a mis-scoped config costs nothing."""


def aot_compile_sharded(cfg, topo3: Tuple[int, int, int], mesh,
                        n_steps: int, backend_tag: str,
                        require_kinds: Optional[Tuple[str, ...]] = None):
    """Compile cfg's PRODUCTION chunk runner sharded over an explicit
    ``Mesh`` (possibly of abstract AOT devices) through the cache ->
    ``(runner, compiled, info)``.

    The one AOT build both tools/aot_overlap.py and abstract-topology
    warmers share: runner construction, packed-spec inference,
    shard_map + donate-jit, lower and cached compile all live here, so
    the overlap tool measures the executable production would run —
    and its compiles warm the on-disk layer for a later real window.
    ``backend_tag`` names the target (e.g. ``"aot:v5e:2x2"``) so an
    abstract-topology entry can never collide with a runnable one."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding

    from fdtd3d_tpu.parallel import mesh as pmesh
    from fdtd3d_tpu.solver import (build_coeffs, build_static,
                                   init_state, make_chunk_runner)
    import dataclasses as _dc
    import functools as _ft

    st = _dc.replace(build_static(cfg), topology=topo3)
    mesh_axes = pmesh.mesh_axis_map(topo3)
    mesh_shape = pmesh.mesh_shape_map(topo3)
    coeffs_np = build_coeffs(st)
    state_shapes = jax.eval_shape(lambda: init_state(st))
    runner = make_chunk_runner(st, mesh_axes, mesh_shape)
    if require_kinds is not None and runner.kind not in require_kinds:
        raise WrongStepKind(
            f"step_kind {runner.kind!r}, wanted one of "
            f"{tuple(require_kinds)}")
    packed = getattr(runner, "packed", False)
    shapes = jax.eval_shape(runner.pack, state_shapes) if packed \
        else state_shapes
    specs = pmesh.packed_specs(shapes, topo3) if packed \
        else pmesh.state_specs(state_shapes, topo3)
    coeff_specs = pmesh.coeff_specs(coeffs_np, topo3)

    fn = pmesh.shard_map_compat(_ft.partial(runner, n=n_steps),
                                mesh, in_specs=(specs, coeff_specs),
                                out_specs=specs)

    def sds(shape_tree, spec_tree):
        return jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
            shape_tree, spec_tree)

    coeff_shapes = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v),
                                       np.asarray(v).dtype),
        coeffs_np)
    args = (sds(shapes, specs), sds(coeff_shapes, coeff_specs))
    key = make_key(cfg, step_kind=runner.kind, topology=topo3,
                   n_steps=n_steps, step_diag=getattr(runner, "diag",
                                                      None),
                   backend=backend_tag, donate=True,
                   avals_fp=avals_fingerprint(*args),
                   devices=mesh_device_ids(mesh))
    jitted = jax.jit(chunk_named(fn), donate_argnums=0)
    compiled, info = get_or_compile(key,
                                    lambda: jitted.lower(*args))
    return runner, compiled, info
