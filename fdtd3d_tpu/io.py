"""Grid dump/load: DAT (raw binary), TXT, BMP; full-state checkpoints.

Reference parity: ``Source/File/`` dumper/loader hierarchy (SURVEY.md §2 —
BMPDumper/BMPLoader/DATDumper/DATLoader/TXTDumper/TXTLoader + BMPHelper)
and the DAT-as-checkpoint posture of §5.4:

* DAT — bare little-endian binary of the grid values (bit-exact roundtrip;
  doubles as the material/field exchange format). A ``.manifest.json``
  sidecar records shape/dtype/step so files are self-describing without
  breaking the bare-values layout.
* TXT — human-readable ``i j k value`` lines.
* BMP — colormapped 2D cut (central slice of the first two active axes),
  written by a dependency-free 24-bit BMP encoder (the reference vendors
  EasyBMP; we need ~40 lines, SURVEY.md §7 non-goals).
* checkpoint — one ``.npz`` of the ENTIRE solver state pytree (fields,
  CPML psi, Drude J, incident line, step counter), the orbax-free
  equivalent of the reference's save->load-from-DAT resume workflow.

Durability contract (docs/ROBUSTNESS.md): EVERY file this package
writes goes through the atomic writer (``atomic_open`` /
``atomic_publish``: tmp file + fsync + ``os.replace``), so a crash
mid-write can never leave a torn artifact under the final name —
asserted structurally by tests/test_lint_atomic_write.py. Append-only
JSONL sinks (telemetry, metrics) are the one sanctioned exception:
each record is a single flushed line, and a torn tail line is
tolerated by their readers. Checkpoints additionally carry a payload
checksum + per-array manifest; readers raise :class:`CheckpointCorrupt`
(naming the path and WHICH check failed) instead of a raw numpy/zip
traceback, and orbax checkpoint directories require a COMMIT marker
written only after the save fully finished.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import struct
import time
import zipfile
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from fdtd3d_tpu import _native
from fdtd3d_tpu import faults as _faults
from fdtd3d_tpu import log as _log


class CheckpointCorrupt(ValueError):
    """A checkpoint failed an integrity check.

    The message names the path and WHICH check failed (zip/npz
    structure, manifest, checksum, missing COMMIT marker). Resume paths
    (CLI ``--resume auto``, the supervisor's rollback) catch this and
    fall back to an older committed snapshot."""


# ---------------------------------------------------------------------------
# atomic writer — the one durable-write primitive
# ---------------------------------------------------------------------------


def _tmp_name(path: str) -> str:
    return f"{path}.tmp.{os.getpid()}"


def _fsync_dir(path: str) -> None:
    """fsync the parent directory so the rename itself is durable."""
    try:
        fd = os.open(os.path.dirname(os.path.abspath(path)) or ".",
                     os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:  # pragma: no cover - platform without dir fsync
        pass


def _publish_tmp(path: str, tmp: str) -> None:
    """The shared publish epilogue of both atomic primitives: fire the
    fail-the-Nth-write fault hook BEFORE the rename (the final name
    must never have been touched on an injected failure), then rename
    into place and fsync the parent directory."""
    _faults.on_write(path)
    os.replace(tmp, path)
    _fsync_dir(path)


def atomic_append(path: str, data: str) -> None:
    """Whole-record append for shared JSONL indexes (the run registry,
    ``fdtd3d_tpu/registry.py``): ONE ``os.write`` of the complete
    record to an ``O_APPEND`` descriptor, then fsync. POSIX O_APPEND
    makes each such write land contiguously, so several concurrent
    runs appending to one ``runs.jsonl`` interleave whole lines —
    never torn ones — and a crash mid-append costs at most its own
    line. (``atomic_open`` is the whole-file flavor; append-mode
    sinks must not rewrite the file they share.)"""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    buf = data.encode()
    fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
    try:
        # os.write may write fewer bytes than asked (quota, RLIMIT,
        # network filesystems) — loop, or the no-torn-lines contract
        # above is fiction exactly when the disk is misbehaving
        while buf:
            n = os.write(fd, buf)
            buf = buf[n:]
        os.fsync(fd)
    finally:
        os.close(fd)


@contextlib.contextmanager
def atomic_open(path: str, mode: str = "w"):
    """Crash-safe whole-file write: tmp + flush + fsync + ``os.replace``.

    The file appears under its final name fully written or not at all;
    a crash (or an injected ``fail_write`` fault) mid-write leaves the
    previous version intact and no debris under the final name. Modes:
    'w'/'wb'/'x'/'xb' only — append-mode sinks don't rewrite and read
    modes don't write."""
    if any(c in mode for c in "ra+"):
        raise ValueError(
            f"atomic_open is for whole-file writes ('w'/'wb'/'x'), "
            f"got mode {mode!r}")
    tmp = _tmp_name(path)
    try:
        with open(tmp, mode) as fh:
            yield fh
            fh.flush()
            os.fsync(fh.fileno())
        _publish_tmp(path, tmp)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_publish(path: str, write_fn) -> None:
    """Atomic publish for writers that need a real filesystem path
    (the native C++ dumpers, ``ndarray.tofile``): ``write_fn(tmp)``
    produces the complete file, which is then fsync'd and renamed into
    place. Same crash contract as :func:`atomic_open`."""
    tmp = _tmp_name(path)
    try:
        write_fn(tmp)
        with open(tmp, "rb") as fh:
            os.fsync(fh.fileno())
        _publish_tmp(path, tmp)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise

# ---------------------------------------------------------------------------
# DAT
# ---------------------------------------------------------------------------


def dump_dat(arr: np.ndarray, path: str, step: Optional[int] = None):
    """Bare binary dump (little-endian, C order) + .manifest.json sidecar.

    Writes through the native C++ backend (native/fdtd3d_io.cpp) when
    built, matching the reference's C++ DATDumper; Python fallback emits
    byte-identical files.
    """
    arr = np.asarray(arr)
    le = arr.astype(arr.dtype.newbyteorder("<"), copy=False)

    def _write(tmp):
        if not _native.write_raw(tmp, le):
            le.tofile(tmp)

    atomic_publish(path, _write)
    # record the dtype of the bytes actually written (little-endian) —
    # recording the source dtype breaks roundtrip for big-endian input.
    manifest = {"shape": list(arr.shape), "dtype": le.dtype.str,
                "order": "C", "endian": "little"}
    if step is not None:
        manifest["step"] = int(step)
    with atomic_open(path + ".manifest.json", "w") as f:
        json.dump(manifest, f)


def load_dat(path: str, shape: Optional[Tuple[int, ...]] = None,
             dtype=None) -> np.ndarray:
    """Load a DAT dump; shape/dtype from the sidecar when not given."""
    if shape is None or dtype is None:
        with open(path + ".manifest.json") as f:
            manifest = json.load(f)
        shape = shape or tuple(manifest["shape"])
        dtype = dtype or np.dtype(manifest["dtype"])
    native = _native.read_raw(path, shape, dtype)
    if native is not None:
        return native
    return np.fromfile(path, dtype=dtype).reshape(shape)


# ---------------------------------------------------------------------------
# TXT
# ---------------------------------------------------------------------------


def dump_txt(arr: np.ndarray, path: str):
    """Reference-style human-readable dump: one ``i j k value`` per line.

    Formatted by the native backend when built (the Python nditer loop is
    ~40x slower on 3D grids); formats are identical (%.9e).
    """
    arr = np.asarray(arr)

    def _write(tmp):
        if _native.dump_txt(tmp, arr):
            return
        with open(tmp, "w") as f:
            it = np.nditer(arr, flags=["multi_index"])
            for v in it:
                idx = " ".join(str(i) for i in it.multi_index)
                if np.iscomplexobj(arr):
                    f.write(f"{idx} {v.real:.9e} {v.imag:.9e}\n")
                else:
                    f.write(f"{idx} {float(v):.9e}\n")

    atomic_publish(path, _write)


def load_txt(path: str, shape: Tuple[int, ...],
             dtype=np.float64) -> np.ndarray:
    native = _native.load_txt(path, shape, dtype)
    if native is not None:
        return native
    out = np.zeros(shape, dtype=dtype)
    nd = len(shape)
    with open(path) as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            idx = tuple(int(p) for p in parts[:nd])
            vals = [float(p) for p in parts[nd:]]
            out[idx] = vals[0] + 1j * vals[1] if np.iscomplexobj(out) \
                else vals[0]
    return out


# ---------------------------------------------------------------------------
# BMP (dependency-free 24-bit encoder + diverging colormap)
# ---------------------------------------------------------------------------


def _bmp_encode(rgb: np.ndarray) -> bytes:
    """uint8 (H, W, 3) RGB -> 24-bit uncompressed BMP bytes."""
    h, w, _ = rgb.shape
    row = w * 3
    pad = (4 - row % 4) % 4
    body = bytearray()
    for y in range(h - 1, -1, -1):  # BMP rows bottom-up, BGR
        body += rgb[y, :, ::-1].tobytes() + b"\x00" * pad
    size = 54 + len(body)
    header = struct.pack("<2sIHHI", b"BM", size, 0, 0, 54)
    info = struct.pack("<IiiHHIIiiII", 40, w, h, 1, 24, 0, len(body),
                       2835, 2835, 0, 0)
    return bytes(header + info + body)


def colormap_diverging(v: np.ndarray) -> np.ndarray:
    """Symmetric blue-white-red map on [-max|v|, +max|v|] -> uint8 RGB."""
    v = np.asarray(v, dtype=np.float64)
    scale = np.max(np.abs(v)) or 1.0
    x = np.clip(v / scale, -1.0, 1.0)
    rgb = np.empty(v.shape + (3,), dtype=np.uint8)
    up = np.clip(1.0 + x, 0.0, 1.0)     # 0 at -1 .. 1 at >=0
    dn = np.clip(1.0 - x, 0.0, 1.0)     # 1 at <=0 .. 0 at +1
    rgb[..., 0] = np.round(255 * np.where(x >= 0, 1.0, up))
    rgb[..., 1] = np.round(255 * np.minimum(up, dn))
    rgb[..., 2] = np.round(255 * np.where(x <= 0, 1.0, dn))
    return rgb


def dump_bmp(arr: np.ndarray, path: str, active_axes=(0, 1)):
    """Central 2D cut of a rank-3 grid -> colormapped BMP.

    The cut plane is spanned by the first two active axes (for 1D modes a
    horizontal strip is emitted). Real part is shown for complex fields.
    """
    arr = np.asarray(arr)
    if np.iscomplexobj(arr):
        arr = arr.real
    axes = list(active_axes)
    if len(axes) == 0:
        axes = [0, 1]
    if len(axes) == 1:
        a = axes[0]
        line = np.moveaxis(arr, a, 0).reshape(arr.shape[a], -1)[:, 0]
        img = np.tile(line[None, :], (24, 1))
    else:
        a, b = axes[0], axes[1]
        rest = [ax for ax in range(arr.ndim) if ax not in (a, b)]
        sl = [slice(None)] * arr.ndim
        for r in rest:
            sl[r] = arr.shape[r] // 2
        cut = arr[tuple(sl)]
        if a > b:  # keep (a, b) order as (rows, cols)
            cut = cut.T
        img = cut.T  # rows = axis b (vertical), cols = axis a
    rgb = colormap_diverging(img)

    def _write(tmp):
        if _native.encode_bmp(tmp, rgb):
            return
        with open(tmp, "wb") as f:
            f.write(_bmp_encode(rgb))

    atomic_publish(path, _write)


def load_bmp_size(path: str) -> Tuple[int, int]:
    """(width, height) of a BMP file (sanity-check helper)."""
    with open(path, "rb") as f:
        head = f.read(26)
    return struct.unpack_from("<ii", head, 18)


def load_bmp(path: str) -> np.ndarray:
    """Decode a 24-bit uncompressed BMP -> uint8 (H, W, 3) RGB.

    The loader half of the reference's BMPLoader (SURVEY.md §2 File I/O
    row). Handles the standard bottom-up row order (positive height) and
    top-down (negative height) variants; anything else (palettized, RLE)
    is out of scope — the reference vendors EasyBMP for those, we only
    need the interchange subset our own dumper and common tools write.
    """
    with open(path, "rb") as f:
        data = f.read()
    if data[:2] != b"BM":
        raise ValueError(f"{path}: not a BMP file")
    (offset,) = struct.unpack_from("<I", data, 10)
    w, h = struct.unpack_from("<ii", data, 18)
    (bpp,) = struct.unpack_from("<H", data, 28)
    (compression,) = struct.unpack_from("<I", data, 30)
    if bpp != 24 or compression != 0:
        raise ValueError(
            f"{path}: only 24-bit uncompressed BMP supported "
            f"(got {bpp}bpp, compression {compression})")
    top_down = h < 0
    h = abs(h)
    row = w * 3
    stride = row + (4 - row % 4) % 4
    # Validate the header against the actual file size BEFORE indexing:
    # a truncated/corrupt file should fail with a clear message, not an
    # opaque frombuffer error (ADVICE r2).
    if w <= 0 or h <= 0:
        raise ValueError(f"{path}: bad BMP dimensions {w}x{h}")
    if offset + (h - 1) * stride + row > len(data):
        raise ValueError(
            f"{path}: truncated BMP ({len(data)} bytes; header claims "
            f"{w}x{h} 24-bit rows ending at byte "
            f"{offset + (h - 1) * stride + row})")
    out = np.empty((h, w, 3), dtype=np.uint8)
    for y in range(h):
        src = offset + y * stride
        line = np.frombuffer(data, np.uint8, row, src).reshape(w, 3)
        out[y if top_down else h - 1 - y] = line[:, ::-1]  # BGR -> RGB
    return out


def load_bmp_gray(path: str) -> np.ndarray:
    """BMP -> float64 (H, W) luminance in [0, 1] (material-init input)."""
    return load_bmp(path).mean(axis=2) / 255.0


# ---------------------------------------------------------------------------
# checkpoints (full solver state pytree)
# ---------------------------------------------------------------------------


def _flatten(prefix: str, tree, out: Dict[str, np.ndarray]):
    if isinstance(tree, dict):
        for k, v in tree.items():
            _flatten(f"{prefix}/{k}" if prefix else k, v, out)
    else:
        arr = np.asarray(tree)
        if arr.dtype.kind == "V":
            # non-native dtypes (bfloat16) hit .npz as raw void bytes and
            # cannot be cast back on load; store widened to f32 instead —
            # bf16 -> f32 is exact, and restore()'s .astype(old.dtype)
            # returns the identical bf16 bits.
            arr = arr.astype(np.float32)
        out[prefix] = arr


def _state_checksum(flat: Dict[str, np.ndarray]) -> int:
    """crc32 over every array's name + raw bytes, in sorted-key order."""
    crc = 0
    for key in sorted(flat):
        crc = zlib.crc32(key.encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(flat[key]).tobytes(), crc)
    return crc


def save_checkpoint(state, path: str, extra: Optional[Dict] = None):
    """Bit-exact .npz snapshot of the whole state pytree.

    Crash-safe: written through :func:`atomic_open` (a crash mid-write
    leaves the previous snapshot intact — an .npz under its final name
    is COMMITTED by construction). The metadata blob carries a payload
    checksum (`_checksum`) and a per-array manifest (`_manifest`) that
    :func:`load_checkpoint` verifies."""
    flat: Dict[str, np.ndarray] = {}
    _flatten("", state, flat)
    meta = dict(extra or {})
    meta["_manifest"] = {k: [list(v.shape), v.dtype.str]
                         for k, v in flat.items()}
    meta["_checksum"] = _state_checksum(flat)
    blob = json.dumps(meta)
    with atomic_open(path, "wb") as f:
        # np.savez on a file OBJECT: no implicit ".npz" suffix games,
        # and the bytes land in the atomic writer's tmp file
        np.savez(f, __meta__=np.frombuffer(
            zlib.compress(blob.encode()), dtype=np.uint8), **flat)


def load_checkpoint(path: str, verify: bool = True) -> Tuple[Dict, Dict]:
    """-> (state pytree of numpy arrays, extra metadata dict).

    Integrity: a truncated/corrupt .npz, a manifest mismatch, or a
    payload-checksum failure raises :class:`CheckpointCorrupt` naming
    the path and the failed check — never a raw numpy/zipfile
    traceback. Checkpoints written before the checksum era (no
    `_checksum`/`_manifest` keys) load without those checks."""
    flat: Dict[str, np.ndarray] = {}
    extra: Dict = {}
    try:
        with np.load(path, allow_pickle=False) as z:
            for key in z.files:
                if key == "__meta__":
                    extra = json.loads(zlib.decompress(z[key].tobytes()))
                    continue
                flat[key] = z[key]
    except (zipfile.BadZipFile, ValueError, OSError, EOFError,
            KeyError, zlib.error, json.JSONDecodeError) as exc:
        raise CheckpointCorrupt(
            f"{path}: unreadable checkpoint (npz/zip structure check "
            f"failed: {type(exc).__name__}: {exc})") from exc
    manifest = extra.pop("_manifest", None)
    checksum = extra.pop("_checksum", None)
    if verify and manifest is not None:
        want = {k: (tuple(s), d) for k, (s, d) in manifest.items()}
        got = {k: (v.shape, v.dtype.str) for k, v in flat.items()}
        if want != got:
            missing = sorted(set(want) - set(got))
            extra_k = sorted(set(got) - set(want))
            changed = sorted(k for k in set(want) & set(got)
                             if want[k] != got[k])
            raise CheckpointCorrupt(
                f"{path}: manifest check failed (missing arrays: "
                f"{missing or 'none'}; unexpected: {extra_k or 'none'}; "
                f"shape/dtype changed: {changed or 'none'})")
    if verify and checksum is not None:
        actual = _state_checksum(flat)
        if actual != checksum:
            raise CheckpointCorrupt(
                f"{path}: payload checksum check failed (stored "
                f"{checksum:#010x}, computed {actual:#010x}) — the "
                f"snapshot was damaged after it was committed")
    state: Dict = {}
    for key, arr in flat.items():
        parts = key.split("/")
        node = state
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return state, extra


def read_checkpoint_meta(path: str) -> Dict:
    """Metadata of a snapshot WITHOUT loading its arrays.

    Works on both backends (an ``.npz`` reads just the ``__meta__``
    member; a directory goes through :func:`read_orbax_meta`). The
    cheap peek resume paths use to decide HOW to resume — supervisor
    state, source topology — before any state bytes move. Integrity of
    the payload is still load_checkpoint's job."""
    if os.path.isdir(path):
        return read_orbax_meta(path)
    try:
        with np.load(path, allow_pickle=False) as z:
            if "__meta__" not in z.files:
                return {}
            extra = json.loads(zlib.decompress(z["__meta__"].tobytes()))
    except (zipfile.BadZipFile, ValueError, OSError, EOFError,
            KeyError, zlib.error, json.JSONDecodeError) as exc:
        raise CheckpointCorrupt(
            f"{path}: unreadable checkpoint metadata "
            f"({type(exc).__name__}: {exc})") from exc
    extra.pop("_manifest", None)
    extra.pop("_checksum", None)
    return extra


# ---------------------------------------------------------------------------
# topology reshard: CPML psi slab layout conversion (reshard-on-resume)
# ---------------------------------------------------------------------------
#
# Every leaf of the state pytree is a GLOBAL array with a topology-
# independent shape — except the CPML psi recursions, whose storage is
# slab-compacted PER SHARD (solver.slab_axes: each shard keeps only the
# 2*(npml+1) boundary planes of its own axis, or the full extent when a
# shard is too thin). A snapshot is therefore topology-portable once
# its psi leaves are converted: expand the source layout to the full
# axis, then compact onto the target layout. Both directions are exact
# data movement; the compact step VALIDATES that every dropped plane is
# zero (physically guaranteed — psi is identically zero outside the
# absorbing slabs — so a non-zero drop means the snapshot and its
# declared layout disagree).

_PSI_GROUPS = ("psi_E", "psi_H", "lopsi_E", "lopsi_H")
_AXES = "xyz"


def psi_slab_expand(arr: np.ndarray, axis: int, n_global: int,
                    topo_a: int, m: Optional[int],
                    key: str = "psi") -> np.ndarray:
    """Stored psi (slab-compact or full) -> full-length global axis.

    ``m`` is the per-side slab plane count of the SOURCE layout
    (solver.slab_axes value), or None for full storage. Shard ``i`` of
    ``topo_a`` holds planes ``[i*2m, i*2m+m)`` (its local lo edge) and
    ``[i*2m+m, (i+1)*2m)`` (its local hi edge)."""
    arr = np.asarray(arr)
    if m is None:
        if arr.shape[axis] != n_global:
            raise ValueError(
                f"reshard: {key} has {arr.shape[axis]} planes along "
                f"axis {_AXES[axis]} but the declared layout is full "
                f"storage of {n_global} — snapshot and layout disagree")
        return arr
    want = 2 * m * topo_a
    if arr.shape[axis] != want:
        raise ValueError(
            f"reshard: {key} has {arr.shape[axis]} planes along axis "
            f"{_AXES[axis]} but the declared slab layout "
            f"(m={m} x {topo_a} shards) stores {want} — snapshot and "
            f"layout disagree")
    shape = list(arr.shape)
    shape[axis] = n_global
    out = np.zeros(shape, dtype=arr.dtype)
    ln = n_global // topo_a

    def _take(a, lo, hi):
        sl = [slice(None)] * a.ndim
        sl[axis] = slice(lo, hi)
        return tuple(sl)

    for i in range(topo_a):
        out[_take(out, i * ln, i * ln + m)] = \
            arr[_take(arr, i * 2 * m, i * 2 * m + m)]
        out[_take(out, (i + 1) * ln - m, (i + 1) * ln)] = \
            arr[_take(arr, i * 2 * m + m, (i + 1) * 2 * m)]
    return out


def psi_slab_compact(full: np.ndarray, axis: int, topo_a: int,
                     m: Optional[int],
                     key: str = "psi") -> np.ndarray:
    """Full-length psi -> the target layout (slab-compact or full).

    VALIDATED: planes outside every target shard's kept slabs must be
    identically zero (they are, for any state a real run produced —
    psi lives only in the global absorbing slabs, which every layout
    keeps). A non-zero drop raises instead of silently losing state."""
    full = np.asarray(full)
    if m is None:
        return full
    n_global = full.shape[axis]
    ln = n_global // topo_a
    shape = list(full.shape)
    shape[axis] = 2 * m * topo_a
    out = np.zeros(shape, dtype=full.dtype)

    def _take(a, lo, hi):
        sl = [slice(None)] * a.ndim
        sl[axis] = slice(lo, hi)
        return tuple(sl)

    kept = np.zeros(n_global, dtype=bool)
    for i in range(topo_a):
        out[_take(out, i * 2 * m, i * 2 * m + m)] = \
            full[_take(full, i * ln, i * ln + m)]
        out[_take(out, i * 2 * m + m, (i + 1) * 2 * m)] = \
            full[_take(full, (i + 1) * ln - m, (i + 1) * ln)]
        kept[i * ln:i * ln + m] = True
        kept[(i + 1) * ln - m:(i + 1) * ln] = True
    dropped = np.where(~kept)[0]
    if dropped.size:
        probe = np.take(full, dropped, axis=axis)
        if np.any(probe != 0):
            raise ValueError(
                f"reshard would drop non-zero psi planes of {key} "
                f"(axis {_AXES[axis]}, planes outside the target slab "
                f"layout m={m} x {topo_a} shards hold non-zero "
                f"recursion state) — the snapshot does not match its "
                f"declared layout; refusing a lossy reshard")
    return out


def reshard_psi_tree(state: Dict, grid_shape: Tuple[int, int, int],
                     src_topology: Tuple[int, int, int],
                     src_slabs: Dict[int, int],
                     dst_topology: Tuple[int, int, int],
                     dst_slabs: Dict[int, int]) -> Dict:
    """Convert every psi leaf of a host-side state tree between
    topologies' slab layouts (everything else passes through).

    ``src_slabs``/``dst_slabs`` map axis index -> per-side plane count
    for axes using slab storage under that topology (solver.slab_axes
    of the respective static setups). Pure numpy; returns a new tree
    sharing the non-psi leaves."""
    for label, topo in (("source", src_topology),
                        ("target", dst_topology)):
        for a in range(3):
            if topo[a] < 1 or grid_shape[a] % topo[a]:
                raise ValueError(
                    f"reshard: {label} topology {tuple(topo)} does not "
                    f"divide grid {tuple(grid_shape)} evenly on axis "
                    f"{_AXES[a]}")
    out = dict(state)
    for group in _PSI_GROUPS:
        if group not in state:
            continue
        newg = {}
        for key, arr in state[group].items():
            ax_letter = key.rsplit("_", 1)[1]
            a = _AXES.index(ax_letter)
            full = psi_slab_expand(arr, a, grid_shape[a],
                                   src_topology[a], src_slabs.get(a),
                                   key=f"{group}/{key}")
            newg[key] = psi_slab_compact(full, a, dst_topology[a],
                                         dst_slabs.get(a),
                                         key=f"{group}/{key}")
        out[group] = newg
    return out


def _import_orbax():
    try:
        import orbax.checkpoint as ocp
        return ocp
    except ImportError as exc:  # pragma: no cover
        raise ImportError(
            "the 'orbax' checkpoint backend needs the orbax-checkpoint "
            "package (pip install orbax-checkpoint), or use the default "
            "npz backend") from exc


# ---------------------------------------------------------------------------
# coordinated commit: two-phase marker protocol for multi-writer snapshots
# ---------------------------------------------------------------------------

# A committed directory-style checkpoint carries this marker, written by
# rank 0 only after EVERY participating writer's per-host marker landed
# (phase 2 of the two-phase protocol below): a preempted/crashed save —
# of any single writer — leaves a directory without it (or with a
# partial marker set), and readers refuse the un-committed snapshot.
ORBAX_COMMIT_MARKER = "COMMIT.fdtd3d"

# Phase 1: each participating process atomically publishes its shards
# plus one of these markers (host id + expected writer count). Phase 2:
# process 0 publishes ORBAX_COMMIT_MARKER only after observing the FULL
# marker set. Discovery (find_checkpoints / commit_status) treats any
# partial set as uncommitted — skipped with a warning, never a crash.
_HOST_MARKER_RE = re.compile(r"^HOST\.(\d+)\.fdtd3d$")


def host_marker_name(host: int) -> str:
    return f"HOST.{int(host):04d}.fdtd3d"


def publish_host_marker(dirpath: str, host: int, num_writers: int):
    """Phase 1 of the coordinated commit, called by EACH writer after
    its own shards are fully written: atomically publish this host's
    marker. The ``host_lost`` / host-scoped ``fail_write`` fault hooks
    fire here, so a lost writer leaves a provably partial set."""
    _faults.on_host_publish(int(host))
    os.makedirs(dirpath, exist_ok=True)
    with atomic_open(os.path.join(dirpath, host_marker_name(host)),
                     "w") as f:
        json.dump({"host": int(host),
                   "num_writers": int(num_writers)}, f)


def commit_status(dirpath: str) -> Dict[str, Any]:
    """Commit-marker completeness of a directory snapshot.

    -> ``{"committed": bool, "markers": [host ids], "num_writers":
    Optional[int], "missing": [host ids], "legacy": bool}``.
    ``legacy`` marks a pre-two-phase directory (COMMIT marker, no host
    markers) — still committed, single-writer era. A COMMIT marker over
    an INCOMPLETE marker set does not count as committed either: the
    partial set is authoritative (a damaged/hand-rolled directory must
    never resurrect as a resume source)."""
    try:
        names = os.listdir(dirpath)
    except OSError:
        return {"committed": False, "markers": [], "num_writers": None,
                "missing": [], "legacy": False}
    markers: List[int] = []
    num_writers: Optional[int] = None
    for name in names:
        m = _HOST_MARKER_RE.match(name)
        if not m:
            continue
        host = int(m.group(1))
        markers.append(host)
        try:
            with open(os.path.join(dirpath, name)) as f:
                nw = int(json.load(f).get("num_writers", 0))
            num_writers = max(num_writers or 0, nw)
        except (OSError, ValueError, json.JSONDecodeError):
            pass  # marker content is advisory; presence is the phase-1 fact
    markers.sort()
    commit = ORBAX_COMMIT_MARKER in names
    if not markers:
        # pre-two-phase directory: COMMIT alone was the whole protocol
        return {"committed": commit, "markers": [], "num_writers": None,
                "missing": [], "legacy": commit}
    authoritative = False
    if commit:
        # the COMMIT marker's recorded writer count is authoritative:
        # a stray marker from an earlier crashed wider attempt must
        # not inflate the expected set of a smaller committed save
        try:
            with open(os.path.join(dirpath, ORBAX_COMMIT_MARKER)) as f:
                num_writers = int(json.load(f)["num_writers"])
            authoritative = True
        except (OSError, ValueError, KeyError, TypeError,
                json.JSONDecodeError):
            pass  # legacy "committed" content: fall through
    if not authoritative and (num_writers is None
                              or num_writers < len(markers)):
        num_writers = max(markers) + 1
    missing = [h for h in range(num_writers) if h not in markers]
    return {"committed": commit and not missing, "markers": markers,
            "num_writers": num_writers, "missing": missing,
            "legacy": False}


def commit_if_complete(dirpath: str, num_writers: int) -> bool:
    """Phase 2, rank 0 only: publish the COMMIT marker iff EVERY
    writer's phase-1 marker is present (a stray marker from an earlier
    crashed attempt neither helps nor hurts). Returns whether it
    committed. Reads only the marker NAMES — one listdir, no per-file
    opens: this is the poll body of save_checkpoint_orbax and must
    stay cheap on the shared filesystems pod checkpoints live on."""
    try:
        names = os.listdir(dirpath)
    except OSError:
        return False
    present = {int(m.group(1)) for m in
               (_HOST_MARKER_RE.match(n) for n in names) if m}
    want = set(range(int(num_writers)))
    if not want <= present:
        return False
    with atomic_open(os.path.join(dirpath, ORBAX_COMMIT_MARKER),
                     "w") as f:
        json.dump({"num_writers": int(num_writers),
                   "hosts": sorted(want)}, f)
    return True


def save_checkpoint_orbax(state, path: str, extra: Optional[Dict] = None,
                          commit_timeout_s: float = 600.0):
    """Sharding-aware checkpoint: every host writes ITS OWN shards.

    The TPU-native alternative to the .npz snapshot for large/multi-host
    runs — no rank-0 gather of the global state (at 1024^3 the npz path
    stages ~30 GB on one host). `path` becomes a directory; metadata
    rides a REQUIRED .meta.json sidecar. Commit is the two-phase marker
    protocol: every process publishes its per-host marker after the
    save finished, and process 0 publishes the COMMIT marker only after
    observing the full set (polling the shared filesystem up to
    ``commit_timeout_s`` — single-process runs observe it immediately,
    so tier-1 never sleeps).
    """
    import jax
    ocp = _import_orbax()
    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ck:
        ck.save(path, state, force=True)
        ck.wait_until_finished()
    n_writers = jax.process_count()
    publish_host_marker(path, jax.process_index(), n_writers)
    if jax.process_index() == 0:
        # atomic publish: a preemption between checkpoint completion and
        # the sidecar write must not strand (or half-write) the metadata
        with atomic_open(path + ".meta.json", "w") as f:
            json.dump(extra or {}, f)
        # COMMIT marker LAST: its presence asserts every writer's
        # shards + markers + the sidecar
        deadline = time.monotonic() + commit_timeout_s
        while not commit_if_complete(path, n_writers):
            if time.monotonic() >= deadline:
                st = commit_status(path)
                raise CheckpointCorrupt(
                    f"{path}: coordinated commit timed out after "
                    f"{commit_timeout_s:.0f}s — hosts {st['missing']} "
                    f"never published their markers (lost writers?); "
                    f"the snapshot stays uncommitted and discovery "
                    f"will skip it")
            time.sleep(0.05)  # pragma: no cover - multi-host only


def read_orbax_meta(path: str) -> Dict:
    """Metadata of a directory checkpoint — validate BEFORE restoring.

    Requires the two-phase commit to have COMPLETED: a missing COMMIT
    marker or a partial per-host marker set raises
    :class:`CheckpointCorrupt` naming the missing writers."""
    path = os.path.abspath(path)
    st = commit_status(path)
    if not st["committed"]:
        if st["markers"] and st["missing"]:
            raise CheckpointCorrupt(
                f"{path}: partial commit-marker set — hosts "
                f"{st['missing']} of {st['num_writers']} never "
                f"published (writer lost mid-commit?); the snapshot "
                f"was never committed; use an older committed one")
        raise CheckpointCorrupt(
            f"{path}: missing {ORBAX_COMMIT_MARKER} marker — the "
            f"checkpoint was never committed (crash or preemption "
            f"mid-save?); use an older committed snapshot")
    meta_path = path + ".meta.json"
    if not os.path.exists(meta_path):
        raise CheckpointCorrupt(
            f"{path}: missing {os.path.basename(meta_path)} sidecar — "
            f"the metadata guards (scheme/size/topology) cannot be "
            f"checked; keep the sidecar next to the checkpoint directory")
    with open(meta_path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as exc:
            raise CheckpointCorrupt(
                f"{path}: corrupt metadata sidecar "
                f"({os.path.basename(meta_path)}): {exc}") from exc


def load_checkpoint_orbax(path: str, target) -> Dict:
    """State pytree restored WITH target's shardings.

    `target` is the live state pytree (or abstract equivalents): shapes,
    dtypes and shardings to restore into — each host reads only its own
    shards. Call read_orbax_meta first and validate.
    """
    import jax
    ocp = _import_orbax()
    path = os.path.abspath(path)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding",
                                                        None)), target)
    with ocp.StandardCheckpointer() as ck:
        return ck.restore(path, abstract)


# ---------------------------------------------------------------------------
# checkpoint discovery + keep-K rotation (resume/rollback both use these)
# ---------------------------------------------------------------------------

# the cadence writer's naming scheme: ckpt_t000123.npz (npz backend) or
# the directory ckpt_t000123 (orbax backend)
_CKPT_NAME_RE = re.compile(r"^ckpt_t(\d+)(\.npz)?$")


def find_checkpoints(save_dir: str) -> List[Tuple[int, str]]:
    """COMMITTED snapshots in ``save_dir`` -> [(t, path)], newest first.

    Committed means: an ``.npz`` under its final name (the atomic
    writer never publishes a partial file), or an orbax directory
    carrying the COMMIT marker. Integrity beyond commit (checksums) is
    verified at load time — resume paths try candidates newest-first
    and fall back past a :class:`CheckpointCorrupt` one."""
    out: List[Tuple[int, str]] = []
    try:
        names = os.listdir(save_dir)
    except OSError:
        return []
    for name in names:
        m = _CKPT_NAME_RE.match(name)
        if not m:
            continue
        path = os.path.join(save_dir, name)
        if os.path.isdir(path):
            st = commit_status(path)
            if not st["committed"]:
                if st["markers"] and st["missing"]:
                    # phase 1 started but never completed: a writer
                    # died mid-commit. Loud skip — a pod operator
                    # should learn a host was lost, not just that an
                    # older snapshot was picked.
                    _log.warn(
                        f"skipping {path}: partial commit-marker set "
                        f"(hosts {st['missing']} of "
                        f"{st['num_writers']} missing) — a writer was "
                        f"lost mid-commit; treating as uncommitted")
                continue  # never committed: crash mid-save
        elif not m.group(2):
            continue  # a FILE without .npz is not one of ours
        out.append((int(m.group(1)), path))
    out.sort(key=lambda kv: (-kv[0], kv[1]))
    return out


def find_latest_checkpoint(save_dir: str) -> Optional[str]:
    """Path of the newest COMMITTED snapshot in save_dir, or None."""
    found = find_checkpoints(save_dir)
    return found[0][1] if found else None


def prune_checkpoints(save_dir: str, keep: int,
                      t_max: Optional[int] = None) -> List[str]:
    """Keep the newest ``keep`` committed snapshots, delete the rest
    (including orbax sidecars). Returns the pruned paths.

    ``t_max`` (the cadence writer passes the current step) restricts
    the rotation to snapshots at t <= t_max: leftovers a previous
    LONGER run left in the same save_dir sort newest and would
    otherwise crowd the live run's own snapshots out of the keep-K
    window — deleting exactly the state a resume needs."""
    import shutil
    pruned: List[str] = []
    if keep <= 0:
        return pruned
    found = find_checkpoints(save_dir)
    if t_max is not None:
        found = [(t, p) for t, p in found if t <= t_max]
    for _t, path in found[keep:]:
        try:
            if os.path.isdir(path):
                shutil.rmtree(path)
                side = path + ".meta.json"
                if os.path.exists(side):
                    os.remove(side)
            else:
                os.remove(path)
            pruned.append(path)
        except OSError:
            pass  # a prune failure must never kill the run
    return pruned


# ---------------------------------------------------------------------------
# periodic output hook (Scheme's dump cadence, SURVEY.md §3.1)
# ---------------------------------------------------------------------------


def write_outputs(sim, step: int):
    """Dump every stored field component in each configured format.

    Multi-process: the gather below is COLLECTIVE (all ranks must call
    it), the file writes happen on rank 0 only. Spans: ``io-gather``
    (device to host) and ``io-write`` (the format writers).
    """
    import jax

    from fdtd3d_tpu import telemetry
    out = sim.cfg.output
    with telemetry.span("io-gather"):
        fields = sim.fields()        # collective allgather
    if jax.process_index() != 0:
        return
    os.makedirs(out.save_dir, exist_ok=True)
    axes = sim.static.mode.active_axes
    with telemetry.span("io-write"):
        for comp, arr in fields.items():
            base = os.path.join(out.save_dir, f"{comp}_t{step:06d}")
            if "dat" in out.formats:
                dump_dat(arr, base + ".dat", step=step)
            if "txt" in out.formats:
                dump_txt(arr, base + ".txt")
            if "bmp" in out.formats:
                dump_bmp(arr, base + ".bmp", axes)


def write_materials(sim):
    """One-time dump of EVERY material grid (reference --save-materials).

    eps at each E component's staggered positions, mu at each H
    component's, uniform sigma_e/sigma_m, and the Drude omega_p/gamma
    grids when dispersion is on — in every configured dump format.
    """
    import jax
    if jax.process_index() != 0:     # host-side only: rank 0 writes
        return
    from fdtd3d_tpu import materials as mats
    out = sim.cfg.output
    os.makedirs(out.save_dir, exist_ok=True)
    mode = sim.static.mode
    mat = sim.cfg.materials
    shape = sim.static.grid_shape

    grids: Dict[str, np.ndarray] = {}
    for comp in mode.e_components:
        grids[f"eps_{comp}"] = mats.scalar_or_grid(
            comp, shape, mode.active_axes, mat.eps, mat.eps_sphere,
            mat.eps_file)
        if mat.use_drude:
            wp, gamma, _ = mats.drude_params(comp, shape,
                                             mode.active_axes, mat)
            grids[f"omega_p_{comp}"] = wp
            grids[f"gamma_{comp}"] = gamma
    for comp in mode.h_components:
        grids[f"mu_{comp}"] = mats.scalar_or_grid(
            comp, shape, mode.active_axes, mat.mu, mat.mu_sphere,
            mat.mu_file)
        if mat.use_drude_m:
            wpm, gm, _ = mats.drude_params(comp, shape, mode.active_axes,
                                           mat, magnetic=True)
            grids[f"omega_pm_{comp}"] = wpm
            grids[f"gamma_m_{comp}"] = gm
    grids["sigma_e"] = mat.sigma_e
    grids["sigma_m"] = mat.sigma_m

    axes = mode.active_axes
    for name, val in grids.items():
        arr = np.broadcast_to(np.asarray(val, dtype=np.float64), shape)
        base = os.path.join(out.save_dir, name)
        if "dat" in out.formats:
            dump_dat(arr, base + ".dat")
        if "txt" in out.formats:
            dump_txt(arr, base + ".txt")
        if "bmp" in out.formats:
            dump_bmp(arr, base + ".bmp", axes)
