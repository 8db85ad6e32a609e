"""Where the harness's arrays live, and the program's psi in the global
layout.

On one device the harness leaves placement to JAX, as it always did.
On a cell's mesh every whole-grid array it makes, reads back or hands
to the reference is made directly with its sharding, or placed slice by
slice from the host, so that no whole grid ever sits on one device: a
grid that needs four chips fits on none of them. The rule goes by
shape: an axis of a rank-3 array that spans the whole grid is sharded
as the program shards its fields; any other axis (a psi slab's own
axis) is kept whole; arrays of other ranks (the incident line, NTFF
faces, scalars) are replicated. Through the reference's steps the
compiler keeps the fields' shardings and may lay out a psi slab anew.

The program keeps CPML psi per shard: along its own axis, each shard of
that axis holds the lo ++ hi slabs of its own extent (or, where a shard
is too thin for two slabs, psi over its whole extent). The reference
keeps the lo ++ hi slabs of the whole grid. ``global_psi`` converts one
into the other, by slicing and nothing else: the global lo planes are
the first shard's lo planes, the global hi planes the last shard's hi
planes, and every other plane a shard holds faces another shard, where
psi is zero; it raises where one is not.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

AXES = "xyz"


class Layout:
    """Placement on the mesh of ``field_sharding`` (the sharding of the
    program's field arrays), or on one device where it spans one."""

    def __init__(self, field_sharding, grid_shape):
        self.grid = tuple(int(n) for n in grid_shape)
        self.mesh = None
        if isinstance(field_sharding, NamedSharding) and \
                len(field_sharding.device_set) > 1:
            self.mesh = field_sharding.mesh
            spec = tuple(field_sharding.spec)
            self.spec = spec + (None,) * (3 - len(spec))

    def sharding(self, shape):
        if len(shape) != 3:
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P(*[
            s if n == g else None
            for s, n, g in zip(self.spec, shape, self.grid)]))

    def make(self, fn, *args):
        """``fn(*args)``, a jittable maker of arrays, made in place. Give
        what varies from run to run (a seed's numbers) as ``args``, not
        as constants of ``fn``, so that one program serves every run."""
        if self.mesh is None:
            return fn(*args)
        shapes = jax.eval_shape(fn, *args)
        return jax.jit(fn, out_shardings=jax.tree.map(
            lambda s: self.sharding(s.shape), shapes))(*args)

    def put(self, tree):
        """Host arrays to their places; each device receives only its
        slice. On one device the arrays stay where they are."""
        if self.mesh is None:
            return tree
        return jax.tree.map(
            lambda x: jax.device_put(x, self.sharding(x.shape)), tree)


def psi_axis(key):
    """A psi leaf ``<comp>_<axis>`` is slab-stored along <axis>."""
    return AXES.index(key.rsplit("_", 1)[1])


def global_psi(state, widths, topology, layout):
    """``state`` with every leaf of its ``psi_*`` groups in the global
    lo ++ hi layout. ``widths`` maps a psi key to the planes the
    reference keeps along its axis (lo ++ hi, so twice a slab);
    ``topology`` is the program's shards per axis. A leaf ``widths``
    does not name passes unchanged, for the reference to refuse. Raises
    where the program's per-shard psi fits neither of its layouts, or
    psi is not zero on a face between shards. With one shard along an
    axis, the identity."""
    out = dict(state)
    for group, leaves in state.items():
        if group.startswith("psi_"):
            out[group] = {
                key: x if key not in widths else _one_psi(
                    x, psi_axis(key), widths[key], topology, layout,
                    f"{group}/{key}")
                for key, x in leaves.items()}
    return out


def _one_psi(x, axis, width, topology, layout, name):
    planes = x.shape[axis]
    shards = int(topology[axis])
    if shards == 1 and planes == width:
        return x
    n = layout.grid[axis]
    if planes not in (width * shards, n) or width % 2:
        raise ValueError(
            f"psi {name}: {planes} planes along {AXES[axis]} fit neither "
            f"{shards} shards of {width} nor the grid's {n}")
    m = width // 2

    def convert(x):
        lo = lax.slice_in_dim(x, 0, m, axis=axis)
        hi = lax.slice_in_dim(x, planes - m, planes, axis=axis)
        inner = lax.slice_in_dim(x, m, planes - m, axis=axis)
        return jnp.concatenate([lo, hi], axis=axis), \
            jnp.max(jnp.abs(inner.astype(jnp.float32)))

    shape = list(x.shape)
    shape[axis] = width
    sharding = None if layout.mesh is None else layout.sharding(shape)
    out, stray = jax.jit(convert, out_shardings=(sharding, None))(x)
    if float(stray) != 0.0:
        raise ValueError(
            f"psi {name}: a slab that faces another shard holds "
            f"{float(stray)!r}, not zero")
    return out
