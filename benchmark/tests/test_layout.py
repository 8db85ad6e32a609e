"""The harness on a mesh, in process on four CPU devices (conftest.py):
the reference partitioned by the compiler, the program's psi in the
global layout, the state handed over whole, and the seeded fields made
in place."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import checks  # noqa: E402
import layout  # noqa: E402
import run  # noqa: E402
import seeding  # noqa: E402
from reference import yee  # noqa: E402

TOPOS = ((1, 2, 2), (2, 2, 1))
N = 40


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _phys(name):
    return run.cell_physics({"config": _config(name)}, rehearse=True)


def _field_sharding(topo):
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(topo),
                ("x", "y", "z"))
    return NamedSharding(mesh, P(*[layout.AXES[a] if topo[a] > 1 else None
                                   for a in range(3)]))


def _window(topo, seed=3000000051):
    """A Window of vacuum256 at its rehearsal size (40^3) with the
    long traffic's cadence, on one device or on ``topo``."""
    cfg = _config("vacuum256")
    args = run.cell_args({"config": cfg, "traffic": {"args": [
        "--norms-every", "200"]}}, rehearse=True)
    if topo is not None:
        args += ["--topology", "manual", "--manual-topology",
                 "x".join(str(p) for p in topo)]
    return run.Window({"config": cfg}, args, seed, rehearse=True)


# -- the reference, partitioned ---------------------------------------------

@pytest.mark.parametrize("topo", TOPOS)
def test_sharded_reference_agrees_with_one_device(topo):
    """The sphere configuration at 40^3, 20 steps from seeded fields.
    The partitioned program fuses and rounds differently from the
    one-device one, so the two agree to a few float32 ulps of each
    family's largest value (2e-7 to 4e-7 read here), not bit for bit:
    1e-6 of it, a thousandth of the cells' 3e-4 limits."""
    phys = _phys("mie512")
    ref = yee.Reference(phys)

    def advance(lay):
        f0 = lay.make(*seeding.maker(phys, 7))
        E = {c: f0[c] for c in yee.E_COMPS}
        H = {c: f0[c] for c in yee.H_COMPS}
        return ref.advance(E, H, lay.make(ref.zero_aux), 0, 20)

    fs = _field_sharding(topo)
    one = advance(layout.Layout(None, phys["size"]))
    sharded = advance(layout.Layout(fs, phys["size"]))
    for fam, i in (("E", 0), ("H", 1)):
        scale = max(float(jnp.max(jnp.abs(one[i][c]))) for c in one[i])
        gap = max(float(np.max(np.abs(np.asarray(one[i][c])
                                      - np.asarray(sharded[i][c]))))
                  for c in one[i])
        assert gap <= 1e-6 * scale, (fam, gap, scale)
        for c in sharded[i]:
            assert sharded[i][c].sharding.is_equivalent_to(fs, 3)


# -- psi in the global layout -------------------------------------------------

def _per_shard(glob, axis, shards):
    """The program's layout of a global lo ++ hi psi (test-local): each
    shard along ``axis`` holds lo ++ hi of its own extent, only the first
    shard's lo and the last shard's hi nonzero."""
    m = glob.shape[axis] // 2
    lo, hi = np.split(glob, 2, axis=axis)
    zero = np.zeros_like(lo)
    parts = []
    for i in range(shards):
        parts += [lo if i == 0 else zero, hi if i == shards - 1 else zero]
    out = np.concatenate(parts, axis=axis)
    assert out.shape[axis] == 2 * m * shards
    return out


def _psi_case(topo, axis):
    rng = np.random.default_rng(5)
    shape = [N, N, N]
    shape[axis] = 18
    glob = rng.standard_normal(shape).astype(np.float32)
    prog = _per_shard(glob, axis, topo[axis])
    fs = _field_sharding(topo)
    return glob, jax.device_put(prog, fs), layout.Layout(fs, (N, N, N))


@pytest.mark.parametrize("topo", TOPOS)
def test_global_psi_round_trips(topo):
    for axis in range(3):
        glob, prog, lay = _psi_case(topo, axis)
        key = f"E{'yzx'[axis]}_{layout.AXES[axis]}"
        state = {"psi_E": {key: prog}, "E": "passes through"}
        out = layout.global_psi(state, {key: 18}, topo, lay)
        assert out["E"] == "passes through"
        got = out["psi_E"][key]
        assert np.array_equal(np.asarray(got), glob)
        if topo[axis] == 1:
            assert got is prog           # one shard: the identity
        assert len(got.sharding.device_set) == 4


@pytest.mark.parametrize("topo", TOPOS)
def test_global_psi_refuses_a_nonzero_face_between_shards(topo):
    axis = 1                             # sharded in both topologies
    glob, prog, lay = _psi_case(topo, axis)
    bad = np.asarray(prog).copy()
    bad[:, 9, :] = 1.0                   # shard 0's hi slab
    with pytest.raises(ValueError, match="faces another shard"):
        layout.global_psi({"psi_E": {"Ex_y": jax.device_put(
            bad, prog.sharding)}}, {"Ex_y": 18}, topo, lay)


@pytest.mark.parametrize("topo", TOPOS)
def test_program_state_reaches_the_reference_in_the_global_layout(topo):
    """20 steps of the program, sharded and on one device, from the same
    seeded fields: the sharded state in the global layout is the
    one-device state, psi included, to float32 rounding."""
    one, sharded = _window(None), _window(topo)
    assert sharded.sim.topology == topo
    for w in (one, sharded):
        w.sim.advance(20)
    ref = yee.Reference(sharded.phys)
    check = checks.make({"kind": "segment"}, 0, yee)
    want = ref.from_program(one.sim.state)
    got = check.from_program(ref, sharded, sharded.sim.state)
    assert got[3] == want[3] == 20
    assert set(got[2]["psi"]) == set(want[2]["psi"])
    for g, w in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want[:3])):
        assert g.shape == w.shape
        scale = max(float(jnp.max(jnp.abs(w))), 1e-30)
        assert float(jnp.max(jnp.abs(g - np.asarray(w)))) <= 1e-5 * scale


def test_a_family_the_reference_does_not_model_raises():
    w = _window(None)
    ref = yee.Reference(w.phys)
    st = dict(w.sim.state)
    ref.from_program(st)
    st["J"] = {c: jnp.zeros_like(st["E"][c]) for c in yee.E_COMPS}
    with pytest.raises(ValueError, match="J"):
        ref.from_program(st)
    with pytest.raises(ValueError, match="J"):
        checks.make({"kind": "segment"}, 0, yee).from_program(ref, w, st)


def test_a_psi_leaf_the_reference_does_not_keep_raises():
    """The layout conversion passes it on; the reference's shape check
    refuses it."""
    w = _window(None)
    ref = yee.Reference(w.phys)
    st = dict(w.sim.state)
    psi = dict(st["psi_E"])
    psi["Ex_q"] = next(iter(psi.values()))
    st["psi_E"] = psi
    with pytest.raises(ValueError, match="psi"):
        checks.make({"kind": "segment"}, 0, yee).from_program(ref, w, st)


# -- the seeded fields ---------------------------------------------------------

@pytest.mark.parametrize("topo", TOPOS)
def test_seeded_fields_on_a_sharded_simulation(topo):
    """Every leaf of the seeded state carries the sharding the program
    gave it, over all four devices, and the fields equal the one-device
    seeded fields bit for bit."""
    from fdtd3d_tpu import cli
    from fdtd3d_tpu.sim import Simulation
    w = _window(topo, seed=3000000052)
    fresh = Simulation(cli.args_to_config(w.args)).state
    got = w.sim.state
    for g, f in zip(jax.tree.leaves(got), jax.tree.leaves(fresh)):
        assert g.sharding.is_equivalent_to(f.sharding, g.ndim)
        assert len(g.sharding.device_set) == 4
    one = seeding.initial_fields(w.phys, 3000000052)
    for c in seeding.COMPS:
        assert np.array_equal(np.asarray(got[c[0]][c]), np.asarray(one[c]))
