"""What decides ``correct``: the timed path's own output against the
plain reference that the configuration names (its ``reference`` key, a
module under benchmark/ with the contract set out in
benchmark/reference/yee.py), at the timed size.

Nothing a check needs runs inside the window: each check reads what it
compares once the window's clock has stopped (``after_window``),
through the same ``Simulation`` and interval loop the window drove. A
traffic file picks one of three checks by ``check.kind``:

* ``segment``: the state the window left and the state one interval
  later, read through the program's public ``Simulation.state``; the
  reference advances the first to the second's step and the E and H
  families are compared.
* ``dump``: the next field dump the loop writes after the window
  (``.dat`` files, read back here with numpy): it must equal the state
  the program held when it wrote it, and match the reference advanced
  from the state the window left.
* ``trajectory``: the whole run from the seeded initial fields (set-up's
  warm-up steps included) to the window's end: final E and H, and the
  NTFF running-DFT accumulators sampled on the way.

A gap is max |program - reference| over a family's components, over
the largest |reference| of that family: one number for E, one for H.

The program's whole state goes to the reference, in the global layout
(``layout.global_psi``); the reference refuses a family it does not
model. On a cell's mesh every whole-grid array of the check lives
sharded as the program's fields do (``layout.Layout``).
"""

import gc
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

import layout
import seeding


def make(check_spec, seed, reference):
    """The traffic's check, comparing against ``reference`` (the loaded
    module the configuration names)."""
    kind = check_spec["kind"]
    cls = {"segment": Segment, "dump": Dump, "trajectory": Trajectory}
    if kind not in cls:
        raise ValueError(f"unknown check kind {kind!r}")
    return cls[kind](check_spec, seed, reference)


class Check:
    def __init__(self, spec, seed, reference):
        self.spec = spec
        self.seed = seed
        self.ref_mod = reference

    def from_program(self, ref, w, state):
        """The reference's inputs from a program state dict: its psi
        turned into the global layout, then handed over whole; the
        reference checks what it receives."""
        widths = {k: v.shape[layout.psi_axis(k)] for k, v in
                  jax.eval_shape(ref.zero_aux)["psi"].items()}
        return ref.from_program(layout.global_psi(
            state, widths, w.sim.topology, w.layout))

    def after_window(self, w):
        """Read what the comparison needs, once the window's clock has
        stopped and the device's peak memory has been read."""


@jax.jit
def _max_abs_diff(a, b):
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))


@jax.jit
def _max_abs(a):
    return jnp.max(jnp.abs(a.astype(jnp.float32)))


def family_gaps(prog, ref, prefix, ref_mod):
    """{prefix_E: gap, prefix_H: gap} of two {comp: array} dicts, over
    the E and H components of the reference module ``ref_mod``; sharded
    arrays reduce in place."""
    out = {}
    for fam, comps in (("E", ref_mod.E_COMPS), ("H", ref_mod.H_COMPS)):
        scale = max(float(_max_abs(ref[c])) for c in comps)
        diff = max(float(_max_abs_diff(prog[c], ref[c])) for c in comps)
        out[f"{prefix}_{fam}"] = diff / scale if scale > 0 else math.inf
    return out


def _release(w):
    """Free the program's device state before the reference runs. The
    simulation keeps its shapes and shardings, so calibrate.py can
    reseed it."""
    w.sim.state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), w.sim.state)
    gc.collect()


def _copy_state(st):
    """A device copy of a program state dict, to hold beyond the next
    advance: on the TPU the program donates its carry, and leaves of
    the unpacked dict (the incident line) may alias it."""
    return jax.tree.map(lambda x: jnp.array(x, copy=True), st)


class Segment(Check):
    """Compare the interval after the window: the state the window left
    against the state one interval later."""

    def warm_up(self, w):
        w.advance_interval()
        # the unpack and the copies the snapshots use
        jax.block_until_ready(_copy_state(w.sim.state))

    def after_window(self, w):
        self.a = _copy_state(w.sim.state)
        w.advance_interval()
        self.b = _copy_state(w.sim.state)

    def compare(self, w):
        _release(w)
        ref = self.ref_mod.Reference(w.phys)
        E, H, aux, t0 = self.from_program(ref, w, self.a)
        del self.a
        prog = {**self.b["E"], **self.b["H"]}
        E, H, aux, _ = ref.advance(E, H, aux, t0, int(self.b["t"]) - t0)
        return family_gaps(prog, {**E, **H}, "field_gap", self.ref_mod)


def read_dat(path):
    with open(path + ".manifest.json") as f:
        man = json.load(f)
    dt = np.dtype(man["dtype"])
    raw = np.fromfile(path, dtype=dt)
    if dt.kind == "V" and dt.itemsize == 2:    # bfloat16 storage
        import ml_dtypes
        raw = raw.view(ml_dtypes.bfloat16)
    return raw.reshape(man["shape"]).astype(np.float32)


class Dump(Check):
    """Compare the first dump the loop writes after the window."""

    def warm_up(self, w):
        from fdtd3d_tpu import _native, diag
        _native.load()                        # builds the native writer
        # the window's chunk and norms, without writing a dump: a dump
        # is 403 MB, and a run writes ~12 of them already
        w.sim.advance(w.interval)
        diag.field_norms(w.sim)
        # the unpack and the copies the snapshots use, the dump's gather
        jax.block_until_ready(_copy_state(w.sim.state))
        w.sim.fields()

    def after_window(self, w):
        self.pre = _copy_state(w.sim.state)
        t = int(self.pre["t"])
        every = w.cfg.output.save_res
        self.step = (t // every + 1) * every
        self.snap = None
        w.keep_dumps = {self.step}
        w.dump_hook = self._hook
        for _ in range((self.step - t) // w.interval):
            w.advance_interval()
        if self.snap is None:
            raise RuntimeError(f"the loop wrote no dump at step {self.step}")

    def _hook(self, w, step):
        if step == self.step:
            # the dict the program unpacked to write this dump
            self.snap = _copy_state(w.sim.state)

    def compare(self, w):
        comps = self.ref_mod.E_COMPS + self.ref_mod.H_COMPS
        dump = {c: read_dat(os.path.join(w.cfg.output.save_dir,
                                         f"{c}_t{self.step:06d}.dat"))
                for c in comps}
        held = {**self.snap["E"], **self.snap["H"]}
        out = {"dump_readback_gap": max(
            float(np.max(np.abs(dump[c] - np.asarray(held[c], np.float32))))
            for c in dump)}
        del self.snap, held
        _release(w)
        ref = self.ref_mod.Reference(w.phys)
        E, H, aux, t0 = self.from_program(ref, w, self.pre)
        del self.pre
        E, H, aux, _ = ref.advance(E, H, aux, t0, self.step - t0)
        out.update(family_gaps(w.layout.put(dump), {**E, **H}, "dump_gap",
                               self.ref_mod))
        return out


class Trajectory(Check):
    """Compare the whole run from the seeded initial fields."""

    def warm_up(self, w):
        from fdtd3d_tpu import diag
        # up to the first NTFF sample: compiles the chunk, the unpack
        # and the sample update; then the norms pass once
        while w.ntff is not None and w.ntff.n_samples == 0:
            w.advance_interval()
        if w.ntff is None:
            w.advance_interval()
        diag.field_norms(w.sim)

    def compare(self, w):
        final = w.sim.state
        t_end = int(final["t"])
        acc_prog = dict(w.ntff.acc) if w.ntff is not None else None
        ntff_cfg = self.spec.get("ntff")
        # the final fields go to the host, the device state is freed
        prog = {c: np.asarray(v) for g in ("E", "H")
                for c, v in final[g].items()}
        del final
        _release(w)
        ref = self.ref_mod.Reference(w.phys)
        f0 = w.layout.make(*seeding.maker(w.phys, self.seed))
        E = {c: f0[c] for c in self.ref_mod.E_COMPS}
        H = {c: f0[c] for c in self.ref_mod.H_COMPS}
        del f0
        ntff = None
        if acc_prog is not None:
            box = ref.ntff_box(int(ntff_cfg["margin"]))
            period_steps = 2.0 * math.pi / (ref.omega * ref.dt)
            every = max(1, round(period_steps / ntff_cfg["samples_per_period"]))
            ntff = {"box": box, "every": every,
                    "start": int(ntff_cfg["start"]), "omega": ref.omega,
                    "acc": w.layout.make(lambda: ref.zero_ntff_acc(box))}
        E, H, aux, acc = ref.advance(E, H, w.layout.make(ref.zero_aux), 0,
                                     t_end, ntff)
        out = family_gaps(w.layout.put(prog), {**E, **H}, "field_gap",
                          self.ref_mod)
        if acc_prog is not None:
            for fam in ("E", "H"):
                keys = [k for k in acc if k[2][0] == fam]
                scale = diff = 0.0
                for k in keys:
                    r = np.asarray(acc[k][0], np.float64) \
                        + 1j * np.asarray(acc[k][1], np.float64)
                    scale = max(scale, float(np.max(np.abs(r))))
                    if k not in acc_prog:     # a face never sampled
                        diff = math.inf
                        continue
                    diff = max(diff, float(np.max(np.abs(acc_prog[k] - r))))
                out[f"ntff_gap_{fam}"] = diff / scale if scale > 0 \
                    else math.inf
        return out
