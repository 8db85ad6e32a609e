"""The data the comparison sees: seeded initial fields.

Every seed gets the same work: six Gaussian blobs per field component,
of the same six widths, at seeded centres with seeded amplitudes (H
scaled by 1/eta0 so both families carry comparable energy). The
fields are made on the device in one jitted call, float32.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

WIDTHS = (3.0, 4.0, 6.0, 8.0, 12.0, 16.0)     # cells, every seed
ETA0 = 376.730313668
COMPS = ("Ex", "Ey", "Ez", "Hx", "Hy", "Hz")


def blob_params(size, seed):
    """Host-side (centres, widths, amplitudes) per component."""
    rng = np.random.default_rng(seed)
    out = {}
    for c in COMPS:
        k = len(WIDTHS)
        cen = np.stack([rng.uniform(0.0, n, k) for n in size], axis=1)
        wid = rng.permutation(np.array(WIDTHS))
        amp = rng.uniform(-1.0, 1.0, k)
        if c[0] == "H":
            amp = amp / ETA0
        out[c] = (cen.astype(np.float32), wid.astype(np.float32),
                  amp.astype(np.float32))
    return out


def _make(size):
    def gen(params):
        out = {}
        for c in COMPS:
            cen, wid, amp = params[c]
            f = jnp.zeros(size, jnp.float32)
            for i in range(len(WIDTHS)):
                g = None
                for a, n in enumerate(size):
                    x = jnp.arange(n, dtype=jnp.float32)
                    s = [1, 1, 1]
                    s[a] = n
                    ga = jnp.exp(-0.5 * ((x - cen[i, a]) / wid[i]) ** 2)
                    ga = ga.reshape(s)
                    g = ga if g is None else g * ga
                f = f + amp[i] * g
            out[c] = f
        return out
    return jax.jit(gen)


_GEN = {}


def maker(phys, seed):
    """(gen, params): ``gen(params)`` makes the seed's fields. ``gen``
    is one jitted program per grid size, the same for every seed, so a
    caller that places its outputs (``layout.Layout.make``) compiles it
    once."""
    size = tuple(int(v) for v in phys["size"])
    if size not in _GEN:
        _GEN[size] = _make(size)
    params = {c: tuple(jnp.asarray(x) for x in p)
              for c, p in blob_params(size, seed).items()}
    return _GEN[size], params


def initial_fields(phys, seed):
    """{comp: float32 field} of the seed, on the default device."""
    gen, params = maker(phys, seed)
    return gen(params)


def draw(seed, lo, hi, salt):
    """A seeded integer in [lo, hi] for the harness's own choices."""
    rng = np.random.default_rng([seed & 0xffffffff, seed >> 32, salt])
    return int(rng.integers(lo, hi + 1))


assert math.isclose(ETA0, math.sqrt(1.25663706212e-6 / 8.8541878128e-12),
                    rel_tol=1e-9)
