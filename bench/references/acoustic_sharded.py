"""The isotropic acoustic reference of `references/acoustic.py` on a grid
decomposed over a mesh, for models one chip cannot hold (the sharded
driver).  Imports nothing of the system under test.

The earth model and the state are sharded P("data", "model", None): grid
x over the mesh's "data" axis, y over "model", z whole.  Every step each
block takes the r = order/2 planes its x and y neighbours hold next to
it (zeros at the grid's edge, as the plain reference's zero padding),
then runs the plain reference's update with the same terms in the same
order:

    u+ = (dt^2 lap(u) + m (2 u - u-) + damp dt u) / (m + damp dt)

A source corner adds dt^2 / m * w_c * wavelet(t) on the block that holds
it; a receiver's corners are read where they lie and summed over the
blocks.  `build_model` is the plain reference's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from harness import files, numerics as nm

STATE = ("u_prev", "u")       # the order of the state compared
PARAMS = ("m", "damp")
AXES = ("data", "model")
_PLAIN = files.physics("acoustic")
build_model = _PLAIN.build_model


def _halo(u, r, axis, name):
    """`u` with the r planes of each neighbour along mesh axis `name`
    (grid axis `axis`) on its sides, zeros past the grid's edge."""
    n = jax.lax.axis_size(name)
    lo = jax.lax.slice_in_dim(u, u.shape[axis] - r, u.shape[axis], axis=axis)
    hi = jax.lax.slice_in_dim(u, 0, r, axis=axis)
    if n > 1:
        lo = jax.lax.ppermute(lo, name, [(i, i + 1) for i in range(n - 1)])
        hi = jax.lax.ppermute(hi, name, [(i + 1, i) for i in range(n - 1)])
    else:
        lo, hi = jnp.zeros_like(lo), jnp.zeros_like(hi)
    return jnp.concatenate([lo, u, hi], axis=axis)


def _lap(u, w2, h):
    """The plain reference's Laplacian, term for term, on a block."""
    r = (len(w2) - 1) // 2
    n = u.shape
    padded = (_halo(u, r, 0, AXES[0]), _halo(u, r, 1, AXES[1]),
              jnp.pad(u, ((0, 0), (0, 0), (r, r))))
    out = None
    for ax in range(3):
        for k, wk in enumerate(w2):
            sl = [slice(0, n[0]), slice(0, n[1]), slice(0, n[2])]
            sl[ax] = slice(k, k + n[ax])
            term = padded[ax][tuple(sl)] * jnp.asarray(wk / h ** 2, u.dtype)
            out = term if out is None else out + term
    return out


@functools.partial(jax.jit, static_argnames=("nt", "order", "dt", "h",
                                             "mesh"))
def _propagate(model, src_idx, src_amp, rec_idx, rec_w, *, nt, order, dt, h,
               mesh):
    w2 = tuple(float(x) for x in nm.central_weights(order, 2))
    field = P(*AXES, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(field, field, P(), P(), P(), P()),
                       out_specs=(field, field, P()), check_vma=False)
    def run(m, damp, src_idx, src_amp, rec_idx, rec_w):
        dtype = m.dtype
        bx, by, _ = m.shape
        ox = jax.lax.axis_index(AXES[0]) * bx
        oy = jax.lax.axis_index(AXES[1]) * by

        def local(idx):
            """Block-local indices (clamped) and whether the block holds
            the point."""
            lx, ly = idx[:, 0] - ox, idx[:, 1] - oy
            mine = (lx >= 0) & (lx < bx) & (ly >= 0) & (ly < by)
            return (jnp.clip(lx, 0, bx - 1), jnp.clip(ly, 0, by - 1),
                    idx[:, 2], mine)

        dtc = jnp.asarray(dt, dtype)
        den = m + damp * dtc
        sx, sy, sz, s_mine = local(src_idx)
        scale = dtc * dtc / m[sx, sy, sz]
        rx, ry, rz, r_mine = local(rec_idx)
        zero = jnp.zeros((), dtype)

        def step(carry, amp):
            u_prev, u = carry
            nxt = (dtc * dtc * _lap(u, w2, h) + m * (2.0 * u - u_prev)
                   + damp * dtc * u) / den
            nxt = nxt.at[sx, sy, sz].add(jnp.where(s_mine, scale * amp,
                                                   zero))
            part = jnp.where(r_mine, nxt[rx, ry, rz] * rec_w, zero)
            smp = jax.lax.psum(part.reshape(-1, 8).sum(axis=1), AXES)
            return (u, nxt), smp

        zeros = jnp.zeros(m.shape, dtype)
        (u_prev, u), traces = jax.lax.scan(step, (zeros, zeros), src_amp)
        return u_prev, u, traces

    u_prev, u, traces = run(model["m"], model["damp"], src_idx, src_amp,
                            rec_idx, rec_w)
    return (u_prev, u), traces


def reference(nt, model, src_idx, src_w, wavelet, rec_idx, rec_w, dt, h,
              order, dtype=jnp.float32):
    """The plain propagate from rest in `dtype` on the model's mesh:
    (state in STATE order, traces (nt, nrec)), as `references/acoustic.py`
    gives them."""
    mdl = {k: v.astype(dtype) for k, v in model.items() if k in PARAMS}
    amp = (np.asarray(wavelet, np.float64)[:, :, None]
           * np.asarray(src_w)[None]).reshape(nt, -1)
    return _propagate(mdl, jnp.asarray(src_idx.reshape(-1, 3)),
                      jnp.asarray(amp, dtype),
                      jnp.asarray(rec_idx.reshape(-1, 3)),
                      jnp.asarray(np.asarray(rec_w).reshape(-1), dtype),
                      nt=int(nt), order=int(order), dt=float(dt),
                      h=float(h), mesh=model["m"].sharding.mesh)
