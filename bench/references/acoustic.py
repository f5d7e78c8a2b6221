"""Isotropic acoustic wave equation: the plain reference and the earth
model.  Imports nothing of the system under test.

    m u_tt + damp u_t = lap(u) + q

discretised as the paper's Listing 1 (second order in time, central
differences of the configuration's space order, zero outside the grid):

    u+ = (dt^2 lap(u) + m (2 u - u-) + damp dt u) / (m + damp dt)

then each source adds dt^2 / m * w_c * wavelet(t) at the eight corners c of
its trilinear cell, and each receiver reads sum_c w_c u+ at its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import numerics as nm

STATE = ("u_prev", "u")       # the order of the state compared
PARAMS = ("m", "damp")


@functools.partial(jax.jit, static_argnames=("shape", "h", "nbl", "vmin",
                                             "vmax", "nlayers", "jitter",
                                             "coeff"))
def _model(key, shape, h, nbl, vmin, vmax, nlayers, jitter, coeff):
    layer = nm.layer_index(key, shape[2], nlayers, jitter)
    vp = vmin + (vmax - vmin) * layer.astype(jnp.float32) / (nlayers - 1)
    m = jnp.broadcast_to((1.0 / vp ** 2).astype(jnp.float32), shape)
    return {"m": m, "damp": nm.damping(shape, nbl, h, coeff)}


def build_model(cfg: dict, key) -> dict:
    """Layered vp from `vp_min_m_s` to `vp_max_m_s`, made on the device in
    one jitted call."""
    a = cfg["assumed"]
    return _model(key, tuple(cfg["shape"]), float(cfg["spacing_m"]),
                  int(cfg["nbl"]), float(cfg["vp_min_m_s"]),
                  float(cfg["vp_max_m_s"]), int(a["layers"]),
                  int(a["interface_jitter_cells"]),
                  float(a["damping_coeff"]))


def _lap(u, w2, h):
    r = (len(w2) - 1) // 2
    up = jnp.pad(u, r)
    n = u.shape
    out = None
    for ax in range(3):
        for k, wk in enumerate(w2):
            sl = [slice(r, r + n[0]), slice(r, r + n[1]), slice(r, r + n[2])]
            sl[ax] = slice(k, k + n[ax])
            term = up[tuple(sl)] * jnp.asarray(wk / h ** 2, u.dtype)
            out = term if out is None else out + term
    return out


@functools.partial(jax.jit, static_argnames=("nt", "order", "dt", "h"))
def _propagate(model, src_idx, src_amp, rec_idx, rec_w, *, nt, order, dt, h):
    m, damp = model["m"], model["damp"]
    dtype = m.dtype
    w2 = tuple(float(x) for x in nm.central_weights(order, 2))
    dtc = jnp.asarray(dt, dtype)
    den = m + damp * dtc
    sx, sy, sz = src_idx[:, 0], src_idx[:, 1], src_idx[:, 2]
    scale = dtc * dtc / m[sx, sy, sz]
    rx, ry, rz = rec_idx[:, 0], rec_idx[:, 1], rec_idx[:, 2]

    def step(carry, amp):
        u_prev, u = carry
        nxt = (dtc * dtc * _lap(u, w2, h) + m * (2.0 * u - u_prev)
               + damp * dtc * u) / den
        nxt = nxt.at[sx, sy, sz].add(scale * amp)
        smp = (nxt[rx, ry, rz] * rec_w).reshape(-1, 8).sum(axis=1)
        return (u, nxt), smp

    zero = jnp.zeros(m.shape, dtype)
    (u_prev, u), traces = jax.lax.scan(step, (zero, zero), src_amp)
    return (u_prev, u), traces


def reference(nt, model, src_idx, src_w, wavelet, rec_idx, rec_w, dt, h,
              order, dtype=jnp.float32):
    """The plain propagate from rest in `dtype`: (state in STATE order,
    traces (nt, nrec)).  `src_idx` (ns, 8, 3) / `src_w` (ns, 8) and
    `rec_idx` / `rec_w` are trilinear stencils, `wavelet` (nt, ns)."""
    mdl = {k: v.astype(dtype) for k, v in model.items() if k in PARAMS}
    amp = (np.asarray(wavelet, np.float64)[:, :, None]
           * np.asarray(src_w)[None]).reshape(nt, -1)
    return _propagate(mdl, jnp.asarray(src_idx.reshape(-1, 3)),
                      jnp.asarray(amp, dtype),
                      jnp.asarray(rec_idx.reshape(-1, 3)),
                      jnp.asarray(np.asarray(rec_w).reshape(-1), dtype),
                      nt=int(nt), order=int(order), dt=float(dt),
                      h=float(h))
