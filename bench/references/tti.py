"""Pseudo-acoustic TTI (Zhang et al. 2011, as in Devito's TTI examples):
the plain reference and the earth model.  Imports nothing of the system
under test.

    m p_tt + damp p_t = (1 + 2 eps) H0(p) + sqrt(1 + 2 delta) Hz(r) + q
    m r_tt + damp r_t = sqrt(1 + 2 delta) H0(p) + Hz(r) + q

H0 = Gxx + Gyy and Hz = Gzz, where Gaa = Da(Da .) and the rotated first
derivatives are

    Dx = cos(th) cos(ph) dx + cos(th) sin(ph) dy - sin(th) dz
    Dy = -sin(ph) dx + cos(ph) dy
    Dz = sin(th) cos(ph) dx + sin(th) sin(ph) dy + cos(th) dz

with central first differences of the configuration's space order, each
pass zero outside the grid.  Time stepping, injection (into p and r) and
receivers (on p) are as in the acoustic reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import numerics as nm

STATE = ("p", "p_prev", "r", "r_prev")
PARAMS = ("m", "damp", "epsilon", "delta", "theta", "phi")


@functools.partial(jax.jit, static_argnames=("shape", "h", "nbl", "vmin",
                                             "vmax", "nlayers", "jitter",
                                             "thomsen", "coeff"))
def _model(key, shape, h, nbl, vmin, vmax, nlayers, jitter, thomsen,
           coeff):
    layer = nm.layer_index(key, shape[2], nlayers, jitter)
    vp = vmin + (vmax - vmin) * layer.astype(jnp.float32) / (nlayers - 1)
    excess = vp / 1000.0 - 1.5         # km/s above water, as Devito's demo

    def field(col):
        return jnp.broadcast_to(col.astype(jnp.float32), shape)

    out = {"m": field(1.0 / vp ** 2),
           "damp": nm.damping(shape, nbl, h, coeff)}
    for name, slope in thomsen:
        out[name] = field(slope * excess)
    return out


def build_model(cfg: dict, key) -> dict:
    """Layered vp with Thomsen parameters and tilt angles linear in the
    layer's velocity (`assumed.thomsen_per_km_s`), made on the device in
    one jitted call."""
    a = cfg["assumed"]
    thomsen = tuple((k, float(a["thomsen_per_km_s"][k]))
                    for k in ("epsilon", "delta", "theta", "phi"))
    return _model(key, tuple(cfg["shape"]), float(cfg["spacing_m"]),
                  int(cfg["nbl"]), float(cfg["vp_min_m_s"]),
                  float(cfg["vp_max_m_s"]), int(a["layers"]),
                  int(a["interface_jitter_cells"]), thomsen,
                  float(a["damping_coeff"]))


def _d1(u, w1, h, ax):
    r = (len(w1) - 1) // 2
    pad = [(0, 0)] * 3
    pad[ax] = (r, r)
    up = jnp.pad(u, pad)
    out = None
    for k, wk in enumerate(w1):
        if wk == 0.0:
            continue
        sl = [slice(None)] * 3
        sl[ax] = slice(k, k + u.shape[ax])
        term = up[tuple(sl)] * jnp.asarray(wk / h, u.dtype)
        out = term if out is None else out + term
    return out


def _dir(u, cosines, w1, h):
    out = None
    for ax, c in enumerate(cosines):
        if c is None:
            continue
        term = c * _d1(u, w1, h, ax)
        out = term if out is None else out + term
    return out


@functools.partial(jax.jit, static_argnames=("nt", "order", "dt", "h"))
def _propagate(model, src_idx, src_amp, rec_idx, rec_w, *, nt, order, dt, h):
    m, damp = model["m"], model["damp"]
    dtype = m.dtype
    w1 = tuple(float(x) for x in nm.central_weights(order, 1))
    dtc = jnp.asarray(dt, dtype)
    sx, sy, sz = src_idx[:, 0], src_idx[:, 1], src_idx[:, 2]
    scale = dtc * dtc / m[sx, sy, sz]
    rx, ry, rz = rec_idx[:, 0], rec_idx[:, 1], rec_idx[:, 2]

    def step(carry, amp):
        p, p_prev, r, r_prev = carry
        ct, st = jnp.cos(model["theta"]), jnp.sin(model["theta"])
        cp, sp = jnp.cos(model["phi"]), jnp.sin(model["phi"])
        dx_c = (ct * cp, ct * sp, -st)
        dy_c = (-sp, cp, None)
        dz_c = (st * cp, st * sp, ct)

        def g(u, c):
            return _dir(_dir(u, c, w1, h), c, w1, h)

        h0_p = g(p, dx_c) + g(p, dy_c)
        hz_r = g(r, dz_c)
        e_fac = 1.0 + 2.0 * model["epsilon"]
        d_fac = jnp.sqrt(1.0 + 2.0 * model["delta"])
        den = m + damp * dtc
        p_next = (dtc * dtc * (e_fac * h0_p + d_fac * hz_r)
                  + m * (2.0 * p - p_prev) + damp * dtc * p) / den
        r_next = (dtc * dtc * (d_fac * h0_p + hz_r)
                  + m * (2.0 * r - r_prev) + damp * dtc * r) / den
        p_next = p_next.at[sx, sy, sz].add(scale * amp)
        r_next = r_next.at[sx, sy, sz].add(scale * amp)
        smp = (p_next[rx, ry, rz] * rec_w).reshape(-1, 8).sum(axis=1)
        return (p_next, p, r_next, r), smp

    zero = jnp.zeros(m.shape, dtype)
    final, traces = jax.lax.scan(step, (zero, zero, zero, zero), src_amp)
    return final, traces


def reference(nt, model, src_idx, src_w, wavelet, rec_idx, rec_w, dt, h,
              order, dtype=jnp.float32):
    """The plain propagate from rest in `dtype`: (state in STATE order,
    traces (nt, nrec)); arguments as the acoustic reference's."""
    mdl = {k: v.astype(dtype) for k, v in model.items() if k in PARAMS}
    amp = (np.asarray(wavelet, np.float64)[:, :, None]
           * np.asarray(src_w)[None]).reshape(nt, -1)
    return _propagate(mdl, jnp.asarray(src_idx.reshape(-1, 3)),
                      jnp.asarray(amp, dtype),
                      jnp.asarray(rec_idx.reshape(-1, 3)),
                      jnp.asarray(np.asarray(rec_w).reshape(-1), dtype),
                      nt=int(nt), order=int(order), dt=float(dt),
                      h=float(h))
