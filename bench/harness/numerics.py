"""Arithmetic the benchmark keeps as its own yardstick.

Finite-difference weights, the time step, trilinear interpolation, the
Ricker wavelet and the damping sponge are re-derived here rather than
imported from the system under test, so that the plain references and the
earth models stay independent of the code they judge.  Each function says
which textbook rule it follows.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def fd_weights(offsets, deriv: int) -> np.ndarray:
    """Weights of the `deriv`-th derivative on integer `offsets` (units of
    the spacing): the Vandermonde moment system sum_k w_k o_k^i = i! d_ij,
    exact for polynomials up to degree len(offsets) - 1."""
    offsets = np.asarray(offsets, np.float64)
    a = np.vander(offsets, offsets.size, increasing=True).T
    b = np.zeros(offsets.size)
    b[deriv] = math.factorial(deriv)
    return np.linalg.solve(a, b)


def central_weights(order: int, deriv: int) -> np.ndarray:
    """Central weights of half-width order // 2 (order + 1 taps).  An odd
    derivative's are made exactly antisymmetric, so its centre tap is 0
    and drops out of the stencil, as it does in the mathematics."""
    if order % 2 or order < 2:
        raise ValueError(f"space order must be even >= 2, got {order}")
    r = order // 2
    w = fd_weights(range(-r, r + 1), deriv)
    return 0.5 * (w - w[::-1]) if deriv % 2 else w


def cfl_dt(spacing: float, vmax: float, order: int, ndim: int = 3,
           safety: float = 0.9) -> float:
    """Explicit leapfrog time step: dt = safety * 2 / sqrt(ndim * sum|w2|)
    * h / vmax, the von Neumann bound of the order-`order` Laplacian (the
    rule Devito's seismic examples use, with their 0.9 safety factor)."""
    a = float(np.sum(np.abs(central_weights(order, 2))))
    return safety * 2.0 / math.sqrt(ndim * a) * spacing / vmax


def nt_for(time_ms: float, dt: float) -> int:
    """Steps needed to cover `time_ms` of simulated time."""
    return max(int(math.ceil(time_ms / 1000.0 / dt)), 1)


def ricker(nt: int, dt: float, f0: float) -> np.ndarray:
    """Ricker wavelet (1 - 2 a) exp(-a), a = (pi f0 (t - 1/f0))^2, (nt,)."""
    t = np.arange(nt) * dt
    a = (np.pi * f0 * (t - 1.0 / f0)) ** 2
    return (1.0 - 2.0 * a) * np.exp(-a)


def trilinear(coords: np.ndarray, spacing: float, shape):
    """Trilinear stencils of off-grid points (physical coordinates, origin
    at index 0): (indices (n, 8, 3) int32, weights (n, 8) float64), corner
    weights the products of (1 - frac, frac) per axis.  Every point must
    lie inside the grid with its whole 2x2x2 cell."""
    fi = np.asarray(coords, np.float64) / spacing
    lo = np.floor(fi).astype(np.int64)
    frac = fi - lo
    if np.any(lo < 0) or np.any(lo + 1 > np.asarray(shape) - 1):
        raise ValueError("an off-grid point lies outside the grid's cells")
    corners = np.stack(np.meshgrid([0, 1], [0, 1], [0, 1], indexing="ij"),
                       axis=-1).reshape(8, 3)
    idx = lo[:, None, :] + corners[None]
    w = np.ones(idx.shape[:2])
    for d in range(3):
        w = w * np.where(corners[None, :, d] == 1, frac[:, None, d],
                         1.0 - frac[:, None, d])
    return idx.astype(np.int32), w


def damping(shape, nbl: int, spacing: float, coeff: float = 1.5,
            dtype=jnp.float32):
    """Absorbing sponge on the device: zero inside, coeff * d^3 / h at
    normalised depth d into the `nbl` outer layers of every face, the
    largest over the axes (Devito's seismic damping profile)."""
    out = None
    for ax, n in enumerate(shape):
        pos = jnp.arange(n, dtype=dtype)
        lo = jnp.clip((nbl - pos) / nbl, 0.0, 1.0)
        hi = jnp.clip((pos - (n - 1 - nbl)) / nbl, 0.0, 1.0)
        prof = coeff * (lo ** 3 + hi ** 3) / spacing
        bshape = [1, 1, 1]
        bshape[ax] = n
        prof = jnp.broadcast_to(prof.reshape(bshape), shape)
        out = prof if out is None else jnp.maximum(out, prof)
    return out.astype(dtype)


def layer_index(key, nz: int, nlayers: int, jitter: int):
    """(nz,) layer number of each depth: `nlayers` equal layers whose
    interfaces move by up to `jitter` cells, drawn from `key`."""
    import jax
    offs = jax.random.randint(key, (nlayers - 1,), -jitter, jitter + 1)
    iface = jnp.arange(1, nlayers) * nz // nlayers + offs
    z = jnp.arange(nz)
    return jnp.sum(z[:, None] >= iface[None, :], axis=1)
