"""Pallas TPU kernel: Mamba2 SSD chunked scan.

This is the paper's temporal-blocking schedule transplanted to a 1-D linear
recurrence (DESIGN.md §5): the sequence is processed in chunks of Q
timesteps; a chunk is advanced entirely in VMEM (intra-chunk term = two
MXU matmuls), and only the (N, P) state — the "wavefront" — crosses chunk
boundaries, resident in VMEM for the whole sequence.  HBM traffic is
exactly one read of the inputs and one write of the outputs; the state
never spills.

Grid: one kernel instance per (batch, head); the chunk loop is a static
python loop inside the kernel (nc = S / Q).

Per chunk (head h, state N x P, chunk Q):
    l      = dt * A                      (Q,)   log-decay
    Lc     = cumsum(l)                   (Q,)   inclusive
    D[i,j] = exp(Lc[i] - Lc[j])  (i>=j)  (Q, Q)
    M      = (C B^T) * D * dt[j]         (Q, Q)  -> MXU
    y      = M @ x + exp(Lc) * (C @ h)   (Q, P)  -> MXU
    h      = exp(Lc[Q-1]) h + B^T diag(exp(Lc[Q-1]-Lc) dt) x
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import resolve_interpret


@dataclasses.dataclass(frozen=True)
class SSDSpec:
    seq_len: int
    chunk: int
    nheads: int
    ngroups: int
    headdim: int      # P
    state: int        # N
    dtype: jnp.dtype = jnp.float32

    @property
    def nchunks(self) -> int:
        assert self.seq_len % self.chunk == 0
        return self.seq_len // self.chunk


def _ssd_kernel(spec: SSDSpec, x_ref, dt_ref, b_ref, c_ref, a_ref,
                y_ref, hout_ref, h_scr):
    Q = spec.chunk
    N, P = spec.state, spec.headdim
    h = pl.program_id(1)

    a = a_ref[0]                                   # scalar A (negative)
    h_scr[...] = jnp.zeros((N, P), jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col

    for c in range(spec.nchunks):
        sl = pl.ds(c * Q, Q)
        xq = x_ref[0, sl, 0, :].astype(jnp.float32)      # (Q, P)
        dtq = dt_ref[0, sl, 0].astype(jnp.float32)       # (Q,)
        Bq = b_ref[0, sl, 0, :].astype(jnp.float32)      # (Q, N)
        Cq = c_ref[0, sl, 0, :].astype(jnp.float32)      # (Q, N)

        l = dtq * a
        Lc = jnp.cumsum(l)                               # (Q,)
        LQ = Lc[Q - 1]

        D = jnp.where(causal, jnp.exp(Lc[:, None] - Lc[None, :]), 0.0)
        M = (Cq @ Bq.T) * D * dtq[None, :]               # (Q, Q)
        hprev = h_scr[...]
        y = M @ xq + jnp.exp(Lc)[:, None] * (Cq @ hprev)  # (Q, P)
        y_ref[0, sl, 0, :] = y.astype(spec.dtype)

        sdecay = jnp.exp(LQ - Lc) * dtq                  # (Q,)
        h_scr[...] = jnp.exp(LQ) * hprev + (Bq * sdecay[:, None]).T @ xq

    hout_ref[0, 0, :, :] = h_scr[...].astype(jnp.float32)


def ssd_scan(spec: SSDSpec, x, dtv, Bm, Cm, A, *,
             interpret: Optional[bool] = None):
    """Chunked SSD scan via Pallas.

    x: (B, S, H, P); dtv: (B, S, H) post-softplus; Bm/Cm: (B, S, G, N);
    A: (H,) negative.  Returns (y (B, S, H, P) f32-accurate in spec.dtype,
    h_final (B, H, N, P) f32).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    kernel = functools.partial(_ssd_kernel, spec)

    return pl.pallas_call(
        kernel,
        grid=(Bsz, H),
        in_specs=[
            pl.BlockSpec((1, S, 1, P), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, S, 1), lambda b, h: (b, 0, h)),
            pl.BlockSpec((1, S, 1, N), lambda b, h: (b, 0, h // rep, 0)),
            pl.BlockSpec((1, S, 1, N), lambda b, h: (b, 0, h // rep, 0)),
            pl.BlockSpec((1,), lambda b, h: (h,)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, 1, P), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, H, P), spec.dtype),
            jax.ShapeDtypeStruct((Bsz, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dtv, Bm, Cm, A)


def kernel_cost(spec: SSDSpec, batch: int) -> dict:
    """Per-call analytic cost (roofline feed)."""
    Q, N, P = spec.chunk, spec.state, spec.headdim
    nc = spec.nchunks
    per_chunk = 2 * Q * Q * N + 2 * Q * Q * P + 2 * Q * N * P * 2 + 6 * Q * Q
    flops = batch * spec.nheads * nc * per_chunk
    itemsize = jnp.dtype(spec.dtype).itemsize
    hbm = batch * spec.seq_len * (
        spec.nheads * P * 2 + spec.nheads + 2 * spec.ngroups * N) * itemsize
    return {"flops": float(flops), "hbm_bytes": float(hbm),
            "state_bytes_resident": spec.nheads * N * P * 4}
