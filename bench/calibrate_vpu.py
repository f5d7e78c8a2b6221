"""Measure the chip's float32 vector-unit ceiling, for `peaks.json`.

A Pallas kernel keeps `chains` independent float32 multiply-add chains,
each one (8, 128) vreg, resident in VMEM and iterates x <- x * a + b on
them; nothing leaves the core until the last iteration.  Each iteration of
a chain is counted as 2 flops per element (a multiply and an add, as the
stencils' flop counts count them).  The script sweeps the number of chains
and the loop unroll, takes the best time of several calls of each variant
by the host clock (each call runs a few hundred milliseconds, well above
the clock's error), and prints one JSON line: every variant's rate and the
best, which is the ceiling recorded in `peaks.json`.

    python bench/calibrate_vpu.py

There is no CPU fallback: without a TPU it exits 1.
"""
from __future__ import annotations

import functools
import json
import sys
import time

GRID = 256          # grid steps per call; each runs all chains
REPS = 5


def _kernel(chains, iters, unroll, x_ref, o_ref):
    import jax
    import jax.numpy as jnp

    a = jnp.float32(0.999)
    b = jnp.float32(1e-3)

    def body(_, xs):
        for _ in range(unroll):      # Mosaic's loops take no unroll
            xs = tuple(x * a + b for x in xs)
        return xs

    xs = tuple(x_ref[c] for c in range(chains))
    xs = jax.lax.fori_loop(0, iters // unroll, body, xs)
    for c in range(chains):
        o_ref[c] = xs[c]


def _call(chains, iters, unroll):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(_kernel, chains, iters, unroll)
    spec = pl.BlockSpec((None, chains, 8, 128), lambda i: (i, 0, 0, 0))
    fn = pl.pallas_call(
        kern, grid=(GRID,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((GRID, chains, 8, 128), jnp.float32))
    return jax.jit(fn)


def measure(chains, iters, unroll):
    import jax
    import jax.numpy as jnp

    fn = _call(chains, iters, unroll)
    x = jnp.ones((GRID, chains, 8, 128), jnp.float32)
    out = jax.block_until_ready(fn(x))        # compile and warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    if not bool(jnp.all(jnp.isfinite(out))):
        raise RuntimeError("the chains left the float32 range")
    flops = 2.0 * GRID * chains * 8 * 128 * iters
    return flops / best, best


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    variants = []
    for chains in (4, 8, 16, 32):
        for unroll in (1, 4, 8):
            iters = (1 << 21) // chains
            rate, sec = measure(chains, iters, unroll)
            variants.append({"chains": chains, "unroll": unroll,
                             "iters": iters, "seconds": sec,
                             "flops_per_s": rate})
            print(f"chains {chains} unroll {unroll}: {rate!r} flop/s "
                  f"({sec!r} s)", file=sys.stderr)
    best = max(variants, key=lambda v: v["flops_per_s"])
    print(json.dumps({"device_kind": dev.device_kind,
                      "f32_vpu_flops_per_s": best["flops_per_s"],
                      "best": best, "variants": variants}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
