"""Where the Pallas kernels run: the one place that maps JAX's backend to
interpret mode.

Interpret mode is a CPU debugging aid; on the TPU the kernels always lower
through Mosaic.  Any other backend is an error rather than a silent
fallback, so no path interprets or swaps executors behind a caller's back.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """`interpret` if given, else True on the CPU backend and False on TPU.

    Raises on any other backend (e.g. GPU), where neither the Mosaic
    lowering nor the CPU interpreter is the intended executor."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas executor for backend {backend!r}: the "
                       "stencil kernels run natively on TPU and interpreted "
                       "on CPU only")
