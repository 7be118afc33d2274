"""Dispatch wrapper for the INT8 PU GEMM."""
from __future__ import annotations

import jax

from .kernel import gemm_int8_tpu
from .ref import gemm_int8_reference


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def gemm_int8(a, w, bias=None, *, shift: int = 7, relu: bool = False,
              residual=None):
    if _use_kernel():
        import jax.numpy as jnp

        b = bias if bias is not None else jnp.zeros((w.shape[1],), jnp.int32)
        return gemm_int8_tpu(a, w, b, residual, shift=shift, relu=relu)
    return gemm_int8_reference(a, w, bias, shift=shift, relu=relu, residual=residual)
