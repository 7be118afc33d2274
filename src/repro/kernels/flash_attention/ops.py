"""Dispatch wrapper: Pallas kernel on TPU, jnp reference elsewhere.

Tests exercise the kernel explicitly via interpret=True.
"""
from __future__ import annotations

from typing import Optional

import jax

from .kernel import flash_attention_tpu
from .ref import banded_attention, chunked_attention, mha_reference

# above this many kv positions, the XLA fallback uses the chunked
# online-softmax path (O(S*block) memory) instead of the dense oracle
CHUNKED_THRESHOLD = 2048


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None):
    if _use_kernel():
        return flash_attention_tpu(q, k, v, causal=causal, window=window, scale=scale)
    if (
        causal
        and window is not None
        and q.shape[1] == k.shape[1]
        and k.shape[1] >= 2 * window
    ):
        return banded_attention(q, k, v, window=window, scale=scale)
    if k.shape[1] > CHUNKED_THRESHOLD:
        return chunked_attention(q, k, v, causal=causal, window=window, scale=scale)
    return mha_reference(q, k, v, causal=causal, window=window, scale=scale)
