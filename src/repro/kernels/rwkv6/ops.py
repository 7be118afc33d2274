"""Dispatch wrapper for the wkv6 recurrence."""
from __future__ import annotations

import jax

from .kernel import wkv6_tpu
from .ref import wkv6_chunked, wkv6_reference


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def wkv6(r, k, v, w, u, state):
    if _use_kernel():
        return wkv6_tpu(r, k, v, w, u, state)
    if r.shape[1] > 1:
        # chunked parallel form: seq/chunk state hops instead of a
        # seq-length sequential scan (exact up to fp reassociation)
        return wkv6_chunked(r, k, v, w, u, state)
    return wkv6_reference(r, k, v, w, u, state)
