"""Dispatch wrapper for the SSD scan."""
from __future__ import annotations

import jax

from .kernel import ssd_scan_tpu
from .ref import ssd_reference


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def ssd_scan(xh, dt, A, B, C):
    """Returns y only (state handling is the model's concern in the jnp path)."""
    if _use_kernel():
        y, _ = ssd_scan_tpu(xh, dt, A, B, C)
        return y
    return ssd_reference(xh, dt, A, B, C)
