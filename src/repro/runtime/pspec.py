"""Activation sharding-constraint hook.

Models are sharding-agnostic; the runtime installs a policy (named activation
points -> PartitionSpec) and models call ``constrain(x, name)`` at those
points. Outside a policy context this is a no-op, so models run identically
on a single device, under tests, and in interpret-mode kernels.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_POLICY: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "repro_sharding_policy", default=None
)


@contextlib.contextmanager
def activation_policy(mesh, specs: dict[str, P]):
    """Install named activation PartitionSpecs for the enclosed trace."""
    tok = _POLICY.set({"mesh": mesh, "specs": dict(specs)})
    try:
        yield
    finally:
        _POLICY.reset(tok)


def _axis_size(mesh, ax) -> int:
    n = 1
    for a in ax if isinstance(ax, tuple) else (ax,):
        n *= mesh.shape[a]
    return n


def _fitted_spec(pol: dict, name: str, shape) -> Optional[P]:
    """The policy's spec for ``name`` on an array of ``shape``, with the mesh
    axes that do not divide their dimension dropped (e.g. seq-parallel specs
    against a decode step's length-1 sequence axis); None without a spec."""
    spec = pol["specs"].get(name)
    if spec is None or len(spec) > len(shape):
        return None
    mesh = pol["mesh"]
    return P(*(ax if ax is not None and dim % _axis_size(mesh, ax) == 0 else None
               for dim, ax in zip(shape, spec)))


def constrain(x, name: str):
    pol = _POLICY.get()
    spec = None if pol is None else _fitted_spec(pol, name, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol["mesh"], spec))


def shards(name: str, shape) -> bool:
    """Whether ``constrain(x, name)`` splits an ``x`` of ``shape`` over more
    than one device under the current policy."""
    pol = _POLICY.get()
    spec = None if pol is None else _fitted_spec(pol, name, shape)
    return spec is not None and any(
        ax is not None and _axis_size(pol["mesh"], ax) > 1 for ax in spec)
