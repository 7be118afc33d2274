"""Seeded weights in the program's parameter layout, made on the device in
one jitted call, in the type they are served in.

The layout (which leaves exist, their shapes) is read from the program by
``jax.eval_shape``; the values come from here, so the reference never uses
weights that the program made. Each layer's leaves are drawn from a key
folded with the layer's index.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> how many leading dims (after the layer dims) are fan-in
_FAN_IN_DIMS = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_in": 1, "w_gate": 1,
                "w_out": 1, "lm_head": 1}
NORM_STD = 0.1  # the program scales by (1 + w): norms vary around one


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    seed = int(seed) % (1 << 64)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def seed_key(words: jax.Array) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))


def _is_block(path) -> bool:
    return any(str(getattr(k, "key", "")) == "blocks" for k in path)


def _std(name: str, shape: tuple[int, ...]) -> float:
    if "norm" in name:
        return NORM_STD
    if name == "embed":  # rows of norm about one, as trained embeddings have
        return 1.0 / math.sqrt(shape[-1])
    if name not in _FAN_IN_DIMS:
        raise KeyError(f"no weight rule for leaf {name!r}")
    return 1.0 / math.sqrt(math.prod(shape[:_FAN_IN_DIMS[name]]))


def _draw(key, shape, name, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * _std(name, shape)).astype(dtype)


def abstract_params(cfg, dtype):
    from repro.models import transformer as tf

    return jax.eval_shape(lambda k: tf.init_params(cfg, k, dtype), jax.random.PRNGKey(0))


def _gen_tree(abstract, key, layer_ids, dtype):
    """Top-level leaves whole; block leaves for the given global layer ids,
    one layer at a time."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        name = _leaf_name(path)
        if _is_block(path):
            shape = leaf.shape[1:]
            out.append(jax.lax.map(
                lambda l, k=k, shape=shape, name=name: _draw(
                    jax.random.fold_in(k, l), shape, name, dtype), layer_ids))
        else:
            out.append(_draw(k, leaf.shape, name, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def make(cfg, seed: int, dtype=jnp.bfloat16):
    """All parameters on the default device, layers stacked (L, ...)."""
    abstract = abstract_params(cfg, dtype)
    L = n_layers(abstract)

    @jax.jit
    def gen(words):
        return _gen_tree(abstract, seed_key(words), jnp.arange(L), dtype)

    return gen(seed_words(seed))


def n_layers(abstract) -> int:
    blocks = abstract["blocks"]
    if len(blocks) != 1:
        raise ValueError("the benchmark's weights cover one uniform block stack")
    return jax.tree.leaves(blocks[0])[0].shape[0]

