"""Chip-compile tests: the chip path's Pallas kernels and decode step,
compiled at published widths for a TPU v5e that is described, not attached.

Nothing runs, so these say nothing about results or speed; they fail where
the chip's compiler would refuse a program (block tiling, VMEM, memory).
The topology is described inside a fixture, never at import, because only
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.rwkv6.kernel import wkv6_tpu
from repro.models import transformer as tf


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described device is written to the cache but
    cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("arch,seq,window", [
    ("qwen3-0.6b", 2048, None),  # 16/8 heads of 128, causal
    ("gemma3-4b", 2048, 1024),  # 8/4 heads of 256, sliding window
])
def test_flash_attention_compiles(one_chip, arch, seq, window):
    cfg = get_config(arch)
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((1, seq, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, G, hd), jnp.bfloat16, sharding=one_chip)
    compiled = _compile(lambda q, k, v: flash_attention_tpu(q, k, v, window=window), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [512, 1])  # multi-chunk prefill, one-token decode
def test_wkv6_compiles(one_chip, seq):
    cfg = get_config("rwkv6-7b")
    h, p = cfg.d_model // cfg.ssm_head_dim, cfg.ssm_head_dim
    x = jax.ShapeDtypeStruct((1, seq, h, p), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((h, p), jnp.float32, sharding=one_chip)
    state = jax.ShapeDtypeStruct((1, h, p, p), jnp.float32, sharding=one_chip)
    compiled = _compile(wkv6_tpu, x, x, x, x, u, state)
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_compiles(one_chip):
    """Full-width qwen3-0.6b decode at batch 8 over a 2048-token bf16 cache
    fits one v5e chip's 16 GB."""
    cfg = get_config("qwen3-0.6b")
    key = jax.random.PRNGKey(0)
    params = _shapes(jax.eval_shape(lambda k: tf.init_params(cfg, k, jnp.bfloat16), key),
                     one_chip)
    cache = _shapes(jax.eval_shape(lambda: tf.init_cache(cfg, 8, 2048, jnp.bfloat16)),
                    one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)}
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda p, c, b, i: tf.decode_step(cfg, p, c, b, i),
                        params, cache, batch, pos)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16e9, total
