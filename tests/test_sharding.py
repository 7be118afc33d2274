"""Sharding policy tests + small-mesh lower/compile smoke (subprocess with
forced host devices — the full 512-device dry-run is exercised by
repro.launch.dryrun; these tests keep the policy honest at test speed)."""
import os
import subprocess
import sys

import pytest
import jax

from repro.configs import all_configs, get_config


class TestPolicyRules:
    def _policy(self, arch, multi_pod=False):
        # policy construction only needs mesh *shape* metadata; build a
        # device-free mesh
        from jax.sharding import AbstractMesh

        from repro.runtime.sharding import make_policy

        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = AbstractMesh(shape, axes)
        return make_policy(get_config(arch), mesh)

    def test_attn_mode_by_divisibility(self):
        assert self._policy("qwen3-0.6b").attn_mode == "heads"  # H=16
        assert self._policy("starcoder2-15b").attn_mode == "heads"  # H=48
        assert self._policy("gemma3-4b").attn_mode == "dmodel"  # H=8 < 16

    def test_fsdp_triggers_on_size(self):
        assert self._policy("grok-1-314b").fsdp  # 314B
        assert self._policy("dbrx-132b").fsdp
        assert not self._policy("qwen3-0.6b").fsdp
        assert not self._policy("rwkv6-7b").fsdp

    def test_multi_pod_batch_axes(self):
        p = self._policy("qwen3-0.6b", multi_pod=True)
        assert p.batch_axes == ("pod", "data")
        p1 = self._policy("qwen3-0.6b", multi_pod=False)
        assert p1.batch_axes == ("data",)

    @pytest.mark.parametrize("arch", sorted(all_configs()))
    def test_param_specs_divisible(self, arch):
        """Every emitted spec must evenly divide its tensor dimension."""
        from repro.launch import specs as lspecs

        policy = self._policy(arch)
        p = lspecs.params_specs(get_config(arch))
        shardings = policy.params_sharding(p)

        def check(leaf, sh):
            spec = sh.spec
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= policy.mesh.shape[a]
                assert leaf.shape[i] % n == 0, (arch, leaf.shape, spec)

        jax.tree.map(check, p, shardings)

    @pytest.mark.parametrize("arch", ["grok-1-314b", "internvl2-76b", "dbrx-132b"])
    def test_big_models_fit_per_chip(self, arch):
        """bf16 params sharded over the 256-chip pod must fit 16 GB/chip."""
        from repro.launch import specs as lspecs

        policy = self._policy(arch)
        p = lspecs.params_specs(get_config(arch))
        shardings = policy.params_sharding(p)
        per_chip = 0
        for leaf, sh in zip(jax.tree.leaves(p), jax.tree.leaves(shardings)):
            n = 1
            for ax in sh.spec:
                if ax is None:
                    continue
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    n *= policy.mesh.shape[a]
            per_chip += leaf.size * 2 / n
        assert per_chip < 10 * 2**30, f"{arch}: {per_chip/2**30:.1f} GiB/chip"


def _cache_reads(jaxpr, layer_shape):
    """(while loops, slices of a layer's cache) in ``jaxpr`` and the jaxprs
    it holds (the layer scan's body, a loop's body)."""
    loops = slices = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            loops += 1
        if (eqn.primitive.name in ("slice", "dynamic_slice")
                and eqn.invars[0].aval.shape == layer_shape):
            slices += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's Jaxpr
                if hasattr(inner, "eqns"):
                    w, s = _cache_reads(inner, layer_shape)
                    loops, slices = loops + w, slices + s
    return loops, slices


def test_decode_scores_policy_keeps_full_cache_read():
    """Under a policy that shards the decode scores over the cache length
    the decode step reads the whole cache (no loop, no block slice);
    without one, or where the spec splits nothing (one device), it loops
    over the live blocks."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from repro.launch import specs
    from repro.runtime.serve import make_serve_step
    from repro.runtime.sharding import make_policy

    cfg = get_config("qwen3-0.6b").reduced()
    b, max_len = 2, 512
    args = (specs.params_specs(cfg), specs.cache_specs(cfg, b, max_len),
            {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32))
    layer = (b, max_len, cfg.num_kv_heads, cfg.resolved_head_dim)

    def reads(policy=None):
        return _cache_reads(jax.make_jaxpr(make_serve_step(cfg, policy))(*args).jaxpr, layer)

    sharded = make_policy(cfg, AbstractMesh((1, 4), ("data", "model")))
    assert sharded.activation_specs().get("decode_scores") is not None
    assert reads(sharded) == (0, 0)
    # one loop in the layer scan, slicing a block of K and one of V
    assert reads() == (1, 2)
    assert reads(make_policy(cfg, AbstractMesh((1, 1), ("data", "model")))) == (1, 2)


SMALL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeCfg
from repro.launch import specs
from repro.launch.mesh import make_debug_mesh
from repro.runtime.sharding import make_policy
from repro.runtime.serve import make_serve_step, make_prefill

mesh = make_debug_mesh(8)  # (2, 4) over ("data", "model")
assert dict(mesh.shape) == {"data": 2, "model": 4}, mesh.shape
arch = os.environ["TEST_ARCH"]
cfg = get_config(arch).reduced()
policy = make_policy(cfg, mesh)
p = specs.params_specs(cfg)
ps = policy.params_sharding(p)

shape = ShapeCfg("t", 64, 4, "prefill")
batch = specs.input_specs(cfg, shape)
with mesh:
    fn = jax.jit(make_prefill(cfg, policy), in_shardings=(ps, policy.inputs_sharding(batch)))
    fn.lower(p, batch).compile()
    c = specs.cache_specs(cfg, 4, 64)
    cs = policy.cache_sharding(c)
    db = specs.decode_input_specs(cfg, ShapeCfg("d", 64, 4, "decode"))
    sfn = jax.jit(make_serve_step(cfg, policy),
                  in_shardings=(ps, cs, policy.inputs_sharding(db),
                                jax.NamedSharding(mesh, jax.sharding.PartitionSpec())))
    sfn.lower(p, c, db, jax.ShapeDtypeStruct((), jnp.int32)).compile()
print("SMALL_MESH_OK")
"""


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "zamba2-7b", "rwkv6-7b", "dbrx-132b"])
def test_reduced_configs_compile_on_small_mesh(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["TEST_ARCH"] = arch
    env["JAX_PLATFORMS"] = "cpu"  # the child never reaches for an accelerator
    out = subprocess.run(
        [sys.executable, "-c", SMALL_MESH_SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert "SMALL_MESH_OK" in out.stdout, out.stderr[-3000:]
