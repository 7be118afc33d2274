"""Per-architecture smoke tests (reduced configs, CPU) + semantic
consistency: one-token decode must reproduce full-sequence forward."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import all_configs, get_config
from repro.models import attention as attn
from repro.models import transformer as tf

ARCHS = sorted(all_configs())


def make_batch(cfg, B, S, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    if cfg.frontend == "frame_embed":
        return {"frame_embeds": jax.random.normal(ks[0], (B, S, cfg.d_model)) * 0.02}
    batch = {"tokens": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)}
    if cfg.frontend == "patch_embed":
        batch["patch_embeds"] = (
            jax.random.normal(ks[2], (B, cfg.n_prefix_embeds, cfg.d_model)) * 0.02
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    """One forward + one train-style step per assigned architecture."""

    def test_forward_shapes_and_finite(self, arch):
        cfg = get_config(arch).reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        B, S = 2, 64
        batch = make_batch(cfg, B, S)
        logits, aux = jax.jit(lambda p, b: tf.forward(cfg, p, b))(params, batch)
        assert logits.shape == (B, S, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert bool(jnp.isfinite(aux["moe_aux"]))

    def test_train_step_no_nans(self, arch):
        """One SGD step on next-token loss: finite loss, finite grads."""
        cfg = get_config(arch).reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
        B, S = 2, 32
        batch = make_batch(cfg, B, S, key=1)
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)

        def loss_fn(p):
            logits, aux = tf.forward(cfg, p, batch, remat=True)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1).mean()
            return nll + 0.01 * aux["moe_aux"]

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        assert bool(jnp.isfinite(loss))
        flat = jax.tree.leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
        assert any(float(jnp.abs(g).max()) > 0 for g in flat)

    def test_decode_step_shapes(self, arch):
        cfg = get_config(arch).reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        B = 2
        cache = tf.init_cache(cfg, B, max_len=128, dtype=jnp.float32)
        batch = make_batch(cfg, B, 1)
        step = jax.jit(lambda p, c, b, pos: tf.decode_step(cfg, p, c, b, pos))
        logits, cache = step(params, cache, batch, jnp.int32(0))
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize(
    "arch", ["qwen3-0.6b", "h2o-danube-3-4b", "gemma3-4b", "rwkv6-7b", "zamba2-7b",
             "musicgen-large"]
)
def test_decode_matches_forward(arch):
    """Replaying a sequence token-by-token through decode_step must match the
    full-sequence forward logits (cache semantics, ring buffers, SSM states,
    shared-block caches)."""
    cfg = get_config(arch).reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    B, S = 1, 24
    batch = make_batch(cfg, B, S, key=7)
    full_logits, _ = jax.jit(lambda p, b: tf.forward(cfg, p, b))(params, batch)

    cache = tf.init_cache(cfg, B, max_len=S, dtype=jnp.float32)
    step = jax.jit(lambda p, c, b, pos: tf.decode_step(cfg, p, c, b, pos))
    outs = []
    for t in range(S):
        if cfg.frontend == "frame_embed":
            bt = {"frame_embeds": batch["frame_embeds"][:, t : t + 1]}
        else:
            bt = {"tokens": batch["tokens"][:, t : t + 1]}
        lg, cache = step(params, cache, bt, jnp.int32(t))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    if cfg.family == "ssm":
        # rwkv: forward uses the chunked WKV, decode the sequential
        # recurrence; their ~5e-6 fp reassociation gap compounds through the
        # per-head group norms (near-zero variance at init) into O(0.1)
        # logit deltas on <2% of entries — assert semantic agreement
        # (identical top-1, close distributions) instead of bitwise logits.
        p_dec = jax.nn.softmax(dec_logits, axis=-1)
        p_full = jax.nn.softmax(full_logits, axis=-1)
        np.testing.assert_allclose(np.asarray(p_dec), np.asarray(p_full), atol=2e-2)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(dec_logits), -1),
            np.argmax(np.asarray(full_logits), -1),
        )
    else:
        np.testing.assert_allclose(
            np.asarray(dec_logits), np.asarray(full_logits), rtol=2e-3, atol=2e-3
        )


def _full_length_decode_attention(p, cfg, x, layer_cache, pos, *, local):
    """Decode attention as it was before the live-row read: K/V repeated
    to every query head and all cache rows scored, rows past ``pos`` masked."""
    from repro.kernels.flash_attention.ref import repeat_kv

    b = x.shape[0]
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cache_len = layer_cache["k"].shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = attn._project_qkv(p, cfg, x, positions)
    slot = jnp.where(jnp.array(local), pos % cache_len, jnp.minimum(pos, cache_len - 1))
    ck = jax.lax.dynamic_update_slice(layer_cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(layer_cache["v"], v, (0, slot, 0, 0))
    kr = repeat_kv(ck, H // G)
    vr = repeat_kv(cv, H // G)
    scores = jnp.einsum("buhq,bthq->bhut", q, kr, preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    valid = jnp.arange(cache_len)[None, :] <= jnp.minimum(pos, cache_len - 1)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhut,bthq->buhq", probs, vr)
    return jnp.einsum("bshq,hqd->bsd", out, p["wo"]), {"k": ck, "v": cv}


# (config, cache slots, ring buffer, positions decoded)
DECODE_ROW_CASES = {
    "dense-512": ("qwen3-0.6b", 512, False, (0, 126, 127, 128, 255, 256, 511, 600)),
    "dense-200": ("qwen3-0.6b", 200, False, (0, 127, 128, 150, 199, 250)),
    "ring-64": ("h2o-danube-3-4b", 64, True, (0, 63, 64, 200, 201)),
    "ring-256": ("h2o-danube-3-4b", 256, True, (0, 127, 128, 255, 256, 300, 511)),
}


@pytest.mark.parametrize("case", sorted(DECODE_ROW_CASES))
def test_decode_attention_reads_live_rows_exactly(case):
    """Reading only the live blocks of the cache gives what scoring every
    row gave, on full-length caches (one whose length is not a multiple of
    the block among them) and on ring buffers before and past their wrap;
    the rows past ``pos`` hold noise that only the mask hides."""
    arch, cache_len, local, positions = DECODE_ROW_CASES[case]
    cfg = get_config(arch).reduced()
    p = attn.init_attn(jax.random.PRNGKey(0), cfg, jnp.float32)
    kk, kv, kx = jax.random.split(jax.random.PRNGKey(1), 3)
    b = 2
    shape = (b, cache_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    cache = {"k": jax.random.normal(kk, shape), "v": jax.random.normal(kv, shape)}
    x = jax.random.normal(kx, (b, 1, cfg.d_model))
    new = jax.jit(lambda c, pos: attn.decode_attention(p, cfg, x, c, pos, local=local))
    old = jax.jit(lambda c, pos: _full_length_decode_attention(p, cfg, x, c, pos, local=local))
    for pos in positions:
        y, c = new(cache, jnp.int32(pos))
        y_ref, c_ref = old(cache, jnp.int32(pos))
        err = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
        assert err <= 1e-5, (pos, err)
        np.testing.assert_array_equal(c["k"], c_ref["k"])
        np.testing.assert_array_equal(c["v"], c_ref["v"])


def test_decode_rows():
    """Whole blocks of ``DECODE_BLOCK`` rows up to the last live slot, never
    more than the cache holds."""
    assert attn.DECODE_BLOCK == 128
    assert [attn.decode_rows(p, 1024) for p in (0, 127, 128, 255, 256, 1023, 5000)] == [
        128, 128, 256, 256, 384, 1024, 1024]
    for cache_len in (32, 100, 128, 200, 512, 1024):
        blk = min(128, cache_len)
        for pos in range(2 * cache_len):
            live = min(pos, cache_len - 1) + 1
            rows = attn.decode_rows(pos, cache_len)
            assert live <= rows <= cache_len, (cache_len, pos, rows)
            assert rows == cache_len or (rows % blk == 0 and rows - live < blk)


def test_vlm_prefix_embeds_change_output():
    cfg = get_config("internvl2-76b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 1, 32
    batch = make_batch(cfg, B, S)
    l1, _ = tf.forward(cfg, params, batch)
    batch2 = dict(batch)
    batch2["patch_embeds"] = batch["patch_embeds"] + 1.0
    l2, _ = tf.forward(cfg, params, batch2)
    assert float(jnp.abs(l1 - l2).max()) > 1e-4


def test_gemma3_plan_five_to_one():
    cfg = get_config("gemma3-4b")
    plan = tf.layer_plan(cfg)
    n_local = sum(b.n for b in plan if b.local)
    n_global = sum(b.n for b in plan if not b.local)
    assert n_local + n_global == 34
    assert n_global == 5  # ~5:1 local:global at 34 layers
    assert all(not b.local for b in plan if b.n == 1 and not b.local)


def test_zamba2_plan_shared_blocks():
    cfg = get_config("zamba2-7b")
    plan = tf.layer_plan(cfg)
    mamba = sum(b.n for b in plan if b.kind == "mamba")
    shared = [b for b in plan if b.kind == "shared_attn"]
    assert mamba == 81
    assert len(shared) == 13  # one per full 6-mamba group
    assert {b.shared_idx for b in shared} == {0, 1}  # alternating


def test_moe_capacity_drops_are_bounded():
    """With capacity factor 1.25, most tokens route (few drops on random data)."""
    cfg = get_config("dbrx-132b").reduced()
    import repro.models.moe as moe_mod

    params_moe = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 0.1
    y, aux = moe_mod.moe_mlp(params_moe, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    # aux loss ~1 for a balanced router at init
    assert 0.5 < float(aux) < 4.0


def test_param_counts_match_pool():
    """Full configs land near the pool's nominal parameter counts."""
    expect = {
        "grok-1-314b": (260e9, 340e9),
        "dbrx-132b": (110e9, 145e9),
        "internvl2-76b": (62e9, 80e9),  # LM backbone of the 76B VLM
        "starcoder2-15b": (13e9, 17e9),
        "rwkv6-7b": (6e9, 9e9),
        "qwen3-0.6b": (0.4e9, 0.85e9),
    }
    for name, (lo, hi) in expect.items():
        n = get_config(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.1f}B not in [{lo/1e9}, {hi/1e9}]"
