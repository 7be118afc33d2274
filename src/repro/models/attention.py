"""Grouped-query attention: full / sliding-window / local-global, optional
qk-norm, RoPE; prefill (full-sequence) and single-token decode paths.

The full-sequence path routes through ``repro.kernels.flash_attention.ops``
which dispatches to the Pallas TPU kernel on TPU and the pure-jnp reference
elsewhere (so CPU dry-runs and tests always lower).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..runtime.pspec import constrain, shards
from .layers import apply_rope, normal, rmsnorm


def init_attn(key, cfg: ArchConfig, dtype) -> dict:
    d, H, G, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": normal(k1, (d, H, hd), s, dtype),
        "wk": normal(k2, (d, G, hd), s, dtype),
        "wv": normal(k3, (d, G, hd), s, dtype),
        "wo": normal(k4, (H, hd, d), 1.0 / math.sqrt(H * hd), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p: dict, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhq->bshq", x, p["wq"])
    k = jnp.einsum("bsd,dgq->bsgq", x, p["wk"])
    v = jnp.einsum("bsd,dgq->bsgq", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def full_attention(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,
    *,
    local: bool,
    window: Optional[int] = None,
) -> jax.Array:
    """Causal (optionally windowed) self-attention over the full sequence."""
    from ..kernels.flash_attention import ops as flash

    b, s, d = x.shape
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    q = constrain(q, "attn_q")
    w = (window or cfg.window) if local else None
    out = flash.flash_attention(q, k, v, causal=True, window=w)
    out = constrain(out, "attn_out")
    return jnp.einsum("bshq,hqd->bsd", out, p["wo"])


# ------------------------------------------------------------- decode path --
def init_kv_cache(cfg: ArchConfig, n_layers: int, batch: int, length: int, dtype) -> dict:
    G, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, batch, length, G, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


DECODE_BLOCK = 128  # cache rows a decode step reads at a time


def decode_rows(pos: int, cache_len: int) -> int:
    """Cache rows a decode at ``pos`` reads: the ``DECODE_BLOCK``-row blocks
    that hold the live slots ``0..min(pos, cache_len - 1)`` (a ring buffer
    fills them in order before it wraps, and after the wrap every slot is
    live), the last block ending at the cache's end."""
    blk = min(DECODE_BLOCK, cache_len)
    live = min(pos, cache_len - 1) + 1
    return min(cache_len, -(-live // blk) * blk)


def _attend_all(q, ck, cv, pos_c):
    """q (b, G, H/G, hd) over every row of the cache (b, S, G, hd), rows past
    ``pos_c`` masked: each cached K/V head is read once for its group of
    query heads."""
    # preferred_element_type keeps the cache operand bf16 (an .astype(f32)
    # on the output makes XLA materialize an f32 copy of the cache)
    scores = jnp.einsum("bgrq,btgq->bgrt", q, ck, preferred_element_type=jnp.float32)
    scores = constrain(scores, "decode_scores")  # t-sharded (flash-decoding)
    scores *= 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.where(jnp.arange(ck.shape[1]) <= pos_c, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bgrt,btgq->bgrq", probs, cv, preferred_element_type=jnp.float32)
    return out.astype(cv.dtype)


def _attend_live_blocks(q, ck, cv, pos_c):
    """``_attend_all`` over only the blocks that hold rows ``0..pos_c``: an
    online softmax over ``DECODE_BLOCK``-row blocks, as many as ``pos_c``
    needs (flash-decoding); one block when the cache holds no more. The last
    block of a cache whose length is not a multiple of the block ends at the
    cache's end and masks the rows an earlier block read."""
    b, G, R, hd = q.shape
    cache_len = ck.shape[1]
    blk = min(DECODE_BLOCK, cache_len)
    scale = 1.0 / math.sqrt(hd)

    def block(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * blk, cache_len - blk)
        kb = jax.lax.dynamic_slice_in_dim(ck, start, blk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(cv, start, blk, axis=1)
        s = jnp.einsum("bgrq,btgq->bgrt", q, kb, preferred_element_type=jnp.float32) * scale
        t = start + jnp.arange(blk)
        s = jnp.where((t >= i * blk) & (t <= pos_c), s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum("bgrt,btgq->bgrq", e.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return m_new, l * corr + e.sum(-1), acc * corr[..., None] + pv

    init = (jnp.full((b, G, R), -1e30, jnp.float32), jnp.zeros((b, G, R), jnp.float32),
            jnp.zeros((b, G, R, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, pos_c // blk + 1, block, init)
    return (acc / l[..., None]).astype(cv.dtype)


def decode_attention(
    p: dict,
    cfg: ArchConfig,
    x: jax.Array,  # (b, 1, d)
    layer_cache: dict,  # {"k": (b, S, g, q), "v": ...} single layer slice
    pos: jax.Array,  # scalar int32 current position
    *,
    local: bool,
) -> tuple[jax.Array, dict]:
    """One token's attention over the layer's cache, after writing its K/V
    row. Reads only the ``decode_rows(pos, S)`` rows that hold live slots,
    in a loop whose trip count the device takes from the traced ``pos`` (one
    program for every position); the rows past them would add exactly 0
    after the softmax. Under an activation policy that shards
    ``decode_scores`` (over the cache length: flash-decoding across
    devices), a block slice would reshard it, so the whole cache is read
    there."""
    b = x.shape[0]
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cache_len = layer_cache["k"].shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)  # q:(b,1,H,hd) k/v:(b,1,G,hd)

    # ring-buffer slot for windowed layers; plain slot otherwise
    pos_c = jnp.minimum(pos, cache_len - 1)
    slot = jnp.where(jnp.array(local), pos % cache_len, pos_c)
    ck = jax.lax.dynamic_update_slice(layer_cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(layer_cache["v"], v, (0, slot, 0, 0))

    qg = q.reshape(b, G, H // G, hd)  # query head h = g * (H/G) + r
    if shards("decode_scores", (b, G, H // G, cache_len)):
        out = _attend_all(qg, ck, cv, pos_c)
    else:
        out = _attend_live_blocks(qg, ck, cv, pos_c)
    y = jnp.einsum("bshq,hqd->bsd", out.reshape(b, 1, H, hd), p["wo"])
    return y, {"k": ck, "v": cv}
