"""Plain float32 forward of a dense GQA decoder, in ``jax.numpy`` at
``default_matmul_precision("highest")``. It imports nothing of the program.

It reads the benchmark's weights in the program's layout: per layer
``norm1``/``norm2`` (an RMSNorm weight of ``1 + w``), ``attn`` with
``wq (d, H, hd)``, ``wk``/``wv (d, G, hd)``, ``wo (H, hd, d)`` and, with
qk-norm, ``q_norm``/``k_norm`` over the head dim; ``mlp`` with ``w_in``,
``w_out`` and, for SwiGLU, ``w_gate``; then ``final_norm`` and the tied
embedding or ``lm_head``. The equations are the published ones: pre-norm
residual blocks, RoPE by rotating halves (theta from the configuration),
causal softmax attention with K/V heads shared by H/G query heads, SwiGLU
(silu(x Wg) * x Wi) or GELU (tanh form) MLP.

``quant`` names the control: the same forward with every matrix rounded
per output channel to int8 or to float8 (e4m3), the precision step below
the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(x):
    return x.astype(jnp.float32)


def quantize(w: jax.Array, quant: str | None, in_dims: int) -> jax.Array:
    """Round ``w`` (fan-in on its first ``in_dims`` axes) per output channel."""
    w = _f32(w)
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=tuple(range(in_dims)), keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(quant)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f32(w))


def rope(x, theta):
    """x: (b, s, heads, hd), positions 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(m: dict, p: dict, x: jax.Array, quant: str | None = None) -> jax.Array:
    """One decoder layer on x (b, s, d) float32."""
    eps = m["norm_eps"]
    H, G, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rmsnorm(x, p["norm1"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, quantize(a["wq"], quant, 1))
    k = jnp.einsum("bsd,dgk->bsgk", h, quantize(a["wk"], quant, 1))
    v = jnp.einsum("bsd,dgk->bsgk", h, quantize(a["wv"], quant, 1))
    if m["qk_norm"]:
        q = rmsnorm(q, a["q_norm"], eps)
        k = rmsnorm(k, a["k_norm"], eps)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    b, s = x.shape[0], x.shape[1]
    q = q.reshape(b, s, G, H // G, hd)
    scores = jnp.einsum("bsgrk,btgk->bgrst", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgrst,btgk->bsgrk", probs, v).reshape(b, s, H, hd)
    x = x + jnp.einsum("bshk,hkd->bsd", o, quantize(a["wo"], quant, 2))
    h = rmsnorm(x, p["norm2"], eps)
    f = p["mlp"]
    up = h @ quantize(f["w_in"], quant, 1)
    if m["mlp"] == "swiglu":
        up = jax.nn.silu(h @ quantize(f["w_gate"], quant, 1)) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + up @ quantize(f["w_out"], quant, 1)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layers(m, stacked, x, quant):
    m = dict(m)

    def body(h, p):
        return layer(m, p, h, quant), None

    with jax.default_matmul_precision(HIGHEST):
        return jax.lax.scan(body, x, stacked)[0]


def layers(m: dict, stacked: dict, x: jax.Array, quant: str | None = None) -> jax.Array:
    """The layers stacked on the leading axis of ``stacked``, in order, on
    the device that holds them."""
    return _layers(tuple(sorted(m.items())), stacked, x, quant)


def table_rows(table, quant: str | None = None):
    """A (vocab, d) embedding table, rounded per row (per vocabulary entry)
    for the control."""
    return quantize(_f32(table).T, quant, 1).T


@partial(jax.jit, static_argnames=("quant",))
def embed(table, tokens, quant=None):
    return table_rows(table, quant)[tokens]


@partial(jax.jit, static_argnames=("eps", "tied", "quant"))
def logits(final_norm, head, x, *, eps, tied, quant=None):
    """Final norm and unembedding of x (..., d); a tied head is the
    (vocab, d) embedding table."""
    h = rmsnorm(x, final_norm, eps)
    with jax.default_matmul_precision(HIGHEST):
        if tied:
            return h @ table_rows(head, quant).T
        return h @ quantize(head, quant, 1)
