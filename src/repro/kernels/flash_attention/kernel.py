"""Pallas TPU flash attention (blockwise online-softmax), GQA + causal +
sliding-window.

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
"arbitrary" (sequential) so the VMEM scratch accumulators (m, l, acc) carry
across kv blocks. The kernel runs head-major: the wrapper transposes
(b, s, H, hd) to (b, H, s, hd) so that every block ends in (rows, head_dim),
which the TPU's (8, 128) tiling accepts (a head axis of block 1 second from
last does not). Block shapes default to (128, head_dim) — MXU-aligned on the
128 lane dimension; the (Bq, Bk) score tile hits the 128x128 MXU.

HBM->VMEM movement per (q_block): q once, k/v streamed per kv block — the
same URAM/BRAM streaming discipline as the paper's PU, re-derived for the
TPU memory hierarchy (HBM -> VMEM -> MXU).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (Bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    # zero padded kv rows: ragged final blocks are padded out-of-bounds and
    # 0 * pad_garbage would still poison the p @ v matmul.
    kv_valid = (ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)) < kv_len
    k = jnp.where(kv_valid, k, 0.0)
    v = jnp.where(kv_valid, v, 0.0)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (Bq, Bk)

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = cols < kv_len
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (Bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # 0 for fully-masked rows
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_tpu(
    q: jax.Array,  # (b, s, H, hd)
    k: jax.Array,  # (b, t, G, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, s, H, hd = q.shape
    t, G = k.shape[1], k.shape[2]
    rep = H // G
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)

    bq = min(block_q, s)
    bk = min(block_k, t)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(t, bk)

    kernel = functools.partial(
        _attn_kernel,
        scale=sc, causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=t,
    )
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda bb, h, qi, ki: (bb, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda bb, h, qi, ki: (bb, h // rep, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, H, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
