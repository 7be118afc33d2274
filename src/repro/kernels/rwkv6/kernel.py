"""Pallas TPU kernel for the RWKV6 wkv recurrence, chunk-tiled.

Grid = (batch, heads, seq_chunks); the chunk dimension is sequential
("arbitrary") so the (P, P) fp32 state matrix lives in VMEM scratch across
chunks — the TPU analogue of keeping the recurrence state resident (URAM-
resident accumulators in the paper's PU). Within a chunk the recurrence
steps run as a loop of (1,P) and (P,P) fp32 VPU ops on VMEM-resident tiles;
HBM traffic is one stream of r/k/v/w tiles per chunk.

The kernel runs head-major: the wrapper transposes (b, s, h, p) to
(b, h, s, p) so that every block ends in (chunk, P), which the TPU's
(8, 128) tiling accepts (a head axis of block 1 second from last does not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                 state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    p = state_scr.shape[0]
    u = u_ref[0].astype(jnp.float32)  # (1, P)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1)).astype(jnp.float32)

    def column(row):
        # (1, P) -> (P, 1) without a transpose: one nonzero per row, so the
        # lane reduction is exact
        return jnp.sum(eye * row, axis=1, keepdims=True)

    def step(t, S):
        row = lambda ref: ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, P)
        rt, kt, vt, wt = row(r_ref), row(k_ref), row(v_ref), row(w_ref)
        # r . (S + u * k^T v) == r S + (r . (u * k)) v, all on the VPU in fp32
        y = jnp.sum(column(rt) * S, axis=0, keepdims=True)
        y = y + jnp.sum(rt * u * kt, axis=1, keepdims=True) * vt
        y_ref[0, 0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return S * column(wt) + column(kt) * vt

    S = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = S

    @pl.when(ci == nc - 1)
    def _finish():
        sout_ref[0, 0] = S.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_tpu(r, k, v, w, u, state, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """r/k/v/w: (b, s, h, p); u: (h, p); state: (b, h, p, p) fp32."""
    b, s, h, p = r.shape
    ch = min(chunk, s)
    nc = pl.cdiv(s, ch)
    pad = nc * ch - s
    if pad:
        padfn = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = padfn(r), padfn(k), padfn(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)

    kernel = functools.partial(_wkv6_kernel, chunk=ch)
    seq_spec = pl.BlockSpec((1, 1, ch, p), lambda bb, hh, cc: (bb, hh, cc, 0))
    state_spec = pl.BlockSpec((1, 1, p, p), lambda bb, hh, cc: (bb, hh, 0, 0))
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, p), lambda bb, hh, cc: (hh, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc * ch, p), r.dtype),
            jax.ShapeDtypeStruct((b, h, p, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*(a.transpose(0, 2, 1, 3) for a in (r, k, v, w)), u.reshape(h, 1, p), state)
    return y.transpose(0, 2, 1, 3)[:, :s], s_out
