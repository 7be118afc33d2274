"""Decode-step microbenchmark: the model's one-token decode program at a
fixed cache position, for each form of decode attention weighed when the
live-block read was chosen.

    PYTHONPATH=src python3 benchmarks/decode_attention.py [--small] [--out FILE]

Forms, swapped into ``repro.models.attention`` while the step compiles:

  repeat     K/V repeated to every query head, every row scored and the
             rows past ``pos`` masked (the form before the live-block read)
  full       grouped contraction (each K/V head read once), every row
  blocks128  grouped, an online softmax over the 128-row blocks that hold
             live rows (the program's form)
  blocks256  the same over 256-row blocks

qwen3-0.6b at published width, bf16 weights and a bf16 cache of random K/V
(batch 8, 1024 rows; rows past ``pos`` are noise the mask must hide), one
compiled program per form for every position. For each form and position:
ms a call (mean of ``--calls`` calls after warm-up, one device sync) and
``logit_err``, the largest |difference| of a row's logits over that row's
RMS, against the same step in float32 at ``highest`` matmul precision in
the ``repeat`` form. ``--small`` runs a reduced config at a CPU size.
"""
from __future__ import annotations

import argparse
import json
import math
import time
from unittest import mock

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.flash_attention.ref import repeat_kv
from repro.models import attention
from repro.models import transformer as tf


def repeat_form(p, cfg, x, layer_cache, pos, *, local):
    """Decode attention with K/V repeated to every query head over every
    cache row."""
    b = x.shape[0]
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cache_len = layer_cache["k"].shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = attention._project_qkv(p, cfg, x, positions)
    slot = jnp.where(jnp.array(local), pos % cache_len, jnp.minimum(pos, cache_len - 1))
    ck = jax.lax.dynamic_update_slice(layer_cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(layer_cache["v"], v, (0, slot, 0, 0))
    kr, vr = repeat_kv(ck, H // G), repeat_kv(cv, H // G)
    scores = jnp.einsum("buhq,bthq->bhut", q, kr, preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    valid = jnp.arange(cache_len)[None, :] <= jnp.minimum(pos, cache_len - 1)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhut,bthq->buhq", probs, vr)
    return jnp.einsum("bshq,hqd->bsd", out, p["wo"]), {"k": ck, "v": cv}


FORMS = {
    "repeat": lambda: mock.patch.object(attention, "decode_attention", repeat_form),
    "full": lambda: mock.patch.object(attention, "_attend_live_blocks", attention._attend_all),
    "blocks128": lambda: mock.patch.object(attention, "DECODE_BLOCK", 128),
    "blocks256": lambda: mock.patch.object(attention, "DECODE_BLOCK", 256),
}


def compile_step(cfg, form, params, caches, batch):
    step = jax.jit(lambda p, c, b, pos: tf.decode_step(cfg, p, c, b, pos))
    with FORMS[form]():
        return step.lower(params, caches, batch, jnp.int32(0)).compile()


def logit_err(logits, ref) -> float:
    logits, ref = logits.astype(jnp.float32)[:, -1], ref[:, -1]
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
    return float(jnp.max(jnp.max(jnp.abs(logits - ref), axis=-1) / rms))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true", help="reduced config, CPU size")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--positions", default="60,300,600,1000")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--out", default=None, help="write the results as JSON here")
    args = ap.parse_args(argv)

    cfg = get_config("qwen3-0.6b")
    batch_size, max_len = 8, 1024
    if args.small:
        cfg, batch_size, max_len = cfg.reduced(), 2, 256
    positions = [min(int(p), max_len - 1) for p in args.positions.split(",")]
    kp, kc, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    params = jax.jit(lambda k: tf.init_params(cfg, k, jnp.bfloat16))(kp)
    leaves, tree = jax.tree.flatten(tf.init_cache(cfg, batch_size, max_len, jnp.bfloat16))
    keys = jax.random.split(kc, len(leaves))
    caches = jax.tree.unflatten(tree, [jax.random.normal(k, a.shape, a.dtype)
                                       for k, a in zip(keys, leaves)])
    batch = {"tokens": jax.random.randint(kt, (batch_size, 1), 0, cfg.vocab_size)}

    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        ref_step = compile_step(cfg, "repeat", f32(params), f32(caches), batch)
    refs = {pos: ref_step(f32(params), f32(caches), batch, jnp.int32(pos))[0]
            for pos in positions}

    results = {"device": jax.devices()[0].device_kind, "batch": batch_size,
               "max_len": max_len, "forms": {}}
    for form in args.forms.split(","):
        step = compile_step(cfg, form, params, caches, batch)
        row = {"ms_per_call": {}, "logit_err": {}}
        for pos in positions:
            p = jnp.int32(pos)
            logits, _ = step(params, caches, batch, p)
            jax.block_until_ready(step(params, caches, batch, p))
            t = time.perf_counter()
            for _ in range(args.calls):
                out = step(params, caches, batch, p)
            jax.block_until_ready(out)
            row["ms_per_call"][pos] = 1e3 * (time.perf_counter() - t) / args.calls
            row["logit_err"][pos] = logit_err(logits, refs[pos])
        results["forms"][form] = row
        print(form, json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
