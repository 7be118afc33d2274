"""Chip smoke test: drive the serving path once on a TPU, through the
entry points a user calls, with qwen3-0.6b at its published width.

    python chip_smoke.py               # one chip: kernels, prefill, serving
    python chip_smoke.py --four-chip   # four chips: the 4-stage pipeline only

One process, no subprocesses; parameters and prompts are made from
``--seed``. Every check raises, so any failed phase exits non-zero. The last
line of standard output is one JSON object naming the device. Printed
seconds are smoke output, not measurements.

The persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and otherwise in ``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO = Path(__file__).resolve().parent

ARCH = "qwen3-0.6b"
# interpret-mode test tolerances (tests/test_kernels.py) for the same dtypes
FLASH_BF16_TOL = dict(rtol=3e-2, atol=3e-2)
WKV6_F32_TOL = dict(rtol=2e-4, atol=2e-4)
# Two independent fp32 paths at "highest" matmul precision (decode step vs
# flash-kernel forward; 4-stage pipeline vs one-device forward) agree to
# rounding. Logits of the seeded model are O(10-100), so the bound is
# relative to the largest reference logit: a wrong position, cache slot or
# stage hand-off moves logits by O(1) of that scale.
LOGITS_REL_TOL = 1e-3


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def compile_with_kernel(fn, *args):
    """jit + compile ``fn`` for ``args``; the program must hold a Pallas
    kernel (``tpu_custom_call``)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(),
            f"no tpu_custom_call in the compiled {getattr(fn, '__name__', fn)}")
    say(f"  compiled in {time.perf_counter() - t0:.1f}s (smoke output)")
    return compiled


def assert_logits_close(got, want, what: str) -> None:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    require(got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
    require(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    say(f"  {what}: max |delta| {err:.3e} over max |logit| {scale:.3e} "
        f"(bound {LOGITS_REL_TOL:g} x max |logit|)")
    require(err <= LOGITS_REL_TOL * scale, f"{what}: logits disagree")


def device_or_exit(need: int) -> dict:
    devs = jax.devices()
    platform = devs[0].platform
    require(platform == "tpu", f"needs a TPU, but JAX found platform {platform!r}")
    require(len(devs) >= need, f"needs {need} TPU devices, JAX found {len(devs)}")
    dev = {"platform": platform, "kind": devs[0].device_kind, "count": len(devs)}
    say(f"device: {dev}")
    return dev


# ------------------------------------------------------------------ phases --
def kernels_phase(key, *, attn_cfg, wkv_heads: int, wkv_head_dim: int,
                  seq: int, wkv_seq: int) -> None:
    """Each main-path Pallas kernel against its jnp reference, both on the
    chip."""
    from repro.kernels.flash_attention.kernel import flash_attention_tpu
    from repro.kernels.flash_attention.ref import mha_reference
    from repro.kernels.rwkv6.kernel import wkv6_tpu
    from repro.kernels.rwkv6.ref import wkv6_reference

    H, G, hd = attn_cfg.num_heads, attn_cfg.num_kv_heads, attn_cfg.resolved_head_dim
    kq, kk, kv, kw = jax.random.split(key, 4)
    q = (jax.random.normal(kq, (2, seq, H, hd)) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, (2, seq, G, hd)) * 0.5).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (2, seq, G, hd)).astype(jnp.bfloat16)
    say(f"flash_attention_tpu: q {q.shape} k/v {k.shape} bf16, causal")
    out = compile_with_kernel(flash_attention_tpu, q, k, v)(q, k, v)
    ref = jax.jit(mha_reference)(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               **FLASH_BF16_TOL)
    say("  matches mha_reference")

    h, p = wkv_heads, wkv_head_dim
    for s in (wkv_seq, 1):
        ks = jax.random.split(jax.random.fold_in(kw, s), 6)
        r = jax.random.normal(ks[0], (1, s, h, p)) * 0.5
        k6 = jax.random.normal(ks[1], (1, s, h, p)) * 0.5
        v6 = jax.random.normal(ks[2], (1, s, h, p))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, s, h, p)) + 2.0)
        u = jax.random.normal(ks[4], (h, p)) * 0.5
        state = jax.random.normal(ks[5], (1, h, p, p)) * 0.3
        say(f"wkv6_tpu: r/k/v/w {r.shape} fp32, state {state.shape}")
        with jax.default_matmul_precision("highest"):
            y, s_out = compile_with_kernel(wkv6_tpu, r, k6, v6, w, u, state)(
                r, k6, v6, w, u, state)
            y_ref, s_ref = jax.jit(wkv6_reference)(r, k6, v6, w, u, state)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **WKV6_F32_TOL)
        np.testing.assert_allclose(np.asarray(s_out), np.asarray(s_ref), **WKV6_F32_TOL)
        say("  matches wkv6_reference (outputs and final state)")


def prefill_phase(cfg, key, *, batch: int, seq: int) -> None:
    """``runtime.serve.make_prefill`` at bf16 over seeded prompts."""
    from repro.models import transformer as tf
    from repro.runtime.serve import make_prefill

    params = tf.init_params(cfg, key, jnp.bfloat16)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq), 0, cfg.vocab_size)
    say(f"prefill: {cfg.name} bf16, tokens {tokens.shape}")
    prefill = compile_with_kernel(make_prefill(cfg), params, {"tokens": tokens})
    t0 = time.perf_counter()
    logits = jax.block_until_ready(prefill(params, {"tokens": tokens}))
    say(f"  ran in {time.perf_counter() - t0:.2f}s (smoke output)")
    require(logits.shape == (batch, seq, cfg.vocab_size), f"prefill logits {logits.shape}")
    require(bool(jnp.isfinite(logits).all()), "prefill logits are not finite")
    say("  logits finite")


def serving_phase(cfg, key, *, slots: int, requests: int, prompt_len: tuple[int, int],
                  new_tokens: int, seed: int) -> None:
    """A ``ServingEngine`` answers seeded requests; its decode-path logits
    after each prompt are cross-checked against the full-sequence forward
    (flash kernel) on the same prompts."""
    from repro.models import transformer as tf
    from repro.runtime.serve import ServingEngine

    rng = np.random.default_rng(seed)
    lo, hi = prompt_len
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(lo, hi + 1))).tolist()
               for _ in range(requests)]
    with jax.default_matmul_precision("highest"):
        params = tf.init_params(cfg, key, jnp.float32)
        eng = ServingEngine(cfg, params, batch_slots=slots,
                            max_len=hi + new_tokens, dtype=jnp.float32)
        rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        say(f"serving: {cfg.name} fp32, {slots} slots, {requests} requests, "
            f"prompts {[len(p) for p in prompts]} tokens, {new_tokens} new each")
        t0 = time.perf_counter()
        done = {r.rid: r for r in eng.run_until_drained()}
        say(f"  drained in {time.perf_counter() - t0:.1f}s incl. compile (smoke output)")
        require(sorted(done) == sorted(rids), f"finished {sorted(done)} of {rids}")
        for r in done.values():
            require(r.done and len(r.generated) == new_tokens,
                    f"request {r.rid}: {len(r.generated)} of {new_tokens} tokens")
            require(all(0 <= t < cfg.vocab_size for t in r.generated),
                    f"request {r.rid}: token id out of range")
        say(f"  all {requests} requests finished with {new_tokens} tokens")

        # right-padding leaves causal logits at earlier positions unchanged,
        # so one forward over the padded prompts checks every request
        tokens = np.zeros((requests, hi), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        say(f"cross-check: forward over padded prompts {tokens.shape}")
        forward = compile_with_kernel(
            lambda prm, t: tf.forward(cfg, prm, {"tokens": t})[0], params, tokens)
        logits = forward(params, tokens)
        want = np.stack([np.asarray(logits[i, len(p) - 1]) for i, p in enumerate(prompts)])
        got = np.stack([np.asarray(done[rid].prompt_logits) for rid in rids])
    assert_logits_close(got, want, "decode-path vs forward logits at the last prompt token")


def pipeline_phase(cfg, key, *, stages: int, microbatches: int, mb: int, seq: int) -> None:
    """The paper's stage pipeline (shard_map + ppermute over a "stage" mesh
    axis) against the plain forward of the same tokens on one device."""
    from repro.models import transformer as tf
    from repro.runtime.pipeline import (
        make_pipeline_forward, make_pipeline_mesh, plan_pipeline, stack_stage_params,
    )

    with jax.default_matmul_precision("highest"):
        params = tf.init_params(cfg, key, jnp.float32)
        plan = plan_pipeline(cfg, n_stages=stages, microbatches=microbatches,
                             seq_len=seq, microbatch_size=mb)
        mesh = make_pipeline_mesh(stages, 1, 1)
        sparams = stack_stage_params(cfg, params, plan)
        tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                    (microbatches, mb, seq), 0, cfg.vocab_size)
        say(f"pipeline: {cfg.name} fp32, {stages} stages x {plan.layers_per_stage} "
            f"layers, {microbatches} microbatches of {mb} x {seq} tokens, "
            f"mesh {dict(mesh.shape)}")
        pipe = compile_with_kernel(make_pipeline_forward(cfg, plan, mesh), sparams, tokens)
        out = pipe(sparams, tokens)
        flat = tokens.reshape(microbatches * mb, seq)
        say("reference: forward on one device")
        forward = compile_with_kernel(
            lambda prm, t: tf.forward(cfg, prm, {"tokens": t})[0], params, flat)
        ref = forward(params, flat)
    assert_logits_close(np.asarray(out).reshape(ref.shape), ref,
                        f"{stages}-stage pipeline vs one-device forward")


# -------------------------------------------------------------------- main --
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-stage pipeline against the one-device forward")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    t_start = time.perf_counter()
    device = device_or_exit(4 if args.four_chip else 1)

    sys.path.insert(0, str(REPO / "src"))
    from repro.configs import get_config

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    if args.four_chip:
        pipeline_phase(cfg, key, stages=4, microbatches=4, mb=1, seq=512)
    else:
        kernels_phase(jax.random.fold_in(key, 0), attn_cfg=cfg,
                      wkv_heads=get_config("rwkv6-7b").num_heads,
                      wkv_head_dim=get_config("rwkv6-7b").ssm_head_dim,
                      seq=2048, wkv_seq=512)
        prefill_phase(cfg, jax.random.fold_in(key, 1), batch=4, seq=1024)
        serving_phase(cfg, jax.random.fold_in(key, 2), slots=4, requests=8,
                      prompt_len=(16, 64), new_tokens=16, seed=args.seed)
    say(f"all phases passed in {time.perf_counter() - t_start:.1f}s (smoke output)")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
