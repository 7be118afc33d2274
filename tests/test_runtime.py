"""Runtime tests: optimizer, data pipeline, checkpointing/fault tolerance,
serving engine, sharding policy, pipeline planner + ISA schedule simulation."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import transformer as tf
from repro.runtime import checkpoint as ckpt
from repro.runtime.data import DataConfig, DataState, TokenStream
from repro.runtime.optimizer import (
    AdafactorConfig,
    AdamWConfig,
    adafactor_init,
    adafactor_update,
    adamw_init,
    adamw_update,
    lr_schedule,
)
from repro.runtime.train import make_train_step


# ---------------------------------------------------------------- optimizer --
class TestOptimizer:
    def _quad_problem(self):
        params = {"w": jnp.array([3.0, -2.0, 1.5]), "b": jnp.array(0.5)}
        loss = lambda p: jnp.sum(jnp.square(p["w"])) + jnp.square(p["b"])
        return params, loss

    def test_adamw_converges_on_quadratic(self):
        params, loss = self._quad_problem()
        c = AdamWConfig(lr=0.2, weight_decay=0.0, warmup_steps=0, total_steps=200)
        opt = adamw_init(c, params)
        l0 = float(loss(params))
        for _ in range(150):
            g = jax.grad(loss)(params)
            params, opt, _ = adamw_update(c, g, opt, params)
        assert float(loss(params)) < 1e-2 * l0

    def test_moment_dtype_bf16(self):
        params, loss = self._quad_problem()
        c = AdamWConfig(moment_dtype=jnp.bfloat16, lr=0.1, warmup_steps=0)
        opt = adamw_init(c, params)
        assert opt["m"]["w"].dtype == jnp.bfloat16
        g = jax.grad(loss)(params)
        params2, opt2, _ = adamw_update(c, g, opt, params)
        assert opt2["v"]["w"].dtype == jnp.bfloat16
        assert not jnp.allclose(params2["w"], params["w"])

    def test_grad_clipping(self):
        params, _ = self._quad_problem()
        c = AdamWConfig(lr=1e-3, grad_clip=1.0, warmup_steps=0)
        opt = adamw_init(c, params)
        huge = jax.tree.map(lambda p: 1e6 * jnp.ones_like(p), params)
        _, _, stats = adamw_update(c, huge, opt, params)
        assert float(stats["grad_norm"]) > 1e5  # measured pre-clip

    def test_lr_schedule_warmup_cosine(self):
        c = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
        assert float(lr_schedule(c, jnp.int32(0))) == 0.0
        assert float(lr_schedule(c, jnp.int32(10))) == pytest.approx(1.0)
        assert float(lr_schedule(c, jnp.int32(100))) == pytest.approx(0.1, abs=1e-6)

    def test_adafactor_converges(self):
        params = {"w": jnp.ones((4, 3)) * 2.0}
        loss = lambda p: jnp.sum(jnp.square(p["w"]))
        c = AdafactorConfig(lr=0.3)
        opt = adafactor_init(c, params)
        for _ in range(100):
            g = jax.grad(loss)(params)
            params, opt, _ = adafactor_update(c, g, opt, params)
        assert float(loss(params)) < 0.1

    def test_adafactor_memory_is_factored(self):
        params = {"w": jnp.ones((128, 64))}
        opt = adafactor_init(AdafactorConfig(), params)
        n = sum(x.size for x in jax.tree.leaves(opt["v"]))
        assert n == 128 + 64  # rank-1 factors, not 128*64


# --------------------------------------------------------------------- data --
class TestDataPipeline:
    def test_deterministic(self):
        cfg = DataConfig(vocab_size=512, seq_len=32, global_batch=8, seed=7)
        a = TokenStream(cfg).next()
        b = TokenStream(cfg).next()
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab_size=512, seq_len=32, global_batch=4)
        batch = TokenStream(cfg).next()
        assert batch["tokens"].shape == (4, 32)
        assert batch["labels"].shape == (4, 32)

    def test_host_sharding_partitions_batch(self):
        cfg = DataConfig(vocab_size=512, seq_len=16, global_batch=8)
        full = TokenStream(cfg).next()
        parts = []
        for h in range(4):
            c = DataConfig(vocab_size=512, seq_len=16, global_batch=8, n_hosts=4, host_id=h)
            parts.append(TokenStream(c).next()["tokens"])
        np.testing.assert_array_equal(np.concatenate(parts, axis=0), full["tokens"])

    def test_state_resume_exact(self):
        cfg = DataConfig(vocab_size=512, seq_len=16, global_batch=4)
        s1 = TokenStream(cfg)
        for _ in range(5):
            s1.next()
        state = DataState.from_dict(s1.state.as_dict())
        expect = s1.next()
        s2 = TokenStream(cfg, state)
        got = s2.next()
        np.testing.assert_array_equal(expect["tokens"], got["tokens"])


# --------------------------------------------------------------- checkpoint --
class TestCheckpoint:
    def _tree(self, key=0):
        k = jax.random.PRNGKey(key)
        return {
            "a": jax.random.normal(k, (8, 4)),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32), "c": jnp.float32(3.5)},
        }

    def test_roundtrip(self, tmp_path):
        tree = self._tree()
        ckpt.save_checkpoint(str(tmp_path), 7, tree)
        restored, step, _ = ckpt.restore_checkpoint(str(tmp_path), tree)
        assert step == 7
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_step_and_gc(self, tmp_path):
        tree = self._tree()
        for s in (1, 5, 9, 12):
            ckpt.save_checkpoint(str(tmp_path), s, tree, keep=2)
        assert ckpt.latest_step(str(tmp_path)) == 12
        remaining = sorted(d for d in os.listdir(tmp_path) if d.startswith("ckpt_"))
        assert len(remaining) == 2  # gc keeps the latest 2

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        tree = self._tree()
        ckpt.save_checkpoint(str(tmp_path), 3, tree)
        # simulate a crash mid-write: directory without manifest
        os.makedirs(tmp_path / "ckpt_0000000009")
        assert ckpt.latest_step(str(tmp_path)) == 3
        restored, step, _ = ckpt.restore_checkpoint(str(tmp_path), tree)
        assert step == 3

    def test_shape_mismatch_rejected(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros((4,))})
        with pytest.raises(ValueError):
            ckpt.restore_checkpoint(str(tmp_path), {"a": jnp.zeros((5,))})

    def test_extra_metadata(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 2, self._tree(), extra={"data_step": 42})
        _, _, extra = ckpt.restore_checkpoint(str(tmp_path), self._tree())
        assert extra["data_step"] == 42


# --------------------------------------------- fault tolerance (end to end) --
class TestFaultTolerance:
    def test_crash_resume_bitexact(self, tmp_path):
        """Train 6 steps straight vs train 3 + 'crash' + resume 3: losses of
        steps 4-6 must match exactly (params + opt + data state captured)."""
        cfg = get_config("qwen3-0.6b").reduced()
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=0)
        step_fn = jax.jit(make_train_step(cfg, None, opt_cfg, remat=False))

        def fresh():
            params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
            return params, adamw_init(opt_cfg, params), TokenStream(dcfg)

        # uninterrupted
        params, opt, stream = fresh()
        losses = []
        for _ in range(6):
            batch = jax.tree.map(jnp.asarray, stream.next())
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["nll"]))

        # interrupted at step 3
        params, opt, stream = fresh()
        for _ in range(3):
            batch = jax.tree.map(jnp.asarray, stream.next())
            params, opt, m = step_fn(params, opt, batch)
        ckpt.save_checkpoint(
            str(tmp_path), 3, {"params": params, "opt": opt},
            extra={"data": stream.state.as_dict()},
        )
        del params, opt, stream  # crash

        template = {"params": tf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)}
        template["opt"] = adamw_init(opt_cfg, template["params"])
        restored, step, extra = ckpt.restore_checkpoint(str(tmp_path), template)
        stream = TokenStream(dcfg, DataState.from_dict(extra["data"]))
        params, opt = restored["params"], restored["opt"]
        resumed = []
        for _ in range(3):
            batch = jax.tree.map(jnp.asarray, stream.next())
            params, opt, m = step_fn(params, opt, batch)
            resumed.append(float(m["nll"]))
        assert resumed == pytest.approx(losses[3:], rel=1e-6)


# ------------------------------------------------------------ serving engine --
class TestServingEngine:
    def test_continuous_batching(self):
        from repro.runtime.serve import ServingEngine

        cfg = get_config("qwen3-0.6b").reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
        for i in range(4):  # more requests than slots -> queueing + recycling
            eng.submit([1 + i, 2, 3], max_new_tokens=4)
        done = eng.run_until_drained(max_ticks=200)
        assert len(done) == 4
        assert all(len(r.generated) == 4 for r in done)
        assert all(0 <= t < cfg.vocab_size for r in done for t in r.generated)

    def test_deterministic_generation(self):
        from repro.runtime.serve import ServingEngine

        cfg = get_config("qwen3-0.6b").reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        outs = []
        for _ in range(2):
            eng = ServingEngine(cfg, params, batch_slots=1, max_len=64)
            eng.submit([5, 6, 7], max_new_tokens=5)
            outs.append(tuple(eng.run_until_drained()[0].generated))
        assert outs[0] == outs[1]

    def test_generation_matches_forward(self):
        """The decode path's logits after the prompt equal the full-sequence
        forward's, and each generated token is the forward's greedy choice
        (the prompt's last token is fed once, not again as the first input)."""
        from repro.runtime.serve import ServingEngine

        cfg = get_config("qwen3-0.6b").reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        prompt = [5, 6, 7, 8]
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=32)
        eng.submit(prompt, max_new_tokens=3)
        (req,) = eng.run_until_drained()
        ref, _ = tf.forward(cfg, params, {"tokens": jnp.array([prompt])})
        np.testing.assert_allclose(req.prompt_logits, ref[0, -1], rtol=1e-4, atol=1e-4)
        for n in range(3):
            seq = jnp.array([prompt + req.generated[:n]])
            logits, _ = tf.forward(cfg, params, {"tokens": seq})
            assert req.generated[n] == int(jnp.argmax(logits[0, -1]))


    def test_decode_calls_carry_kv_rows(self, tmp_path):
        """A profiled drain puts on every ``engine.decode_call`` the cache
        rows its decode reads: ``decode_rows`` of the slot's position."""
        import glob

        from jax.profiler import ProfileData

        from repro.models.attention import decode_rows
        from repro.runtime.serve import ServingEngine

        cfg = get_config("qwen3-0.6b").reduced()
        params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        max_len = 256
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=max_len)
        eng.submit(list(range(1, 131)), max_new_tokens=3)  # past 128 rows
        eng.submit([5, 6, 7], max_new_tokens=2)
        jax.profiler.start_trace(str(tmp_path))
        try:
            done = eng.run_until_drained(max_ticks=50)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        calls: dict[int, list] = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "engine.decode_call":
                        stats = dict(ev.stats)
                        calls.setdefault(int(stats["rid"]), []).append(
                            (ev.start_ns, int(stats["kv_rows"])))
        assert len(done) == 2
        for req in done:  # its prompt, then every output token but the first
            n = len(req.prompt) + req.max_new_tokens - 1
            rows = [r for _, r in sorted(calls[req.rid])]
            assert rows == [decode_rows(pos, max_len) for pos in range(n)]
        assert {r for c in calls.values() for _, r in c} == {128, 256}


# ------------------------------------------------------------------ pipeline --
class TestPipelinePlanner:
    def test_plan_boundaries_cover_all_layers(self):
        from repro.runtime.pipeline import plan_pipeline

        cfg = get_config("h2o-danube-3-4b")
        plan = plan_pipeline(cfg, n_stages=4, microbatches=8, seq_len=2048,
                            microbatch_size=4)
        assert plan.boundaries[0] == 0 and plan.boundaries[-1] == cfg.num_layers
        sizes = np.diff(plan.boundaries)
        assert sizes.max() - sizes.min() <= 1  # balanced

    def test_stage_programs_validate_and_simulate(self):
        """The emitted coordination programs must execute deadlock-free on
        the discrete-event simulator (schedule verification)."""
        from repro.core import MultiPUSimulator
        from repro.core.pu import PUSpec
        from repro.runtime.pipeline import plan_pipeline

        cfg = get_config("qwen3-0.6b")
        plan = plan_pipeline(cfg, n_stages=4, microbatches=6, seq_len=1024,
                            microbatch_size=2)
        for p in plan.programs:
            p.validate()
        pus = [PUSpec(pid=i, kind="PU2x", sa_rows=64, sa_cols=8, slr=i // 2)
               for i in range(4)]
        sim = MultiPUSimulator(pus)
        res = sim.run(plan.programs, first_pid=0, last_pid=3)
        assert not res.deadlocked
        assert res.rounds == 6  # all microbatches drained

    def test_predicted_throughput_scales_with_stages(self):
        from repro.runtime.pipeline import plan_pipeline

        cfg = get_config("h2o-danube-3-4b")
        t1 = plan_pipeline(cfg, n_stages=1, microbatches=8, seq_len=2048,
                          microbatch_size=4).predicted_throughput
        t4 = plan_pipeline(cfg, n_stages=4, microbatches=8, seq_len=2048,
                          microbatch_size=4).predicted_throughput
        assert 3.0 <= t4 / t1 <= 4.01


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as tf
from repro.runtime.pipeline import (
    make_pipeline_forward, make_pipeline_mesh, plan_pipeline, stack_stage_params,
)

cfg = get_config("h2o-danube-3-4b").reduced()
params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
B, S, M = 4, 16, 2
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
ref, _ = tf.forward(cfg, params, {"tokens": toks})

plan = plan_pipeline(cfg, n_stages=4, microbatches=M, seq_len=S, microbatch_size=B // M)
mesh = make_pipeline_mesh(4, 1, 1)
sparams = stack_stage_params(cfg, params, plan)
fn = jax.jit(make_pipeline_forward(cfg, plan, mesh))
toks_mb = toks.reshape(M, B // M, S)
out = fn(sparams, toks_mb).reshape(B, S, cfg.vocab_size)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
print("PIPELINE_EQUIVALENCE_OK")
"""


def test_pipeline_forward_matches_reference_subprocess():
    """4 'devices' (forced host platform), 4 pipeline stages: the shard_map +
    ppermute pipeline must reproduce the plain forward logits."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # the child never reaches for an accelerator
    out = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert "PIPELINE_EQUIVALENCE_OK" in out.stdout, out.stderr[-3000:]
