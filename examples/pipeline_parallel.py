"""The paper's coordination technique on TPU: pipeline an LM across mesh
stages with compiler-emitted instruction programs, verify the schedule on
the discrete-event simulator, execute via shard_map + ppermute, and show
runtime strategy switching (pipeline vs hybrid) without reconfiguration.

Run with forced host devices to see real multi-stage execution on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/pipeline_parallel.py --stages 4
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import MultiPUSimulator, PipelineMember
from repro.core.pu import PUSpec
from repro.models import transformer as tf
from repro.runtime.pipeline import (
    layer_cost_seconds,
    make_pipeline_forward,
    make_pipeline_mesh,
    plan_pipeline,
    stack_stage_params,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    B, S = 4, 32
    mb = B // args.microbatches

    # --- step 1: the compiler plans the pipeline + emits ISA programs ------
    plan = plan_pipeline(cfg, n_stages=args.stages, microbatches=args.microbatches,
                        seq_len=S, microbatch_size=mb)
    print(f"plan: {plan.n_stages} stages x {plan.layers_per_stage} layers, "
          f"boundaries {plan.boundaries}")
    print(f"analytic: {plan.predicted_throughput:.1f} microbatches/s, "
          f"latency {plan.predicted_latency*1e3:.2f} ms")
    print("\nstage 1 instruction programs (coordination expressed in the ISA):")
    print(plan.programs[1].ld.disassemble())

    # --- step 2: verify the schedule on the discrete-event simulator -------
    pus = [PUSpec(pid=i, kind="PU2x", sa_rows=64, sa_cols=8, slr=i // 2)
           for i in range(args.stages)]
    sim = MultiPUSimulator(pus)
    member = PipelineMember(first_pid=0, last_pid=args.stages - 1, label="lm")
    res = sim.run(plan.programs, members=[member])
    mres = res.members[0]
    print(f"\nsimulator: {mres.rounds} microbatches drained, "
          f"{mres.throughput_fps(warmup=1):.1f} microbatches/s, "
          f"deadlock={res.deadlocked}, {res.tokens_sent} REQ/ACK tokens")

    # --- step 3: execute on the mesh (shard_map + ppermute) ----------------
    n_dev = len(jax.devices())
    if n_dev < args.stages:
        raise SystemExit(
            f"{n_dev} device(s) for {args.stages} stages: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.stages} "
            f"to run the mesh execution step on the CPU"
        )
    mesh = make_pipeline_mesh(args.stages, 1, 1)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    sparams = stack_stage_params(cfg, params, plan)
    fn = jax.jit(make_pipeline_forward(cfg, plan, mesh))
    toks = jax.random.randint(jax.random.PRNGKey(1), (args.microbatches, mb, S),
                              0, cfg.vocab_size)
    out = fn(sparams, toks)
    ref, _ = tf.forward(cfg, params, {"tokens": toks.reshape(B, S)})
    err = float(jnp.max(jnp.abs(out.reshape(B, S, -1) - ref)))
    print(f"\nmesh execution: logits {out.shape}, max |delta| vs plain "
          f"forward = {err:.2e}")

    # --- step 4: strategy switching without reconfiguration ----------------
    # 4a. On the simulator: the PU array is fixed; sim.reset() clears only
    # the transient ICU/ISU state and a re-planned instruction schedule with
    # fewer stages runs on the same machine (repro.deploy.System wraps this
    # load/switch/run cycle for compiled DNN deployments).
    print("\nruntime switching on the fixed simulated machine:")
    for n_stages in sorted({args.stages, max(1, args.stages // 2)}, reverse=True):
        alt = plan_pipeline(cfg, n_stages=n_stages, microbatches=args.microbatches,
                            seq_len=S, microbatch_size=mb)
        sim.reset()
        r = sim.run(alt.programs,
                    members=[PipelineMember(0, n_stages - 1, f"{n_stages}stg")])
        print(f"  stages={n_stages}: {r.members[0].throughput_fps(warmup=1):8.1f} "
              f"microbatches/s measured (deadlock={r.deadlocked})")

    # 4b. At TPU scale: the same trade-off, analytically.
    print("\nanalytic deployment sweep (same mesh, new instruction programs):")
    chips = 256
    for n_stages in (1, 2, 4, 8):
        dp = chips // n_stages
        t = layer_cost_seconds(get_config(args.arch), 4096, 4, 1)
        full = get_config(args.arch)
        per_stage = -(-full.num_layers // n_stages) * t
        thr = dp / per_stage  # dp replicas x pipeline rate
        lat = (n_stages + args.microbatches - 1) * per_stage
        print(f"  stages={n_stages:2d} dp={dp:3d}: throughput {thr:9.1f} mb/s, "
              f"latency {lat*1e3:6.2f} ms")


if __name__ == "__main__":
    main()
