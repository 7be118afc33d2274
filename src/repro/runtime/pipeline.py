"""Pipeline-parallel inference executor — the paper's instruction-based
multi-PU coordination, adapted to TPU.

The compiler side mirrors Sec. IV: an analytic per-layer profile feeds the
same DP partitioner used for the FPGA (contiguous layer ranges -> stages,
minimizing the max stage time), and the coordination pattern is *emitted as
instruction programs* (LD: WAIT_REQ/SEND_ACK, CP: compute, ST:
WAIT_ACK/SEND_REQ with BID ping-pong) that execute on the discrete-event
simulator for schedule verification. The TPU lowering realizes the same
dependency structure as static dataflow: one jax.lax.scan over schedule
ticks inside shard_map, with lax.ppermute boundary transfers along the
"stage" mesh axis and the double-buffered carry playing the role of the
B0/B1 BID ping-pong.

Runtime strategy switching without reconfiguration (the paper's headline
feature): the same weights + mesh serve any (n_stages x data replicas)
deployment — changing strategy = swapping the compiled instruction schedule
(a re-jit), never re-provisioning the cluster.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.isa import Compute, Group, Opcode, Sync
from ..core.program import Program, PUProgram
from ..models import transformer as tf
from ..models.layers import embed, rmsnorm, unembed

# ---------------------------------------------------------- analytic costs --
PEAK_FLOPS = 197e12
HBM_BW = 819e9


def layer_cost_seconds(cfg: ArchConfig, seq_len: int, batch: int, chips: int = 1) -> float:
    """Roofline max(compute, memory) for one transformer layer."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.resolved_head_dim
    H, G = cfg.num_heads, cfg.num_kv_heads
    tokens = seq_len * batch
    gate = 2 if cfg.mlp in ("swiglu", "geglu") else 1
    mlp_flops = 2 * tokens * d * f * (gate + 1)
    attn_proj = 2 * tokens * d * hd * (H + 2 * G) + 2 * tokens * H * hd * d
    attn_scores = 4 * tokens * min(seq_len, cfg.window if cfg.attn == "swa" else seq_len) * H * hd
    if cfg.family == "moe":
        mlp_flops *= cfg.top_k
    flops = (mlp_flops + attn_proj + attn_scores) / chips
    w_bytes = 2 * (d * f * (gate + 1) * (cfg.n_experts or 1) + d * hd * (H + 2 * G) + H * hd * d) / chips
    act_bytes = 2 * tokens * d * 6 / chips
    return max(flops / PEAK_FLOPS, (w_bytes + act_bytes) / HBM_BW)


# ----------------------------------------------------------------- planner --
@dataclass
class PipelinePlan:
    cfg: ArchConfig
    n_stages: int
    microbatches: int
    layers_per_stage: int  # padded (uniform for SPMD execution)
    boundaries: list[int]  # DP-optimal contiguous layer ranges
    stage_time_s: float  # analytic steady-state stage time
    programs: list[PUProgram] = field(default_factory=list)

    @property
    def predicted_throughput(self) -> float:
        return 1.0 / self.stage_time_s if self.stage_time_s else 0.0

    @property
    def predicted_latency(self) -> float:
        return (self.n_stages + self.microbatches - 1) * self.stage_time_s


def plan_pipeline(cfg: ArchConfig, *, n_stages: int, microbatches: int,
                  seq_len: int, microbatch_size: int,
                  chips_per_stage: int = 1) -> PipelinePlan:
    """DP-partition the layer stack into contiguous stages (Sec. IV-B with a
    homogeneous PU pool; heterogeneous stage widths = chips_per_stage lists
    are supported by the underlying partitioner in repro.compiler)."""
    L = cfg.num_layers
    per = layer_cost_seconds(cfg, seq_len, microbatch_size, chips_per_stage)
    # uniform layers => optimal contiguous cut is the balanced one
    base = L // n_stages
    extra = L % n_stages
    boundaries, acc = [0], 0
    for s in range(n_stages):
        acc += base + (1 if s < extra else 0)
        boundaries.append(acc)
    lps = math.ceil(L / n_stages)
    stage_time = lps * per
    plan = PipelinePlan(
        cfg=cfg,
        n_stages=n_stages,
        microbatches=microbatches,
        layers_per_stage=lps,
        boundaries=boundaries,
        stage_time_s=stage_time,
    )
    plan.programs = emit_stage_programs(plan)
    return plan


def emit_stage_programs(plan: PipelinePlan) -> list[PUProgram]:
    """The coordination pattern as ISA instruction programs (one PU per
    stage): verifiable on the discrete-event simulator, and the ground truth
    the shard_map lowering must realize."""
    from ..core.isa import AddrCyc, DataMove

    progs = []
    S, M = plan.n_stages, plan.microbatches
    cfg = plan.cfg
    mb_bytes = 64 * 1024  # symbolic microbatch activation footprint
    region = lambda s: 0x100_0000 * (s + 1)  # boundary tensor base per edge

    for s in range(S):
        first, last = s == 0, s == S - 1
        n_layers = plan.boundaries[s + 1] - plan.boundaries[s]

        ld_ops: list = []
        if not first:
            ld_ops.append(Sync(op=Opcode.WAIT_REQ, pid=s - 1, bid=0, base_bid=0, nc=1, ic=1))
        ld_ops += [
            DataMove(op=Opcode.LINEAR_ADM, cur_ba=region(s), length=mb_bytes, channel=(2 * s) % 32),
            AddrCyc(ba=region(s), aoffs=mb_bytes, nc=1, ic=1),
        ]
        if not first:
            ld_ops.append(Sync(op=Opcode.SEND_ACK, pid=s - 1, bid=0, base_bid=0, nc=1, ic=1))

        # one aggregate GEMM per round (layer count folds into n)
        cp_ops = [
            Compute(
                m=min(cfg.d_model, 4095),
                n=min(1024 * max(1, n_layers), 65535),
                k=min(cfg.d_ff, 16383),
            )
        ]

        st_ops: list = []
        if not last:
            st_ops.append(Sync(op=Opcode.WAIT_ACK, pid=s + 1, bid=0, base_bid=0, nc=1, ic=1))
        st_ops += [
            DataMove(op=Opcode.LINEAR_ADM, cur_ba=region(s + 1), length=mb_bytes, channel=(2 * s + 1) % 32),
            AddrCyc(ba=region(s + 1), aoffs=mb_bytes, nc=1, ic=1),
        ]
        if not last:
            st_ops.append(Sync(op=Opcode.SEND_REQ, pid=s + 1, bid=0, base_bid=0, nc=1, ic=1))

        # ACK-bypass prologue: this stage pre-authorizes its upstream
        # producer's two boundary buffers (Fig. 3 pattern).
        prologue = (
            [Sync(op=Opcode.SEND_ACK, pid=s - 1, bid=b, nc=0) for b in (0, 1)]
            if not first
            else []
        )
        ld = Program.assemble(Group.LD, prologue + ld_ops, rounds=M,
                              loop_ba=len(prologue), name=f"stage{s}.LD")
        cp = Program.assemble(Group.CP, cp_ops, rounds=M, name=f"stage{s}.CP")
        st = Program.assemble(Group.ST, st_ops, rounds=M, name=f"stage{s}.ST")
        progs.append(PUProgram(s, ld, cp, st, label=f"stage{s}"))
    return progs


# ---------------------------------------------------------------- executor --
def make_pipeline_mesh(n_stages: int, n_data: int = 1, n_model: int = 1):
    # Auto axes: the executor slices the last stage's logits out of a
    # stage-sharded array, which explicit-axis sharding rejects
    return jax.make_mesh((n_stages, n_data, n_model), ("stage", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def stack_stage_params(cfg: ArchConfig, params: dict, plan: PipelinePlan) -> dict:
    """Restack per-layer params (L, ...) -> (S, layers_per_stage, ...) with
    zero padding for ragged final stages (padded layers are skipped by the
    validity mask in the stage body)."""
    blocks = params["blocks"]
    assert len(blocks) == 1, "pipeline executor supports uniform-stack archs"
    stacked = blocks[0]
    S, lps = plan.n_stages, plan.layers_per_stage

    def restack(x):
        L = x.shape[0]
        pad = S * lps - L
        xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return xp.reshape(S, lps, *x.shape[1:])

    out = dict(params)
    out["blocks"] = [jax.tree.map(restack, stacked)]
    return out


def make_pipeline_forward(cfg: ArchConfig, plan: PipelinePlan, mesh: Mesh):
    """Pipelined forward: (stage-stacked params, tokens (M, mb, s)) -> logits.

    SPMD over the "stage" axis; each tick every stage runs its layer block
    and ppermutes its activation to the next stage (BID ping-pong == the
    scan carry's double buffer). M microbatches drain in M + S - 1 ticks."""
    S, M, lps = plan.n_stages, plan.microbatches, plan.layers_per_stage
    L = cfg.num_layers

    def stage_body(params, x, stage_id):
        """Run this stage's layers on x (mb, s, d)."""
        layer_base = stage_id * lps

        def body(h, inp):
            li, p = inp
            valid = (layer_base + li) < L
            h_new, _ = tf._layer_forward(cfg, "dense", cfg.attn == "swa", p, h)
            h = jnp.where(valid, h_new, h)
            return h, None

        bparams = params["blocks"][0]
        x, _ = jax.lax.scan(body, x, (jnp.arange(lps), bparams))
        return x

    def _is_block_path(path) -> bool:
        return any(str(getattr(k, "key", "")) == "blocks" for k in path)

    def fn(params, tokens):
        # params: stage-stacked; tokens: (M, mb, s)
        def shard_fn(params_s, tokens_s):
            # block params arrive as (1, lps, ...) stage slices; embeddings /
            # head / norms are replicated across stages
            params_local = jax.tree_util.tree_map_with_path(
                lambda p, x: x[0] if _is_block_path(p) else x,
                params_s,
            )
            stage_id = jax.lax.axis_index("stage")
            mb, s = tokens_s.shape[1], tokens_s.shape[2]
            d = cfg.d_model
            dtype = params_local["embed"].dtype

            n_ticks = M + S - 1
            carry_in = jnp.zeros((mb, s, d), dtype)
            outputs = jnp.zeros((M, mb, s, cfg.vocab_size), jnp.float32)

            def tick(state, t):
                carry, outs = state
                mb_idx = jnp.clip(t, 0, M - 1)
                x_first = embed(params_local["embed"], tokens_s[mb_idx])
                x = jnp.where(stage_id == 0, x_first, carry)
                h = stage_body(params_local, x, stage_id)
                # emit logits at the last stage for valid ticks
                out_idx = jnp.clip(t - (S - 1), 0, M - 1)
                hn = rmsnorm(h, params_local["final_norm"], cfg.norm_eps)
                logits = unembed(
                    params_local["embed"] if cfg.tie_embeddings else params_local["lm_head"],
                    hn, tied=cfg.tie_embeddings,
                ).astype(jnp.float32)
                emit = (stage_id == S - 1) & (t >= S - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(emit, logits, outs[out_idx]), out_idx, 0
                )
                # boundary transfer: stage i -> i+1 (the SEND_REQ/WAIT_REQ pair)
                nxt = jax.lax.ppermute(
                    h, "stage", [(i, (i + 1) % S) for i in range(S)]
                )
                return (nxt, outs), None

            (carry, outputs), _ = jax.lax.scan(
                tick, (carry_in, outputs), jnp.arange(n_ticks)
            )
            return outputs[None]  # re-add stage dim for the out_spec

        pspec_params = jax.tree_util.tree_map_with_path(
            lambda p, _: P("stage") if _is_block_path(p) else P(), params
        )
        out = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(pspec_params, P()),
            out_specs=P("stage"),
            check_vma=False,
        )(params, tokens)
        # logits live on the last stage; slice it out
        return out[-1]

    return fn
