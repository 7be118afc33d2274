"""Per-architecture sharding policy over the production mesh.

Mesh axes: ("data", "model") single-pod 16x16, ("pod", "data", "model")
multi-pod 2x16x16. The "pod" axis is pure data parallelism; "data" carries
batch (plus FSDP weight sharding for the largest models); "model" carries
tensor parallelism.

Placement rules (chosen per arch by divisibility and size — DESIGN.md §5):
  * q-heads sharded on "model" when H % model_size == 0 ("heads" mode),
    otherwise row-parallel d_model contraction ("dmodel" mode, e.g. gemma3
    with H=8 < 16);
  * GQA k/v projections replicate when G < model_size (they are small);
    decode KV caches shard on head_dim when divisible, else on sequence;
  * MLP hidden / MoE d_ff / vocab dims shard on "model";
  * FSDP: when bf16 params / model_size exceed ~4 GB/chip, weight tensors
    additionally shard their d_model/vocab dim over "data" (grok-1, dbrx,
    internvl2);
  * SSM heads (mamba/rwkv) shard on "model" via activation constraints.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig

FSDP_THRESHOLD_BYTES = 4 << 30  # per-chip bf16 param budget before FSDP


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclass
class ShardingPolicy:
    cfg: ArchConfig
    mesh: Mesh
    batch_axes: tuple  # ("data",) or ("pod", "data")
    attn_mode: str  # "heads" | "dmodel"
    fsdp: bool
    model_size: int

    # ---------------------------------------------------------------- specs --
    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """Spec for one parameter. Per-layer stacks under ``blocks/`` carry a
        leading layer dim: compute the spec on the unstacked shape, then
        prepend a replicated axis."""
        if "blocks/" in path and len(shape) >= 1:
            base = self._param_spec_base(path, shape[1:])
            return P(None, *base)
        return self._param_spec_base(path, shape)

    def _param_spec_base(self, path: str, shape: tuple[int, ...]) -> P:
        cfg, M = self.cfg, self.model_size
        fsdp_ax = "data" if self.fsdp else None

        def fs(dim_size):  # fsdp axis only if divisible
            return fsdp_ax if fsdp_ax and dim_size % self._data_size == 0 else None

        if path.endswith("embed"):
            return P("model", fs(shape[-1]))
        if path.endswith("lm_head"):
            return P(fs(shape[0]), "model")
        if path.endswith("patch_proj"):
            return P(None, "model")
        if re.search(r"attn/wq$", path):
            H = shape[-2]
            if self.attn_mode == "heads" and H % M == 0:
                return P(fs(shape[0]), "model", None)
            return P("model", None, None)  # row-parallel
        if re.search(r"attn/w[kv]$", path):
            G = shape[-2]
            if self.attn_mode == "heads" and G % M == 0:
                return P(fs(shape[0]), "model", None)
            if self.attn_mode == "heads":
                return P(fs(shape[0]), None, None)  # small: replicate on model
            return P("model", None, None)
        if re.search(r"attn/wo$", path):
            H = shape[0]
            if self.attn_mode == "heads" and H % M == 0:
                return P("model", None, fs(shape[-1]))
            return P(None, None, "model")
        if re.search(r"(q_norm|k_norm)$", path):
            return P(None)
        if re.search(r"moe/router$", path):
            return P(None, None)
        if re.search(r"moe/w_(in|gate)$", path):
            return P(None, fs(shape[-2]), "model")  # TP over d_ff + FSDP over d
        if re.search(r"moe/w_out$", path):
            return P(None, "model", fs(shape[-1]))
        if re.search(r"mlp/w_(in|gate)$", path) or path.endswith("cm_Wk"):
            return P(fs(shape[-2]), "model")
        if re.search(r"mlp/w_out$", path) or path.endswith("cm_Wv"):
            return P("model", fs(shape[-1]))
        if re.search(r"mamba/w_in$", path):
            return P("model", None)  # row-parallel into the SSD block
        if re.search(r"mamba/w_out$", path):
            return P(None, "model") if shape[-2] % M == 0 else P(None, None)
        if re.search(r"tm/W[rkvg]$", path) or path.endswith("cm_Wr"):
            # column-parallel: output d-sharded == wkv-head-sharded (64 heads
            # / 16 shards = 4 heads each), so the whole time-mix stays local
            # and only Wo's contraction all-reduces once per layer.
            return P(None, "model")
        if path.endswith("tm/Wo"):
            return P("model", None)  # row-parallel: consumes d-sharded y*g
        if re.search(r"tm/(A_mix|A_w|B_mix|B_w)$", path):
            return P(*([None] * len(shape)))  # tiny LoRA mats: replicate
        # norms, biases, scalars, conv kernels, small vectors: replicated
        return P(*([None] * len(shape)))

    @property
    def _data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def params_sharding(self, params_shape: Any) -> Any:
        """Pytree of NamedSharding matching a params(-shaped) pytree."""

        def fn(path, leaf):
            spec = self.param_spec(_path_str(path), leaf.shape)
            # drop axes that do not divide evenly (safety net)
            spec = self._validate(spec, leaf.shape)
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(fn, params_shape)

    def _validate(self, spec: P, shape: tuple[int, ...]) -> P:
        fixed = []
        for i, ax in enumerate(spec):
            if ax is None:
                fixed.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([self.mesh.shape[a] for a in axes]))
            fixed.append(ax if i < len(shape) and shape[i] % n == 0 else None)
        return P(*fixed)

    # -------------------------------------------------------------- inputs --
    def batch_spec(self, ndim: int) -> P:
        return P(self.batch_axes, *([None] * (ndim - 1)))

    def inputs_sharding(self, tree: Any) -> Any:
        return jax.tree.map(
            lambda x: NamedSharding(self.mesh, self._validate(self.batch_spec(len(x.shape)), x.shape)),
            tree,
        )

    # --------------------------------------------------------------- cache --
    def cache_spec(self, path: str, shape: tuple[int, ...]) -> P:
        cfg, M = self.cfg, self.model_size
        leaf_name = path.rsplit("/", 1)[-1]
        if leaf_name in ("k", "v"):  # (L, b, t, G, hd)
            L, b, t, G, hd = shape
            # flash-decoding layout: shard the cache SEQUENCE over "model" —
            # decode then gathers the tiny q instead of the huge cache, and
            # softmax only all-reduces per-row stats. (hd-sharding forces an
            # all-gather of the whole cache per layer: measured 1000x worse.)
            if t % M == 0:
                return P(None, self.batch_axes, "model", None, None)
            if hd % M == 0:
                return P(None, self.batch_axes, None, None, "model")
            return P(None, self.batch_axes, None, None, None)
        if leaf_name in ("ssm", "wkv"):  # (L, b, H, N|P, P)
            H = shape[2]
            return P(None, self.batch_axes, "model" if H % M == 0 else None, None, None)
        # conv state / shift registers: batch only
        return P(None, self.batch_axes, *([None] * (len(shape) - 2)))

    def cache_sharding(self, cache_shape: Any) -> Any:
        def fn(path, leaf):
            spec = self._validate(self.cache_spec(_path_str(path), leaf.shape), leaf.shape)
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(fn, cache_shape)

    # ---------------------------------------------------- activation policy --
    def activation_specs(self) -> dict[str, P]:
        B = self.batch_axes
        # sequence parallelism on the residual stream: saved (remat) per-layer
        # activations shard over data x model — constrain() drops the "model"
        # axis automatically when seq doesn't divide (e.g. decode steps).
        # Exception: token-shift families (rwkv) read x[t-1], and XLA lowers
        # the shifted concat on a seq-sharded tensor as a full all-gather
        # per projection — residuals stay seq-replicated there.
        sp_ax = None if self.cfg.family == "ssm" else "model"
        specs = {
            "emb": P(B, sp_ax, None),
            "residual": P(B, sp_ax, None),
            "logits": P(B, None, "model"),
            "ffn_hidden": P(B, None, "model"),
            "moe_dispatch": P(B, None, None, None),
            "moe_expert_in": P(B, None, None, None),
            "moe_hidden": P(B, None, None, "model"),
            "moe_expert_out": P(B, None, None, "model"),
            "decode_scores": P(B, None, None, "model"),
        }
        if self.attn_mode == "heads":
            specs["attn_q"] = P(B, None, "model", None)
            specs["attn_out"] = P(B, None, "model", None)
            specs["attn_chunk"] = P(None, B, "model", None, None)
        if self.cfg.family in ("ssm", "hybrid"):
            H = self.cfg.ssm_heads if self.cfg.family == "hybrid" else self.cfg.d_model // self.cfg.ssm_head_dim
            if H % self.model_size == 0:
                specs["ssm_x"] = P(B, None, "model", None)
                specs["wkv_state"] = P(B, None, "model", None, None)
        return specs


def make_policy(cfg: ArchConfig, mesh: Mesh) -> ShardingPolicy:
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    model_size = mesh.shape["model"]
    attn_mode = "heads" if cfg.num_heads % model_size == 0 else "dmodel"
    params_bf16 = cfg.param_count() * 2
    fsdp = params_bf16 / model_size > FSDP_THRESHOLD_BYTES
    return ShardingPolicy(
        cfg=cfg,
        mesh=mesh,
        batch_axes=batch_axes,
        attn_mode=attn_mode,
        fsdp=fsdp,
        model_size=model_size,
    )
