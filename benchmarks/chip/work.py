"""The operations and bytes that each call needs, from shapes and live
lengths: what the algorithm needs, not what the compiled program does.

A change that adds wasted work leaves these counts as they are, so the
shares computed from them fall. Attention is counted causal (half of the
dense score matrix). Decode counts the weights once per call, the live K/V
rows of the one sequence the call advances, and the row it writes.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ------------------------------------------------------------------ counts --
def layer_params(m: dict) -> int:
    """Parameters of one dense GQA decoder layer: projections, MLP, norms."""
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, G = m["num_heads"], m["num_kv_heads"]
    attn = d * hd * (H + 2 * G) + H * hd * d
    mlp = d * f * (3 if m["mlp"] in ("swiglu", "geglu") else 2)
    norms = 2 * d + (2 * hd if m.get("qk_norm") else 0)
    return attn + mlp + norms


def embed_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"] * (1 if m["tie_embeddings"] else 2)


def param_count(m: dict) -> int:
    return m["num_layers"] * layer_params(m) + embed_params(m) + m["d_model"]


def matmul_params_per_token(m: dict) -> int:
    """Weights each token multiplies through: every layer's projections and
    MLP, and the unembedding (the embedding lookup is a gather)."""
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, G = m["num_heads"], m["num_kv_heads"]
    per_layer = d * hd * (H + 2 * G) + H * hd * d + d * f * (3 if m["mlp"] in ("swiglu", "geglu") else 2)
    return m["num_layers"] * per_layer + m["vocab_size"] * d


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * itemsize


def attention_flops(s: int, t: int, heads: int, head_dim: int, *, causal: bool) -> float:
    """QK^T and PV for s queries over t keys; causal keeps half the pairs."""
    dense = 4.0 * s * t * heads * head_dim
    return dense / 2 if causal else dense


def decode_call(m: dict, pos: int, itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) one decode call needs to advance one sequence whose
    new token sits at ``pos``: every weight read once, the ``pos + 1`` live
    K/V rows read, and one row written."""
    ctx = pos + 1
    flops = 2.0 * matmul_params_per_token(m) + m["num_layers"] * attention_flops(
        1, ctx, m["num_heads"], m["head_dim"], causal=False)
    row = kv_bytes_per_token(m, itemsize)
    nbytes = itemsize * param_count(m) + row * ctx + row
    return flops, nbytes
