"""The benchmark's operation and byte counts against hand totals, and its
table of chip peaks."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import run as bench
from benchmarks.chip import work

CONFIGS = Path(bench.HERE) / "configs"


def model(name):
    return bench.model_numbers(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen3_parameters_and_kv_bytes():
    m = model("qwen3-0.6b")
    assert work.param_count(m) == pytest.approx(0.596e9, rel=1e-3)
    assert work.kv_bytes_per_token(m) == 114_688  # 28 layers x 2 x 8 heads x 128 x 2 bytes


def test_starcoder2_layer_parameters():
    # bigcode/starcoder2-15b: 40 layers, d 6144, 48/4 heads of 128, FFN 24576
    # (two matrices), vocabulary 49152 untied
    m = {"num_layers": 40, "d_model": 6144, "num_heads": 48, "num_kv_heads": 4,
         "head_dim": 128, "d_ff": 24576, "mlp": "gelu", "vocab_size": 49152,
         "tie_embeddings": False}
    assert work.layer_params(m) == pytest.approx(383.8e6, rel=1e-3)
    assert work.param_count(m) == pytest.approx(15.96e9, rel=1e-3)


@pytest.mark.parametrize("s", [1, 128, 2048])
def test_causal_attention_is_half_the_dense(s):
    dense = work.attention_flops(s, s, 48, 128, causal=False)
    assert work.attention_flops(s, s, 48, 128, causal=True) == dense / 2
    assert dense == 4 * s * s * 48 * 128


def test_decode_call_reads_weights_once_and_live_rows():
    m = model("qwen3-0.6b")
    f0, b0 = work.decode_call(m, 0)
    f9, b9 = work.decode_call(m, 9)
    assert b9 - b0 == 9 * work.kv_bytes_per_token(m)
    assert b0 == 2 * work.param_count(m) + 2 * work.kv_bytes_per_token(m)
    assert f9 - f0 == 28 * work.attention_flops(1, 9, 16, 128, causal=False)


def test_peaks_v5e_and_unknown_device_raises():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_roofline_names_its_bound():
    p = work.peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, p) == (1.0, "compute")
    assert work.roofline_seconds(1.0, 819e9, p) == (1.0, "memory")
