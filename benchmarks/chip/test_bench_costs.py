"""``costs.py`` against counts made by hand at the cells' widths."""
import json
from pathlib import Path

import pytest

from chip import costs, peaks

CONFIGS = Path(__file__).resolve().parent / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_glm4_counts():
    c = cfg("glm4-9b-w4kv8")
    # per layer: q 4096x4096, k and v 4096x256, o 4096x4096, three MLP 4096x13696
    per_layer = 4096 * 4096 * 2 + 4096 * 256 * 2 + 3 * 4096 * 13696
    assert costs.linear_flops_per_token(c) == 2 * per_layer * 8
    assert costs.head_flops(c) == 2 * 4096 * 151552
    # int8 K and V codes of 2 heads x 128 plus a f32 scale per head: 4.2 KB
    # per token over the 8 layers
    assert costs.kv_bytes_per_token(c) * 8 == 8 * 2 * 2 * (128 + 4) == 4224
    planes = sum(lin.bits * lin.K // 8 * lin.N for _, lin in costs.decode_qmm_calls(c))
    assert planes == 8 * per_layer // 2 + 4096 * 151552      # 0.82 GB + 0.62 GB
    q = costs.Linear("wq", 4096, 4096, 4)
    assert costs.qmm_bytes(32, q) == 4 * 512 * 4096 + 4 * 4096 + 2 * 32 * 4096 + 4 * 32 * 4096
    assert costs.qmm_flops(32, q) == 2 * 32 * 4096 * 4096
    # the head runs on the last valid row of a prefill chunk only
    pre = costs.prefill_qmm_calls(c)
    assert len(pre) == 7 * 8 + 1 and pre[-1][0] == 1 and pre[0][0] == 128


def test_phi3_counts():
    c = cfg("phi3-mini-w4kv8")
    # MHA: 32 KV heads of 96, int8 codes + f32 scales: 6.4 KB per token per layer
    assert costs.kv_bytes_per_token(c) == 2 * 32 * (96 + 4) == 6400
    lengths = [1700] * 32
    assert costs.decode_attn_bytes(c, lengths) == 8 * (32 * 1700 * 6400 + 2 * 2 * 32 * 32 * 96)
    assert costs.decode_attn_flops(c, lengths) == 4 * 32 * 96 * 8 * 32 * 1700


def test_prefill_chunk_flops_sum_each_tokens_prefix():
    c = cfg("phi3-mini-w4kv8")
    start, valid = 1536, 77
    attn = sum(4 * 32 * 96 * 8 * (start + i + 1) for i in range(valid))
    want = valid * costs.linear_flops_per_token(c) + costs.head_flops(c) + attn
    assert costs.prefill_chunk_flops(c, start, valid) == pytest.approx(want, rel=1e-12)


def test_least_time_takes_the_larger_bound():
    p = peaks.peak_for("TPU v5 lite")
    lin = costs.Linear("wg", 4096, 13696, 4)
    assert costs.qmm_least_seconds(32, lin, p) == costs.qmm_bytes(32, lin) / p.hbm_bytes_s
    assert costs.qmm_least_seconds(4096, lin, p) == costs.qmm_flops(4096, lin) / p.bf16_flops


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
