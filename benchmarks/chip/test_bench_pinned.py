"""The dense model's numbers, pinned: the weights a seed makes, every cost
function at ``glm4-chat``'s widths, and the reference's logits at a small
size.  Moving code between the benchmark's modules leaves them as they
are; a change to any of them is a change to the yardstick."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chip import costs, harness, peaks, reference, smoke, weights

CONFIGS = Path(__file__).resolve().parent / "configs"
SEED = 2**33 + 5
CHUNKS = [(0, 128), (128, 128), (1536, 77), (1920, 1)]     # (start, valid)
LENGTHS = [1, 17, 129, 1020, 1664, 2048]                   # a decode's rows

# sha256 (first 16 hex digits) of each leaf's bytes at the smoke size
LEAVES = {
    "['blocks'][0]['attn']['wk']": "6e350f81de7d246e",
    "['blocks'][0]['attn']['wo']": "34551f033a6827c8",
    "['blocks'][0]['attn']['wq']": "0c29379d4bc6217b",
    "['blocks'][0]['attn']['wv']": "75ba8e1bad3aa178",
    "['blocks'][0]['ln1']": "14981270bd3bb141",
    "['blocks'][0]['ln2']": "151874cfa27b4d12",
    "['blocks'][0]['mlp']['wd']": "dcb1003c0bf9adf8",
    "['blocks'][0]['mlp']['wg']": "a8eeecbb1e654db0",
    "['blocks'][0]['mlp']['wu']": "93d45e0f623c98a5",
    "['embed']": "db653176808dfb7f",
    "['final_norm']": "72ece9c3efe45873",
    "['lm_head']": "626af30023cca84e",
}


def glm4():
    return json.loads((CONFIGS / "glm4-9b-w4kv8.json").read_text())


def test_dense_weights_are_pinned():
    from repro.models import build_model

    cfg = smoke.config()
    model = build_model(harness.program_config(cfg))
    params = weights.program_params(cfg, SEED, model)
    got = {jax.tree_util.keystr(path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == LEAVES


def test_dense_costs_are_pinned():
    c = glm4()
    assert [costs.prefill_chunk_flops(c, s, v) for s, v in CHUNKS] == [
        420009213952.0, 422156697600.0, 268401246208.0, 4756471808.0]
    assert costs.decode_step_flops(c, LENGTHS) == 27667595264.0
    assert costs.decode_attn_bytes(c, LENGTHS) == 24803200.0
    assert costs.decode_attn_flops(c, LENGTHS) == 639500288.0
    assert costs.kv_bytes_per_token(c) == 528.0
    peak = peaks.peak_for("TPU v5 lite")
    layer = [("wd", 13696, 4096, 4), ("wg", 4096, 13696, 4),
             ("wk", 4096, 256, 4), ("wo", 4096, 4096, 4),
             ("wq", 4096, 4096, 4), ("wu", 4096, 13696, 4),
             ("wv", 4096, 256, 4)]
    for calls, rows, head_rows, least in (
            (costs.decode_qmm_calls(c), 32, 32, 0.0018545227643467644),
            (costs.prefill_qmm_calls(c), 128, 1, 0.002891172993898465)):
        assert len(calls) == 57
        assert sorted({(m, lin.name, lin.K, lin.N, lin.bits) for m, lin in calls}) == (
            [(head_rows, "lm_head", 4096, 151552, 8)] + [(rows,) + x for x in layer])
        assert sum(costs.qmm_least_seconds(m, lin, peak) for m, lin in calls) == least


def test_reference_logits_are_pinned():
    cfg = smoke.config()
    toks = [203, 21, 45, 59, 45, 201, 218, 146, 9, 23, 83, 108]
    lg = reference.logits(cfg, SEED, toks)
    assert lg.shape == (12, 251)
    # float32 matmuls: the CPU's summation order may move the last bits
    assert lg[[0, 5, 11], [3, 100, 250]] == pytest.approx(
        [-0.01134947407990694, -0.8322725296020508, 1.0161513090133667], rel=1e-5)
    assert float(np.abs(lg).sum()) == pytest.approx(2302.841552734375, rel=1e-6)
