"""A configuration's family module is found by the program's
``ModelConfig.family``, as ``families/<family>.py``: a new family is new
files only.  Shown with a toy ``moe`` family written to a scratch
directory, for a file whose ``program_config`` is the program's MoE
configuration; the ``dense`` family is the functions the pinned tests
pin."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chip import costs, harness, reference, smoke, weights

CONFIGS = Path(__file__).resolve().parent / "configs"
TOY = '''"""A toy family: records which of its functions the harness called."""
import jax

from .. import costs as shared      # the shared pieces, by relative import

CALLS = []


def program_params(cfg, seed, model):
    CALLS.append("program_params")
    return model.init(jax.random.PRNGKey(seed % 2 ** 31))


def reference_layer(cfg, key, l, h):
    CALLS.append("reference_layer")     # while the reference traces it
    return h


def prefill_chunk_flops(cfg, start, valid):
    CALLS.append("prefill_chunk_flops")
    return 1000.0 * valid


def decode_step_flops(cfg, lengths):
    CALLS.append("decode_step_flops")
    return 10.0 * sum(lengths)


def decode_attn_bytes(cfg, lengths):
    return 0.0


decode_attn_flops = decode_attn_bytes


def decode_qmm_calls(cfg):
    return []


prefill_qmm_calls = decode_qmm_calls


def kv_bytes_per_token(cfg):
    return 0.0


qmm_least_seconds = shared.qmm_least_seconds
'''


def moe_config() -> dict:
    """The smoke cell's file, served as the program's MoE configuration,
    with expert keys of its own."""
    cfg = smoke.config()
    cfg.update(name="toy-moe", program_config="moonshot-v1-16b-a3b",
               num_experts=4, experts_per_token=2, moe_interleave=1,
               capacity_factor=2.0)
    return cfg


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "moe.py").write_text(TOY)
    monkeypatch.setattr(harness, "FAMILIES", tmp_path)
    mod = harness.family(moe_config())
    mod.CALLS.clear()
    return mod


def test_program_config_passes_every_model_field():
    pcfg = harness.program_config(moe_config())
    assert pcfg.family == "moe" and pcfg.name == "moonshot-v1-16b-a3b"
    assert (pcfg.num_experts, pcfg.experts_per_token, pcfg.moe_interleave,
            pcfg.capacity_factor) == (4, 2, 1, 2.0)
    assert (pcfg.num_layers, pcfg.d_model, pcfg.num_heads, pcfg.hd,
            pcfg.vocab_size) == (2, 64, 4, 16, 251)
    dense = harness.program_config(smoke.config())
    assert dense.family == "dense" and dense.name == "glm4-9b"
    with pytest.raises(ValueError, match="head_dim"):   # 0: d_model / heads
        harness.program_config(dict(smoke.config(), head_dim=0))


def test_build_judge_and_context_reach_the_family_by_name(toy):
    cfg = moe_config()
    model, sparams = harness.build(cfg, 2**31 + 3)
    assert toy.CALLS == ["program_params"] and model.cfg.num_experts == 4

    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 251, 20), list(rng.integers(0, 251, 6)))]
    stats = harness.judge(cfg, 2**31 + 3, samples)
    assert "reference_layer" in toy.CALLS and stats["mean_gap"] >= 0

    recorder = SimpleNamespace(
        names={"prefill": "pre", "decode": "step"},
        between=lambda lo, hi: ([(0, 16), (16, 4)], [[5, 9], [6]]))
    before = {"counts": {"decode_steps": 1}, "obs": {"serve.decode_steps": 1.0}}
    after = {"counts": {"decode_steps": 4},
             "obs": {"serve.decode_steps": 4.0, "serve.first_tokens": 2.0}}
    ctx = harness._context(cfg, {}, SimpleNamespace(device_kind="TPU v5 lite"),
                           recorder, 0.0, 2.0, (0.5, 1.5), {}, before, after)
    assert ctx.costs is toy and ctx.window_flops == 1000.0 * 20 + 10.0 * 20
    assert ctx.counts == {"decode_steps": 3}
    assert ctx.obs == {"serve.decode_steps": 3.0, "serve.first_tokens": 2.0}


def test_a_family_with_no_module_names_the_file():
    with pytest.raises(FileNotFoundError, match=r"families/moe\.py"):
        harness.family(moe_config())
    with pytest.raises(FileNotFoundError, match=r"families/moe\.py"):
        harness.build(moe_config(), 1)


def test_a_family_module_lacking_a_function_is_refused(tmp_path, monkeypatch):
    (tmp_path / "moe.py").write_text(TOY.replace("def kv_bytes_per_token",
                                                 "def kv_bytes_of_token"))
    monkeypatch.setattr(harness, "FAMILIES", tmp_path)
    with pytest.raises(AttributeError, match="kv_bytes_per_token"):
        harness.family(moe_config())


def test_dense_family_is_the_pinned_functions():
    cfg = json.loads((CONFIGS / "glm4-9b-w4kv8.json").read_text())
    dense = harness.family(cfg)
    assert harness.family(smoke.config()) is dense
    assert dense.program_params is weights.program_params
    assert dense.reference_layer is reference.layer
    for name in harness.FAMILY_API[2:]:
        assert getattr(dense, name) is getattr(costs, name), name
