"""The window's counter deltas (``ctx.obs``: every counter of the engine's
``repro.obs`` registry) and the two metrics that read them, on a fake
context and on the smoke engine's own counters."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chip import harness, smoke

CONFIGS = Path(__file__).resolve().parent / "configs"


def read(name, obs, cfg=None):
    return harness.metric_reader(name)(SimpleNamespace(obs=obs, cfg=cfg))


def test_attn_live_share_over_every_block_slot():
    cfg = json.loads((CONFIGS / "glm4-9b-w4kv8.json").read_text())
    obs = {"serve.attn_live_pages": 1237118.0, "serve.decode_steps": 532.0}
    # 32 rows x 2048 / 16 = 128 block slots a row
    assert read("attn_live_share", obs, cfg) == 100.0 * 1237118 / (532 * 32 * 128)
    assert read("attn_live_share", dict(obs, **{"serve.decode_steps": 0.0}), cfg) is None
    assert read("attn_live_share", {"serve.decode_steps": 5.0}, cfg) is None


def test_first_token_host_ms_per_admission():
    obs = {"serve.first_token_host_seconds": 1.2, "serve.first_tokens": 80.0}
    assert read("first_token_host_ms", obs) == pytest.approx(15.0, rel=1e-12)
    assert read("first_token_host_ms", dict(obs, **{"serve.first_tokens": 0.0})) is None
    assert read("first_token_host_ms", {}) is None


def test_counter_deltas_of_a_served_window():
    cfg = smoke.config()
    model, sparams = harness.build(cfg, 2**31 + 21)
    recorder = harness.Recorder(*harness._program_fns(model))
    eng = harness.make_engine(model, sparams, cfg, recorder)
    rng = np.random.default_rng(4)
    eng.submit(rng.integers(0, cfg["vocab_size"], 30), 6)
    for _ in range(3):
        eng.step()
    before = harness._counters(eng)
    for n in (20, 45):
        eng.submit(rng.integers(0, cfg["vocab_size"], n), 5)
    eng.run_until_drained()
    after = harness._counters(eng)
    obs = harness._delta(before["obs"], after["obs"])
    counts = harness._delta(before["counts"], after["counts"])
    assert obs["serve.decode_steps"] == counts["decode_steps"] > 0
    assert obs["serve.first_tokens"] == 2
    assert obs["pipeline.bubbles"] == counts["bubbles"]
    assert set(after["obs"]) >= {"serve.attn_live_pages", "serve.tokens_total",
                                 "serve.first_token_host_seconds"}
    share = read("attn_live_share", obs, cfg)
    assert 0 < share <= 100
    assert read("first_token_host_ms", obs) > 0
