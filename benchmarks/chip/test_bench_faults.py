"""A whole run on the CPU at a small size, with the chip's look skipped:
sound, it comes out ``correct``; with the timed path broken underneath in
each way a one-chip serving cell can break, it does not: a token altered
where the sampler produces it, a decode step that returns its KV state
unchanged, and a decode step that computes half of its rows."""
import time

import jax
import pytest

from chip import harness, smoke

LIMITS = {"mean_gap": {"limit": 0.05}}
CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
               "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def checkout(tmp_path):
    """A run's compilation cache goes to a scratch checkout, and the
    process's cache settings are put back afterwards."""
    was = {f: getattr(jax.config, f) for f in CACHE_FLAGS}
    yield tmp_path
    for f, v in was.items():
        jax.config.update(f, v)


def _run(traffic, seed, root):
    """A run of the ``glm4-chat`` cell, cut to the smoke size, under the
    mix ``traffic`` (cut likewise)."""
    return harness.run("glm4-chat", seed, 2.0, False, t_start=time.perf_counter(),
                       root=root, bench=harness.load_benchmark(),
                       require_chip=False, cfg=smoke.config(),
                       mix_spec=smoke.mix(traffic), limits=LIMITS,
                       log=lambda *_: None)


@pytest.mark.parametrize("traffic", ["chat-closed32", "docqa-closed32"])
def test_sound_run_is_correct(traffic, checkout):
    r = _run(traffic, 2**31 + 7, checkout)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert set(r["metrics"]) == {m["name"] for m in
                                 harness.load_benchmark()["end_to_end"]}
    assert r["device"]["platform"] == "cpu"


def test_altered_token_is_not_correct(monkeypatch, checkout):
    from repro.serve import engine

    real = engine.sample_rows

    def off_by_one(logits, *args):
        return (real(logits, *args) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_rows", off_by_one)
    r = _run("chat-closed32", 5, checkout)
    assert not r["correct"]
    assert r["checks"]["mean_gap"]["value"] > LIMITS["mean_gap"]["limit"]


def _unchanged_state(real):
    def dec(sparams, cache, tokens):
        kept = jax.tree.map(jax.numpy.copy, cache)   # the call donates cache
        logits, _ = real(sparams, cache, tokens)
        return logits, kept
    return dec


def _half_batch(real):
    def dec(sparams, cache, tokens):
        logits, cache = real(sparams, cache, tokens)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), cache
    return dec


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_broken_decode_step_is_not_correct(fault, monkeypatch, checkout):
    real_fns = harness._program_fns

    def broken(model):
        prefill, decode = real_fns(model)
        dec = fault(decode)
        dec.__name__ = decode.__name__
        dec._cache_size = decode._cache_size
        return prefill, dec

    monkeypatch.setattr(harness, "_program_fns", broken)
    r = _run("chat-closed32", 9, checkout)
    assert not r["correct"]
    assert r["checks"]["mean_gap"]["value"] > LIMITS["mean_gap"]["limit"]
