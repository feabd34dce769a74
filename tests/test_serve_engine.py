"""Continuous-batching engine: slot invariants, mid-decode admission,
token-for-token parity with the legacy static greedy loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.quant.qat import policy_for
from repro.serve import SamplingParams, ServeEngine, SlotCachePool
from repro.train.serve import make_decode_step, make_prefill, quantize_for_serving

RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def served():
    """(model, sparams, shared jit fns) at a 4-bit policy — one compile
    budget for the whole module."""
    cfg = get_config("glm4-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    sparams = quantize_for_serving(model, params,
                                   policy_for(model, default_bits=4))
    fns = {"cache": "slot",  # legacy engine under test; paged: test_serve_paged.py
           "prefill_fn": make_prefill(model),
           "decode_fn": make_decode_step(model, donate=False)}
    return cfg, model, sparams, fns


def _prompt(cfg, n=8, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size))


def _static_loop(model, sparams, prompt, gen, max_len):
    """The legacy launch/serve.py greedy loop at batch=1."""
    logits, cache = model.prefill(sparams, tokens=jnp.asarray(prompt)[None],
                                  max_len=max_len)
    dec = make_decode_step(model, donate=False)
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    out = [int(tok[0, 0])]
    for _ in range(gen):
        logits, cache = dec(sparams, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        out.append(int(tok[0, 0]))
    return out


# --------------------------------------------------------------- slot pool
def test_slot_pool_alloc_free_invariants(served):
    _, model, _, _ = served
    pool = SlotCachePool(model, num_slots=3, max_len=16)
    slots = [pool.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2] and pool.num_free == 0
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.free(1)
    assert pool.num_free == 1 and pool.alloc() == 1  # lowest free reused
    with pytest.raises(ValueError):
        pool.free(7)          # never allocated
    pool.free(0)
    with pytest.raises(ValueError):
        pool.free(0)          # double free
    assert pool.active_slots == frozenset({1, 2})
    assert pool.occupancy() == pytest.approx(2 / 3)


def test_slot_pool_write_validates(served):
    _, model, _, _ = served
    pool = SlotCachePool(model, num_slots=2, max_len=16)
    good = model.init_cache(1, 16)
    with pytest.raises(ValueError):
        pool.write(0, good)   # slot not allocated
    slot = pool.alloc()
    with pytest.raises(ValueError):
        pool.write(slot, model.init_cache(1, 32))  # wrong cache length
    with pytest.raises(ValueError):
        pool.write(slot, model.init_cache(2, 16))  # wrong batch
    pool.write(slot, good)    # correct shapes accepted


# ------------------------------------------------------------------ parity
def test_single_request_matches_static_loop(served):
    cfg, model, sparams, fns = served
    prompt, gen = _prompt(cfg), 6
    want = _static_loop(model, sparams, prompt, gen, max_len=len(prompt) + gen + 1)
    eng = ServeEngine(model, sparams, num_slots=3,
                      max_len=len(prompt) + gen + 1, **fns)
    rid = eng.submit(prompt, max_new_tokens=gen + 1)
    eng.run_until_drained()
    assert eng.output(rid) == want


def test_single_request_parity_rwkv():
    """The slot pool is family-generic: same parity for the O(1)-state
    RWKV cache (no k/v leaves, no length bound)."""
    cfg = get_config("rwkv6-1.6b", smoke=True)
    model = build_model(cfg)
    sparams = quantize_for_serving(model, model.init(RNG),
                                   policy_for(model, default_bits=4))
    prompt, gen = _prompt(cfg, 6), 4
    want = _static_loop(model, sparams, prompt, gen, max_len=16)
    eng = ServeEngine(model, sparams, num_slots=2, max_len=16, cache="slot")
    rid = eng.submit(prompt, max_new_tokens=gen + 1)
    eng.run_until_drained()
    assert eng.output(rid) == want


# ------------------------------------------------------- continuous batching
def test_admission_mid_decode_preserves_running(served):
    cfg, model, sparams, fns = served
    p1, p2, p3 = _prompt(cfg, 8, 1), _prompt(cfg, 8, 2), _prompt(cfg, 8, 3)

    def solo(prompt, n):
        eng = ServeEngine(model, sparams, num_slots=2, max_len=32, **fns)
        rid = eng.submit(prompt, max_new_tokens=n)
        eng.run_until_drained()
        return eng.output(rid)

    eng = ServeEngine(model, sparams, num_slots=2, max_len=32, **fns)
    r1 = eng.submit(p1, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    # both slots get traffic while r1 is mid-decode; r3 must queue
    r2 = eng.submit(p2, max_new_tokens=4)
    r3 = eng.submit(p3, max_new_tokens=5)
    assert eng.num_running == 1 and eng.num_queued == 2
    eng.step()  # r2 takes the free slot, r3 keeps waiting
    assert eng.num_running == 2 and eng.num_queued == 1
    eng.run_until_drained()

    assert eng.output(r1) == solo(p1, 12)   # running seq not corrupted
    assert eng.output(r2) == solo(p2, 4)    # admitted seq clean slot
    assert eng.output(r3) == solo(p3, 5)    # queued seq reuses r2's slot
    m = {r["id"]: r for r in eng.metrics()["requests"]}
    assert m[r2]["ttft_steps"] == 0         # free slot -> admitted same step
    assert m[r3]["ttft_steps"] > 0          # had to wait for a slot


def test_queue_backpressure_and_length_bound(served):
    cfg, model, sparams, fns = served
    eng = ServeEngine(model, sparams, num_slots=1, max_len=16,
                      max_pending=2, **fns)
    with pytest.raises(ValueError):
        eng.submit(_prompt(cfg, 10), max_new_tokens=10)  # 20 > max_len 16
    eng.submit(_prompt(cfg, 4), max_new_tokens=3)
    eng.submit(_prompt(cfg, 4), max_new_tokens=3)
    with pytest.raises(RuntimeError):
        eng.submit(_prompt(cfg, 4), max_new_tokens=3)    # queue full
    eng.run_until_drained()
    assert all(r["state"] == "finished" for r in eng.metrics()["requests"])


def test_eos_frees_slot_early(served):
    cfg, model, sparams, fns = served
    prompt = _prompt(cfg)
    ref = _static_loop(model, sparams, prompt, 7, max_len=len(prompt) + 8)
    eos = ref[3]
    stop = ref.index(eos)  # ref may repeat tokens; EOS cuts at FIRST hit
    eng = ServeEngine(model, sparams, num_slots=2, max_len=32, **fns)
    rid = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    eng.run_until_drained()
    out = eng.output(rid)
    assert out == ref[:stop + 1] and out[-1] == eos
    assert eng.pool.num_free == 2             # slot released


def test_sampling_deterministic_per_seed(served):
    cfg, model, sparams, fns = served
    prompt = _prompt(cfg)

    def run(seed):
        eng = ServeEngine(model, sparams, num_slots=2, max_len=32, **fns)
        rid = eng.submit(prompt, max_new_tokens=6,
                         sampling=SamplingParams(temperature=1.0, seed=seed))
        eng.run_until_drained()
        return eng.output(rid)

    assert run(5) == run(5)


def test_metrics_aggregate(served):
    cfg, model, sparams, fns = served
    eng = ServeEngine(model, sparams, num_slots=2, max_len=32, **fns)
    for s in (1, 2, 3):
        eng.submit(_prompt(cfg, 8, s), max_new_tokens=4)
    m = eng.run_until_drained()
    assert m["tokens_total"] == 12 and m["tokens_per_s"] > 0
    assert 0.0 < m["mean_occupancy"] <= 1.0
    assert m["decode_steps"] <= m["steps"]


def test_attn_live_pages_counts_each_rows_pages(served):
    """``serve.attn_live_pages`` grows by ``ceil(len / bs)`` per running
    row each decode step, ``len`` counting the token the step writes."""
    cfg, model, sparams, fns = served
    bs = 4
    eng = ServeEngine(model, sparams, num_slots=3, max_len=32,
                      block_size=bs, decode_fn=fns["decode_fn"])
    for n, s in ((3, 1), (8, 2), (13, 3)):
        eng.submit(_prompt(cfg, n, s), max_new_tokens=12)
    eng.step()                                   # admits all three
    live = eng.obs.get("serve.attn_live_pages")
    for _ in range(5):
        rows = list(eng.scheduler.running.values())
        assert len(rows) == 3
        want = live.value + sum(-(-min(q.cached_len + 1, 32) // bs)
                                for q in rows)
        eng.step()
        assert live.value == want
