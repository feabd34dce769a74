"""The plain reference against the serving path at a small size on the
CPU: prefill through the paged cache, then decode, must give the logits
the reference's full forward gives; and the control (the program with its
int4 KV path on) must read farther from the reference than the program."""
import jax.numpy as jnp
import numpy as np

from chip import control, harness, reference, smoke

SEED = 2**32 + 17
# the serving path rounds every activation and matmul output to bf16 where
# the reference keeps float32; at d_model 64 and 2 layers that moves the
# logits by about 1% (rel-L2).  A wrong scale, head or rotation errs by O(1)
RTOL = 5e-2


def served_logits(cfg, prompt, steps):
    """Prefill ``prompt`` in the engine's chunks into a paged int8 pool,
    then decode ``steps`` greedy tokens: (tokens, logits per position)."""
    from repro.serve.cache import PagedCachePool
    from repro.train.serve import make_chunked_prefill, make_decode_step

    model, sparams = harness.build(cfg, SEED)
    e = cfg["engine"]
    pool = PagedCachePool(model, e["num_slots"], e["max_len"],
                          block_size=e["block_size"],
                          kv_bits=cfg["policy"]["kv_bits"])
    prefill, decode = make_chunked_prefill(model), make_decode_step(model)
    seq = pool.alloc_seq()
    pool.ensure(seq, len(prompt) + steps + 1)
    C = e["prefill_chunk"]
    for lo in range(0, len(prompt), C):
        buf = np.zeros((1, C), np.int32)
        piece = prompt[lo:lo + C]
        buf[0, :len(piece)] = piece
        logits, cache = prefill(sparams, pool.step_cache(), jnp.asarray(buf),
                                seq, lo, len(piece))
        pool.accept(cache)
    rows = [np.asarray(logits, np.float32).reshape(-1)]
    toks = []
    for _ in range(steps):
        toks.append(int(rows[-1].argmax()))
        feed = np.zeros((e["num_slots"], 1), np.int32)
        feed[seq, 0] = toks[-1]
        logits, cache = decode(sparams, pool.step_cache(), jnp.asarray(feed))
        pool.accept(cache)
        rows.append(np.asarray(logits, np.float32)[seq].reshape(-1))
    return toks, np.stack(rows)


def test_reference_matches_prefill_then_decode():
    cfg = smoke.config()
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    toks, got = served_logits(cfg, prompt, 6)
    want = reference.logits(cfg, SEED, np.concatenate([prompt, toks]))[len(prompt) - 1:]
    for g, w in zip(got, want):
        assert np.linalg.norm(g - w) / np.linalg.norm(w) < RTOL
    gaps = reference.logit_gaps(cfg, SEED, [(prompt, toks + [int(got[-1].argmax())],
                                             {"served": np.asarray(toks + [int(got[-1].argmax())])})])
    assert float(np.max(gaps[0]["served"])) < 0.1


def test_control_reads_farther_than_the_program():
    cfg = smoke.config()
    rng = np.random.default_rng(1)
    samples = []
    for n in (40, 90, 60):
        prompt = rng.integers(0, cfg["vocab_size"], n)
        toks, _ = served_logits(cfg, prompt, 24)
        samples.append((prompt, toks))
    model, sparams = harness.build(cfg, SEED)
    ctrl = control.control_tokens(model, sparams, cfg, samples, kv_bits=4)
    judged = reference.logit_gaps(cfg, SEED, [
        (p, s, {"served": np.asarray(s), "control": np.asarray(c)})
        for (p, s), c in zip(samples, ctrl)])
    lower = harness.gap_stats([j["served"] for j in judged])["mean_gap"]
    upper = harness.gap_stats([j["control"] for j in judged])["mean_gap"]
    assert upper > 3 * lower, (lower, upper)
