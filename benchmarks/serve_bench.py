# Static vs continuous (slot/paged) batching tokens/s at ReLeQ policies.
"""Serving benchmark: ``python -m benchmarks.serve_bench [--arch glm4-9b]``.

One workload of requests with heterogeneous output lengths, served three
ways at each ``--bits`` policy:

- **static**: the legacy fixed-batch loop — each batch decodes until its
  *longest* member finishes, early finishers idle their slot,
- **continuous**: :class:`repro.serve.ServeEngine` with the legacy slot
  pool — finished slots refilled from the queue on the very next step,
- **paged**: the block-granular engine with chunked prefill.

A separate *mixed-prompt-length* section pins the paged engine's two
structural wins and records them in ``BENCH_serve.json``:

- compile churn: the paged engine compiles exactly ONE prefill and ONE
  decode executable for any prompt-length mix (jit cache counters
  asserted), while the slot engine compiles a prefill per distinct
  length;
- memory: at EQUAL paged-leaf cache bytes the paged pool serves strictly
  more concurrent sequences than the slot pool.

A *multi-tenant* section pins the prefix cache's two wins on the
workload it exists for — N tenants sharing a long system prompt with
short unique user turns, arrivals staggered one request per step:

- prefill work: at a fixed total prompt length, prefill *launches*
  must be strictly decreasing as the share ratio rises (0 -> 0.5 -> 1
  of the system prompt reused across a tenant's requests) — shared
  full blocks are mapped by refcount, never re-prefilled;
- admitted concurrency: at EQUAL cache bytes (same block pool) the
  sharing engine must reach a strictly higher peak of concurrently
  running sequences than a ``prefix_cache=False`` baseline, because
  the admission gate charges only *new* blocks against the pool.

A *speculative* section benchmarks quantized self-draft decoding
(``repro.spec``) on a weight-traffic-bound cell: acceptance rate per
draft bitwidth, end-to-end tokens/s vs the non-spec paged engine, and
p50/p99 per-step decode latency, with a hard ``>= 1.3x`` speedup gate at
the cheapest draft (CI fails the build if speculation stops paying).

A *paged-vs-slot gate* section asserts the tentpole claim — the paged
path is the fast path — at every weight bitwidth: at an equal-or-smaller
KV-byte budget the paged engine (oversubscribed rows, the capacity
paging buys) must reach ``>=`` the slot engine's tokens/s.  Drives run
as time-adjacent order-rotated pairs and the gate takes the MEDIAN
per-pair ratio over ``--gate-trials`` pairs, so shared-machine noise
hits both modes alike and a single stalled drive cannot flip the verdict.

A *quantized-KV* section exercises the int8/int4 block pool end to end:
exact token parity against the fp-KV oracle (``kv_oracle=True`` stores
the QDQ values ``kernels/ref.py`` attends — equality, not allclose),
executable pins (still ONE prefill + ONE decode with quantized blocks),
per-bitwidth kv8/kv4 tokens/s, and the capacity gate: at equal cache
bytes the int4-KV pool must run ``>= 2x`` the slot pool's peak
concurrent sequences.

Prints ``name,tokens_per_s,derived`` CSV rows (useful tokens only — a
finished sequence's padding steps never count for any mode).  All modes
share one jit cache per policy; a warmup pass runs before timing.

``BENCH_serve.json`` schema (trajectory diffs key off these fields):

- ``tokens_per_s``: per weight-bitwidth ``{static, continuous, paged,
  continuous_vs_static, paged_vs_static}`` wall-clock tokens/s,
- ``paged_mixed_prompts``: per pool kind ``{prefill_executables,
  decode_executables, peak_concurrent, kv_bytes, tokens_per_s,
  preemptions}`` + ``distinct_prompt_lens``,
- ``paged_vs_slot_gate``: per weight-bitwidth ``{slot, paged}`` best-of
  tokens/s, ``ratio`` (median per-pair paged/slot, the ``>= 1``
  assertion) and ``pair_ratios`` at equal KV bytes, plus the gate's own
  workload size and the paged row/block budget,
- ``kv_quant``: ``parity`` (oracle exact-match), ``executables``
  (prefill/decode pins), ``tokens_per_s`` (kv8/kv4 paged rows) and
  ``concurrency_int4`` (peak sequences at equal bytes vs slot, the
  ``>= 2x`` assertion),
- ``multi_tenant``: prefix-cache section — per share-ratio
  ``{prefill_launches, prefix_hit_rate, hit_tokens, cow_copies,
  peak_concurrent, preemptions}`` (launches strictly decreasing with
  ratio, the assertion) plus ``concurrency`` ``{shared_peak,
  baseline_peak, num_blocks, kv_bytes}`` — shared peak strictly above
  the ``prefix_cache=False`` baseline at equal cache bytes — and the
  section's tenant/prompt geometry,
- ``speculative``: per draft-bitwidth acceptance/speedup medians,
- ``hotpath``: the one-token-hotpath gate — ``{baseline, hotpath}``
  best-of tokens/s, ``ratio`` (median per-pair hotpath/baseline, the
  ``>= 1.15`` assertion), ``pair_ratios``, the attribution split
  (``decode_host_p50_ms <= 0.25 * decode_step_p50_ms`` asserted on the
  hotpath engine), pipeline lookahead/bubble counts, and the executable
  pins (still ONE prefill + ONE decode),
- ``observability``: ``overhead`` (median enabled/disabled tokens-per-s
  ratio, the ``>= 0.97`` tracing-overhead gate) + ``smoke_trace``
  (event/drop counts, recompiles-after-warmup, span names, device/host
  p50 of the traced multi-tenant speculative run; the Chrome trace
  itself lands in ``results/trace_smoke.json``),
- ``provenance``: git sha / timestamp / jax version / device count
  (``repro.obs.run_provenance``) stamped on every record.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.obs import run_provenance
from repro.obs.trace import Tracer
from repro.quant.qat import policy_for
from repro.serve import SamplingParams, ServeEngine
from repro.spec import SpecConfig, snap_params_to_grid
from repro.train.serve import (
    make_chunked_prefill,
    make_decode_step,
    make_prefill,
    make_verify_chunk,
    quantize_for_serving,
)


def make_workload(n: int, prompt_len: int, gen: int, vocab: int, seed: int = 0):
    """(prompts (n, prompt_len), gens (n,)) — gen lengths spread over
    [gen//4, gen] so static batches always carry stragglers."""
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, (n, prompt_len), dtype=np.int64)
    lo = max(1, gen // 4)
    gens = np.linspace(lo, gen, n).round().astype(int)
    return prompts, rng.permutation(gens)


def run_static(model, sparams, prompts, gens, batch, max_len,
               prefill_fn, decode_fn) -> tuple[float, int]:
    """Fixed-batch loop -> (seconds, useful tokens)."""
    n = len(prompts)
    total = 0
    t0 = time.perf_counter()
    for lo in range(0, n, batch):
        p = jnp.asarray(prompts[lo:lo + batch])
        g = gens[lo:lo + batch]
        logits, cache = prefill_fn(sparams, p, max_len)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        emitted = np.ones(len(g), np.int64)  # prefill token
        for _ in range(int(g.max())):
            logits, cache = decode_fn(sparams, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
            emitted += emitted < g + 1  # only unfinished sequences count
        total += int(emitted.sum())
    return time.perf_counter() - t0, total


def run_continuous(model, sparams, prompts, gens, num_slots, max_len,
                   prefill_fn, decode_fn, **kw) -> dict:
    engine = ServeEngine(model, sparams, num_slots=num_slots,
                         max_len=max_len, decode_fn=decode_fn,
                         prefill_fn=prefill_fn, **kw)
    for p, g in zip(prompts, gens):
        engine.submit(p, int(g) + 1)
    return engine.run_until_drained()


def run_paged_mixed(model, sparams, cfg, args) -> dict:
    """Mixed-prompt-length section: slot vs paged at equal KV bytes.

    Asserts the paged engine's acceptance contract — exactly one prefill
    and one decode executable for the whole length mix (jit cache
    counters), and strictly more concurrent sequences than the slot pool
    at an equal-or-smaller KV-byte budget — and returns the numbers for
    ``BENCH_serve.json``.
    """
    rng = np.random.default_rng(2)
    n = args.requests
    max_len = args.prompt_len + args.gen + 1
    bs = args.block_size
    plens = np.linspace(2, args.prompt_len, n).round().astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, int(l)) for l in plens]
    gens = rng.permutation(
        np.linspace(max(1, args.gen // 4), args.gen, n).round().astype(int))
    # equal-bytes budget: paged pool (incl the garbage block) holds at most
    # floor(slot tokens / bs) blocks — never MORE KV bytes than the slot pool
    num_blocks = args.batch * max_len // bs
    setups = {
        "slot": dict(cache="slot", num_slots=args.batch),
        "paged": dict(cache="paged", num_slots=2 * args.batch,
                      block_size=bs, num_blocks=num_blocks,
                      prefill_chunk=args.prefill_chunk),
    }
    out = {}
    for kind, kw in setups.items():
        prefill_fn = (make_chunked_prefill(model, donate=False)
                      if kind == "paged" else make_prefill(model))
        decode_fn = make_decode_step(model, donate=False)

        def drive():
            eng = ServeEngine(model, sparams, max_len=max_len,
                              prefill_fn=prefill_fn, decode_fn=decode_fn,
                              **kw)
            for p, g in zip(prompts, gens):
                eng.submit(p, int(g) + 1)
            peak = 0
            t0 = time.perf_counter()
            while eng.scheduler.has_work():
                eng.step()
                peak = max(peak, eng.num_running)
            return eng, peak, time.perf_counter() - t0

        drive()  # warmup: all compiles land outside timing (same shapes,
        #          so the executable counters below are unchanged)
        eng, peak, dt = drive()
        m = eng.metrics()
        out[kind] = {
            "prefill_executables": prefill_fn._cache_size(),
            "decode_executables": decode_fn._cache_size(),
            "peak_concurrent": peak,
            "kv_bytes": eng.pool.cache_bytes(),
            "tokens_per_s": round(m["tokens_total"] / dt, 1),
            "preemptions": m.get("preemptions", 0),
        }
    assert out["paged"]["prefill_executables"] == 1, out
    assert out["paged"]["decode_executables"] == 1, out
    assert out["paged"]["kv_bytes"] <= out["slot"]["kv_bytes"], out
    assert out["paged"]["peak_concurrent"] > out["slot"]["peak_concurrent"], out
    out["distinct_prompt_lens"] = len(set(int(l) for l in plens))
    return out


def run_paged_gate(model, cfg, args, params) -> dict:
    """The tentpole gate: paged >= slot tokens/s at every weight bitwidth.

    Fair fight at equal KV bytes: the slot pool serves ``batch`` rows of
    ``max_len`` tokens; the paged pool gets the SAME byte budget
    (``batch * max_len // block_size`` usable blocks) but ``2 * batch``
    sequence rows — oversubscription is the capacity block granularity
    buys, and more concurrent rows per decode step is where the
    throughput comes from.  The gate runs its own workload size
    (>= 24 requests, >= 48 generated tokens) regardless of
    ``--requests``/``--gen``: short drives (~0.2 s) are dominated by OS
    scheduling noise on a shared box, and a shallow queue never exercises
    the oversubscribed slots.  Noise discipline: per bitwidth the slot
    and paged drives run as time-adjacent order-rotated PAIRS and the
    gate asserts the MEDIAN per-pair ratio >= 1 over ``--gate-trials``
    pairs (pairing cancels minutes-scale machine-load drift; the median
    rejects a single stalled drive).
    """
    n_gate = max(args.requests, 24)
    gen_gate = max(args.gen, 48)
    prompts, gens = make_workload(n_gate, args.prompt_len, gen_gate,
                                  cfg.vocab_size, seed=5)
    max_len = args.prompt_len + gen_gate + 1
    bs = args.block_size
    num_blocks = args.batch * max_len // bs  # equal-bytes budget
    out: dict = {"trials": args.gate_trials,
                 "requests": n_gate, "gen": gen_gate,
                 "paged_num_slots": 2 * args.batch,
                 "paged_num_blocks": num_blocks}
    for bits in args.bits:
        sparams = quantize_for_serving(model, params,
                                       policy_for(model, default_bits=bits))
        prefill_slot = make_prefill(model)
        prefill_paged = make_chunked_prefill(model, donate=False)
        decode_fn = make_decode_step(model, donate=False)

        def drive(kind):
            if kind == "slot":
                kw = dict(cache="slot", num_slots=args.batch,
                          prefill_fn=prefill_slot)
            else:
                kw = dict(cache="paged", num_slots=2 * args.batch,
                          block_size=bs, num_blocks=num_blocks,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=prefill_paged)
            eng = ServeEngine(model, sparams, max_len=max_len,
                              decode_fn=decode_fn, **kw)
            for p, g in zip(prompts, gens):
                eng.submit(p, int(g) + 1)
            m = eng.run_until_drained()
            return m["tokens_per_s"], eng.pool.cache_bytes()

        best = {"slot": 0.0, "paged": 0.0}
        kv_bytes = {}
        for kind in ("slot", "paged"):  # warmup: compiles land outside
            _, kv_bytes[kind] = drive(kind)
        assert kv_bytes["paged"] <= kv_bytes["slot"], kv_bytes
        pair_ratios = []
        for t in range(args.gate_trials):
            order = (("slot", "paged") if t % 2 == 0
                     else ("paged", "slot"))
            pair = {}
            for kind in order:
                pair[kind], _ = drive(kind)
                best[kind] = max(best[kind], pair[kind])
            pair_ratios.append(pair["paged"] / pair["slot"])
        median = sorted(pair_ratios)[len(pair_ratios) // 2]
        out[str(bits)] = {
            "slot": round(best["slot"], 1),
            "paged": round(best["paged"], 1),
            "ratio": round(median, 3),
            "pair_ratios": [round(r, 3) for r in pair_ratios],
            "kv_bytes": kv_bytes,
        }
        assert median >= 1.0, (
            f"paged-fast-path gate: median paged/slot tokens-per-s ratio "
            f"{median:.3f} < 1 at {bits}-bit weights (equal KV bytes "
            f"{kv_bytes['paged']} <= {kv_bytes['slot']}) — {out}")
    return out


def run_kv_quant(model, cfg, args, sparams) -> dict:
    """Quantized-KV section: oracle parity, executable pins, kv8/kv4
    tokens/s, and the int4 equal-bytes concurrency gate.

    - **parity**: the int4-KV engine must emit EXACTLY the tokens of the
      fp-KV oracle engine (``kv_oracle=True`` stores the QDQ values the
      ``kernels/ref.py`` oracle attends) — the dequantized codes·scale
      product is bitwise the stored oracle float, so this is equality.
    - **executables**: mixed prompt lengths through the quantized pool
      still compile exactly ONE prefill and ONE decode (per-layer bits
      are data, not shape).
    - **concurrency**: at an equal-or-smaller byte budget than the slot
      pool, int4 blocks (half the bytes of fp16 KV even with per-token
      f32 scales at the smoke head_dim) must run ``>= 2x`` the slot
      pool's peak concurrent sequences.
    """
    from repro.serve import PagedCachePool, SlotCachePool

    rng = np.random.default_rng(7)
    n = args.requests
    max_len = args.prompt_len + args.gen + 1
    bs = args.block_size
    plens = np.linspace(2, args.prompt_len, n).round().astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, int(l)) for l in plens]
    gens = rng.permutation(
        np.linspace(max(1, args.gen // 4), args.gen, n).round().astype(int))

    def drive(**kw):
        eng = ServeEngine(model, sparams, max_len=max_len, cache="paged",
                          block_size=bs, prefill_chunk=args.prefill_chunk,
                          **kw)
        for p, g in zip(prompts, gens):
            eng.submit(p, int(g) + 1)
        peak = 0
        t0 = time.perf_counter()
        while eng.scheduler.has_work():
            eng.step()
            peak = max(peak, eng.num_running)
        dt = time.perf_counter() - t0
        outs = [eng.output(i) for i in range(n)]
        return eng, outs, peak, eng.metrics()["tokens_total"] / dt

    # --- fp-KV-oracle token parity (deterministic: exact equality)
    _, got, _, _ = drive(num_slots=args.batch, kv_bits=4)
    _, want, _, _ = drive(num_slots=args.batch, kv_bits=4, kv_oracle=True)
    assert got == want, "int4-KV tokens diverge from the fp-KV oracle"
    out: dict = {"parity": {"kv_bits": 4, "oracle_match": True,
                            "requests": n}}

    # --- executable pins with quantized blocks (mixed prompt lengths)
    prefill_fn = make_chunked_prefill(model, donate=False)
    decode_fn = make_decode_step(model, donate=False)
    eng, _, _, _ = drive(num_slots=args.batch, kv_bits=8,
                         prefill_fn=prefill_fn, decode_fn=decode_fn)
    out["executables"] = {"prefill": prefill_fn._cache_size(),
                          "decode": decode_fn._cache_size()}
    assert out["executables"] == {"prefill": 1, "decode": 1}, out

    # --- kv8/kv4 paged tokens/s (trajectory rows; reuse the warm
    # executables-section fns — kv_bits is data, so kv8 and kv4 share
    # the SAME compiled prefill/decode and the rows time steady state)
    fns = dict(prefill_fn=prefill_fn, decode_fn=decode_fn)
    out["tokens_per_s"] = {}
    for kv_bits in (8, 4):
        drive(num_slots=args.batch, kv_bits=kv_bits, **fns)
        _, _, _, tps = drive(num_slots=args.batch, kv_bits=kv_bits, **fns)
        out["tokens_per_s"][f"kv{kv_bits}"] = round(tps, 1)

    # --- int4 equal-bytes concurrency: >= 2x the slot pool's peak
    slot_bytes = SlotCachePool(model, args.batch, max_len).cache_bytes()
    probe = PagedCachePool(model, 1, max_len, block_size=bs, kv_bits=4)
    per_block = probe.cache_bytes() / probe.num_blocks
    num_blocks = int(slot_bytes // per_block)
    eng_slot = ServeEngine(model, sparams, num_slots=args.batch,
                           max_len=max_len, cache="slot")
    for p, g in zip(prompts, gens):
        eng_slot.submit(p, int(g) + 1)
    slot_peak = 0
    while eng_slot.scheduler.has_work():
        eng_slot.step()
        slot_peak = max(slot_peak, eng_slot.num_running)
    eng, _, q4_peak, _ = drive(num_slots=4 * args.batch, kv_bits=4,
                               num_blocks=num_blocks)
    out["concurrency_int4"] = {
        "slot_peak": slot_peak,
        "paged_int4_peak": q4_peak,
        "slot_kv_bytes": slot_bytes,
        "paged_kv_bytes": eng.pool.cache_bytes(),
        "ratio": round(q4_peak / max(slot_peak, 1), 2),
    }
    assert eng.pool.cache_bytes() <= slot_bytes, out["concurrency_int4"]
    assert q4_peak >= 2 * slot_peak, (
        f"int4-KV concurrency gate: peak {q4_peak} < 2x slot peak "
        f"{slot_peak} at equal cache bytes — {out['concurrency_int4']}")
    return out


def run_multi_tenant(model, cfg, args, sparams) -> dict:
    """Multi-tenant prefix-cache section: N tenants x shared system
    prompt x short user turns, arrivals staggered one per step (so a
    tenant's later requests see the blocks its first request published).

    Two sub-gates, both deterministic (counts and peaks, not timing):

    - **launch sweep** (ample blocks): at a FIXED total prompt length,
      raise the share ratio — the fraction of the prompt drawn from the
      tenant's system prompt — through 0 / 0.5 / 1 and assert prefill
      launches strictly decrease: shared full blocks are mapped by
      refcount instead of re-prefilled, and only the unique tail still
      runs chunks.  One prefill + one decode executable across the
      whole sweep (partial prefill reuses the fixed-shape chunk).
    - **concurrency gate** (tight blocks, equal bytes): the SAME pool
      (same ``num_blocks``, byte-identical) serves the full-share
      workload with and without ``prefix_cache``.  The pool is sized so
      a no-sharing admission (every request charged its full
      ``ceil((prompt+1)/bs)`` blocks) can hold only a couple of
      sequences, while the sharing gate — which charges *new* blocks
      only — admits every tenant's tail alongside one copy of each
      system prompt.  Peak concurrently-running sequences must be
      strictly higher with sharing.
    """
    bs = args.block_size
    T = args.mt_tenants
    S = args.mt_shared // bs * bs  # full blocks only — hits are block-granular
    plen = S + args.mt_user
    gen = 8
    max_len = plen + gen + 1
    blocks_per_seq = -(-max_len // bs)
    n = max(args.requests, 2 * T)
    rng = np.random.default_rng(11)
    sys_prompts = rng.integers(0, cfg.vocab_size, (max(T, 2), S))

    def make_prompts(n_req, tenants, ratio, seed):
        r = np.random.default_rng(seed)
        shared = int(S * ratio) // bs * bs
        prompts = r.integers(0, cfg.vocab_size, (n_req, plen))
        for i in range(n_req):
            prompts[i, :shared] = sys_prompts[i % tenants, :shared]
        return prompts

    def drive(prompts, prefill_fn, decode_fn, *, num_slots, num_blocks,
              prefix_cache=True):
        eng = ServeEngine(model, sparams, num_slots=num_slots,
                          max_len=max_len, cache="paged", block_size=bs,
                          num_blocks=num_blocks,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=prefill_fn, decode_fn=decode_fn,
                          prefix_cache=prefix_cache)
        submitted, peak = 0, 0
        while submitted < len(prompts) or eng.scheduler.has_work():
            while submitted < len(prompts) and eng.steps >= submitted:
                eng.submit(prompts[submitted], gen + 1)
                submitted += 1
            eng.step()
            peak = max(peak, eng.num_running)
        return eng, peak

    # --- launch sweep: ample pool (full capacity per slot + slack, so
    # launch counts are preemption-free and exactly reproducible)
    ample = args.batch * (blocks_per_seq + 2) + 1
    prefill_fn = make_chunked_prefill(model, donate=False)
    decode_fn = make_decode_step(model, donate=False)
    out: dict = {"tenants": T, "shared_tokens": S, "user_tokens": plen - S,
                 "requests": n, "gen": gen, "ratios": {}}
    launches = []
    for ratio in (0.0, 0.5, 1.0):
        prompts = make_prompts(n, T, ratio, seed=23)
        eng, peak = drive(prompts, prefill_fn, decode_fn,
                          num_slots=args.batch, num_blocks=ample)
        m = eng.metrics()
        pc = m["prefix_cache"]
        assert m["preemptions"] == 0, (ratio, m["preemptions"])
        out["ratios"][str(ratio)] = {
            "prefill_launches": m["prefill_launches"],
            "prefix_hit_rate": round(m["prefix_hit_rate"], 3),
            "hit_tokens": pc["hit_tokens"],
            "cow_copies": pc["cow_copies"],
            "peak_concurrent": peak,
            "preemptions": m["preemptions"],
        }
        launches.append(m["prefill_launches"])
    assert launches[0] > launches[1] > launches[2], (
        f"multi-tenant gate: prefill launches {launches} not strictly "
        f"decreasing over share ratios 0/0.5/1 — {out}")
    assert prefill_fn._cache_size() == 1, prefill_fn._cache_size()
    assert decode_fn._cache_size() == 1, decode_fn._cache_size()
    out["prefill_launches"] = launches

    # --- concurrency gate: tight pool, equal bytes, full sharing.
    # Sized from the sharing engine's true demand — one prefix chain per
    # tenant, every request's unique tail + decode block, the 1-block
    # admission watermark per sequence, a little slack, the garbage
    # block — which is far below n_gate * blocks-per-request, the rent a
    # no-sharing admission charges.
    t_gate, n_gate = 2, max(args.requests, 8)
    prefix_blocks = S // bs
    gate_blocks = (t_gate * prefix_blocks
                   + n_gate * (blocks_per_seq - prefix_blocks)
                   + n_gate + 2 + 1)
    prompts = make_prompts(n_gate, t_gate, 1.0, seed=29)
    pf2 = make_chunked_prefill(model, donate=False)
    df2 = make_decode_step(model, donate=False)
    beng, base_peak = drive(prompts, pf2, df2, num_slots=n_gate,
                            num_blocks=gate_blocks, prefix_cache=False)
    weng, shared_peak = drive(prompts, pf2, df2, num_slots=n_gate,
                              num_blocks=gate_blocks, prefix_cache=True)
    assert weng.pool.cache_bytes() == beng.pool.cache_bytes()
    out["concurrency"] = {
        "shared_peak": shared_peak,
        "baseline_peak": base_peak,
        "requests": n_gate, "tenants": t_gate,
        "num_blocks": gate_blocks,
        "kv_bytes": weng.pool.cache_bytes(),
    }
    assert shared_peak > base_peak, (
        f"multi-tenant gate: shared peak concurrency {shared_peak} not "
        f"above the no-sharing baseline {base_peak} at equal cache "
        f"bytes — {out['concurrency']}")
    assert pf2._cache_size() == 1 and df2._cache_size() == 1
    return out


def run_spec(args) -> dict:
    """Speculative section: acceptance x draft bitwidth + tokens/s vs the
    non-spec paged engine, with the ``>= 1.3x`` gate at the cheapest
    draft.

    Runs on its own d256/L4 glm4 cell regardless of ``--arch``: the smoke
    dims are dispatch-bound (every decode step costs the same regardless
    of bitwidth), so a low-bit draft cannot win there — speculation's
    margin only appears once per-step cost scales with weight traffic.
    Weights are first snapped onto the cheapest draft's quantization grid
    (:func:`repro.spec.draft.snap_params_to_grid`), which makes every
    low-bit re-pack LOSSLESS: acceptance ~ 1 by construction and honestly
    measured, so the section isolates the *mechanical* speedup ceiling
    (draft roll at ~bits/8 of target traffic + one k+1-wide amortized
    verify) from draft quality, which is a property of trained weights.
    """
    dm = args.spec_cell
    cfg = replace(get_config("glm4-9b", smoke=True), name="spec-cell",
                  d_model=dm, d_ff=2 * dm, num_layers=4,
                  num_heads=dm // 32, head_dim=32, num_kv_heads=2)
    model = build_model(cfg)
    params = snap_params_to_grid(model, model.init(jax.random.PRNGKey(0)),
                                 min(args.spec_draft_bits))
    sparams = quantize_for_serving(model, params,
                                   policy_for(model, default_bits=8))
    # homogeneous gens at full occupancy: every decode step carries all
    # `batch` rows, so median step latency / tokens-per-step is a clean
    # per-token cost (the gate metric — medians over ~100 steps reject
    # shared-machine noise that wall-clock tokens/s cannot)
    rng = np.random.default_rng(3)
    n = 2 * args.batch
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len)
               for _ in range(n)]
    gens = np.full(n, args.gen)
    max_len = args.prompt_len + args.gen + 1
    prefill_fn = make_chunked_prefill(model, donate=False)
    decode_fn = make_decode_step(model, donate=False)
    verify_fn = make_verify_chunk(model, donate=False)

    def drive(spec):
        eng = ServeEngine(model, sparams, num_slots=args.batch,
                          max_len=max_len, cache="paged",
                          block_size=args.block_size,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=prefill_fn, decode_fn=decode_fn,
                          verify_fn=verify_fn, spec=spec)
        for p, g in zip(prompts, gens):
            eng.submit(p, int(g) + 1)
        t0 = time.perf_counter()
        eng.run_until_drained()
        dt = time.perf_counter() - t0
        m = eng.metrics()
        return m, m["tokens_total"] / dt

    def step_ms(m):
        return {"decode_step_p50_ms": round(m["decode_step_p50_ms"], 3),
                "decode_step_p99_ms": round(m["decode_step_p99_ms"], 3)}

    def per_token_ms(m):
        """Median over decode steps of (step latency / tokens that step
        emitted) — the gate metric.  Truncated tail windows carry their
        own (cheap step, few tokens) ratio instead of skewing a global
        mean, and the median rejects shared-machine latency spikes."""
        return m["decode_tok_p50_ms"]

    # warmups: land every compile (prefill, 8b decode, per-bits draft
    # decode, verify) outside the timed drives
    specs = [None] + [SpecConfig(k=args.spec_k, draft_bits=b)
                      for b in args.spec_draft_bits]
    for spec in specs:
        drive(spec)
    # best-of-N per mode, modes interleaved: a transient slowdown of the
    # shared machine lands inside ONE drive, not inside every drive of one
    # mode — the gate compares each mode's cleanest median
    best: dict = {}
    for _ in range(args.spec_trials):
        for spec in specs:
            m, tps = drive(spec)
            key = spec.draft_bits if spec else None
            if key not in best or per_token_ms(m) < per_token_ms(best[key][0]):
                best[key] = (m, tps)
    m0, tps0 = best[None]
    out = {
        "cell": {"arch": "glm4-9b", "d_model": cfg.d_model,
                 "num_layers": cfg.num_layers},
        "k": args.spec_k,
        "target_bits": 8,
        "trials": args.spec_trials,
        "baseline": {"tokens_per_s": round(tps0, 1),
                     "per_token_ms": round(per_token_ms(m0), 3),
                     **step_ms(m0)},
        "drafts": {},
    }
    for bits in args.spec_draft_bits:
        m, tps = best[bits]
        out["drafts"][str(bits)] = {
            "tokens_per_s": round(tps, 1),
            "per_token_ms": round(per_token_ms(m), 3),
            "speedup_vs_paged": round(per_token_ms(m0) / per_token_ms(m), 3),
            "acceptance_rate": round(m["spec"]["acceptance_rate"], 3),
            "proposed": m["spec"]["proposed"],
            "accepted": m["spec"]["accepted"],
            **step_ms(m),
        }
    top = max(d["speedup_vs_paged"] for d in out["drafts"].values())
    assert top >= 1.3, (
        f"speculative decoding gate: best speedup {top:.3f}x < 1.3x "
        f"over non-spec paged on the spec cell — {out}")
    return out


def run_hotpath_gate(args) -> dict:
    """One-token hotpath section: on-device sampling + the lookahead
    pipeline vs the synchronous host-sampling engine, with two gates.

    Runs on its own wide-vocab cell (``--hotpath-vocab``, smoke glm4
    body): the host path's per-step cost — the ``(rows, V)`` logits
    fetch plus a per-row float64 ``warp_probs`` — scales with the vocab
    while the device step barely does, so this is the regime the
    tentpole exists for.  The workload samples (temperature 1, nucleus
    0.9): host sampling is the cost being moved on device, and greedy
    token parity between the two paths is already pinned in
    tests/test_sampler_device.py — this section measures throughput.
    All ``2 * batch == num_slots`` requests are submitted up front with
    homogeneous budgets, so after admission the queue is empty and the
    lookahead pipeline runs steady-state.

    Gates (CI fails the build on either):

    - **throughput**: hotpath tokens/s ``>= 1.15x`` the host-sampling
      baseline — same noise discipline as the paged-vs-slot gate
      (time-adjacent order-rotated pairs, MEDIAN per-pair ratio over
      ``--gate-trials`` pairs);
    - **attribution**: on the hotpath engine ``decode_host_p50_ms <=
      0.25 * decode_step_p50_ms`` — the Python serving loop stays off
      the critical path (dispatch counts as device time, so the bound
      means the same thing on asynchronous and synchronous backends).

    Also asserts the executable pins survive: ONE prefill + ONE decode
    jit entry after serving both modes (the shared sampler jit is
    tracked separately by the engine's recompile detector).
    """
    vocab = args.hotpath_vocab
    cfg = replace(get_config("glm4-9b", smoke=True), name="hotpath-cell",
                  vocab_size=vocab)
    model = build_model(cfg)
    sparams = quantize_for_serving(model, model.init(jax.random.PRNGKey(0)),
                                   policy_for(model, default_bits=8))
    prefill_fn = make_chunked_prefill(model, donate=False)
    decode_fn = make_decode_step(model, donate=False)
    rng = np.random.default_rng(23)
    n = 2 * args.batch
    gen = max(args.gen, 48)
    prompts = [rng.integers(0, vocab, args.prompt_len) for _ in range(n)]
    max_len = args.prompt_len + gen + 1
    sampling = SamplingParams(temperature=1.0, top_p=0.9, seed=29)

    def drive(hot):
        eng = ServeEngine(model, sparams, num_slots=n, max_len=max_len,
                          cache="paged", block_size=args.block_size,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=prefill_fn, decode_fn=decode_fn,
                          sample_device=hot, pipeline=hot)
        for p in prompts:
            eng.submit(p, gen + 1, sampling=sampling)
        return eng.run_until_drained()

    for hot in (False, True):  # warmup: compiles land outside timing
        drive(hot)
    best: dict = {}
    pair_ratios = []
    for t in range(args.gate_trials):
        order = (False, True) if t % 2 == 0 else (True, False)
        pair = {}
        for hot in order:
            m = drive(hot)
            pair[hot] = m["tokens_per_s"]
            if hot not in best or pair[hot] > best[hot]["tokens_per_s"]:
                best[hot] = m
        pair_ratios.append(pair[True] / pair[False])
    median = sorted(pair_ratios)[len(pair_ratios) // 2]
    mh = best[True]
    out = {
        "cell": {"arch": "glm4-9b", "vocab_size": vocab},
        # engine modes under test, in launch/serve.py flag terms — so a
        # regression here is bisectable with the same switches
        "modes": {"baseline": "--host-sampling --no-pipeline",
                  "hotpath": "default (device sampling + pipeline)"},
        "trials": args.gate_trials, "requests": n, "gen": gen,
        "baseline": round(best[False]["tokens_per_s"], 1),
        "hotpath": round(mh["tokens_per_s"], 1),
        "ratio": round(median, 3),
        "pair_ratios": [round(r, 3) for r in pair_ratios],
        "decode_step_p50_ms": round(mh["decode_step_p50_ms"], 3),
        "decode_host_p50_ms": round(mh["decode_host_p50_ms"], 3),
        "host_fraction_p50": round(mh["decode_host_p50_ms"]
                                   / mh["decode_step_p50_ms"], 3),
        "pipeline": mh["pipeline"],
        "executables": {"prefill": prefill_fn._cache_size(),
                        "decode": decode_fn._cache_size()},
    }
    assert median >= 1.15, (
        f"hotpath throughput gate: median hotpath/baseline tokens-per-s "
        f"ratio {median:.3f} < 1.15 — {out}")
    assert (mh["decode_host_p50_ms"]
            <= 0.25 * mh["decode_step_p50_ms"]), (
        f"hotpath attribution gate: decode_host_p50 "
        f"{mh['decode_host_p50_ms']:.3f} ms > 0.25 x step p50 "
        f"{mh['decode_step_p50_ms']:.3f} ms — {out}")
    assert mh["pipeline"]["lookahead_steps"] > 0, out
    assert out["executables"] == {"prefill": 1, "decode": 1}, out
    return out


def run_obs_gate(model, cfg, args, sparams, trace_path: str | None) -> dict:
    """Observability section: the tracing-overhead gate plus a traced
    multi-tenant speculative smoke run exported as a Chrome-trace file.

    - **overhead gate**: tokens/s with a live ``Tracer`` + registry must
      stay within 3% of the tracing-disabled engine (``span()`` on a
      disabled tracer is one attribute check; instrument observes are a
      lock + float add).  Same noise discipline as the paged-vs-slot
      gate: time-adjacent order-rotated pairs, MEDIAN per-pair ratio
      over ``--gate-trials`` pairs.
    - **smoke trace**: two tenants sharing a system prompt, staggered
      arrivals, speculative decoding on — the full acceptance scenario —
      traced end to end and saved to ``trace_smoke.json`` (CI uploads
      it; open at ui.perfetto.dev).  Asserts the trace is *balanced*
      (zero open spans after the drain), shows ZERO ``xla.compile``
      events after warmup, attributes every decode step into device vs
      host time, and round-trips as valid Chrome-trace JSON (every
      event carries name/ph/ts/pid/tid; X events carry dur).
    """
    n_gate = max(args.requests, 24)
    gen_gate = max(args.gen, 48)
    prompts, gens = make_workload(n_gate, args.prompt_len, gen_gate,
                                  cfg.vocab_size, seed=13)
    max_len = args.prompt_len + gen_gate + 1
    prefill_fn = make_chunked_prefill(model, donate=False)
    decode_fn = make_decode_step(model, donate=False)

    def drive(tracer):
        eng = ServeEngine(model, sparams, num_slots=args.batch,
                          max_len=max_len, cache="paged",
                          block_size=args.block_size,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=prefill_fn, decode_fn=decode_fn,
                          tracer=tracer)
        for p, g in zip(prompts, gens):
            eng.submit(p, int(g) + 1)
        m = eng.run_until_drained()
        return m["tokens_per_s"]

    for kind in ("off", "on"):  # warmup: compiles land outside timing
        drive(Tracer(enabled=True) if kind == "on" else None)
    pair_ratios = []
    for t in range(args.gate_trials):
        order = ("off", "on") if t % 2 == 0 else ("on", "off")
        pair = {}
        for kind in order:
            pair[kind] = drive(Tracer(enabled=True) if kind == "on"
                               else None)
        pair_ratios.append(pair["on"] / pair["off"])
    median = sorted(pair_ratios)[len(pair_ratios) // 2]
    out: dict = {"overhead": {
        "ratio": round(median, 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios],
        "trials": args.gate_trials, "requests": n_gate, "gen": gen_gate,
    }}
    assert median >= 0.97, (
        f"tracing-overhead gate: median enabled/disabled tokens-per-s "
        f"ratio {median:.4f} < 0.97 (3% budget) — {out}")

    # --- traced multi-tenant speculative smoke run
    bs = args.block_size
    S, plen, gen, n = 2 * bs, 3 * bs, 16, 6
    rng = np.random.default_rng(17)
    sys_prompts = rng.integers(0, cfg.vocab_size, (2, S))
    sprompts = rng.integers(0, cfg.vocab_size, (n, plen))
    for i in range(n):
        sprompts[i, :S] = sys_prompts[i % 2]
    smax_len = plen + gen + 1
    pf = make_chunked_prefill(model, donate=False)
    df = make_decode_step(model, donate=False)
    vf = make_verify_chunk(model, donate=False)

    def smoke(tracer):
        eng = ServeEngine(model, sparams, num_slots=4, max_len=smax_len,
                          cache="paged", block_size=bs,
                          prefill_chunk=args.prefill_chunk,
                          prefill_fn=pf, decode_fn=df, verify_fn=vf,
                          spec=SpecConfig(k=4, draft_bits=4),
                          tracer=tracer)
        submitted = 0
        while submitted < n or eng.scheduler.has_work():
            while submitted < n and eng.steps >= 2 * submitted:
                eng.submit(sprompts[submitted], gen + 1)
                submitted += 1
            eng.step()
        return eng

    smoke(None)  # warmup: draft/verify/prefill compiles land here
    tracer = Tracer(enabled=True)
    tracer.name_thread("serve-loop")
    eng = smoke(tracer)
    m = eng.metrics()
    assert m["recompiles"] == 0, (
        f"smoke trace saw {m['recompiles']} xla.compile events after "
        f"warmup — steady-state serving must not recompile")
    assert tracer.depth() == 0, (
        f"unbalanced trace: {tracer.depth()} spans still open after "
        f"the drain")
    names = {e["name"] for e in tracer.events()}
    for want in ("queue.wait", "admit", "prefill.chunk", "decode.step",
                 "spec.draft", "spec.verify", "spec.resolve"):
        assert want in names, f"smoke trace missing {want!r} spans: {names}"
    assert "decode_device_p50_ms" in m and "decode_host_p50_ms" in m, m
    doc = tracer.to_chrome()
    for ev in doc["traceEvents"]:  # schema check, then round-trip
        for key in ("name", "ph", "pid", "tid"):
            assert key in ev, ev
        assert ev["ph"] in ("X", "i", "M"), ev
        if ev["ph"] == "X":
            assert "dur" in ev and "ts" in ev, ev
    json.loads(json.dumps(doc))
    out["smoke_trace"] = {
        "events": tracer.num_events,
        "dropped": tracer.dropped,
        "span_names": sorted(names),
        "recompiles": m["recompiles"],
        "spec_acceptance": round(m["spec"]["acceptance_rate"], 3),
        "prefix_hits": m["prefix_hits"],
        "prefix_lookups": m["prefix_lookups"],
        "decode_device_p50_ms": round(m["decode_device_p50_ms"], 3),
        "decode_host_p50_ms": round(m["decode_host_p50_ms"], 3),
    }
    if trace_path:
        os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
        tracer.save(trace_path)
        out["smoke_trace"]["path"] = trace_path
    return out


def bench(args):
    """-> (csv rows, (cfg, model, sparams at args.bits[0]) for reuse)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts, gens = make_workload(args.requests, args.prompt_len, args.gen,
                                  cfg.vocab_size)
    max_len = args.prompt_len + args.gen + 1
    rows = []
    first_sparams = None
    for bits in args.bits:
        sparams = quantize_for_serving(model, params,
                                       policy_for(model, default_bits=bits))
        if first_sparams is None:
            first_sparams = sparams
        prefill_fn = make_prefill(model)
        # static batch == num_slots -> identical decode executable
        decode_fn = make_decode_step(model, donate=False)
        # warm both paths: every static batch size that will occur (the
        # tail batch compiles its own executables) and the batch-1
        # admission prefill (continuous), so compiles land outside timing
        warm_sizes = {args.batch}
        if args.requests % args.batch:
            warm_sizes.add(args.requests % args.batch)
        chunk_fn = make_chunked_prefill(model, donate=False)
        for b in warm_sizes:
            run_static(model, sparams, prompts[:b], np.minimum(gens[:b], 2),
                       b, max_len, prefill_fn, decode_fn)
        run_continuous(model, sparams, prompts[:2], np.minimum(gens[:2], 2),
                       args.batch, max_len, prefill_fn, decode_fn,
                       cache="slot")
        run_continuous(model, sparams, prompts[:2], np.minimum(gens[:2], 2),
                       args.batch, max_len, chunk_fn, decode_fn,
                       cache="paged", block_size=args.block_size,
                       prefill_chunk=args.prefill_chunk)

        dt, total = run_static(model, sparams, prompts, gens, args.batch,
                               max_len, prefill_fn, decode_fn)
        tps_static = total / dt
        rows.append((f"serve_static@{bits}b", tps_static,
                     f"tokens={total};batch={args.batch}"))

        m = run_continuous(model, sparams, prompts, gens, args.batch,
                           max_len, prefill_fn, decode_fn, cache="slot")
        tps_cont = m["tokens_per_s"]
        rows.append((f"serve_continuous@{bits}b", tps_cont,
                     f"tokens={m['tokens_total']};"
                     f"occupancy={m['mean_occupancy']:.2f};"
                     f"vs_static={tps_cont / max(tps_static, 1e-9):.2f}x"))

        m = run_continuous(model, sparams, prompts, gens, args.batch,
                           max_len, chunk_fn, decode_fn, cache="paged",
                           block_size=args.block_size,
                           prefill_chunk=args.prefill_chunk)
        tps_paged = m["tokens_per_s"]
        rows.append((f"serve_paged@{bits}b", tps_paged,
                     f"tokens={m['tokens_total']};"
                     f"block_occ={m['mean_block_occupancy']:.2f};"
                     f"vs_static={tps_paged / max(tps_static, 1e-9):.2f}x"))
    return rows, (cfg, model, first_sparams)


def write_record(args, rows, path: str, paged_mixed: dict | None = None,
                 speculative: dict | None = None,
                 paged_gate: dict | None = None,
                 kv_quant: dict | None = None,
                 multi_tenant: dict | None = None,
                 observability: dict | None = None,
                 hotpath: dict | None = None) -> dict:
    """Persist the per-bitwidth static/continuous/paged tokens/s plus the
    mixed-prompt-length paged section so the perf trajectory is comparable
    across PRs (CI uploads this file as an artifact; humans diff it).
    Every record carries a ``provenance`` stamp (git sha, timestamp, jax
    version, device count) so a perf number stays interpretable."""
    per_bits: dict[str, dict] = {}
    for name, tps, derived in rows:
        mode, b = name.replace("serve_", "").split("@")
        per_bits.setdefault(b, {})[mode] = round(tps, 1)
    for b, d in per_bits.items():
        if "static" in d and "continuous" in d and d["static"] > 0:
            d["continuous_vs_static"] = round(d["continuous"] / d["static"], 3)
        if "static" in d and "paged" in d and d["static"] > 0:
            d["paged_vs_static"] = round(d["paged"] / d["static"], 3)
    rec = {
        "benchmark": "serve_bench",
        "provenance": run_provenance(),
        "arch": args.arch, "smoke": bool(args.smoke),
        "requests": args.requests, "batch": args.batch,
        "prompt_len": args.prompt_len, "gen": args.gen,
        "block_size": args.block_size, "prefill_chunk": args.prefill_chunk,
        "tokens_per_s": per_bits,
    }
    if paged_mixed is not None:
        rec["paged_mixed_prompts"] = paged_mixed
    if paged_gate is not None:
        rec["paged_vs_slot_gate"] = paged_gate
    if kv_quant is not None:
        rec["kv_quant"] = kv_quant
    if multi_tenant is not None:
        rec["multi_tenant"] = multi_tenant
    if speculative is not None:
        rec["speculative"] = speculative
    if observability is not None:
        rec["observability"] = observability
    if hotpath is not None:
        rec["hotpath"] = hotpath
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "results",
                           "BENCH_serve.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--bits", type=int, nargs="+", default=[2, 4, 8])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size == continuous slot count")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="paged engine: fixed prefill chunk length")
    ap.add_argument("--gate-trials", type=int, default=3,
                    help="paged-vs-slot gate: timed drives per mode "
                         "(interleaved best-of, noise rejection)")
    ap.add_argument("--kv", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the quantized-KV section (oracle parity, "
                         "executable pins, int4 2x-concurrency gate)")
    ap.add_argument("--mt", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the multi-tenant prefix-cache section "
                         "(launch sweep + equal-bytes concurrency gate)")
    ap.add_argument("--mt-tenants", type=int, default=4,
                    help="multi-tenant section: tenants in the launch "
                         "sweep (each owns one system prompt)")
    ap.add_argument("--mt-shared", type=int, default=512,
                    help="multi-tenant section: system-prompt tokens "
                         "(rounded down to whole blocks)")
    ap.add_argument("--mt-user", type=int, default=16,
                    help="multi-tenant section: unique user-turn tokens "
                         "appended per request")
    ap.add_argument("--spec", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the speculative-decoding section (1.3x gate)")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="speculative window (draft tokens per step)")
    ap.add_argument("--spec-trials", type=int, default=3,
                    help="timed drives per mode (best-of, noise rejection)")
    ap.add_argument("--spec-cell", type=int, default=512,
                    help="spec-section cell width (d_model; d_ff/heads "
                         "scale with it)")
    ap.add_argument("--spec-draft-bits", type=int, nargs="+", default=[2, 4],
                    help="draft bitwidths to sweep (weights snapped to the "
                         "cheapest one's grid)")
    ap.add_argument("--hotpath", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the one-token-hotpath section (>= 1.15x "
                         "throughput gate + <= 0.25 host-fraction gate)")
    ap.add_argument("--hotpath-vocab", type=int, default=4096,
                    help="hotpath-section cell vocab (host sampling cost "
                         "scales with it; device step barely does)")
    ap.add_argument("--obs", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the observability section (<= 3% tracing-"
                         "overhead gate + traced multi-tenant spec smoke "
                         "run exported as a Chrome trace)")
    ap.add_argument("--trace-out",
                    default=os.path.join(os.path.dirname(__file__),
                                         "results", "trace_smoke.json"),
                    help="Chrome-trace path for the smoke run "
                         "('' disables the file)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="JSON record path ('' disables)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    rows, (cfg, model, sparams) = bench(args)
    print("name,tokens_per_s,derived")
    for name, tps, derived in rows:
        print(f"{name},{tps:.1f},{derived}", flush=True)
    gate = run_paged_gate(model, cfg, args,
                          model.init(jax.random.PRNGKey(0)))
    for b in args.bits:
        d = gate[str(b)]
        print(f"paged_gate@{b}b,{d['paged']:.1f},"
              f"slot={d['slot']:.1f};ratio={d['ratio']:.3f}x;"
              f"equal_kv_bytes", flush=True)
    kv = None
    if args.kv:
        kv = run_kv_quant(model, cfg, args, sparams)
        c = kv["concurrency_int4"]
        print(f"kv_quant: oracle_parity=exact "
              f"executables=1/1 "
              f"kv8={kv['tokens_per_s']['kv8']:.1f} "
              f"kv4={kv['tokens_per_s']['kv4']:.1f} tok/s, "
              f"int4 peak_concurrent {c['paged_int4_peak']} >= "
              f"2x slot {c['slot_peak']} at kv_bytes "
              f"{c['paged_kv_bytes']} <= {c['slot_kv_bytes']}", flush=True)
    mt = None
    if args.mt:
        mt = run_multi_tenant(model, cfg, args, sparams)
        c = mt["concurrency"]
        print(f"multi_tenant: prefill_launches "
              f"{' > '.join(str(l) for l in mt['prefill_launches'])} "
              f"over share 0/0.5/1 ({mt['tenants']} tenants x "
              f"{mt['shared_tokens']}-token system prompt), "
              f"peak_concurrent shared={c['shared_peak']} vs "
              f"no-sharing={c['baseline_peak']} at equal kv_bytes "
              f"{c['kv_bytes']}", flush=True)
    mixed = run_paged_mixed(model, sparams, cfg, args)
    print(f"paged_mixed: prefill_executables="
          f"{mixed['paged']['prefill_executables']} "
          f"(slot compiled {mixed['slot']['prefill_executables']} for "
          f"{mixed['distinct_prompt_lens']} lengths), "
          f"peak_concurrent paged={mixed['paged']['peak_concurrent']} "
          f"vs slot={mixed['slot']['peak_concurrent']} at "
          f"kv_bytes {mixed['paged']['kv_bytes']} <= "
          f"{mixed['slot']['kv_bytes']}", flush=True)
    obs = None
    if args.obs:
        obs = run_obs_gate(model, cfg, args, sparams, args.trace_out)
        st = obs["smoke_trace"]
        print(f"observability: tracing overhead ratio "
              f"{obs['overhead']['ratio']:.4f} >= 0.97, smoke trace "
              f"{st['events']} events ({st['dropped']} dropped, "
              f"{st['recompiles']} recompiles), device/host p50 "
              f"{st['decode_device_p50_ms']:.2f}/"
              f"{st['decode_host_p50_ms']:.2f} ms"
              + (f" -> {st['path']}" if "path" in st else ""), flush=True)
    hot = None
    if args.hotpath:
        hot = run_hotpath_gate(args)
        print(f"hotpath: {hot['hotpath']:.1f} vs baseline "
              f"{hot['baseline']:.1f} tok/s "
              f"(median ratio {hot['ratio']:.3f}x >= 1.15), host p50 "
              f"{hot['decode_host_p50_ms']:.2f} ms = "
              f"{hot['host_fraction_p50']:.3f} of step "
              f"{hot['decode_step_p50_ms']:.2f} ms (<= 0.25), "
              f"lookahead {hot['pipeline']['lookahead_steps']} / bubbles "
              f"{hot['pipeline']['bubbles']}, executables 1/1", flush=True)
    spec = None
    if args.spec:
        spec = run_spec(args)
        base = spec["baseline"]["tokens_per_s"]
        print(f"serve_spec_paged@8b,{base:.1f},"
              f"cell=d{spec['cell']['d_model']}L{spec['cell']['num_layers']};"
              f"k={spec['k']}", flush=True)
        for bits, d in spec["drafts"].items():
            print(f"serve_spec@{bits}b_draft,{d['tokens_per_s']:.1f},"
                  f"acceptance={d['acceptance_rate']:.3f};"
                  f"speedup={d['speedup_vs_paged']:.2f}x;"
                  f"p50={d['decode_step_p50_ms']:.2f}ms;"
                  f"p99={d['decode_step_p99_ms']:.2f}ms", flush=True)
    if args.out:
        write_record(args, rows, args.out, paged_mixed=mixed,
                     speculative=spec, paged_gate=gate, kv_quant=kv,
                     multi_tenant=mt, observability=obs, hotpath=hot)
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
