"""Serving launcher: ``python -m repro.launch.serve --arch <id> --bits 4``.

Thin CLI over :class:`repro.serve.ServeEngine`.  Loads (or initializes)
params, packs them at a ReLeQ policy, and serves a synthetic workload:

- ``--mode continuous`` (default): staggered-arrival requests with
  heterogeneous output lengths, admitted mid-decode — reports tokens/s,
  per-request TTFT, row occupancy and (paged) preemptions + block
  occupancy.  ``--cache paged`` (default) uses the block-granular pool
  with chunked prefill; ``--cache slot`` keeps the legacy slot pool for
  one release as the parity baseline.
- ``--mode static``: the legacy one-shot fixed-batch greedy loop (kept
  as the parity/latency baseline).

``--kv-bits B [B ...]`` quantizes the paged KV blocks themselves (int8
codes + per-token-head scales, nibble-packed at 4 bits; one value per
layer or one for all) — ``--kv-oracle`` serves the same tokens from the
dequantized fp values as a parity check.

``--prefix-cache`` (default on, paged only) shares full prompt-prefix
KV blocks across sequences via the pool's refcounted trie; ``--tenants
N`` shapes the synthetic workload into N tenants sharing a system
prompt so the hit-rate/shared-blocks printout exercises it.

``--spec-k K --draft-bits B`` turns on speculative decoding with the
quantized self-draft (``repro.spec``): the same packed weights re-read
at B bitplanes roll K tokens per window and one batched verify call
scores them against the full-precision policy — output is distribution-
exact, so every other flag means the same thing with spec on.  Paged
cache only.

Token selection runs on device by default (``repro.serve.sampler``) with
a one-step-lookahead decode pipeline hiding the host loop —
``--host-sampling`` falls back to fetching full logits and sampling in
Python (the bisectable legacy path), ``--no-pipeline`` keeps device
sampling but dispatches synchronously; the summary line reports which
path ran plus the lookahead/bubble counts.

Observability (``repro.obs``): ``--trace out.json`` records the full
request lifecycle (queue wait, engine steps, admissions with their
prefill chunks, logits fetch and first-token choice, decode dispatch /
wait / host split, spec windows, preempt/COW/evict instants) as a
Chrome-trace file loadable at ui.perfetto.dev; ``--metrics-interval N``
logs one ``snapshot`` line every N steps (tokens, tokens/s, the
engine's queued and running request counts, recompiles);
``--log-json`` switches all structured logs to JSON lines.  Run under
``jax.profiler.trace(dir)`` to get the same spans beside the device's
ops on one timeline.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import build_model
from repro.quant.policy import QuantPolicy
from repro.quant.qat import policy_for
from repro.serve import SamplingParams, ServeEngine
from repro.train.serve import make_decode_step, quantize_for_serving


def build(cfg, *, bits: int = 4, policy_json=None, ckpt_dir=None):
    """Model for ``cfg``, its params (restored from ``ckpt_dir``, else
    random from seed 0) packed for serving, and the policy that packed
    them (``policy_json``, else ``bits`` with the frozen groups at 8)."""
    model = build_model(cfg)
    if ckpt_dir:
        from repro import ckpt as ckpt_lib

        tree, _, step = ckpt_lib.restore(ckpt_dir)
        params = jax.tree.map(jnp.asarray, tree["params"])
        print(f"restored step {step} from {ckpt_dir}")
    else:
        params = model.init(jax.random.PRNGKey(0))
    if policy_json:
        policy = QuantPolicy.from_file(policy_json)
    else:
        policy = policy_for(model, default_bits=bits)
    return model, quantize_for_serving(model, params, policy), policy


def _static(args, cfg, model, sparams, policy):
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    logits, cache = model.prefill(sparams, tokens=prompts,
                                  max_len=args.prompt_len + args.gen + 1)
    dec = make_decode_step(model, donate=False)
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    t0 = time.time()
    toks = [tok]
    for _ in range(args.gen):
        logits, cache = dec(sparams, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        toks.append(tok)
    dt = time.time() - t0
    print(f"served batch={args.batch} gen={args.gen} at "
          f"{dt / args.gen * 1e3:.1f} ms/token-step "
          f"(avg policy {policy.average_bits():.1f} bits)")
    print("first sequence:", jnp.concatenate(toks, 1)[0].tolist())


def _continuous(args, cfg, model, sparams, policy):
    from repro.obs import get_logger
    from repro.obs.trace import Tracer
    from repro.spec import SpecConfig

    max_len = args.prompt_len + args.gen + 1
    spec = (SpecConfig(k=args.spec_k, draft_bits=args.draft_bits)
            if args.spec_k else None)
    kv_kw = {}
    if args.kv_bits:
        kv_kw["kv_bits"] = (args.kv_bits[0] if len(args.kv_bits) == 1
                            else args.kv_bits)
        kv_kw["kv_oracle"] = args.kv_oracle
    tracer = Tracer(enabled=True) if args.trace else None
    if tracer is not None:
        tracer.name_thread("serve-loop")
    engine = ServeEngine(model, sparams, num_slots=args.num_slots,
                         max_len=max_len, cache=args.cache,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         prefill_chunk=args.prefill_chunk,
                         prefix_cache=args.prefix_cache,
                         sample_device=not args.host_sampling,
                         pipeline=not args.no_pipeline,
                         spec=spec, tracer=tracer, **kv_kw)
    mlog = get_logger("serve.metrics")
    rng = np.random.default_rng(1)
    gens = [int(g) for g in
            rng.integers(max(1, args.gen // 2), args.gen + 1, args.requests)]
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len))
    if args.tenants:
        # multi-tenant mix: requests of one tenant share a system-prompt
        # prefix (3/4 of the prompt), exercising the prefix cache
        shared = args.prompt_len * 3 // 4
        sys_prompts = rng.integers(0, cfg.vocab_size,
                                   (args.tenants, shared))
        for i in range(args.requests):
            prompts[i, :shared] = sys_prompts[i % args.tenants]
    sampling = SamplingParams(temperature=args.temperature)
    submitted = 0
    while submitted < args.requests or engine.scheduler.has_work():
        # staggered arrivals: a fresh request every --arrival-every steps
        while (submitted < args.requests
               and engine.steps >= submitted * args.arrival_every):
            engine.submit(prompts[submitted], gens[submitted] + 1,
                          sampling=sampling)
            submitted += 1
        engine.step()
        if args.metrics_interval and engine.steps % args.metrics_interval == 0:
            m = engine.metrics()
            mlog.event("snapshot", step=engine.steps,
                       tokens=m["tokens_total"],
                       tokens_per_s=m["tokens_per_s"],
                       queued=engine.num_queued, running=engine.num_running,
                       recompiles=m["recompiles"])
    m = engine.metrics()
    print(f"served {args.requests} requests on {args.num_slots} "
          f"{args.cache} rows (avg policy {policy.average_bits():.1f} bits)")
    print(f"tokens/s={m['tokens_per_s']:.1f} occupancy={m['mean_occupancy']:.2f} "
          f"decode_steps={m['decode_steps']} tokens={m['tokens_total']}"
          + (f" preemptions={m['preemptions']} "
             f"block_occ={m['mean_block_occupancy']:.2f}"
             if args.cache == "paged" else ""))
    sm, pl = m["sampler"], m["pipeline"]
    print(f"sampler={'device' if sm['device'] else 'host'} "
          f"fallbacks={sm['fallbacks']} "
          f"pipeline={'on' if pl['enabled'] else 'off'} "
          f"lookahead={pl['lookahead_steps']} bubbles={pl['bubbles']} "
          f"device/host p50={m['decode_device_p50_ms']:.2f}/"
          f"{m['decode_host_p50_ms']:.2f} ms")
    if args.cache == "paged":
        pc = m["prefix_cache"]
        print(f"prefix_cache={'on' if pc['enabled'] else 'off'} "
              f"hit_rate={m['prefix_hit_rate']:.3f} "
              f"hits={m['prefix_hits']}/{m['prefix_lookups']} lookups "
              f"blocks_shared={m['blocks_shared']:.1f} "
              f"prefill_launches={m['prefill_launches']} "
              f"hit_tokens={pc['hit_tokens']} cow={pc['cow_copies']} "
              f"evictions={pc['evictions']}")
    if "spec" in m:
        s = m["spec"]
        print(f"spec k={s['k']} draft_bits={args.draft_bits} "
              f"windows={s['windows']} "
              f"acceptance={s['acceptance_rate']:.3f} "
              f"({s['accepted']}/{s['proposed']})")
    for r in m["requests"]:
        print(f"  req {r['id']}: {r['new_tokens']} tokens, "
              f"ttft={r['ttft_steps']} steps / {r['ttft_s'] * 1e3:.0f} ms, "
              f"latency={r['latency_s'] * 1e3:.0f} ms")
    print("first sequence:", engine.output(0))
    if tracer is not None:
        tracer.save(args.trace)
        print(f"wrote {tracer.num_events} trace events "
              f"({tracer.dropped} dropped) to {args.trace} — open at "
              f"ui.perfetto.dev or chrome://tracing")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--mode", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--policy-json", default=None)
    ap.add_argument("--batch", type=int, default=4,
                    help="static mode: fixed batch size")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="continuous mode: max concurrent sequences")
    ap.add_argument("--cache", choices=("paged", "slot"), default="paged",
                    help="paged: block-granular pool + chunked prefill "
                         "(one executable for any prompt mix); slot: "
                         "legacy slot pool, kept one release for parity")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged cache: tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged cache: physical KV blocks (default: full "
                         "slot-equivalent capacity; less oversubscribes "
                         "and may preempt)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="paged cache: fixed prefill chunk length")
    ap.add_argument("--kv-bits", type=int, nargs="+", default=None,
                    help="paged cache: quantize KV blocks to this many "
                         "bits (one value for all layers, or one per "
                         "layer; int8 codes + per-token-head scales, "
                         "nibble-packed at 4; requires --cache paged)")
    ap.add_argument("--kv-oracle", action="store_true",
                    help="store the dequantized fp KV values instead of "
                         "codes (parity oracle for --kv-bits; same "
                         "tokens, fp-size pool)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="paged cache: share full prompt-prefix KV blocks "
                         "across sequences (refcounted copy-on-write; "
                         "auto-off for ring/recurrent families)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="continuous mode: group requests into this many "
                         "tenants sharing a system-prompt prefix (0 = "
                         "fully unique prompts)")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous mode: synthetic workload size")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="continuous mode: steps between request arrivals")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="continuous mode: speculate this many tokens per "
                         "window with the quantized self-draft (0 = off; "
                         "requires --cache paged)")
    ap.add_argument("--draft-bits", type=int, default=2,
                    help="bitwidth of the self-draft's packed-weight view "
                         "(fewer bitplanes read per draft step)")
    ap.add_argument("--host-sampling", action="store_true",
                    help="continuous mode: select tokens on the host from "
                         "fetched logits (the bisectable legacy path) "
                         "instead of the on-device fused sampler; implies "
                         "--no-pipeline")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="continuous mode: disable the one-step-lookahead "
                         "decode pipeline (synchronous dispatch/fetch "
                         "every step)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="continuous mode: record a Chrome-trace of the "
                         "run (queue wait, engine steps, admissions, "
                         "prefill chunks, decode dispatch/wait/host "
                         "split, spec windows, preempt/COW/evict "
                         "instants) — open at ui.perfetto.dev")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="continuous mode: log a snapshot line (tokens, "
                         "tokens/s, queued and running requests, "
                         "recompiles) every N engine steps (0 = off)")
    ap.add_argument("--log-json", action="store_true",
                    help="structured logs as JSON lines instead of text")
    args = ap.parse_args()

    compile_cache.enable()
    if args.log_json:
        from repro.obs import configure
        configure(json_mode=True)
    cfg = get_config(args.arch, smoke=args.smoke)
    model, sparams, policy = build(cfg, bits=args.bits,
                                   policy_json=args.policy_json,
                                   ckpt_dir=args.ckpt_dir)
    if args.mode == "static":
        _static(args, cfg, model, sparams, policy)
    else:
        _continuous(args, cfg, model, sparams, policy)


if __name__ == "__main__":
    main()
