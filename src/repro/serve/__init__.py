"""repro.serve — continuous batching over a PAGED KV cache for
ReLeQ-quantized models.

Why
---
The paper's payoff is inference: a learned mixed-precision policy buys
~2.2x over 8-bit execution, but only if the deployment path keeps the
hardware busy.  Iteration-level (Orca-style) batching fixes the padding
waste of static batches; block-granular (vLLM-style) paging fixes the
two costs that remained:

- **memory**: a slot pool gives every sequence a ``max_len``-sized cache
  row, so mixed-length traffic wastes most of the pool.  The paged pool
  hands out fixed-size KV *blocks* on demand — at equal cache bytes it
  runs strictly more concurrent sequences (pinned in the benchmark).
- **compile churn**: full-prompt prefill compiles one executable per
  distinct prompt length.  Chunked prefill feeds fixed-shape chunks with
  (seq, start, valid) as data — ONE prefill + ONE decode executable for
  any traffic mix (pinned via jit cache counters).

Architecture (one file per concern)
-----------------------------------
- ``request.py``   Request / SamplingParams (greedy / temperature /
  top-k / top-p nucleus) / token selection; replay bookkeeping for
  preemption resume.
- ``queue.py``     FIFO admission queue with optional backpressure;
  ``push_front`` requeues preempted sequences at the head.
- ``cache.py``     ``PagedCachePool`` — transformer K/V as a
  ``(L, num_blocks, block_size, KV, hd)`` block pool + per-sequence block
  tables (physical block 0 is a reserved garbage sink for idle decode
  rows); O(1)-state leaves (Mamba ``ssm_*``, RWKV ``wkv``/token-shift)
  keep slot semantics behind the same interface via
  ``models.model.cache_batch_axis``.  Sliding-window archs keep their
  ring layout — the block size shrinks to divide the ring length.
  ``SlotCachePool`` is the legacy slot pool, kept one release behind
  ``--cache slot`` as the parity baseline.
- ``scheduler.py`` ``ContinuousScheduler`` — admits on free row + free
  blocks for the whole prompt, reserves one token of growth per running
  sequence before each decode, and on block exhaustion *preempts and
  requeues the youngest sequence* (recompute-style: re-admission replays
  prompt + emitted tokens; greedy decode makes the replay exact, so the
  client-visible stream is unchanged).
- ``engine.py``    ``ServeEngine(cache="paged"|"slot")`` — ``submit()`` /
  ``step()`` / ``run_until_drained()`` + per-request (TTFT, latency,
  preemptions) and aggregate (tokens/s, row + block occupancy) metrics.
  ``ServeEngine.from_params`` packs training params at a ReLeQ
  ``QuantPolicy`` once, at construction.  ``spec=SpecConfig(...)`` turns
  on speculative decoding with a quantized self-draft (``repro.spec``):
  the same packed weights re-read at fewer bitplanes roll k tokens per
  window through the SAME paged block tables (zero extra KV blocks), one
  fixed-shape ``verify_chunk`` call scores all k+1 positions at the
  serving policy, and exact rejection sampling keeps the emitted stream
  distribution-identical to non-speculative serving.

Decode attends by block table through ``kernels.ops.paged_attention``: a
Pallas kernel that folds several pages of a row into each grid step, its
page operands indexed through the scalar-prefetched block table, and
skips the steps past a row's length (each live block copied once —
``kernels/paged_attention.py``), with a gather + ``decode_attention``
oracle in ``kernels/ref.py`` as the CPU path.

Use
---
    from repro.serve import ServeEngine, SamplingParams
    engine = ServeEngine.from_params(model, params, policy, num_slots=8,
                                     max_len=256, block_size=16)
    rid = engine.submit(prompt_ids, max_new_tokens=64)
    engine.run_until_drained()
    tokens, stats = engine.output(rid), engine.metrics()

CLI: ``python -m repro.launch.serve --mode continuous [--cache slot]``.
Benchmark: ``python -m benchmarks.serve_bench`` (static vs slot vs paged
per bitwidth + the mixed-prompt-length paged section; CI uploads its
``BENCH_serve.json``).

Guarantees
----------
- Paged output is token-for-token identical to the slot engine — and the
  slot engine to the legacy static loop — for the same request stream
  (greedy, all three model families; pinned in
  ``tests/test_serve_paged.py`` / ``tests/test_serve_engine.py``).
- Speculative output is token-identical to non-speculative under greedy
  and distribution-exact at temperature>0 (chi-square gated), for ANY
  draft policy — acceptance only moves speed, never the stream.
- Allocator exactness (hypothesis-tested): no double-alloc, no leak,
  free-list exhaustion surfaces as preemption, never a crash.

Sharding: pass ``mesh=`` and the pool is placed over the mesh's data
axes via ``repro.dist`` — the paged pool's *block* axis sits where the
slot axis did, so ``cache_specs`` covers both (parity pinned in
``tests/test_distributed.py::test_sharded_pool_parity``).  The engine
runs each step under its mesh and XLA partitions it.  XLA cannot
partition a Pallas kernel, so ``kernels/ops`` places each in a
``shard_map``: rows split over the data axes, weights and the KV pool
gathered whole onto every device.  A partitioned step rounds differently from a one-device step:
its logits agree to a tolerance, not bitwise, and greedy streams may
part at a near-tie (pinned in
``tests/test_distributed.py::test_sharded_paged_step_matches_one_device``).
Admission is still a single-host decision; making it collective across
hosts is the recorded ROADMAP follow-up.

Known limits (ROADMAP "Open items"): no beam search / logit bias,
single-host admission, no block sharing between sequences (prefix
caching) yet.
"""
from repro.serve.cache import PagedCachePool, SlotCachePool
from repro.serve.engine import ServeEngine
from repro.serve.queue import AdmissionQueue
from repro.serve.request import Request, RequestState, SamplingParams
from repro.serve.scheduler import ContinuousScheduler

__all__ = [
    "AdmissionQueue", "ContinuousScheduler", "PagedCachePool", "Request",
    "RequestState", "SamplingParams", "ServeEngine", "SlotCachePool",
]
