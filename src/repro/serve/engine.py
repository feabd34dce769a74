"""ServeEngine: the top-level continuous-batching serve loop.

``submit()`` enqueues a request; ``step()`` runs one engine iteration
(admit -> prefill new sequences -> one packed decode step over every
running row); ``run_until_drained()`` steps until queue and rows are
empty.  Weights stay bit-packed (``quant.pack``) at a ReLeQ
``QuantPolicy`` for the whole lifetime of the engine — quantization cost
is paid once at construction, not per request.

Two cache backends (``cache=`` / ``launch/serve.py --cache``):

- ``"paged"`` (default): block-granular ``PagedCachePool``.  Admission
  runs *fixed-shape chunked prefill* directly into the sequence's blocks
  — any mix of prompt lengths compiles exactly ONE prefill executable and
  ONE decode executable (the slot path compiles a prefill per distinct
  prompt length).  Before each decode the scheduler reserves one token of
  growth per running sequence; block exhaustion preempts-and-requeues the
  youngest sequence, whose re-admission replays prompt + emitted tokens
  (deterministic greedy decode ⇒ the client-visible stream is unchanged).
- ``"slot"``: the legacy slot pool (full-prompt prefill + splice), kept
  one release as the parity baseline.

Prefix caching (paged, ``prefix_cache=True`` default): admission consults
the pool's refcounted trie (serve/cache.py) and maps a shared prompt
prefix into the new sequence's block table with increfs — the chunked
prefill then runs only over the tail, so N tenants sharing a system
prompt prefill it once.  Divergent writes copy-on-write, the admission
gate counts new blocks only (higher admitted concurrency at equal cache
bytes), and a cache-hit sequence is token-identical to a cold one
(parity-gated in tests/test_prefix_cache.py).  Disabled automatically
for ring/recurrent families where paged KV is not the whole state.

Numerics: the decode step is row-independent (per-sequence attention/SSM
state, drop-free MoE routing in decode), so a request's tokens are
bit-identical whether it shares the batch with 0 or ``num_slots - 1``
other sequences — and the paged decode gathers each sequence's pages into
exactly the contiguous rows the slot pool stores, which is what pins
paged-vs-slot token parity (tests/test_serve_paged.py).

Speculative decoding (``spec=SpecConfig(...)``, paged cache only): each
step the engine rolls up to ``spec.k`` tokens per row with a *quantized
self-draft* — the same packed weights re-packed at fewer bitplanes
(``repro.spec``), reading and writing the SAME ``PagedCachePool`` blocks
through the row's block table, so speculation allocates zero extra KV —
then scores all ``k + 1`` positions of every row in ONE batched
``verify_chunk`` call and resolves each window with the
distribution-exact rejection sampler (``repro.spec.sampler``).  Greedy
spec output is token-identical to non-spec decode; sampled output is
exactly target-distributed.  EOS / ``max_new_tokens`` can land anywhere
inside a window (multi-token emission per step).

One-token hotpath (``sample_device=True`` / ``pipeline=True``, both
default): token selection runs ON DEVICE (``serve/sampler.py``) so each
decode step fetches a ``(num_slots,) int32`` token vector instead of the
``(num_slots, V)`` logits matrix, and the sampled vector is itself the
next step's input — ``last_token`` lives in a device-resident buffer.
On top of that sits a ONE-STEP-LOOKAHEAD pipeline.  Timeline, one row::

    synchronous (host sampling, pre-PR-9):
        [dispatch t][--device t--][fetch (B,V)][sample/bookkeep t] ->
        [dispatch t+1][--device t+1--][fetch][sample/bookkeep t+1] ...
        host work sits on the critical path every step.

    pipelined (device sampling + lookahead):
        [dispatch t][dispatch t+1][fetch tokens t][bookkeep t]
                     (device runs t, then t+1, back to back)
        step t+1 is dispatched BEFORE step t's tokens are fetched, so
        the fetch + Python bookkeeping of step t overlap step t+1's
        device compute.  Steady-state host work is off the critical
        path; ``decode.wait`` (the host blocked on the token fetch)
        absorbs the wait and ``decode.host`` shrinks toward zero.

    The lookahead only launches when the next step is *composition-
    stable*: nothing queued to admit, every running request has budget
    for one more token after this step, and the scheduler can reserve
    the extra write position without preempting
    (``scheduler.reserve_lookahead``).  Any other step falls back to
    the synchronous order and counts ``pipeline.bubbles``.  Arrivals
    are never delayed by an in-flight step: ``step()`` admits BEFORE
    syncing it (admission touches only free rows and free blocks), and
    the composition change just bubbles that step's chain.  A realized
    EOS inside a lookahead only invalidates that row's phantom token
    (decode is row-independent): the token is discarded at sync, the
    phantom KV write at ``cached_len`` lands in a block that is never
    full (so never published to the prefix trie) and is fully rewritten
    by the next occupant's prefill before it is read.  Escape hatches:
    ``--host-sampling`` / ``--no-pipeline`` on ``launch/serve.py``.

Metrics: per-request TTFT (seconds *and* engine steps), wall latency,
token counts and preemptions, plus aggregate tokens/s, p50/p99 per-step
decode latency, mean row occupancy, (paged) mean block occupancy, and
(spec) windows/proposed/accepted counts with the acceptance rate.

Observability (``repro.obs``): every aggregate above lives in a typed
instrument on the engine's metrics :class:`~repro.obs.Registry`
(``registry=`` to share one across engines; ``engine.obs.snapshot()``
is the JSON view) — ``metrics()`` is rebuilt on the registry with
byte-compatible keys and the same ``metrics_window`` sliding-window
percentile semantics.  A :class:`~repro.obs.Tracer` (``tracer=``)
records the full request lifecycle as Chrome-trace spans: queue wait
(retro-dated to enqueue), each engine step (``serve.step``), admission
with prefix hit/replay counts, every prefill chunk, the host's wait for
the prefill's logits (``admit.fetch``) and its choice of the first
token (``admit.sample``), each decode step split into dispatch, the
host blocked on the result fetch (``decode.wait``) and host bookkeeping
(``decode.host``), speculative draft/verify/fix-up phases with
per-window acceptance, preempt instants, pool COW/eviction/flush
instants, and an ``xla.compile`` instant whenever a jit cache grows
(``_cache_size`` delta — steady state must show zero).  While a
``jax.profiler`` session records, every span and instant is also a
profiler annotation on the device trace's clock — also with the default
disabled tracer — so each idle stretch of the device can be charged to
the host phase open over it.  Disabled tracing costs one attribute
check and one ``TraceAnnotation.is_enabled()`` per call site (<= 3%%
tokens/s, gated in ``benchmarks/serve_bench.py``).  See
``docs/metrics.md``.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Registry
from repro.obs.trace import NULL_TRACER
from repro.quant.policy import QuantPolicy
from repro.serve.cache import PagedCachePool, SlotCachePool
from repro.serve.queue import AdmissionQueue
from repro.serve.request import Request, SamplingParams
from repro.serve.sampler import row_arrays, sample_rows
from repro.serve.scheduler import ContinuousScheduler
from repro.train.serve import (
    make_chunked_prefill,
    make_decode_step,
    make_prefill,
    make_verify_chunk,
)


@dataclass
class _Inflight:
    """One dispatched-but-unsynced decode step: the sampled token vector
    (a ``(num_slots,) int32`` device array, possibly still computing),
    the emission positions it was sampled at, and a snapshot of the rows
    it covered (identity-checked at sync — a row that turned over since
    dispatch carried a phantom token, which is discarded)."""

    tokens: object        # (num_slots,) int32 device array
    positions: object     # (num_slots,) int32 device array
    rows: dict            # slot -> RunningSeq at dispatch time


class ServeEngine:
    def __init__(self, model, sparams, *, num_slots: int = 8,
                 max_len: int = 256, cache: str = "paged",
                 block_size: int = 16, num_blocks: int | None = None,
                 prefill_chunk: int = 16, max_pending: int = 0,
                 decode_fn=None, prefill_fn=None, mesh=None,
                 spec=None, verify_fn=None, kv_bits=None,
                 kv_oracle: bool = False, metrics_window: int = 512,
                 prefix_cache: bool = True, registry=None, tracer=None,
                 sample_device: bool = True, pipeline: bool = True):
        if cache not in ("paged", "slot"):
            raise ValueError(f"cache={cache!r} (want 'paged' or 'slot')")
        if (kv_bits is not None or kv_oracle) and cache != "paged":
            raise ValueError("kv_bits / kv_oracle require cache='paged' "
                             "(the slot pool stores fp KV only)")
        if metrics_window < 1:
            raise ValueError("metrics_window must be >= 1")
        self.model = model
        self.sparams = sparams
        self.cache_kind = cache
        # observability: a private registry/disabled tracer by default —
        # pass shared ones to aggregate across engines or record a trace
        self.obs = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # mesh != None places the KV pool over the mesh's data axes
        # (repro.dist sharding hook), and every step runs under the mesh,
        # which XLA partitions over it (see step())
        self.mesh = mesh
        if cache == "paged":
            self.pool = PagedCachePool(model, num_slots, max_len,
                                       block_size=block_size,
                                       num_blocks=num_blocks, mesh=mesh,
                                       kv_bits=kv_bits, kv_oracle=kv_oracle,
                                       prefix_cache=prefix_cache)
            self._prefill = prefill_fn or make_chunked_prefill(model)
            self.prefill_chunk = prefill_chunk
        else:
            self.pool = SlotCachePool(model, num_slots, max_len, mesh=mesh)
            self._prefill = prefill_fn or make_prefill(model)
        self.pool.tracer = self.tracer  # COW / eviction / flush instants
        self.queue = AdmissionQueue(max_pending)
        self.scheduler = ContinuousScheduler(self.pool, self.queue,
                                             registry=self.obs)
        # decode_fn/prefill_fn let callers share one jit cache across
        # engines (the benchmark warms up on a throwaway engine).  The
        # default decode donates the pool cache — step() immediately
        # replaces it, so XLA updates the KV buffers in place
        self._decode = decode_fn or make_decode_step(model, donate=True)
        # per-sequence token bound now lives on the pool (None for
        # recurrent/ring state, where any length fits)
        self._length_bound = self.pool.length_bound
        # speculative decoding: draft = the target's own packed weights at
        # a lower-bit policy, sharing this pool's blocks (repro.spec)
        self.spec = spec
        if spec is not None:
            if cache != "paged":
                raise ValueError("speculative decoding requires "
                                 "cache='paged'")
            self._verify = verify_fn or make_verify_chunk(model)
            self._draft_sparams = self._resolve_draft(spec)
        # one-token hotpath: device-side sampling feeds a device-resident
        # token buffer; the lookahead pipeline additionally dispatches
        # step t+1 before syncing step t (device sampling only — the
        # pipeline's whole point is not fetching logits, and spec already
        # amortizes host work over k+1 tokens per step)
        self._sample_device = bool(sample_device)
        self._pipeline_on = bool(pipeline and sample_device
                                 and spec is None)
        self._inflight: _Inflight | None = None
        self._row_sig = None      # batch-composition key for _row_params
        self._row_dev = None      # cached device sampling-param arrays
        self._next_id = 0
        self._step_idx = 0
        # every aggregate lives on the registry; ``metrics()`` reads the
        # instruments back with byte-compatible keys.  Latency series are
        # windowed histograms — bounded host memory on a long-lived
        # engine, and percentiles are a sliding window over the last
        # ``metrics_window`` decode steps, identical to the full history
        # on runs shorter than the window (the old deque semantics)
        obs = self.obs
        TOK = (1, 4, 16, 64, 256, 1024, 4096)   # token-count boundaries
        self._c_tokens = obs.counter("serve.tokens_total", unit="tokens")
        self._c_decode_steps = obs.counter("serve.decode_steps", unit="steps")
        self._c_run_seconds = obs.counter("serve.run_seconds", unit="s")
        self._c_occ_sum = obs.counter("serve.occupancy_sum")
        self._c_block_occ_sum = obs.counter("serve.block_occupancy_sum")
        self._c_attn_live_pages = obs.counter(
            "serve.attn_live_pages", unit="pages",
            desc="KV pages the running rows' decode attention reads")
        self._c_prefill_launches = obs.counter("serve.prefill_launches")
        self._c_recompiles = obs.counter(
            "serve.recompiles", desc="jit cache growth after construction")
        self._h_decode = obs.histogram("serve.decode_step_seconds", unit="s",
                                       window=metrics_window)
        self._h_decode_tok = obs.histogram("serve.decode_tok_seconds",
                                           unit="s", window=metrics_window)
        self._h_device = obs.histogram("serve.decode_device_seconds",
                                       unit="s", window=metrics_window)
        self._h_host = obs.histogram("serve.decode_host_seconds", unit="s",
                                     window=metrics_window)
        self._h_queue_wait = obs.histogram("serve.queue_wait_seconds",
                                           unit="s", window=metrics_window)
        # prefix-cache observability, same bounded-window discipline:
        # (hit, replay) token pairs per admission -> windowed hit rate
        # (appended together, so the two windows stay aligned); a
        # per-step sample of the pool's shared-block gauge -> window mean
        self._h_admit_hit = obs.histogram("prefix.admit_hit_tokens",
                                          unit="tokens", buckets=TOK,
                                          window=metrics_window)
        self._h_admit_total = obs.histogram("prefix.admit_replay_tokens",
                                            unit="tokens", buckets=TOK,
                                            window=metrics_window)
        self._h_shared = obs.histogram("prefix.blocks_shared", unit="blocks",
                                       buckets=TOK, window=metrics_window)
        # the host's choice of each admission's first token: admissions
        # that chose one, and the seconds spent choosing (``admit.sample``)
        self._c_first_tokens = obs.counter(
            "serve.first_tokens", unit="requests",
            desc="admissions that chose a first token")
        self._c_first_token_s = obs.counter(
            "serve.first_token_host_seconds", unit="s",
            desc="host time choosing admissions' first tokens")
        self._c_spec_windows = obs.counter("spec.windows")
        self._c_spec_proposed = obs.counter("spec.proposed", unit="tokens")
        self._c_spec_accepted = obs.counter("spec.accepted", unit="tokens")
        # hotpath observability: lookahead dispatches vs bubbles (steps
        # that fell back to the synchronous order while the pipeline was
        # on), and spec steps that fell back to the full-logits host
        # resolve because not every window was greedy
        self._c_lookahead = obs.counter(
            "pipeline.lookahead", unit="steps",
            desc="decode steps dispatched before the previous sync")
        self._c_bubbles = obs.counter(
            "pipeline.bubbles", unit="steps",
            desc="pipeline-on steps that ran synchronously")
        self._c_fallbacks = obs.counter(
            "sampler.fallbacks", unit="steps",
            desc="device-sampling steps resolved via host logits fetch")
        # device time inside the current step, accumulated by the decode/
        # spec paths and split out of the step wall time by ``step()``
        self._device_seconds = 0.0
        # jit-cache baselines for compile/recompile detection (a shared
        # pre-warmed fn starts above zero; only *growth* is an event)
        self._exec_sizes: dict[str, int] = {}
        for kind, fn in (("prefill", self._prefill), ("decode", self._decode),
                         ("verify", getattr(self, "_verify", None)),
                         ("sample", sample_rows if sample_device else None)):
            size_fn = getattr(fn, "_cache_size", None)
            if size_fn is not None:
                self._exec_sizes[kind] = size_fn()
        self.requests: dict[int, Request] = {}

    @classmethod
    def from_params(cls, model, params, policy: QuantPolicy, **kw):
        """Quantize + bit-pack training params at ``policy`` and serve."""
        from repro.train.serve import quantize_for_serving

        return cls(model, quantize_for_serving(model, params, policy), **kw)

    def _resolve_draft(self, spec):
        """SpecConfig -> draft serving params (most-specific source wins:
        pre-packed sparams > per-group policy > uniform draft_bits)."""
        if spec.draft_sparams is not None:
            return spec.draft_sparams
        from repro.spec.draft import low_bit_view

        return low_bit_view(self.model, self.sparams,
                            bits=spec.draft_bits, policy=spec.draft_policy)

    # ------------------------------------------------------------- frontend
    def submit(self, prompt, max_new_tokens: int,
               sampling: SamplingParams | None = None,
               eos_id: int | None = None) -> int:
        req = Request(self._next_id, np.asarray(prompt), max_new_tokens,
                      sampling or SamplingParams(), eos_id)
        if self._length_bound is not None and req.total_len() > self._length_bound:
            raise ValueError(
                f"request needs {req.total_len()} cache tokens > pool "
                f"max_len {self._length_bound}")
        req.arrival_step = self._step_idx
        self.queue.push(req)  # may raise (backpressure): nothing registered
        self._next_id += 1
        self.requests[req.request_id] = req
        return req.request_id

    @property
    def steps(self) -> int:
        return self._step_idx

    @property
    def num_queued(self) -> int:
        return len(self.queue)

    @property
    def num_running(self) -> int:
        return self.scheduler.num_running

    def _note_exec(self, kind: str, fn) -> None:
        """Emit an ``xla.compile`` instant + counter bump when a jit cache
        grew past its last observed size — steady-state serving must show
        zero of these after warmup (acceptance-gated in serve_bench)."""
        size_fn = getattr(fn, "_cache_size", None)
        if size_fn is None:
            return
        sz = size_fn()
        prev = self._exec_sizes.get(kind, 0)
        if sz > prev:
            self._exec_sizes[kind] = sz
            self._c_recompiles.inc(sz - prev)
            self.tracer.instant("xla.compile", kind=kind, cache_size=sz,
                                step=self._step_idx)

    # ------------------------------------------------------------- prefill
    def _admit_slot(self, req: Request, slot: int):
        """Legacy path: full-prompt prefill at its exact length + splice."""
        with self.tracer.span("prefill.full", request=req.request_id,
                              tokens=len(req.prompt)):
            logits, cache1 = self._prefill(
                self.sparams, jnp.asarray(req.prompt)[None, :],
                self.pool.max_len)
            self.pool.write(slot, cache1)
        self._note_exec("prefill", self._prefill)
        self._c_prefill_launches.inc()
        with self.tracer.span("admit.fetch", request=req.request_id):
            row = np.asarray(logits)[0, -1]  # blocks until prefill lands
        return self._first_token(req, row), len(req.prompt), True

    def _admit_paged(self, req: Request, seq: int, hit: int = 0):
        """Chunked prefill straight into the sequence's blocks.  Every
        chunk call has the same shapes — one executable total (``start``
        is data, so a prefix-cache tail starting mid-prompt reuses it
        too).  ``hit`` tokens were already mapped from the prefix trie by
        the scheduler (``pool.map_shared``): only the tail is prefilled,
        beginning at the shared boundary — block-aligned, or one token
        shy of it when the whole prompt hit and the last block was COW'd
        at admission (the tail token's logits seed sampling).  On resume
        after preemption the prompt + emitted tokens are replayed (exact
        recompute, minus whatever the trie still holds) and no new token
        is emitted."""
        replay = req.replay_tokens()
        C = self.prefill_chunk
        logits = None
        for lo in range(hit, len(replay), C):
            piece = replay[lo:lo + C]
            valid = len(piece)
            buf = np.zeros((1, C), np.int32)
            buf[0, :valid] = piece
            with self.tracer.span("prefill.chunk", seq=seq, start=lo,
                                  valid=valid, request=req.request_id):
                logits, cache = self._prefill(
                    self.sparams, self.pool.step_cache(), jnp.asarray(buf),
                    seq, lo, valid)
                self.pool.accept(cache)
            self._note_exec("prefill", self._prefill)
            self._c_prefill_launches.inc()
        # the whole replay is now fed: record it so completed blocks
        # publish into the trie for the next tenant
        self.pool.record_tokens(seq, replay)
        req.prefix_cached_tokens += hit
        self._h_admit_hit.observe(hit)
        self._h_admit_total.observe(len(replay))
        if req.output_tokens:  # resume: last emitted token is the next feed
            return req.output_tokens[-1], len(replay), False
        with self.tracer.span("admit.fetch", request=req.request_id):
            row = np.asarray(logits)[0, 0]  # blocks until prefill lands
        return self._first_token(req, row), len(replay), True

    def _first_token(self, req: Request, row: np.ndarray) -> int:
        """Choose an admission's first token on the host from its fetched
        ``(V,)`` logits row, counted in ``serve.first_tokens`` and timed
        in ``serve.first_token_host_seconds``."""
        t = time.perf_counter()
        with self.tracer.span("admit.sample", request=req.request_id,
                              temperature=req.sampling.temperature):
            tok = req.select_token(row)
        self._c_first_token_s.inc(time.perf_counter() - t)
        self._c_first_tokens.inc()
        return tok

    # ----------------------------------------------------------------- loop
    def step(self) -> dict:
        """One engine iteration.  Returns the step's events:
        ``{"admitted": [ids], "tokens": [(id, tok)], "finished": [ids],
        "preempted": [ids]}``.

        With ``mesh=`` the step runs under ``jax.set_mesh(mesh)`` whatever
        the caller's context, so the model's sharding constraints (and
        MoE expert parallelism) always see the engine's mesh.
        """
        with (jax.set_mesh(self.mesh) if self.mesh is not None
              else contextlib.nullcontext()), \
                self.tracer.span("serve.step", step=self._step_idx):
            return self._step()

    def _step(self) -> dict:
        t0 = time.perf_counter()
        tr = self.tracer
        events = {"admitted": [], "tokens": [], "finished": [],
                  "preempted": []}

        # 0) a lookahead decode dispatched by the PREVIOUS step is this
        #    step's decode — it is synced below AFTER admissions.  A
        #    fully-stale inflight (every dispatched row finished at the
        #    last sync) is dropped without a fetch: its writes went to
        #    blocks that are rewritten before any read.
        inf = self._inflight
        self._inflight = None
        if inf is not None and not any(
                self.scheduler.running.get(s) is q
                for s, q in inf.rows.items()):
            inf = None

        # 1) admit queued requests into free rows (mid-decode is fine:
        #    running sequences are untouched, their blocks never move.
        #    An in-flight lookahead is no different — it reads and writes
        #    only blocks owned by the rows it was dispatched over, never
        #    the free/cached blocks admission draws from — so arrivals
        #    since its dispatch are admitted NOW, not one step late;
        #    _pipeline_tail sees the composition change and bubbles
        #    instead of chaining the newcomer a garbage feed)
        with tr.span("admit.schedule"):
            admissions = self.scheduler.admissions()
        for req, slot, hit in admissions:
            wait = time.perf_counter() - req.queued_time
            self._h_queue_wait.observe(wait)
            tr.complete("queue.wait", start=req.queued_time, dur=wait,
                        request=req.request_id,
                        requeued=req.preemptions > 0)
            with tr.span("admit", request=req.request_id, seq=slot,
                         prefix_hit_tokens=hit) as sp:
                if self.cache_kind == "paged":
                    tok, cached, emitted = self._admit_paged(req, slot, hit)
                else:
                    tok, cached, emitted = self._admit_slot(req, slot)
                sp.set(replay_tokens=cached,
                       new_tokens=cached - hit)
            if emitted:
                self._emit(req, tok, events)
            events["admitted"].append(req.request_id)
            self.scheduler.start(req, slot, tok, cached_len=cached)
            if req.done:  # 1-token budget (or instant EOS): row back now
                self._finish(self.scheduler.finish(slot), events)

        if inf is not None:
            # 2/3 pipelined) the in-flight lookahead IS this step's
            #    decode: its write positions were reserved at dispatch,
            #    so no reserve_for_decode — chain-or-bubble, then sync
            self._timed_decode(
                events, tr, lambda ev: self._pipeline_tail(inf, ev),
                mode="pipelined")
        else:
            # 2) reserve next-token blocks; exhaustion preempts youngest
            #    (spec mode reserves per-window inside _spec_step instead)
            if self.cache_kind == "paged" and self.spec is None:
                with tr.span("decode.reserve"):
                    preempted = self.scheduler.reserve_for_decode()
                for req in preempted:
                    events["preempted"].append(req.request_id)
                    tr.instant("preempt", request=req.request_id,
                               step=self._step_idx)

            # 3) one packed decode step (or speculative window) over every
            #    running row
            if self.scheduler.running:
                self._timed_decode(events, tr, self._sync_body,
                                   mode="spec" if self.spec is not None
                                   else "decode")

        self._step_idx += 1
        self._c_run_seconds.inc(time.perf_counter() - t0)
        return events

    def _timed_decode(self, events: dict, tr, body, mode: str) -> None:
        """Run one decode body under the ``decode.step`` span with the
        occupancy counters and the device/host wall-time split.
        Attribution (documented in docs/metrics.md): ``_device_seconds``
        is time spent DRIVING OR AWAITING the device — jit dispatch
        (``decode.dispatch`` span; a near-zero enqueue on async backends,
        the compute itself on synchronous ones) plus the host blocked on
        the token-vector fetch (``decode.wait`` span) — and
        ``decode.host`` is the rest of the step wall time: the Python
        serving loop (sampling on the legacy path, emit/advance
        bookkeeping, table uploads).  The pipelined loop times dispatch
        and sync separately, so the next step's dispatch is never folded
        into the current step's fetch wait."""
        self._c_occ_sum.inc(self.pool.occupancy())
        if self.cache_kind == "paged":
            self._c_block_occ_sum.inc(self.pool.block_occupancy())
            # each row attends over its length plus the token this step
            # writes, clamped to the row's window: ceil(eff_len / bs)
            self._c_attn_live_pages.inc(sum(
                self.pool.blocks_needed(seq.cached_len + 1)
                for seq in self.scheduler.running.values()))
            if self.pool.prefix_cache:
                self._h_shared.observe(self.pool.blocks_shared)
        self._c_decode_steps.inc()
        self._device_seconds = 0.0
        t_dec = time.perf_counter()
        n_tok = len(events["tokens"])
        with tr.span("decode.step", step=self._step_idx,
                     rows=len(self.scheduler.running), mode=mode) as sp:
            body(events)
            emitted = len(events["tokens"]) - n_tok
            sp.set(tokens=emitted)
        dt = time.perf_counter() - t_dec
        self._h_decode.observe(dt)
        if emitted > 0:  # an all-stale sync can emit 0; see metrics()
            self._h_decode_tok.observe(dt / emitted)
        self._h_device.observe(self._device_seconds)
        self._h_host.observe(max(dt - self._device_seconds, 0.0))

    def _sync_body(self, events: dict) -> None:
        """Decode body for a step with no pipelined predecessor."""
        if self.spec is not None:
            self._spec_step(events)
        elif self._sample_device:
            self._pipeline_tail(self._dispatch_decode(), events)
        else:
            self._decode_once(events)

    # ------------------------------------------------------ device hotpath
    def _row_params(self):
        """Device-resident per-row sampling parameters, re-uploaded only
        when the batch composition changes (slot -> request mapping)."""
        sched = self.scheduler
        sig = tuple(sorted((s, q.request.request_id)
                           for s, q in sched.running.items()))
        if sig != self._row_sig:
            arrs = row_arrays(self.pool.num_slots,
                              ((s, q.request)
                               for s, q in sched.running.items()))
            self._row_dev = tuple(jnp.asarray(a) for a in arrs)
            self._row_sig = sig
        return self._row_dev

    def _dispatch_decode(self, toks_dev=None, positions=None) -> _Inflight:
        """Dispatch one packed decode + fused on-device sampling WITHOUT
        blocking: the returned handle's ``tokens`` is a ``(num_slots,)``
        int32 device array that may still be computing.  The synchronous
        head builds the feed from host ``last_token``s; a chained
        (lookahead) dispatch feeds the previous step's device token
        vector straight back in — zero host round-trip."""
        sched = self.scheduler
        if toks_dev is None:
            toks = np.zeros((self.pool.num_slots, 1), np.int32)
            pos = np.zeros((self.pool.num_slots,), np.int32)
            for slot, seq in sched.running.items():
                toks[slot, 0] = seq.last_token
                pos[slot] = len(seq.request.output_tokens)
            toks_dev, positions = jnp.asarray(toks), jnp.asarray(pos)
        t_dev = time.perf_counter()
        with self.tracer.span("decode.dispatch", rows=len(sched.running)):
            logits, cache = self._decode(
                self.sparams, self.pool.step_cache(), toks_dev)
            self.pool.accept(cache)
            tokens = sample_rows(logits[:, -1], *self._row_params(),
                                 positions)
        # dispatch counts as device time: on an async backend it is a
        # near-zero enqueue, on a synchronous one (CPU) it IS the compute
        # — either way it is time driving the device, not serving-loop
        # Python (see _timed_decode for the full attribution schema)
        self._device_seconds += time.perf_counter() - t_dev
        self._note_exec("decode", self._decode)
        self._note_exec("sample", sample_rows)
        return _Inflight(tokens, positions, dict(sched.running))

    def _sync_inflight(self, inf: _Inflight, events: dict) -> None:
        """Block on the in-flight token vector, then emit/advance.  Rows
        whose sequence turned over since dispatch (finished at the last
        sync while the lookahead was already running) carried a phantom
        token, which is discarded here."""
        t_dev = time.perf_counter()
        with self.tracer.span("decode.wait", rows=len(inf.rows)):
            toks = np.asarray(inf.tokens)  # blocks until compute lands
        self._device_seconds += time.perf_counter() - t_dev
        with self.tracer.span("decode.host"):
            for slot, seq in inf.rows.items():
                if self.scheduler.running.get(slot) is not seq:
                    continue
                tok = int(toks[slot])
                self._emit(seq.request, tok, events)
                if seq.request.done:
                    self._finish(self.scheduler.finish(slot), events)
                else:
                    self.scheduler.advance(slot, tok)

    def _pipeline_tail(self, inf: _Inflight, events: dict) -> None:
        """Dispatch the NEXT step's decode (when safe) BEFORE syncing the
        current one — the blocking fetch + Python bookkeeping below then
        overlap the device's next step.  Ineligible steps fall back to
        plain sync order and count ``pipeline.bubbles``.

        Chaining feeds ``inf.tokens`` back in for EVERY slot, so it is
        only valid while the running composition is exactly the rows the
        in-flight step was dispatched over — a row admitted since (step()
        admits before this sync) has no token in that vector and must
        wait for the next synchronous head."""
        nxt = None
        same_rows = (len(self.scheduler.running) == len(inf.rows) and all(
            inf.rows.get(s) is q for s, q in self.scheduler.running.items()))
        if self._pipeline_on:
            if same_rows and self._lookahead_ok():
                nxt = self._dispatch_decode(inf.tokens[:, None],
                                            inf.positions + 1)
                self._c_lookahead.inc()
            else:
                self._c_bubbles.inc()
        self._sync_inflight(inf, events)
        self._inflight = nxt

    def _lookahead_ok(self) -> bool:
        """Can step t+1 be dispatched before step t's tokens land?
        Requires: nothing queued to admit, every running request with
        budget for at least one more token after this step (an EOS can
        still land — that row's phantom token is discarded at sync), and
        a non-preempting reservation of the t+1 write position."""
        if len(self.queue):
            return False
        for seq in self.scheduler.running.values():
            req = seq.request
            if len(req.output_tokens) + 2 > req.max_new_tokens:
                return False
        return self.scheduler.reserve_lookahead()

    def _decode_sync(self, events: dict) -> None:
        """One synchronous decode step, no lookahead (the spec path's
        ``max_k == 0`` fallback).  Device sampling only when every row is
        greedy — there device and host draws are bitwise-identical, so the
        fallback composes with spec windows.  Any sampled row must draw
        from the HOST streams (``Request.rng_for``): window size depends
        on pool pressure i.e. batch composition, and a ``k == 0`` window
        emitting from the device threefry stream while a ``k > 0`` window
        emits the same position from the numpy stream would break the
        windowing-invariance contract."""
        if self._sample_device and all(
                seq.request.sampling.temperature <= 0.0
                for seq in self.scheduler.running.values()):
            self._sync_inflight(self._dispatch_decode(), events)
        else:
            self._decode_once(events)

    def _decode_once(self, events: dict) -> None:
        """One packed single-token decode over every running row, host
        sampling (``sample_device=False`` — the bisectable legacy path).
        Here ``_device_seconds`` keeps the pre-pipeline semantics:
        dispatch + the blocking (num_slots, V) logits fetch."""
        toks = np.zeros((self.pool.num_slots, 1), np.int32)
        for slot, seq in self.scheduler.running.items():
            toks[slot, 0] = seq.last_token
        t_dev = time.perf_counter()
        with self.tracer.span("decode.wait",
                              rows=len(self.scheduler.running)):
            logits, cache = self._decode(
                self.sparams, self.pool.step_cache(), jnp.asarray(toks))
            self.pool.accept(cache)
            rows = np.asarray(logits[:, -1])  # (num_slots, V) — blocks here
        self._device_seconds += time.perf_counter() - t_dev
        self._note_exec("decode", self._decode)
        with self.tracer.span("decode.host"):
            for slot, seq in list(self.scheduler.running.items()):
                tok = seq.request.select_token(rows[slot])
                self._emit(seq.request, tok, events)
                if seq.request.done:
                    self._finish(self.scheduler.finish(slot), events)
                else:
                    self.scheduler.advance(slot, tok)

    # ------------------------------------------------------------ spec path
    def _spec_step(self, events: dict) -> None:
        """One speculative window: draft-roll k tokens per row with the
        low-bit self-draft, verify all k + 1 positions of every row in ONE
        batched chunk call, resolve by exact rejection sampling, emit.

        Cache discipline (the no-extra-KV contract): the draft reads and
        writes the SAME pool blocks through each row's block table; rows
        not drafting a given depth have their block-table row pointed at
        the garbage block for that call, so no live block is ever touched
        on their behalf.  Recurrent (non-paged) state is snapshotted
        before the draft roll and restored for the verifier, whose
        padding-masked chunk pass recomputes it exactly; a rejection
        triggers one fix-up verify at the accepted length (same shapes —
        same executable).  ``length`` is host-authoritative and rewritten
        after emission, so rejected positions' stale KV sits beyond every
        attention mask until genuinely overwritten.

        Greedy fast path (``sample_device`` and every running request at
        temperature 0 — the parity-critical default): the draft roll
        keeps its token argmaxes on device, and verify/resolve fetches
        only the ``(B, C)`` target-argmax and ``(B, max_k)`` draft
        vectors instead of the ``(B, C, V)`` logits tensor; each window
        resolves with :func:`repro.spec.sampler.greedy_window`
        (bitwise-equal to ``spec_window`` for greedy).  Mixed or sampled
        batches keep the exact rejection sampler on the full logits and
        count ``sampler.fallbacks``.
        """
        from repro.spec.sampler import (
            KIND_DRAFT,
            draft_token,
            greedy_window,
            spec_window,
        )

        pool, sched, spec = self.pool, self.scheduler, self.spec
        B = pool.num_slots
        ring_cap = None
        if pool.paged_keys and self.model.cfg.sliding_window is not None:
            # ring caches: a window must never wrap — a wrapped draft
            # write would clobber live in-window KV that a rejection
            # cannot restore.  Rows near the wrap point fall back to
            # k = 0 (still 1 token/step via the verifier).
            ring_cap = pool.blocks_per_seq * pool.block_size

        want: dict[int, int] = {}
        for slot, seq in sched.running.items():
            req = seq.request
            k = min(spec.k, req.max_new_tokens - len(req.output_tokens) - 1)
            if ring_cap is not None:
                k = min(k, ring_cap - 1 - seq.cached_len)
            want[slot] = max(k, 0)
        granted, preempted = sched.reserve_for_spec(want)
        for req in preempted:
            events["preempted"].append(req.request_id)
            self.tracer.instant("preempt", request=req.request_id,
                                step=self._step_idx)
        if not sched.running:
            return
        max_k = max(granted.values())
        if max_k == 0:
            self._decode_sync(events)  # nothing to speculate this step
            return
        greedy_fast = (self._sample_device and
                       all(seq.request.sampling.temperature <= 0.0
                           for seq in sched.running.values()))
        if self._sample_device and not greedy_fast:
            self._c_fallbacks.inc()

        lengths0 = {s: seq.cached_len for s, seq in sched.running.items()}
        # snapshot O(1) recurrent leaves (explicit copies: the decode and
        # verify calls donate the cache dict, invalidating originals)
        snap_keys = [key for key in pool.cache
                     if key not in pool.paged_keys and key != "length"]
        snap = {key: jnp.copy(pool.cache[key]) for key in snap_keys}

        # --- draft roll: k low-bit decode steps through the shared pool
        draft_toks: dict[int, list[int]] = {s: [] for s in granted}
        q_probs: dict[int, list] = {s: [] for s in granted}
        cur = np.zeros((B, 1), np.int32)
        for slot, seq in sched.running.items():
            cur[slot, 0] = seq.last_token
        # greedy-fast roll state: the fed token never leaves the device,
        # and the per-depth draft columns accumulate for ONE batched
        # fetch after the verify dispatch
        if greedy_fast:
            granted_arr = np.zeros((B,), np.int32)
            for slot, k in granted.items():
                granted_arr[slot] = k
            granted_dev = jnp.asarray(granted_arr)
            cur_dev = first_dev = jnp.asarray(cur)
        draft_cols: list = []
        # masked tables are nested (grants only expire as depth grows), so
        # upload one device array per DISTINCT mask, not one per depth —
        # and the common all-rows-full-window mask IS the pool's mirror,
        # already resident
        bt_key, bt_dev = None, None
        with self.tracer.span("spec.draft", max_k=max_k,
                              rows=len(sched.running)):
            for depth in range(1, max_k + 1):
                cache_d = dict(pool.cache)
                bt = pool.block_tables.copy()
                masked = False
                for slot in range(B):
                    if granted.get(slot, 0) < depth:
                        bt[slot] = 0  # garbage sink: row sits this one out
                        masked = masked or pool.block_tables[slot].any()
                key = bt.tobytes()
                # re-upload if the mask changed OR a donating backend ate
                # the previous buffer (CPU ignores donation; accelerators
                # don't)
                if key != bt_key or bt_dev.is_deleted():
                    bt_key = key
                    bt_dev = (jnp.asarray(bt) if masked
                              else pool.block_tables_dev())
                cache_d["block_tables"] = bt_dev
                t_dev = time.perf_counter()
                if greedy_fast:
                    # greedy draft == argmax, taken on device — no fetch;
                    # rows past their window carry their last token
                    logits, cache = self._decode(self._draft_sparams,
                                                 cache_d, cur_dev)
                    pool.accept(cache)
                    nxt = jnp.argmax(logits[:, -1], axis=-1)
                    col = jnp.where(granted_dev >= depth,
                                    nxt.astype(jnp.int32), cur_dev[:, 0])
                    cur_dev = col[:, None]
                    draft_cols.append(col)
                    self._device_seconds += time.perf_counter() - t_dev
                    continue
                logits, cache = self._decode(self._draft_sparams, cache_d,
                                             jnp.asarray(cur))
                pool.accept(cache)
                rows = np.asarray(logits[:, -1])
                self._device_seconds += time.perf_counter() - t_dev
                for slot, seq in sched.running.items():
                    if granted[slot] < depth:
                        continue
                    req = seq.request
                    pos = len(req.output_tokens) + depth - 1
                    tok, q = draft_token(rows[slot], req.sampling,
                                         req.rng_for(pos, KIND_DRAFT))
                    draft_toks[slot].append(tok)
                    q_probs[slot].append(q)
                    cur[slot, 0] = tok
        self._note_exec("decode", self._decode)

        # --- verify: ONE batched fixed-shape chunk over every pool row.
        # Width is always spec.k + 1 (short windows pad with valid < C),
        # so every step reuses one executable.
        C = spec.k + 1
        ver_toks = np.zeros((B, C), np.int32)
        starts = np.zeros((B,), np.int32)
        valids = np.zeros((B,), np.int32)
        for slot, seq in sched.running.items():
            k = granted[slot]
            ver_toks[slot, 0] = seq.last_token
            if not greedy_fast:
                ver_toks[slot, 1:1 + k] = draft_toks[slot]
            starts[slot] = lengths0[slot]
            valids[slot] = k + 1
        if greedy_fast:
            # the feed stays on device: [last, draft_1..draft_max_k],
            # padded to the fixed verify width (the tail beyond a row's
            # window is masked by ``valids`` — its values are never read)
            body = jnp.concatenate(
                [first_dev] + [c[:, None] for c in draft_cols], axis=1)
            ver_toks_dev = jnp.pad(body, ((0, 0), (0, C - body.shape[1])))
        else:
            ver_toks_dev = jnp.asarray(ver_toks)
        bt_full = pool.block_tables_dev()  # mirror, shared with the fix-up
        cache_v = dict(pool.cache)
        for key in snap_keys:  # keep `snap` alive for a possible fix-up
            cache_v[key] = jnp.copy(snap[key])
        cache_v["block_tables"] = bt_full
        starts_dev = jnp.asarray(starts)
        target = tops = drafts = None
        t_dev = time.perf_counter()
        with self.tracer.span("spec.verify", rows=len(sched.running),
                              width=C):
            logits, cache = self._verify(
                self.sparams, cache_v, ver_toks_dev, starts_dev,
                jnp.asarray(valids))
            pool.accept(cache)
            if greedy_fast:
                # fetch per-position target argmaxes + the draft columns
                # — (B, C) + (B, max_k) int32, not (B, C, V) float32
                tops = np.asarray(jnp.argmax(logits, axis=-1)
                                  .astype(jnp.int32))
                drafts = np.asarray(jnp.stack(draft_cols, axis=1))
                ver_toks[:, 1:1 + max_k] = drafts  # host copy for fix-up
            else:
                target = np.asarray(logits)  # (B, C, V) float32
        self._device_seconds += time.perf_counter() - t_dev
        self._note_exec("verify", self._verify)

        # --- resolve each window on the host: greedy argmax comparison
        # on the fast path, exact rejection sampling otherwise
        emitted_by_slot: dict[int, list[int]] = {}
        with self.tracer.span("spec.resolve") as sp_res:
            proposed = accepted_total = 0
            for slot, seq in sched.running.items():
                req = seq.request
                k = granted[slot]
                if greedy_fast:
                    emitted, accepted = greedy_window(drafts[slot, :k],
                                                      tops[slot])
                else:
                    emitted, accepted = spec_window(
                        draft_toks[slot], target[slot, :k + 1],
                        req.sampling, req.rng_for,
                        base_pos=len(req.output_tokens),
                        q_probs=q_probs[slot])
                emitted_by_slot[slot] = emitted
                self._c_spec_windows.inc()
                proposed += k
                accepted_total += accepted
            self._c_spec_proposed.inc(proposed)
            self._c_spec_accepted.inc(accepted_total)
            sp_res.set(proposed=proposed, accepted=accepted_total)

        # --- recurrent fix-up: a rejection means the verifier advanced
        # wkv/SSM state through tokens that were never emitted; re-run the
        # same chunk at the accepted lengths (identical prefix => exact)
        if snap and any(len(emitted_by_slot[s]) < int(valids[s])
                        for s in emitted_by_slot):
            valids2 = np.zeros((B,), np.int32)
            for slot in emitted_by_slot:
                valids2[slot] = len(emitted_by_slot[slot])
            cache_f = dict(pool.cache)
            for key in snap_keys:
                cache_f[key] = snap[key]
            # the mirror re-uploads itself if a donating verify consumed
            # the buffer (CPU ignores donation; accelerators don't)
            cache_f["block_tables"] = pool.block_tables_dev()
            if ver_toks_dev.is_deleted():
                ver_toks_dev, starts_dev = (jnp.asarray(ver_toks),
                                            jnp.asarray(starts))
            t_dev = time.perf_counter()
            with self.tracer.span("spec.fixup"):
                _, cache = self._verify(
                    self.sparams, cache_f, ver_toks_dev, starts_dev,
                    jnp.asarray(valids2))
                pool.accept(cache)
            self._device_seconds += time.perf_counter() - t_dev

        # --- emit (EOS / budget can land mid-window), then restore the
        # host-authoritative lengths: the verifier wrote start + valid
        lengths1 = np.zeros((B,), np.int32)
        for slot, seq in list(sched.running.items()):
            req = seq.request
            finished = False
            for tok in emitted_by_slot[slot]:
                self._emit(req, tok, events)
                if req.done:
                    self._finish(sched.finish(slot), events)
                    finished = True
                    break
                sched.advance(slot, tok)
            if not finished:
                lengths1[slot] = seq.cached_len
        pool.cache["length"] = jnp.asarray(lengths1)

    def run_until_drained(self, max_steps: int = 100_000) -> dict:
        steps = 0
        while self.scheduler.has_work():
            if steps >= max_steps:
                raise RuntimeError(f"not drained after {max_steps} steps")
            self.step()
            steps += 1
        return self.metrics()

    # -------------------------------------------------------------- metrics
    def _emit(self, req: Request, tok: int, events: dict) -> None:
        if not req.output_tokens:
            req.first_token_time = time.perf_counter()
            req.first_token_step = self._step_idx
        req.output_tokens.append(tok)
        self._c_tokens.inc()
        events["tokens"].append((req.request_id, tok))

    def _finish(self, req: Request, events: dict) -> None:
        req.finish_time = time.perf_counter()
        events["finished"].append(req.request_id)

    def metrics(self) -> dict:
        per_request = []
        for req in self.requests.values():
            per_request.append({
                "id": req.request_id,
                "state": req.state.value,
                "prompt_len": int(req.prompt.size),
                "new_tokens": len(req.output_tokens),
                "preemptions": req.preemptions,
                "ttft_s": req.ttft(),
                "ttft_steps": (None if req.first_token_step is None
                               else req.first_token_step - req.arrival_step),
                "latency_s": (None if req.finish_time is None
                              else req.finish_time - req.arrival_time),
                "prefix_cached_tokens": req.prefix_cached_tokens,
            })
        # every aggregate below reads the registry instruments — keys are
        # byte-compatible with the pre-registry dict, windowed series keep
        # the exact ``metrics_window`` percentile semantics (Histogram
        # windows reproduce np.percentile over the last N samples)
        decode_steps = int(self._c_decode_steps.value)
        tokens_total = int(self._c_tokens.value)
        run_seconds = self._c_run_seconds.value
        occ = (self._c_occ_sum.value / decode_steps
               if decode_steps else 0.0)
        out = {
            "steps": self._step_idx,
            "decode_steps": decode_steps,
            "tokens_total": tokens_total,
            "tokens_per_s": (tokens_total / run_seconds
                             if run_seconds > 0 else 0.0),
            "mean_occupancy": occ,
            "num_slots": self.pool.num_slots,
            "cache": self.cache_kind,
            "preemptions": self.scheduler.preemptions,
            "recompiles": int(self._c_recompiles.value),
            "requests": per_request,
            # one-token hotpath counters (docs/metrics.md): lookahead =
            # steps whose decode was dispatched before the previous sync;
            # bubbles = pipeline-on steps that ran synchronously;
            # fallbacks = device-sampling steps resolved via a host
            # logits fetch (non-greedy speculative windows)
            "sampler": {
                "device": self._sample_device,
                "fallbacks": int(self._c_fallbacks.value),
            },
            "pipeline": {
                "enabled": self._pipeline_on,
                "lookahead_steps": int(self._c_lookahead.value),
                "bubbles": int(self._c_bubbles.value),
            },
        }
        if self._h_decode.count:
            out["decode_step_p50_ms"] = self._h_decode.percentile(50) * 1e3
            out["decode_step_p99_ms"] = self._h_decode.percentile(99) * 1e3
            # device/host attribution of the same steps (spans carry the
            # per-step values; these are the windowed medians)
            out["decode_device_p50_ms"] = self._h_device.percentile(50) * 1e3
            out["decode_host_p50_ms"] = self._h_host.percentile(50) * 1e3
            if self._h_decode_tok.count:  # step cost / tokens delivered
                out["decode_tok_p50_ms"] = (
                    self._h_decode_tok.percentile(50) * 1e3)
        if self._h_queue_wait.count:
            out["queue_wait_p50_ms"] = self._h_queue_wait.percentile(50) * 1e3
        if self.cache_kind == "paged":
            out["mean_block_occupancy"] = (
                self._c_block_occ_sum.value / decode_steps
                if decode_steps else 0.0)
            out["block_size"] = self.pool.block_size
            out["num_blocks"] = self.pool.num_blocks
            out["prefill_launches"] = int(self._c_prefill_launches.value)
            # windowed (metrics_window-bounded, like the latency deques):
            # hit rate over the last admissions, shared-block gauge mean
            # over the last decode steps.  ``prefix_hit_rate`` is that
            # *windowed token ratio*; the unambiguous raw lifetime
            # counters ride alongside as prefix_hits / prefix_lookups
            total = self._h_admit_total.window_sum()
            out["prefix_hit_rate"] = (
                self._h_admit_hit.window_sum() / total if total else 0.0)
            out["prefix_hits"] = self.pool.prefix_hits
            out["prefix_lookups"] = self.pool.prefix_lookups
            out["blocks_shared"] = self._h_shared.window_mean()
            out["prefix_cache"] = {
                "enabled": self.pool.prefix_cache,
                "lookups": self.pool.prefix_lookups,
                "hits": self.pool.prefix_hits,
                "hit_tokens": self.pool.prefix_hit_tokens,
                "cow_copies": self.pool.cow_copies,
                "evictions": self.pool.prefix_evictions,
                "cached_blocks": self.pool.prefix_cached_blocks,
            }
            if self.pool.kv_bits is not None:
                out["kv_bits"] = list(self.pool.kv_bits)
                out["kv_oracle"] = self.pool.kv_oracle
        if self.spec is not None:
            windows = int(self._c_spec_windows.value)
            proposed = int(self._c_spec_proposed.value)
            accepted = int(self._c_spec_accepted.value)
            out["spec"] = {
                "k": self.spec.k,
                "windows": windows,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": (accepted / proposed
                                    if proposed else 0.0),
            }
        return out

    def output(self, request_id: int) -> list[int]:
        return list(self.requests[request_id].output_tokens)
