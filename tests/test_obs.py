"""Observability gates: registry instruments + concurrent-snapshot
consistency (hypothesis), windowed percentile exactness, tracer span
balance under exceptions / preemption / spec rejection on a REAL engine,
Chrome-trace schema validation, near-zero disabled cost, spans on the
JAX profiler's timeline (also from a disabled tracer), the engine's
first-token counters, named scopes in the serving HLO, engine
``metrics()`` key compatibility, and structured-log rate limiting."""
import glob
import io
import json
import os
import threading

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis: skip ONLY property tests
    import types

    st = types.SimpleNamespace(integers=lambda *a, **k: None,
                               lists=lambda *a, **k: None,
                               floats=lambda *a, **k: None)

    def given(*a, **k):
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

from repro.obs import Counter, Gauge, Histogram, Registry, run_provenance
from repro.obs.log import StructuredLogger, configure, json_mode
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Tracer

RNG = jax.random.PRNGKey(0)


# ------------------------------------------------------------- instruments
def test_counter_gauge_basics():
    reg = Registry()
    c = reg.counter("c", unit="tok")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.add(-2)
    assert g.value == 5.0
    # same name -> same instrument (independent call sites share a series)
    assert reg.counter("c") is c
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "unit": "tok", "value": 5.0}
    assert snap["g"]["value"] == 5.0


def test_registry_kind_collision_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_histogram_bucket_counts_and_snapshot():
    h = Histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5 and s["sum"] == pytest.approx(56.05)
    assert s["min"] == 0.05 and s["max"] == 50.0
    assert s["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 1, "+inf": 1}
    json.dumps(s)  # snapshot must be JSON-safe as-is
    # bucket-interpolated percentiles stay inside the data range
    assert 0.05 <= h.percentile(50) <= 50.0
    assert h.percentile(99) >= h.percentile(50)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(min_value=1e-6, max_value=10.0,
                          allow_nan=False), min_size=1, max_size=60),
       st.integers(min_value=1, max_value=20))
def test_windowed_percentile_is_exact_np_percentile(values, window):
    """The ``metrics_window`` contract: with ``window=N`` the histogram's
    percentile is EXACTLY np.percentile over the last N observations —
    what the serve engine's latency deques always reported."""
    h = Histogram("h", window=window)
    for v in values:
        h.observe(v)
    tail = np.asarray(values[-window:])
    for q in (50, 90, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(tail, q)))
    assert h.window_sum() == pytest.approx(float(tail.sum()))
    assert h.window_mean() == pytest.approx(float(tail.mean()))


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=20, max_value=100))
def test_snapshot_consistent_under_concurrent_writers(threads, per_thread):
    """Evaluator-pool regime: writer threads hammer shared instruments
    while a reader snapshots.  Every mid-flight snapshot must be
    JSON-safe and monotone (counters never regress), and the final
    snapshot must account for every observation exactly."""
    reg = Registry()
    c = reg.counter("n")
    h = reg.histogram("lat", window=8)
    stop = threading.Event()
    seen = []

    def writer():
        for i in range(per_thread):
            c.inc()
            h.observe(0.001 * (i + 1))

    def reader():
        while not stop.is_set():
            seen.append(reg.snapshot()["n"]["value"])

    ws = [threading.Thread(target=writer) for _ in range(threads)]
    rd = threading.Thread(target=reader)
    rd.start()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    stop.set()
    rd.join()
    assert all(a <= b for a, b in zip(seen, seen[1:]))  # monotone reads
    final = reg.snapshot()
    json.dumps(final)
    assert final["n"]["value"] == threads * per_thread
    assert final["lat"]["count"] == threads * per_thread
    assert len(h.samples()) == min(8, threads * per_thread)


def test_run_provenance_is_json_safe_and_complete():
    prov = run_provenance()
    for key in ("git_sha", "git_dirty", "timestamp_utc", "python",
                "jax", "device_count", "device_platform"):
        assert key in prov
    assert json.loads(json.dumps(prov)) == prov


# ------------------------------------------------------------------ tracer
def test_disabled_tracer_is_free_and_silent():
    tr = Tracer(enabled=False)
    assert tr.span("a") is NULL_SPAN          # shared no-op, no allocation
    assert tr.span("b", x=1) is tr.span("c")  # same singleton every call
    with tr.span("a"):
        tr.instant("i")
        tr.complete("c", start=0.0, dur=1.0)
    assert tr.num_events == 0 and tr.dropped == 0
    assert NULL_TRACER.span("x") is NULL_SPAN


def test_span_balance_survives_exceptions():
    """``__exit__`` records the span even when the body raises — the
    error path (preemption, rejected window, failed admission) can never
    leave a dangling open span, and the exception type is attached."""
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("outer", step=1):
            with tr.span("inner"):
                raise RuntimeError("boom")
    assert tr.depth() == 0
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    assert all(e["args"]["error"] == "RuntimeError" for e in evs)
    assert all(e["dur_s"] >= 0 for e in evs)


def test_span_set_args_and_nesting_depth():
    tr = Tracer(enabled=True)
    with tr.span("a") as sp:
        assert tr.depth() == 1
        with tr.span("b"):
            assert tr.depth() == 2
        sp.set(tokens=3, mode="spec")
    assert tr.depth() == 0
    a = tr.events("a")[0]
    assert a["args"] == {"tokens": 3, "mode": "spec"}


def test_ring_bound_and_dropped_count():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(20):
        tr.instant("tick", i=i)
    assert tr.num_events == 8
    assert tr.dropped == 12
    # the ring keeps the NEWEST events
    assert [e["args"]["i"] for e in tr.events()] == list(range(12, 20))
    tr.clear()
    assert tr.num_events == 0 and tr.dropped == 0


def test_complete_retro_dates_and_clamps():
    tr = Tracer(enabled=True)
    tr.complete("queue.wait", start=0.5, dur=0.25, request=3)
    tr.complete("neg", start=1.0, dur=-0.1)   # clock skew clamps to 0
    ev = tr.events("queue.wait")[0]
    assert ev["dur_s"] == pytest.approx(0.25)
    assert ev["args"]["request"] == 3
    assert tr.events("neg")[0]["dur_s"] == 0.0


def _validate_chrome(doc):
    """Chrome-trace schema: what ui.perfetto.dev actually requires."""
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["pid"] == 1 and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
        elif ev["ph"] == "i":
            assert ev["s"] == "t"
        else:
            assert ev["name"] == "thread_name"
    assert json.loads(json.dumps(doc)) == doc  # round-trip stable


def test_chrome_export_schema_and_thread_names(tmp_path):
    tr = Tracer(enabled=True)
    tr.name_thread("serve-loop")
    with tr.span("decode.step", step=0, arr=np.int64(7)):
        tr.instant("preempt", request=np.int32(1))
    doc = tr.to_chrome()
    _validate_chrome(doc)
    # numpy args were coerced to plain JSON scalars
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["decode.step"]["args"]["arr"] == 7
    assert by_name["preempt"]["args"]["request"] == 1
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "serve-loop"
    path = tmp_path / "trace.json"
    tr.save(str(path))
    assert json.load(open(path)) == doc


# ------------------------------------------------ the profiler's timeline
def _profile(logdir):
    """A profiler session over ``logdir`` with host annotations only (no
    Python function tracer)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(logdir), profiler_options=opts)


def _host_events(logdir) -> list[tuple[str, int, int, dict]]:
    """``(name, start_ns, end_ns, args)`` of every host event the profiler
    recorded under ``logdir``."""
    paths = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_disabled_tracer_spans_reach_the_profiler(tmp_path):
    """A disabled tracer's spans and instants become profiler annotations,
    with their args (``set()`` included) and nesting, while a session
    records; the ring stays empty, ``complete()`` stays out of the
    profile, and outside a session ``span()`` is the shared no-op."""
    tr = Tracer(enabled=False)
    assert tr.span("a", x=1) is NULL_SPAN
    with _profile(tmp_path):
        with tr.span("outer", request=3) as sp:
            with tr.span("inner"):
                tr.instant("mark", k=7)
            sp.set(hit=5)
        tr.complete("retro", start=0.0, dur=1.0)
        with pytest.raises(RuntimeError):
            with tr.span("failing"):
                raise RuntimeError("boom")
    assert tr.span("a") is NULL_SPAN and NULL_TRACER.span("b") is NULL_SPAN
    assert tr.num_events == 0 and tr.depth() == 0
    ev = {e[0]: e for e in _host_events(tmp_path)}
    assert ev["outer"][3] == {"request": 3, "hit": 5}
    assert ev["mark"][3] == {"k": 7}
    assert ev["failing"][3] == {"error": "RuntimeError"}
    assert _inside(ev["inner"], ev["outer"]) and _inside(ev["mark"], ev["inner"])
    assert ev["mark"][2] - ev["mark"][1] < ev["inner"][2] - ev["inner"][1]
    assert "retro" not in ev


def test_enabled_tracer_records_ring_and_profiler_alike(tmp_path):
    tr = Tracer(enabled=True)
    with _profile(tmp_path):
        with tr.span("decode.step", step=2) as sp:
            sp.set(tokens=4)
    ring = tr.events("decode.step")[0]
    prof = [e for e in _host_events(tmp_path) if e[0] == "decode.step"]
    assert ring["args"] == {"step": 2, "tokens": 4}
    assert len(prof) == 1 and prof[0][3] == ring["args"]
    # the two clocks time the same stretch
    assert prof[0][2] - prof[0][1] == pytest.approx(ring["dur_s"] * 1e9,
                                                    rel=0.5, abs=2e5)


# ------------------------------------------------- engine span balance
@pytest.fixture(scope="module")
def glm4():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.quant.qat import policy_for
    from repro.train.serve import (
        make_chunked_prefill,
        make_decode_step,
        make_verify_chunk,
        quantize_for_serving,
    )

    cfg = get_config("glm4-9b", smoke=True)
    model = build_model(cfg)
    sparams = quantize_for_serving(model, model.init(RNG),
                                   policy_for(model, default_bits=4))
    fns = {"prefill_fn": make_chunked_prefill(model, donate=False),
           "decode_fn": make_decode_step(model, donate=False),
           "verify_fn": make_verify_chunk(model, donate=False)}
    return cfg, model, sparams, fns


def _prompt(cfg, n, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size))


def test_engine_spans_balance_under_preemption_and_spec(glm4):
    """A scarce-pool speculative run — forced preemption, near-zero
    acceptance (2-bit draft on random weights), replay — must leave the
    tracer balanced, with every lifecycle span present and a schema-valid
    Chrome export.  This is the adversarial regime for span leaks."""
    from repro.serve import ServeEngine
    from repro.spec import SpecConfig

    cfg, model, sparams, fns = glm4
    tr = Tracer(enabled=True)
    tr.name_thread("serve-loop")
    eng = ServeEngine(model, sparams, num_slots=4, max_len=16, cache="paged",
                      block_size=4, num_blocks=9, prefill_chunk=4,
                      spec=SpecConfig(k=3, draft_bits=2), tracer=tr,
                      **fns)
    rids = [eng.submit(_prompt(cfg, 4, seed=s), max_new_tokens=8)
            for s in range(4)]
    eng.run_until_drained()
    assert all(len(eng.output(r)) == 8 for r in rids)

    m = eng.metrics()
    assert m["preemptions"] > 0                   # pressure was real
    assert m["spec"]["windows"] > 0
    assert m["spec"]["proposed"] > m["spec"]["accepted"]  # rejections hit
    assert tr.depth() == 0                        # balanced by construction
    names = {e["name"] for e in tr.events()}
    for want in ("queue.wait", "serve.step", "admit", "prefill.chunk",
                 "decode.step", "decode.wait", "decode.host", "spec.draft",
                 "spec.verify", "spec.resolve", "preempt"):
        assert want in names, want
    assert all(e["dur_s"] >= 0 for e in tr.events())
    # preempted requests re-queue: their second wait is its own sample
    requeued = [e for e in tr.events("queue.wait")
                if e["args"].get("requeued")]
    assert requeued
    _validate_chrome(tr.to_chrome())


def test_engine_spans_balance_on_admission_failure(glm4):
    """A prompt whose first chunk cannot fit keeps failing admission;
    blocked-admission attempts are counted and no span leaks."""
    from repro.serve import ServeEngine

    cfg, model, sparams, fns = glm4
    fns = {k: fns[k] for k in ("prefill_fn", "decode_fn")}
    tr = Tracer(enabled=True)
    eng = ServeEngine(model, sparams, num_slots=2, max_len=16, cache="paged",
                      block_size=4, num_blocks=9, prefill_chunk=4,
                      tracer=tr, **fns)
    big = eng.submit(_prompt(cfg, 12, seed=0), max_new_tokens=3)
    small = eng.submit(_prompt(cfg, 4, seed=1), max_new_tokens=8)
    eng.run_until_drained()
    assert len(eng.output(big)) == 3 and len(eng.output(small)) == 8
    assert eng.obs.get("sched.admitted").value >= 2
    assert tr.depth() == 0
    assert eng.pool.num_free_blocks == eng.pool.num_blocks - 1  # no leak
    _validate_chrome(tr.to_chrome())


# what each engine span nests in (docs/metrics.md, "Serve lifecycle spans")
ENGINE_PARENTS = {"admit.schedule": "serve.step", "admit": "serve.step",
                  "prefill.chunk": "admit", "prefill.full": "admit",
                  "admit.fetch": "admit", "admit.sample": "admit",
                  "decode.reserve": "serve.step", "decode.step": "serve.step",
                  "decode.dispatch": "decode.step",
                  "decode.wait": "decode.step", "decode.host": "decode.step"}


@pytest.mark.parametrize("cache", ["paged", "slot"])
def test_engine_spans_on_the_profiler_timeline(glm4, tmp_path, cache):
    """The engine's default (disabled) tracer puts every host phase of a
    step on the profiler's timeline, each inside its parent, and counts
    the first tokens it chose."""
    from repro.serve import SamplingParams, ServeEngine

    cfg, model, sparams, fns = glm4
    fns = ({k: fns[k] for k in ("prefill_fn", "decode_fn")}
           if cache == "paged" else {"decode_fn": fns["decode_fn"]})
    eng = ServeEngine(model, sparams, num_slots=2, max_len=32, cache=cache,
                      block_size=4, prefill_chunk=4, **fns)
    rids = [eng.submit(_prompt(cfg, 6, seed=s), max_new_tokens=5,
                       sampling=SamplingParams(temperature=0.8 * (s % 2),
                                               top_p=0.9, seed=s))
            for s in range(3)]
    with _profile(tmp_path):
        eng.run_until_drained()
    assert all(len(eng.output(r)) == 5 for r in rids)
    events = [e for e in _host_events(tmp_path)
              if e[0] in set(ENGINE_PARENTS) | {"serve.step"}]
    names = {e[0] for e in events}
    want = set(ENGINE_PARENTS) - ({"prefill.full"} if cache == "paged"
                                  else {"prefill.chunk", "decode.reserve"})
    assert want <= names, want - names
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    for e in events:
        parent = ENGINE_PARENTS.get(e[0])
        if parent is not None:
            assert any(_inside(e, p) for p in by_name[parent]), e
    assert len(by_name["serve.step"]) == eng.steps
    assert len(by_name["admit.sample"]) == len(rids)
    assert {e[3]["request"] for e in by_name["admit.sample"]} == set(rids)
    assert eng.obs.get("serve.first_tokens").value == len(rids)
    assert eng.obs.get("serve.first_token_host_seconds").value > 0


def test_first_tokens_count_only_admissions_that_chose_one(glm4):
    """A preempted request re-admits by replay and chooses no new first
    token: ``serve.first_tokens`` counts requests, not admissions."""
    from repro.serve import ServeEngine

    cfg, model, sparams, fns = glm4
    fns = {k: fns[k] for k in ("prefill_fn", "decode_fn")}
    eng = ServeEngine(model, sparams, num_slots=4, max_len=16, cache="paged",
                      block_size=4, num_blocks=9, prefill_chunk=4, **fns)
    rids = [eng.submit(_prompt(cfg, 4, seed=s), max_new_tokens=8)
            for s in range(4)]
    eng.run_until_drained()
    assert all(len(eng.output(r)) == 8 for r in rids)
    assert eng.metrics()["preemptions"] > 0
    admitted = eng.obs.get("sched.admitted").value
    assert eng.obs.get("serve.first_tokens").value == len(rids) < admitted


def test_serving_hlo_names_embed_quant_and_lm_head(glm4):
    """The embedding's fake-quant and the head carry named scopes into the
    decode step's and the prefill chunk's HLO metadata, so a profile
    names the device ops they become."""
    import jax.numpy as jnp

    from repro.serve.cache import PagedCachePool

    cfg, model, sparams, fns = glm4
    pool = PagedCachePool(model, 2, 16, block_size=4, num_blocks=9)
    cache = pool.step_cache()
    dec = fns["decode_fn"].lower(sparams, cache, jnp.zeros((2, 1), jnp.int32))
    pre = fns["prefill_fn"].lower(sparams, cache, jnp.zeros((1, 4), jnp.int32),
                                  0, 0, 3)
    for lowered in (dec, pre):
        hlo = lowered.compile().as_text()
        for scope in ("embed_quant", "lm_head"):
            assert f"/{scope}/" in hlo, scope


def test_engine_metrics_keys_unchanged(glm4):
    """The registry rebuild of ``metrics()`` is key-compatible with the
    pre-registry dict (downstream benchmarks parse these), plus the new
    observability keys."""
    from repro.serve import ServeEngine

    cfg, model, sparams, fns = glm4
    fns = {k: fns[k] for k in ("prefill_fn", "decode_fn")}
    eng = ServeEngine(model, sparams, num_slots=2, max_len=16, cache="paged",
                      block_size=4, prefill_chunk=4, **fns)
    rid = eng.submit(_prompt(cfg, 4, seed=0), max_new_tokens=4)
    eng.run_until_drained()
    assert len(eng.output(rid)) == 4
    m = eng.metrics()
    legacy = {"steps", "decode_steps", "tokens_total", "tokens_per_s",
              "mean_occupancy", "num_slots", "cache", "preemptions",
              "requests", "mean_block_occupancy", "block_size",
              "num_blocks", "prefill_launches", "prefix_hit_rate",
              "blocks_shared", "prefix_cache", "decode_step_p50_ms",
              "decode_step_p99_ms", "decode_tok_p50_ms"}
    assert legacy <= set(m), legacy - set(m)
    # new: raw prefix counters (satellite: hit-RATE ambiguity fix),
    # recompile count, device/host split, queue wait
    for key in ("prefix_hits", "prefix_lookups", "recompiles",
                "decode_device_p50_ms", "decode_host_p50_ms",
                "queue_wait_p50_ms"):
        assert key in m, key
    assert m["recompiles"] == 0          # shared pre-warmed executables
    assert m["prefix_lookups"] >= m["prefix_hits"] >= 0
    json.dumps(m)                        # the whole dict is JSON-safe


# ----------------------------------------------------------------- logging
def test_structured_log_rate_limit_and_suppressed_count():
    out = io.StringIO()
    lg = StructuredLogger("t", min_interval_s=60.0, stream=out)
    assert lg.event("episode", reward=1.0)           # first always lands
    assert not lg.event("episode", reward=2.0)       # inside the interval
    assert not lg.event("episode", reward=3.0)
    assert lg.event("other", x=1)                    # per-event budgets
    assert lg.event("episode", reward=4.0, force=True)
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 3
    assert "suppressed=2" in lines[-1]               # drops are reported
    assert lg.emitted == 3


def test_structured_log_json_mode_round_trips():
    out = io.StringIO()
    lg = StructuredLogger("search", stream=out)
    configure(json_mode=True)
    try:
        assert json_mode()
        lg.event("episode", episode=3, reward=0.75, quant=np.float64(0.5))
        rec = json.loads(out.getvalue())
        assert rec["logger"] == "search" and rec["event"] == "episode"
        assert rec["episode"] == 3 and rec["reward"] == 0.75
    finally:
        configure(json_mode=False)
    lg.event("episode", episode=4, reward=0.8125)
    text = out.getvalue().strip().splitlines()[-1]
    assert text.startswith("[search] episode ")
    assert "episode=4" in text and "reward=0.8125" in text
