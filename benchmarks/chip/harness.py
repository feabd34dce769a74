"""One run of one cell: set-up, warm-up, the measured window, the check of
what the window served against the plain reference, one result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by its name in ``BENCHMARK.json``:

- a configuration is ``configs/<name>.json`` (sizes, quantization policy,
  engine settings) and is served through the program's own entry points:
  ``build_model``, ``quantize_for_serving`` at ``policy_for``'s policy,
  ``ServeEngine.submit`` / ``step``;
- the program's family of that configuration (``ModelConfig.family``) is
  ``families/<family>.py``: its weights from the seed, the reference's
  decoder layer and the costs of its work (``FAMILY_API``);
- a traffic mix is ``traffic/<name>.json``, read by ``traffic.py``;
- a per-layer metric is ``metrics/<name>.py``, whose ``read(ctx)`` returns
  a number or ``None`` (nothing to read, and the metric is left out); the
  context (``_context``) holds the family's costs, the window's delta of
  every counter of the engine's registry and the reduced trace, with every
  span and named scope of the program;
- the limits of a cell's comparison are ``limits/<workload>.json``: the
  gap statistics of ``gap_stats`` it compares, each with its limit.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import peaks
from . import reference
from . import spans as spansmod
from . import trace as tracemod
from . import traffic as trafficmod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "families"
# what a family module provides: the seed's weights in the program's
# layout, the reference's decoder layer (``reference.logit_gaps``'s
# ``layer_fn``), and the operations and bytes of the work (``costs.py``)
FAMILY_API = ("program_params", "reference_layer", "prefill_chunk_flops",
              "decode_step_flops", "decode_attn_bytes", "decode_attn_flops",
              "decode_qmm_calls", "prefill_qmm_calls", "kv_bytes_per_token",
              "qmm_least_seconds")
HOST_SLACK_S = 60.0       # how long after the close a submitted request may
                          # still wait for its first token before it fails
MAX_SAMPLES = 8           # finished greedy requests judged by the reference


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ lookup
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = find(bench["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def load_limits(workload: str) -> dict:
    return json.loads((HERE / "limits" / f"{workload}.json").read_text())


@functools.cache
def _family_module(path: Path):
    # loaded as a module of this package's ``families``, so that its
    # relative imports reach the shared modules
    spec = importlib.util.spec_from_file_location(
        f"{__package__}.families.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in FAMILY_API if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"{path} does not define {missing}")
    return mod


def family(cfg: dict):
    """The module of the program's family of the configuration ``cfg``:
    ``families/<ModelConfig.family>.py``."""
    from repro.configs import get_config

    name = get_config(cfg["program_config"]).family
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"{cfg['name']}: the program's family {name!r} has no module "
            f"{path}")
    return _family_module(path)


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


# ---------------------------------------------------------------- devices
def chips(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's devices are {devs}")
    if len(devs) < count:
        raise NoChip(f"{count} TPU chips needed, found {len(devs)}")
    return devs


# ------------------------------------------------------------------ build
def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the named
    ``program_config`` with every key of the file that is a field of it
    (lists as tuples).  The file's ``name`` is the benchmark's own."""
    from repro.configs import ModelConfig, get_config

    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    pcfg = dataclasses.replace(get_config(cfg["program_config"]), **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg.items() if k in fields})
    if pcfg.hd != cfg["head_dim"]:
        raise ValueError(f"{cfg['name']}: the program's head_dim is "
                         f"{pcfg.hd}, the file's {cfg['head_dim']}")
    return pcfg


def build(cfg: dict, seed: int):
    """The program's model and its serving params, packed at the
    configuration's policy from weights made from ``seed``."""
    import jax

    from repro.models import build_model
    from repro.quant.qat import policy_for
    from repro.train.serve import quantize_for_serving

    fam = family(cfg)
    model = build_model(program_config(cfg))
    pol = cfg["policy"]
    policy = policy_for(model, default_bits=pol["weight_bits"])
    frozen = {g.name: pol["frozen_bits"] for g in model.quant_groups()
              if g.name in pol["frozen"]}
    if policy.frozen != frozen:
        raise ValueError(f"the program freezes {policy.frozen}, the "
                         f"configuration states {frozen}")
    params = fam.program_params(cfg, seed, model)
    sparams = quantize_for_serving(model, params, policy)
    del params
    jax.block_until_ready(sparams)
    return model, sparams


class Recorder:
    """Wraps the engine's jitted prefill and decode calls to log, on the
    host, what each launch computes: a chunk's start and valid tokens, a
    decode step's per-row context lengths."""

    def __init__(self, prefill, decode):
        self.engine = None
        self.names = {"prefill": prefill.__name__, "decode": decode.__name__}
        self.prefill_log: list[tuple[float, int, int]] = []
        self.decode_log: list[tuple[float, list[int]]] = []

        def pre(sparams, cache, tokens, seq, start, valid):
            self.prefill_log.append((time.perf_counter(), int(start), int(valid)))
            return prefill(sparams, cache, tokens, seq, start, valid)

        def dec(sparams, cache, tokens):
            rows = self.engine.scheduler.running.values()
            self.decode_log.append((time.perf_counter(),
                                    [s.cached_len + 1 for s in rows]))
            return decode(sparams, cache, tokens)

        pre._cache_size = prefill._cache_size
        dec._cache_size = decode._cache_size
        self.prefill, self.decode = pre, dec

    def between(self, lo: float, hi: float):
        return ([(s, v) for t, s, v in self.prefill_log if lo <= t < hi],
                [ls for t, ls in self.decode_log if lo <= t < hi])


def make_engine(model, sparams, cfg: dict, recorder: Recorder):
    from repro.serve import ServeEngine

    e = cfg["engine"]
    eng = ServeEngine(model, sparams, num_slots=e["num_slots"],
                      max_len=e["max_len"], block_size=e["block_size"],
                      num_blocks=e["num_blocks"],
                      prefill_chunk=e["prefill_chunk"],
                      kv_bits=cfg["policy"]["kv_bits"],
                      prefix_cache=e["prefix_cache"], metrics_window=1 << 20,
                      prefill_fn=recorder.prefill, decode_fn=recorder.decode)
    recorder.engine = eng
    return eng


# ------------------------------------------------------------ the clients
@dataclasses.dataclass
class Sent:
    spec: trafficmod.RequestSpec
    submitted: float
    times: list = dataclasses.field(default_factory=list)
    finished: float | None = None


class ClosedLoop:
    """``clients`` closed-loop clients: each submits its next request
    before the step that follows the one that finished its last."""

    def __init__(self, eng, mix: trafficmod.Traffic, documents):
        self.eng, self.mix, self.documents = eng, mix, documents
        self.sent: dict[int, Sent] = {}
        self.waiting = mix.clients       # clients with nothing in flight
        self.next = 0
        self.open = True

    def submit(self) -> None:
        from repro.serve import SamplingParams

        s = self.mix.spec["sampling"]
        while self.open and self.waiting:
            spec = self.mix.request(self.next, self.documents)
            sampling = (SamplingParams() if spec.greedy else SamplingParams(
                temperature=s["temperature"], top_p=s["top_p"],
                seed=spec.sampling_seed))
            t = time.perf_counter()
            rid = self.eng.submit(spec.prompt, spec.max_new_tokens,
                                  sampling=sampling)
            self.sent[rid] = Sent(spec, t)
            self.next += 1
            self.waiting -= 1

    def receive(self, events: dict, t: float) -> int:
        for rid, _ in events["tokens"]:
            self.sent[rid].times.append(t)
        for rid in events["finished"]:
            self.sent[rid].finished = t
            self.waiting += 1
        return len(events["finished"])

    def turn(self) -> None:
        """One engine step with the clients' work around it, each in a host
        annotation that the trace's idle gaps are charged to."""
        import jax

        with jax.profiler.TraceAnnotation("client.submit"):
            self.submit()
        with jax.profiler.TraceAnnotation("engine.step"):
            events = self.eng.step()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("client.wait"):
            self.receive(events, t)


def load_documents(eng, documents) -> None:
    """Prefill the corpus into the prefix cache: each document is served
    once for one token, and its blocks stay in the trie."""
    for doc in documents:
        eng.submit(doc, 1)
    eng.run_until_drained()


def warm_up(loop: ClosedLoop) -> None:
    """Run the traffic until a quarter of the clients have finished a
    request: every shape and path the window uses (admission and chunked
    prefill, decode with and without lookahead, the sampler, finishing and
    re-admitting) has then run."""
    done = 0
    while done < max(1, loop.mix.clients // 4):
        loop.submit()
        events = loop.eng.step()
        done += loop.receive(events, time.perf_counter())


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache, at a fixed directory inside the
    checkout: set here even where ``JAX_COMPILATION_CACHE_DIR`` names
    another, so that two checkouts measured side by side share nothing."""
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()       # caches every executable, however quick
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts the executables JAX compiles or loads from its cache."""

    def __init__(self):
        import jax

        self.count = 0

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def sync(eng) -> None:
    """Wait for everything the engine has dispatched."""
    import jax

    jax.block_until_ready(eng.pool.cache)
    inflight = getattr(eng, "_inflight", None)
    if inflight is not None:
        jax.block_until_ready(inflight.tokens)


# -------------------------------------------------------------- the window
def measure(loop: ClosedLoop, seconds: float, trace_dir: str | None):
    """The measured window, and with ``trace_dir`` a profiler trace of a
    few seconds in its middle, started and stopped with the device idle.
    Returns the window's bounds and the trace's host bounds."""
    import jax

    eng = loop.eng
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t_trace = t0 + seconds / 3.0 if trace_dir else None
    trace_len = min(3.0, seconds / 3.0)
    traced = None
    span = None
    while True:
        loop.turn()
        now = time.perf_counter()
        if t_trace is not None and traced is None and now >= t_trace:
            sync(eng)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host annotations only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN)
            span.__enter__()
            traced = [time.perf_counter(), None]
        elif span is not None and now >= traced[0] + trace_len:
            sync(eng)
            traced[1] = time.perf_counter()
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            span = None
        if now >= t_end and span is None:
            return t0, now, traced


def drain(loop: ClosedLoop, t0: float, t_close: float) -> None:
    """After the close: no new requests; step on until every request sent
    in the window has its first token, or ``HOST_SLACK_S`` has passed."""
    loop.open = False
    deadline = time.perf_counter() + HOST_SLACK_S
    late = [s for s in loop.sent.values()
            if t0 <= s.submitted < t_close and not s.times]
    while any(not s.times for s in late) and time.perf_counter() < deadline:
        loop.receive(loop.eng.step(), time.perf_counter())


def end_to_end(loop: ClosedLoop, t0: float, t_close: float) -> dict:
    window = t_close - t0
    sent = [s for s in loop.sent.values() if t0 <= s.submitted < t_close]
    ttft = [(s.times[0] - s.submitted) * 1e3 for s in sent if s.times]
    gaps, tokens = [], 0
    for s in loop.sent.values():
        for i, t in enumerate(s.times):
            if t0 <= t <= t_close:
                tokens += 1
                if i:
                    gaps.append((t - s.times[i - 1]) * 1e3)
    out = {"tokens_per_s": tokens / window}
    if ttft:
        out["ttft_p50_ms"] = trafficmod.p_tail(ttft, 0.50)
        out["ttft_p95_ms"] = trafficmod.p_tail(ttft, 0.95)
    if gaps:
        out["itl_p95_ms"] = trafficmod.p_tail(gaps, 0.95)
    return out, len(sent), sum(1 for s in sent if not s.times)


def judged_samples(loop: ClosedLoop, t0: float, seed: int):
    """Finished greedy requests served in the window: the longest, then a
    draw from the seed, ``MAX_SAMPLES`` in all."""
    eng = loop.eng
    done = [rid for rid, s in loop.sent.items()
            if s.spec.greedy and s.finished is not None and s.finished >= t0]
    if not done:
        return []
    size = lambda rid: len(eng.requests[rid].prompt) + len(eng.requests[rid].output_tokens)
    longest = max(done, key=size)
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed) % 2 ** 64, 5])
    pick = [longest] + list(rng.choice(rest, min(len(rest), MAX_SAMPLES - 1),
                                       replace=False))
    return [(np.asarray(eng.requests[r].prompt), list(eng.requests[r].output_tokens))
            for r in pick]


def gap_stats(gaps) -> dict:
    """Over every judged position: the mean gap by which the judged token's
    reference logit lies below the reference's best (the number compared),
    the widest such gap, and the share of positions where it lies below."""
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    return {"mean_gap": float(g.mean()), "widest_gap": float(g.max()),
            "off_best_share": float(np.mean(g > 0))}


def judge(cfg: dict, seed: int, samples) -> dict:
    """The reference's judgement of the served greedy tokens."""
    judged = reference.logit_gaps(
        cfg, seed, [(p, s, {"served": np.asarray(s)}) for p, s in samples],
        layer_fn=family(cfg).reference_layer,
        shape=(MAX_SAMPLES, cfg["engine"]["max_len"]))
    return gap_stats([j["served"] for j in judged])


# ------------------------------------------------------------------- a run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = ROOT, require_chip: bool = True,
        bench: dict | None = None, cfg: dict | None = None,
        mix_spec: dict | None = None, limits: dict | None = None,
        log=print) -> dict:
    """One run of ``workload``; returns the result line's object.  Raises
    ``NoChip`` when the cell's chips are missing and ``require_chip``."""
    import jax

    bench = bench or load_benchmark(root)
    cell = find(bench["workloads"], workload, "workload")
    cfg = cfg or load_config(bench, cell["config"], root)
    mix_spec = mix_spec or trafficmod.load(cell["traffic"])
    limits = limits or load_limits(workload)
    devs = chips(cell["chips"]) if require_chip else jax.devices()
    dev = devs[0]
    log(f"compile cache: {enable_compile_cache(root)}")
    compiles = CompileCounter()
    model, sparams = build(cfg, seed)
    recorder = Recorder(*_program_fns(model))
    eng = make_engine(model, sparams, cfg, recorder)
    mix = trafficmod.Traffic(mix_spec, seed, cfg["vocab_size"])
    documents = mix.documents()
    load_documents(eng, documents)
    loop = ClosedLoop(eng, mix, documents)
    warm_up(loop)
    sync(eng)
    mem = dev.memory_stats() or {}
    log(f"after set-up: {eng.pool.num_blocks} KV blocks, HBM in use "
        f"{mem.get('bytes_in_use')!r}, peak {mem.get('peak_bytes_in_use')!r}")
    before = _counters(eng)
    n_compiled = compiles.count
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        t0, t_close, traced = measure(loop, seconds, trace_dir)
        in_window = compiles.count - n_compiled
        after = _counters(eng)
        setup_s = t0 - t_start
        drain(loop, t0, t_close)
        e2e, attempted, failed = end_to_end(loop, t0, t_close)
        e2e["setup_s"] = setup_s
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        result = {"attempted": attempted, "failed": failed}
        if trace:
            red = {**tracemod.load(trace_dir), **spansmod.load(trace_dir)}
            ctx = _context(cfg, mix_spec, dev, recorder, t0, t_close,
                           traced, red, before, after)
            result["metrics"] = {}
            for m in cell_metrics(bench, workload, "per_layer"):
                value = metric_reader(m["name"])(ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = tracemod.busy_s(red)
            device["window_s"] = tracemod.window_s(red)
            result["breakdown"] = {
                "device_ops": [list(x) for x in tracemod.top_ops(red)],
                "idle_gaps": [list(x) for x in tracemod.idle_gaps(red)]}
        else:
            result["metrics"] = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell_metrics(bench, workload, "end_to_end")
                if m["name"] in e2e}
        result["device"] = device
        log(f"window: {t_close - t0!r} s, {attempted} requests sent, "
            f"{failed} without a first token; end to end {e2e}")
        log(f"compilations inside the window: {in_window}")
        bs = cfg["engine"]["block_size"]
        live = [sum(-(-n // bs) for n in ls) for ls in recorder.between(t0, t_close)[1]]
        if live:
            log(f"KV blocks the rows held at each decode step: mean "
                f"{float(np.mean(live))!r}, most {max(live)}, of "
                f"{eng.pool.num_blocks - 1} in the pool")
        samples = judged_samples(loop, t0, seed)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs, so that the
    # reference fits and the peak read above is the program's alone
    del loop, eng, sparams, model, recorder
    gc.collect()
    t_ref = time.perf_counter()
    stats = judge(cfg, seed, samples) if samples else {}
    log(f"reference: {len(samples)} requests, "
        f"{sum(len(s) for _, s in samples)} served tokens judged in "
        f"{time.perf_counter() - t_ref!r} s: {stats}")
    # the cell's limits file names the gap statistic it compares
    checks = {name: {"value": stats.get(name, float("inf")),
                     "limit": lim["limit"]} for name, lim in limits.items()}
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def _program_fns(model):
    from repro.train.serve import make_chunked_prefill, make_decode_step

    return make_chunked_prefill(model), make_decode_step(model)


def _counters(eng) -> dict:
    """``counts``: four counts the harness takes from the engine; ``obs``:
    every counter of the engine's ``repro.obs`` registry, by name."""
    m = eng.metrics()
    return {
        "counts": {"decode_steps": m["decode_steps"],
                   "bubbles": m["pipeline"]["bubbles"],
                   "prefix_hit_tokens": eng.pool.prefix_hit_tokens,
                   "admitted_tokens": sum(
                       len(r.prompt) for r in eng.requests.values()
                       if r.first_token_step is not None)},
        "obs": {name: inst["value"] for name, inst in eng.obs.snapshot().items()
                if inst["type"] == "counter"}}


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def _context(cfg, mix_spec, dev, recorder, t0, t_close, traced, red,
             before, after):
    """What a per-layer metric may read (``metrics/*.py``).  ``before`` and
    ``after`` are ``_counters`` at the window's two ends."""
    from repro.serve.sampler import sample_rows

    costs = family(cfg)
    pre_w, dec_w = recorder.between(t0, t_close)
    pre_t, dec_t = recorder.between(*traced)
    window_flops = (sum(costs.prefill_chunk_flops(cfg, s, v) for s, v in pre_w)
                    + sum(costs.decode_step_flops(cfg, ls) for ls in dec_w))
    return SimpleNamespace(
        cfg=cfg, traffic=mix_spec, peak=peaks.peak_for(dev.device_kind),
        trace=red, costs=costs, tracemod=tracemod, spans=spansmod,
        modules=dict(recorder.names, sampler=sample_rows.__name__),
        counts=_delta(before["counts"], after["counts"]),
        obs=_delta(before["obs"], after["obs"]),
        traced_prefill=pre_t, traced_decode=dec_t,
        window_s=t_close - t0, window_flops=window_flops)
