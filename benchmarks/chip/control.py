"""Readings that the limits of ``correct`` are set from, for one cell:

    python3 benchmarks/chip/control.py --workload <cell> --seconds 15 \\
        --seeds 11 12 13

For each seed, in one process: the program serves a short window of the
cell's traffic as a run does, the same finished greedy requests a run
would judge are drawn, and then the control, the program with its int4
KV path switched on (the nearest precision below the configuration's
int8 KV), is fed the same prompts and served tokens, position by
position.  The reference judges both, by the same numbers a run reports
(``harness.gap_stats``): the served tokens give the program's reading (the
lower one of a limit), the tokens the control puts first the control's
(the upper one).  One JSON line per seed.  Not part of a benchmark run.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CONTROL_KV_BITS = 4      # int4 KV: the nearest precision below the cells' int8


def control_tokens(model, sparams, cfg, samples, kv_bits: int):
    """What the program, with ``kv_bits`` KV blocks, puts first at each
    served position of ``samples``, fed the served tokens themselves."""
    import jax.numpy as jnp

    from repro.serve.cache import PagedCachePool
    from repro.train.serve import make_chunked_prefill, make_decode_step

    e = cfg["engine"]
    B, C = len(samples), e["prefill_chunk"]
    pool = PagedCachePool(model, B, e["max_len"], block_size=e["block_size"],
                          kv_bits=kv_bits, prefix_cache=False)
    prefill, decode = make_chunked_prefill(model), make_decode_step(model)
    out = [[] for _ in samples]
    for i, (prompt, served) in enumerate(samples):
        seq = pool.alloc_seq()
        assert seq == i and pool.ensure(seq, len(prompt) + len(served))
        for lo in range(0, len(prompt), C):
            piece = prompt[lo:lo + C]
            buf = np.zeros((1, C), np.int32)
            buf[0, :len(piece)] = piece
            logits, cache = prefill(sparams, pool.step_cache(),
                                    jnp.asarray(buf), seq, lo, len(piece))
            pool.accept(cache)
        out[i].append(int(np.asarray(logits).reshape(-1).argmax()))
    for t in range(max(len(s) for _, s in samples) - 1):
        feed = np.zeros((B, 1), np.int32)
        for i, (_, served) in enumerate(samples):
            if t + 1 < len(served):
                feed[i, 0] = served[t]
        logits, cache = decode(sparams, pool.step_cache(), jnp.asarray(feed))
        pool.accept(cache)
        top = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for i, (_, served) in enumerate(samples):
            if t + 1 < len(served):
                out[i].append(int(top[i]))
    return out


def save_fixture(red: dict, path: str, seconds: float) -> None:
    """The first ``seconds`` of a reduced trace's window, as JSON."""
    lo = red["window"][0]
    hi = lo + int(seconds * 1e9)
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    Path(path).write_text(json.dumps({
        **{k: keep(red[k]) for k in ("ops", "modules", "host", "spans", "scoped")},
        "window": [lo, hi]}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace-out", default=None,
                    help="also trace the first seed's window and write the "
                         "first 0.3 s of it, reduced, to this JSON file (the "
                         "trace tests' fixture)")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(ROOT / "src"))
    from chip import harness, reference
    from chip import spans as spansmod
    from chip import trace as tracemod
    from chip import traffic as trafficmod

    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    cfg = harness.load_config(bench, cell["config"])
    harness.chips(cell["chips"])
    harness.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        model, sparams = harness.build(cfg, seed)
        recorder = harness.Recorder(*harness._program_fns(model))
        eng = harness.make_engine(model, sparams, cfg, recorder)
        mix = trafficmod.Traffic(trafficmod.load(cell["traffic"]), seed,
                                 cfg["vocab_size"])
        docs = mix.documents()
        harness.load_documents(eng, docs)
        loop = harness.ClosedLoop(eng, mix, docs)
        harness.warm_up(loop)
        trace_dir = None
        if args.trace_out and seed == args.seeds[0]:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        w0, close, _ = harness.measure(loop, args.seconds, trace_dir)
        if trace_dir:
            save_fixture({**tracemod.load(trace_dir), **spansmod.load(trace_dir)},
                         args.trace_out, 0.3)
        harness.drain(loop, w0, close)
        samples = harness.judged_samples(loop, w0, seed)
        del loop, eng, recorder
        gc.collect()
        ctrl = control_tokens(model, sparams, cfg, samples, CONTROL_KV_BITS)
        del sparams, model
        gc.collect()
        judged = reference.logit_gaps(
            cfg, seed, [(p, s, {"served": np.asarray(s), "control": np.asarray(c)})
                        for (p, s), c in zip(samples, ctrl)],
            layer_fn=harness.family(cfg).reference_layer,
            shape=(harness.MAX_SAMPLES, cfg["engine"]["max_len"]))
        flips = sum(int(np.sum(np.asarray(c) != np.asarray(s)))
                    for (_, s), c in zip(samples, ctrl))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": harness.gap_stats([j["served"] for j in judged]),
            "control": harness.gap_stats([j["control"] for j in judged]),
            "control_kv_bits": CONTROL_KV_BITS,
            "samples": len(samples),
            "tokens": sum(len(s) for _, s in samples),
            "control_flips": flips,
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
