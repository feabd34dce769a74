"""Smoke run of the serving path on one TPU v5e chip.

    python chip_smoke.py               # phases (a)-(d) on one chip
    python chip_smoke.py --four-chips  # the KV pool sharded over four chips

Serves glm4-9b (``configs/glm4_9b.py``) at its published widths — d_model
4096, 32 heads, 2 KV heads, head_dim 128, d_ff 13696, vocab 151552 — with
its depth cut to 8 of 40 layers: the unrolled bf16 copy that packing makes
of all 40 layers would not fit one chip's 16 GB.  Weights are random from
seed 0, packed at the 4-bit policy with the frozen groups (embedding, head)
at 8 bits.  Everything runs through the entry points a user calls:
``launch.serve.build`` (init + ``quantize_for_serving``), then
``ServeEngine`` with the paged cache, device sampler and lookahead pipeline
at their defaults.

One chip:
  (a) bf16 paged KV: 8 requests, prompt 64, gen 32, 4 slots, greedy;
  (b) the same with ``kv_bits=8``;
  (c) the same with ``kv_bits=4``;
  (d) prefill logits of one prompt on the Pallas path against the jnp
      oracle (``REPRO_PALLAS=ref``), and the first decode's logits of
      both from the Pallas path's prefilled pool, for bf16, int8 and
      int4 KV at the 4-bit policy, and for int8 and int4 KV with the
      layers re-packed at 3 bits, where the q/k/v planes fit the fused
      decode kernel, so that kernel runs too.
``--four-chips``: a ("data", "model") = (4, 1) mesh shards the paged
pool's block axis over four chips and XLA partitions each step over it.
Its logits must match one chip's within a tolerance, and its greedy
streams must equal one chip's up to the first near-tie.  A partitioned
step rounds differently from a one-chip step, so bitwise equality is not
what it can promise.

A phase fails the run when the device is not a TPU, a compiled prefill or
decode executable lacks its Pallas kernels, a request returns fewer tokens
than asked, or a logit is not finite.  Only a run in which every phase
passed prints the JSON result line, and it is the last line of stdout.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DEPTH = 8                        # of glm4-9b's 40 layers
PROMPT, GEN, SLOTS, REQUESTS = 64, 32, 4, 8
MAX_LEN = PROMPT + GEN + 1
# Pallas-vs-oracle tolerance on the logits, ||pallas - oracle|| / ||oracle||.
PARITY_RTOL = 5e-2
PARITY_WHY = (
    "each kernel matches the oracle to ~1e-7 per matmul, but both paths "
    "round every linear and attention output to bf16, so a sum that lands "
    "on the other side of a rounding boundary moves one activation by "
    "2^-8, and the random-init layers amplify such flips: at 8 layers a CPU "
    "run measured 0.7% at d_model 64 and 1.2% at 256, growing with width.  "
    "5e-2 leaves room for d_model 4096; a wrong unpack, scale, head or "
    "block mapping errs by O(1).  Both decode from the same KV pool, the "
    "Pallas path's prefill, so the decode logits judge the decode kernels "
    "alone")
# Prefill logits with int4 KV blocks, where each path prefills its own
# pool: no int4 kernel runs in prefill, but its chunks attend over int4
# codes of their own projections
PREFILL_INT4_RTOL = 0.25
PREFILL_INT4_WHY = (
    "an int4 code step is 1/7 of a token-head's range, so a projection that "
    "one bf16 rounding moves across a code boundary moves that K or V "
    "element by up to 1/7 of its range, not 2^-8, and later chunks and "
    "layers carry it: a TPU v5e measured 0.083 at 4 bits and 8 layers.  "
    "0.25 still tells this from a wrong kernel's O(1)")
# (weights' bits, kv_bits) of phase (d), with the attention kernel each
# decode step must run: at 3 bits the q/k/v planes fit the fused kernel
PARITY_CASES = (
    (4, None, "paged_decode_attention"),
    (4, 8, "paged_decode_attention_int8"),
    (4, 4, "paged_decode_attention_int4"),
    (3, 8, "fused_qkv_paged_decode_int8"),
    (3, 4, "fused_qkv_paged_decode_int4"),
)
FOUR_WHY = (
    "the same kernels on the same operands, but XLA partitions the ops "
    "between them over the batch rows and may round a bf16 element the "
    "other way, which the random-init layers amplify as in phase (d).  A "
    "stream may leave the one-chip stream only where the one-chip margin "
    "between the two tokens is within twice the largest logit difference "
    "the partitioning makes on that request, or one bf16 step (a near-tie)")


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu(count: int = 1):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX's devices are {devs}")
    if len(devs) < count:
        fail(f"{count} TPU chips needed, found {len(devs)}")
    return devs


def kernels_of(fn, *args) -> set[str]:
    """Pallas kernels in the TPU executable that ``fn`` compiles to."""
    from repro.launch.hlo_analysis import tpu_kernels

    return tpu_kernels(fn.lower(*args).compile().as_text())


def check_kernels(prefill, decode, eng, attn: str) -> None:
    """The engine's prefill chunk must run qmm; its decode step qmm and
    the attention kernel ``attn``."""
    import jax.numpy as jnp

    C = eng.prefill_chunk
    pre = kernels_of(prefill, eng.sparams, eng.pool.step_cache(),
                     jnp.zeros((1, C), jnp.int32), 0, 0, C)
    dec = kernels_of(decode, eng.sparams, eng.pool.step_cache(),
                     jnp.zeros((SLOTS, 1), jnp.int32))
    print(f"  prefill kernels: {sorted(pre)}")
    print(f"  decode kernels:  {sorted(dec)}")
    if not any(k.startswith("qmm_") for k in pre):
        fail("prefill executable has no qmm kernel")
    if not any(k.startswith("qmm_") for k in dec):
        fail("decode executable has no qmm kernel")
    if attn not in dec:
        fail(f"decode executable has no {attn} kernel")


def finite_watch(fn, flags: list):
    """``fn`` (returns ``(logits, cache)``) that also records, on the
    device and without a sync, whether every logit was finite."""
    import jax.numpy as jnp

    def call(*args):
        logits, cache = fn(*args)
        flags.append(jnp.all(jnp.isfinite(logits)))
        return logits, cache

    call._cache_size = fn._cache_size   # the engine's recompile counter
    return call


def prompts(vocab: int):
    import numpy as np

    return np.random.default_rng(1).integers(0, vocab, (REQUESTS, PROMPT))


def serve(model, sparams, *, kv_bits=None, mesh=None, attn=None):
    """Build an engine, check its executables run the kernels (when
    ``attn`` names the attention kernel), serve the 8 requests.  Returns
    (greedy streams, engine, set-up seconds, serve seconds, the jitted
    (prefill, decode) pair)."""
    import jax
    import numpy as np

    from repro.serve import ServeEngine
    from repro.train.serve import make_chunked_prefill, make_decode_step

    t0 = time.perf_counter()
    prefill, decode = make_chunked_prefill(model), make_decode_step(model)
    flags: list = []
    eng = ServeEngine(model, sparams, num_slots=SLOTS, max_len=MAX_LEN,
                      kv_bits=kv_bits, mesh=mesh,
                      prefill_fn=finite_watch(prefill, flags),
                      decode_fn=finite_watch(decode, flags))
    if attn:   # compiled under the mesh the engine runs its steps in
        with (jax.set_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            check_kernels(prefill, decode, eng, attn)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    rids = [eng.submit(p, GEN) for p in prompts(model.cfg.vocab_size)]
    eng.run_until_drained()
    outs = [eng.output(r) for r in rids]
    seconds = time.perf_counter() - t0
    short = [len(o) for o in outs if len(o) < GEN]
    if short:
        fail(f"requests returned {short} tokens, {GEN} asked")
    if not all(bool(f) for f in flags):
        fail("non-finite logits while serving")
    if not np.all([0 <= t < model.cfg.vocab_size for o in outs for t in o]):
        fail("token id outside the vocabulary")
    return outs, eng, setup, seconds, (prefill, decode)


def logits_of(fns, model, sparams, prompt, feeds=None, *, kv_bits=None,
              mesh=None):
    """Prefill ``prompt`` into a fresh pool row, then decode ``feeds`` one
    step each (by default one step on the prefill's argmax).  ``fns`` is a
    jitted (prefill, decode) pair.  Returns (prefill logits (V,), the last
    decode's logits (V,) or None when ``feeds`` is empty, feeds)."""
    pool, seq, pre = prefilled(fns[0], model, sparams, prompt,
                               kv_bits=kv_bits, mesh=mesh)
    feeds = [int(pre.argmax())] if feeds is None else [int(t) for t in feeds]
    return pre, decode_logits(fns[1], sparams, pool, seq, feeds), feeds


def prefilled(prefill, model, sparams, prompt, *, kv_bits=None, mesh=None):
    """A fresh pool (the engine's) with ``prompt`` prefilled into one row
    in the engine's 16-token chunks: (pool, row, prefill logits (V,))."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.cache import PagedCachePool

    pool = PagedCachePool(model, SLOTS, MAX_LEN, kv_bits=kv_bits, mesh=mesh)
    seq = pool.alloc_seq()
    pool.ensure(seq, MAX_LEN)
    C = 16
    for lo in range(0, len(prompt), C):
        logits, cache = prefill(sparams, pool.step_cache(),
                                jnp.asarray(prompt[None, lo:lo + C]), seq,
                                lo, C)
        pool.accept(cache)
    return pool, seq, np.asarray(logits, np.float32).reshape(-1)


def decode_logits(step, sparams, pool, seq: int, feeds):
    """Decode ``feeds`` into row ``seq`` of ``pool``, one step each; the
    last step's logits (V,), or None when ``feeds`` is empty."""
    import jax.numpy as jnp
    import numpy as np

    dec = None
    for t in feeds:
        toks = np.zeros((SLOTS, 1), np.int32)
        toks[seq, 0] = t
        logits, cache = step(sparams, pool.step_cache(), jnp.asarray(toks))
        pool.accept(cache)
        dec = np.asarray(logits, np.float32)[seq].reshape(-1)
    return dec


def compare(name: str, got, want, rtol: float) -> dict:
    """rel-L2, max |diff| and argmax agreement of two logit vectors;
    fails above ``rtol`` or on a non-finite logit."""
    import numpy as np

    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail(f"non-finite {name} logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    out = {"rel_l2": rel, "max_abs": float(np.abs(got - want).max()),
           "argmax_equal": bool(got.argmax() == want.argmax())}
    print(f"  {name}: rel_l2={rel!r} max_abs={out['max_abs']!r} "
          f"argmax_equal={out['argmax_equal']}")
    if not rel <= rtol:
        fail(f"{name} logits differ: rel_l2={rel} > {rtol}")
    return out


def parity(model, sparams, kv_bits, attn, pallas_fns=None) -> None:
    """Phase (d), one case: the same prompt through the Pallas kernels and
    through the jnp oracle, whose f32 dots run at full precision, then one
    decode step of each from the Pallas path's prefilled pool.
    ``pallas_fns`` reuses a served phase's executables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.serve import make_chunked_prefill, make_decode_step

    prompt = prompts(model.cfg.vocab_size)[0]
    fns = pallas_fns or (make_chunked_prefill(model), make_decode_step(model))
    pool, seq, pre_p = prefilled(fns[0], model, sparams, prompt,
                                 kv_bits=kv_bits)
    dec = kernels_of(fns[1], sparams, pool.step_cache(),
                     jnp.zeros((SLOTS, 1), jnp.int32))
    print(f"  decode kernels: {sorted(dec)}")
    if attn not in dec:
        fail(f"parity decode executable has no {attn} kernel")
    feeds = [int(pre_p.argmax())]
    kv = jax.tree.map(jnp.copy, pool.cache)   # the decode step donates it
    dec_p = decode_logits(fns[1], sparams, pool, seq, feeds)
    was = os.environ.get("REPRO_PALLAS")
    os.environ["REPRO_PALLAS"] = "ref"   # read when the oracle fns trace
    try:
        with jax.default_matmul_precision("highest"):
            ref = (make_chunked_prefill(model), make_decode_step(model))
            pool_r, seq_r, pre_r = prefilled(ref[0], model, sparams, prompt,
                                             kv_bits=kv_bits)
            if seq_r != seq or not np.array_equal(pool_r.block_tables,
                                                  pool.block_tables):
                fail("the oracle's pool row is not the Pallas path's")
            pool_r.accept(kv)
            dec_r = decode_logits(ref[1], sparams, pool_r, seq, feeds)
    finally:
        if was is None:
            del os.environ["REPRO_PALLAS"]
        else:
            os.environ["REPRO_PALLAS"] = was
    compare("prefill", pre_p, pre_r,
            PREFILL_INT4_RTOL if kv_bits == 4 else PARITY_RTOL)
    compare("decode", dec_p, dec_r, PARITY_RTOL)


def peak_gb(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


def one_chip(model, sparams, dev) -> None:
    from repro.spec.draft import low_bit_view

    served = {}
    for name, kv_bits, attn in (("a", None, "paged_decode_attention"),
                                ("b", 8, "paged_decode_attention_int8"),
                                ("c", 4, "paged_decode_attention_int4")):
        require_tpu()
        print(f"phase ({name}): kv_bits={kv_bits or 'bf16'}")
        outs, eng, setup, secs, served[kv_bits] = serve(
            model, sparams, kv_bits=kv_bits, attn=attn)
        m = eng.metrics()
        print(f"  setup_s={setup!r} serve_s={secs!r} "
              f"tokens={sum(map(len, outs))} decode_steps="
              f"{m['decode_steps']} recompiles={m['recompiles']} "
              f"peak_hbm_gb={peak_gb(dev)!r}")
    print("phase (d): Pallas vs jnp oracle logits, "
          f"tolerance rel_l2 <= {PARITY_RTOL}: {PARITY_WHY}.  Prefill with "
          f"int4 KV: rel_l2 <= {PREFILL_INT4_RTOL}: {PREFILL_INT4_WHY}")
    views = {4: sparams}
    for bits, kv_bits, attn in PARITY_CASES:
        require_tpu()
        if bits not in views:
            views[bits] = low_bit_view(model, sparams, bits=bits)
        print(f" weights {bits} bits, kv_bits={kv_bits or 'bf16'}:")
        t0 = time.perf_counter()
        parity(model, views[bits], kv_bits, attn,
               served.get(kv_bits) if bits == 4 else None)
        print(f"  seconds={time.perf_counter() - t0!r} "
              f"peak_hbm_gb={peak_gb(dev)!r}")


def four_chips(model, sparams, devs) -> None:
    """The one-chip streams, then the same prompts over a (4, 1) mesh;
    logits of both compared where it matters."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    want, _, setup, secs, one = serve(model, sparams)
    print(f"one chip: setup_s={setup!r} serve_s={secs!r}")
    mesh = jax.make_mesh((4, 1), ("data", "model"), devices=devs[:4],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rep = jax.device_put(sparams, NamedSharding(mesh, P()))
    got, eng, setup, secs, four = serve(model, rep, mesh=mesh,
                                        attn="paged_decode_attention")
    for key in eng.pool.paged_keys:
        placed = {s.device for s in eng.pool.cache[key].addressable_shards}
        if len(placed) != 4:
            fail(f"pool leaf {key} is on {len(placed)} devices, not 4")
    print(f"four chips: setup_s={setup!r} serve_s={secs!r} pool leaves "
          f"{list(eng.pool.paged_keys)} sharded over 4 devices: "
          f"{eng.pool.cache['k'].sharding}")
    print(f"four chips vs one: tolerance rel_l2 <= {PARITY_RTOL}: {FOUR_WHY}")
    equal = 0
    for i, p in enumerate(prompts(model.cfg.vocab_size)):
        w, g = want[i], got[i]
        j = next((j for j, (a, b) in enumerate(zip(w, g)) if a != b), None)
        # both paths decode the one-chip stream up to where the streams
        # part (one step when they do not), so their logits there compare
        feeds = w[:1] if j is None else w[:j]
        pre1, at1, _ = logits_of(one, model, sparams, p, feeds)
        with jax.set_mesh(mesh):
            pre4, at4, _ = logits_of(four, model, rep, p, feeds, mesh=mesh)
        diffs = [compare(f"request {i} prefill", pre4, pre1, PARITY_RTOL)]
        if feeds:
            diffs.append(compare(f"request {i} decode at token {len(feeds)}",
                                 at4, at1, PARITY_RTOL))
        if j is None:
            equal += 1
            continue
        at1 = pre1 if j == 0 else at1
        gap = float(at1[w[j]] - at1[g[j]])
        # a margin of one bf16 step at the logit's size is a tie whatever
        # the measured differences
        ulp = float(2.0 ** (np.floor(np.log2(max(abs(at1[w[j]]), 1e-30)))
                            - 7))
        bound = max(2 * max(d["max_abs"] for d in diffs), ulp)
        print(f"  request {i} parts at token {j}: one chip {w[j]}, four "
              f"chips {g[j]}, one-chip margin {gap!r}, near-tie bound "
              f"{bound!r}")
        if not gap <= bound:
            fail(f"request {i} parts at token {j} with a one-chip margin "
                 f"{gap} above {bound}, twice the largest logit difference "
                 "the partitioning made on this request: not a near-tie")
    print(f"four chips: {equal} of {len(got)} greedy streams equal the "
          "one-chip run; the others part at a near-tie")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve with the KV pool sharded over four chips "
                         "and compare with one chip")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"run from a checkout of the repository: no src/repro in {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    devs = require_tpu(4 if args.four_chips else 1)
    import jax

    from repro.configs.glm4_9b import CONFIG
    from repro.launch import compile_cache
    from repro.launch.serve import build

    print(f"compile cache: {compile_cache.enable()}")
    print(f"devices: {devs}")
    print(f"device_kind: {devs[0].device_kind}")
    cfg = dataclasses.replace(CONFIG, num_layers=DEPTH)
    print(f"model: {cfg.name} depth cut to {cfg.num_layers} of "
          f"{CONFIG.num_layers} layers; widths d_model={cfg.d_model} "
          f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
          f"head_dim={cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    t0 = time.perf_counter()
    model, sparams, policy = build(cfg, bits=4)
    jax.block_until_ready(sparams)
    print(f"init_pack_s={time.perf_counter() - t0!r} "
          f"avg_bits={policy.average_bits()!r} "
          f"peak_hbm_gb={peak_gb(devs[0])!r}")
    if args.four_chips:
        four_chips(model, sparams, devs)
    else:
        one_chip(model, sparams, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
