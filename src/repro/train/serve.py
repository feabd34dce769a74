"""Serving transform: pack ReLeQ's bitwidths into bitplane weights.

``quantize_for_serving`` converts a training params pytree + QuantPolicy
into the *serving layout*:

- per-layer LIST structure (the decode path unrolls layers so each layer's
  packed buffers specialize to their own bitwidth),
- every packable matrix replaced by ``{"planes": (bits, K//8, N) uint8,
  "scale": (1, N) f32, "bits": int}`` (expert banks get a leading E axis),
- embeddings kept dense but tagged ``{"w": ..., "bits": b}`` (a gather, not
  a matmul; QDQ applied at lookup),
- norms / routers / decay-LoRA etc. untouched.

Pure-jax and shape-static given the policy, so the dry-run can lower
``decode_step`` over ``jax.eval_shape(quantize_for_serving, ...)`` structs
without materializing a single weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.quant.pack import Packed, QDQ, pack_weight
from repro.quant.policy import QuantPolicy
from repro.quant.qat import get_by_path, path_key, set_by_path
from repro.quant.wrpn import FP_BITS


@functools.partial(jax.jit, static_argnums=1)
def _pack_jit(w, bits: int):
    # one fused program: run op by op, the (bits, K, N) uint32 bit planes
    # of a 4096 x 151552 head at 8 bits would take 20 GB of device memory
    return pack_weight(w.astype(jnp.float32), bits)


def _pack_matrix(w, bits: int):
    if bits >= 16:  # not worth packing; serve bf16
        return w
    return Packed(*_pack_jit(w, bits), bits)


def _pack_bank(w, bits: int):
    """(E, K, N) expert bank -> per-expert packed planes."""
    if bits >= 16:
        return w
    packed = jax.vmap(lambda m: pack_weight(m.astype(jnp.float32), bits))(w)
    return Packed(packed[0], packed[1], bits)


def quantize_for_serving(model, params, policy: QuantPolicy):
    cfg = model.cfg
    groups = model.quant_groups()
    by_key = {path_key(g.path): g for g in groups}

    # 1) unroll the stacked blocks into per-layer lists
    blocks = params["blocks"]
    if isinstance(blocks, list):  # transformer: n_sub stacked subtrees
        unrolled = [
            [jax.tree.map(lambda a: a[i], sub)
             for i in range(jax.tree.leaves(sub)[0].shape[0])]
            for sub in blocks
        ]
    else:  # rwkv: one stacked subtree
        L = jax.tree.leaves(blocks)[0].shape[0]
        unrolled = [jax.tree.map(lambda a: a[i], blocks) for i in range(L)]

    out = dict(params)
    out["blocks"] = unrolled

    # 2) walk groups, replacing leaves
    for g in groups:
        bits = policy.get(g.name)
        if g.path[0] == "blocks":
            if isinstance(blocks, list):
                sub = g.path[1]
                rest = g.path[2:]
                layer_tree = unrolled[sub][g.layer]
            else:
                rest = g.path[1:]
                layer_tree = unrolled[g.layer]
            w = get_by_path(layer_tree, rest)
            packed = _pack_bank(w, bits) if w.ndim == 3 else _pack_matrix(w, bits)
            new_layer = set_by_path(layer_tree, rest, packed)
            if isinstance(blocks, list):
                unrolled[sub][g.layer] = new_layer
            else:
                unrolled[g.layer] = new_layer
        elif g.path == ("embed",):
            if bits < FP_BITS:
                out["embed"] = QDQ(params["embed"], bits)
        elif g.path == ("lm_head",):
            out["lm_head"] = _pack_matrix(params["lm_head"], bits)
        else:  # pragma: no cover - future group kinds
            w = get_by_path(params, g.path)
            out = set_by_path(out, g.path, _pack_matrix(w, bits))
    return out


def serving_bytes(model, sparams) -> int:
    """Total weight bytes the decode step streams (roofline input)."""
    total = 0
    for leaf in jax.tree.leaves(sparams):
        total += leaf.size * leaf.dtype.itemsize
    return total


def make_decode_step(model, donate: bool = True):
    def step(sparams, cache, tokens):
        return model.decode_step(sparams, cache, tokens)

    return jax.jit(step, donate_argnums=(1,) if donate else ())


def make_prefill(model):
    """jit'd full-prompt prefill over serving-layout params (slot path).

    ``max_len`` is static (it sizes the KV cache); each distinct prompt
    length compiles its own executable — the compile churn the paged
    engine's chunked prefill (:func:`make_chunked_prefill`) eliminates.
    Kept for ``--cache slot`` parity.
    """

    def pre(sparams, tokens, max_len):
        return model.prefill(sparams, tokens=tokens, max_len=max_len)

    return jax.jit(pre, static_argnums=(2,))


def make_chunked_prefill(model, donate: bool = True):
    """jit'd fixed-shape chunk prefill into a pooled cache (paged path).

    ``step(sparams, cache, tokens (1, C), seq, start, valid)`` — C is
    static (baked by the tokens shape); seq/start/valid are data.  Any mix
    of prompt lengths therefore compiles exactly ONE executable (pinned by
    ``tests/test_serve_paged.py`` via the jit cache-size counter).  The
    pool cache is donated so chunk writes update the KV blocks in place.
    """

    def pre(sparams, cache, tokens, seq, start, valid):
        return model.prefill_chunk(sparams, cache, tokens, seq, start, valid)

    return jax.jit(pre, donate_argnums=(1,) if donate else ())


def make_verify_chunk(model, donate: bool = True):
    """jit'd batched speculative verifier over the pooled cache.

    ``ver(sparams, cache, tokens (B, C), starts (B,), valids (B,))`` — B is
    every pool row, C = spec window (k + 1); starts/valids are data, so all
    k+1 positions of every row are scored by ONE executable per window
    width (pinned alongside the prefill counter in the spec parity tests).
    Returns all-position logits (B, C, V) — the rejection sampler consumes
    them on the host.
    """

    def ver(sparams, cache, tokens, starts, valids):
        return model.verify_chunk(sparams, cache, tokens, starts, valids)

    return jax.jit(ver, donate_argnums=(1,) if donate else ())
