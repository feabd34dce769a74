"""The plain float32 reference of the served model, and the comparison
that decides ``correct``.

It imports nothing of the program.  It makes the weights itself from the
run's seed (``weights.py``), quantizes them as the configuration states
(WRPN mid-tread codes with one max-abs scale per output column: the layers
at ``weight_bits``, the embedding and the head at ``frozen_bits``), keeps
K and V as ``kv_bits`` codes with one max-abs scale per token and head, and
runs a straightforward causal forward in float32 with
``default_matmul_precision("highest")``.  The block-wise forward loop here
(``logits``, ``logit_gaps``) embeds, runs a family's decoder layer
(``layer_fn``: the ``reference_layer`` of ``families/<family>.py``) layer
by layer over a block of whole sequences, and reads the logits in blocks
of rows, so that it fits beside nothing else on the chip.  The dense
family's layer is ``layer``: RMSNorm, rotary embedding (the split-half
convention), grouped-query attention and a SwiGLU MLP.

What it compares: a served token is judged by how far the reference's
logit for it lies below the reference's best logit at that position
(``gap``).  A greedy token of a sound run lies at most a rounding below
it; a wrong token lies a typical top-two margin or more below.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


def wrpn_dequant(w, bits: int):
    """Quantize-dequantize at ``bits`` with one max|w| scale per column."""
    w = w.astype(jnp.float32)
    n = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8)
    return jnp.round(jnp.clip(w / scale, -1.0, 1.0) * n) / n * scale


def kv_qdq(x, bits: int):
    """Quantize-dequantize K or V at ``bits`` with one max-abs scale per
    (token, head) over the head dimension."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / safe), -qmax, qmax) * scale


def rms_norm(x, gain, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, positions, theta: float):
    """x (T, heads, hd) rotated at ``positions`` (T,): the first and the
    second half of each head are the two coordinates of each pair."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention of one sequence: q (T, H, hd),
    k and v (T, KV, hd); query head h reads KV head h // (H // KV)."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v)


def _layer(cfg, h, lw):
    """One decoder layer over a block of sequences h (B, T, D)."""
    B, T, D = h.shape
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    bits, kv_bits = cfg["policy"]["weight_bits"], cfg["policy"]["kv_bits"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    w = {n: wrpn_dequant(lw[n], bits) for n in W.ATTN + W.MLP}
    x = rms_norm(h, lw["ln1"], eps)
    pos = jnp.arange(T)

    def one(xs):
        q = rope((xs @ w["wq"]).reshape(T, H, hd), pos, theta)
        k = kv_qdq(rope((xs @ w["wk"]).reshape(T, KV, hd), pos, theta), kv_bits)
        v = kv_qdq((xs @ w["wv"]).reshape(T, KV, hd), kv_bits)
        return attention(q, k, v).reshape(T, H * hd) @ w["wo"]

    h = h + jax.lax.map(one, x)
    x = rms_norm(h, lw["ln2"], eps)
    return h + (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def layer(cfg, key, l, h):
    """Dense decoder layer ``l`` of the seed's weights over h (B, T, D)."""
    return _layer(cfg, h, W.layer(cfg, key, l))


@functools.lru_cache(maxsize=None)
def _jits(cfg_json: str, layer_fn):
    cfg = json.loads(cfg_json)

    @jax.jit
    def embed(key, tokens):
        e = wrpn_dequant(W.embed(cfg, key), cfg["policy"]["frozen_bits"])
        return jnp.take(e, tokens, axis=0)

    @jax.jit
    def layer(key, l, h):
        return layer_fn(cfg, key, l, h)

    @jax.jit
    def head(key, h, judged):
        """Per row of h (R, D): the best logit minus the logit of each
        judged token (R, J); -1 marks a token not judged."""
        x = rms_norm(h, W.final_norm(cfg, key), cfg["norm_eps"])
        logits = x @ wrpn_dequant(W.lm_head(cfg, key), cfg["policy"]["frozen_bits"])
        got = jnp.take_along_axis(logits, jnp.maximum(judged, 0), axis=1)
        return jnp.where(judged >= 0, logits.max(axis=1, keepdims=True) - got,
                         jnp.nan)

    return embed, layer, head


def _hidden(cfg, key, toks, layer_fn):
    """Final hidden states (B, T, D) of token blocks ``toks`` (B, T)."""
    embed, layer, _ = _jits(json.dumps(cfg, sort_keys=True), layer_fn)
    h = embed(key, jnp.asarray(toks))
    for l in range(cfg["num_layers"]):
        h = layer(key, l, h)
    return h


def logits(cfg, seed: int, tokens, layer_fn=layer):
    """Full logits (T, V) of one sequence: for small sizes and tests."""
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, key, np.asarray(tokens, np.int32)[None], layer_fn)[0]
        x = rms_norm(h, W.final_norm(cfg, key), cfg["norm_eps"])
        w = wrpn_dequant(W.lm_head(cfg, key), cfg["policy"]["frozen_bits"])
        return np.asarray(x @ w)


def logit_gaps(cfg, seed: int, samples, *, layer_fn=layer, rows: int = 256,
               shape: tuple[int, int] | None = None):
    """The reference's judgement of served tokens.

    ``samples``: list of ``(prompt, served, judged)`` where ``served`` are
    the tokens the program emitted after ``prompt`` (fed back in order)
    and ``judged`` maps a label to tokens, one per served position, to be
    judged there (the served tokens themselves, or what a control put
    first).  Returns, per sample, ``{label: gaps}``: at each position, the
    reference's best logit minus its logit for the judged token.
    ``shape`` pads the block of sequences to (sequences, tokens), so that
    every call of a cell runs the same compiled programs.  ``layer_fn``
    is the family's decoder layer, ``(cfg, key, l, h) -> h``.
    """
    head = _jits(json.dumps(cfg, sort_keys=True), layer_fn)[2]
    key = W.seed_key(seed)
    labels = sorted({lab for _, _, j in samples for lab in j})
    lens = [len(p) + len(s) - 1 for p, s, _ in samples]
    B, T = shape or (len(samples), -(-max(lens) // rows) * rows)
    toks = np.zeros((B, T), np.int32)
    judged = np.full((B, T, len(labels)), -1, np.int32)
    for i, (p, s, j) in enumerate(samples):
        seq = np.concatenate([np.asarray(p), np.asarray(s)[:-1]]).astype(np.int32)
        toks[i, :len(seq)] = seq
        for c, lab in enumerate(labels):
            judged[i, len(p) - 1:len(p) - 1 + len(s), c] = j[lab]
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, key, toks, layer_fn)
        flat = h.reshape(-1, h.shape[-1])
        jflat = judged.reshape(-1, len(labels))
        gaps = np.concatenate([
            np.asarray(head(key, flat[r:r + rows], jnp.asarray(jflat[r:r + rows])))
            for r in range(0, flat.shape[0], rows)])
    gaps = gaps.reshape(B, T, len(labels))
    out = []
    for i, (p, s, _) in enumerate(samples):
        lo = len(p) - 1
        out.append({lab: gaps[i, lo:lo + len(s), c]
                    for c, lab in enumerate(labels)})
    return out
