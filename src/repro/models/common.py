"""Shared building blocks: norms, RoPE/M-RoPE, blocked attention, sharding.

Everything is a pure function over explicit param pytrees (no framework).
All attention paths are *blocked* (flash-style online softmax over KV
chunks) so the 32k/500k shapes never materialize an (S, S) score matrix —
a hard requirement for the dry-run memory analysis to prove fit.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def _mesh_axes() -> tuple[str, ...]:
    m = jax.sharding.get_abstract_mesh()
    return () if m.empty else tuple(m.axis_names)


def shard_profile() -> str:
    """Activation-sharding profile (REPRO_SHARD_PROFILE):

    - ``tp``   : batch over (pod, data); TP over model; residual replicated
                 on model (Megatron-style, the baseline).
    - ``tp_sp``: tp + the residual stream sequence-sharded over model
                 between blocks (Megatron sequence parallelism).
    - ``fsdp`` : batch over (pod, data, model) — no activation TP; weights
                 fully sharded over all axes (ZeRO-3).
    - ``dp``   : pure data parallelism under an *outer* ``shard_map``
                 (train_step.make_dp_train_step): the model body runs on a
                 per-shard local batch, so every in-model constraint must
                 no-op — sharding constraints are illegal inside manual
                 collectives, and the shard is the whole world anyway.
    """
    return os.environ.get("REPRO_SHARD_PROFILE", "tp")


def batch_axes():
    """Axes the global batch shards over."""
    prof = shard_profile()
    if prof == "dp":
        return None
    axes = ("pod", "data", "model") if prof == "fsdp" else ("pod", "data")
    axes = tuple(a for a in axes if a in _mesh_axes())
    return axes if axes else None


def model_axis():
    if shard_profile() in ("fsdp", "dp"):
        return None
    return "model" if "model" in _mesh_axes() else None


def seq_axis():
    """Residual-stream sequence axis (tp_sp profile only)."""
    if shard_profile() == "tp_sp" and "model" in _mesh_axes():
        return "model"
    return None


def readout_axes():
    """Batch axes at the vocab-parallel readout: never includes "model"
    (the vocab dim owns it in every profile — a vocab matmul whose tokens
    are also model-sharded would otherwise compute full (D, V) f32 grad
    partials on every chip; EXPERIMENTS.md §Perf)."""
    if shard_profile() == "dp":
        return None
    axes = tuple(a for a in ("pod", "data") if a in _mesh_axes())
    return axes if axes else None


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that no-ops outside a mesh context.

    spec entries may be None, an axis name, or a tuple of axis names; any
    axis not present in the ambient mesh — or whose size does not divide the
    corresponding array dim — is dropped, so the same model code runs on the
    1-device smoke mesh, the single-pod and the multi-pod mesh, and on archs
    whose head counts don't divide the model axis (e.g. glm4 kv=2).
    """
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return x
    sizes = dict(zip(m.axis_names, m.axis_sizes))
    clean = []
    for dim, s in zip(x.shape, spec):
        names = tuple(a for a in ((s,) if isinstance(s, str) else tuple(s or ()))
                      if a in sizes)
        # largest suffix whose product divides the dim (handles e.g. 1600-wide
        # dims on a 256-way combined axis by falling back to 16-way)
        pick = None
        for start in range(len(names)):
            sub = names[start:]
            prod = 1
            for a in sub:
                prod *= sizes[a]
            if dim % prod == 0:
                pick = sub[0] if len(sub) == 1 else tuple(sub)
                break
        clean.append(pick)
    if all(pick is None for pick in clean):  # fully replicated: no-op (and
        return x                             # legal inside shard_map bodies)
    return jax.lax.with_sharding_constraint(x, P(*clean))


# ---------------------------------------------------------------------------
# sequence-chunked cross-entropy (readout never materializes full logits)
# ---------------------------------------------------------------------------


def chunked_ce(readout_fn, h: jax.Array, labels: jax.Array,
               chunk: int = 512):
    """Mean next-token CE + mean logz² over (B, S) tokens.

    ``readout_fn(h_chunk) -> logits_f32``.  Scans rematerialized sequence
    chunks so only (B, chunk, V) logits are live at once; the backward
    recomputes each chunk's logits.
    """
    B, S, D = h.shape
    c = min(chunk, S)
    Sp = -(-S // c) * c
    hp = jnp.pad(h, ((0, 0), (0, Sp - S), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, Sp - S)))
    valid = jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, Sp - S)))
    nch = Sp // c
    hb = jnp.moveaxis(hp.reshape(B, nch, c, D), 1, 0)
    lb = jnp.moveaxis(lp.reshape(B, nch, c), 1, 0)
    vb = jnp.moveaxis(valid.reshape(B, nch, c), 1, 0)

    @jax.checkpoint
    def chunk_fn(carry, inp):
        nll_sum, z2_sum = carry
        hc, lc, vc = inp
        logits = readout_fn(hc)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        # label pick as a masked reduce, NOT take_along_axis: a gather over
        # the vocab-sharded dim makes GSPMD all-gather the full (B, c, V)
        # f32 logits (a 5 GB/device temp at the glm4 fsdp train_4k cell);
        # the compare+sum keeps the vocab dim sharded end to end
        vid = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        tgt = jnp.sum(jnp.where(vid == lc[..., None], logits, 0.0), axis=-1)
        nll_sum = nll_sum + jnp.sum((logz - tgt) * vc)
        z2_sum = z2_sum + jnp.sum(jnp.square(logz) * vc)
        return (nll_sum, z2_sum), None

    (nll_sum, z2_sum), _ = jax.lax.scan(
        chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hb, lb, vb))
    n = float(B * S)
    return nll_sum / n, z2_sum / n


# ---------------------------------------------------------------------------
# linear application (raw | Packed bitplane serving weight)
# ---------------------------------------------------------------------------


def apply_linear(x: jax.Array, w) -> jax.Array:
    """x @ w where w is a raw array or a Packed bitplane weight."""
    from repro.kernels import ops as kops
    from repro.quant.pack import Packed

    if isinstance(w, Packed):
        return kops.qmm(x, w.planes, w.scale, bits=w.bits).astype(x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16, scale: float | None = None):
    s = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma).astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim//2,) float32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S) int32."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]  # (..., S, 1, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions3: jax.Array, theta: float, sections: tuple[int, int, int]
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: 3 position streams (t, h, w) rotate
    disjoint sections of each head's dim.

    x: (B, S, H, hd); positions3: (3, B, S) int32; sections: half-dim split
    (sums to hd//2), e.g. hd=128 -> (16, 24, 24).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    # section id per frequency index
    sec_sizes = jnp.asarray(sections)
    bounds = jnp.cumsum(sec_sizes)
    idx = jnp.arange(hd // 2)
    sec_id = jnp.sum(idx[:, None] >= bounds[None, :], axis=1)  # 0/1/2
    # pick the position stream per frequency
    pos = positions3.astype(jnp.float32)  # (3, B, S)
    pos_sel = jnp.take(pos, sec_id, axis=0)  # (hd/2, B, S)
    ang = jnp.moveaxis(pos_sel, 0, -1) * inv  # (B, S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocked (flash-style) attention — pure JAX, O(S·chunk) memory
# ---------------------------------------------------------------------------


_NEG = -1e30  # finite "-inf" so the online-softmax carries stay NaN-free


def _tile_mask(q_pos, k_pos, S: int, causal: bool, window):
    mask = (k_pos < S)[None, :]
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return mask


def _flash_fwd_blocks(qb, kb, vb, S, causal, window, cq, ck):
    """qb: (B,nq,cq,KV,G,hd); kb/vb: (B,nk,ck,KV,hd).
    -> out (B,nq,cq,KV,G,hd) f32, lse (B,nq,cq,KV,G) f32."""
    B, nq, _, KV, G, hd = qb.shape
    nk = kb.shape[1]
    scale = hd ** -0.5

    def q_block(args):
        qi, q_tile = args
        q_pos = qi * cq + jnp.arange(cq)

        def kv_step(carry, inputs):
            acc, m, l = carry
            ki, k_tile, v_tile = inputs
            k_pos = ki * ck + jnp.arange(ck)
            s = jnp.einsum("bqkgh,bckh->bqkgc", q_tile.astype(jnp.float32),
                           k_tile.astype(jnp.float32)) * scale
            mask5 = _tile_mask(q_pos, k_pos, S, causal, window)[None, :, None, None, :]
            s = jnp.where(mask5, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask5, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqkgc,bckh->bqkgh", p, v_tile.astype(jnp.float32))
            return (acc * corr[..., None] + pv, m_new, l_new), None

        acc0 = jnp.zeros((B, cq, KV, G, hd), jnp.float32)
        m0 = jnp.full((B, cq, KV, G), _NEG, jnp.float32)
        l0 = jnp.zeros((B, cq, KV, G), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        lse = m + jnp.log(jnp.maximum(l, 1e-20))
        return out, lse

    outs, lses = jax.lax.map(q_block, (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    return jnp.moveaxis(outs, 0, 1), jnp.moveaxis(lses, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qb, kb, vb, S, causal, window, cq, ck):
    out, _ = _flash_fwd_blocks(qb, kb, vb, S, causal, window, cq, ck)
    return out


def _flash_vjp_fwd(qb, kb, vb, S, causal, window, cq, ck):
    out, lse = _flash_fwd_blocks(qb, kb, vb, S, causal, window, cq, ck)
    return out, (qb, kb, vb, out, lse)


def _flash_vjp_bwd(S, causal, window, cq, ck, res, g):
    """Manual flash backward: recompute p per (q-block × kv-block) tile from
    the saved logsumexp — score tiles never round-trip HBM as saved scan
    carries (the 6.8 TB/chip failure mode of autodiff through the fwd scan;
    EXPERIMENTS.md §Perf).  dq accumulates via scatter-add into its block
    index; dk/dv are per-kv-block scan outputs."""
    qb, kb, vb, out, lse = res
    B, nq, _, KV, G, hd = qb.shape
    nk = kb.shape[1]
    scale = hd ** -0.5
    g = g.astype(jnp.float32)
    # D_i = rowsum(dout ⊙ out): (B,nq,cq,KV,G)
    Drow = jnp.sum(g * out, axis=-1)
    lse_safe = jnp.where(lse <= _NEG / 2, 1e30, lse)  # padded rows -> p = 0

    def kv_step(dq_acc, inputs):
        ki, k_tile, v_tile = inputs        # (B,ck,KV,hd)
        k_pos = ki * ck + jnp.arange(ck)
        kf = k_tile.astype(jnp.float32)
        vf = v_tile.astype(jnp.float32)

        def q_step(carry, inputs_q):
            dk, dv, dq_acc = carry
            qi, q_tile, g_tile, lse_i, D_i = inputs_q
            q_pos = qi * cq + jnp.arange(cq)
            qf = q_tile.astype(jnp.float32)
            s = jnp.einsum("bqkgh,bckh->bqkgc", qf, kf) * scale
            mask5 = _tile_mask(q_pos, k_pos, S, causal, window)[None, :, None, None, :]
            p = jnp.where(mask5, jnp.exp(s - lse_i[..., None]), 0.0)
            dv = dv + jnp.einsum("bqkgc,bqkgh->bckh", p, g_tile)
            dp = jnp.einsum("bqkgh,bckh->bqkgc", g_tile, vf)
            ds = p * (dp - D_i[..., None]) * scale
            dk = dk + jnp.einsum("bqkgc,bqkgh->bckh", ds, qf)
            dq_i = jnp.einsum("bqkgc,bckh->bqkgh", ds, kf)
            dq_acc = dq_acc.at[:, qi].add(dq_i)
            return (dk, dv, dq_acc), None

        dk0 = jnp.zeros((B, ck, KV, hd), jnp.float32)
        dv0 = jnp.zeros((B, ck, KV, hd), jnp.float32)
        (dk, dv, dq_acc), _ = jax.lax.scan(
            q_step, (dk0, dv0, dq_acc),
            (jnp.arange(nq), jnp.moveaxis(qb, 1, 0), jnp.moveaxis(g, 1, 0),
             jnp.moveaxis(lse_safe, 1, 0), jnp.moveaxis(Drow, 1, 0)))
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros(qb.shape, jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        kv_step, dq0,
        (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
    dk = jnp.moveaxis(dks, 0, 1).astype(kb.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).astype(vb.dtype)
    return dq.astype(qb.dtype), dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def blocked_attention(
    q: jax.Array,        # (B, S, H, hd)
    k: jax.Array,        # (B, S, KV, hd)
    v: jax.Array,        # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: int | None = None,   # sliding-window size (None = full)
    q_chunk: int = 512,
    kv_chunk: int = 512,
) -> jax.Array:
    """Flash attention (fwd: online softmax over KV chunks; bwd: manual
    tile recompute via custom_vjp).  Supports GQA + SWA.  Never
    materializes (S, S); peak per-tile memory O(B·q_chunk·kv_chunk·H/KV).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    cq = min(q_chunk, S)
    ck = min(kv_chunk, S)
    Sq = -(-S // cq) * cq
    Sk = -(-S // ck) * ck
    qp = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    qb = qp.reshape(B, Sq // cq, cq, KV, G, hd)
    kb = kp.reshape(B, Sk // ck, ck, KV, hd)
    vb = vp.reshape(B, Sk // ck, ck, KV, hd)
    out = _flash(qb, kb, vb, S, causal, window, cq, ck)
    out = out.reshape(B, Sq, KV * G, hd)[:, :S]
    return out.astype(q.dtype)


def chunk_attention(
    q: jax.Array,        # (B, C, H, hd) — one fixed-shape prompt chunk
    k_ctx: jax.Array,    # (B, T, KV, hd) — already-cached context
    v_ctx: jax.Array,    # (B, T, KV, hd)
    ctx_pos: jax.Array,  # (B, T) absolute token index per context slot, -1 = empty
    k_new: jax.Array,    # (B, C, KV, hd) — this chunk's keys (pre-write)
    v_new: jax.Array,    # (B, C, KV, hd)
    q_pos: jax.Array,    # (B, C) absolute token index per query (garbage tail ok)
    *,
    window: int | None = None,
) -> jax.Array:
    """Chunked-prefill attention: queries attend [context cache ; own chunk].

    The chunk's keys are taken from ``k_new`` rather than the cache so a
    ring-layout (sliding-window) cache is never read at slots the chunk is
    about to overwrite.  Masking is purely in absolute token positions, so
    the same code covers linear caches (slot t holds token t), ring caches
    (slot s holds the youngest token ≡ s mod T), and paged gathers.  fp32
    masked softmax — same arithmetic as :func:`decode_attention`.
    """
    B, C, H, hd = q.shape
    KV = k_new.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qf = q.reshape(B, C, KV, G, hd).astype(jnp.float32)

    def scores(k):
        return jnp.einsum("bckgh,btkh->bkgct", qf,
                          k.astype(jnp.float32)) * scale

    def mask(key_pos):  # (B, Tk) -> (B, 1, 1, C, Tk)
        ok = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            ok &= q_pos[:, :, None] - key_pos[:, None, :] < window
        return ok[:, None, None]

    s = jnp.concatenate(
        [jnp.where(mask(ctx_pos), scores(k_ctx), _NEG),
         jnp.where(mask(q_pos), scores(k_new), _NEG)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    v = jnp.concatenate([v_ctx, v_new], axis=1).astype(jnp.float32)
    o = jnp.einsum("bkgct,btkh->bckgh", p, v)
    return o.reshape(B, C, H, hd).astype(q.dtype)


def decode_attention(
    q: jax.Array,        # (B, 1, H, hd) — single new token
    k_cache: jax.Array,  # (B, T, KV, hd)
    v_cache: jax.Array,  # (B, T, KV, hd)
    length: jax.Array,   # (B,) valid prefix lengths (int32)
    *,
    window: int | None = None,
) -> jax.Array:
    """One-step attention against a (possibly windowed) KV cache."""
    B, T, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,btkh->bkgt", qf, k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(T)[None, :]  # (1, T)
    valid = pos < length[:, None]
    if window is not None:
        valid &= pos >= (length[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)
