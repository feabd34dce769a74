"""Pallas TPU kernel: paged decode attention (gather-free block attention).

The paged KV pool stores every sequence's cache as fixed-size blocks
scattered through one big ``(NB, bs, KV, hd)`` pool; a per-sequence block
table maps logical block ``j`` to its physical block id.  The jnp fallback
(``ref.paged_attention_ref``) materializes the gather — ``nb*bs`` tokens
per sequence round-trip HBM twice.  This kernel never materializes it:
the grid is ``(B, nb)`` and the *block table itself is the BlockSpec index
map* (scalar-prefetched, the canonical Pallas paged-attention trick), so
each grid step DMAs exactly one physical block into VMEM and folds it into
an online-softmax accumulator.  HBM traffic is the minimum possible: each
live block is read once.

Numerics match ``models.common.decode_attention`` (fp32 scores/softmax,
finite -1e30 mask) — the paged-vs-slot parity contract.

``paged_attention_quant_pallas`` is the same online-softmax sweep over
*quantized* KV blocks (int8 codes, or nibble-packed uint8 at uniform
int4) with per-(token, KV-head) scales: each DMA'd block is dequantized
in VMEM — ``codes.f32 * scale`` — so HBM traffic shrinks by the code
width (2-4x vs bf16) and the dequantized values never round-trip HBM.

Nibble-packed blocks are never re-interleaved in VMEM (a lane shape cast
Mosaic refuses).  Byte ``m`` holds head dims ``2m`` (low nibble) and
``2m+1`` (high nibble), so the wrapper hands the kernel ``q`` split into
its even and odd head dims on a leading axis: ``q·k = q_even·lo +
q_odd·hi``, and ``p @ v`` comes out as the same two halves, which the
wrapper interleaves back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (KV, G, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bs, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.einsum("kgh,tkh->kgt", q, k,
                   preferred_element_type=jnp.float32) * scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    mask = pos < len_ref[b]                            # (1, 1, bs)
    s = jnp.where(mask, s, _NEG)
    m_old, l_old = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m_old - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_old * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgt,tkh->kgh", p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[...][..., None], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jax.Array,             # (B, KV, G, hd)
    k_pool: jax.Array,        # (NB, bs, KV, hd)
    v_pool: jax.Array,        # (NB, bs, KV, hd)
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32 — effective (clamped) lengths
    *,
    interpret: bool = False,
) -> jax.Array:
    """One decode step of attention over paged KV, out (B, KV, G, hd) f32."""
    B, KV, G, hd = q.shape
    NB, bs, KVk, hdk = k_pool.shape
    nb = block_tables.shape[1]
    assert (KV, hd) == (KVk, hdk), (q.shape, k_pool.shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # block table + lengths
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
            # the block table IS the index map: grid step (b, j) pulls
            # physical block bt[b, j] straight from HBM
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),       # running max
            pltpu.VMEM((KV, G), jnp.float32),       # running denom
            pltpu.VMEM((KV, G, hd), jnp.float32),   # weighted-V accumulator
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, bs=bs, scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_pool, v_pool)


def _nibbles(codes):
    """(…, n) uint8 nibble pairs -> (low, high) signed codes as f32."""
    u = codes.astype(jnp.int32)
    return ((u & 0x0F) - 8).astype(jnp.float32), ((u >> 4) - 8).astype(jnp.float32)


def _paged_attn_quant_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                             vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                             bs: int, scale: float, packed4: bool):
    b, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ks = ks_ref[0][..., None]                         # (bs, KV, 1)
    vs = vs_ref[0][..., None]
    # dequantize in VMEM: codes * per-(token, head) scale.  q and the
    # accumulator carry a leading axis of head-dim parts: one part for
    # int8, (even, odd) for nibble-packed int4
    if packed4:
        k_parts = [c * ks for c in _nibbles(k_ref[0])]
        v_parts = [c * vs for c in _nibbles(v_ref[0])]
    else:
        k_parts = [k_ref[0].astype(jnp.float32) * ks]
        v_parts = [v_ref[0].astype(jnp.float32) * vs]
    s = sum(jnp.einsum("kgh,tkh->kgt", q_ref[0, i].astype(jnp.float32), k,
                       preferred_element_type=jnp.float32)
            for i, k in enumerate(k_parts)) * scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    mask = pos < len_ref[b]
    s = jnp.where(mask, s, _NEG)
    m_old, l_old = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m_old - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_old * corr + jnp.sum(p, axis=-1)
    for i, v in enumerate(v_parts):
        acc_ref[i] = acc_ref[i] * corr[..., None] + jnp.einsum(
            "kgt,tkh->kgh", p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[...][..., None], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_quant_pallas(
    q: jax.Array,             # (B, KV, G, hd)
    k_pool: jax.Array,        # (NB, bs, KV, hd) int8 | (NB, bs, KV, hd//2) u8
    v_pool: jax.Array,        # same container as k_pool
    k_scale: jax.Array,       # (NB, bs, KV) float32
    v_scale: jax.Array,       # (NB, bs, KV) float32
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32 — effective (clamped) lengths
    *,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over quantized paged KV, out (B, KV, G, hd) f32.

    Same scalar-prefetched block-table gather as the fp kernel — the block
    table IS the BlockSpec index map — but each grid step DMAs int8/int4
    codes plus a (bs, KV) scale sliver and dequantizes in VMEM.
    """
    B, KV, G, hd = q.shape
    NB, bs, KVk, hds = k_pool.shape
    nb = block_tables.shape[1]
    packed4 = k_pool.dtype == jnp.uint8
    assert KV == KVk and hds == (hd // 2 if packed4 else hd), (
        q.shape, k_pool.shape)
    parts = 2 if packed4 else 1
    # (B, parts, KV, G, hds): part i holds head dims i, i + parts, ...
    qp = q.reshape(B, KV, G, hds, parts).transpose(0, 4, 1, 2, 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, parts, KV, G, hds),
                         lambda b, j, bt, ln: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hds),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hds),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, bs, KV),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, parts, KV, G, hds),
                               lambda b, j, bt, ln: (b, 0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((parts, KV, G, hds), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attn_quant_kernel, bs=bs,
                               scale=hd ** -0.5, packed4=packed4)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, parts, KV, G, hds), jnp.float32),
        interpret=interpret,
        name=f"paged_decode_attention_{'int4' if packed4 else 'int8'}",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      qp, k_pool, v_pool, k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32))
    return out.transpose(0, 2, 3, 4, 1).reshape(B, KV, G, hd)
