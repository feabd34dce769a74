"""Pallas TPU kernel: paged decode attention (gather-free block attention).

The paged KV pool stores every sequence's cache as fixed-size blocks
(pages) scattered through one big ``(NB, bs, KV, hd)`` pool; a
per-sequence block table maps logical page ``j`` to its physical block
id.  The jnp fallback (``ref.paged_attention_ref``) materializes the
gather — ``nb*bs`` tokens per sequence round-trip HBM twice.  This kernel
never materializes it.

The grid is ``(B, ceil(nb / P))``: each grid step folds ``P`` consecutive
pages of one row into an online-softmax accumulator.  Each pool (K and V
codes, and for quantized pools the K and V ``(bs, KV)`` scale slivers)
is handed to the kernel as ``P`` page operands; operand ``i`` of step
``(b, s)`` is block ``bt[b, s*P + i]`` — the scalar-prefetched block
table IS the BlockSpec index map — so the pipeline copies a step's pages
of every pool together, double-buffered, while the step before
computes, across row ends too.  (Gathering the scales into one block a
step instead would halve the operands, but XLA's gather of the padded
``(bs, KV=2)`` scale tiles costs a decode step more than it saves.)

The pipeline re-fetches an operand only when its block index changes,
and the index maps use that to skip: the steps past a row's last live
page (``ceil(len / bs)``) repeat the last live step's indices and
compute nothing, and in the last live step a page operand past the
row's end keeps the page it held at the step before.  So each live page
is copied once, no dead step copies or computes anything, and a dead
step costs only the grid step itself.  The output is written at a row's
last grid step; a length-0 row gets exact zeros.

A step's scores are one 2-D matmul per KV head over its ``P * bs``
tokens, not an einsum batched over the head axis: that axis sits between
tokens and head dims, and the batched form costs Mosaic a transpose of
every page.  (The pools are not ``memory_space=ANY`` with hand-made
copies: Mosaic lays an int8 ``(KV=2, hd)`` page out padded to 4 rows,
and refuses a copy of the logical 2-row page from it.)

``P`` (``pages_per_step``) comes from the shapes alone: ``TOKENS_PER_STEP``
tokens a step, fewer where the double-buffered operands and the step's
float32 working set would pass ``VMEM_BUDGET``, and never more than
``nb``.

Numerics match ``models.common.decode_attention`` (fp32 scores/softmax,
finite -1e30 mask) — the paged-vs-slot parity contract.

``paged_attention_quant_pallas`` runs the same sweep over *quantized* KV
pages (int8 codes, or nibble-packed uint8 at uniform int4) with
per-(token, KV-head) scales: each copied page is dequantized in VMEM —
``codes.f32 * scale`` — so HBM traffic shrinks by the code width (2-4x vs
bf16) and the dequantized values never round-trip HBM.

Nibble-packed pages are never re-interleaved in VMEM (a lane shape cast
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
TOKENS_PER_STEP = 256
# VMEM a step may hold: both buffers of every page operand, the step's
# float32 K and V, and one head's slice of each.  Half of
# ``VMEM_LIMIT``, v5e's default scoped limit, which the kernel states so
# that a compile for a described chip refuses what the chip would.
VMEM_BUDGET = 8 << 20
VMEM_LIMIT = 16 << 20


def _tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array in TPU tiles: the last dim padded to 128
    lanes, the second-last to the dtype's sublane tile (8 rows of 32 bits,
    so 16 for 16-bit and 32 for 8-bit values)."""
    *lead, rows, lanes = shape
    sub = 8 * 4 // itemsize
    n = -(-rows // sub) * sub * -(-lanes // 128) * 128 * itemsize
    for d in lead:
        n *= d
    return n


def step_vmem_bytes(P: int, bs: int, kv: int, hds: int, dtype) -> int:
    """VMEM the sweep holds at ``P`` pages a step over pools of ``dtype``
    pages ``(bs, kv, hds)`` (int8 / uint8 are quantized, with float32
    scales; uint8 packs two int4 codes a byte): see ``VMEM_BUDGET``."""
    dtype = jnp.dtype(dtype)
    t = P * bs
    parts = 2 if dtype == jnp.uint8 else 1
    n = 2 * 2 * P * _tile_bytes((bs, kv, hds), dtype.itemsize)  # 2 slots x K, V
    if dtype in (jnp.int8, jnp.uint8):
        n += 2 * 2 * P * _tile_bytes((bs, kv), 4)
    n += 2 * parts * _tile_bytes((t, kv, hds), 4)     # f32 K and V
    return n + 2 * parts * _tile_bytes((t, hds), 4)   # one head's slices


def pages_per_step(bs: int, kv: int, hds: int, dtype, nb: int) -> int:
    """Pages one grid step folds in: ``TOKENS_PER_STEP // bs``, cut until
    the step fits ``VMEM_BUDGET``, at most ``nb`` and at least 1."""
    P = max(1, min(nb, TOKENS_PER_STEP // bs))
    while P > 1 and step_vmem_bytes(P, bs, kv, hds, dtype) > VMEM_BUDGET:
        P -= 1
    return P


def _nibbles(codes):
    """(…, n) uint8 nibble pairs -> (low, high) signed codes as f32."""
    u = codes.astype(jnp.int32)
    return ((u & 0x0F) - 8).astype(jnp.float32), ((u >> 4) - 8).astype(jnp.float32)


def _fp_pages(k, v):
    """One step's fp pages -> ([K parts], [V parts]) in f32."""
    return [k.astype(jnp.float32)], [v.astype(jnp.float32)]


def _quant_pages(k, v, ks, vs, *, packed4: bool):
    """One step's quantized pages and (t, KV) scales -> f32 parts:
    ``codes * scale``, split into (low, high) nibbles for packed int4."""
    ks, vs = ks[..., None], vs[..., None]
    if packed4:
        return ([c * ks for c in _nibbles(k)], [c * vs for c in _nibbles(v)])
    return ([k.astype(jnp.float32) * ks], [v.astype(jnp.float32) * vs])


def _step_pages(block_tables, lengths, bs: int, P: int):
    """(B, S*P) physical block of page operand ``i`` at grid step ``s``,
    at ``[b, s*P + i]``.

    Steps past the row's last live page repeat the last live step's
    blocks, and an operand whose page lies past the row's end keeps the
    block it held at the step before: the pipeline re-fetches a block only
    when its index changes, so neither costs a copy.  (A row's first step
    has no such block of its own row and repeats the last live page.)"""
    B, nb = block_tables.shape
    S = pl.cdiv(nb, P)
    last = jnp.maximum(jnp.minimum(pl.cdiv(lengths, bs), nb) - 1, 0)[:, None]
    s = jnp.minimum(jnp.arange(S * P, dtype=jnp.int32)[None] // P, last // P)
    j = s * P + jnp.arange(S * P, dtype=jnp.int32)[None] % P
    j = jnp.where(j <= last, j, jnp.where(s > 0, j - P, last))
    return jnp.take_along_axis(block_tables, j, axis=1)


def _sweep_kernel(pg_ref, len_ref, q_ref, *refs, P: int, bs: int,
                  scale: float, dequant):
    """Online-softmax sweep over ``P`` pages a grid step.

    ``refs``: ``P`` page operands of each pool (K and V codes, then K and
    V scales where the pool is quantized), the output, then the running
    max, denominator and weighted-V accumulator."""
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    pages = [refs[n:n + P] for n in range(0, len(refs) - 4, P)]
    b, s = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    t = P * bs

    @pl.when(s == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s * t < length)
    def _():
        # (t, KV, ...) in page order.  q and the accumulator carry a
        # leading axis of head-dim parts: one part, or (even, odd) for
        # nibble-packed int4
        k_parts, v_parts = dequant(*(
            jnp.concatenate([r[0] for r in pool], axis=0) for pool in pages))
        KV = k_parts[0].shape[1]
        # one 2-D matmul per KV head: a batched einsum over the head axis,
        # which sits between tokens and head dims, costs Mosaic a
        # transpose of every page
        sc = jnp.stack([sum(jax.lax.dot_general(
            q_ref[0, i, h].astype(jnp.float32), k[:, h, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for i, k in enumerate(k_parts)) for h in range(KV)]) * scale
        pos = s * t + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
        mask = pos < length                               # (1, 1, t)
        sc = jnp.where(mask, sc, _NEG)                    # (KV, G, t)
        m_old, l_old = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1))
        p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m_old - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_old * corr + jnp.sum(p, axis=-1)
        for i, v in enumerate(v_parts):
            pv = jnp.stack([jnp.dot(p[h], v[:, h, :],
                                    preferred_element_type=jnp.float32)
                            for h in range(KV)])
            acc_ref[i] = acc_ref[i] * corr[..., None] + pv

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[...][..., None], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


def _sweep(qp, pools, block_tables, lengths, *, dequant, name: str,
           interpret: bool):
    """Run the sweep: ``qp`` (B, parts, KV, G, hds), ``pools`` the arrays
    whose leading axis is the physical page: the (NB, bs, KV, hds) K and
    V pools, then their (NB, bs, KV) scales where quantized.  Returns
    (B, parts, KV, G, hds) f32."""
    B, parts, KV, G, hds = qp.shape
    bs = pools[0].shape[1]
    nb = block_tables.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    P = pages_per_step(bs, KV, hds, pools[0].dtype, nb)

    def page_spec(a, i):
        zeros = (0,) * (a.ndim - 1)
        return pl.BlockSpec((1,) + a.shape[1:],
                            lambda b, s, pg, ln: (pg[b, s * P + i],) + zeros)

    q_spec = pl.BlockSpec((1, parts, KV, G, hds),
                          lambda b, s, pg, ln: (b, 0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # step pages + lengths
        grid=(B, pl.cdiv(nb, P)),
        in_specs=[q_spec] + [page_spec(a, i) for a in pools
                             for i in range(P)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),           # running max
            pltpu.VMEM((KV, G), jnp.float32),           # running denom
            pltpu.VMEM((parts, KV, G, hds), jnp.float32),  # weighted V
        ],
    )
    kernel = functools.partial(_sweep_kernel, P=P, bs=bs,
                               scale=(parts * hds) ** -0.5, dequant=dequant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(_step_pages(jnp.asarray(block_tables, jnp.int32), lengths, bs, P),
      lengths, qp, *(a for a in pools for _ in range(P)))


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
    assert (KV, hd) == k_pool.shape[2:], (q.shape, k_pool.shape)
    out = _sweep(q[:, None], (k_pool, v_pool), block_tables, lengths,
                 dequant=_fp_pages, name="paged_decode_attention",
                 interpret=interpret)
    return out[:, 0]


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

    The fp kernel's sweep over int8/int4 code pages and their (bs, KV)
    scale slivers, dequantized in VMEM.
    """
    B, KV, G, hd = q.shape
    NB, bs, KVk, hds = k_pool.shape
    packed4 = k_pool.dtype == jnp.uint8
    assert KV == KVk and hds == (hd // 2 if packed4 else hd), (
        q.shape, k_pool.shape)
    parts = 2 if packed4 else 1
    # (B, parts, KV, G, hds): part i holds head dims i, i + parts, ...
    qp = q.reshape(B, KV, G, hds, parts).transpose(0, 4, 1, 2, 3)
    out = _sweep(qp, (k_pool, v_pool, k_scale.astype(jnp.float32),
                      v_scale.astype(jnp.float32)), block_tables, lengths,
                 dequant=functools.partial(_quant_pages, packed4=packed4),
                 name=f"paged_decode_attention_{'int4' if packed4 else 'int8'}",
                 interpret=interpret)
    return out.transpose(0, 2, 3, 4, 1).reshape(B, KV, G, hd)
