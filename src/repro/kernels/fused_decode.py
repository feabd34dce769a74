"""Pallas TPU kernel: fused bit-serial QKV projection + quantized paged
decode attention — one kernel, zero dequantized HBM round-trips.

The unfused decode step materializes three dequantized activation tensors
(q/k/v) plus a dequantized KV gather in HBM between four kernels.  This
kernel keeps the whole token step on-chip:

  grid (B, nb), scalar-prefetched block table + lengths (same trick as
  ``paged_attention.py`` — the block table IS the BlockSpec index map):

  j == 0      bit-serial q/k/v projections straight off the packed uint8
              bitplanes (``qmm.py`` bitserial math: ``x @ W = (Σ_b 2^b
              (x @ plane_b) − n·Σx) / n · scale``), RoPE from prefetched
              cos/sin rows, then the new token's K/V quantized in-VMEM
              (``quant.pack.kv_quantize`` numerics) and emitted as code +
              scale outputs — the *caller* scatters them into the pool,
              so the kernel has no aliased in-place operands.
  every j     one physical KV block DMA'd in, dequantized in VMEM
              (codes·scale), folded into an online-softmax accumulator.
  j == nb-1   the new token's (dequantized) K/V folded in from scratch —
              numerically identical to write-then-attend — and the
              normalized output written.

Weight planes ride in whole (index map pinned to block 0, so Mosaic DMAs
them once, not once per block step).  :func:`fits_vmem` says whether
they fit its budget; ``models.transformer`` takes the unfused Pallas pair
(``qmm`` + ``paged_attention_quant``) when they do not.  The new token's
codes leave the kernel as int8 and are nibble-packed by the wrapper for
an int4 pool; nibble-packed blocks are read in even/odd head-dim parts
(see ``paged_attention.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import _nibbles
from repro.kernels.qmm import bit_rows
from repro.quant.pack import kv_pack_int4, kv_quantize

_NEG = -1e30
# Packed q/k/v planes ride whole in VMEM (double-buffered); above this
# many plane bytes the caller takes the unfused qmm + paged-attention pair.
VMEM_BUDGET = 8 << 20


def fits_vmem(wq, wk, wv) -> bool:
    """Whether the fused kernel can hold these packed projections."""
    return sum(w.planes.size for w in (wq, wk, wv)) <= VMEM_BUDGET


def _project_heads(xs, p_ref, s_ref, out_ref, *, bits: int, hd: int):
    """Bit-serial ``x @ W`` one head (``hd`` output columns) at a time,
    straight off the packed planes (``qmm.py`` bitserial math), each head's
    row rounded to the activation dtype into ``out_ref[h]``.

    ``xs``: (8, D//8) — the row split into its 8 bit-row slices
    (``qmm.split_rows``); ``p_ref``: (bits, D//8, n_heads*hd) uint8."""
    n_lvl = 2 ** (bits - 1) - 1 if bits > 1 else 1
    rowsum = jnp.sum(jnp.sum(xs.astype(jnp.float32), axis=1, keepdims=True),
                     axis=0, keepdims=True)                   # (1, 1)

    def head(h, carry):
        cols = pl.ds(pl.multiple_of(h * hd, hd), hd)
        acc = jnp.zeros((1, hd), jnp.float32)
        for b in range(bits):  # static unroll: one binary matmul per plane
            pb = p_ref[b, :, cols]
            for i in range(8):
                acc += float(1 << b) * jnp.dot(
                    xs[i:i + 1], bit_rows(pb, i).astype(xs.dtype),
                    preferred_element_type=jnp.float32)
        y = (acc - n_lvl * rowsum) / n_lvl * s_ref[:, cols]
        out_ref[pl.ds(h, 1), :] = y.astype(xs.dtype).astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], head, 0)


def _rope(x, cos, sin_signed):
    """Rotate-half RoPE on rows ``x`` (…, hd): ``x·cos + roll(x)·sin`` with
    ``cos = [c, c]`` and ``sin_signed = [-s, s]`` — the same products and
    sums as ``models.common.apply_rope``."""
    return x * cos + pltpu.roll(x, x.shape[-1] // 2, x.ndim - 1) * sin_signed


def _parts(x, parts: int):
    """(…, hd) f32 -> [(…, hd // parts)] head-dim parts ``i, i+parts, …``.
    Selection by a 0/1 matmul: each output is one input times 1, exact
    for the bf16-valued rows and small-integer codes it is used on."""
    if parts == 1:
        return [x]
    hd = x.shape[-1]
    d = jax.lax.broadcasted_iota(jnp.int32, (hd, hd // parts), 0)
    m = jax.lax.broadcasted_iota(jnp.int32, (hd, hd // parts), 1)
    return [jnp.dot(x, (d == parts * m + i).astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
            for i in range(parts)]


def _fused_kernel(bt_ref, len_ref, x_ref, qp_ref, qs_ref, kp_ref, ks_ref,
                  vp_ref, vs_ref, k_ref, v_ref, ksc_ref, vsc_ref, cos_ref,
                  sin_ref, qmax_ref,
                  o_ref, kc_out, vc_out, ksc_out, vsc_out,
                  m_ref, l_ref, acc_ref, q_s, qh_s, kh_s, vh_s, kn_s, vn_s, *,
                  bs: int, H: int, KV: int, hd: int, bits_q: int,
                  bits_k: int, bits_v: int, packed4: bool):
    b, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    G = H // KV
    parts = 2 if packed4 else 1
    scale = hd ** -0.5

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xs = x_ref[0]                                         # (8, D//8)
        qmax = qmax_ref[0, 0]
        cos, sin = cos_ref[0], sin_ref[0]                     # (1, hd)
        # projections off the packed planes, each rounded to the activation
        # dtype like apply_linear (parity with the unfused path)
        _project_heads(xs, qp_ref, qs_ref, qh_s, bits=bits_q, hd=hd)
        _project_heads(xs, kp_ref, ks_ref, kh_s, bits=bits_k, hd=hd)
        _project_heads(xs, vp_ref, vs_ref, vh_s, bits=bits_v, hd=hd)
        # apply_rope returns in the activation dtype — mirror the round-trip
        q = _rope(qh_s[...], cos, sin).astype(x_ref.dtype).astype(jnp.float32)
        for i, qp in enumerate(_parts(q, parts)):
            for kv in range(KV):
                q_s[i, kv] = qp[kv * G:(kv + 1) * G]
        k = _rope(kh_s[...], cos, sin).astype(x_ref.dtype).astype(jnp.float32)
        k_codes, k_sc = kv_quantize(k, qmax)                  # (KV, hd), (KV,)
        v_codes, v_sc = kv_quantize(vh_s[...], qmax)
        kn_s[...] = k_codes.astype(jnp.float32) * k_sc[:, None]
        for i, vp in enumerate(_parts(v_codes.astype(jnp.float32), parts)):
            vn_s[i] = vp * v_sc[:, None]
        kc_out[0] = k_codes
        vc_out[0] = v_codes
        ksc_out[0] = k_sc[None]
        vsc_out[0] = v_sc[None]

    ksc = ksc_ref[0][..., None]                               # (bs, KV, 1)
    vsc = vsc_ref[0][..., None]
    if packed4:
        k_parts = [c * ksc for c in _nibbles(k_ref[0])]
        v_parts = [c * vsc for c in _nibbles(v_ref[0])]
    else:
        k_parts = [k_ref[0].astype(jnp.float32) * ksc]
        v_parts = [v_ref[0].astype(jnp.float32) * vsc]
    s = sum(jnp.einsum("kgh,tkh->kgt", q_s[i], k,
                       preferred_element_type=jnp.float32)
            for i, k in enumerate(k_parts)) * scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    mask = pos < len_ref[b]                                   # pre-write length
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
        # fold the new token in from scratch — write-then-attend semantics
        kn = kn_s[...]                                        # (KV, hd)
        s_new = sum(jnp.einsum("kgh,kh->kg", q_s[i], kp,
                               preferred_element_type=jnp.float32)
                    for i, kp in enumerate(_parts(kn, parts))) * scale
        m_old, l_old = m_ref[...], l_ref[...]
        m_fin = jnp.maximum(m_old, s_new)
        p_new = jnp.exp(s_new - m_fin)
        corr = jnp.exp(m_old - m_fin)
        l_fin = l_old * corr + p_new                           # > 0 always
        acc = (acc_ref[...] * corr[..., None]
               + p_new[..., None] * vn_s[...][:, :, None, :])
        o_ref[0] = (acc / jnp.maximum(l_fin[..., None], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bits_q", "bits_k", "bits_v", "num_heads", "interpret"))
def fused_qkv_paged_decode_pallas(
    x: jax.Array,             # (B, D) post-norm hidden, one token per row
    wq_planes, wq_scale,      # (bits_q, D//8, H*hd) u8, (1, H*hd) f32
    wk_planes, wk_scale,      # (bits_k, D//8, KV*hd)
    wv_planes, wv_scale,      # (bits_v, D//8, KV*hd)
    k_pool, v_pool,           # (NB, bs, KV, hd) int8 | (NB, bs, KV, hd//2) u8
    k_scale, v_scale,         # (NB, bs, KV) f32
    block_tables, lengths,    # (B, nb) i32, (B,) i32 — PRE-write lengths
    cos, sin,                 # (B, hd//2) f32 RoPE rows at position lengths[b]
    qmax,                     # scalar f32 — this layer's KV code ceiling
    *,
    bits_q: int, bits_k: int, bits_v: int, num_heads: int,
    interpret: bool = False,
):
    """Returns ``(attn (B, KV, G, hd) f32, k_codes (B, KV, hd_s),
    v_codes, k_sc (B, KV) f32, v_sc (B, KV) f32)``."""
    B, D = x.shape
    H = num_heads
    NB, bs, KV, hds = k_pool.shape
    packed4 = k_pool.dtype == jnp.uint8
    parts = 2 if packed4 else 1
    hd = hds * parts
    G = H // KV
    nb = block_tables.shape[1]
    D8 = D // 8
    # row-major (B, 1, ·) layouts: every per-row block spans its array's
    # last two axes, the only legal TPU tile for a one-row block
    xs = x.reshape(B, D8, 8).transpose(0, 2, 1)                # (B, 8, D//8)
    cos_f = jnp.concatenate([cos, cos], -1)[:, None].astype(jnp.float32)
    sin_f = jnp.concatenate([-sin, sin], -1)[:, None].astype(jnp.float32)
    row = lambda b, j, bt, ln: (b, 0, 0)
    whole = lambda b, j, bt, ln: (0, 0, 0)
    vec = lambda b, j, bt, ln: (0, 0)
    page = lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)
    page_sc = lambda b, j, bt, ln: (bt[b, j], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 8, D8), row),
            pl.BlockSpec((bits_q, D8, H * hd), whole),
            pl.BlockSpec((1, H * hd), vec),
            pl.BlockSpec((bits_k, D8, KV * hd), whole),
            pl.BlockSpec((1, KV * hd), vec),
            pl.BlockSpec((bits_v, D8, KV * hd), whole),
            pl.BlockSpec((1, KV * hd), vec),
            pl.BlockSpec((1, bs, KV, hds), page),
            pl.BlockSpec((1, bs, KV, hds), page),
            pl.BlockSpec((1, bs, KV), page_sc),
            pl.BlockSpec((1, bs, KV), page_sc),
            pl.BlockSpec((1, 1, hd), row),
            pl.BlockSpec((1, 1, hd), row),
            pl.BlockSpec((1, 1), vec, memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, parts, KV, G, hds),
                         lambda b, j, bt, ln: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, KV, hd), row),
            pl.BlockSpec((1, KV, hd), row),
            pl.BlockSpec((1, 1, KV), row),
            pl.BlockSpec((1, 1, KV), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),              # running max
            pltpu.VMEM((KV, G), jnp.float32),              # running denom
            pltpu.VMEM((parts, KV, G, hds), jnp.float32),  # weighted-V acc
            pltpu.VMEM((parts, KV, G, hds), jnp.float32),  # roped q parts
            pltpu.VMEM((H, hd), jnp.float32),              # q rows pre-RoPE
            pltpu.VMEM((KV, hd), jnp.float32),             # k rows pre-RoPE
            pltpu.VMEM((KV, hd), jnp.float32),             # v rows
            pltpu.VMEM((KV, hd), jnp.float32),             # new-token K
            pltpu.VMEM((parts, KV, hds), jnp.float32),     # new-token V parts
        ],
    )
    kernel = functools.partial(
        _fused_kernel, bs=bs, H=H, KV=KV, hd=hd, bits_q=bits_q,
        bits_k=bits_k, bits_v=bits_v, packed4=packed4)
    out, kc, vc, ks, vs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, parts, KV, G, hds), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, KV, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, 1, KV), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, KV), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * VMEM_BUDGET + (16 << 20)),
        interpret=interpret,
        name=f"fused_qkv_paged_decode_{'int4' if packed4 else 'int8'}",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      xs, wq_planes, wq_scale.astype(jnp.float32),
      wk_planes, wk_scale.astype(jnp.float32),
      wv_planes, wv_scale.astype(jnp.float32),
      k_pool, v_pool, k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32), cos_f, sin_f,
      jnp.asarray(qmax, jnp.float32).reshape(1, 1))
    out = out.transpose(0, 2, 3, 4, 1).reshape(B, KV, G, hd)
    if packed4:  # the pool's nibble container: pack the new codes here
        kc, vc = kv_pack_int4(kc), kv_pack_int4(vc)
    return out, kc, vc, ks[:, 0], vs[:, 0]
