"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy (``REPRO_PALLAS`` env var):
- ``auto`` (default): compiled Pallas on TPU, pure-jnp ref off-TPU.
  (Interpret mode executes the kernel body in Python per grid step —
  correct but far slower than the jnp oracle, and inside a jit it unrolls
  the whole grid into the XLA graph.  The serving hot loop runs in
  ``auto``, so off-TPU it must take the fast oracle, never interpret.)
- ``interpret``: force interpret mode (kernel tests use this).
- ``ref``: force the pure-jnp oracle (what the CPU training loops use).
- ``on``: force compiled Pallas (real TPU runs).

The wrappers own all shape normalization: flattening batch dims, padding to
tile multiples, slicing back.  Under a mesh (``jax.set_mesh``) they also
place each Pallas call with :func:`_on_mesh`, since XLA cannot partition a
Mosaic kernel.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as kref
from repro.kernels.fake_quant import fake_quant_pallas
from repro.kernels.fused_decode import fused_qkv_paged_decode_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_quant_pallas)
from repro.kernels.qmm import DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, qmm_pallas
from repro.quant.wrpn import tensor_scale


def _mode() -> str:
    m = os.environ.get("REPRO_PALLAS", "auto")
    if m not in ("auto", "interpret", "ref", "on"):
        raise ValueError(f"REPRO_PALLAS={m!r}")
    return m


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _on_mesh(kernel, rows: tuple[bool, ...], *args):
    """``kernel(*args)``, a Pallas call, placed on the ambient mesh.

    Off a mesh (or inside a ``shard_map``, where each device already holds
    its own operands) it is called as is.  On a mesh it runs in a
    ``shard_map``: the arguments flagged in ``rows`` and every output are
    split along their leading (row) axis over the mesh's batch axes when
    that axis divides, and the other arguments — weights, and the KV pool,
    whose block tables hold global block ids — are gathered whole onto
    each device.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return kernel(*args)
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = math.prod(mesh.shape[a] for a in batch)
    split = [a.shape[0] for a, r in zip(args, rows) if r]
    spec = P(batch) if batch and split and split[0] % n == 0 else P()
    return jax.shard_map(kernel, mesh=mesh, check_vma=False,
                         in_specs=tuple(spec if r else P() for r in rows),
                         out_specs=spec)(*args)


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def fake_quant(w: jax.Array, bits, scale=None) -> jax.Array:
    """WRPN QDQ on an arbitrary-shape tensor; runtime ``bits`` scalar."""
    bits = jnp.asarray(bits, jnp.int32)
    if scale is None:
        scale = tensor_scale(w)
    scale = jnp.asarray(scale, jnp.float32).reshape(())
    mode = _mode()
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return kref.fake_quant_ref(w, bits, scale)
    interpret = mode == "interpret"
    shape = w.shape
    w2 = w.reshape(-1, shape[-1]) if w.ndim != 2 else w
    M, N = w2.shape
    bm, bn = min(256, M), min(256, N)
    w2p = _pad_to(w2, (bm, bn))  # pad up to tile multiples, slice back below
    out = _on_mesh(functools.partial(fake_quant_pallas, block=(bm, bn),
                                     interpret=interpret),
                   (False, False, False), w2p, bits, scale)
    out = out[:M, :N]
    return out.reshape(shape)


def paged_attention(
    q: jax.Array,             # (B, 1, H, hd) — one new token per sequence
    k_pool: jax.Array,        # (NB, bs, KV, hd[/2]) — one layer's paged blocks
    v_pool: jax.Array,        # same container as k_pool
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32 effective lengths
    k_scale: jax.Array | None = None,  # (NB, bs, KV) f32 — quantized pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over a paged KV pool -> (B, 1, H, hd).

    Pallas path DMAs each live block once (no gather materialization);
    ref path gathers pages then runs the identical decode_attention math.
    Passing ``k_scale``/``v_scale`` selects the quantized-block path
    (int8 codes, or nibble-packed uint8 at uniform int4): blocks are
    dequantized in VMEM / post-gather, never re-materialized in HBM.
    """
    B, _, H, hd = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    mode = _mode()
    quantized = k_scale is not None
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        if quantized:
            out = kref.quant_paged_attention_ref(
                q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths)
        else:
            out = kref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                           lengths)
        return out.astype(q.dtype)
    interpret = mode == "interpret"
    if quantized:
        out = _on_mesh(
            functools.partial(paged_attention_quant_pallas,
                              interpret=interpret),
            (True, False, False, False, False, True, True),
            q.reshape(B, KV, G, hd), k_pool, v_pool, k_scale, v_scale,
            block_tables, lengths)
    else:
        out = _on_mesh(
            functools.partial(paged_attention_pallas, interpret=interpret),
            (True, False, False, True, True),
            q.reshape(B, KV, G, hd), k_pool, v_pool, block_tables, lengths)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def fused_qkv_paged_decode(
    x: jax.Array,             # (B, D) post-norm hidden, one token per row
    wq, wk, wv,               # quant.pack.Packed projection weights
    k_pool, v_pool,           # quantized paged blocks (pre-write)
    k_scale, v_scale,         # (NB, bs, KV) f32
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) int32 — lengths BEFORE the new token
    qmax,                     # scalar f32 — this layer's KV code ceiling
    *,
    rope_theta: float,
    num_heads: int,
    num_kv_heads: int,
):
    """Fused bit-serial QKV + RoPE + KV-quantize + paged attention.

    Returns ``(attn (B, 1, H, hd) in x.dtype, k_codes, v_codes, k_sc,
    v_sc)`` — codes/scales for the new token, which the caller scatters
    into the pool (write-then-attend ≡ the kernel's attend-with-splice).

    TPU path is ONE kernel (``kernels.fused_decode``); the caller checks
    ``fused_decode.fits_vmem`` first and takes the unfused qmm + paged
    attention pair when the planes do not fit.  Off-TPU, the composed
    oracle (bitwise the unfused qmm + rope + quantize + attend chain).
    """
    B, D = x.shape
    H, KV = num_heads, num_kv_heads
    packed4 = k_pool.dtype == jnp.uint8
    hd = k_pool.shape[-1] * 2 if packed4 else k_pool.shape[-1]
    mode = _mode()
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        out, kc, vc, ks, vs = kref.fused_qkv_paged_decode_ref(
            x, wq, wk, wv, k_pool, v_pool, k_scale, v_scale, block_tables,
            lengths, qmax, rope_theta, H, KV)
        return out.astype(x.dtype), kc, vc, ks, vs
    interpret = mode == "interpret"
    # RoPE rows for each sequence's write position (tiny: B × hd/2)
    from repro.models.common import rope_freqs

    inv = rope_freqs(hd, rope_theta)                          # (hd/2,)
    ang = lengths.astype(jnp.float32)[:, None] * inv          # (B, hd/2)
    out, kc, vc, ks, vs = _on_mesh(
        functools.partial(fused_qkv_paged_decode_pallas, bits_q=wq.bits,
                          bits_k=wk.bits, bits_v=wv.bits, num_heads=H,
                          interpret=interpret),
        (True,) + (False,) * 10 + (True,) * 4 + (False,),
        x, wq.planes, wq.scale, wk.planes, wk.scale, wv.planes, wv.scale,
        k_pool, v_pool, k_scale, v_scale, block_tables, lengths,
        jnp.cos(ang), jnp.sin(ang), jnp.asarray(qmax, jnp.float32))
    return out.reshape(B, 1, H, hd).astype(x.dtype), kc, vc, ks, vs


def qmm(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    *,
    bits: int,
    path: str = "auto",
    out_dtype=jnp.float32,
) -> jax.Array:
    """Batched y = x @ dequant(packed).  x: (..., K); packed: (bits, K//8, N).

    ``path='auto'`` picks bitserial when the flattened batch M ≤ 32 (decode
    regime: memory-bound, MXU idle) and dequant otherwise (DESIGN.md §3).
    """
    *batch, K = x.shape
    bts, K8, N = packed.shape
    assert bts == bits and K8 * 8 == K, (x.shape, packed.shape, bits)
    M = 1
    for b in batch:
        M *= b
    x2 = x.reshape(M, K)
    if path == "auto":
        path = "bitserial" if M <= 32 else "dequant"
    mode = _mode()
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        out = kref.qmm_ref(x2, packed, scale, bits)
        return out.astype(out_dtype).reshape(*batch, N)
    interpret = mode == "interpret"

    def run(x2, packed, scale):
        # pad M (cheap) to its tile; N and K take partial edge tiles
        # (qmm.py).  On a mesh this runs on each device's rows.
        m = x2.shape[0]
        bm = min(m, DEFAULT_BM)
        out = qmm_pallas(
            _pad_to(x2, (bm, 1)), packed, scale, bits=bits, path=path,
            block=(bm, DEFAULT_BN, DEFAULT_BK),
            interpret=interpret, out_dtype=out_dtype,
        )
        return out[:m]

    out = _on_mesh(run, (True, False, False), x2, packed, scale.reshape(1, N))
    return out.reshape(*batch, N)
