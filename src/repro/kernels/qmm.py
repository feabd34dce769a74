"""Pallas TPU kernels: packed low-bit weight × activation matmul (qmm).

Two paths over the same bitplane storage (DESIGN.md §3):

``dequant`` (prefill / training-shape regime, compute-bound)
    Per (bm, bn, bk) tile: unpack the k bitplanes in VMEM, reconstruct the
    signed codes once, run ONE MXU matmul at bf16.  HBM traffic for weights
    is k/8 bytes/weight; MXU work identical to a dense matmul.

``bitserial`` (decode regime, memory-bound)
    The TPU analogue of Stripes: ``x @ W = (Σ_b 2^b (x @ plane_b) − n·Σ_k x)
    / n · scale``.  Each binary plane hits the MXU separately, so compute
    scales linearly with k — irrelevant at decode batch sizes where the MXU
    is starved anyway — and weight traffic is the same k/8 bytes/weight.
    Keeping the planes as {0,1} bf16 matmuls (instead of reconstructing)
    means the unpack loop never materializes an int tile: each plane is a
    byte-shift + mask, which Mosaic maps onto VPU lanes.

Both paths share the oracle :func:`repro.kernels.ref.qmm_ref`.

Layout notes
------------
- packed: ``(bits, K//8, N) uint8`` — N minor-most (lane axis); byte row
  ``j`` holds contraction rows ``8j..8j+7`` (row ``8j+i`` in bit ``i``).
- The kernels never interleave the 8 bit-rows of a byte back into one
  ``(K, N)`` plane (a sublane shape cast Mosaic refuses).  The wrapper
  instead permutes the *activation*: ``x`` arrives as ``(8, M, K//8)``
  with ``xs[i, :, j] = x[:, 8j+i]``, so ``x @ plane_b = Σ_i xs[i] @
  ((packed[b] >> i) & 1)`` — one 2-D shift + mask per bit row, each a
  plain MXU matmul over ``K//8``.  The permutation is a tiny XLA
  transpose of the activation (M ≤ a few hundred rows).
- The k-grid accumulates into a VMEM f32 scratch; output is written on the
  last k step (revisited-output pattern), with the per-column scale applied
  once at the end.
- Tiles: (bm, bn, bk) = (128, 256, 2048), each clipped to its axis.
  ``bk // 8`` and ``bn`` are then multiples of 128 (the lane width) or
  the whole axis, and the grid takes ``cdiv`` steps, so any N and K tile
  without padding the packed weights: a partial last N tile writes only
  its in-bounds columns, and a partial last K tile reads plane rows past
  ``K // 8`` (undefined on the chip) against activation columns that are
  zero-padded, so they add nothing.  x tile 8·128·256·2 B = 512 KiB,
  packed tile ≤ 8·256·256 B = 512 KiB, acc 128 KiB — comfortably in VMEM
  with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 256, 2048


def bit_rows(plane, i: int):
    """Bit ``i`` of every byte of a ``(rows, n)`` uint8 plane tile, as
    int32 {0, 1}: row ``j`` holds contraction row ``8j + i``."""
    return (plane.astype(jnp.int32) >> i) & 1


def split_rows(x):
    """``(M, K)`` activation -> ``(8, M, K//8)`` with ``[i, :, j] = x[:, 8j+i]``
    — the layout in which :func:`bit_rows` of a packed plane multiplies."""
    M, K = x.shape
    return x.reshape(M, K // 8, 8).transpose(2, 0, 1)


def _qmm_dequant_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *, bits, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_lvl = 2 ** (bits - 1) - 1 if bits > 1 else 1
    acc = acc_ref[...]
    for i in range(8):  # static unroll over the bit rows of each byte
        u = bit_rows(p_ref[0], i)
        for b in range(1, bits):  # Σ_b plane_b << b
            u = u + (bit_rows(p_ref[b], i) << b)
        w = (u - n_lvl).astype(jnp.float32).astype(jnp.bfloat16)
        x = x_ref[i].astype(jnp.bfloat16)
        acc += jnp.dot(x, w, preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / n_lvl * s_ref[...]).astype(o_ref.dtype)


def _qmm_bitserial_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, off_ref, *, bits, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        off_ref[...] = jnp.zeros_like(off_ref)

    acc = acc_ref[...]
    off = off_ref[...]
    for i in range(8):  # static unroll: bit rows x planes binary matmuls
        x = x_ref[i].astype(jnp.bfloat16)
        for b in range(bits):
            pb = bit_rows(p_ref[b], i).astype(jnp.float32).astype(jnp.bfloat16)
            acc += float(1 << b) * jnp.dot(x, pb,
                                           preferred_element_type=jnp.float32)
        # rank-1 offset: n_lvl · rowsum(x), accumulated over the K grid
        off += jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)
    acc_ref[...] = acc
    off_ref[...] = off

    @pl.when(k == nk - 1)
    def _finish():
        n_lvl = 2 ** (bits - 1) - 1 if bits > 1 else 1
        y = (acc_ref[...] - n_lvl * off_ref[...]) / n_lvl * s_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bits", "path", "block", "interpret", "out_dtype")
)
def qmm_pallas(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    *,
    bits: int,
    path: str = "dequant",
    block: tuple[int, int, int] = (DEFAULT_BM, DEFAULT_BN, DEFAULT_BK),
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """y[M,N] = x[M,K] @ dequant(packed[bits,K//8,N], scale[1,N]).

    M must be a multiple of its tile (ops.qmm pads it); N and K need not
    be (module notes).  ``bits`` static (the packed buffer's plane count
    is structural).
    """
    M, K = x.shape
    bts, K8, N = packed.shape
    if bts != bits or K8 * 8 != K:
        raise ValueError(f"packed {packed.shape} inconsistent with x {x.shape}, bits={bits}")
    bm, bn, bk = (min(block[0], M), min(block[1], N), min(block[2], K))
    if M % bm or bk % 8:
        raise ValueError(f"shape {(M, K, N)} not tileable by block {(bm, bn, bk)}")
    bk8 = bk // 8
    nk = pl.cdiv(K8, bk8)
    grid = (M // bm, pl.cdiv(N, bn), nk)
    x = jnp.pad(x, ((0, 0), (0, nk * bk - K)))
    kernel = _qmm_dequant_kernel if path == "dequant" else _qmm_bitserial_kernel
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if path == "bitserial":
        scratch.append(pltpu.VMEM((bm, 1), jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, bits=bits, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, bm, bk8), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bits, bk8, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"qmm_{path}_{bits}b",
    )(split_rows(x), packed, scale)
