"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per the brief: sweep shapes/dtypes per kernel and assert_allclose against
the ref.py oracle.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.kernels.fake_quant import fake_quant_pallas
from repro.kernels.qmm import qmm_pallas
from repro.quant.pack import pack_weight
from repro.quant.wrpn import tensor_scale

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("shape", [(8, 128), (256, 256), (64, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", [2, 4, 8, 32])
def test_fake_quant_kernel(shape, dtype, bits):
    w = jnp.asarray(RNG.normal(size=shape), dtype)
    scale = tensor_scale(w)
    got = fake_quant_pallas(w, jnp.int32(bits), scale,
                            block=(min(128, shape[0]), min(128, shape[1])),
                            interpret=True)
    want = kref.fake_quant_ref(w, jnp.int32(bits), scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("mkn", [(8, 64, 128), (32, 128, 128), (128, 256, 128)])
@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("path", ["dequant", "bitserial"])
def test_qmm_kernel(mkn, bits, path):
    _check_qmm_kernel(RNG, *mkn, bits, path)


def _check_qmm_kernel(rng, M, K, N, bits, path):
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    planes, scale = pack_weight(w, bits)
    want = kref.qmm_ref(x, planes, scale, bits)
    got = qmm_pallas(x, planes, scale.reshape(1, N), bits=bits, path=path,
                     block=(min(128, M), min(128, N), min(128, K)),
                     interpret=True)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 2e-2, rel  # bf16 MXU accumulation tolerance


# The partial-tile tests draw from their own generators, so the shared RNG
# stream (and with it every later test's data) stays as it was.
@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("path", ["dequant", "bitserial"])
def test_qmm_kernel_partial_tiles(bits, path):
    """K//8 = 40 plane rows in tiles of 16 and N = 200 columns in tiles of
    128: the last tile of both axes is partial."""
    _check_qmm_kernel(np.random.default_rng(11), 8, 320, 200, bits, path)


@pytest.mark.parametrize("path", ["dequant", "bitserial"])
def test_qmm_ops_partial_tiles(monkeypatch, path):
    """``ops.qmm`` tiles at its defaults: K//8 = 257 plane rows and N = 300
    columns each end in a partial tile, and M = 130 pads to two row tiles."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    rng = np.random.default_rng(12)
    M, K, N = 130, 2056, 300
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    planes, scale = pack_weight(w, 4)
    want = kref.qmm_ref(x, planes, scale, 4)
    got = ops.qmm(x, planes, scale, bits=4, path=path)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 2e-2, rel


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_qmm_dtypes(xdtype):
    M, K, N = 16, 64, 128
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(M, K)), xdtype)
    planes, scale = pack_weight(w, 4)
    want = kref.qmm_ref(x.astype(jnp.float32), planes, scale, 4)
    got = qmm_pallas(x, planes, scale.reshape(1, N), bits=4,
                     block=(16, 128, 64), interpret=True)
    rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
    assert rel < 3e-2


@pytest.mark.parametrize("shape", [(3, 7, 4, 2, 1, 8),    # B,NB,bs,KV,G,hd
                                   (2, 9, 8, 1, 4, 16),
                                   (4, 5, 16, 2, 2, 16)])
@pytest.mark.parametrize("kvdtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel(shape, kvdtype):
    """Pallas paged decode attention (block-table index maps + online
    softmax) vs the gather-then-decode_attention oracle."""
    from repro.kernels.paged_attention import paged_attention_pallas

    B, NB, bs, KV, G, hd = shape
    nb = NB - 1  # logical blocks per sequence (block 0 = garbage sink)
    q = jnp.asarray(RNG.normal(size=(B, 1, KV * G, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(NB, bs, KV, hd)), kvdtype)
    v = jnp.asarray(RNG.normal(size=(NB, bs, KV, hd)), kvdtype)
    # each sequence gets a distinct permutation of physical blocks
    bt = jnp.stack([1 + (jnp.arange(nb) + b) % (NB - 1) for b in range(B)])
    lengths = jnp.asarray([(7 * b + 3) % (nb * bs) + 1 for b in range(B)],
                          jnp.int32)
    want = kref.paged_attention_ref(q, k, v, bt, lengths)
    got = paged_attention_pallas(q.reshape(B, KV, G, hd), k, v, bt, lengths,
                                 interpret=True).reshape(B, 1, KV * G, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_paged_attention_ops_dispatch():
    from repro.kernels import ops

    os.environ["REPRO_PALLAS"] = "interpret"
    try:
        B, NB, bs, KV, G, hd = 2, 5, 4, 2, 2, 8
        q = jnp.asarray(RNG.normal(size=(B, 1, KV * G, hd)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(NB, bs, KV, hd)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(NB, bs, KV, hd)), jnp.float32)
        bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        lengths = jnp.asarray([5, 8], jnp.int32)
        got = ops.paged_attention(q, k, v, bt, lengths)
        want = kref.paged_attention_ref(q, k, v, bt, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
    finally:
        os.environ["REPRO_PALLAS"] = "ref"


def test_ops_wrapper_pads_and_dispatches():
    from repro.kernels import ops

    os.environ["REPRO_PALLAS"] = "interpret"
    try:
        w = jnp.asarray(RNG.normal(size=(64, 96)), jnp.float32)
        x = jnp.asarray(RNG.normal(size=(3, 5, 64)), jnp.float32)  # odd batch
        planes, scale = pack_weight(w, 3)
        got = ops.qmm(x, planes, scale, bits=3)
        want = kref.qmm_ref(x.reshape(15, 64), planes, scale, 3).reshape(3, 5, 96)
        rel = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
        assert rel < 2e-2
    finally:
        os.environ["REPRO_PALLAS"] = "ref"


# ------------------------------------------------------- quantized KV blocks
def _quant_pools(NB, bs, KV, hd, kv_bits, seed=11):
    """Random fp pool -> (codes, scales) in the requested block container."""
    from repro.quant.pack import kv_pack_int4, kv_quantize

    rng = np.random.default_rng(seed)
    qmax = float(2 ** (kv_bits - 1) - 1)
    kf = jnp.asarray(rng.normal(size=(NB, bs, KV, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(NB, bs, KV, hd)), jnp.float32)
    kc, ks = kv_quantize(kf, qmax)
    vc, vs = kv_quantize(vf, qmax)
    if kv_bits == 4:  # nibble-packed uint8 container
        kc, vc = kv_pack_int4(kc), kv_pack_int4(vc)
    return kc, vc, ks, vs, qmax


@pytest.mark.parametrize("kv_bits", [8, 4])  # int8 codes / packed-int4 codes
@pytest.mark.parametrize("case", ["block_boundary", "length_zero", "one_block"])
def test_paged_attention_quant_edge_cases(kv_bits, case):
    """Quantized-KV paged decode in interpret mode at the edges: length
    exactly on a block boundary, all-masked length-0 garbage rows (zero
    output, no NaN from the denominator guard), and an nb == 1 table."""
    from repro.kernels.paged_attention import paged_attention_quant_pallas

    B, bs, KV, G, hd = 3, 4, 2, 2, 8
    nb = 1 if case == "one_block" else 3
    NB = 1 + B * nb
    q = jnp.asarray(RNG.normal(size=(B, 1, KV * G, hd)), jnp.float32)
    kc, vc, ks, vs, _ = _quant_pools(NB, bs, KV, hd, kv_bits)
    bt = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
    if case == "block_boundary":
        lengths = jnp.asarray([bs, 2 * bs, nb * bs], jnp.int32)
    elif case == "length_zero":
        lengths = jnp.asarray([0, 0, bs + 1], jnp.int32)
        bt = bt.at[0].set(0).at[1].set(0)  # dead rows sit on the garbage sink
    else:
        lengths = jnp.asarray([1, bs // 2, bs], jnp.int32)
    got = paged_attention_quant_pallas(
        q.reshape(B, KV, G, hd), kc, vc, ks, vs, bt, lengths,
        interpret=True).reshape(B, 1, KV * G, hd)
    want = kref.quant_paged_attention_ref(q, kc, vc, ks, vs, bt, lengths)
    assert not np.any(np.isnan(np.asarray(got)))
    live = np.asarray(lengths) > 0
    if case == "length_zero":
        # all-masked rows: the kernel's l == 0 guard yields exact zeros
        # (the jnp oracle's masked softmax degenerates to a uniform
        # average there — dead rows are never consumed, so only the
        # no-NaN/zero contract matters, not oracle agreement)
        np.testing.assert_array_equal(np.asarray(got[~live]), 0.0)
    np.testing.assert_allclose(np.asarray(got[live]),
                               np.asarray(want, np.float32)[live],
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_bits", [8, 4])
@pytest.mark.parametrize("lengths_case", ["mid", "boundary", "zero"])
def test_fused_decode_kernel_vs_ref(kv_bits, lengths_case):
    """The fused QKV+RoPE+quantize+attend kernel vs its composed oracle:
    identical codes/scales bitwise, attention output equal at activation
    (bf16) resolution."""
    from repro.kernels.fused_decode import fused_qkv_paged_decode_pallas
    from repro.models.common import rope_freqs
    from repro.quant.pack import Packed

    B, nb, bs, KV, G, hd, D = 3, 3, 4, 2, 2, 8, 32
    H = KV * G
    NB = 1 + B * nb
    Tc = nb * bs
    kc, vc, ks, vs, qmax = _quant_pools(NB, bs, KV, hd, kv_bits)
    bt = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
    lengths = {"mid": [1, 5, Tc - 1],
               "boundary": [bs - 1, bs, 2 * bs - 1],
               "zero": [0, 0, 3]}[lengths_case]
    lengths = jnp.asarray(lengths, jnp.int32)
    x = jnp.asarray(RNG.normal(size=(B, D)), jnp.bfloat16)
    packs = {}
    for name, n_out, bits in (("wq", H * hd, 4), ("wk", KV * hd, 3),
                              ("wv", KV * hd, 8)):
        p, s = pack_weight(jnp.asarray(RNG.normal(size=(D, n_out)),
                                       jnp.float32), bits)
        packs[name] = Packed(p, s, bits)
    wq, wk, wv = packs["wq"], packs["wk"], packs["wv"]
    ro, rkc, rvc, rks, rvs = kref.fused_qkv_paged_decode_ref(
        x, wq, wk, wv, kc, vc, ks, vs, bt, lengths, jnp.float32(qmax),
        1e4, H, KV)
    inv = rope_freqs(hd, 1e4)
    ang = lengths.astype(jnp.float32)[:, None] * inv
    po, pkc, pvc, pks, pvs = fused_qkv_paged_decode_pallas(
        x, wq.planes, wq.scale, wk.planes, wk.scale, wv.planes, wv.scale,
        kc, vc, ks, vs, bt, lengths, jnp.cos(ang), jnp.sin(ang),
        jnp.float32(qmax), bits_q=wq.bits, bits_k=wk.bits, bits_v=wv.bits,
        num_heads=H, interpret=True)
    po = po.reshape(B, 1, H, hd)
    assert not np.any(np.isnan(np.asarray(po)))
    np.testing.assert_array_equal(np.asarray(pkc), np.asarray(rkc))
    np.testing.assert_array_equal(np.asarray(pvc), np.asarray(rvc))
    np.testing.assert_array_equal(np.asarray(pks), np.asarray(rks))
    np.testing.assert_array_equal(np.asarray(pvs), np.asarray(rvs))
    # output contract is the activation dtype (bf16): exact there
    np.testing.assert_array_equal(
        np.asarray(po.astype(jnp.bfloat16), np.float32),
        np.asarray(ro.astype(jnp.bfloat16), np.float32))
