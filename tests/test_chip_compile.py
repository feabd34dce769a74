"""Ahead-of-time compiles of the serving path's Pallas kernels for TPU v5e.

Each test lowers one kernel wrapper from ``repro.kernels.ops`` at
glm4-9b's published widths (d_model 4096, 32 heads, 2 KV heads,
head_dim 128, d_ff 13696, vocab 151552) and compiles it with the TPU
compiler for a *described* ``v5e:2x2`` topology — no chip attached.  The
compiler refuses here what the chip would refuse (unsupported layouts,
unaligned tiles, too much VMEM), so these guard every change to the
kernels or to the tiles ``ops`` picks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import fused_decode
from repro.kernels import ops
from repro.launch.hlo_analysis import tpu_kernels
from repro.quant.pack import Packed

D, H, KV, HD, FF, VOCAB = 4096, 32, 2, 128, 13696, 151552
SLOTS, BLOCK, NB, POOL = 4, 16, 8, 64   # 4 rows x 128 tokens, 16-token blocks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip, with the
    persistent compile cache off: a compile for a described chip is written
    to it but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Compile ``fn`` at the given shapes through the compiled-Pallas
    dispatch; returns the kernel names in the executable."""
    monkeypatch.setenv("REPRO_PALLAS", "on")

    def run(fn, *args):
        return tpu_kernels(jax.jit(fn).lower(*args).compile().as_text())

    return run


def _packed(spec, K, N, bits):
    return Packed(spec((bits, K // 8, N), jnp.uint8),
                  spec((1, N), jnp.float32), bits)


@pytest.mark.parametrize("path", ["bitserial", "dequant"])
@pytest.mark.parametrize("M,K,N,bits", [
    (SLOTS, D, D, 4),          # wq / wo, one decode step
    (16, FF, D, 4),            # wd over a 16-token prefill chunk
    (SLOTS, D, VOCAB, 8),      # lm_head at the frozen groups' 8 bits
    (128, FF, D, 8),           # wd over 128 rows at 8 bits: partial K tile
    (SLOTS, 3072, 32064, 8),   # phi3-mini's lm_head: partial N tile
])
def test_qmm_compiles(spec, compiled_kernels, M, K, N, bits, path):
    w = _packed(spec, K, N, bits)
    names = compiled_kernels(
        lambda x, w: ops.qmm(x, w.planes, w.scale, bits=w.bits, path=path),
        spec((M, K), jnp.bfloat16), w)
    assert names == {f"qmm_{path}_{bits}b"}


@pytest.mark.parametrize("kv,rows,nb,pool", [
    pytest.param("bf16", SLOTS, NB, POOL, id="bf16"),
    pytest.param("int8", SLOTS, NB, POOL, id="int8"),
    pytest.param("int4", SLOTS, NB, POOL, id="int4"),
    # glm4-chat's decode: 32 rows of 128 pages over a 4097-block pool
    pytest.param("int8", 32, 128, 4097, id="int8-cell"),
    pytest.param("int4", 32, 128, 4097, id="int4-cell"),
])
def test_paged_attention_compiles(spec, compiled_kernels, kv, rows, nb, pool):
    q = spec((rows, 1, H, HD), jnp.bfloat16)
    bt, ln = spec((rows, nb), jnp.int32), spec((rows,), jnp.int32)
    if kv == "bf16":
        pages = spec((pool, BLOCK, KV, HD), jnp.bfloat16)
        names = compiled_kernels(ops.paged_attention, q, pages, pages, bt, ln)
        assert names == {"paged_decode_attention"}
        return
    dt, hds = (jnp.int8, HD) if kv == "int8" else (jnp.uint8, HD // 2)
    pages = spec((pool, BLOCK, KV, hds), dt)
    sc = spec((pool, BLOCK, KV), jnp.float32)
    names = compiled_kernels(ops.paged_attention, q, pages, pages, bt, ln,
                             sc, sc)
    assert names == {f"paged_decode_attention_{kv}"}


@pytest.mark.parametrize("kv", ["int8", "int4"])
def test_fused_decode_compiles(spec, compiled_kernels, kv):
    bits = 3   # the widest q/k/v planes within the kernel's VMEM budget
    wq, wk, wv = (_packed(spec, D, n, bits) for n in (H * HD, KV * HD,
                                                      KV * HD))
    assert fused_decode.fits_vmem(wq, wk, wv)
    dt, hds = (jnp.int8, HD) if kv == "int8" else (jnp.uint8, HD // 2)
    pool = spec((POOL, BLOCK, KV, hds), dt)
    sc = spec((POOL, BLOCK, KV), jnp.float32)

    def step(x, wq, wk, wv, pool, sc, bt, ln, qmax):
        return ops.fused_qkv_paged_decode(
            x, wq, wk, wv, pool, pool, sc, sc, bt, ln, qmax,
            rope_theta=10000.0, num_heads=H, num_kv_heads=KV)

    names = compiled_kernels(
        step, spec((SLOTS, D), jnp.bfloat16), wq, wk, wv, pool, sc,
        spec((SLOTS, NB), jnp.int32), spec((SLOTS,), jnp.int32),
        spec((), jnp.float32))
    assert names == {f"fused_qkv_paged_decode_{kv}"}


@pytest.mark.parametrize("kv", ["bf16", "int4"])
def test_kernels_compile_on_a_mesh(topo, spec, compiled_kernels, kv):
    """On a (4, 1) ("data", "model") mesh of the described chips, with the
    rows and the pool's block axis sharded as ``ServeEngine(mesh=)``
    places them, the decode kernels compile: XLA cannot partition a Mosaic
    call, so ``ops`` must place each one itself."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def on(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*axes)))

    x = on((SLOTS, D), jnp.bfloat16, "data")
    q = on((SLOTS, 1, H, HD), jnp.bfloat16, "data")
    bt, ln = on((SLOTS, NB), jnp.int32), on((SLOTS,), jnp.int32)
    w = Packed(on((4, D // 8, D), jnp.uint8), on((1, D), jnp.float32), 4)
    if kv == "bf16":
        pool = on((POOL, BLOCK, KV, HD), jnp.bfloat16, "data")
        scales = ()
    else:
        pool = on((POOL, BLOCK, KV, HD // 2), jnp.uint8, "data")
        sc = on((POOL, BLOCK, KV), jnp.float32, "data")
        scales = (sc, sc)

    def step(x, w, q, pool, bt, ln, *scales):
        return (ops.qmm(x, w.planes, w.scale, bits=w.bits),
                ops.paged_attention(q, pool, pool, bt, ln, *scales))

    with jax.set_mesh(mesh):
        names = compiled_kernels(step, x, w, q, pool, bt, ln, *scales)
    attn = "" if kv == "bf16" else f"_{kv}"
    assert names == {"qmm_bitserial_4b", f"paged_decode_attention{attn}"}
