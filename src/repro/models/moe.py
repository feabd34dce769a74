"""Mixture-of-Experts FFN: sort-based dispatch with capacity dropping.

Why sort-based (vs the one-hot dispatch einsum): the dispatch einsum is
O(T²·k·cf) FLOPs in local token count T — at 65k tokens/shard it costs more
than the experts themselves by 100×.  Sorting tokens by expert and
scatter/gathering into a (E, C, D) capacity buffer is O(T·D + T log T) and
maps onto GSPMD expert parallelism: the buffer is sharded over experts
("model" axis) while tokens stay batch-sharded — the scatter across those
two shardings is exactly the MoE all-to-all.

Top-k routing follows the configs: softmax gates, renormalized over the
selected k (moonshot top-6, llama4 top-1).  Tokens beyond an expert's
capacity C = ceil(cf · T·k/E) are dropped (standard TPU practice; the
residual path carries them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import batch_axes, constrain, swiglu


def moe_params_shape(E: int, D: int, F: int):
    return {
        "router": (D, E),
        "wg": (E, D, F),
        "wu": (E, D, F),
        "wd": (E, F, D),
    }


def init_moe(key, E: int, D: int, F: int, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    s_in, s_out = D ** -0.5, F ** -0.5
    return {
        "router": (jax.random.normal(ks[0], (D, E), jnp.float32) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * s_in).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * s_in).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * s_out).astype(dtype),
    }


def _maybe_dequant_bank(w, dtype):
    """Serving path: Packed expert bank (planes (E,bits,K/8,F), scale
    (E,1,F)) -> dequantized bank (E, K, F).  Traffic from HBM is the packed
    buffer (k/8 bytes/weight); the bf16 bank is a transient."""
    from repro.quant.pack import Packed, dequant_packed

    if not isinstance(w, Packed):
        return w
    deq = jax.vmap(lambda pl, sc: dequant_packed(pl, sc, w.bits))
    bank = deq(w.planes, w.scale)
    return constrain(bank.astype(dtype), "model", None, None)


def _dispatch(x2: jax.Array, idx_k: jax.Array, gate_k: jax.Array, E: int,
              C: int, k: int):
    """Per-group sort-based dispatch: x2 (T, D) -> (buf (E, C, D), meta).

    Vmapped over the batch dim by moe_ffn, so every sort/scatter is LOCAL
    to one sequence's shard — GSPMD partitions batched ops on their batch
    dim natively.  (A single global sort/scatter over all tokens does NOT
    partition: the compiler falls back to full rematerialization — the
    172-334 GB/device failure mode; EXPERIMENTS.md §Perf.)
    """
    T, D = x2.shape
    flat_e = idx_k.reshape(-1)                                 # (T·k,)
    order = jnp.argsort(flat_e, stable=True)                   # priority = token order
    se = flat_e[order]
    src = order // k
    first = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * k) - first[se]
    valid = pos < C
    dst = jnp.where(valid, se * C + pos, E * C)                # E*C = OOB sentinel
    xs = x2[src] * valid[:, None].astype(x2.dtype)
    buf = jnp.zeros((E * C, D), x2.dtype).at[dst].set(xs, mode="drop")
    return buf.reshape(E, C, D), (order, src, dst, valid)


def _undispatch(y_e: jax.Array, gate_k: jax.Array, meta, T: int, k: int):
    order, src, dst, valid = meta
    E, C, D = y_e.shape
    y_flat = y_e.reshape(E * C, D)
    y_sorted = y_flat[jnp.minimum(dst, E * C - 1)] * valid[:, None].astype(y_flat.dtype)
    gate_sorted = gate_k.reshape(-1)[order].astype(y_flat.dtype)
    return jnp.zeros((T, D), y_flat.dtype).at[src].add(
        y_sorted * gate_sorted[:, None])


def _route(x: jax.Array, router: jax.Array, k: int):
    """(gates (…,E), gate_k, idx_k, aux-loss ingredients)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(gates, k)
    gate_k = gate_k / jnp.maximum(jnp.sum(gate_k, -1, keepdims=True), 1e-9)
    return gates, gate_k, idx_k


def _expert_ffn(bufe, p, dtype):
    """bufe (E, C, D) or (B, E, C, D) — batched expert SwiGLU."""
    wg, wu, wd = (_maybe_dequant_bank(p[m], dtype) for m in ("wg", "wu", "wd"))
    eq_in = "becd,edf->becf" if bufe.ndim == 4 else "ecd,edf->ecf"
    eq_out = "becf,efd->becd" if bufe.ndim == 4 else "ecf,efd->ecd"
    g = jnp.einsum(eq_in, bufe, wg)
    u = jnp.einsum(eq_in, bufe, wu)
    h = swiglu(g, u)
    return jnp.einsum(eq_out, h, wd)


def _aux_loss(gates, idx_k, E: int, k: int):
    me = jnp.mean(gates.reshape(-1, E), axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx_k.reshape(-1)].add(
        1.0).astype(jnp.float32) / max(idx_k.size, 1)
    return E * jnp.sum(me * ce)


def moe_ffn(
    x: jax.Array,              # (B, S, D)
    p: dict,
    *,
    k: int,
    capacity_factor: float = 1.25,
    no_drop: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar).

    Under a mesh with a "model" axis this runs the explicit
    expert-parallel path (shard_map + all-to-all, see ``_moe_ep``): the
    GSPMD-auto formulation replicates the dispatch gather/scatter inside
    the layer scan (hundreds of GB/device; EXPERIMENTS.md §Perf).
    Meshless (smoke tests / CPU search): local per-sequence dispatch.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and "model" in mesh.axis_names:
        return _moe_ep(x, p, k=k, capacity_factor=capacity_factor,
                       no_drop=no_drop, mesh=mesh)
    B, S, D = x.shape
    E = p["router"].shape[1]
    gates, gate_k, idx_k = _route(x, p["router"], k)
    aux = _aux_loss(gates, idx_k, E, k)
    C = S * k if no_drop else int(-(-S * k * capacity_factor // E))
    C = max(8, -(-C // 8) * 8)
    bufe, meta = jax.vmap(
        lambda x2, i, g: _dispatch(x2, i, g, E, C, k))(x, idx_k, gate_k)
    y_e = _expert_ffn(bufe, p, x.dtype)
    y = jax.vmap(lambda ye, gk, m: _undispatch(ye, gk, m, S, k))(
        y_e, gate_k, meta)
    return y.astype(x.dtype), aux


def _moe_ep(x, p, *, k, capacity_factor, no_drop, mesh):
    """Explicit expert parallelism: shard_map over the whole mesh.

    Per device: local top-k + sort-based dispatch into an (E, C_loc, D)
    buffer; all-to-all over "model" exchanges expert slices (each model
    shard owns E/m experts); batched expert FFN; inverse all-to-all;
    local un-dispatch.  Everything inside is device-local — no GSPMD
    guessing — and the a2a is the canonical MoE collective.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E = p["router"].shape[1]
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.axis_sizes))
    m_sz = sizes["model"]
    if E % m_sz:
        raise ValueError(f"experts {E} must divide model axis {m_sz}")
    # activation layout: tokens sharded over EVERY axis inside the MoE —
    # batch over the (profile) batch axes, sequence over "model" when the
    # batch doesn't already cover it.  Tokens replicated over model would
    # make each expert shard process every token m× (caught by the VMA
    # check); sequence-sharding on entry removes the redundancy.
    from repro.models.common import batch_axes

    baxes = tuple(batch_axes() or ())
    prod = 1
    for a in baxes:
        prod *= sizes[a]
    while baxes and B % prod:
        prod //= sizes[baxes[0]]
        baxes = baxes[1:]
    seq_over_model = "model" not in baxes and S % m_sz == 0
    check_vma = True
    if not seq_over_model and "model" not in baxes:
        check_vma = False  # decode fallback: tiny redundant compute over model
    x_spec = P(baxes if len(baxes) > 1 else (baxes[0] if baxes else None),
               "model" if seq_over_model else None, None)
    B_loc = B // max(prod, 1)
    T_loc = B_loc * (S // m_sz if seq_over_model else S)
    C = T_loc * k if no_drop else int(-(-T_loc * k * capacity_factor // E))
    C = max(8, -(-C // 8) * 8)

    bank_spec = P("model", None, None)

    def local(x_loc, router, wg, wu, wd):
        Bl, Sl, Dl = x_loc.shape
        x2 = x_loc.reshape(Bl * Sl, Dl)
        gates, gate_k, idx_k = _route(x2, router, k)
        aux = _aux_loss(gates, idx_k, E, k)
        red = baxes + (("model",) if seq_over_model else ())
        if red:  # aux varies only across the token-sharded axes
            aux = jax.lax.pmean(aux, axis_name=red if len(red) > 1 else red[0])
        buf, meta = _dispatch(x2, idx_k, gate_k, E, C, k)  # (E, C, D)
        # a2a: (m, E_loc, C, D) -> (E_loc, m·C, D) on each model shard
        buf = buf.reshape(m_sz, E // m_sz, C, Dl)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=2,
                                  tiled=True)          # (1, E_loc, m·C, D)
        recv = recv.reshape(E // m_sz, m_sz * C, Dl)
        y_loc = _expert_ffn(recv, {"wg": wg, "wu": wu, "wd": wd}, x_loc.dtype)
        y_loc = y_loc.reshape(1, E // m_sz, m_sz * C, Dl)
        back = jax.lax.all_to_all(y_loc, "model", split_axis=2, concat_axis=0,
                                  tiled=True)          # (m, E_loc, C, D)
        y_e = back.reshape(E, C, Dl)
        y = _undispatch(y_e, gate_k, meta, Bl * Sl, k)
        return y.reshape(Bl, Sl, Dl).astype(x_loc.dtype), aux

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, P(), bank_spec, bank_spec, bank_spec),
        out_specs=(x_spec, P()),
        check_vma=check_vma,
    )
    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    if hasattr(wg, "planes"):  # Packed serving bank: dequant before entry
        wg, wu, wd = (_maybe_dequant_bank(p[m], x.dtype)
                      for m in ("wg", "wu", "wd"))
    y, aux = fn(x, p["router"], wg, wu, wd)
    return y, aux[()] if hasattr(aux, "shape") and aux.shape else aux
