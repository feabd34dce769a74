"""QAT parameter transform: apply the WRPN STE fake-quant to every
quantizable group at its policy bitwidth — with bitwidths entering the jit'd
step as DATA, so one executable serves every ReLeQ policy candidate.

Paths are string keys ``"blocks/0/attn/wq"``; leaves with a stacked layer
axis get a per-layer bits vector and are vmapped (nested vmap for expert
banks), so the scan-based forward sees per-layer heterogeneous bitwidths at
zero HLO cost.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.quant.policy import QuantPolicy
from repro.quant.wrpn import FP_BITS, _fq_ste, tensor_scale


def path_key(path: tuple) -> str:
    return "/".join(str(p) for p in path)


def get_by_path(tree, path: tuple):
    node = tree
    for p in path:
        node = node[p]
    return node


def set_by_path(tree, path: tuple, value):
    """Functional set returning a shallow-copied tree along the path."""
    if not path:
        return value
    head, rest = path[0], path[1:]
    if isinstance(tree, list):
        new = list(tree)
    else:
        new = dict(tree)
    new[head] = set_by_path(tree[head], rest, value)
    return new


def bits_assignment(groups, policy: QuantPolicy) -> dict[str, np.ndarray]:
    """QuantPolicy -> {path_key: int32 () or (L_stack,) array}."""
    per_path: dict[tuple, dict | int] = {}
    for g in groups:
        b = policy.get(g.name)
        if g.layer is None:
            per_path[g.path] = b
        else:
            per_path.setdefault(g.path, {})[g.layer] = b
    out = {}
    for path, v in per_path.items():
        if isinstance(v, dict):
            L = max(v) + 1
            arr = np.full((L,), FP_BITS, np.int32)
            for i, b in v.items():
                arr[i] = b
            out[path_key(path)] = arr
        else:
            out[path_key(path)] = np.int32(v)
    return out


def _paths_index(groups):
    """path_key -> path tuple (stable order)."""
    return {path_key(g.path): g.path for g in groups}


def _qdq(leaf: jax.Array, bits: jax.Array, spec=None) -> jax.Array:
    """STE fake-quant at per-output-column scale (reduce dim ``ndim - 2``
    of every leaf: the matrix contraction dim, under any stacking of
    layer/expert axes) — exactly the codes the bitplane serving path
    packs, so there is no train/serve gap.

    ``spec`` (the leaf's PartitionSpec under the ambient mesh) anchors the
    scale's and output's sharding.  Without it, GSPMD propagates a
    conflicting layout onto the (..., 1, N) scale and re-broadcasting it
    against the weight triggers an *involuntary full rematerialization* of
    the whole stacked tensor — the 22.9 GB/device fsdp failure mode the
    dryrun log pointed at wrpn.py (scale div/mul + the STE backward's
    ``|w| <= scale`` compare).
    """
    ax = leaf.ndim - 2 if leaf.ndim >= 2 else 0
    scale = jax.lax.stop_gradient(tensor_scale(leaf, axis=ax))
    bits = jnp.asarray(bits, jnp.int32)
    bits = bits.reshape(bits.shape + (1,) * (leaf.ndim - bits.ndim))
    if spec is None:
        return _fq_ste(leaf, bits, scale)
    entries = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
    scale = jax.lax.with_sharding_constraint(
        scale, P(*(None if i == ax else e for i, e in enumerate(entries))))
    return jax.lax.with_sharding_constraint(
        _fq_ste(leaf, bits, scale), P(*entries))


def quantize_params(params, bits_map: dict[str, jax.Array], groups):
    """Return params with every group's leaf QDQ'd at its bitwidth.

    Under an ambient mesh (``jax.set_mesh``) each leaf's QDQ is annotated
    with its ``dist/sharding.py`` rule-table spec — see ``_qdq``."""
    from repro.models.common import shard_profile

    idx = _paths_index(groups)
    mesh = jax.sharding.get_abstract_mesh()
    # dp profile: the step body runs inside shard_map (manual axes), where
    # sharding constraints are illegal — and pointless, params replicate
    use_mesh = not mesh.empty and shard_profile() != "dp"
    new = params
    for key, bits in bits_map.items():
        path = idx[key]
        leaf = get_by_path(params, path)
        spec = None
        if use_mesh:
            from repro.dist.sharding import leaf_spec

            spec = leaf_spec([str(p) for p in path], leaf.shape, mesh)
        new = set_by_path(new, path, _qdq(leaf, jnp.asarray(bits), spec))
    return new


def policy_for(model, default_bits: int = 8) -> QuantPolicy:
    """Fresh all-``default_bits`` policy with the model's frozen groups."""
    groups = model.quant_groups()
    return QuantPolicy(
        tuple(g.name for g in groups),
        {g.name: default_bits for g in groups},
        default_bits=default_bits,
        frozen=model.frozen_bits(),
    )
