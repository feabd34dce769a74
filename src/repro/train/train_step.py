"""QAT train/eval step factories.

``train_step(state, batch, bits_map)``:
  1. QDQ every quantizable weight group at its (runtime-data) bitwidth —
     the paper's WRPN technique with STE, so "short retrain" inside the
     ReLeQ environment is just N of these steps at the candidate policy.
  2. forward + backward with the configured remat policy,
  3. AdamW update (fp32 or int8 moments).

``bits_map`` is a pytree of int32 leaves: feeding the SAME executable
different policies costs nothing — that's what makes the RL environment's
inner loop cheap at scale (DESIGN.md §4).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamW, global_norm
from repro.quant.qat import quantize_params


def init_state(model, optimizer: AdamW, rng) -> dict:
    params = model.init(rng)
    return {"params": params, "opt": optimizer.init(params)}


def make_train_step(model, optimizer: AdamW, *, remat: str = "none",
                    donate: bool = True):
    groups = model.quant_groups()

    def step(state, batch, bits_map):
        def loss_fn(params):
            qp = quantize_params(params, bits_map, groups)
            return model.loss(qp, batch, remat=remat)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state["params"])
        new_params, new_opt = optimizer.update(state["params"], grads, state["opt"])
        out = {"loss": loss, "grad_norm": global_norm(grads), **metrics}
        return {"params": new_params, "opt": new_opt}, out

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_dp_train_step(model, optimizer: AdamW, mesh, *, axis: str = "data",
                       planes: int = 2, remat: str = "none",
                       donate: bool = True):
    """Pure data-parallel QAT train step with a *compressed* gradient
    all-reduce (``dist.collectives.compressed_allreduce_tree``: fp8-plane
    all-gather + error feedback) instead of the exact fp32 psum.

    The whole step runs under ``jax.shard_map`` over ``axis``: params/opt
    replicate, the batch shards its leading dim, each shard backprops its
    local microbatch, and the gradient crosses the wire as ``planes`` fp8
    payloads per element — ``planes + 4/n`` bytes/element vs 4 for fp32
    (measured from compiled HLO by ``launch/dryrun.py --dp-collectives``).
    What the last plane couldn't represent is carried per-shard in the
    train state (``state["ef"]``, leading axis = shard count) and folded
    into the next step — the standard error-feedback construction that
    keeps compressed SGD unbiased over time.  ``planes=0`` switches to the
    exact fp32 pmean (the wire-byte baseline; EF carries zeros).

    Requires ``REPRO_SHARD_PROFILE=dp`` so in-model sharding constraints
    no-op inside the manual (shard_map) context.

    ``init_dp_state(model, optimizer, rng, mesh, axis)`` builds the
    matching state; step signature matches ``make_train_step``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import compressed_allreduce_tree

    groups = model.quant_groups()
    n_shards = dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]

    def step(state, batch, bits_map):
        def local(params, opt, ef, batch, bits_map):
            ef = jax.tree.map(lambda e: e[0], ef)  # drop the shard axis

            def loss_fn(p):
                qp = quantize_params(p, bits_map, groups)
                return model.loss(qp, batch, remat=remat)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if planes:
                grads, ef = compressed_allreduce_tree(
                    grads, axis, residuals=ef, planes=planes,
                    axis_size=n_shards)
            else:  # exact fp32 baseline
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
            loss = jax.lax.pmean(loss, axis)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
            new_params, new_opt = optimizer.update(params, grads, opt)
            out = {"loss": loss, "grad_norm": global_norm(grads), **metrics}
            return (new_params, new_opt,
                    jax.tree.map(lambda e: e[None], ef), out)

        new_p, new_o, new_ef, out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(axis), P()),
            check_vma=False,
        )(state["params"], state["opt"], state["ef"], batch, bits_map)
        return {"params": new_p, "opt": new_o, "ef": new_ef}, out

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def init_dp_state(model, optimizer: AdamW, rng, mesh, axis: str = "data"):
    """Train state for :func:`make_dp_train_step`: params + opt moments
    plus the per-shard error-feedback residual tree (leading shard axis)."""
    params = model.init(rng)
    n = dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]
    ef = jax.tree.map(
        lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params)
    return {"params": params, "opt": optimizer.init(params), "ef": ef}


def make_eval_step(model):
    """Eval NLL of a *quantized* model — the ReLeQ accuracy-proxy input."""
    groups = model.quant_groups()

    def step(params, batch, bits_map):
        qp = quantize_params(params, bits_map, groups)
        _, metrics = model.loss(qp, batch)
        return metrics["nll"]

    return jax.jit(step)


def make_fp_eval_step(model):
    def step(params, batch):
        _, metrics = model.loss(params, batch)
        return metrics["nll"]

    return jax.jit(step)
