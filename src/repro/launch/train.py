"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant QAT training loop (trainer.py) on whatever devices
exist — the production entry point a real fleet would invoke per host.  On
this CPU container it drives the reduced configs (--smoke, default); on a
TPU slice drop --smoke and point --mesh at the pod shape.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import SyntheticLMData
from repro.models import build_model
from repro.optim import AdamW, cosine_schedule
from repro.quant.policy import QuantPolicy
from repro.quant.qat import bits_assignment, policy_for
from repro.train.train_step import init_state, make_train_step
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--bits", type=int, default=8, help="uniform QAT bits")
    ap.add_argument("--policy-json", default=None,
                    help="QuantPolicy JSON from a ReLeQ search")
    ap.add_argument("--opt8", action="store_true", help="8-bit Adam moments")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="mesh shape over local devices, e.g. '2,4'; enables "
                         "sharded training + elastic checkpoint restore")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = jax.make_mesh(shape, ("data", "model")[:len(shape)],
                             axis_types=(jax.sharding.AxisType.Auto,) * len(shape))

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=20, total=args.steps),
                weight_decay=0.1, moments="int8" if args.opt8 else "fp32")
    groups = model.quant_groups(seq_len=args.seq_len)
    if args.policy_json:
        policy = QuantPolicy.from_file(args.policy_json)
    else:
        policy = policy_for(model, default_bits=args.bits)
    bits_map = {k: jnp.asarray(v)
                for k, v in bits_assignment(groups, policy).items()}

    data = SyntheticLMData(seed=args.seed, global_batch=args.global_batch,
                           seq_len=args.seq_len, vocab=cfg.vocab_size)
    state = init_state(model, opt, jax.random.PRNGKey(args.seed))
    step_fn = make_train_step(model, opt, remat=args.remat)
    trainer = Trainer(model=model, optimizer=opt, data=data, step_fn=step_fn,
                      bits_map=bits_map, ckpt_dir=args.ckpt_dir, mesh=mesh)
    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"training {args.arch} ({n/1e6:.1f}M params, QAT "
          f"avg {policy.average_bits():.1f} bits) for {args.steps} steps"
          + (f" on mesh {mesh.shape}" if mesh is not None else ""))
    if mesh is not None:
        from repro.dist import elastic

        with jax.set_mesh(mesh):
            trainer.run(elastic.place(state, mesh), args.steps)
    else:
        trainer.run(state, args.steps)


if __name__ == "__main__":
    main()
