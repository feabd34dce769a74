import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake devices only; never open a chip

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. constructs ShapeDtypeStruct inputs (launch/specs.py — no allocation),
  3. ``jit(step).lower(...).compile()`` with the dist/sharding.py specs,
  4. prints ``memory_analysis()`` (proves fit) and ``cost_analysis()``,
  5. parses the optimized HLO for collective bytes,
  6. writes the roofline record to benchmarks/results/dryrun/.

Run one cell:   python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh pod
Run everything: python -m repro.launch.dryrun --all        (spawns subprocesses)

The 512 fake CPU devices exist ONLY in this process — never set the
XLA_FLAGS override globally.
"""
import argparse
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _log(msg):
    print(msg, flush=True)


DEFAULT_PROFILE = {"train": "fsdp", "prefill": "tp", "decode": "tp"}


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, serve_bits: int = 4,
             remat: str = "full", out_dir: str | None = None,
             seq_shard: bool | None = None, profile: str | None = None,
             tag: str = "") -> dict:
    from repro.configs import SHAPES, cell_is_runnable, get_config
    from repro.dist import sharding as shd
    from repro.launch import specs as S
    from repro.launch.hlo_analysis import analyze_hlo, roofline_from_costs
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.quant.qat import bits_assignment, policy_for
    from repro.train.train_step import make_eval_step  # noqa: F401 (import check)

    shape = SHAPES[shape_name]
    # per-kind default (train: fsdp — the layout that fits every arch in
    # 16 GB; serve cells: tp); shard_profile() reads the env var lazily
    profile = profile or DEFAULT_PROFILE[shape.kind]
    os.environ["REPRO_SHARD_PROFILE"] = profile
    ok, reason = cell_is_runnable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    n_params = S.count_params(model)
    n_active = S.active_params(cfg, model)
    if seq_shard is None:
        seq_shard = shape.seq_len >= 32_768

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = AdamW(lr=3e-4, weight_decay=0.1,
                        moments="int8" if n_params > 60e9 else "fp32")
            groups = model.quant_groups(seq_len=shape.seq_len)
            policy = policy_for(model, default_bits=8)
            bits_map = {k: jnp.asarray(v)
                        for k, v in bits_assignment(groups, policy).items()}

            def step(state, batch, bm):
                from repro.quant.qat import quantize_params

                def loss_fn(p):
                    qp = quantize_params(p, bm, groups)
                    return model.loss(qp, batch, remat=remat)

                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"])
                new_p, new_o = opt.update(state["params"], grads, state["opt"])
                return {"params": new_p, "opt": new_o}, loss

            pstruct = S.params_struct(model)
            ostruct = jax.eval_shape(opt.init, pstruct)
            state_struct = {"params": pstruct, "opt": ostruct}
            batch = S.batch_struct(cfg, shape, train=True)
            st_specs = shd.state_specs(state_struct, mesh)
            in_sh = (shd.to_named(st_specs, mesh),
                     shd.to_named(shd.batch_specs(batch, mesh, seq_shard=False), mesh),
                     None)
            lowered = jax.jit(step, in_shardings=in_sh,
                              donate_argnums=(0,)).lower(
                state_struct, batch, bits_map)
            model_flops = 6.0 * n_active * shape.global_batch * shape.seq_len

        elif shape.kind == "prefill":
            def step(params, batch):
                logits, _ = model.forward(
                    params, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
                    positions=batch.get("positions"), remat="full")
                return logits.astype(jnp.bfloat16)

            pstruct = S.params_struct(model)
            batch = S.batch_struct(cfg, shape, train=False)
            in_sh = (shd.to_named(shd.param_specs(pstruct, mesh), mesh),
                     shd.to_named(shd.batch_specs(batch, mesh, seq_shard=seq_shard), mesh))
            lowered = jax.jit(step, in_shardings=in_sh).lower(pstruct, batch)
            model_flops = 2.0 * n_active * shape.global_batch * shape.seq_len

        else:  # decode
            policy = policy_for(model, default_bits=serve_bits)
            sparams, cache, tokens = S.decode_structs(model, shape, policy)

            def step(sp, c, t):
                logits, c2 = model.decode_step(sp, c, t)
                return logits.astype(jnp.bfloat16), c2

            cache_sh = shd.to_named(shd.cache_specs(cache, mesh), mesh)
            in_sh = (shd.to_named(shd.param_specs(sparams, mesh), mesh),
                     cache_sh,
                     shd.to_named(shd.batch_specs(tokens, mesh), mesh))
            # out cache sharding pinned to the in sharding so donation aliases
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=(None, cache_sh),
                              donate_argnums=(1,)).lower(sparams, cache, tokens)
            model_flops = 2.0 * n_active * shape.global_batch * serve_bits / 8.0

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()  # XLA:CPU: while bodies counted ONCE
    costs = analyze_hlo(compiled.as_text())  # loop-corrected (see hlo_analysis)
    rl = roofline_from_costs(costs, chips=chips, model_flops=model_flops)

    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)  # donated in/out overlap
    mem_d = {
        "argument_bytes": arg_b, "output_bytes": out_b, "temp_bytes": tmp_b,
        "alias_bytes": alias_b,
        "peak_bytes": arg_b + tmp_b + max(out_b - alias_b, 0),
    }
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": chips,
        "status": "ok", "params": n_params, "active_params": n_active,
        "profile": profile, "remat": remat, "serve_bits": serve_bits,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_d, "roofline": rl.row(),
        "fits_16g": mem_d["peak_bytes"] < 16e9,
    }
    _log(f"[dryrun] {arch} × {shape_name} × {mesh_kind} ({profile}): "
         f"peak/device={mem_d['peak_bytes']/1e9:.2f} GB "
         f"flops/chip={rl.flops:.3e} bottleneck={rl.bottleneck} "
         f"roofline_frac={rl.roofline_fraction:.3f} "
         f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    _log(f"  memory_analysis: {mem_d}")
    _log(f"  terms: compute={rl.t_compute*1e3:.1f}ms memory={rl.t_memory*1e3:.1f}ms "
         f"collective={rl.t_collective*1e3:.1f}ms useful={rl.useful_ratio:.2f}")
    _log(f"  raw cost_analysis (uncorrected): flops={cost.get('flops'):.3e}")
    _log(f"  collectives: {costs.coll_summary()}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fn = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_kind}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def run_dp_collectives(arch: str = "glm4-9b", *, planes: int = 2,
                       devices: int = 8, seq_len: int = 64,
                       global_batch: int = 8,
                       out_dir: str | None = None) -> dict:
    """Wire-byte report for the compressed data-parallel gradient path.

    Compiles ``train_step.make_dp_train_step`` twice on a ``devices``-wide
    data mesh — fp8-plane compressed all-reduce (error feedback carried in
    the train state) vs the exact fp32 pmean — and reports the *measured*
    gradient-collective payload bytes from each optimized HLO
    (``hlo_analysis``: trip-count-corrected).  Smoke-sized model: the
    ratio is what matters and it is size-invariant (planes + 4/n vs 4
    bytes per gradient element).
    """
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import SyntheticLMData
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.quant.qat import bits_assignment, policy_for
    from repro.train.train_step import init_dp_state, make_dp_train_step

    prev_profile = os.environ.get("REPRO_SHARD_PROFILE")
    os.environ["REPRO_SHARD_PROFILE"] = "dp"
    try:
        mesh = jax.make_mesh((devices,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg)
        opt = AdamW(lr=1e-3)
        bm = {k: jnp.asarray(v) for k, v in bits_assignment(
            model.quant_groups(), policy_for(model, 8)).items()}
        n_grad = sum(l.size for l in jax.tree.leaves(model.init(
            jax.random.PRNGKey(0))))

        wire = {}
        with jax.set_mesh(mesh):
            state = init_dp_state(model, opt, jax.random.PRNGKey(0), mesh)
            data = SyntheticLMData(seed=0, global_batch=global_batch,
                                   seq_len=seq_len, vocab=cfg.vocab_size)
            batch = {k: jnp.asarray(v) for k, v in data.next().items()}
            # send-bytes per device from the measured per-kind payloads: a
            # ring all-reduce sends 2(n-1)/n x its payload, all-gather/
            # all-to-all send (n-1)/n x their (output) payload
            frac = (devices - 1) / devices
            send_mult = {"all-reduce": 2 * frac, "all-gather": frac,
                         "all-to-all": frac, "reduce-scatter": frac,
                         "collective-permute": 1.0}
            for name, p in (("compressed", planes), ("exact", 0)):
                step = make_dp_train_step(model, opt, mesh, planes=p,
                                          donate=False)
                compiled = jax.jit(step).lower(state, batch, bm).compile()
                costs = analyze_hlo(compiled.as_text())
                wire[name] = {
                    "payload_bytes": round(costs.coll_bytes),
                    "send_bytes": round(sum(
                        send_mult.get(k, 1.0) * v
                        for k, v in costs.coll_bytes_by_kind.items())),
                    "by_kind": {k: round(v) for k, v in
                                costs.coll_bytes_by_kind.items()},
                }
    finally:
        if prev_profile is None:
            os.environ.pop("REPRO_SHARD_PROFILE", None)
        else:
            os.environ["REPRO_SHARD_PROFILE"] = prev_profile
    red = (wire["exact"]["send_bytes"]
           / max(wire["compressed"]["send_bytes"], 1.0))
    rec = {
        "benchmark": "dp_collectives", "arch": cfg.name, "devices": devices,
        "planes": planes, "grad_elements": n_grad,
        "wire": wire, "send_reduction_x": round(red, 3),
        "analytic_send_bytes_per_elem": {
            "exact": 8.0 * frac,
            "compressed": 2.0 * planes * frac,
        },
    }
    _log(f"[dp-collectives] {cfg.name} x{devices}dev planes={planes}: "
         f"exact send={wire['exact']['send_bytes']/1e6:.1f}MB "
         f"compressed send={wire['compressed']['send_bytes']/1e6:.1f}MB "
         f"-> {red:.2f}x wire reduction")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"dp_collectives_{arch}.json"),
                  "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def run_all(meshes=("pod", "multipod"), out_dir=RESULTS_DIR, archs=None,
            shapes=None, timeout: int = 3600, profile: str | None = None):
    """Spawn one subprocess per cell (isolates the 512-device client and
    caps compile-memory growth).  profile=None picks the per-kind default
    (train cells: fsdp — the layout that fits every arch in 16 GB;
    serve cells: tp)."""
    from repro.configs import SHAPES, all_archs, cell_is_runnable

    archs = archs or all_archs()
    shapes = shapes or list(SHAPES)
    results = []
    for arch in archs:
        for shape in shapes:
            cell_profile = profile or DEFAULT_PROFILE[SHAPES[shape].kind]
            for mesh in meshes:
                ok, reason = cell_is_runnable(arch, shape)
                fn = os.path.join(out_dir, f"{arch}_{shape}_{mesh}.json")
                if not ok:
                    os.makedirs(out_dir, exist_ok=True)
                    with open(fn, "w") as f:
                        json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                                   "status": "skipped", "reason": reason}, f)
                    _log(f"[dryrun] SKIP {arch} × {shape}: {reason}")
                    continue
                if os.path.exists(fn):
                    _log(f"[dryrun] cached {arch} × {shape} × {mesh}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh,
                       "--profile", cell_profile, "--out", out_dir]
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout)
                _log(r.stdout.strip())
                if r.returncode != 0:
                    _log(f"[dryrun] FAIL {arch} × {shape} × {mesh} "
                         f"({time.time()-t0:.0f}s):\n{r.stderr[-3000:]}")
                    results.append({"arch": arch, "shape": shape, "mesh": mesh,
                                    "status": "fail"})
                else:
                    results.append({"arch": arch, "shape": shape, "mesh": mesh,
                                    "status": "ok"})
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--profile", default=None, choices=[None, "tp", "tp_sp", "fsdp"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--dp-collectives", action="store_true",
                    help="measure compressed-vs-exact DP gradient wire "
                         "bytes (PR-2 follow-up) instead of a cell compile")
    ap.add_argument("--planes", type=int, default=2)
    args = ap.parse_args()
    if args.dp_collectives:
        run_dp_collectives(args.arch or "glm4-9b", planes=args.planes,
                           out_dir=args.out or RESULTS_DIR)
        return
    if args.all:
        run_all(out_dir=args.out or RESULTS_DIR, profile=args.profile)
        return
    run_cell(args.arch, args.shape, args.mesh, serve_bits=args.bits,
             remat=args.remat, out_dir=args.out, profile=args.profile,
             tag=args.tag)


if __name__ == "__main__":
    main()
