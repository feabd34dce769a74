# Lockstep vs async ReLeQ search throughput at equal final reward.
"""Autotune benchmark: ``python -m benchmarks.autotune_bench``.

Runs the SAME LeNet-scale search (4 quantizable groups, the layer-2-is-
sensitive oracle from tests/test_core_rl.py) two ways:

- **lockstep**: ``core.search.ReLeQSearch`` — every PPO update waits for
  the episode's evaluation to return (the pre-autotune architecture);
- **async**: ``repro.autotune.AutotuneService`` — a worker pool
  evaluates candidates concurrently while the actor keeps rolling out
  and the learner consumes completions out of order.

The evaluator charges ``--eval-ms`` of wall time per *fresh* candidate
(``time.sleep`` stands in for the short QAT retrain, which runs on the
accelerator and releases the GIL — exactly the latency the async
service hides; memoized repeats are free in both modes, through the same
shared ``EvalCache``).

Acceptance contract (asserted, recorded in ``BENCH_autotune.json``):
the async service must reach the lockstep run's best reward — extra
episode chunks are granted up to ``--max-extra`` if it lags — at
**strictly higher** evaluation throughput (episodes/s).  Also recorded:
evaluations-to-best-reward for both modes and the memo hit-rates.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "results",
                           "BENCH_autotune.json")


def make_env_components(eval_ms: float):
    """LeNet-scale synthetic env + a retrain-latency-charging oracle."""
    from repro.core.env import QuantEnv
    from repro.core.evalcache import EvalCache
    from repro.models.model import QuantGroup

    groups = [QuantGroup(f"L{i}", ("blocks",), i, (64, 64), 64 * 64,
                         64 * 64 * 50) for i in range(4)]
    sens = [2.0, 2.0, 6.0, 2.5]

    def oracle(bits):
        time.sleep(eval_ms / 1e3)  # the "short retrain" wall time
        acc = 1.0
        for i, g in enumerate(groups):
            acc *= 1.0 / (1.0 + np.exp(-(bits[g.name] - sens[i]) * 2.2))
        return float(acc)

    def make_factory():
        cache = EvalCache()

        def evaluate(bits):
            value, _ = cache.get_or_compute(bits, lambda: oracle(bits))
            return value

        def factory(i):
            return QuantEnv(groups=groups, evaluate=evaluate,
                            weight_std={g.name: 0.5 for g in groups},
                            eval_mode="episode_end")

        factory.eval_cache = cache
        factory.evaluate = evaluate
        factory.compute = oracle
        return factory

    return groups, make_factory


def run_lockstep(make_factory, episodes: int, seed: int) -> dict:
    from repro.core.search import ReLeQSearch

    search = ReLeQSearch(make_factory(), num_envs=1, seed=seed)
    t0 = time.perf_counter()
    res = search.run(episodes=episodes)
    wall = time.perf_counter() - t0
    # first reach of the best reward (same definition the async side uses)
    best_at = 1 + min(i for i, e in enumerate(res.episodes)
                      if e["reward"] >= res.best_reward)
    return {
        "mode": "lockstep", "episodes": episodes, "wall_s": round(wall, 3),
        "episodes_per_s": round(episodes / wall, 3),
        "best_reward": res.best_reward,
        "evals_to_best": best_at,
        "evaluations": res.cache_stats["misses"],
        "cache_hit_rate": round(res.cache_stats["hit_rate"], 3),
    }


def run_async(make_factory, episodes: int, seed: int, workers: int,
              target_reward: float, max_extra: int) -> dict:
    from repro.autotune import AutotuneService, ServiceConfig

    service = AutotuneService(
        make_factory(), accuracy_thread_safe=True,  # sleep-bound oracle
        config=ServiceConfig(num_workers=workers, max_inflight=2 * workers,
                             batch_episodes=workers, seed=seed))
    total_eps, wall, evals_to_best = 0, 0.0, 0
    best = -np.inf
    budget = episodes + max_extra
    chunk = episodes
    # equal-final-reward contract: chase the lockstep best, granting
    # extra episode chunks (bounded) if the async run hasn't matched yet
    while True:
        res = service.run(episodes=chunk)
        wall += res.service_stats["wall_s"]
        if res.best_reward > best:
            best = res.best_reward
            evals_to_best = total_eps + res.service_stats["evals_to_best"]
        total_eps += chunk
        if best >= target_reward - 1e-6 or total_eps >= budget:
            break
        chunk = min(episodes, budget - total_eps)
    stats = res.service_stats
    service.shutdown()
    return {
        "mode": "async", "workers": workers, "episodes": total_eps,
        "wall_s": round(wall, 3),
        "episodes_per_s": round(total_eps / wall, 3),
        "best_reward": best,
        "evals_to_best": evals_to_best,
        "evaluations": res.cache_stats["misses"],
        "cache_hit_rate": round(res.cache_stats["hit_rate"], 3),
        "ppo_updates": stats["updates"],
        "stale_dropped": stats["stale_dropped"],
        "archive_size": stats["archive_size"],
    }


def bench(args) -> dict:
    _, make_factory = make_env_components(args.eval_ms)
    lock = run_lockstep(make_factory, args.episodes, args.seed)
    print(f"lockstep: {lock['episodes_per_s']:.2f} eps/s, "
          f"best={lock['best_reward']:.4f} after {lock['evals_to_best']} "
          f"episodes ({lock['evaluations']} retrains)", flush=True)
    a = run_async(make_factory, args.episodes, args.seed, args.workers,
                  target_reward=lock["best_reward"],
                  max_extra=args.max_extra)
    print(f"async x{args.workers}: {a['episodes_per_s']:.2f} eps/s, "
          f"best={a['best_reward']:.4f} after {a['evals_to_best']} "
          f"episodes ({a['evaluations']} retrains, "
          f"{a['stale_dropped']} stale dropped)", flush=True)

    speedup = a["episodes_per_s"] / max(lock["episodes_per_s"], 1e-9)
    from repro.obs import run_provenance

    rec = {
        "benchmark": "autotune_bench",
        "provenance": run_provenance(),
        "env": {"groups": 4, "bitset": 7, "eval_ms": args.eval_ms,
                "episodes": args.episodes, "seed": args.seed},
        "lockstep": lock, "async": a,
        "throughput_ratio": round(speedup, 3),
        "reached_lockstep_reward": bool(
            a["best_reward"] >= lock["best_reward"] - 1e-6),
    }
    # acceptance: equal final reward at strictly higher throughput
    assert rec["reached_lockstep_reward"], rec
    assert speedup > 1.0, rec
    print(f"async/lockstep throughput: {speedup:.2f}x at equal final "
          f"reward", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=40)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--eval-ms", type=float, default=25.0,
                    help="simulated short-retrain wall time per fresh "
                         "candidate (device-bound, GIL-free)")
    ap.add_argument("--max-extra", type=int, default=200,
                    help="extra async episodes allowed to match the "
                         "lockstep best reward")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing (fewer episodes)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="JSON record path ('' disables)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    if args.smoke:
        args.episodes = min(args.episodes, 30)

    rec = bench(args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
