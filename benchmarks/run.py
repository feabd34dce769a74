# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: ``python -m benchmarks.run [--full] [--only NAME]``.

quick (default): the RL-driven artifacts run on the CPU-budget networks
with shortened searches; --full widens to the 7-network Table-2 sweep.
The roofline rows come from the dry-run records if present.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import paper
    from benchmarks import roofline as rf

    nets = paper.FULL_NETS if args.full else paper.QUICK_NETS
    benches = [
        ("table2", lambda: paper.table2_bitwidths(nets)),
        ("fig5", paper.fig5_policy_evolution),
        ("fig6", paper.fig6_pareto),
        ("fig7", paper.fig7_learning_curves),
        ("fig8", lambda: paper.fig8_tvm_speedup(nets)),
        ("fig9", lambda: paper.fig9_stripes(nets)),
        ("table4", paper.table4_admm),
        ("table5", paper.table5_ppo_clip),
        ("fig10", paper.fig10_reward_ablation),
        ("lstm_ablation", paper.lstm_ablation),
        ("qmm", paper.qmm_microbench),
    ]
    print("name,us_per_call,derived")
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
        except Exception as e:  # keep the harness going; surface the failure
            print(f"{name},0.0,ERROR:{type(e).__name__}:{str(e)[:120]}",
                  flush=True)
            continue
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)

    # roofline rows (from dry-run artifacts, if the sweep has run)
    try:
        for r in rf.rows(rf.load_records()):
            if r["status"] == "skipped":
                print(f"roofline/{r['cell'].replace(' ', '')},0.0,skipped")
            else:
                print(f"roofline/{r['cell'].replace(' ', '')},0.0,"
                      f"bottleneck={r['bottleneck']};frac={r['roofline_frac']:.3f};"
                      f"peakGB={r['peak_gb']:.1f}")
    except Exception:
        pass


if __name__ == "__main__":
    main()
