"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Serves the cell's configuration (``configs/``) under its traffic mix
(``traffic/``) on the chip JAX finds, measures a window of ``--seconds``,
checks what the window served against the plain float32 reference, and
prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of a
few seconds of the window), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared beside its limit, which the
last lines of standard error repeat.  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(ROOT / "src"))
    from chip import harness

    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in order if k in result}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is out: leave without the interpreter's teardown, which
    # can outlast the run's time limit while the runtime frees the chip
    os._exit(code)
