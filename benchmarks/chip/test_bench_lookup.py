"""Everything ``BENCHMARK.json`` names is found by its name, the file keeps
to the benchmark's contract, and the command refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

from chip import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_loads():
    for c in BENCH["configs"]:
        cfg = harness.load_config(BENCH, c["name"], ROOT)
        assert cfg["name"] == c["name"] and set(c["reduced"]) <= set(cfg["reduced"])
    for w in BENCH["workloads"]:
        assert traffic.load(w["traffic"])["name"] == w["traffic"]
        limits = harness.load_limits(w["name"])
        assert limits and all(k in ("mean_gap", "widest_gap", "off_best_share")
                              and v["limit"] > 0 for k, v in limits.items())
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    for m in BENCH["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_contract_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert harness.cell_metrics(BENCH, w["name"], "per_layer")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "glm4-chat",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_refuses_without_a_tpu():
    r = _run(ROOT, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU found" in r.stderr


def test_command_refuses_with_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
