"""A cell cut to a size the CPU runs in seconds, for the harness's tests:
glm4-9b's configuration file and mixes with every width and length
shrunk.  The structure, the engine settings and the policy are the
cell's own."""
import copy
import json
from pathlib import Path

from . import traffic

HERE = Path(__file__).resolve().parent


def config(name: str = "glm4-9b-w4kv8") -> dict:
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    cfg.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               head_dim=16, d_ff=96, vocab_size=251)
    cfg["engine"] = dict(cfg["engine"], num_slots=4, max_len=256,
                         prefill_chunk=16, num_blocks=129)
    return cfg


def mix(name: str) -> dict:
    spec = copy.deepcopy(traffic.load(name))
    spec.update(clients=4, batch=64)
    for key, lo, hi, median in (("prompt", 8, 64, 20), ("output", 4, 16, 8)):
        spec[key].update(min=lo, max=hi)
        if "median" in spec[key]:
            spec[key]["median"] = median
    if spec["documents"]:
        spec["documents"].update(count=4, tokens=64)
    return spec
