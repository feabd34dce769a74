"""Model weights made from a run's seed, by the benchmark and not by the
program, so that the plain reference can make the very same values
without taking anything the program made.

Every matrix is drawn on its own key, ``fold_in(fold_in(key(seed), leaf),
layer)``, so the reference can regenerate one layer at a time while the
serving path gets all of them from one jitted call, in the bf16 it serves
them from.  Matrices are normal with std ``1/sqrt(fan_in)``, the embedding
normal with std 0.02, RMSNorm gains ``1 + 0.1·normal`` in float32.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> its id in the key schedule (never renumber: the id is part
# of the weights every seed stands for)
LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3, "ln1": 4, "ln2": 5,
            "wq": 6, "wk": 7, "wv": 8, "wo": 9, "wg": 10, "wu": 11, "wd": 12}
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("wg", "wu", "wd")


def seed_key(seed: int):
    """A threefry key from any whole number, 64-bit seeds included."""
    hi, lo = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(2)
    return jax.random.wrap_key_data(np.array([hi, lo], np.uint32))


def layer_shapes(cfg) -> dict[str, tuple[int, int]]:
    D, H, KV, hd, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return {"wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
            "wo": (H * hd, D), "wg": (D, F), "wu": (D, F), "wd": (F, D)}


def _matrix(key, name, layer, shape, std):
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _gain(key, name, layer, d):
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    return 1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)


def layer(cfg, key, l: int) -> dict:
    """Layer ``l``: its two RMSNorm gains (f32) and seven matrices (bf16)."""
    out = {n: _matrix(key, n, l, s, s[0] ** -0.5)
           for n, s in layer_shapes(cfg).items()}
    out["ln1"] = _gain(key, "ln1", l, cfg["d_model"])
    out["ln2"] = _gain(key, "ln2", l, cfg["d_model"])
    return out


def embed(cfg, key):
    return _matrix(key, "embed", 0, (cfg["vocab_size"], cfg["d_model"]), 0.02)


def lm_head(cfg, key):
    return _matrix(key, "lm_head", 0, (cfg["d_model"], cfg["vocab_size"]),
                   cfg["d_model"] ** -0.5)


def final_norm(cfg, key):
    return _gain(key, "final_norm", 0, cfg["d_model"])


def _program_tree(cfg, key):
    layers = [layer(cfg, key, l) for l in range(cfg["num_layers"])]
    stack = lambda n: jnp.stack([lw[n] for lw in layers])
    return {
        "embed": embed(cfg, key),
        "blocks": [{"ln1": stack("ln1"), "ln2": stack("ln2"),
                    "attn": {n: stack(n) for n in ATTN},
                    "mlp": {n: stack(n) for n in MLP}}],
        "final_norm": final_norm(cfg, key),
        "lm_head": lm_head(cfg, key),
    }


@functools.lru_cache(maxsize=None)
def _program_tree_jit(cfg_json: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda key: _program_tree(cfg, key))


def program_params(cfg, seed: int, model):
    """The whole parameter tree in the layout of the program's
    ``TransformerLM.init``, made on the device in one jitted call, after a
    check that ``model`` lays its parameters out so."""
    make = _program_tree_jit(json.dumps(cfg, sort_keys=True))
    key = seed_key(seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(make, key)
    if (jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))):
        raise ValueError("the program's parameter layout is not the one the "
                         "benchmark makes weights for")
    return make(key)
