"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: ``make`` returns them
in the reference's flat layout (per-layer leaves stacked over layers),
``program_params`` hands the same arrays to the program in its own
pytree, and the reference makes them again from the seed once the
program is gone, so it takes nothing the program made.

Scales: linear maps draw N(0, 1/fan_in), so each layer keeps the
residual's scale; norm gains draw 1 + 0.1 N(0, 1), so a gain the program
dropped shows in its logits; the embedding and the head draw
N(0, 6.25/d), so the logits spread with a standard deviation of about
2.5, as a trained model's do.
"""
from __future__ import annotations

import functools

import numpy as np


def dims(model: dict) -> dict:
    heads = model["num_attention_heads"]
    return {
        "layers": model["num_hidden_layers"], "d": model["hidden_size"],
        "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": model.get("head_dim") or model["hidden_size"] // heads,
        "ff": model["intermediate_size"], "vocab": model["vocab_size"],
        "tied": bool(model["tie_word_embeddings"]),
        "qk_norm": bool(model["equations"]["qk_norm"]),
    }


def shapes(dm: dict) -> dict[str, tuple]:
    """Leaf name -> shape, in the reference's layout."""
    n, d, hd, ff = dm["layers"], dm["d"], dm["head_dim"], dm["ff"]
    q, kv = dm["heads"] * hd, dm["kv_heads"] * hd
    out = {"embed": (dm["vocab"], d), "final_norm": (d,),
           "ln1": (n, d), "ln2": (n, d),
           "wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
           "wo": (n, q, d),
           "w_gate": (n, d, ff), "w_up": (n, d, ff), "w_down": (n, ff, d)}
    if dm["qk_norm"]:
        out |= {"q_norm": (n, hd), "k_norm": (n, hd)}
    if not dm["tied"]:
        out["lm_head"] = (d, dm["vocab"])
    return out


def _scale(name: str, shape: tuple, d: int) -> tuple[float, float]:
    """(mean, std) of a leaf."""
    if name in ("embed", "lm_head"):
        return 0.0, 2.5 / d ** 0.5
    if name.endswith("norm") or name in ("ln1", "ln2"):
        return 1.0, 0.1
    return 0.0, shape[-2] ** -0.5          # N(0, 1/fan_in)


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


@functools.lru_cache(maxsize=None)
def _maker(dims_items: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    dm = dict(dims_items)
    leaves = shapes(dm)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = {}
        for k, (name, shape) in zip(keys, sorted(leaves.items())):
            mean, std = _scale(name, shape, dm["d"])
            out[name] = (mean + std * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        return out

    return jax.jit(make)


def make(model: dict, seed: int, dtype: str = "float32") -> dict:
    """All weights of ``model`` from ``seed``, on the default device."""
    return _maker(tuple(sorted(dims(model).items())), dtype)(seed_key(seed))


def program_params(w: dict, dm: dict) -> dict:
    """The same arrays in the program's pytree (``models/lm.init_params``
    layout: one stacked block group, no tail)."""
    attn = {k: {"w": w[k]} for k in ("wq", "wk", "wv", "wo")}
    if dm["qk_norm"]:
        attn |= {"q_norm": {"g": w["q_norm"]}, "k_norm": {"g": w["k_norm"]}}
    block = {"ln1": {"g": w["ln1"]}, "attn": attn, "ln2": {"g": w["ln2"]},
             "ffn": {k: {"w": w[k]} for k in ("w_gate", "w_up", "w_down")}}
    params = {"embed": {"table": w["embed"]},
              "final_norm": {"g": w["final_norm"]},
              "blocks": {"pos0": block}, "tail": []}
    if not dm["tied"]:
        params["lm_head"] = {"w": w["lm_head"]}
    return params
