"""Dense grouped-query attention with a SwiGLU MLP in every layer
(Qwen3, Mistral): RMSNorm, rotate-half RoPE, optional per-head q/k
RMSNorm, a tied or separate output head.  The default family."""
from __future__ import annotations

from families import BYTES

REFERENCE = "model"


def arch_config(model: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file in the
    Hugging Face ``config.json`` form (plus its ``equations`` block)."""
    from repro.configs.base import ArchConfig

    eq = model["equations"]
    if eq["norm"] != "rmsnorm" or eq["ffn"] != "swiglu" \
            or model["hidden_act"] != "silu":
        raise ValueError(f"{name}: the bench drives dense RMSNorm/SwiGLU "
                         f"models only, got {eq}")
    heads = model["num_attention_heads"]
    return ArchConfig(
        name=name, family="dense",
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=heads, n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        head_dim=model.get("head_dim") or model["hidden_size"] // heads,
        qk_norm=bool(eq["qk_norm"]),
        qkv_bias=bool(model.get("attention_bias", False)),
        attn_window=model.get("sliding_window"),
        rope_theta=float(model["rope_theta"]),
        act="silu", ffn_gated=True, norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        compute_dtype=model["torch_dtype"],
        source=f"bench/configs/{name}.json")


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


# -- weights ----------------------------------------------------------------

def leaves(dm: dict) -> dict[str, tuple]:
    """Leaf name -> shape, in the reference's layout (per-layer leaves
    stacked over layers)."""
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


def scale(name: str, shape: tuple, dm: dict) -> tuple[float, float]:
    """(mean, std) of a leaf: linear maps N(0, 1/fan_in), so each layer
    keeps the residual's scale; norm gains 1 + 0.1 N(0, 1), so a gain the
    program dropped shows in its logits; the embedding and the head
    N(0, 6.25/d), so the logits spread with a standard deviation of
    about 2.5, as a trained model's do."""
    if name in ("embed", "lm_head"):
        return 0.0, 2.5 / dm["d"] ** 0.5
    if name.endswith("norm") or name in ("ln1", "ln2"):
        return 1.0, 0.1
    return 0.0, shape[-2] ** -0.5          # N(0, 1/fan_in)


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


# -- work counts (the rules in families/__init__.py) -------------------------

def matmul_params(dm: dict) -> int:
    """Weights a token multiplies by: every layer's projections and FFN,
    and the output head (the tied embedding serves as the head)."""
    d, hd, ff = dm["d"], dm["head_dim"], dm["ff"]
    attn = d * hd * (2 * dm["heads"] + 2 * dm["kv_heads"])
    return dm["layers"] * (attn + 3 * d * ff) + d * dm["vocab"]


def weight_bytes(dm: dict, dtype: str) -> int:
    """Bytes of weights one step reads: the matmul weights and the norm
    gains, at ``dtype``."""
    norms = dm["layers"] * (2 * dm["d"] + (2 * dm["head_dim"]
                                           if dm["qk_norm"] else 0)) + dm["d"]
    return (matmul_params(dm) + norms) * BYTES[dtype]


def kv_bytes_per_token(dm: dict, dtype: str) -> int:
    return 2 * dm["layers"] * dm["kv_heads"] * dm["head_dim"] * BYTES[dtype]


def attn_flops(dm: dict, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` keys, all
    layers."""
    return 4 * dm["layers"] * dm["heads"] * dm["head_dim"] * kv_len


def prefill_flops(dm: dict, start: int, stop: int) -> int:
    """Prompt positions ``[start, stop)``, each attending itself and all
    before it (causal)."""
    n = stop - start
    kv_sum = (start + 1 + stop) * n // 2        # sum of (p + 1)
    return 2 * matmul_params(dm) * n + attn_flops(dm, 1) * kv_sum


def decode_work(dm: dict, dtype: str, iterations: int,
                kv_lens_sum: int, tokens: int) -> tuple[int, int]:
    """(flops, bytes) the decode loop needs for ``iterations`` loop
    iterations that produced ``tokens`` lane-tokens over live contexts
    summing to ``kv_lens_sum``: weights once per iteration, each lane's
    live KV (its only per-lane state) once per token."""
    flops = 2 * matmul_params(dm) * tokens + attn_flops(dm, 1) * kv_lens_sum
    byts = weight_bytes(dm, dtype) * iterations \
        + kv_bytes_per_token(dm, dtype) * kv_lens_sum
    return flops, byts
