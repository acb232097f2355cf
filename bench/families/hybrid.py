"""Mamba-2 layers with grouped-query attention layers among them, a
SwiGLU MLP after every mixer (Granite 4.0-H, model type
``granitemoehybrid``): RMSNorm; the Mamba-2 mixer (in_proj to z, x, B,
C, Δ; a depthwise causal conv with bias over x, B, C; the SSD recurrence
with one group; D skip; the gated RMSNorm; out_proj); attention without
a position embedding at the configured softmax scale; muP multipliers on
the embedding, on every residual branch and on the logits; a tied head.

Leaves are laid out by position in the layer pattern: ``l<i>.<leaf>``
stacked over the pattern's repeats, which is the program's own stacking
(``models/lm.init_params``), so no weight is copied to hand it over.
"""
from __future__ import annotations

import math

from families import BYTES

REFERENCE = "hybrid"
SSM_STATE_BYTES = 4          # the recurrent state is held in float32
KINDS = {"mamba": "mamba2", "attention": "attn"}


def _period(types: list) -> int:
    """Length of the shortest pattern that ``types`` repeats whole."""
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))


def _check(model: dict, name: str) -> None:
    eq = model["equations"]
    want = {"hidden_act": "silu", "normalization_function": "rmsnorm",
            "position_embedding_type": "nope", "attention_bias": False,
            "mamba_proj_bias": False, "mamba_conv_bias": True,
            "mamba_n_groups": 1, "num_local_experts": 0,
            "tie_word_embeddings": True}
    got = {k: model[k] for k in want}
    if got != want or eq["norm"] != "rmsnorm" or eq["ffn"] != "swiglu" \
            or model["intermediate_size"] != model["shared_intermediate_size"]:
        raise ValueError(f"{name}: the hybrid family drives {want} with one "
                         f"SwiGLU width, got {got}, {eq}")
    if model["mamba_n_heads"] * model["mamba_d_head"] \
            != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError(f"{name}: mamba heads x head size must be the "
                         f"expanded width")


def arch_config(model: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file in the
    Hugging Face ``config.json`` form (plus its ``equations`` block)."""
    from repro.configs.base import ArchConfig

    _check(model, name)
    dm = dims(model)
    return ArchConfig(
        name=name, family="hybrid",
        n_layers=dm["layers"], d_model=dm["d"], n_heads=dm["heads"],
        n_kv_heads=dm["kv_heads"], d_ff=dm["ff"], vocab_size=dm["vocab"],
        head_dim=dm["head_dim"], rope=False,
        attn_scale=float(model["attention_multiplier"]),
        embed_multiplier=dm["embedding_multiplier"],
        residual_multiplier=dm["residual_multiplier"],
        logits_divisor=dm["logits_scaling"],
        block_pattern=tuple(KINDS[t] for t in dm["pattern"]),
        ssm_state=dm["ssm_state"], ssm_head_dim=dm["ssm_head_dim"],
        ssm_expand=int(model["mamba_expand"]), conv_width=dm["conv"],
        mamba_ffn=True, act="silu", ffn_gated=True,
        norm_eps=float(model["rms_norm_eps"]), tie_embeddings=True,
        param_dtype=model["torch_dtype"], compute_dtype=model["torch_dtype"],
        source=f"bench/configs/{name}.json")


def dims(model: dict) -> dict:
    types = list(model["layer_types"])
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    period = _period(types)
    heads = model["num_attention_heads"]
    return {
        "layers": model["num_hidden_layers"], "d": model["hidden_size"],
        "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": model["hidden_size"] // heads,
        "ff": model["shared_intermediate_size"],
        "vocab": model["vocab_size"], "tied": True,
        "pattern": tuple(types[:period]),
        "ssm_heads": model["mamba_n_heads"],
        "ssm_head_dim": model["mamba_d_head"],
        "ssm_state": model["mamba_d_state"], "conv": model["mamba_d_conv"],
        "embedding_multiplier": float(model["embedding_multiplier"]),
        "residual_multiplier": float(model["residual_multiplier"]),
        "logits_scaling": float(model["logits_scaling"]),
    }


def _counts(dm: dict) -> tuple[int, int, int]:
    """(pattern repeats, Mamba layers, attention layers)."""
    groups = dm["layers"] // len(dm["pattern"])
    mamba = groups * dm["pattern"].count("mamba")
    return groups, mamba, dm["layers"] - mamba


def _inner(dm: dict) -> tuple[int, int]:
    """(d_inner, conv channels)."""
    di = dm["ssm_heads"] * dm["ssm_head_dim"]
    return di, di + 2 * dm["ssm_state"]


# -- weights ----------------------------------------------------------------

def leaves(dm: dict) -> dict[str, tuple]:
    """Leaf name -> shape: ``l<i>.<leaf>`` for pattern position ``i``,
    stacked over the pattern's repeats."""
    g, d, ff = _counts(dm)[0], dm["d"], dm["ff"]
    di, conv = _inner(dm)
    h, q = dm["ssm_heads"], dm["heads"] * dm["head_dim"]
    kv = dm["kv_heads"] * dm["head_dim"]
    mlp = {"ln2": (g, d), "w_gate": (g, d, ff), "w_up": (g, d, ff),
           "w_down": (g, ff, d)}
    mixer = {
        "mamba": {"in_proj": (g, d, 2 * di + 2 * dm["ssm_state"] + h),
                  "conv_w": (g, dm["conv"], conv), "conv_b": (g, conv),
                  "a_log": (g, h), "dt_bias": (g, h), "d_skip": (g, h),
                  "norm": (g, di), "out_proj": (g, di, d)},
        "attention": {"wq": (g, d, q), "wk": (g, d, kv), "wv": (g, d, kv),
                      "wo": (g, q, d)},
    }
    out = {"embed": (dm["vocab"], d), "final_norm": (d,)}
    for i, kind in enumerate(dm["pattern"]):
        for k, shape in ({"ln1": (g, d)} | mixer[kind] | mlp).items():
            out[f"l{i}.{k}"] = shape
    return out


def scale(name: str, shape: tuple, dm: dict) -> tuple[float, float]:
    """(mean, std) of a leaf, chosen so that the residual stream and the
    logits have the dense family's scales (``dense.scale``) through the
    multipliers: the embedding N(0, 6.25/d) over the embedding
    multiplier, so the stream starts as small as the dense family's;
    maps into the stream (wo, out_proj, w_down) N(0, 1/fan_in) over the
    residual multiplier, so each branch adds at unit scale; other linear
    maps N(0, 1/fan_in).  Since the head is the tied embedding, the final
    norm's gain carries the embedding multiplier times the logits'
    divisor, so the logits spread with a standard deviation of about 2.5
    after it; it and the other norm gains and D are 1 + 0.1 N(0, 1) of
    their mean, so a gain the program dropped shows.  The conv bias is
    0.1 N(0, 1).  A_log and dt_bias are drawn on a log scale with 95% of
    A = exp(A_log) in [1, 16] and of softplus(dt_bias) (about
    exp(dt_bias) there) in [0.001, 0.1], Mamba-2's initial ranges, so the
    state decays as a trained model's does and carries from chunk to
    chunk.  (With the embedding at the head's scale instead, 12 times it
    would outweigh the 80 branches, and the model would echo its input
    token.)"""
    leaf = name.split(".", 1)[-1]
    if leaf == "embed":
        return 0.0, 2.5 / (dm["embedding_multiplier"] * dm["d"] ** 0.5)
    if leaf == "final_norm":
        gain = dm["embedding_multiplier"] * dm["logits_scaling"]
        return gain, 0.1 * gain
    if leaf in ("ln1", "ln2", "norm", "d_skip"):
        return 1.0, 0.1
    if leaf == "a_log":
        return math.log(4.0), math.log(16.0) / 4
    if leaf == "dt_bias":
        return math.log(0.01), math.log(10.0) / 2
    if leaf == "conv_b":
        return 0.0, 0.1
    std = shape[-2] ** -0.5                 # N(0, 1/fan_in)
    if leaf in ("wo", "out_proj", "w_down"):
        std /= dm["residual_multiplier"]
    return 0.0, std


def program_params(w: dict, dm: dict) -> dict:
    """The same arrays in the program's pytree (``models/lm.init_params``
    layout: one stacked group per pattern position, no tail)."""
    def lin(k):
        return {"w": k}

    blocks = {}
    for i, kind in enumerate(dm["pattern"]):
        lw = {k.split(".", 1)[1]: v for k, v in w.items()
              if k.startswith(f"l{i}.")}
        block = {"ln1": {"g": lw["ln1"]}, "ln2": {"g": lw["ln2"]},
                 "ffn": {k: lin(lw[k]) for k in ("w_gate", "w_up", "w_down")}}
        if kind == "mamba":
            block["mamba"] = {
                "in_proj": lin(lw["in_proj"]), "conv_w": lw["conv_w"],
                "conv_b": lw["conv_b"], "a_log": lw["a_log"],
                "dt_bias": lw["dt_bias"], "d_skip": lw["d_skip"],
                "norm": {"g": lw["norm"]}, "out_proj": lin(lw["out_proj"])}
        else:
            block["attn"] = {k: lin(lw[k]) for k in ("wq", "wk", "wv", "wo")}
        blocks[f"pos{i}"] = block
    return {"embed": {"table": w["embed"]},
            "final_norm": {"g": w["final_norm"]},
            "blocks": blocks, "tail": []}


# -- work counts (the rules in families/__init__.py) -------------------------

def matmul_params(dm: dict) -> int:
    """Weights a token multiplies by: every layer's projections, convs
    and MLP, and the tied head."""
    _, n_m, n_a = _counts(dm)
    d, hd, ff = dm["d"], dm["head_dim"], dm["ff"]
    di, conv = _inner(dm)
    mamba = d * (2 * di + 2 * dm["ssm_state"] + dm["ssm_heads"]) \
        + dm["conv"] * conv + di * d
    attn = d * hd * (2 * dm["heads"] + 2 * dm["kv_heads"])
    return n_m * mamba + n_a * attn + dm["layers"] * 3 * d * ff \
        + d * dm["vocab"]


def weight_bytes(dm: dict, dtype: str) -> int:
    """Bytes of weights one step reads: the matmul and conv weights, and
    every bias, per-head constant and norm gain, at ``dtype``."""
    _, n_m, _ = _counts(dm)
    di, conv = _inner(dm)
    small = n_m * (conv + 3 * dm["ssm_heads"] + di) \
        + 2 * dm["layers"] * dm["d"] + dm["d"]
    return (matmul_params(dm) + small) * BYTES[dtype]


def kv_bytes_per_token(dm: dict, dtype: str) -> int:
    return 2 * _counts(dm)[2] * dm["kv_heads"] * dm["head_dim"] * BYTES[dtype]


def state_bytes_per_lane(dm: dict, dtype: str) -> int:
    """One lane's recurrent state over all Mamba layers: the SSM state
    (float32) and the conv's last inputs (at ``dtype``)."""
    _, n_m, _ = _counts(dm)
    ssm = dm["ssm_heads"] * dm["ssm_state"] * dm["ssm_head_dim"]
    return n_m * (ssm * SSM_STATE_BYTES
                  + (dm["conv"] - 1) * _inner(dm)[1] * BYTES[dtype])


def attn_flops(dm: dict, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` keys, all
    attention layers."""
    return 4 * _counts(dm)[2] * dm["heads"] * dm["head_dim"] * kv_len


def ssd_flops(dm: dict) -> int:
    """The recurrence of one token, all Mamba layers: per state element
    the decay, the input's multiply-add and the output's multiply-add."""
    _, n_m, _ = _counts(dm)
    return 5 * n_m * dm["ssm_heads"] * dm["ssm_state"] * dm["ssm_head_dim"]


def prefill_flops(dm: dict, start: int, stop: int) -> int:
    """Prompt positions ``[start, stop)``: each through every weight and
    the Mamba recurrence, and attending itself and all before it."""
    n = stop - start
    kv_sum = (start + 1 + stop) * n // 2        # sum of (p + 1)
    return (2 * matmul_params(dm) + ssd_flops(dm)) * n \
        + attn_flops(dm, 1) * kv_sum


def decode_work(dm: dict, dtype: str, iterations: int,
                kv_lens_sum: int, tokens: int) -> tuple[int, int]:
    """(flops, bytes) the decode loop needs for ``iterations`` loop
    iterations that produced ``tokens`` lane-tokens over live contexts
    summing to ``kv_lens_sum``: weights once per iteration, each lane's
    live KV once per token, and each lane's whole Mamba state read and
    written per token."""
    flops = (2 * matmul_params(dm) + ssd_flops(dm)) * tokens \
        + attn_flops(dm, 1) * kv_lens_sum
    byts = weight_bytes(dm, dtype) * iterations \
        + kv_bytes_per_token(dm, dtype) * kv_lens_sum \
        + 2 * state_bytes_per_lane(dm, dtype) * tokens
    return flops, byts
