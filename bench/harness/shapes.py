"""Operations and bytes a step needs, from the model's shapes alone.

What the algorithm needs, not what an implementation happens to move:
weights once per step at the stated compute dtype (the embedding's rows
are a lookup and not counted; the output head is), the live KV of each
lane (its ``kv_len`` rows, not the pool's ``max_len``), and two
operations per weight per token plus the attention over the live context.
A later kernel cannot make these counts stale.
"""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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
    return (matmul_params(dm) + norms) * _BYTES[dtype]


def kv_bytes_per_token(dm: dict, dtype: str) -> int:
    return 2 * dm["layers"] * dm["kv_heads"] * dm["head_dim"] * _BYTES[dtype]


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
    summing to ``kv_lens_sum``."""
    flops = 2 * matmul_params(dm) * tokens + attn_flops(dm, 1) * kv_lens_sum
    byts = weight_bytes(dm, dtype) * iterations \
        + kv_bytes_per_token(dm, dtype) * kv_lens_sum
    return flops, byts
