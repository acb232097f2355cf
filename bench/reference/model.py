"""Plain float32 reference of the configurations' equations, written from
the published descriptions (Qwen3 and Mistral ``config.json``; RMSNorm,
rotate-half RoPE, grouped-query causal attention, optional per-head
query/key RMSNorm before RoPE, an optional sliding window, SwiGLU, tied
or separate output head).

It imports nothing of the program.  Weights come in the benchmark's flat
layout (``harness/weights.py``); every matrix product runs at
``Precision.HIGHEST``, so a TPU computes it in full float32.  One
sequence at a time, layer by layer under ``lax.scan``, the head in
blocks of rows: it fits one chip at the configurations' full sizes.

``fp8=True`` is the control: the same equations with every matrix
product's inputs rounded to float8 e4m3 (scaled per row of activations
and per output column of weights, as fp8 inference does), the nearest
precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 256
_E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8):
    """a (..., k) @ w (k, n) in float32."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, -2)
    return jnp.matmul(a, w, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, H, D), rotate-half convention, position = row index."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, lw, c, fp8):
    s = h.shape[0]
    hd, nh, nkv = c["head_dim"], c["heads"], c["kv_heads"]
    x = _rmsnorm(h, lw["ln1"], c["eps"])
    q = _mm(x, lw["wq"], fp8).reshape(s, nh, hd)
    k = _mm(x, lw["wk"], fp8).reshape(s, nkv, hd)
    v = _mm(x, lw["wv"], fp8).reshape(s, nkv, hd)
    if c["qk_norm"]:
        q = _rmsnorm(q, lw["q_norm"], c["eps"])
        k = _rmsnorm(k, lw["k_norm"], c["eps"])
    q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    group = nh // nkv
    q = q.reshape(s, nkv, group, hd)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k, precision=HI) / hd ** 0.5
    dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    causal = dist >= 0
    if c["window"] is not None:
        causal &= dist < c["window"]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(s, nh * hd)
    h = h + _mm(o, lw["wo"], fp8)
    x = _rmsnorm(h, lw["ln2"], c["eps"])
    ff = jax.nn.silu(_mm(x, lw["w_gate"], fp8)) * _mm(x, lw["w_up"], fp8)
    return h + _mm(ff, lw["w_down"], fp8)


def _hidden(w, tokens, c, fp8):
    """Final-normed hidden states (S, d) of one sequence."""
    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "final_norm", "lm_head")}
    h = w["embed"][tokens].astype(jnp.float32)

    def body(h, lw):
        return _layer(h, lw, c, fp8), None

    h, _ = jax.lax.scan(body, h, per_layer)
    return _rmsnorm(h, w["final_norm"], c["eps"])


def _head(w, c):
    return w["embed"].T if c["tied"] else w["lm_head"]


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def gaps(w, tokens, targets, *, cfg, control=False):
    """For each position ``i`` of ``tokens`` (S,): the reference's best
    logit less its logit for ``targets[i]``.  With ``control``, also the
    reference's best less its logit for the token the fp8 forward puts
    first.  ``cfg`` is a hashable tuple of ``config`` items."""
    c = dict(cfg)
    head = _head(w, c)
    ref_h = _hidden(w, tokens, c, False)
    ctl_h = _hidden(w, tokens, c, True) if control else ref_h
    s = tokens.shape[0]
    nb = s // HEAD_BLOCK

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(ref_h, i * HEAD_BLOCK, HEAD_BLOCK)
        tgt = jax.lax.dynamic_slice_in_dim(targets, i * HEAD_BLOCK,
                                           HEAD_BLOCK)
        ref = _mm(rows, head, False)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, tgt[:, None], -1)[:, 0]
        if not control:
            return best - got, jnp.zeros_like(best)
        crow = jax.lax.dynamic_slice_in_dim(ctl_h, i * HEAD_BLOCK,
                                            HEAD_BLOCK)
        top = jnp.argmax(_mm(crow, head, True), -1)
        alt = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return best - got, best - alt

    prog, ctl = jax.lax.map(block, jnp.arange(nb))
    return prog.reshape(s), ctl.reshape(s)


def config(model: dict) -> tuple:
    """The hashable equation constants of a configuration file."""
    heads = model["num_attention_heads"]
    return tuple(sorted({
        "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": model.get("head_dim") or model["hidden_size"] // heads,
        "eps": float(model["rms_norm_eps"]),
        "theta": float(model["rope_theta"]),
        "qk_norm": bool(model["equations"]["qk_norm"]),
        "tied": bool(model["tie_word_embeddings"]),
        "window": model.get("sliding_window"),
    }.items()))
