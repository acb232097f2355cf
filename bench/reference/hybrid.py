"""Plain float32 reference of the hybrid family's equations, written from
the published description (Granite 4.0-H ``config.json``, model type
``granitemoehybrid``, and the Mamba-2 paper): the embedding times its
multiplier; per layer an RMSNorm, a mixer, its branch times the residual
multiplier, an RMSNorm, a SwiGLU MLP and its branch times the residual
multiplier; a final RMSNorm and the tied head divided by the logits'
divisor.  The mixer is either

- Mamba-2: in_proj to z, x, B, C, Δ; a depthwise causal conv of width
  ``mamba_d_conv`` with bias over (x, B, C) and SiLU; Δ = softplus(Δ +
  dt_bias); the SSD recurrence, token by token, ``S_t = exp(Δ_t A) S_{t-1}
  + Δ_t x_t ⊗ B_t`` and ``y_t = S_t C_t + D x_t`` with A = -exp(A_log) and
  one group; the gated RMSNorm ``rmsnorm(y · silu(z)) · g``; out_proj;
- or grouped-query causal attention with no position embedding, its
  scores scaled by ``attention_multiplier``.

It imports nothing of the program, and the recurrence is the sequential
scan, not the chunked form the program computes.  Weights come in the
family's flat layout (``families/hybrid.leaves``) and are rounded to the
configuration's bf16, the values the program holds; every product runs
at ``Precision.HIGHEST``.  One sequence at a time, the pattern's repeats
under ``lax.scan``, the head in blocks of rows: it fits one chip at the
configuration's full size.

``fp8=True`` is the control: the same equations with every matrix
product's inputs, and the recurrence's x, B and C, rounded to float8 e4m3
(scaled per row of activations and per output column of weights), the
nearest precision below the bfloat16 the configuration states.
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


def _mlp(h, lw, c, fp8):
    x = _rmsnorm(h, lw["ln2"], c["eps"])
    ff = jax.nn.silu(_mm(x, lw["w_gate"], fp8)) * _mm(x, lw["w_up"], fp8)
    return h + c["residual"] * _mm(ff, lw["w_down"], fp8)


def _mamba(h, lw, c, fp8):
    s = h.shape[0]
    nh, p, n, width = c["ssm_heads"], c["ssm_head_dim"], c["ssm_state"], \
        c["conv"]
    di = nh * p
    x = _rmsnorm(h, lw["ln1"], c["eps"])
    proj = _mm(x, lw["in_proj"], fp8)
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], \
        proj[:, 2 * di + 2 * n:]
    hist = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    conv = sum(hist[i:i + s] * lw["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    xs, b, cc = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    if fp8:
        xs, b, cc = _fp8(xs, -1), _fp8(b, -1), _fp8(cc, -1)
    delta = jax.nn.softplus(dt + lw["dt_bias"])            # (S, H)
    a = -jnp.exp(lw["a_log"])                              # (H,)
    xh = xs.reshape(s, nh, p)

    def step(state, inp):                                  # state (H, P, N)
        x_t, b_t, c_t, d_t = inp
        state = state * jnp.exp(d_t * a)[:, None, None] \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n)), (xh, b, cc, delta))
    y = y + lw["d_skip"][:, None] * xh
    y = _rmsnorm(y.reshape(s, di) * jax.nn.silu(z), lw["norm"], c["eps"])
    return h + c["residual"] * _mm(y, lw["out_proj"], fp8)


def _attention(h, lw, c, fp8):
    """Causal attention, a block of ``HEAD_BLOCK`` queries at a time over
    every key (the whole score matrix of a long sequence would not fit
    beside the float32 weights)."""
    s = h.shape[0]
    hd, nh, nkv = c["head_dim"], c["heads"], c["kv_heads"]
    x = _rmsnorm(h, lw["ln1"], c["eps"])
    q = _mm(x, lw["wq"], fp8).reshape(s // HEAD_BLOCK, HEAD_BLOCK, nkv,
                                      nh // nkv, hd)
    k = _mm(x, lw["wk"], fp8).reshape(s, nkv, hd)
    v = _mm(x, lw["wv"], fp8).reshape(s, nkv, hd)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)

    def block(i):
        scores = jnp.einsum("qhgd,khd->hgqk", q[i], k,
                            precision=HI) * c["attn_scale"]
        rows = i * HEAD_BLOCK + jnp.arange(HEAD_BLOCK)
        causal = rows[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        if fp8:
            pr = _fp8(pr, -1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(s // HEAD_BLOCK)).reshape(s, nh * hd)
    return h + c["residual"] * _mm(o, lw["wo"], fp8)


def _held(x, c):
    """The values the program holds: weights ``x`` rounded to the
    configuration's dtype, kept in float32.  Rounded where they are read,
    a layer at a time, by ``reduce_precision``: the compiler moves a
    convert to bf16 out of the layer loop, onto every weight at once, and
    that copy does not fit beside the float32 weights."""
    finfo = jnp.finfo(jnp.dtype(c["weights"]))
    return jax.lax.reduce_precision(x, finfo.nexp, finfo.nmant)


def _hidden(w, tokens, c, fp8):
    """Final-normed hidden states (S, d) of one sequence."""
    h = _held(w["embed"][tokens], c) * c["embedding"]
    stacked = {k: v for k, v in w.items() if "." in k}

    def body(h, gw):
        for i, kind in enumerate(c["pattern"]):
            lw = {k.split(".", 1)[1]: _held(v, c) for k, v in gw.items()
                  if k.startswith(f"l{i}.")}
            h = (_mamba if kind == "mamba" else _attention)(h, lw, c, fp8)
            h = _mlp(h, lw, c, fp8)
        return h, None

    h, _ = jax.lax.scan(body, h, stacked)
    return _rmsnorm(h, _held(w["final_norm"], c), c["eps"])


def _head(w, c):
    """The tied head, divided by the logits' divisor."""
    return _held(w["embed"], c).T / c["logits_scaling"]


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
    types = list(model["layer_types"])
    period = next(p for p in range(1, len(types) + 1)
                  if len(types) % p == 0
                  and types == types[:p] * (len(types) // p))
    heads = model["num_attention_heads"]
    return tuple(sorted({
        "heads": heads, "kv_heads": model["num_key_value_heads"],
        "head_dim": model["hidden_size"] // heads,
        "eps": float(model["rms_norm_eps"]),
        "pattern": tuple(types[:period]),
        "ssm_heads": model["mamba_n_heads"],
        "ssm_head_dim": model["mamba_d_head"],
        "ssm_state": model["mamba_d_state"], "conv": model["mamba_d_conv"],
        "attn_scale": float(model["attention_multiplier"]),
        "embedding": float(model["embedding_multiplier"]),
        "residual": float(model["residual_multiplier"]),
        "logits_scaling": float(model["logits_scaling"]),
        "weights": model["torch_dtype"],
    }.items()))
