"""Mamba2 (SSD) block, built on the chunked gated-linear-attention engine
(models/gla.py) — the SSD duality: q=C, k=B, v=Δ·x, log-decay = Δ·A.

A prompt or a prefill chunk runs through ``chunked_gla``, from a carried
state when one is given; the one-token decode step through the O(1)
recurrent ``gla_step``.  The depthwise causal conv carries its last
``conv_width - 1`` inputs.  The output is normalised by the gated RMSNorm
(``rmsnorm(y · silu(z)) · g``) before ``out_proj``, as Mamba-2 publishes
it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import common as cm
from . import gla


def init(key, cfg: ArchConfig) -> dict:
    kg = cm.KeyGen(key)
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    dt = jnp.dtype(cfg.param_dtype)
    conv_ch = di + 2 * n
    return {
        # order: z (gate), x, B, C, dt
        "in_proj": cm.linear_init(kg(), d, 2 * di + 2 * n + h, dtype=dt),
        "conv_w": (jax.random.normal(kg(), (cfg.conv_width, conv_ch),
                                     jnp.float32) * 0.2).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "a_log": jnp.zeros((h,), dt),               # A = -exp(a_log) = -1
        "dt_bias": jnp.zeros((h,), dt),
        "d_skip": jnp.ones((h,), dt),
        "norm": cm.rmsnorm_init(di, dt),            # gated RMSNorm gain
        "out_proj": cm.linear_init(kg(), di, d, dtype=dt),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None, length=None):
    """Depthwise causal conv1d.  x: (B, L, C); w: (W, C).
    ``state``: (B, W-1, C) carry-in; returns (out, new_state), the new
    state being the last W-1 inputs up to ``length`` (int or traced
    scalar; None: all L)."""
    bsz, l, c = x.shape
    wlen = w.shape[0]
    if state is None:
        state = jnp.zeros((bsz, wlen - 1, c), x.dtype)
    ext = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(wlen):
        out = out + ext[:, i:i + l].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    if wlen == 1:
        new_state = state
    elif length is None:
        new_state = ext[:, l:]
    else:
        new_state = jax.lax.dynamic_slice_in_dim(ext, length, wlen - 1, 1)
    return (jax.nn.silu(out + b.astype(jnp.float32))).astype(x.dtype), new_state


def _project(p, xin, cfg: ArchConfig):
    di, n = cfg.d_inner, cfg.ssm_state
    cd = jnp.dtype(cfg.compute_dtype)
    zxbcdt = cm.linear(p["in_proj"], xin, cd)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * n]
    dt_raw = zxbcdt[..., 2 * di + 2 * n:]
    return z, xbc, dt_raw


def apply(p: dict, xin: jax.Array, cfg: ArchConfig, *,
          state: dict | None = None, length=None
          ) -> tuple[jax.Array, dict]:
    """xin: (B, L, d).  state: {"ssm": (B,H,N,P), "conv": (B,W-1,C)}, the
    state after the tokens before this chunk (None: a fresh sequence).

    ``length`` (int or traced scalar; None: L) counts the real tokens of
    the chunk; the positions after it are padding and leave the state
    exactly as the real tokens left it — their Δ is 0, so their decay is
    exp(0) and their input term 0, and the conv state is taken at
    ``length``.  Their outputs are garbage for the caller to drop."""
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hd = cfg.ssm_head_dim
    cd = jnp.dtype(cfg.compute_dtype)
    z, xbc, dt_raw = _project(p, xin, cfg)

    conv_state = None if state is None else state["conv"]
    conv_out, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                      conv_state, length)
    x = conv_out[..., :di]
    b_ = conv_out[..., di:di + n]
    c_ = conv_out[..., di + n:]

    bsz, l, _ = xin.shape
    a = -jnp.exp(p["a_log"].astype(jnp.float32))          # (h,)
    delta = jax.nn.softplus(dt_raw.astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))  # (B,L,h)
    if length is not None:
        delta = jnp.where((jnp.arange(l) < length)[None, :, None], delta, 0.0)
    log_decay = delta * a                                  # (B,L,h)

    xh = x.reshape(bsz, l, h, hd).astype(jnp.float32)
    vh = xh * delta[..., None]                             # Δ·x
    qh = jnp.broadcast_to(c_[:, :, None, :], (bsz, l, h, n))
    kh = jnp.broadcast_to(b_[:, :, None, :], (bsz, l, h, n))
    s0 = None if state is None else state["ssm"]

    if s0 is not None and l == 1:
        y, s_final = gla.gla_step(qh[:, 0], kh[:, 0], vh[:, 0],
                                  log_decay[:, 0], None, s0)
        y = y[:, None]
    else:
        pad = (-l) % gla.DEFAULT_CHUNK
        if pad:
            padf = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            qh, kh, vh, log_decay = padf(qh), padf(kh), padf(vh), padf(log_decay)
        y, s_final = gla.chunked_gla(qh, kh, vh, log_decay,
                                     initial_state=s0)
        y = y[:, :l]

    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * xh
    gated = y.reshape(bsz, l, di) * jax.nn.silu(z.astype(jnp.float32))
    y = cm.rmsnorm(p["norm"], gated, cfg.norm_eps).astype(cd)
    return cm.linear(p["out_proj"], y, cd), {"ssm": s_final, "conv": new_conv}


def init_state(cfg: ArchConfig, batch: int, dtype) -> dict:
    h, n, hd = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    conv_ch = cfg.d_inner + 2 * n
    return {"ssm": jnp.zeros((batch, h, n, hd), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype)}
