"""Chunked gated linear attention — the shared engine for Mamba2 (SSD) and
mLSTM (both are gated-linear-attention recurrences).

    y_i = sum_{j<=i} (q_i · k_j) * exp(cum_i - cum_j + g_j) * v_j
    cum = inclusive cumsum of per-step log-decay

computed chunk-parallel (the paper's chunking insight applied to the
sequence dimension): intra-chunk quadratic term + inter-chunk state
S (B, H, N, P) carried by a lax.scan over chunks.  Every exponential is
of a sum of log-decays over a span that ends where it is read, plus the
source's log-gain, so none exceeds ``exp(gain)`` however fast a head
decays (a factorised ``exp(cum_i) * exp(-cum_j)`` would underflow and
overflow at a realistic Mamba-2 decay over a 64-token chunk).

Decode uses the O(1) recurrent step (``gla_step``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 64


def chunked_gla(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, log_gain: jax.Array | None = None,
                *, chunk: int = DEFAULT_CHUNK,
                initial_state: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """q, k: (B, L, H, N); v: (B, L, H, P); log_decay/log_gain: (B, L, H).

    Returns (y (B, L, H, P) fp32, final_state (B, H, N, P) fp32).
    """
    b, l, h, n = q.shape
    p = v.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    qf = q.astype(jnp.float32).reshape(b, nc, chunk, h, n)
    kf = k.astype(jnp.float32).reshape(b, nc, chunk, h, n)
    vf = v.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    ld = log_decay.astype(jnp.float32).reshape(b, nc, chunk, h)
    g = (jnp.zeros_like(ld) if log_gain is None
         else log_gain.astype(jnp.float32).reshape(b, nc, chunk, h))

    lcum = jnp.cumsum(ld, axis=2)                  # within-chunk cumsum
    total = lcum[:, :, -1, :]                      # (b, nc, h)

    # intra-chunk: att[i, j] = (q_i · k_j) exp(cum_i - cum_j + g_j), i >= j
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    seg = lcum[:, :, :, None, :] - lcum[:, :, None, :, :] \
        + g[:, :, None, :, :]                      # (b, nc, i, j, h)
    w = jnp.exp(jnp.where(mask, seg, -jnp.inf))
    att = jnp.einsum("bcihn,bcjhn->bchij", qf, kf) \
        * w.transpose(0, 1, 4, 2, 3)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", att, vf)

    # local end-of-chunk states: S_loc = sum_j exp(total - cum_j + g_j) k_j ⊗ v_j
    ks = kf * jnp.exp(total[:, :, None, :] - lcum + g)[..., None]
    s_loc = jnp.einsum("bcjhn,bcjhp->bchnp", ks, vf)
    qd = qf * jnp.exp(lcum)[..., None]             # decayed queries

    # scan chunks: S_c = exp(total_c) * S_{c-1} + S_loc_c
    decay_c = jnp.exp(total)                       # (b, nc, h)

    def step(s_prev, inp):
        dc, sl = inp                               # (b, h), (b, h, n, p)
        s_new = s_prev * dc[..., None, None] + sl
        return s_new, s_prev

    s0 = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    from . import flags

    if flags.UNROLL_FOR_ACCOUNTING:
        s, prevs = s0, []
        for c in range(nc):
            prevs.append(s)
            s, _ = step(s, (decay_c[:, c], s_loc[:, c]))
        s_final = s
        s_prevs = jnp.stack(prevs, axis=1)
    else:
        s_final, s_prevs = jax.lax.scan(
            step, s0, (decay_c.swapaxes(0, 1), s_loc.swapaxes(0, 1)))
        s_prevs = s_prevs.swapaxes(0, 1)           # (b, nc, h, n, p)

    # inter-chunk: y_i += exp(lcum_i) * q_i · S_{c-1}
    y_inter = jnp.einsum("bcihn,bchnp->bcihp", qd, s_prevs)

    y = (y_intra + y_inter).reshape(b, l, h, p)
    return y, s_final


def gla_step(q: jax.Array, k: jax.Array, v: jax.Array,
             log_decay: jax.Array, log_gain: jax.Array | None,
             state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single-token recurrent step.

    q, k: (B, H, N); v: (B, H, P); log_decay/log_gain: (B, H);
    state: (B, H, N, P).  Returns (y (B, H, P), new_state).
    """
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    ld = log_decay.astype(jnp.float32)
    gain = (jnp.zeros_like(ld) if log_gain is None
            else log_gain.astype(jnp.float32))
    s_new = state * jnp.exp(ld)[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", kf * jnp.exp(gain)[..., None], vf)
    y = jnp.einsum("bhn,bhnp->bhp", qf, s_new)
    return y, s_new


def gla_reference(q, k, v, log_decay, log_gain=None):
    """Naive O(L²) oracle for tests."""
    b, l, h, n = q.shape
    cum = jnp.cumsum(log_decay.astype(jnp.float32), axis=1)
    g = (jnp.zeros_like(cum) if log_gain is None
         else log_gain.astype(jnp.float32))
    w = cum[:, :, None, :] - cum[:, None, :, :] + g[:, None, :, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    w = jnp.where(mask[None, :, :, None], jnp.exp(w), 0.0)
    att = jnp.einsum("bihn,bjhn->bijh", q.astype(jnp.float32),
                     k.astype(jnp.float32)) * w
    return jnp.einsum("bijh,bjhp->bihp", att, v.astype(jnp.float32))
