"""Attention: GQA / MQA / MHA, qk-norm, QKV bias, sliding windows,
cross-attention (VLM), KV caches (full + ring-buffer for SWA).

Three softmax-attention implementations share one signature:
  * naive   — full S×S materialisation (oracle; small shapes only)
  * chunked — online-softmax over kv blocks in pure jnp (lax.scan); the
              default for big shapes and for the dry-run (no S² buffers)
  * flash   — the Pallas kernel (kernels/flash_attention.py)
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels import ops as kops
from ..kernels import ref as kref
from . import common as cm


# ---------------------------------------------------------------------------
# chunked online-softmax attention (pure jnp, GQA-aware, no repeat)
# ---------------------------------------------------------------------------

def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int | None = None,
                      kv_len: Any = None, scale: float | None = None,
                      block_kv: int = 1024) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    Online softmax over kv blocks — peak memory O(Sq * block_kv), flash
    math in pure jnp.  ``kv_len`` (int or traced scalar) masks cache/pad
    slots; q positions are end-aligned: row r ↦ kv_len - Sq + r.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_len = skv if kv_len is None else kv_len
    block_kv = min(block_kv, skv)
    nblocks = (skv + block_kv - 1) // block_kv
    pad = nblocks * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    qg = q.reshape(b, hkv, group, sq, d).astype(jnp.float32) * scale
    qi = (kv_len - sq) + jnp.arange(sq)  # global q positions

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, jblk = inputs  # (hkv? no: (B? ...)) see swap: (hkv? )
        # kblk: (B, hkv, block_kv, d) after swapaxes: axis0 moved
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kblk.astype(jnp.float32))
        kj = jblk * block_kv + jnp.arange(block_kv)
        mask = kj[None, :] < kv_len
        if causal:
            mask = mask & (qi[:, None] >= kj[None, :])
        if window is not None:
            mask = mask & ((qi[:, None] - kj[None, :]) < window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[None, None, None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - m_new))
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    kb = k.reshape(b, hkv, nblocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nblocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    from . import flags

    if flags.UNROLL_FOR_ACCOUNTING:
        carry = (m0, l0, a0)
        for j in range(nblocks):
            carry, _ = step(carry, (kb[j], vb[j], jnp.int32(j)))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0), (kb, vb, jnp.arange(nblocks)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    return out.reshape(b, hq, sq, d)


def sdpa(q, k, v, *, impl: str = "chunked", causal: bool = True,
         window: int | None = None, kv_len: Any = None,
         scale: float | None = None) -> jax.Array:
    if impl == "skip":
        # Accounting aid: removes the attention mixing entirely so the
        # dry-run can isolate attention's flop/byte contribution by
        # subtraction (flash-adjusted roofline).  The value path is kept
        # live (seq-mean of v, broadcast to q's shape) so projections and
        # shapes survive while the O(S²) mixing disappears.
        group = q.shape[1] // k.shape[1]
        vbar = jnp.mean(v.astype(jnp.float32), axis=2, keepdims=True)
        vbar = jnp.repeat(vbar, group, axis=1).astype(q.dtype)
        return jnp.broadcast_to(vbar, q.shape) + 0 * q
    if impl == "flash":
        # Pallas kernel needs static kv_len; only full (non-cache) path.
        # With a tuner flagged on (--kernel-autotune) the (block_q,
        # block_kv) tile is a measured winner instead of the analytic
        # plan_attention prior.
        assert kv_len is None or isinstance(kv_len, int)
        from . import flags

        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    scale=scale, tuner=flags.KERNEL_TUNER)
    if impl == "naive":
        assert kv_len is None or isinstance(kv_len, int)
        return kref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    return attention_chunked(q, k, v, causal=causal, window=window,
                             kv_len=kv_len, scale=scale)


# ---------------------------------------------------------------------------
# attention module
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig, *, cross: bool = False) -> dict:
    kg = cm.KeyGen(key)
    d, hd = cfg.d_model, cfg.head_dim_
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": cm.linear_init(kg(), d, cfg.n_heads * hd, bias=cfg.qkv_bias,
                             dtype=dt),
        "wk": cm.linear_init(kg(), d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias,
                             dtype=dt),
        "wv": cm.linear_init(kg(), d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias,
                             dtype=dt),
        "wo": cm.linear_init(kg(), cfg.n_heads * hd, d, dtype=dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = cm.rmsnorm_init(hd, dt)
        p["k_norm"] = cm.rmsnorm_init(hd, dt)
    if cross:
        p["kv_norm"] = cm.rmsnorm_init(d, dt)
    return p


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def self_attention(p: dict, x: jax.Array, cfg: ArchConfig, *,
                   positions: jax.Array, window: int | None,
                   impl: str = "chunked",
                   cache: dict | None = None,
                   cache_pos: Any = None, stepwise: bool = False
                   ) -> tuple[jax.Array, dict | None]:
    """x: (B, S, d).  Without a cache: full causal self-attention (train /
    one-shot prefill).  With a cache and S == 1 (decode): attend the
    cache as it stands plus the new token, and return the write as a
    ``KVWrite`` per leaf rather than a written cache (``lm.commit`` makes
    it).  With a cache and S > 1: write K/V at ``cache_pos`` (ring slot
    for SWA) and attend the written cache — causally (chunked prefill),
    or, ``stepwise``, each query over the rows before it plus its own
    token as decode does (speculative verify, which must agree with
    decode token for token)."""
    cd = jnp.dtype(cfg.compute_dtype)
    hd = cfg.head_dim_
    q = _split_heads(cm.linear(p["wq"], x, cd), cfg.n_heads, hd)
    k = _split_heads(cm.linear(p["wk"], x, cd), cfg.n_kv_heads, hd)
    v = _split_heads(cm.linear(p["wv"], x, cd), cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = cm.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = cm.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope:
        cos, sin = cm.rope_angles(positions, hd, cfg.rope_theta)
        q = cm.apply_rope(q, cos, sin)
        k = cm.apply_rope(k, cos, sin)
    scale = cfg.attn_scale

    if cache is None:
        out = sdpa(q, k, v, impl=impl, causal=True, window=window,
                   scale=scale)
        new_cache = None
    else:
        s_cache = cache["k"].shape[2]
        slot = cache_pos % s_cache if window is not None else cache_pos
        kt = k.astype(cache["k"].dtype)
        vt = v.astype(cache["v"].dtype)
        if q.shape[2] == 1:
            # Decode: attend the cache as it stands plus the new token,
            # and hand the write back instead of a written cache.
            out = _step_attention(
                q, cache["k"], cache["v"], kt, vt, cache_pos, scale=scale,
                replaced=slot if window is not None else None)
            at = jnp.full((q.shape[0],), slot, jnp.int32)
            new_cache = {"k": KVWrite(kt[:, :, 0], at),
                         "v": KVWrite(vt[:, :, 0], at)}
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], kt,
                                              (0, 0, slot, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], vt,
                                              (0, 0, slot, 0))
            new_cache = {"k": ck, "v": cv}
            if stepwise:
                out = _step_attention(q, ck, cv, kt, vt, cache_pos,
                                      scale=scale)
            elif window is None:
                # The einsum-softmax form partitions cleanly when the
                # cache seq dim is sharded (long-context SP), unlike a
                # kv-block scan.
                kv_len = jnp.minimum(cache_pos + q.shape[2], s_cache)
                out = _cache_attention(q, ck, cv, kv_len, causal=True,
                                       scale=scale)
            else:
                # Ring buffer: every populated slot is within the window
                # by construction (cache length == window).
                out = _ring_attention(q, ck, cv, cache_pos, s_cache,
                                      scale=scale)
    out = cm.linear(p["wo"], _merge_heads(out), cd)
    return out, new_cache


def _scaled(q, scale):
    """q in float32 times the softmax scale (None: 1/sqrt(head_dim))."""
    qf = q.astype(jnp.float32)
    return qf / (q.shape[-1] ** 0.5) if scale is None else qf * scale


def _cache_attention(q, ck, cv, kv_len, *, causal: bool, scale=None):
    """Einsum-softmax attention over a (possibly sharded) KV cache.
    q: (B, Hq, Sq, D); ck/cv: (B, Hkv, S, D); kv_len: valid slot count."""
    b, hq, sq, d = q.shape
    hkv, s_cache = ck.shape[1], ck.shape[2]
    group = hq // hkv
    qg = _scaled(q.reshape(b, hkv, group, sq, d), scale)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ck.astype(jnp.float32))
    kj = jnp.arange(s_cache)[None, :]
    mask = kj < kv_len
    if causal:
        qi = (kv_len - sq) + jnp.arange(sq)[:, None]
        mask = mask & (qi >= kj)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, cv.astype(jnp.float32))
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def _step_attention(q, ck, cv, k, v, pos, *, replaced=None, scale=None):
    """Einsum-softmax attention of each query over the cache rows before
    its position, joined in one softmax with its own key and value: the
    decode form.

    q: (B, Hq, L, D) at positions ``pos .. pos + L - 1``; ck/cv: (B, Hkv,
    S, D); k/v: (B, Hkv, L, D), the queries' own keys and values in the
    cache's dtype.  Query ``r`` reads cache row ``j`` when ``j < pos + r``
    and ``j != replaced`` (the ring row a decode token overwrites).  The
    rows a query reads hold the same values whether its chunk was written
    first (a speculative verify) or not (decode), so the two agree token
    for token.  Decode attention is a memory-bound GEMV over the cache."""
    b, hq, sq, d = q.shape
    hkv, s_cache = ck.shape[1], ck.shape[2]
    group = hq // hkv
    qg = _scaled(q.reshape(b, hkv, group, sq, d), scale)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ck.astype(jnp.float32))
    kj = jnp.arange(s_cache)[None, :]
    mask = kj < pos + jnp.arange(sq)[:, None]
    if replaced is not None:
        mask = mask & (kj != replaced)
    s = jnp.where(mask, s, -jnp.inf)
    s_own = jnp.einsum("bhgqd,bhqd->bhgq", qg,
                       k.astype(jnp.float32))[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_own)
    p = jnp.exp(s - m)
    p_own = jnp.exp(s_own - m)
    denom = jnp.sum(p, axis=-1, keepdims=True) + p_own
    out = (jnp.einsum("bhgqk,bhkd->bhgqd", p, cv.astype(jnp.float32))
           + p_own * v.astype(jnp.float32)[:, :, None]) / denom
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def _ring_attention(q, ck, cv, cache_pos, s_cache, *, scale=None):
    """Decode attention over a ring-buffer SWA cache: softmax over the
    populated slots (≤ window of them); permutation-invariant since RoPE
    phases were applied at write time."""
    b, hq, sq, d = q.shape
    hkv = ck.shape[1]
    group = hq // hkv
    n_valid = jnp.minimum(cache_pos + sq, s_cache)
    qg = _scaled(q.reshape(b, hkv, group, sq, d), scale)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ck.astype(jnp.float32))
    mask = jnp.arange(s_cache)[None, :] < n_valid
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, cv.astype(jnp.float32))
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def cross_attention(p: dict, x: jax.Array, kv_feats: jax.Array,
                    cfg: ArchConfig, *, impl: str = "chunked"
                    ) -> jax.Array:
    """x: (B, S, d) queries; kv_feats: (B, T, d) frontend embeddings
    (image patches / conditioning frames).  Non-causal, no RoPE."""
    cd = jnp.dtype(cfg.compute_dtype)
    hd = cfg.head_dim_
    feats = cm.rmsnorm(p["kv_norm"], kv_feats.astype(cd), cfg.norm_eps)
    q = _split_heads(cm.linear(p["wq"], x, cd), cfg.n_heads, hd)
    k = _split_heads(cm.linear(p["wk"], feats, cd), cfg.n_kv_heads, hd)
    v = _split_heads(cm.linear(p["wv"], feats, cd), cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = cm.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = cm.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    out = sdpa(q, k, v, impl="chunked" if impl == "flash" else impl,
               causal=False)
    return cm.linear(p["wo"], _merge_heads(out), cd)


class KVWrite(NamedTuple):
    """A decode step's cache write, not yet made: each batch row's new K
    or V token ``(B, H_kv, D)`` and the cache row it goes to, ``(B,)``
    (``pos``, or ``pos % s_cache`` in a ring)."""

    token: jax.Array
    at: jax.Array


def write_tokens(caches: list, writes: list, keep: jax.Array) -> list:
    """Write each batch row's token of every ``KVWrite`` in ``writes`` into
    its cache ``(B, H_kv, S, D)`` at the row ``at`` — in place when the
    caches are donated buffers or a loop carry.  Only the batch rows whose
    ``keep`` is True are visited, one after another in one loop over all
    the caches; every other row's bytes stay as they were."""
    order = jnp.argsort(~keep, stable=True)     # the rows to write first

    def put(i, cs):
        b = order[i]
        return [_put_row(c, w, b) for c, w in zip(cs, writes, strict=True)]

    return jax.lax.fori_loop(0, jnp.sum(keep, dtype=jnp.int32), put,
                             list(caches))


def _put_row(cache, w: KVWrite, b):
    """Batch row ``b`` of ``w`` into ``cache``.  The write covers the
    aligned block of rows around ``at`` that fills one tile of the TPU's
    layout (8 x 128 words: 32 // itemsize rows), setting one row of it: a
    one-row update makes the compiler lay the whole cache out by heads
    and copy it at every step.  An ``at`` past the cache changes
    nothing."""
    _, h, s, d = cache.shape
    t = min(32 // cache.dtype.itemsize, s)
    at = w.at[b]
    start = jnp.clip((at // t) * t, 0, s - t)
    block = jax.lax.dynamic_slice(cache, (b, 0, start, 0), (1, h, t, d))
    row = (jnp.arange(t) == at - start)[None, None, :, None]
    tok = w.token[b].astype(cache.dtype)[None, :, None, :]
    return jax.lax.dynamic_update_slice(
        cache, jnp.where(row, tok, block), (b, 0, start, 0))


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               window: int | None, dtype) -> dict:
    s = min(window, max_len) if window is not None else max_len
    shape = (batch, cfg.n_kv_heads, s, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
