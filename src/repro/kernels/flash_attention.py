"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax tiling: grid (batch*q_heads, q_blocks, kv_blocks) with the
kv dimension innermost; running max/denominator/accumulator live in VMEM
scratch across kv steps.  Supports causal masking, sliding windows (SWA)
and grouped KV heads (GQA) — the kv-head block index is derived from the
q-head grid index, so no HBM repeat of K/V is ever materialised.

The (block_q, block_kv) tile comes from tuning.plan_attention — the
paper's chunk-size model applied to the VMEM budget: blocks as large as
double-buffering allows (T_m floor), grid deep enough to keep the
DMA/compute pipeline full (C chunks per core).

Fully-masked tiles (above the causal diagonal / outside the window) skip
their compute via pl.when — on real hardware this removes ~half the work
for causal prefill, the structural analogue of the paper's "don't schedule
empty chunks".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import resolve_interpret

NEG_INF = float("-inf")
_STAT_LANES = 128  # TPU scratch wants a 128-lane trailing dim


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int | None,
            block_q: int, block_kv: int, sq: int, skv: int, kv_len: int,
            nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Query positions are aligned to the *end* of the kv axis so the same
    # kernel serves training (sq == skv) and chunked prefill (sq < skv).
    q_off = iq * block_q + (kv_len - sq)
    k_off = ik * block_kv

    # Tile visibility: skip tiles that the causal diagonal or the window
    # excludes entirely (plus tiles fully in kv padding).
    visible = k_off < kv_len
    if causal:
        visible &= q_off + block_q - 1 >= k_off
    if window is not None:
        visible &= q_off - (k_off + block_kv - 1) < window

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        qi = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kj = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kj < kv_len
        if causal:
            mask &= qi >= kj
        if window is not None:
            mask &= (qi - kj) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Rows with no visible key yet keep m == -inf; exp of (-inf - -inf)
        # is NaN — neutralise via the mask / alpha guards below.
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int | None = None,
    scale: float | None = None, kv_len: int | None = None,
    sq_true: int | None = None,
    block_q: int = 128, block_kv: int = 128,
    plan: tuple[int, int] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D), Hq % Hkv == 0.

    Sq/Skv must be multiples of the block sizes.  ops.py pads and passes
    ``kv_len`` = true kv length (padding keys masked) and ``sq_true`` =
    true q length, so real q rows keep end-aligned positions
    (row r ↦ global position r + kv_len - sq_true).  An externally-chosen
    ``plan`` — a (block_q, block_kv) pair, e.g. a measured winner from
    ``autotune.KernelTuner.plan_attention`` — overrides the block
    arguments."""
    if plan is not None:
        block_q, block_kv = plan
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, skv, block_q, block_kv)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_len = kv_len if kv_len is not None else skv
    sq_true = sq_true if sq_true is not None else sq
    nq, nk = sq // block_q, skv // block_kv

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, sq=sq_true, skv=skv,
        kv_len=kv_len, nk=nk)

    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda g, i, j: (g // hq, g % hq, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda g, i, j: (g // hq, (g % hq) // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda g, i, j: (g // hq, (g % hq) // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda g, i, j: (g // hq, g % hq, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def _paged_kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                  hq: int, hkv: int, sq: int, page_size: int, nk: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    group = hq // hkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lens_ref[b]
    q_off = kv_len - sq          # queries end-aligned, as in _kernel
    k_off = j * page_size

    @pl.when(k_off < kv_len)
    def _compute():
        qi = q_off + jax.lax.broadcasted_iota(
            jnp.int32, (sq, page_size), 0)
        kj = k_off + jax.lax.broadcasted_iota(
            jnp.int32, (sq, page_size), 1)
        mask = kj < kv_len
        if causal:
            mask &= qi >= kj
        for kh in range(hkv):
            k = k_ref[:, kh, :].astype(jnp.float32)
            v = v_ref[:, kh, :].astype(jnp.float32)
            for h in range(kh * group, (kh + 1) * group):
                # Per head, exactly _kernel's online-softmax step.
                q = q_ref[0, h].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ()))) * scale
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h, :, 0]
                l_prev = l_ref[h, :, 0]
                m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
                alpha = jnp.where(m_prev == NEG_INF, 0.0,
                                  jnp.exp(m_prev - m_cur))
                l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
                acc_ref[h] = acc_ref[h] * alpha[:, None] + \
                    jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
                m_ref[h] = jnp.broadcast_to(m_cur[:, None], (sq, _STAT_LANES))
                l_ref[h] = jnp.broadcast_to(l_cur[:, None], (sq, _STAT_LANES))

    @pl.when(j == nk - 1)
    def _finalize():
        for h in range(hq):
            l = l_ref[h, :, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_ref[h] / l_safe[:, None]).astype(o_ref.dtype)


def paged_flash_attention_pallas(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, kv_lens: jax.Array, *,
    page_size: int, causal: bool = True, scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention reading K/V through a page table.

    ``q``: (B, Hq, Sq, D) queries, end-aligned per lane (row ``r`` of
    lane ``b`` sits at global position ``kv_lens[b] - Sq + r``).
    ``k_pages``/``v_pages``: the paged pool's flat token-major stores,
    ``(n_pages * page_size, Hkv, D)`` — page ``p`` owns rows
    ``[p*ps, (p+1)*ps)``.  ``page_table``: (B, n_blocks) int32, lane
    ``b``'s block ``j`` lives in page ``page_table[b, j]``.  ``kv_lens``:
    (B,) int32 true kv length per lane.

    The page table and lengths ride in as **scalar-prefetched**
    operands (``pltpu.PrefetchScalarGridSpec``): the BlockSpec index map
    reads ``page_table[b, j]`` to aim each kv tile's DMA directly at
    its page in HBM — the indirection costs an SMEM lookup, not a
    gather materialising the contiguous view.  The grid is (lane, page):
    one step DMAs a whole ``(page_size, Hkv, D)`` page, every KV head at
    once — the block's trailing ``(Hkv, D)`` are the store's own dims,
    so it is a legal TPU tile at any head count (a one-head
    ``(page_size, 1, D)`` block is not) — and runs every query head of
    the lane against it.  Per head the arithmetic is
    ``flash_attention_pallas``'s with ``block_kv == page_size``, so the
    two are byte-identical over contiguously-laid K/V — the property
    the paged pool's hypothesis test pins (tests/test_serve_paged.py).
    Pages at or past a lane's length skip their compute via
    ``pl.when`` — unmapped (scratch) entries are never touched, the
    structural "don't schedule empty chunks".

    Sliding windows are unsupported by design: the paged pool rejects
    SWA (a wrapped ring write would straddle shared pages)."""
    b, hq, sq, d = q.shape
    rows, hkv, _ = k_pages.shape
    assert hq % hkv == 0, (hq, hkv)
    ps = int(page_size)
    assert rows % ps == 0, (rows, ps)
    nk = int(page_table.shape[1])
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _paged_kernel, scale=scale, causal=causal, hq=hq, hkv=hkv, sq=sq,
        page_size=ps, nk=nk)

    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, hq, sq, d),
                         lambda i, j, pt, lens: (i, 0, 0, 0)),
            pl.BlockSpec((ps, hkv, d),
                         lambda i, j, pt, lens: (pt[i, j], 0, 0)),
            pl.BlockSpec((ps, hkv, d),
                         lambda i, j, pt, lens: (pt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, hq, sq, d), lambda i, j, pt, lens: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, sq, d), jnp.float32),
            pltpu.VMEM((hq, sq, _STAT_LANES), jnp.float32),
            pltpu.VMEM((hq, sq, _STAT_LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
      q, k_pages, v_pages)
