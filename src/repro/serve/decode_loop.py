"""Fused on-device decode loop: ``k`` tokens per dispatch, donated buffers.

The scheduler's legacy decode path pays the worst dispatch granularity
the paper warns about: one device round-trip per decoded token —
``block_until_ready`` + ``device_get`` every tick, plus a fresh jit
dispatch (params-pytree flatten, executor future machinery, two engine
queries) per token.  On small models the host overhead is a large
fraction of the step time, and it is *fixed per dispatch* — exactly the
``T0`` of the paper's Overhead Law, re-read along the time axis.

This module is the fused alternative: one compiled ``lax.fori_loop``
advances every slot in the pool by up to ``k`` tokens per dispatch.

* **Dynamic trip count** — the loop bound is ``max(steps)`` where
  ``steps`` rides in as data, so a single compilation serves every
  depth ``k <= max_depth`` (no per-depth recompiles; ``fori_loop`` with
  a traced bound lowers to ``while``).
* **Masked early-exit** — each lane carries its remaining-step budget;
  a lane whose budget hits zero (request finished mid-loop, or its slot
  cache is full) stops merging cache writes and stops advancing, just
  like an inactive lane in the legacy per-tick step.
* **Donated slot buffers** — the whole cache pool is donated into the
  fused step (``donate_argnums``), extending the donation pattern of
  ``SlotKVCachePool.write_slot``: XLA aliases the output pool into the
  input buffers, so a decode dispatch allocates no new cache storage.
* **Token writes in place** — a lane attends its cache row as it stands
  plus the new token and returns the token (``attention.KVWrite``), not
  a written row; at the start of the next iteration ``masked_merge``
  writes one K and one V token per active lane and attention layer into
  the loop's carried pool, before the lanes read it.  An iteration
  writes bytes per lane, not the pool.
* **Device-resident token chain** — the final per-lane tokens come back
  as a device array that feeds the *next* dispatch directly, so the
  host never has to sync a token to keep the loop going; emitted tokens
  are drained asynchronously by the scheduler.

Token semantics are identical to the per-tick path: the per-lane step is
the same ``lane`` computation (shared with ``ServeScheduler``'s legacy
``_decode_step`` via ``make_lane_step``), greedy argmax, same masked
cache merge — a lane may compute garbage past its stop point but never
merges or emits it.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import flags, lm
from ..models.attention import KVWrite

# One compiled fused step serves every depth up to this; the scheduler
# clamps adaptive depth decisions against it.
DEFAULT_MAX_DEPTH = 32

# Self-speculative decoding (draft from the lane's own token history,
# verify in one batched forward).  The history ring is the draft
# proposer's only state; the candidate depths are the compiled widths
# the ``serve_spec_depth`` decision picks between.
DEFAULT_SPEC_HISTORY = 64
SPEC_DEPTH_CANDIDATES = (1, 2, 4, 8)


def make_lane_step(cfg: ArchConfig, *, window: int | None = None,
                   kernel_tuner=None) -> Callable:
    """The per-slot decode lane, vmapped over the pool.

    ``lanes(params, caches, toks, poss) -> (next_toks, outs)`` where
    every leading dim is ``n_slots``.  ``outs`` mirrors the pool: an
    attention layer's leaves are ``KVWrite``s (the lane's new K/V token
    ``(H_kv, D)`` and its row), a recurrent layer's the new state.  The
    lane reads its row and writes nothing; ``masked_merge`` makes the
    writes.  Both the legacy per-tick step and the fused loop body call
    exactly this function, so their per-step numerics cannot diverge.
    """

    def lane(params, row_caches, tok, pos):
        caches = jax.tree.map(
            lambda x: None if x is None else x[None], row_caches,
            is_leaf=lambda x: x is None)
        with flags.kernel_tuner(kernel_tuner or flags.KERNEL_TUNER):
            logits, new = lm.forward_step(
                params, tok[None, None], caches, pos, cfg, window=window)
        squeezed = jax.tree.map(
            lambda x: None if x is None else x[0], new,
            is_leaf=lambda x: x is None)
        return jnp.argmax(logits[0, 0], axis=-1), squeezed

    return jax.vmap(lane, in_axes=(None, 0, 0, 0))


@jax.named_scope("merge")
def masked_merge(caches, new_caches, active):
    """Advance the pool for active lanes only: inactive lanes (free
    slots, mid-prefill slots, lanes past their stop point) must not see
    their KV rows or recurrent states advanced by the garbage token
    their lane computed.

    What is merged follows what the lanes returned (``lm.commit``).  A
    ``KVWrite`` leaf — a token per lane — is written into the pool at
    its row, for the active lanes alone, in place; an inactive lane's
    cache is never written.  A full row or state (speculative verify
    rows, recurrent states) is kept by a ``where`` over the lanes.  Its
    operations carry the scope ``merge``."""
    return lm.commit(caches, new_caches, active)


def _per_shard(merge: Callable, cache_shardings) -> Callable:
    """``merge`` run on each device's shard of a mesh-sharded pool: a
    device writes the tokens of the lanes it holds (slots split over
    'data', heads or head dims over 'model'; the serve mesh never splits
    the sequence dim).  Partitioned by GSPMD instead, a write at a
    traced lane index gathers the pool across the slot axis."""
    from jax.sharding import PartitionSpec as P

    def dims(sh, ndim):
        return tuple(sh.spec) + (None,) * (ndim - len(sh.spec))

    first = jax.tree.leaves(cache_shardings)[0]
    lanes_axis = dims(first, 1)[0]

    def out_spec(sh, out):
        if isinstance(out, KVWrite):
            b, h, seq, d = dims(sh, 4)
            if seq is not None:
                raise ValueError("per-shard token writes need the pool's "
                                 f"sequence dim whole; it is split {seq}")
            return KVWrite(P(b, h, d), P(b))
        return sh.spec

    def run(caches, outs, active):
        specs = jax.tree.map(lambda sh: sh.spec, cache_shardings)
        return jax.shard_map(
            merge, mesh=first.mesh,
            in_specs=(specs, jax.tree.map(out_spec, cache_shardings, outs),
                      P(lanes_axis)),
            out_specs=specs)(caches, outs, active)

    return run


def _replicated_like(shardings):
    """Fully-replicated NamedShardings over the same mesh — the
    deliberate mid-loop reshard target for the HLO-audit gate test."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.tree.map(
        lambda s: None if s is None
        else NamedSharding(s.mesh, PartitionSpec()),
        shardings, is_leaf=lambda x: x is None)


def make_fused_decode_step(cfg: ArchConfig, *, window: int | None = None,
                           kernel_tuner=None,
                           max_depth: int = DEFAULT_MAX_DEPTH,
                           cache_shardings=None,
                           _inject_reshard: bool = False) -> Callable:
    """Build the jitted fused decode step.

    ``fused(params, caches, toks, poss, steps)`` advances lane ``i`` by
    ``steps[i]`` greedy tokens (``0 <= steps[i] <= max_depth``) and
    returns ``(new_caches, out_buf, final_toks)``:

    * ``new_caches`` — the pool after all merged writes (the input pool
      is **donated**; the caller must rebind it);
    * ``out_buf``    — ``(max_depth, n_slots)`` int32; row ``j`` holds
      lane ``i``'s ``j``-th emitted token for ``j < steps[i]`` (rows at
      and past a lane's budget repeat its last token — the scheduler
      truncates at each request's stop point);
    * ``final_toks`` — each lane's last token, ready to feed the next
      dispatch without a host round-trip.

    The loop runs ``max(steps)`` iterations (a traced bound: one
    compilation for all depths), so idle lanes never stretch the trip
    count beyond the deepest active budget.

    ``cache_shardings`` (a pytree of NamedShardings mirroring the slot
    pool) pins the mesh-sharded pool's placement at loop entry with a
    sharding constraint: the donated output must alias the sharded
    input buffers exactly, and the constraint stops GSPMD from electing
    to reshard the pool across the ``fori_loop`` carry.

    ``_inject_reshard`` (tests/CI only) re-constrains the pool to fully
    replicated *inside* the loop body — the exact mid-serve reshard the
    constraint exists to prevent.  ``analysis/hlo_audit`` lowers a step
    built this way to prove its gate fails when the hazard is real;
    the scheduler never sets it.
    """
    lanes = make_lane_step(cfg, window=window, kernel_tuner=kernel_tuner)
    max_depth = max(int(max_depth), 1)
    reshard_to = _replicated_like(cache_shardings) \
        if _inject_reshard and cache_shardings is not None else None

    def fused(params, caches, toks, poss, steps):
        if cache_shardings is not None:
            caches = jax.lax.with_sharding_constraint(caches,
                                                      cache_shardings)
        n = toks.shape[0]
        out_buf = jnp.zeros((max_depth, n), jnp.int32)
        no_writes = jax.tree.map(
            jnp.zeros_like, jax.eval_shape(lanes, params, caches, toks,
                                           poss)[1])

        def merge(caches, outs, active):
            if cache_shardings is None:
                return masked_merge(caches, outs, active)
            return _per_shard(masked_merge, cache_shardings)(
                caches, outs, active)

        def body(j, carry):
            caches, held, wrote, toks, poss, rem, out_buf = carry
            # The last iteration's writes land first, so the lanes read
            # the pool they were written into and XLA updates the carry in
            # place.  (Made after the lanes, the writes would not be
            # ordered after the lanes' reads, and XLA would copy the pool.)
            caches = merge(caches, held, wrote)
            if reshard_to is not None:
                caches = jax.lax.with_sharding_constraint(caches,
                                                          reshard_to)
            active = rem > 0
            next_toks, outs = lanes(params, caches, toks, poss)
            toks = jnp.where(active, next_toks, toks)
            out_buf = out_buf.at[j].set(toks)
            step = active.astype(poss.dtype)
            return (caches, outs, active, toks, poss + step, rem - step,
                    out_buf)

        trip = jnp.minimum(jnp.max(steps), max_depth)
        caches, held, wrote, toks, _, _, out_buf = jax.lax.fori_loop(
            0, trip, body, (caches, no_writes, jnp.zeros(n, bool), toks,
                            poss, jnp.minimum(steps, max_depth), out_buf))
        return merge(caches, held, wrote), out_buf, toks

    return jax.jit(fused, donate_argnums=(1,))


_ATTN_KINDS = ("attn", "shared_attn")


# ---------------------------------------------------------------------------
# self-speculative decoding (n-gram draft → one batched verify → rollback)
# ---------------------------------------------------------------------------

def _check_spec_arch(cfg: ArchConfig, window) -> None:
    """Speculation needs per-position rollback, which only pure
    full-attention stacks give for free: a rejected draft's KV entry
    sits past the accept point where the causal mask never reads it and
    the next verify window overwrites it.  A sliding-window ring write
    would clobber *live* entries ``window`` positions back, and a
    recurrent (SSM/xLSTM) state absorbs the draft tokens with no way to
    unwind — both are hard errors, not silent wrong tokens."""
    kinds = set(cfg.layer_kinds())
    if not kinds <= set(_ATTN_KINDS):
        raise ValueError(
            f"speculative decoding requires attention-only archs "
            f"(recurrent state cannot roll back); got {sorted(kinds)}")
    if window is not None:
        raise ValueError(
            "speculative decoding requires full attention (a ring-buffer "
            "window write would clobber live entries on rollback); got "
            f"window={window}")


def draft_from_history(hist: jax.Array, depth: int) -> jax.Array:
    """Prompt-lookup draft for one lane: ``depth - 1`` candidate tokens
    from the lane's recent token history (``hist``, oldest→newest,
    ``-1``-padded; the last entry is the lane's current carry token).

    The proposer finds the most recent earlier occurrence of the
    current *bigram* (the standard prompt-lookup heuristic: long enough
    to skip spurious single-token hits, short enough to fire on
    templated text) and proposes the tokens that followed it.  No match
    proposes the carry token repeated — drafts only ever gate *extra*
    accepted tokens, so a bad draft costs nothing but the verify width
    the ``serve_spec_depth`` decision already budgeted."""
    h = hist.shape[0]
    d = int(depth)
    j = jnp.arange(1, h - 1)
    hit = (hist[j - 1] == hist[h - 2]) & (hist[j] == hist[h - 1])
    best = jnp.max(jnp.where(hit, j, -1))
    lo = jnp.clip(best + 1, 0, h - (d - 1))
    cont = jax.lax.dynamic_slice(hist, (lo,), (d - 1,))
    fallback = jnp.full((d - 1,), hist[h - 1], hist.dtype)
    # -1 padding never matches a real token; the verify rejects it, but
    # it must not reach the embedding gather as a negative index.
    return jnp.maximum(jnp.where(best >= 0, cont, fallback), 0)


def _draft_batch(hist: jax.Array, depth: int) -> jax.Array:
    """``draft_from_history`` over all lanes at once — numerically
    identical to ``vmap(draft_from_history)`` but shaped for the hot
    loop body: the no-match fallback (carry token repeated) folds into
    the gather *indices* instead of a ``where`` over gathered values,
    so the whole draft lowers to one compare/reduce fusion plus one
    gather."""
    h = hist.shape[1]
    d = int(depth)
    j = jnp.arange(1, h - 1)
    hit = (hist[:, :-2] == hist[:, h - 2:h - 1]) \
        & (hist[:, 1:-1] == hist[:, h - 1:h])
    best = jnp.max(jnp.where(hit, j[None, :], -1), axis=1)
    lo = jnp.clip(best + 1, 0, h - (d - 1))
    k = jnp.arange(d - 1)[None, :]
    idx = jnp.where(best[:, None] >= 0, lo[:, None] + k, h - 1)
    return jnp.maximum(jnp.take_along_axis(hist, idx, axis=1), 0)


def make_spec_lane_step(cfg: ArchConfig, *, depth: int,
                        window: int | None = None,
                        kernel_tuner=None) -> Callable:
    """The per-slot *verify* lane, vmapped over the pool.

    ``lanes(params, caches, seqs, poss) -> (verified, new_caches)``:
    ``seqs`` is ``(n_slots, depth)`` — each lane's carry token followed
    by its ``depth - 1`` drafts — and ``verified`` is ``(n_slots,
    depth)``, the greedy argmax after every fed position.  One forward
    verifies all ``depth`` positions; it is the model the
    non-speculative lane runs, just fed a chunk (which it writes before
    attending it), and each position attends the same cache rows plus
    its own token, so position ``j``'s logits are byte-identical to what
    ``j`` sequential steps over the same tokens would produce."""
    _check_spec_arch(cfg, window)

    def lane(params, row_caches, seq, pos):
        caches = jax.tree.map(
            lambda x: None if x is None else x[None], row_caches,
            is_leaf=lambda x: x is None)
        with flags.kernel_tuner(kernel_tuner or flags.KERNEL_TUNER):
            logits, new = lm.forward_cached(
                params, seq[None], caches, pos, cfg, window=window,
                all_logits=True)
        squeezed = jax.tree.map(
            lambda x: None if x is None else x[0], new,
            is_leaf=lambda x: x is None)
        return jnp.argmax(logits[0], axis=-1), squeezed

    return jax.vmap(lane, in_axes=(None, 0, 0, 0))


def _spec_emit(drafts, verified, rem):
    """Accept/emit bookkeeping shared by both speculative loop bodies.

    ``verified[:, j]`` is the model's token after position ``j`` of the
    fed chunk; draft ``j`` is accepted iff every earlier draft matched
    (the longest-matching-prefix rule — exactly the tokens sequential
    greedy decoding would have produced, by induction).  Each active
    lane emits ``accepted + 1`` tokens (the corrected token rides on
    every verify), clamped to its remaining budget for mid-loop
    completion.  Returns ``(n_emit, new_toks)``."""
    match = (drafts == verified[:, :-1]).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    n_emit = jnp.minimum(accepted + 1, rem)
    last = jnp.clip(n_emit - 1, 0)
    new_toks = jnp.take_along_axis(verified, last[:, None], axis=1)[:, 0]
    return n_emit, new_toks


def _shift_history(hist, verified, n_emit):
    """Shift each lane's ``n_emit`` freshly-emitted tokens into its
    history ring (oldest→newest).  ``n_emit == 0`` (inactive lane)
    slices the original ring back out unchanged.  One batched gather
    over ``concat([hist, verified])`` rather than a vmapped
    dynamic-slice: the loop body runs every round, and XLA:CPU lowers
    the dense take to a single contiguous gather."""
    full = jnp.concatenate([hist, verified], axis=1)
    idx = n_emit[:, None] + jnp.arange(hist.shape[1])[None, :]
    return jnp.take_along_axis(full, idx, axis=1)


def _spec_write_out(out_buf, verified, cursor, n_emit):
    """Write each lane's emitted tokens into its ``out_buf`` rows
    ``cursor .. cursor + n_emit - 1`` (rows the drain reads in order).
    Dense gather + ``where`` over the whole ``(max_depth, n)`` grid
    instead of a 2D scatter: XLA:CPU lowers scatters to a scalar loop,
    and this runs in the hot loop body every verify round."""
    d = verified.shape[1]
    r = jnp.arange(out_buf.shape[0])[:, None]
    idx = jnp.clip(r - cursor[None, :], 0, d - 1)
    gathered = jnp.take_along_axis(verified.T, idx, axis=0)
    mask = (r >= cursor[None, :]) & (r < (cursor + n_emit)[None, :])
    return jnp.where(mask, gathered, out_buf)


def make_spec_decode_step(cfg: ArchConfig, *, depth: int,
                          history: int = DEFAULT_SPEC_HISTORY,
                          window: int | None = None, kernel_tuner=None,
                          max_depth: int = DEFAULT_MAX_DEPTH,
                          cache_shardings=None,
                          _inject_reshard: bool = False) -> Callable:
    """Build the jitted *self-speculative* fused decode step.

    ``fused(params, caches, hist, toks, poss, steps)`` — the
    ``make_fused_decode_step`` contract plus the per-lane token-history
    ring ``hist`` (``(n_slots, history)`` int32, ``-1``-padded, last
    entry = carry token).  Each loop round drafts ``depth - 1``
    candidate tokens per lane from its history (prompt-lookup bigram
    match), verifies all ``depth`` positions in **one** batched
    forward, accepts the longest matching prefix plus the corrected
    token, and rolls each lane back to its accept point — emitted
    output is byte-identical to greedy non-speculative decoding by
    construction, the loop just covers ``steps[i]`` tokens in fewer
    rounds.  Returns ``(new_caches, hist, out_buf, final_toks, stats)``
    where ``stats`` is ``(3,)`` int32: loop rounds executed, per-lane
    verify events, tokens emitted — the drain feeds them to the
    ``serve_spec_accept`` acceptance EMA behind the next
    ``serve_spec_depth`` decision.

    Rollback inside the donated loop: a rejected draft's KV entry sits
    at a position ``>=`` the lane's rolled-back position, where the
    causal mask never reads it, and the next verify round's ``depth``
    writes start at the rolled-back position and always cover the stale
    extent (it is at most ``depth - 1`` long) — so no cache write-back
    beyond the ordinary donated merge is ever needed.  The pool is
    **donated** at position 1 exactly like the non-speculative step.
    """
    d = max(int(depth), 2)
    lanes = make_spec_lane_step(cfg, depth=d, window=window,
                                kernel_tuner=kernel_tuner)
    max_depth = max(int(max_depth), 1)
    reshard_to = _replicated_like(cache_shardings) \
        if _inject_reshard and cache_shardings is not None else None

    def fused(params, caches, hist, toks, poss, steps):
        if cache_shardings is not None:
            caches = jax.lax.with_sharding_constraint(caches,
                                                      cache_shardings)
        n = toks.shape[0]
        out_buf = jnp.zeros((max_depth, n), jnp.int32)
        rem0 = jnp.minimum(steps, max_depth)

        def cond(carry):
            return jnp.any(carry[4] > 0)

        def body(carry):
            caches, hist, toks, poss, rem, out_buf, lane_rounds = carry
            if reshard_to is not None:
                caches = jax.lax.with_sharding_constraint(caches,
                                                          reshard_to)
            active = rem > 0
            drafts = _draft_batch(hist, d)
            seqs = jnp.concatenate([toks[:, None], drafts], axis=1)
            verified, new_caches = lanes(params, caches, seqs, poss)
            caches = masked_merge(caches, new_caches, active)
            n_emit, new_toks = _spec_emit(drafts, verified, rem)
            out_buf = _spec_write_out(out_buf, verified, rem0 - rem,
                                      n_emit)
            hist = _shift_history(hist, verified, n_emit)
            toks = jnp.where(active, new_toks, toks)
            # Per-lane round counters fuse with the elementwise carry
            # updates; the stats reduces run once after the loop.
            return (caches, hist, toks, poss + n_emit, rem - n_emit,
                    out_buf, lane_rounds + active.astype(jnp.int32))

        caches, hist, toks, _, _, out_buf, lane_rounds = \
            jax.lax.while_loop(
                cond, body,
                (caches, hist, toks, poss, rem0, out_buf,
                 jnp.zeros(n, jnp.int32)))
        # A lane is active for a prefix of the loop's rounds and emits
        # >= 1 token per active round, so: loop rounds = max lane
        # rounds, verify events = their sum, and every dispatched token
        # is emitted by exit (the cond drains rem to zero).
        stats = jnp.stack([jnp.max(lane_rounds),
                           jnp.sum(lane_rounds), jnp.sum(rem0)])
        return caches, hist, out_buf, toks, stats

    return jax.jit(fused, donate_argnums=(1,))


def make_paged_spec_lane_step(cfg: ArchConfig, *, depth: int,
                              page_size: int, max_len: int,
                              kernel_tuner=None) -> Callable:
    """The per-slot speculative verify lane over a *paged* pool, vmapped:
    ``make_paged_lane_step``'s gather-view construction with the
    ``depth``-wide verify forward, returning the ``depth`` newly-written
    KV tokens per attention layer (``(H_kv, depth, D)``) for the caller
    to scatter through the page table outside the vmap."""
    _check_spec_arch(cfg, None)
    kinds = tuple(cfg.layer_kinds())
    ps = int(page_size)
    d = max(int(depth), 2)

    def lane(params, pt_row, caches, seq, pos):
        idx = (pt_row[:, None] * ps
               + jnp.arange(ps, dtype=pt_row.dtype)[None, :]
               ).reshape(-1)[:max_len]
        row = [jax.tree.map(lambda x: x[idx].transpose(1, 0, 2)[None], c)
               for c in caches]
        with flags.kernel_tuner(kernel_tuner or flags.KERNEL_TUNER):
            logits, new = lm.forward_cached(
                params, seq[None], row, pos, cfg, window=None,
                all_logits=True)
        outs = [jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x[0], pos, d, axis=1),
            c) for c in new]
        return jnp.argmax(logits[0], axis=-1), outs

    axes = [None if kind in _ATTN_KINDS else 0 for kind in kinds]
    return jax.vmap(lane, in_axes=(None, 0, axes, 0, 0))


def make_paged_spec_decode_step(cfg: ArchConfig, *, depth: int,
                                page_size: int, max_len: int,
                                history: int = DEFAULT_SPEC_HISTORY,
                                kernel_tuner=None,
                                max_depth: int = DEFAULT_MAX_DEPTH,
                                cache_shardings=None,
                                _inject_reshard: bool = False) -> Callable:
    """The self-speculative fused step over a paged pool:
    ``fused(params, caches, page_tables, hist, toks, poss, steps)`` —
    the ``make_spec_decode_step`` contract with the page-table
    indirection riding in as data.  Each verify round scatters its
    ``depth`` KV tokens per lane through the table; positions past a
    lane's budget window or ``max_len`` are routed to the scratch page.
    Page-refcount safety is the *caller's* pre-dispatch contract: the
    scheduler's ``ensure_writable`` covers the whole speculative window
    ``[pos, pos + take + depth - 1)``, so a rejected draft only ever
    lands in a page this slot owns exclusively — never in a shared
    prefix page."""
    d = max(int(depth), 2)
    lanes = make_paged_spec_lane_step(cfg, depth=d, page_size=page_size,
                                      max_len=max_len,
                                      kernel_tuner=kernel_tuner)
    kinds = tuple(cfg.layer_kinds())
    ps = int(page_size)
    max_depth = max(int(max_depth), 1)
    reshard_to = _replicated_like(cache_shardings) \
        if _inject_reshard and cache_shardings is not None else None

    def fused(params, caches, page_tables, hist, toks, poss, steps):
        if cache_shardings is not None:
            caches = jax.lax.with_sharding_constraint(caches,
                                                      cache_shardings)
        n = toks.shape[0]
        n_pages_slot = page_tables.shape[1]
        out_buf = jnp.zeros((max_depth, n), jnp.int32)
        lane_ix = jnp.arange(n)
        rem0 = jnp.minimum(steps, max_depth)

        def cond(carry):
            return jnp.any(carry[4] > 0)

        def body(carry):
            caches, hist, toks, poss, rem, out_buf, lane_rounds = carry
            if reshard_to is not None:
                caches = jax.lax.with_sharding_constraint(caches,
                                                          reshard_to)
            active = rem > 0
            drafts = _draft_batch(hist, d)
            seqs = jnp.concatenate([toks[:, None], drafts], axis=1)
            verified, outs = lanes(params, page_tables, caches, seqs,
                                   poss)
            q = poss[:, None] + jnp.arange(d)[None, :]     # (n, d)
            pages = page_tables[lane_ix[:, None],
                                jnp.clip(q // ps, 0, n_pages_slot - 1)]
            ok = active[:, None] & (q < max_len)
            flat_ix = jnp.where(ok, pages * ps + q % ps, 0).reshape(-1)

            def merge(kind, c, o):
                if c is None:
                    return None
                if kind in _ATTN_KINDS:
                    return jax.tree.map(
                        lambda x, v: x.at[flat_ix].set(
                            v.transpose(0, 2, 1, 3).reshape(
                                (-1,) + x.shape[1:]).astype(x.dtype)),
                        c, o)
                return masked_merge(c, o, active)

            caches = [merge(kind, c, o) for kind, c, o in
                      zip(kinds, caches, outs, strict=True)]
            n_emit, new_toks = _spec_emit(drafts, verified, rem)
            out_buf = _spec_write_out(out_buf, verified, rem0 - rem,
                                      n_emit)
            hist = _shift_history(hist, verified, n_emit)
            toks = jnp.where(active, new_toks, toks)
            # Same fused per-lane round counters as the contiguous body.
            return (caches, hist, toks, poss + n_emit, rem - n_emit,
                    out_buf, lane_rounds + active.astype(jnp.int32))

        caches, hist, toks, _, _, out_buf, lane_rounds = \
            jax.lax.while_loop(
                cond, body,
                (caches, hist, toks, poss, rem0, out_buf,
                 jnp.zeros(n, jnp.int32)))
        stats = jnp.stack([jnp.max(lane_rounds),
                           jnp.sum(lane_rounds), jnp.sum(rem0)])
        return caches, hist, out_buf, toks, stats

    return jax.jit(fused, donate_argnums=(1,))


def make_paged_lane_step(cfg: ArchConfig, *, page_size: int, max_len: int,
                         kernel_tuner=None) -> Callable:
    """The per-slot decode lane over a *paged* pool, vmapped.

    ``lanes(params, page_tables, caches, toks, poss) ->
    (next_toks, lane_outs)`` where ``caches`` is the paged pool tree
    (flat page stores for attention layers, slot-major state for
    recurrent ones) and ``page_tables`` is ``(n_slots, pages_per_slot)``
    int32.  Each lane gathers its pages into the *same contiguous
    ``(H_kv, max_len, D)`` view* the slot pool hands
    ``make_lane_step``'s lane, then runs the identical
    ``lm.forward_step`` — byte-for-byte the contiguous computation,
    because every position past the lane's ``kv_len`` is masked to
    exactly zero weight regardless of which garbage the unmapped
    (scratch-page) gather rows carry.

    ``lane_outs`` is per-layer: the new KV token ``(H_kv, D)`` for
    attention layers (``KVWrite.token``; the caller scatters it into the
    shared page store *outside* the vmap), the full new state for
    recurrent layers.
    """
    kinds = tuple(cfg.layer_kinds())
    ps = int(page_size)

    def lane(params, pt_row, caches, tok, pos):
        idx = (pt_row[:, None] * ps
               + jnp.arange(ps, dtype=pt_row.dtype)[None, :]
               ).reshape(-1)[:max_len]

        def view(kind, c):
            if c is None:
                return None
            if kind in _ATTN_KINDS:
                return jax.tree.map(
                    lambda x: x[idx].transpose(1, 0, 2)[None], c)
            return jax.tree.map(lambda x: x[None], c)

        row = [view(kind, c) for kind, c in zip(kinds, caches,
                                                strict=True)]
        with flags.kernel_tuner(kernel_tuner or flags.KERNEL_TUNER):
            logits, new = lm.forward_step(
                params, tok[None, None], row, pos, cfg, window=None)

        def out(kind, c):
            if c is None:
                return None
            if kind in _ATTN_KINDS:
                return jax.tree.map(
                    lambda w: w.token[0], c,
                    is_leaf=lambda x: isinstance(x, KVWrite))
            return jax.tree.map(lambda x: x[0], c)

        outs = [out(kind, c) for kind, c in zip(kinds, new, strict=True)]
        return jnp.argmax(logits[0, 0], axis=-1), outs

    axes = [None if kind in _ATTN_KINDS else 0 for kind in kinds]
    return jax.vmap(lane, in_axes=(None, 0, axes, 0, 0))


def make_paged_decode_step(cfg: ArchConfig, *, page_size: int,
                           max_len: int, kernel_tuner=None,
                           max_depth: int = DEFAULT_MAX_DEPTH,
                           cache_shardings=None,
                           _inject_reshard: bool = False) -> Callable:
    """Build the jitted fused decode step over a paged pool.

    ``fused(params, caches, page_tables, toks, poss, steps)`` — same
    contract as ``make_fused_decode_step`` with the page-table
    indirection riding in as data (loop-invariant: the host resolves
    allocation and copy-on-write *before* dispatch, so the table never
    changes mid-loop).  The pool tree is **donated** at position 1,
    exactly like the contiguous step, and per-iteration attention KV
    lands via one scatter per layer into the flat page store: active
    lanes write ``table[pos // ps] * ps + pos % ps``, inactive lanes
    are routed to the scratch page's row 0 (their garbage is never
    mapped by any table).
    """
    lanes = make_paged_lane_step(cfg, page_size=page_size,
                                 max_len=max_len,
                                 kernel_tuner=kernel_tuner)
    kinds = tuple(cfg.layer_kinds())
    ps = int(page_size)
    max_depth = max(int(max_depth), 1)
    reshard_to = _replicated_like(cache_shardings) \
        if _inject_reshard and cache_shardings is not None else None

    def fused(params, caches, page_tables, toks, poss, steps):
        if cache_shardings is not None:
            caches = jax.lax.with_sharding_constraint(caches,
                                                      cache_shardings)
        n = toks.shape[0]
        out_buf = jnp.zeros((max_depth, n), jnp.int32)
        lane_ix = jnp.arange(n)
        no_writes = jax.tree.map(
            jnp.zeros_like, jax.eval_shape(lanes, params, page_tables,
                                           caches, toks, poss)[1])

        def write(caches, outs, flat_ix, active):
            def merge(kind, c, o):
                if c is None:
                    return None
                if kind in _ATTN_KINDS:
                    return jax.tree.map(
                        lambda x, v: x.at[flat_ix].set(v.astype(x.dtype)),
                        c, o)
                return masked_merge(c, o, active)

            return [merge(kind, c, o) for kind, c, o in
                    zip(kinds, caches, outs, strict=True)]

        def body(j, carry):
            caches, held, held_ix, wrote, toks, poss, rem, out_buf = carry
            # As in the contiguous step: the last iteration's tokens land
            # first, so the lanes read what was just written in place.
            caches = write(caches, held, held_ix, wrote)
            if reshard_to is not None:
                caches = jax.lax.with_sharding_constraint(caches,
                                                          reshard_to)
            active = rem > 0
            next_toks, outs = lanes(params, page_tables, caches, toks,
                                    poss)
            pages = page_tables[lane_ix, poss // ps]
            flat_ix = jnp.where(active, pages * ps + poss % ps, 0)
            toks = jnp.where(active, next_toks, toks)
            out_buf = out_buf.at[j].set(toks)
            step = active.astype(poss.dtype)
            return (caches, outs, flat_ix, active, toks, poss + step,
                    rem - step, out_buf)

        trip = jnp.minimum(jnp.max(steps), max_depth)
        caches, held, held_ix, wrote, toks, _, _, out_buf = \
            jax.lax.fori_loop(0, trip, body, (
                caches, no_writes, jnp.zeros(n, jnp.int32),
                jnp.zeros(n, bool), toks, poss, jnp.minimum(steps, max_depth),
                out_buf))
        return write(caches, held, held_ix, wrote), out_buf, toks

    return jax.jit(fused, donate_argnums=(1,))
