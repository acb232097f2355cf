"""Slot-based KV/SSM cache pool for continuous batching.

The old engine held one monolithic cache per *batch* — every request in a
batch had to start and stop together, and a new batch meant a fresh
``lm.init_caches`` allocation.  The pool instead allocates the per-layer
caches **once**, with the leading batch dimension reinterpreted as
``n_slots`` fixed-size slots.  A request acquires a slot from the
free-list on admission, carries its own position inside the slot, and
releases the slot when it finishes — so requests of different lengths
join and leave the running batch with no cache reallocation (asserted by
``allocations``, which counts device-buffer allocations and must stay at
1 for the pool's lifetime).

Layer cache layout (from ``lm.init_caches``):
  * attention:      {"k","v"} of shape (n_slots, H_kv, S, D) — full or
    ring-buffer (SWA) along S;
  * mamba2 / xLSTM: recurrent state arrays with leading dim n_slots;
  * cross_attn:     None (KV recomputed from frontend feats — the
    scheduler does not serve cross-attention requests).

Rows are functionally updated (``.at[slot].set``); XLA reuses the
buffers, and the pool arrays never change shape — the property that lets
one compiled decode step serve every mix of active requests.

``PagedKVCachePool`` (below) is the paged alternative: attention KV
lives in fixed-size *pages* indexed through a per-slot ``int32`` page
table, so a request's HBM footprint grows with its sequence instead of
being ``max_len`` up front, and identical prompt prefixes share pages
copy-on-write through a token-keyed prefix cache.  Same donation /
poison / adopt discipline, same ``allocations`` invariant.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core import strict
from ..models import lm
from . import spans

_ATTN_KINDS = ("attn", "shared_attn")


def _tree_map(fn, tree):
    """tree_map that keeps ``None`` layer entries (cross_attn) in place."""
    return jax.tree.map(fn, tree, is_leaf=lambda x: x is None)


def _maybe(fn):
    return lambda x: None if x is None else fn(x)


class _StateReset:
    """Zeroes one slot's rows of every recurrent (non-attention) layer,
    under the span ``serve.state_reset``: one jitted update with those
    layers' states donated, as ``write_slot`` writes a row (an eager
    ``.at[slot].set(0)`` per leaf would copy each leaf's whole pool
    array).  A pool without recurrent layers does nothing."""

    def __init__(self, kinds, caches: list, shardings):
        self.layers = tuple(i for i, (k, c) in enumerate(
            zip(kinds, caches, strict=True))
            if c is not None and k not in _ATTN_KINDS)
        self._shardings = None if shardings is None \
            else [shardings[i] for i in self.layers]
        self._jit = None

    def __call__(self, caches: list, slot: int) -> list:
        if not self.layers:
            return caches
        with spans.span("serve.state_reset", layers=len(self.layers)):
            if self._jit is None:
                def zero(states, s):
                    return jax.tree.map(
                        lambda x: jax.lax.dynamic_update_slice(
                            x, jnp.zeros((1,) + x.shape[1:], x.dtype),
                            (s,) + (0,) * (x.ndim - 1)), states)

                self._jit = jax.jit(zero, donate_argnums=0,
                                    out_shardings=self._shardings)
            new = self._jit([caches[i] for i in self.layers],
                            jnp.int32(slot))
            out = list(caches)
            for i, c in zip(self.layers, new, strict=True):
                out[i] = c
            return out


class SlotOverflowError(ValueError):
    """A lane's position would pass its slot's ``max_len`` storage —
    cache writes past that point land in another slot's rows (silent
    wraparound through the ring/dynamic-slice indexing).  The scheduler
    budgets every fused dispatch against the slot's remaining room, so
    raising here means that accounting broke; typed so the serve loop
    can turn it into a structured failure instead of corrupt output."""

    def __init__(self, slot: int, pos: int, max_len: int):
        self.slot = slot
        self.pos = pos
        self.max_len = max_len
        super().__init__(
            f"slot {slot} advanced past max_len: {pos} > {max_len}")


class CacheLayoutError(ValueError):
    """An adopted cache tree does not match the pool's layout.  The
    fused decode step *donates* the pool, so adopt() is a blind rebind —
    a step built for different geometry (other slot count, other arch,
    other dtype) would silently become the pool and corrupt every later
    slot read.  Checked structurally (shapes/dtypes, no device sync)."""


def _layout(tree) -> tuple:
    """Hashable (shape, dtype) signature of a cache tree — what adopt()
    compares; flattening a few dozen array stubs is host microseconds."""
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree.leaves(tree))


class SlotKVCachePool:
    """Fixed-size cache slots with a free-list and per-slot positions."""

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int, *,
                 window: int | None = None, dtype=None, mesh=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self._donated_to: str | None = None
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.window = window if window is not None else cfg.attn_window
        self.caches = lm.init_caches(cfg, n_slots, max_len,
                                     window=self.window, dtype=dtype)
        # Mesh-sharded placement: the slot (batch) dim splits into
        # data-parallel groups, KV heads over 'model' (the same
        # launch/sharding cache_specs rules the dry-run path uses).  The
        # device_put happens once, before serving — placement of the one
        # allocation, not a reallocation.
        self.mesh = mesh
        self.shardings = None
        if mesh is not None:
            from ..launch import sharding as sharding_lib

            self.shardings = sharding_lib.to_shardings(
                mesh, sharding_lib.cache_specs(cfg, mesh, n_slots, max_len))
            self.caches = jax.device_put(self.caches, self.shardings)
        self._layout_sig = _layout(self.caches)
        self._reset = _StateReset(cfg.layer_kinds(), self.caches,
                                  self.shardings)
        self.allocations = 1            # init_caches calls ever made
        self._free = list(range(n_slots - 1, -1, -1))
        self.positions = [0] * n_slots  # tokens cached per slot (host side)
        self.owner: list[Any] = [None] * n_slots
        self._write_jit = None

    # -- donation poison (strict mode) ---------------------------------------
    @property
    def caches(self):
        """The per-layer cache pytree.  Under strict mode
        (``core.strict``), reading this between a donating dispatch
        (``mark_donated``) and the matching ``adopt()`` raises
        ``DonatedCacheError``: the arrays' device buffers are already
        aliased into the dispatch's outputs."""
        if self._donated_to is not None and strict.enabled():
            raise strict.DonatedCacheError(self._donated_to)
        return self._caches

    @caches.setter
    def caches(self, tree) -> None:
        self._caches = tree
        self._donated_to = None

    def mark_donated(self, consumer: str) -> None:
        """Poison ``caches`` until the next rebind (``adopt()`` or a
        direct assignment).  The scheduler calls this immediately after
        handing the pool to a ``donate_argnums`` dispatch.  Costs one
        string store; the read-side check only fires under strict mode."""
        self._donated_to = consumer

    # -- free-list -----------------------------------------------------------
    def free_slots(self) -> int:
        return len(self._free)

    def acquire(self, owner: Any = None) -> int | None:
        """Claim a slot for ``owner``; None when the pool is exhausted.
        The slot's recurrent state is zeroed (ring/full KV rows need no
        wipe — attention masks by position — but SSM states carry over)."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.owner[slot] = owner
        self.positions[slot] = 0
        self._zero_slot_states(slot)
        return slot

    def release(self, slot: int) -> None:
        if self.owner[slot] is None and slot in self._free:
            raise ValueError(f"slot {slot} double-released")
        self.owner[slot] = None
        self.positions[slot] = 0
        self._free.append(slot)

    def _zero_slot_states(self, slot: int) -> None:
        """Zero the non-attention (recurrent) state rows of ``slot``."""
        self.caches = self._reset(self.caches, slot)

    # -- slot I/O ------------------------------------------------------------
    def read_slot(self, slot: int):
        """The slot's caches as a batch-of-1 pytree (device views)."""
        return _tree_map(_maybe(lambda x: x[slot:slot + 1]), self.caches)

    def write_slot(self, slot: int, row_caches) -> None:
        """Write a batch-of-1 cache pytree back into ``slot``.

        Goes through one jitted update with the pool donated, so XLA
        aliases the output into the existing buffers — an eager
        ``.at[slot].set`` would copy every layer's full pool array per
        chunk.  ``slot`` rides in as a traced scalar (one compile total).
        """
        if self._write_jit is None:
            def write(caches, row, s):
                return jax.tree.map(
                    lambda c, n: c if c is None else
                    jax.lax.dynamic_update_slice(
                        c, n.astype(c.dtype),
                        (s,) + (0,) * (c.ndim - 1)),
                    caches, row, is_leaf=lambda x: x is None)

            # Explicit out_shardings on the mesh path: donation aliasing
            # requires output placement to equal the input's, and pinning
            # it stops GSPMD from ever resharding the pool mid-serve.
            self._write_jit = jax.jit(write, donate_argnums=0,
                                      out_shardings=self.shardings)
        self.caches = self._write_jit(self.caches, row_caches,
                                      jnp.int32(slot))

    def adopt(self, new_caches) -> None:
        """Rebind the pool to ``new_caches`` — the output of a step that
        **donated** the current pool (the fused decode loop,
        serve/decode_loop.py, like ``write_slot`` above).  The old
        arrays' buffers were aliased into the new ones by XLA; after
        this call the previous ``self.caches`` must never be touched
        again.  No allocation happens: ``allocations`` stays wherever
        it is (the invariant the donation tests pin at 1).

        Raises ``CacheLayoutError`` when the adopted tree's shapes or
        dtypes differ from the pool's — the step that produced it was
        built for different geometry, and rebinding would corrupt every
        later slot read."""
        if _layout(new_caches) != self._layout_sig:
            raise CacheLayoutError(
                f"adopted cache tree does not match the pool layout "
                f"(n_slots={self.n_slots}, max_len={self.max_len}, "
                f"arch={self.cfg.name})")
        self.caches = new_caches

    def advance(self, slot: int, n: int) -> int:
        """Advance ``slot``'s position by ``n`` cached tokens (the fused
        decode path moves a slot by up to ``k`` per dispatch).  The
        caller must have budgeted ``n`` against ``max_len``; raises
        ``SlotOverflowError`` on overshoot — cache writes past the
        slot's storage would wrap into other slots' rows."""
        if n < 0:
            raise ValueError(f"negative advance: {n}")
        pos = self.positions[slot] + n
        if pos > self.max_len:
            raise SlotOverflowError(slot, pos, self.max_len)
        self.positions[slot] = pos
        return pos

    def rollback(self, slot: int, n: int) -> int:
        """Roll ``slot``'s position back by ``n`` tokens (speculative
        decode: drafts past the accept point are rejected).  Rows at and
        past the rolled-back position become dead storage — the causal
        mask never attends a position at or past the query's own, and
        the next decode write starts at the rolled-back position and
        covers the stale extent — so no device work is needed, only the
        position bookkeeping."""
        if n < 0:
            raise ValueError(f"negative rollback: {n}")
        if n > self.positions[slot]:
            raise ValueError(
                f"rollback of {n} past slot {slot}'s position "
                f"{self.positions[slot]}")
        self.positions[slot] -= n
        return self.positions[slot]

    def positions_array(self) -> jax.Array:
        """Per-slot positions as an (n_slots,) int32 device array (free
        slots report 0; their decode lanes are ignored)."""
        return jnp.asarray(
            [min(p, self.max_len - 1) for p in self.positions], jnp.int32)


class PagePoolExhaustedError(RuntimeError):
    """Every page is referenced by a live slot and nothing in the prefix
    cache is evictable: an ``ensure_writable`` could not be honoured.
    With the default sizing (``n_slots * pages_per_slot`` pages plus
    slack) this cannot happen for slot writes — it means the pool was
    constructed deliberately undersized, or refcounts leaked."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        super().__init__(
            f"page pool exhausted: all {n_pages} pages are referenced "
            f"by live slots (prefix cache already evicted)")


@dataclasses.dataclass
class _PrefixEntry:
    """One cached prompt block: the page holding the KV of tokens
    ``[len(key) - n_tokens, len(key))`` of the prefix spelled by ``key``
    (the dict key is the full token tuple up to and including this
    block, so a lookup hit *is* the content check — no hash-collision
    re-derivation needed)."""

    page: int
    n_tokens: int            # tokens of the block this page holds
    full: bool               # page-aligned block (ps tokens) or partial
    last_used: int = 0       # LRU clock for eviction


class PagedKVCachePool:
    """Paged KV pool: fixed-size pages behind per-slot page tables, with
    copy-on-write prefix sharing.

    Layout per attention layer: one flat token-major page store
    ``{"k","v"}`` of shape ``(n_pages * page_size, H_kv, D)`` — page
    ``p`` owns rows ``[p*ps, (p+1)*ps)``.  A slot's logical row is the
    gather of its table's pages (``decode_loop.make_paged_decode_step``
    and ``read_slot`` build that contiguous view), so the model code
    underneath is byte-identical to the contiguous pool: same
    ``lm.forward_step``, same masked attention, garbage past a lane's
    position masked to exactly zero either way.  Recurrent layer state
    (SSM/xLSTM) has no sequence axis to page — it stays slot-major,
    exactly as in ``SlotKVCachePool``.

    Page 0 is a permanently-allocated scratch page: unmapped table
    entries point at it, and the fused decode step routes inactive
    lanes' writes there, so a single scatter per layer serves every mix
    of active lanes without dynamic shapes.

    Copy-on-write protocol (all host-side bookkeeping, device work only
    for the actual page copies):

    * every page has a refcount (slot tables + prefix-cache entries);
    * ``ensure_writable(slot, lo, hi)`` must precede any device
      write into ``[lo, hi)`` — it allocates unmapped pages and
      copy-on-writes shared ones (one donated ``dynamic_update_slice``
      per copied page);
    * ``register_prefix`` publishes a freshly-prefilled prompt's pages
      into the token-keyed prefix cache (including the partial tail
      page, which is what makes CoW fire on the very next decode
      write); ``acquire_with_prefix`` maps a later matching prompt's
      cached pages read-only and reports how many prefill tokens that
      avoided;
    * a page whose refcount hits zero goes back to the free list and is
      *poisoned* until re-acquired: under strict mode every table the
      pool hands out is validated first, and a stale mapping raises
      ``strict.StalePageError`` instead of silently gathering rows a
      new owner may already be writing.

    Donation discipline is the contiguous pool's: ``caches`` poisons
    between ``mark_donated`` and ``adopt``, ``allocations`` stays at 1
    for the pool's lifetime (pages are *mapped*, never reallocated — the
    invariant is now bounded by pages, not slots)."""

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int, *,
                 page_size: int = 16, n_pages: int | None = None,
                 window: int | None = None, dtype=None, mesh=None,
                 prefix_cache: bool = True):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        window = window if window is not None else cfg.attn_window
        if window is not None and window > 0:
            raise ValueError(
                "PagedKVCachePool does not support ring-buffer (SWA) "
                "windows: a wrapped write would straddle pages shared "
                "read-only; use SlotKVCachePool for windowed archs")
        self._donated_to: str | None = None
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.window = None
        self.page_size = ps = max(int(page_size), 1)
        self.pages_per_slot = -(-max_len // ps)          # ceil
        # Default sizing: full residency for every slot, plus one
        # pages-per-slot worth of slack so prefix-cache entries survive
        # a full pool, plus the scratch page.
        self.n_pages = int(n_pages) if n_pages is not None else \
            1 + n_slots * self.pages_per_slot + self.pages_per_slot
        if self.n_pages < 2:
            raise ValueError(f"n_pages must be >= 2, got {self.n_pages}")
        self.kinds = tuple(cfg.layer_kinds())
        # One init_caches call fixes every layer's geometry; attention
        # entries are then re-laid as flat page stores (the transient
        # slot-major attn arrays are dropped on the spot).
        tmp = lm.init_caches(cfg, n_slots, max_len, window=None,
                             dtype=dtype)

        def _page_store(c):
            if c is None:
                return None
            n, h, _, d = c.shape    # (n_slots, H_kv, S, D)
            return jnp.zeros((self.n_pages * ps, h, d), c.dtype)

        self.caches = [
            _tree_map(_page_store, c) if kind in _ATTN_KINDS else c
            for kind, c in zip(self.kinds, tmp, strict=True)]
        del tmp
        self.mesh = mesh
        self.shardings = None
        if mesh is not None:
            from ..launch import sharding as sharding_lib

            self.shardings = sharding_lib.to_shardings(
                mesh, sharding_lib.paged_cache_specs(
                    cfg, mesh, n_slots, max_len))
            self.caches = jax.device_put(self.caches, self.shardings)
        self._layout_sig = _layout(self.caches)
        self._reset = _StateReset(self.kinds, self.caches, self.shardings)
        self.allocations = 1            # init_caches calls ever made
        self._free = list(range(n_slots - 1, -1, -1))
        self.positions = [0] * n_slots
        self.owner: list[Any] = [None] * n_slots
        # Page bookkeeping (all host-side).  Page 0 is scratch: refcount
        # pinned at 1 so it can never be allocated or freed.
        self.page_refs = [0] * self.n_pages
        self.page_refs[0] = 1
        self._free_pages = list(range(self.n_pages - 1, 0, -1))
        self._poisoned: set[int] = set()
        self.page_tables = [[0] * self.pages_per_slot
                            for _ in range(n_slots)]
        # Prefix cache: token-tuple → page entry (see _PrefixEntry).
        # ``_partials[key]`` lists the lengths of registered partial
        # tails extending the full-block chain ``key``.  Reuse is only
        # sound when *every* layer keys its state by position: a
        # recurrent layer's state is a running reduction over all
        # tokens, so skipping a reused prefix would skip its updates —
        # mixed archs keep paged layout but always prefill in full.
        self.prefix_cache = bool(prefix_cache) and \
            all(k in _ATTN_KINDS for k in self.kinds)
        self._prefix: dict[tuple, _PrefixEntry] = {}
        self._partials: dict[tuple, list[int]] = {}
        self._lru = 0
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefill_tokens_avoided = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        self._write_jits: dict[int, Any] = {}
        self._read_jit = None
        self._copy_jit = None

    # -- donation poison (strict mode) ---------------------------------------
    @property
    def caches(self):
        """The per-layer cache pytree (page stores for attention,
        slot-major state for recurrent layers).  Poisons between
        ``mark_donated`` and ``adopt`` exactly like the slot pool."""
        if self._donated_to is not None and strict.enabled():
            raise strict.DonatedCacheError(self._donated_to)
        return self._caches

    @caches.setter
    def caches(self, tree) -> None:
        self._caches = tree
        self._donated_to = None

    def mark_donated(self, consumer: str) -> None:
        self._donated_to = consumer

    def adopt(self, new_caches) -> None:
        """Rebind after a donating dispatch (see
        ``SlotKVCachePool.adopt``); raises ``CacheLayoutError`` on a
        tree built for different page geometry."""
        if _layout(new_caches) != self._layout_sig:
            raise CacheLayoutError(
                f"adopted cache tree does not match the paged pool "
                f"layout (n_pages={self.n_pages}, "
                f"page_size={self.page_size}, arch={self.cfg.name})")
        self.caches = new_caches

    # -- slot lifecycle ------------------------------------------------------
    def free_slots(self) -> int:
        return len(self._free)

    def free_pages(self) -> int:
        return len(self._free_pages)

    def pages_in_use(self) -> int:
        """Pages currently referenced (tables + prefix cache), scratch
        excluded."""
        return self.n_pages - 1 - len(self._free_pages)

    def acquire(self, owner: Any = None) -> int | None:
        """Claim a slot (no prefix lookup); None when exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.owner[slot] = owner
        self.positions[slot] = 0
        self._zero_slot_states(slot)
        return slot

    def acquire_with_prefix(self, owner: Any,
                            prompt) -> tuple[int | None, int]:
        """Claim a slot and map any cached prefix of ``prompt`` into its
        page table read-only.  Returns ``(slot, reused)`` where
        ``reused`` is the number of prompt tokens whose KV is already
        resident (the caller starts prefill there).  Reuse is capped at
        ``len(prompt) - 1``: the last prompt token is always recomputed
        so the first-token logits exist."""
        slot = self.acquire(owner)
        if slot is None:
            return None, 0
        if not self.prefix_cache or prompt is None:
            return slot, 0
        toks = tuple(int(t) for t in prompt)
        self.prefix_lookups += 1
        ps = self.page_size
        cap = len(toks) - 1
        reused, j = 0, 0
        key: tuple = ()
        while (j + 1) * ps <= cap:
            cand = toks[:(j + 1) * ps]
            entry = self._prefix.get(cand)
            if entry is None:
                break
            self._map_shared(slot, j, entry)
            key, reused = cand, (j + 1) * ps
            j += 1
        # Longest registered partial tail extending the matched chain
        # (this is the block whose later extension is what CoW protects).
        for plen in sorted(self._partials.get(key, ()), reverse=True):
            # A tail page reaching past ``cap`` is still mappable — its
            # content for positions < cap is identical by key match; the
            # recomputed last token CoW-copies it before any write.
            if min(plen, cap) <= reused:
                continue
            entry = self._prefix.get(toks[:plen])
            if entry is not None:
                self._map_shared(slot, j, entry)
                reused = min(plen, cap)
                break
        if reused:
            self.prefix_hits += 1
            self.prefill_tokens_avoided += reused
            self.positions[slot] = reused
        return slot, reused

    def _map_shared(self, slot: int, j: int, entry: _PrefixEntry) -> None:
        self.page_refs[entry.page] += 1
        self.page_tables[slot][j] = entry.page
        self._lru += 1
        entry.last_used = self._lru

    def fork(self, src: int, owner: Any = None) -> int | None:
        """Clone ``src`` into a fresh slot sharing every mapped page
        copy-on-write (refcounts bumped; first divergent write on either
        side triggers the copy).  Recurrent state is copied eagerly — it
        has no page indirection to share.  None when no slot is free."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.owner[slot] = owner
        self.positions[slot] = self.positions[src]
        for j, pid in enumerate(self.page_tables[src]):
            if pid:
                self.page_refs[pid] += 1
            self.page_tables[slot][j] = pid

        def copy_row(kind: str, cache):
            if cache is None or kind in _ATTN_KINDS:
                return cache
            return _tree_map(_maybe(lambda x: x.at[slot].set(x[src])),
                             cache)

        self.caches = [copy_row(kind, c) for kind, c in
                       zip(self.kinds, self.caches, strict=True)]
        return slot

    def release(self, slot: int) -> None:
        if self.owner[slot] is None and slot in self._free:
            raise ValueError(f"slot {slot} double-released")
        self.owner[slot] = None
        self.positions[slot] = 0
        for j, pid in enumerate(self.page_tables[slot]):
            if pid:
                self._unref_page(pid)
            self.page_tables[slot][j] = 0
        self._free.append(slot)

    def _zero_slot_states(self, slot: int) -> None:
        self.caches = self._reset(self.caches, slot)

    # -- page allocation / refcounts -----------------------------------------
    def _unref_page(self, pid: int) -> None:
        self.page_refs[pid] -= 1
        if self.page_refs[pid] <= 0:
            # Freed: poisoned until re-acquired (strict.StalePageError).
            self.page_refs[pid] = 0
            self._poisoned.add(pid)
            self._free_pages.append(pid)

    def _alloc_page(self) -> int:
        if not self._free_pages:
            self._evict_for_space()
        if not self._free_pages:
            raise PagePoolExhaustedError(self.n_pages)
        pid = self._free_pages.pop()
        self._poisoned.discard(pid)
        self.page_refs[pid] = 1
        return pid

    def _evict_for_space(self) -> None:
        """Drop least-recently-used prefix entries whose page nobody
        else references until a page frees up (called only when the
        free list is empty)."""
        evictable = sorted(
            (e.last_used, key) for key, e in self._prefix.items()
            if self.page_refs[e.page] == 1)
        for _, key in evictable:
            self._drop_entry(key)
            self.prefix_evictions += 1
            if self._free_pages:
                return

    def _drop_entry(self, key: tuple) -> None:
        entry = self._prefix.pop(key)
        if not entry.full:
            chain = key[:len(key) - entry.n_tokens]
            lens = self._partials.get(chain)
            if lens is not None:
                lens.remove(len(key))
                if not lens:
                    del self._partials[chain]
        self._unref_page(entry.page)

    def ensure_writable(self, slot: int, lo: int, hi: int) -> bool:
        """Make pages covering ``[lo, hi)`` of ``slot`` exclusively
        writable: allocate unmapped entries, copy-on-write shared ones.
        Must precede every device write (prefill scatter, decode
        dispatch).  Returns True when the page table changed (the caller
        re-uploads it)."""
        if hi <= lo:
            return False
        if hi > self.max_len:
            raise SlotOverflowError(slot, hi, self.max_len)
        ps = self.page_size
        table = self.page_tables[slot]
        changed = False
        for j in range(lo // ps, -(-hi // ps)):
            pid = table[j]
            if pid == 0:
                table[j] = self._alloc_page()
                changed = True
            elif self.page_refs[pid] > 1:
                fresh = self._alloc_page()
                self._copy_page(pid, fresh)
                self.page_refs[pid] -= 1
                table[j] = fresh
                self.cow_copies += 1
                changed = True
        return changed

    def _copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page in every attention layer's store
        (donated jit, traced page ids: one compile total)."""
        if self._copy_jit is None:
            ps, kinds = self.page_size, self.kinds

            def copy(caches, src_s, dst_s):
                def per_layer(kind, c):
                    if kind not in _ATTN_KINDS or c is None:
                        return c
                    return _tree_map(_maybe(lambda x: (
                        jax.lax.dynamic_update_slice(
                            x, jax.lax.dynamic_slice(
                                x, (src_s * ps, 0, 0),
                                (ps,) + x.shape[1:]),
                            (dst_s * ps, 0, 0)))), c)

                return [per_layer(kind, c)
                        for kind, c in zip(kinds, caches, strict=True)]

            self._copy_jit = jax.jit(copy, donate_argnums=0,
                                     out_shardings=self.shardings)
        self.caches = self._copy_jit(self.caches, jnp.int32(src),
                                     jnp.int32(dst))

    # -- prefix cache --------------------------------------------------------
    def register_prefix(self, slot: int, tokens) -> int:
        """Publish ``slot``'s freshly-prefilled prompt pages into the
        prefix cache (full blocks plus the partial tail).  The cache
        takes a reference on each page, so the slot's own next write
        into the tail page copy-on-writes it — cached content is never
        mutated.  Returns the number of new entries."""
        if not self.prefix_cache:
            return 0
        toks = tuple(int(t) for t in tokens)
        ps = self.page_size
        table = self.page_tables[slot]
        added = 0
        for j in range(-(-len(toks) // ps)):
            end = min((j + 1) * ps, len(toks))
            key = toks[:end]
            if key in self._prefix:
                self._lru += 1
                self._prefix[key].last_used = self._lru
                continue
            pid = table[j]
            if pid == 0:        # nothing prefilled here (shouldn't be)
                continue
            n_tok = end - j * ps
            full = n_tok == ps
            self.page_refs[pid] += 1
            self._lru += 1
            self._prefix[key] = _PrefixEntry(
                page=pid, n_tokens=n_tok, full=full, last_used=self._lru)
            if not full:
                self._partials.setdefault(toks[:j * ps], []).append(end)
            added += 1
        return added

    # -- stale-page validation (strict mode) ---------------------------------
    def _validate_tables(self) -> None:
        """Raise ``strict.StalePageError`` if any live slot's table maps
        a freed page — the paged analogue of the donated-buffer read."""
        for slot in range(self.n_slots):
            if self.owner[slot] is None:
                continue
            for pid in self.page_tables[slot]:
                if pid and (pid in self._poisoned
                            or self.page_refs[pid] <= 0):
                    raise strict.StalePageError(slot, pid)

    # -- slot I/O ------------------------------------------------------------
    def _slot_indices(self, slot: int) -> np.ndarray:
        """Flat page-store row index of every logical position of
        ``slot`` (length ``max_len``; unmapped entries resolve to the
        scratch page, whose garbage the attention mask zeroes out)."""
        ps = self.page_size
        table = np.fromiter(self.page_tables[slot], np.int32)
        idx = (table[:, None] * ps
               + np.arange(ps, dtype=np.int32)[None, :]).reshape(-1)
        return idx[:self.max_len]

    def page_table_array(self) -> jax.Array:
        """Every slot's page table as an (n_slots, pages_per_slot) int32
        device array — the fused decode step's gather indirection.
        Validated against freed pages first (strict mode)."""
        if strict.enabled():
            self._validate_tables()
        return jnp.asarray(self.page_tables, jnp.int32)

    def read_slot(self, slot: int):
        """The slot's caches as a contiguous batch-of-1 pytree: pages
        gathered through the table for attention layers (shape-identical
        to ``SlotKVCachePool.read_slot``, so the same compiled prefill
        serves both pools), slot rows for recurrent state."""
        if strict.enabled():
            self._validate_tables()
        if self._read_jit is None:
            kinds = self.kinds

            def read(caches, idx, slot_s):
                def per_layer(kind, c):
                    if c is None:
                        return None
                    if kind in _ATTN_KINDS:
                        return _tree_map(_maybe(
                            lambda x: x[idx].transpose(1, 0, 2)[None]), c)
                    return _tree_map(_maybe(
                        lambda x: jax.lax.dynamic_slice(
                            x, (slot_s,) + (0,) * (x.ndim - 1),
                            (1,) + x.shape[1:])), c)

                return [per_layer(kind, c)
                        for kind, c in zip(kinds, caches, strict=True)]

            self._read_jit = jax.jit(read)
        return self._read_jit(self.caches,
                              jnp.asarray(self._slot_indices(slot)),
                              jnp.int32(slot))

    def write_slot(self, slot: int, row_caches, lo: int = 0,
                   hi: int | None = None) -> None:
        """Scatter ``row_caches`` (a batch-of-1 contiguous view, as
        returned by the prefill step) back into the slot's pages for
        logical positions ``[lo, hi)``; recurrent state is written
        whole.  The caller must have ``ensure_writable``-d the range —
        shared prefix pages outside it are never touched.  Donated jit,
        one compile per distinct segment length (the prefill bucket
        set)."""
        hi = self.max_len if hi is None else hi
        seg = hi - lo
        if seg <= 0:
            return
        if hi > self.max_len:
            raise SlotOverflowError(slot, hi, self.max_len)
        fn = self._write_jits.get(seg)
        if fn is None:
            kinds = self.kinds

            def write(caches, row, idx, start_s, slot_s):
                def per_layer(kind, c, r):
                    if c is None:
                        return None
                    if kind in _ATTN_KINDS:
                        def scatter(x, n):
                            piece = jax.lax.dynamic_slice_in_dim(
                                n[0], start_s, seg, axis=1)
                            return x.at[idx].set(
                                piece.transpose(1, 0, 2).astype(x.dtype))

                        return jax.tree.map(scatter, c, r,
                                            is_leaf=lambda x: x is None)
                    return jax.tree.map(
                        lambda x, n: x if x is None else
                        jax.lax.dynamic_update_slice(
                            x, n.astype(x.dtype),
                            (slot_s,) + (0,) * (x.ndim - 1)),
                        c, r, is_leaf=lambda x: x is None)

                return [per_layer(kind, c, r) for kind, c, r in
                        zip(kinds, caches, row, strict=True)]

            fn = jax.jit(write, donate_argnums=0,
                         out_shardings=self.shardings)
            self._write_jits[seg] = fn
        idx = self._slot_indices(slot)[lo:hi]
        self.caches = fn(self.caches, row_caches, jnp.asarray(idx),
                         jnp.int32(lo), jnp.int32(slot))

    def advance(self, slot: int, n: int) -> int:
        """Advance the slot's position (see ``SlotKVCachePool.advance``)."""
        if n < 0:
            raise ValueError(f"negative advance: {n}")
        pos = self.positions[slot] + n
        if pos > self.max_len:
            raise SlotOverflowError(slot, pos, self.max_len)
        self.positions[slot] = pos
        return pos

    def rollback(self, slot: int, n: int) -> int:
        """Roll ``slot``'s position back by ``n`` tokens — page-refcount
        safe by construction: the dispatch's ``ensure_writable`` covered
        the whole speculative window before any device write, so every
        page touching the rolled-back range is exclusively owned by this
        slot (refcount 1) and *stays mapped* — its stale rows are dead
        storage the causal mask never reads and the next decode write
        overwrites.  No page is freed or unmapped here: unmapping would
        strand the window's allocation work, and freeing a page that a
        concurrent prefix registration might share is exactly the
        use-after-free class this pool's strict-mode validation hunts.
        Raises if a shared page covers the range (the caller skipped
        ``ensure_writable`` — a hard bug, not a recoverable state)."""
        if n < 0:
            raise ValueError(f"negative rollback: {n}")
        pos = self.positions[slot]
        if n > pos:
            raise ValueError(
                f"rollback of {n} past slot {slot}'s position {pos}")
        ps = self.page_size
        table = self.page_tables[slot]
        for j in range((pos - n) // ps, -(-pos // ps)):
            pid = table[j]
            if pid and self.page_refs[pid] > 1:
                raise ValueError(
                    f"rollback range [{pos - n}, {pos}) of slot {slot} "
                    f"touches shared page {pid} (refcount "
                    f"{self.page_refs[pid]}): the dispatch skipped "
                    f"ensure_writable over its speculative window")
        self.positions[slot] = pos - n
        return self.positions[slot]

    def positions_array(self) -> jax.Array:
        return jnp.asarray(
            [min(p, self.max_len - 1) for p in self.positions], jnp.int32)

    def prefix_stats(self) -> dict:
        """Prefix-cache effectiveness counters (what BENCH_load.json
        reports)."""
        return {
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": self.prefix_hits / self.prefix_lookups
            if self.prefix_lookups else 0.0,
            "prefill_tokens_avoided": self.prefill_tokens_avoided,
            "cow_copies": self.cow_copies,
            "prefix_evictions": self.prefix_evictions,
            "prefix_entries": len(self._prefix),
            "pages_in_use": self.pages_in_use(),
            "n_pages": self.n_pages,
            "page_size": self.page_size,
        }

    def reset_prefix_stats(self) -> None:
        """Zero the effectiveness counters (cached entries stay live) —
        the load harness calls this after its untimed prewarm so the
        reported hit rate covers only the replayed trace."""
        self.prefix_lookups = self.prefix_hits = 0
        self.prefill_tokens_avoided = 0
        self.cow_copies = self.prefix_evictions = 0
