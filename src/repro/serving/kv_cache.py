"""Paged, layout-aware KV cache for continuous-batching serving.

The decode KV cache is a pool of fixed-size **pages** shared by all live
requests; each request owns an ordered list of page ids (its *block table*)
covering logical positions ``0 .. len-1``.  Admitting a request allocates
pages for its *prompt only*; each decode step grows the block table
incrementally (:meth:`SequencePages.ensure`), and finishing a request
returns its pages — sequences of different lengths coexist without padding
the cache to a common length, and pool capacity is consumed by tokens that
actually exist rather than by reserved lifetimes (the scheduler handles
exhaustion by preempting, see :mod:`repro.serving.scheduler`).

The allocator tracks a **refcount** per live page id (PR 5): a page may be
shared byte-for-byte by several requests and by the prefix cache
(:mod:`repro.serving.prefix_cache`), ``free`` drops one reference, and the
page returns to the free list only at refcount zero.  A free of a page with
no outstanding references — which would eventually hand one page to two
requests and silently cross their KV streams — still fails loudly at the
``free`` call, shared pages included.

Sharing rests on three invariants, spelled out here because every layer of
the serving stack leans on them:

  - **pages are immutable once full** — the paged step only ever writes
    positions ``lens .. lens + new_counts - 1``, so a page whose every
    token is committed is never touched again (truncation is the one
    exception, handled next); only such full pages enter the prefix cache;
  - **copy-on-write before any in-place write** — a partially-filled page
    about to be written (the admission cursor landing mid-page on a
    fully-cached prompt, or a speculative rollback truncating into a kept
    tail page) must be private first: :meth:`PagedKVPool.cow` allocates a
    fresh page, device-copies the contents, and swaps it into the block
    table, so no shared page is ever written in place;
  - **cache keys include the layout** — pages are whole ``m_r``-aligned
    microkernel tiles, so the prefix-cache hash chain is rooted in
    ``(m_r, page_tokens)`` and a layout change can never alias stale KV.

The page size is derived from the active :class:`~repro.core.layout.
PackedLayout`: ``page_tokens = round_up(requested, m_r)``, so a page always
holds a whole number of microkernel M-tiles and decode attention reads
tiles the mmt4d kernels can consume directly — the paper's amortized
prepacking argument (§4.1) extended from weights to KV pages.  Chunked
prefill (``Engine(chunk_tokens=...)``) keeps the same alignment on the
write side: chunk sizes are rounded up to ``m_r`` too, so every chunk
lands as whole tiles and a paused prefill's held pages stay valid KV
(positions ``0..cursor-1``) across a displacement — only ``release()``
invalidates them.

Device-side pool arrays live inside the engine's cache pytree
(``{"k_pages","v_pages"}: [G, P, Hkv, T/f, f*dh]``, built by
``transformer.init_paged_caches``): each page is head-major and
lane-dense, ``f = gcd(128 // dh, T)`` consecutive tokens of one kv head
side by side in a row of ``f*dh`` lanes (``f = 1`` at ``dh >= 128``), the
layout ``models.attention.KVPageLayout`` owns.  The flat step updates the
pool in place and the ragged-attention kernel reads it where it lies, by
layer index and page id.  This module owns the host-side
bookkeeping (allocator, per-request block tables) plus the pytree helpers
that separate shared page pools from per-slot recurrent state.

Page 0 is reserved as the **trash page**: padded prefill positions and
inactive decode slots scatter their (masked-out) K/V there, so a fixed-shape
step can never corrupt a live request.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layout import PackedLayout, ceil_div, round_up
from repro.obs.telemetry import NULL as _NULL_OBS

__all__ = ["PoolError", "OutOfPages", "PagedKVPool", "SequencePages",
           "copy_pages", "fresh_slot_states", "prefill_view", "merge_slot",
           "map_slot_states"]


class PoolError(RuntimeError):
    """An allocator contract violation (double-free, foreign free, sharing
    a dead page) or allocation failure.  Raised explicitly — unlike the
    ``assert`` statements it replaced, the check survives ``python -O``,
    because a refcount bug in a production drain silently crossing two
    requests' KV streams is exactly the failure mode optimized runs must
    still catch.  The message carries the diagnostic payload (page id,
    refcount, owner rids via :meth:`PagedKVPool.holders`)."""


class OutOfPages(PoolError):
    """The pool cannot satisfy an allocation (admission must wait)."""


class PagedKVPool:
    """Host-side refcounting page allocator for the device page pool.

    ``page_tokens`` is rounded up to a multiple of the layout's ``m_r`` so
    page boundaries coincide with packed-tile boundaries.  Page 0 is the
    trash page (``reserved_pages``) and is never handed out — every
    capacity question should use :attr:`usable_pages`, not ``num_pages``.

    Sharing (PR 5): :meth:`alloc` hands out a page at refcount 1,
    :meth:`share` adds a reference (a prefix-cache hit handing the page to
    a second request, or the cache registering its own claim), and
    :meth:`free` drops one — the page returns to the free list only at
    refcount zero.  A page with refcount > 1 is **read-only** (see the
    module docstring); :meth:`cow` is the copy-on-write split that makes a
    shared page writable again.  Two optional hooks integrate the prefix
    cache without the allocator knowing its structure:

      - ``reclaimer``: an object with ``evictable() -> int`` and
        ``evict(n) -> int``; :meth:`alloc` calls ``evict(1)`` on an empty
        free list before raising, so cache-held pages are always
        reclaimable under pool pressure — the scheduler's "a solo request
        fits" termination invariant survives the cache holding pages;
      - ``page_copier``: ``fn(src, dst)`` performing the device-side page
        copy :meth:`cow` needs (the engine owns the cache pytree).
    """

    def __init__(self, num_pages: int, page_tokens: int,
                 layout: Optional[PackedLayout] = None):
        if layout is not None:
            page_tokens = round_up(page_tokens, layout.m_r)
        assert num_pages >= 2, "need at least the trash page + one real page"
        self.num_pages = num_pages
        self.page_tokens = page_tokens
        self.reserved_pages = 1          # page 0: the trash page
        # LIFO free list → recently-freed (cache-warm) pages are reused first
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}   # page id -> outstanding references
        self._seqs: "weakref.WeakSet[SequencePages]" = weakref.WeakSet()
        # allocator stats (cumulative; peak_used drives pool-sizing decisions)
        self.total_allocs = 0
        self.total_shares = 0
        self.total_frees = 0
        self.peak_used = 0
        self.cow_copies = 0
        self.reclaimer = None            # prefix cache, when enabled
        self.page_copier = None          # engine-installed device page copy
        self.obs = _NULL_OBS             # telemetry; engine swaps in a live one

    @property
    def usable_pages(self) -> int:
        """Pages that can ever hold live KV (reserved pages excluded)."""
        return self.num_pages - self.reserved_pages

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.usable_pages - len(self._free)

    @property
    def num_available(self) -> int:
        """Free pages plus cache-held pages reclaimable on demand — the
        number an admission/growth decision may count on, since
        :meth:`alloc` evicts from the cache before giving up."""
        extra = self.reclaimer.evictable() if self.reclaimer is not None else 0
        return len(self._free) + extra

    def pages_for(self, tokens: int) -> int:
        return ceil_div(max(0, tokens), self.page_tokens)

    def can_fit(self, tokens: int) -> bool:
        return self.pages_for(tokens) <= self.num_available

    def ref(self, page: int) -> int:
        """Outstanding references to ``page`` (0 = free)."""
        return self._ref.get(page, 0)

    def is_shared(self, page: int) -> bool:
        return self._ref.get(page, 0) > 1

    def sequences(self) -> List["SequencePages"]:
        """Live block tables registered with this pool (weakly held)."""
        return [s for s in self._seqs]

    def holders(self, page: int) -> List:
        """Owner ids (request ids, where known) of the live sequences whose
        block table holds ``page`` — the context a double-free / sanitizer
        diagnostic needs in the middle of a long drain."""
        return sorted({s.owner for s in self._seqs
                       if s.owner is not None and page in s.pages})

    def ledger(self) -> dict:
        """Read-only snapshot of the allocator state for external audits
        (:func:`repro.analysis.aliasing.check_pool_consistency`): the
        refcount map and the free list.  Copies — mutating the allocator
        stays the privilege of this module (enforced by the AST lint's
        allocator-privacy rule)."""
        return {"refs": dict(self._ref), "free": list(self._free)}

    def alloc(self) -> int:
        if not self._free and self.reclaimer is not None:
            # LRU eviction under pool pressure: cached-but-unreferenced
            # pages are reclaimable, so a cache can never deadlock a drain
            self.reclaimer.evict(1)
        if not self._free:
            raise OutOfPages("KV pool exhausted")
        p = self._free.pop()
        self._ref[p] = 1
        self.total_allocs += 1
        self.peak_used = max(self.peak_used, self.num_used)
        return p

    def share(self, pages: Iterable[int]) -> None:
        """Add one reference to each page (it must be live).  The new
        holder sees the page read-only: shared pages are never written in
        place (:meth:`cow` first)."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise PoolError(
                    f"page {p} shared while not allocated (ref=0, holders: "
                    f"{self.holders(p) or 'none'}) — sharing a dead page "
                    f"would resurrect freed KV")
            self._ref[p] += 1
            self.total_shares += 1

    def free(self, pages: Iterable[int]) -> None:
        for p in pages:
            if not 0 < p < self.num_pages:
                raise PoolError(
                    f"page {p} freed outside the pool's usable range "
                    f"1..{self.num_pages - 1} (page 0 is the trash page)")
            if p not in self._ref:
                raise PoolError(
                    f"page {p} freed twice (or never allocated): ref="
                    f"{self._ref.get(p, 0)}, still held by requests "
                    f"{self.holders(p) or 'none'} — a double-free hands one "
                    f"page to two requests and crosses their KV")
            self._ref[p] -= 1
            self.total_frees += 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)

    def cow(self, seq: "SequencePages", idx: int) -> int:
        """Copy-on-write split of ``seq.pages[idx]``: if the page is
        shared, allocate a private copy (device contents copied via
        ``page_copier``), swap it into the block table, and drop the
        sequence's reference on the original — the other holders keep the
        immutable original, the sequence gets a writable twin.  No-op on an
        unshared page.  Returns the (possibly new) page id; may raise
        :class:`OutOfPages` like any allocation."""
        old = seq.pages[idx]
        if self._ref.get(old, 0) <= 1:
            return old
        new = self.alloc()
        if self.page_copier is not None:
            try:
                self.page_copier(old, new)
            except Exception as e:
                # a failed device copy must not leak the fresh page or
                # leave a half-copied page in the block table; surface a
                # typed error the caller can degrade on (prefix-cache
                # fallback re-prefills, the engine quarantines)
                self.free([new])
                raise PoolError(
                    f"page_copier failed copying page {old} -> {new} "
                    f"(holders of {old}: {self.holders(old) or 'none'}): "
                    f"{e}") from e
        seq.pages[idx] = new
        self.free([old])
        self.cow_copies += 1
        self.obs.cow()
        return new

    def stats(self) -> dict:
        """Allocator counters.  ``free_pages``/``usable_pages`` exclude the
        reserved trash page consistently (``num_pages`` does not), so cache
        occupancy ratios have a correct denominator; ``pages_per_request``
        is the mean block-table length over live sequences — the
        per-request share of the pool the aggregate counters hide."""
        live = [len(s.pages) for s in self._seqs if s.pages]
        return {"num_pages": self.num_pages, "page_tokens": self.page_tokens,
                "reserved_pages": self.reserved_pages,
                "usable_pages": self.usable_pages,
                "num_used": self.num_used, "num_free": self.num_free,
                "free_pages": self.num_free,
                "live_requests": len(live),
                "pages_per_request": (sum(live) / len(live)) if live else 0.0,
                "shared_pages": sum(1 for r in self._ref.values() if r > 1),
                "peak_used": self.peak_used, "total_allocs": self.total_allocs,
                "total_shares": self.total_shares,
                "total_frees": self.total_frees,
                "cow_copies": self.cow_copies}


@dataclasses.dataclass(eq=False)
class SequencePages:
    """One request's block table: ordered page ids covering 0..len-1.

    Entries may be *shared* (prefix-cache hits: refcount > 1, read-only —
    always a prefix of the table, since writes only ever append past the
    cached cursor); :meth:`release`/:meth:`truncate` drop references, not
    necessarily pages.  ``eq=False`` keeps identity hashing so the pool's
    weak registry (``stats()["pages_per_request"]``) can track live
    tables.  ``owner`` (the scheduler sets it to the request id) exists
    purely for diagnostics: allocator asserts and the runtime sanitizer
    name the requests holding a page via :meth:`PagedKVPool.holders`."""

    pool: PagedKVPool
    pages: List[int] = dataclasses.field(default_factory=list)
    owner: Optional[int] = None

    def __post_init__(self):
        self.pool._seqs.add(self)

    @property
    def capacity(self) -> int:
        return len(self.pages) * self.pool.page_tokens

    def ensure(self, tokens: int) -> None:
        """Grow the block table to cover ``tokens`` logical positions.
        All-or-nothing: a partial allocation is rolled back on failure."""
        start = len(self.pages)
        try:
            while self.capacity < tokens:
                self.pages.append(self.pool.alloc())
        except OutOfPages:
            self.pool.free(self.pages[start:])
            del self.pages[start:]
            raise

    def release(self) -> None:
        self.pool.free(self.pages)
        self.pages = []

    def truncate(self, tokens: int) -> int:
        """Shrink the block table to cover ``tokens`` logical positions,
        freeing whole trailing pages — the speculative-decode rollback:
        rejected draft positions past ``tokens`` either share the last kept
        page (their stale K/V is masked by ``lens + new_counts`` and
        overwritten by the next write at that position) or sit in trailing
        pages this returns to the pool.  Pages stay ``m_r``-aligned whole
        tiles — truncation only ever drops whole pages, never splits one —
        and the frees go through the pool's double-free accounting like any
        release (a shared trailing page just loses this table's reference).

        A **shared** page is never truncated into: when ``tokens`` lands
        mid-page and the kept tail page is shared, the next write at
        position ``tokens`` would mutate it in place under the other
        holders — so it is CoW-split first (the engine's normal flows keep
        shared pages behind the cursor and this never fires, but the
        rollback path must be safe against any caller).  Returns the number
        of page references dropped."""
        keep = self.pool.pages_for(tokens)
        dropped = self.pages[keep:]
        self.pool.free(dropped)
        del self.pages[keep:]
        if keep and tokens % self.pool.page_tokens:
            self.pool.cow(self, keep - 1)
        return len(dropped)

    def block_row(self, max_pages: int) -> np.ndarray:
        assert len(self.pages) <= max_pages, (len(self.pages), max_pages)
        row = np.zeros((max_pages,), np.int32)
        row[:len(self.pages)] = self.pages
        return row


# ---------------------------------------------------------------------------
# cache-pytree helpers: page pools are shared, recurrent state is per-slot
# ---------------------------------------------------------------------------

def map_slot_states(caches, fn):
    """Apply ``fn`` to per-slot recurrent leaves ([G, slots, ...]); pass the
    shared ``*_pages`` pool leaves through unchanged."""
    if isinstance(caches, dict):
        return {k: (v if k.endswith("_pages") else map_slot_states(v, fn))
                for k, v in caches.items()}
    return fn(caches)


def fresh_slot_states(caches):
    """A zeroed single-slot ([G, 1, ...]) recurrent-state tree matching
    ``caches`` — the state a request starts prefill from."""
    return map_slot_states(
        caches, lambda x: jnp.zeros(x.shape[:1] + (1,) + x.shape[2:], x.dtype))


def prefill_view(caches, fresh):
    """Single-slot cache view for prefill: shared pools from ``caches``,
    recurrent state from the zeroed single-slot tree ``fresh``."""
    if isinstance(caches, dict):
        return {k: (v if k.endswith("_pages") else prefill_view(v, fresh[k]))
                for k, v in caches.items()}
    return fresh


def _copy_pages(caches, src, dst):
    def rec(node):
        if isinstance(node, dict):
            return {k: (v.at[:, dst].set(v[:, src]) if k.endswith("_pages")
                        else rec(v))
                    for k, v in node.items()}
        return node
    return rec(caches)


copy_pages = jax.jit(_copy_pages, donate_argnums=(0,))
copy_pages.__doc__ = """Device-side page copy for copy-on-write splits:
duplicate page ``src``'s contents into ``dst`` in every ``*_pages`` pool
leaf ([G, P, Hkv, T/f, f*dh], every layer's whole page of every kv head;
page dim = axis 1, as ``KVPageLayout`` keeps it) of the cache pytree, leaving
per-slot recurrent state untouched.  One jitted program per cache
structure (the engine primes it at warmup), with the input donated so the
pool is updated in place rather than doubled."""


def merge_slot(caches, updated, slot: int):
    """Merge a prefill result back: pools are taken from ``updated`` (pages
    were written there), the [G, 1, ...] recurrent state is written into row
    ``slot`` of the full tree."""
    if isinstance(caches, dict):
        return {k: (updated[k] if k.endswith("_pages")
                    else merge_slot(v, updated[k], slot))
                for k, v in caches.items()}
    return jax.lax.dynamic_update_slice_in_dim(
        caches, updated.astype(caches.dtype), slot, axis=1)
