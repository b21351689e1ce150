"""Pass 2 — the KV-write aliasing pass.

Proves, from the jaxpr alone, that every write into the paged KV pool is
*guarded*: its destination index is computed from the block-table gather
(so a row can only write its own pages) **and** carries the trash-page
route (``jnp.where(valid, page, 0)`` — invalid positions land on page 0,
never on live KV).  Runs on the unit updates
(:func:`repro.models.attention.paged_kv_update` /
:func:`flat_paged_kv_update`) and on the full fused step jaxprs, where
the pool scatters live inside the layer ``scan``.

The complementary *dynamic* half — a write into a page with ``ref > 1``
is impossible without a preceding ``cow()`` — cannot be read off a jaxpr
(refcounts are host state), so it is split into
:func:`check_pool_consistency`, a ledger audit run after traffic: every
live page's refcount must equal the number of sequences holding it plus
its prefix-cache node (if any), the free list must be disjoint from live
pages, and the trash page must never be held.  Together with the
``REPRO_SANITIZE`` runtime hook (``analysis.sanitize``, which asserts
``ref == 1`` at the moment of each in-place write) this closes the CoW
contract end to end.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_tools import TRASH_LABEL, TaintWalker, WriteSite
from repro.analysis.report import Finding

__all__ = ["taint_step", "lint_kv_writes", "lint_engine_aliasing",
           "check_pool_consistency"]

_PASS = "kv-aliasing"

# labels a guarded pool write's *indices* must carry: provenance through
# the block-table gather, and the validity-predicated zero route
REQUIRED_INDEX_LABELS = frozenset({"block_tables", TRASH_LABEL})


def _leaf_labels(args: Sequence, role_of_arg: dict) -> List[Optional[Set[str]]]:
    """Per-flat-leaf label sets for a positional arg tuple.
    ``role_of_arg``: arg position -> role string, or callable(path_str) ->
    role (for pytree args like the cache dict where only ``*_pages``
    leaves are the pool)."""
    labels = []
    leaves, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    for path, _leaf in leaves:
        idx = path[0].idx
        role = role_of_arg.get(idx)
        if callable(role):
            role = role(jax.tree_util.keystr(path))
        labels.append({role} if role else set())
    return labels


def taint_step(fn, abstract_args: tuple, role_of_arg: dict) -> TaintWalker:
    """Trace ``fn`` at the given ``ShapeDtypeStruct`` args and taint-walk
    the closed jaxpr with the given arg roles."""
    closed = jax.make_jaxpr(fn)(*abstract_args)
    return TaintWalker().run(closed, _leaf_labels(abstract_args, role_of_arg))


def lint_kv_writes(walker: TaintWalker, family: str,
                   *, expect_writes: int = 1) -> List[Finding]:
    """Judge the walker's recorded write sites against the pool contract."""
    f: List[Finding] = []
    pool_writes = [w for w in walker.write_sites if w.writes("pages")]
    if len(pool_writes) < expect_writes:
        f.append(Finding(
            _PASS, "missing-write", family,
            f"found {len(pool_writes)} pool write(s), expected >= "
            f"{expect_writes} — either the analyzer lost the pages label "
            f"or a write was restructured past the walker; the pass is "
            f"only meaningful when it sees the writes it judges"))
    for w in pool_writes:
        missing = REQUIRED_INDEX_LABELS - w.index_labels
        if missing:
            f.append(Finding(
                _PASS, "unguarded-write", f"{w.prim} @ {w.where}",
                f"{family}: pool write indices lack {sorted(missing)} "
                f"(have {sorted(w.index_labels)}; jaxpr path {w.path}) — "
                f"every KV write must be addressed through the block-table "
                f"gather and route invalid rows to trash page 0",
                detail={"labels": sorted(w.index_labels)}))
        if w.mode and "PROMISE_IN_BOUNDS" in w.mode:
            f.append(Finding(
                _PASS, "unsafe-scatter-mode", f"{w.prim} @ {w.where}",
                f"{family}: pool scatter compiled with PROMISE_IN_BOUNDS — "
                f"an out-of-ladder index would write out of bounds instead "
                f"of dropping; pool writes must stay FILL_OR_DROP"))
    return f


def _attention_unit_walkers(engine):
    """Taint-walk the unit KV-update functions at this engine's shapes."""
    from repro.models import attention
    from repro.models.transformer import pattern_period
    model = engine.model
    cfg = model.cfg
    S = jax.ShapeDtypeStruct
    i32, dt = jnp.int32, model.compute_dtype
    pool = engine.pool
    lay = attention.KVPageLayout(cfg.n_kv_heads, cfg.d_head, pool.page_tokens)
    groups = cfg.n_layers // pattern_period(cfg)
    shp = (groups, pool.num_pages) + lay.page_shape
    cache = {"k_pages": S(shp, dt), "v_pages": S(shp, dt)}
    b, mp = engine.slots, engine.max_pages
    out = []
    s = engine.chunk_tokens or engine._bucket
    kv = S((b, s, cfg.n_kv_heads, cfg.d_head), dt)
    out.append(("paged_kv_update", taint_step(
        lambda c, k, v, bt, ln, nc, g: attention.paged_kv_update(
            c, k, v, layer=g, block_tables=bt, lens=ln, new_counts=nc),
        (cache, kv, kv, S((b, mp), i32), S((b,), i32), S((b,), i32),
         S((), i32)),
        {0: lambda p: "pages" if "_pages" in p else None,
         3: "block_tables", 4: "validity", 5: "validity"}), 2))
    if engine.chunked:
        w = engine._flat_shapes()[0]
        kvf = S((1, w, cfg.n_kv_heads, cfg.d_head), dt)
        out.append(("flat_paged_kv_update", taint_step(
            lambda c, k, v, bt, r, q, g: attention.flat_paged_kv_update(
                c, k, v, layer=g, block_tables=bt, row_ids=r, q_pos=q),
            (cache, kvf, kvf, S((b, mp), i32), S((w,), i32), S((w,), i32),
             S((), i32)),
            {0: lambda p: "pages" if "_pages" in p else None,
             3: "block_tables", 4: "validity", 5: "validity"}), 2))
    return out


def lint_engine_aliasing(engine, label: str = "engine") -> List[Finding]:
    """Run pass 2 on one engine: the unit updates, plus one full fused-step
    jaxpr per active step family (widest shape — the scatters are identical
    across ladder widths, so one representative keeps the pass fast)."""
    f: List[Finding] = []
    model = engine.model
    here = f"{label} ({model.cfg.name})"
    for name, walker, expect in _attention_unit_walkers(engine):
        f.extend(lint_kv_writes(walker, f"{here} {name}",
                                expect_writes=expect))

    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        engine.params)
    caches = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        engine.caches)
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    b, mp = engine.slots, engine.max_pages
    cache_role = {1: lambda p: "pages" if "_pages" in p else None}
    # one pool K + one pool V scatter per layer scan body = 2 sites
    if engine.chunked:
        w = engine._flat_shapes()[0]
        walker = taint_step(
            model.flat_decode_step,
            (params, caches, S((1, w), i32), S((b, mp), i32),
             S((w,), i32), S((w,), i32), S((b,), i32)),
            {**cache_role, 3: "block_tables", 4: "validity", 5: "validity"})
        f.extend(lint_kv_writes(walker, f"{here} flat_decode_step[1,{w}]",
                                expect_writes=2))
    else:
        walker = taint_step(
            model.paged_decode_step,
            (params, caches, S((b, 1), i32), S((b, mp), i32),
             S((b,), i32), S((b,), i32), None),
            {**cache_role, 3: "block_tables", 4: "validity", 5: "validity"})
        f.extend(lint_kv_writes(walker, f"{here} paged_decode_step[{b},1]",
                                expect_writes=2))
    return f


def check_pool_consistency(engine, label: str = "engine") -> List[Finding]:
    """Dynamic half of the aliasing contract: audit the pool ledger
    against its holders (live sequences + prefix-cache nodes), and the
    resilience contract's zero-leak-on-cancel: a retired rid — finished,
    cancelled, timed out, shed or quarantined — may not hold pages."""
    f: List[Finding] = []
    pool = engine.pool
    here = f"{label} pool"

    retired = getattr(engine, "_retired_rids", set())
    for seq in pool.sequences():
        if seq.owner in retired and seq.pages:
            f.append(Finding(
                _PASS, "retired-holds-pages", here,
                f"retired rid {seq.owner} still holds pages {seq.pages} — "
                f"zero-leak-on-cancel violated (every retirement path must "
                f"release the block table)"))
    ledger = pool.ledger()
    refs, free = ledger["refs"], ledger["free"]

    live_and_free = set(refs) & set(free)
    if live_and_free:
        f.append(Finding(_PASS, "ledger-free-live", here,
                         f"pages {sorted(live_and_free)} are on the free "
                         f"list while refcounted live — the next alloc "
                         f"would hand one page to two requests"))
    if 0 in refs or 0 in free:
        f.append(Finding(_PASS, "ledger-trash", here,
                         "trash page 0 appears in the allocator ledger — "
                         "it must never be allocated or freed"))
    for p, r in sorted(refs.items()):
        if r < 1:
            f.append(Finding(_PASS, "ledger-refcount", here,
                             f"page {p} live with ref={r}"))

    held: dict = {}
    for seq in pool.sequences():
        for p in seq.pages:
            held[p] = held.get(p, 0) + 1
    cached = set()
    if engine.prefix_cache is not None:
        cached = set(engine.prefix_cache.pages())
    for p in sorted(set(held) | cached | set(refs)):
        want = held.get(p, 0) + (1 if p in cached else 0)
        have = refs.get(p, 0)
        if want != have:
            f.append(Finding(
                _PASS, "ledger-mismatch", here,
                f"page {p}: ref={have} but held by {held.get(p, 0)} "
                f"sequence(s) (requests {pool.holders(p)}) "
                f"{'+ prefix cache ' if p in cached else ''}— a stale "
                f"refcount makes CoW-before-write undecidable"))
    return f
