"""Opt-in runtime sanitizer for the pool write path (``REPRO_SANITIZE=1``).

Passes 1–2 prove the *static* halves of the layout/aliasing contracts;
this hook enforces the *dynamic* halves on every real step, at host
speed, before the device call runs:

* every valid write destination this step touches is a live, in-range,
  **private** page — ``ref == 1`` — so an in-place write to a shared
  page (the CoW-before-write violation) fails loudly at the step that
  would corrupt another request's KV, naming the page, its refcount and
  the owning request ids;
* no valid position routes to trash page 0 (that is a block table not
  covering the write window: tokens silently dropped);
* the step width is a member of the declared shape ladder and
  ``m_r``-aligned (tile-whole writes) — the runtime twin of the shape
  linter, catching widths produced by state mutated after construction;
* the resilience contract (PR 8): a retired rid — finished, cancelled,
  timed out, shed or quarantined — is never still scheduled and never
  still holds pages (zero-leak-on-cancel), and a quarantined request's
  privately-held pages are actually free after
  ``cancel(cache_pages=False)`` — quarantined KV can never have reached
  the prefix cache.

Destinations are recomputed host-side through the same addressing rules
the device scatters use (for the flat step, literally
:func:`repro.kernels.ragged_attn.ref.flat_write_destinations` — the
write half of the oracle), so the sanitizer can't drift from the kernel
contract without the identity tests failing too.

Install via ``REPRO_SANITIZE=1`` in the environment (every ``Engine``
self-installs at construction) or explicitly::

    from repro.analysis.sanitize import install
    san = install(engine)      # idempotent; returns the StepSanitizer

Warmup traffic is inherently clean (``new_counts == 0`` / ``row_ids ==
-1`` everywhere), so installing before warmup costs only the host check.
"""

from __future__ import annotations

from typing import Optional, Set

import numpy as np

from repro.kernels.ragged_attn.ref import flat_write_destinations

__all__ = ["SanitizerError", "StepSanitizer", "install"]


class SanitizerError(AssertionError):
    """A runtime layout/aliasing contract violation on the pool write path."""


class StepSanitizer:
    """Host-side pre-step checker wrapped around an engine's jitted steps."""

    def __init__(self, engine):
        self.engine = engine
        self.pool = engine.pool
        self.m_r = engine._bucket
        self.checks = 0            # steps inspected
        self.pages_checked = 0     # (page, step) write destinations audited
        self.cancels_checked = 0   # quarantine/cancel page audits
        self.paged_widths: Optional[Set[int]] = self._declared_paged_widths()
        self.flat_widths: Optional[Set[int]] = (
            set(engine._flat_shapes()) if engine.chunked else None)

    def _declared_paged_widths(self) -> Optional[Set[int]]:
        eng = self.engine
        if eng._bucket == 1:
            return None            # hybrids prefill at exact lengths
        widths = {1}
        if eng.spec_tokens is not None:
            widths.add(eng.spec_tokens + 1)
        l, seen = eng._bucket, set()
        while True:
            b = eng._prefill_bucket(l)
            if b in seen:
                break
            seen.add(b)
            l = b + 1
        return widths | seen

    # ------------------------------------------------------------------
    def _fail(self, message: str) -> None:
        raise SanitizerError(f"REPRO_SANITIZE: {message}")

    def _check_width(self, s: int, ladder: Optional[Set[int]],
                     kind: str) -> None:
        if ladder is None:
            return
        if s not in ladder:
            self._fail(
                f"{kind} step width {s} is not in the declared shape "
                f"ladder {sorted(ladder)} — an un-warmed width retraces "
                f"XLA and breaks tile-whole writes")

    def _check_pages(self, pages, where: str) -> None:
        pool = self.pool
        for p in np.unique(np.asarray(pages)):
            p = int(p)
            self.pages_checked += 1
            if p == 0:
                self._fail(
                    f"{where}: a valid position writes trash page 0 — the "
                    f"block table does not cover the write window; these "
                    f"tokens would be silently dropped")
            if not 0 < p < pool.num_pages:
                self._fail(f"{where}: write destination page {p} is outside "
                           f"the pool (num_pages={pool.num_pages})")
            ref = pool.ref(p)
            if ref == 0:
                self._fail(
                    f"{where}: write into unallocated page {p} (ref=0) — "
                    f"the block table references a freed page")
            if ref > 1:
                self._fail(
                    f"{where}: in-place write to page {p} with ref={ref} "
                    f"(holders: requests {pool.holders(p)}) — shared pages "
                    f"are read-only; PagedKVPool.cow() must split the page "
                    f"before any write or every holder's KV is corrupted")

    def check_retired(self) -> None:
        """Zero-leak-on-cancel: a retired rid (finished, cancelled, timed
        out, shed, quarantined) must be gone from the schedule and must
        hold no pages.  Runs before every step, so a leak is caught at
        the step after the retirement that caused it."""
        retired = getattr(self.engine, "_retired_rids", None)
        if not retired:
            return
        sched = self.engine.scheduler
        for r in list(sched.running.values()) + list(sched.waiting):
            if r.rid in retired:
                self._fail(f"retired rid {r.rid} is still scheduled "
                           f"(status={r.status}) — cancel/finish must "
                           f"remove the request from the scheduler")
        for s in self.pool.sequences():
            if s.owner in retired and s.pages:
                self._fail(
                    f"retired rid {s.owner} still holds pages {s.pages} — "
                    f"zero-leak-on-cancel violated (release() must run on "
                    f"every retirement path)")

    # ------------------------------------------------------------------
    def check_paged(self, token, block_tables, lens, new_counts) -> None:
        token = np.asarray(token)
        bt = np.asarray(block_tables)
        lens = np.asarray(lens)
        counts = np.asarray(new_counts)
        self.checks += 1
        b, s = token.shape
        self._check_width(s, self.paged_widths, "paged")
        t = self.pool.page_tokens
        for bi in range(b):
            n = int(counts[bi])
            if n <= 0:
                continue             # inert row: every write trash-routed
            pos = int(lens[bi]) + np.arange(n)
            slot = np.minimum(pos // t, bt.shape[1] - 1)
            self._check_pages(
                bt[bi, slot],
                f"paged step row {bi} (lens={int(lens[bi])}, "
                f"new_count={n})")

    def check_flat(self, token, block_tables, row_ids, q_pos) -> None:
        token = np.asarray(token)
        bt = np.asarray(block_tables)
        row_ids = np.asarray(row_ids)
        q_pos = np.asarray(q_pos)
        self.checks += 1
        w = token.shape[1]
        self._check_width(w, self.flat_widths, "flat")
        if self.m_r > 1 and w % self.m_r != 0:
            self._fail(f"flat step width {w} is not m_r-aligned "
                       f"(m_r={self.m_r}) — tile writes would be partial")
        pages, _off, valid = flat_write_destinations(bt, row_ids, q_pos,
                                                     self.pool.page_tokens)
        if valid.any():
            rows = sorted(int(r) for r in np.unique(row_ids[valid]))
            self._check_pages(pages[valid],
                              f"flat step (rows {rows}, "
                              f"{int(valid.sum())} valid tokens)")


def install(engine) -> StepSanitizer:
    """Wrap ``engine._paged_step`` / ``engine._flat_step`` with pre-call
    contract checks.  Idempotent per engine."""
    existing = getattr(engine, "sanitizer", None)
    if existing is not None:
        return existing
    san = StepSanitizer(engine)

    orig_paged = engine._paged_step

    def paged_checked(params, caches, token, bt, lens, counts, idx=None):
        san.check_retired()
        san.check_paged(token, bt, lens, counts)
        return orig_paged(params, caches, token, bt, lens, counts, idx)

    engine._paged_step = paged_checked
    if engine._flat_step is not None:
        orig_flat = engine._flat_step

        def flat_checked(params, caches, token, bt, row_ids, q_pos, idx):
            san.check_retired()
            san.check_flat(token, bt, row_ids, q_pos)
            return orig_flat(params, caches, token, bt, row_ids, q_pos, idx)

        engine._flat_step = flat_checked

    # quarantine audit: a cancel(cache_pages=False) is the engine saying
    # "this KV is poisoned" — pages the request held privately must end
    # the call free (a nonzero ref would mean the poisoned KV slipped
    # into the prefix cache or another block table)
    orig_cancel = engine.scheduler.cancel

    def cancel_checked(rid, reason="cancelled", *, cache_pages=True):
        solo = []
        if not cache_pages:
            live = ([r for r in engine.scheduler.waiting if r.rid == rid] +
                    [r for r in engine.scheduler.running.values()
                     if r.rid == rid])
            if live and live[0].pages is not None:
                solo = [p for p in live[0].pages.pages
                        if engine.pool.ref(p) == 1]
        out = orig_cancel(rid, reason, cache_pages=cache_pages)
        if solo:
            san.cancels_checked += 1
            for p in solo:
                if engine.pool.ref(p) != 0:
                    san._fail(
                        f"quarantined page {p} of rid {rid} survived "
                        f"cancel(cache_pages=False) with "
                        f"ref={engine.pool.ref(p)} (holders: "
                        f"{engine.pool.holders(p)}) — quarantined KV must "
                        f"never reach the prefix cache")
        return out

    engine.scheduler.cancel = cancel_checked
    engine.sanitizer = san
    return san
