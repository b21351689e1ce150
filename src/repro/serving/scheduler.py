"""Continuous-batching scheduler: FCFS admission into fixed decode slots,
lazy page allocation, preemption-by-recomputation.

The engine owns a fixed number of decode *slots* (rows of the batched decode
step — the compiled step shape never changes).  The scheduler:

  - queues incoming requests in **arrival order** (``add`` inserts by the
    request's ``arrival`` stamp, so benchmarks may enqueue a trace out of
    order without stalling replay behind a not-yet-arrived head; preempted
    requests always sit at the *front* of the queue, ahead of any arrival),
  - admits a waiting request when a slot is free AND the pool has pages for
    its **prompt** plus a small **watermark** of free pages (the watermark is
    headroom so running requests can grow a few tokens before the next
    preemption; it is waived when nothing else is running, since then there
    is nobody left to grow),
  - interleaves prefill and decode.  In the **monolithic** policy (the
    PR-1/2 baseline) newly-admitted requests are prefilled one at a time
    (each at its own length — no cross-request prompt padding), then every
    running slot advances one token per engine step.  In the **chunked**
    policy (``chunk_tokens`` set) admission books pages for the *first
    chunk* only and the request enters a ``prefilling`` state: each engine
    step feeds it the next ``chunk_tokens``-sized slice of its prompt
    (:meth:`Scheduler.plan_chunks`) inside the same fused batch that
    advances every decoding slot one token — a long admission is spread
    across steps and never stalls running decodes (Sarathi-style
    token-budget scheduling).  The per-request ``prefill_cursor`` tracks
    how many prompt tokens have KV in the cache; when it reaches the
    prompt length the request samples its first token and starts decoding,
  - **grows** every running request by one KV position per decode step
    (:meth:`Scheduler.grow`) — or, under speculative decoding, by ``1 + k``
    positions for the fed-back token plus the row's draft tokens, where
    only the first position is mandatory and the speculative remainder is
    shed on pressure instead of preempting for it —
    allocating pages only as sequences actually
    lengthen instead of reserving ``prompt + max_new - 1`` up front — a pool
    sized for average-length outputs serves long-tail traffic instead of
    idling behind reservations (the paper's amortized-packing economics,
    §4.1, applied to KV capacity; same philosophy as SVE's one-binary-many-
    vector-lengths: one pool size, many output-length distributions),
  - on :class:`~repro.serving.kv_cache.OutOfPages` during growth,
    **preempts** the youngest-admitted running request: its pages are
    released, and it re-enters the waiting queue at the front with its
    already-generated tokens folded into the prompt, so re-admission
    *recomputes* the interrupted sequence.  Because rows are mathematically
    independent and prefill logits at the last prompt token equal the decode
    logits that produced the next token (the batch-independence property
    proven in tests/test_scheduler.py), recomputation reproduces exactly the
    same greedy continuation — and the same sampled one, since sampling keys
    are derived from (seed, rid, position), not from batch composition,
  - evicts finished requests, returning their slot and pages to the free
    lists immediately.

A mid-prefill victim is **paused**, not preempted: it keeps its pages (the
KV for prompt tokens ``0 .. prefill_cursor-1`` stays valid) and its cursor,
gives up only its slot, and resumes from the cursor on re-admission —
already-written chunks are never recomputed.  Pausing frees no pages, but
it stops the victim's chunk-per-step page demand and shrinks the victim
set, so the preemption loop moves on to decoding victims.  Only as a last
resort — the sole running request still cannot grow and the remaining
pages are held by paused waiters — are a paused request's pages
**reclaimed** (released in full, cursor reset to 0, a true preemption that
recomputes the partial prefill); this is what keeps drains terminating at
any pool size.

Termination: the victim is always the *youngest* admitted request, so the
oldest running request is only ever preempted when it runs alone — and a
solo request can always finish, because ``add`` asserts every request's
whole KV lifetime fits the pool by itself and the reclaim fallback can
always hand a solo request the entire pool.  The oldest request therefore
always makes progress, and drains terminate even when the pool is far
smaller than the sum of reservations (see the OutOfPages-under-load test).

With a **prefix cache** attached (PR 5, :mod:`repro.serving.prefix_cache`),
admission starts prefill at the longest cached prefix of the prompt:
matched pages are *shared* into the block table (refcounted, read-only)
and ``prefill_cursor``/``len`` begin at the hit cursor — a fully-cached
prompt recomputes only its final position (whose logits the first pick
needs), CoW-splitting the shared page that position writes into.
Preemption then **releases pages into the cache instead of freeing them**:
generated tokens fold into the prompt first, the written full pages are
inserted under the fold-extended prompt's keys, and re-admission finds
them — recompute covers only the uncached suffix (at most the partial
last page plus the never-written final pick) instead of the whole
sequence, turning the PR-2 fold path into a cache hit.  Cached pages are
always reclaimable (the pool evicts LRU cache-only pages when its free
list runs dry, and availability checks count ``pool.num_available``), so
every preemption/termination argument above survives the cache holding
pages.

A note on the token budget: ``chunk_tokens`` caps one row's prefill
segment per step, and the ``token_budget`` cap on total assigned new
tokens bounds the whole step — so it bounds both how many slots prefill
concurrently (page-allocation raggedness) and the widest compiled step;
decoding slots are never budget-stalled — decode progress is
unconditional.

**Flat-segment layout contract** (the engine's one step with
``chunk_tokens`` set; :meth:`Scheduler.plan_segments`): the step is one
``[1, W]`` token stream, ``W`` from a geometric ladder of the layout's
``m_r`` multiples capped at the token budget (tile writes stay whole).
Each scheduled row occupies a contiguous *segment* of the stream:
position ``i`` carries ``row_ids[i]`` (the slot; ``-1`` = padding) and
``q_pos[i]`` (the token's absolute position in that row — its segment
offset plus the row's cursor/len), and the attention mask is
segment-aware causal (``kv_pos <= q_pos[i]`` within the row's own page
stream, see kernels/ragged_attn).  A decode row costs
exactly its ``1 + granted_drafts`` real positions — not a padded
chunk-width row — so the budget is token-exact: ``sum(segment lengths)
<= token_budget`` counts only real tokens, and decode segments are
never budget-stalled (they are planned before prefill chunks).

``eager=True`` restores the PR-1 policy (reserve the full lifetime at
admission; growth never fails) — kept as the benchmark baseline.

Request lifecycle state machine
-------------------------------

Every request moves through ``Request.status`` states along exactly these
edges (terminal ``finish_reason`` in parentheses):

  - ``(new) → waiting`` — ``add()`` passed the admission checks and
    inserted the request into the bounded wait queue in arrival order.
  - ``(new) → finished (rejected)`` — ``add()`` shed the request instead:
    the queue is at ``queue_limit`` depth, or the queue's predicted page
    demand (prompt pages of every queued-but-pageless request plus this
    one) exceeds ``queue_pages``.  The shed is a typed
    :class:`AdmissionError` raised *before* any state is taken — fast
    rejection under overload instead of unbounded queueing; the engine
    converts it into a ``finish_reason="rejected"`` row.  (A request whose
    KV budget can never fit ``max_len`` or the pool even alone raises the
    same typed error with ``kind="impossible"`` — a caller bug, not an
    overload signal, so the engine re-raises it.)
  - ``waiting → prefilling`` (chunked) or ``waiting → running``
    (monolithic) — ``admit()``: a slot was free, pages were available, and
    the arrival time has passed.
  - ``prefilling → running`` — the prefill cursor reached the prompt
    length and the first token was picked.
  - ``prefilling → waiting`` (*paused*) — displaced mid-prefill: keeps
    pages + cursor, surrenders only the slot.
  - ``waiting (paused) → waiting`` (*reclaimed*) — last-resort page
    recovery released the paused pages and reset the cursor.
  - ``running → waiting`` (*preempted*) — youngest victim of pool
    exhaustion: generated tokens folded into the prompt, pages released
    (into the prefix cache when attached), recompute on re-admission.
  - ``running → finished (eos | length)`` — ``done()``; the one
    happy-path exit.
  - ``waiting | prefilling | running → finished (timeout)`` —
    ``expire(now)``: the request's ``deadline_s`` elapsed (any state), or
    ``max_queue_s`` elapsed before it was ever admitted.
  - ``waiting | prefilling | running → finished (cancelled | timeout |
    error)`` — ``cancel(rid, reason)``: works from *any* live state,
    including between a speculative rollback and the next step (out_tokens
    only ever holds accepted tokens, so there is no mid-rollback state to
    corrupt).  The slot (if any) is returned, pages are released — into
    the prefix cache when the KV is valid (``cache_pages=True``), straight
    to the free list when it is quarantined (``reason="error"``: a
    NaN-logit row's pages must never be shared) — and the request never
    re-enters any queue.

Cancellation and the termination proof: cancel/expire only ever *remove*
work (a cancelled request frees its slot and pages and never returns), so
every quantity the termination argument counts — waiting requests, pages
the oldest request still needs — is monotonically helped by a
cancellation, and the proof above survives unchanged.  Admission
rejections shrink the queue before it holds state, so they cannot strand
pages either.  Zero-leak-on-cancel (a cancelled request leaves no live
pages, a quarantined request's private pages never reach the cache) is
checked dynamically by the ``REPRO_SANITIZE=1`` sanitizer and audited by
``analysis.aliasing.check_pool_consistency``.

Invariants & how they're checked
--------------------------------

The standing contracts above are machine-enforced, each by a named
analysis pass (:mod:`repro.analysis`; run all of them via
``scripts/tier1.sh --analyze``) or test:

  - **m_r alignment** — pages, chunk widths, flat widths, and prefill
    buckets are whole microkernel tiles from a finite geometric ladder:
    the shape-ladder linter (``analysis.shapes.lint_engine_shapes``)
    re-derives each ladder from this contract, diffs it against the
    engine, and walks every compiled step family's jaxpr asserting all
    dims static; plus tests/test_flat_step.py's ladder tests.
  - **zero post-warmup traces** — ``Engine.warmup`` compiles every
    reachable shape: the recompile-hazard detector
    (``analysis.retrace.RetraceDetector``) diffs the model's per-trace
    argument signatures after ``mark()`` and names the leaf (shape/
    dtype/weak_type) that forced any new trace; plus the zero-trace
    regression tests in tests/test_chunked_prefill.py etc.
  - **CoW before write / guarded pool writes** — every jaxpr-level KV
    write is addressed through the block-table gather with the
    trash-page route (``analysis.aliasing.lint_engine_aliasing``), the
    refcount ledger always matches holders + cache
    (``analysis.aliasing.check_pool_consistency``), and under
    ``REPRO_SANITIZE=1`` every in-place page write asserts ``ref == 1``
    at runtime (``analysis.sanitize``).
  - **termination** — youngest-victim preemption, the solo-fit admission
    assert (on ``usable_pages``/``num_available``, enforced by the AST
    lint's capacity-asserts rule), and the reclaim fallback:
    tests/test_scheduler.py's OutOfPages-under-load drains.
  - **token identity** — flat/chunked/monolithic/spec/prefix-cache
    outputs are bitwise the baseline's: the A/B drains in
    tests/test_flat_step.py, tests/test_speculative.py,
    tests/test_prefix_cache.py and the bench smoke.
  - **allocator hygiene** — ``._free``/``._ref`` are mutated only in
    kv_cache.py and no unseeded randomness enters serving code: the AST
    lint (``analysis.ast_lint``, ``scripts/lint_invariants.py``).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional

import numpy as np

from repro.obs.telemetry import NULL as _NULL_OBS
from repro.serving.kv_cache import (OutOfPages, PagedKVPool, PoolError,
                                    SequencePages)

__all__ = ["AdmissionError", "Request", "Scheduler", "finish_reason_for"]


class AdmissionError(RuntimeError):
    """``Scheduler.add`` refused a request.  ``kind`` says why:

    - ``"queue-depth"`` / ``"page-demand"`` — overload shed: the bounded
      wait queue is full, or its predicted page demand already exceeds the
      configured cap.  Transient; the engine reports the request with
      ``finish_reason="rejected"`` instead of queueing it unboundedly.
    - ``"impossible"`` — the request's KV budget can never fit ``max_len``
      or the pool even running alone: a caller bug, never admissible.

    Raised explicitly (not an ``assert``) so the admission contract
    survives ``python -O`` — an impossible request slipping into the queue
    would deadlock the preemption loop's termination argument."""

    def __init__(self, rid: int, kind: str, message: str):
        super().__init__(message)
        self.rid = rid
        self.kind = kind


def finish_reason_for(tokens, max_new: int, eos_id: Optional[int]):
    """The single finish-reason rule, shared by the continuous path
    (:meth:`Request.done`) and ``Engine.generate``'s static post-hoc
    classification so the two can never drift: the first eos strictly
    before the final permitted position finishes the stream as ``"eos"``
    (keeping ``i + 1`` tokens, eos included); otherwise the stream runs to
    ``max_new`` and finishes as ``"length"`` — an eos that lands *on* the
    final token is a length finish, since the budget, not the eos, is what
    stopped generation.  Returns ``(n_kept, reason)``."""
    if eos_id is not None:
        for i, t in enumerate(tokens[:max_new]):
            if t == eos_id and i < max_new - 1:
                return i + 1, "eos"
    return min(len(tokens), max_new), "length"


@dataclasses.dataclass
class Request:
    """One generation request and its runtime state."""

    rid: int
    prompt: np.ndarray            # [L] int32 prompt tokens
    max_new: int
    eos_id: Optional[int] = None
    arrival: float = 0.0
    # per-request sampling params (multi-tenant serving: one batch mixes
    # temperatures and seeds; the speculative acceptance rule needs the
    # request's own key stream, not a global one).  ``temperature == 0``
    # forces greedy for this request even in a sampled drain; ``seed=None``
    # falls back to the engine step's seed.
    temperature: float = 1.0
    seed: Optional[int] = None
    # per-request SLO bounds, both measured from ``arrival`` against the
    # clock the engine is stepped with (``step(now=...)``; wall-clock when
    # the engine drives its own drain): ``deadline_s`` bounds the whole
    # lifetime in any state, ``max_queue_s`` bounds only the time spent
    # waiting before the *first* admission.  ``None`` = unbounded.
    # Expiry finishes the request as ``finish_reason="timeout"`` with
    # whatever tokens it has (padded like an eos row in ``generate``).
    deadline_s: Optional[float] = None
    max_queue_s: Optional[float] = None

    # runtime state (owned by the scheduler/engine)
    status: str = "waiting"       # waiting | prefilling | running | finished
    slot: int = -1
    pages: Optional[SequencePages] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    len: int = 0                  # tokens whose KV is in the cache
    finish_reason: Optional[str] = None
    admit_seq: int = -1           # admission order; preemption evicts max
    preempted: bool = False       # waiting at the front for re-admission
    num_preemptions: int = 0
    folded: int = 0               # leading out_tokens already in the prompt
    # chunked prefill (chunk_tokens set): prompt tokens whose KV is written.
    # Survives a pause (pages kept) so the prefill resumes, not restarts;
    # reset to 0 only when pages are actually released (preempt/reclaim).
    prefill_cursor: int = 0
    num_pauses: int = 0
    chunk_steps: int = 0          # prefill steps run (monolithic: per call)
    # prefix-cache accounting: out_tokens watermark at the last admission
    # (a resume's "generated since" denominator) and whether a reclaim
    # reset the cursor (its resume legitimately recomputes never-cached
    # prefill work, so the resume-recompute bound does not apply)
    out_at_admit: int = 0
    reclaimed: bool = False
    cached_upto: int = 0          # tokens whose pages entered the cache at
                                  # the last preempt (resume-eviction probe)
    # telemetry: (label, t) lifecycle marks appended by repro.obs when the
    # engine runs with telemetry on; stays empty under the NULL recorder
    obs_events: List = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def kv_budget(self) -> int:
        """KV slots this request can ever occupy from here: the (possibly
        recompute-extended) prompt plus every remaining generated token that
        is fed back (the final token never is).  Invariant under preemption
        — folding k generated tokens into the prompt grows ``prompt_len`` by
        k and shrinks the remaining budget by k.  Valid while waiting."""
        return self.prompt_len + (self.max_new - len(self.out_tokens)) - 1

    def done(self) -> bool:
        if len(self.out_tokens) >= self.max_new or (
                self.eos_id is not None and self.out_tokens
                and self.out_tokens[-1] == self.eos_id):
            self.finish_reason = self.finish_reason or finish_reason_for(
                self.out_tokens, self.max_new, self.eos_id)[1]
            return True
        return False


class Scheduler:
    def __init__(self, max_slots: int, pool: PagedKVPool, max_len: int, *,
                 eager: bool = False, watermark_pages: int = 1,
                 chunk_tokens: Optional[int] = None, chunk_align: int = 1,
                 prefix_cache=None, queue_limit: Optional[int] = None,
                 queue_pages: Optional[int] = None, telemetry=None):
        self.max_slots = max_slots
        self.pool = pool
        self.max_len = max_len
        self.eager = eager
        self.watermark_pages = watermark_pages
        self.chunk_tokens = chunk_tokens       # None = monolithic prefill
        self.chunk_align = max(1, chunk_align)  # layout m_r: chunks stay tiles
        self.prefix_cache = prefix_cache       # None = no sharing (PR-2/3/4)
        # admission control: bound on queued requests / on the queue's
        # predicted page demand; None = unbounded (the pre-PR-8 behavior)
        self.queue_limit = queue_limit
        self.queue_pages = queue_pages
        self.obs = telemetry if telemetry is not None else _NULL_OBS
        assert prefix_cache is None or not eager, \
            "prefix cache needs lazy allocation: eager reservation books " \
            "full lifetimes, which shared (refcounted) pages would double-count"
        self.waiting: Deque[Request] = deque()
        self.running: Dict[int, Request] = {}          # slot -> request
        self._free_slots: List[int] = list(range(max_slots - 1, -1, -1))
        self._admit_counter = 0
        self.num_preemptions = 0
        self.num_pauses = 0
        self.prefill_stall_steps = 0           # steps where a chunk got < ask
        self.spec_grow_fallbacks = 0           # speculative page asks shed
        self.peak_running = 0
        self.peak_waiting = 0          # high-water queue depth (the
                                       # queue-growth monitor's context:
                                       # was a growth excursion also a
                                       # lifetime high?)
        # preempt-resume accounting under the prefix cache: scalar totals
        # for stats() plus a bounded window of per-event records (the
        # cache contract asserted by tests/bench: recompute <=
        # generated_since + one partial page, unless a reclaim dropped the
        # pages or pool-pressure eviction beat the resume to them)
        self.resumes = 0
        self.resume_recompute_tokens = 0
        self.resume_events: Deque[dict] = deque(maxlen=256)
        # resilience counters (PR 8): requests shed at add(), expired past
        # their deadline, cancelled by the caller, or quarantined (a
        # NaN-logit row retired with its pages kept out of the cache)
        self.num_rejected = 0
        self.num_timeouts = 0
        self.num_cancels = 0
        self.num_quarantines = 0

    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def num_free_slots(self) -> int:
        return len(self._free_slots)

    def add(self, req: Request) -> None:
        """Queue one request, or refuse it with a typed
        :class:`AdmissionError` — ``kind="impossible"`` for a request that
        could never run (caller bug), ``kind="queue-depth"`` /
        ``"page-demand"`` for an overload shed when the bounded queue is
        configured.  Sheds are decided *before* any state is taken, so a
        rejection can never strand a slot or a page."""
        if req.kv_budget > self.max_len:
            raise AdmissionError(
                req.rid, "impossible",
                f"request {req.rid}: KV budget {req.kv_budget} (prompt "
                f"{req.prompt_len} + max_new {req.max_new} - 1) exceeds "
                f"engine max_len {self.max_len}")
        if self.pool.pages_for(req.kv_budget) > self.pool.usable_pages:
            raise AdmissionError(
                req.rid, "impossible",
                f"request {req.rid}: KV budget {req.kv_budget} can never "
                f"fit the pool ({self.pool.usable_pages} usable pages of "
                f"{self.pool.page_tokens} tokens) — it could neither run "
                f"eagerly nor survive preemption (cached pages don't help: "
                f"they are reclaimable, not extra capacity)")
        # overload shed signals (bounded wait queue).  Preempted/paused
        # requests re-enter via appendleft, never through add(), so already
        # -admitted work is never shed here.
        if self.queue_limit is not None \
                and len(self.waiting) >= self.queue_limit:
            self.num_rejected += 1
            raise AdmissionError(
                req.rid, "queue-depth",
                f"request {req.rid} shed: wait queue at its bound "
                f"({len(self.waiting)}/{self.queue_limit}) — admitting "
                f"would queue unboundedly under overload")
        if self.queue_pages is not None:
            # predicted demand: prompt pages of every queued request that
            # holds no pages yet, plus the incoming one (paused waiters'
            # held pages are already booked, not future demand)
            demand = self.pool.pages_for(req.prompt_len) + sum(
                self.pool.pages_for(r.prompt_len) for r in self.waiting
                if r.pages is None)
            if demand > self.queue_pages:
                self.num_rejected += 1
                raise AdmissionError(
                    req.rid, "page-demand",
                    f"request {req.rid} shed: queued prompt-page demand "
                    f"{demand} exceeds queue_pages={self.queue_pages} — "
                    f"the backlog already outsizes what the pool can "
                    f"drain promptly")
        req.status = "waiting"
        # insert in arrival order (stable: FCFS among equal arrivals), but
        # never ahead of preempted requests — they resume first regardless
        i, n = 0, len(self.waiting)
        while i < n and self.waiting[i].preempted:
            i += 1
        while i < n and self.waiting[i].arrival <= req.arrival:
            i += 1
        self.waiting.insert(i, req)
        self.peak_waiting = max(self.peak_waiting, len(self.waiting))
        self.obs.request_queued(req)

    def admit(self, now: Optional[float] = None,
              limit: Optional[int] = None) -> List[Request]:
        """Admit waiting requests (FCFS) while a slot is free and the pool
        has pages for the head's prompt plus the watermark (``eager=True``:
        for its full KV budget; chunked: for its *next chunk* only — the
        rest of the prompt is paged in as the cursor advances).  Returns the
        newly-admitted requests; the engine prefills them (monolithic) or
        streams them chunk by chunk (``status == "prefilling"``).  ``now``
        gates admission by arrival time (benchmark trace replay); ``limit``
        caps this call's admissions — the monolithic engine admits one at a
        time so each admission's prefill lands in the prefix cache before
        the next admission's lookup (same-step arrivals then share)."""
        admitted = []
        while (self.waiting and self._free_slots
               and (limit is None or len(admitted) < limit)
               and (now is None or self.waiting[0].arrival <= now)):
            if not self._pages_available(self.waiting[0]):
                # with nothing running, nobody will ever free pages on its
                # own — reclaim paused waiters (never the head itself, whose
                # held pages reduce its need) so the head always progresses
                # and drains terminate at any pool size
                if not self.running and \
                        self._reclaim_one_paused(exclude=self.waiting[0]):
                    continue
                break
            req = self.waiting.popleft()
            req.slot = self._free_slots.pop()
            was_preempted, was_reclaimed = req.preempted, req.reclaimed
            fresh_pages = req.pages is None
            req.preempted = False
            req.reclaimed = False
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            try:
                self._take_pages(req, was_preempted, was_reclaimed)
            except OutOfPages:
                # _pages_available said yes but the allocation still
                # failed (a cache eviction raced it, or a fault-injection
                # harness spiked the allocator): undo the half-admission
                # completely — nothing leaks, the head retries next step
                if fresh_pages and req.pages is not None:
                    req.pages.release()
                    req.pages = None
                    req.prefill_cursor = 0
                    req.len = 0
                self._free_slots.append(req.slot)
                req.slot = -1
                req.status = "waiting"
                req.preempted = was_preempted
                req.reclaimed = was_reclaimed
                self.waiting.appendleft(req)
                break
            self.running[req.slot] = req
            self.obs.request_admitted(req)
            admitted.append(req)
        self.peak_running = max(self.peak_running, len(self.running))
        return admitted

    def _take_pages(self, req: Request, was_preempted: bool,
                    was_reclaimed: bool) -> None:
        """The page-acquiring half of one admission (everything that can
        raise :class:`OutOfPages`), split out so ``admit`` can roll the
        whole thing back atomically when an allocation fails *after* the
        availability check said yes."""
        if req.pages is None:            # a paused request keeps its pages
            req.pages = SequencePages(self.pool, owner=req.rid)
            if self.prefix_cache is not None:
                self._acquire_prefix(req)
                if was_preempted:
                    recompute = req.prompt_len - req.prefill_cursor
                    self.resumes += 1
                    self.resume_recompute_tokens += recompute
                    self.resume_events.append({
                        "rid": req.rid,
                        "recompute": recompute,
                        "generated_since": (len(req.out_tokens)
                                            - req.out_at_admit),
                        "reclaimed": was_reclaimed,
                        # pool pressure may LRU-evict a victim's cached
                        # pages before it resumes — the bound then
                        # legitimately does not apply (output identity
                        # always does)
                        "evicted": req.prefill_cursor < min(
                            req.cached_upto, req.prompt_len - 1)})
        req.out_at_admit = len(req.out_tokens)
        if self.chunk_tokens is not None:
            # chunked: pages arrive with each chunk (plan_chunks); a
            # resumed pause continues from its cursor, never from 0
            assert req.prefill_cursor < req.prompt_len
            req.status = "prefilling"
            req.len = req.prefill_cursor
            if self.eager:               # eager A/B: lifetime up front
                req.pages.ensure(req.kv_budget)
        else:
            req.status = "running"
            # eager: reserve the whole lifetime; lazy: the prompt only —
            # decode steps grow the block table via grow()
            req.pages.ensure(req.kv_budget if self.eager
                             else req.prompt_len)

    def _acquire_prefix(self, req: Request) -> None:
        """Start ``req`` at its longest cached prefix: matched pages are
        shared into the (empty) block table and the prefill cursor jumps to
        the hit — a fully-cached prompt recomputes only its final position.
        When the capped cursor lands *inside* the last shared page (only
        the fully-cached case; full-page hits leave the cursor on a page
        boundary), that page is CoW-split now, before prefill writes the
        final position into it — no shared page is ever written in place.
        If even the CoW copy cannot be allocated, the tail page is handed
        back instead and its block re-prefills from the aligned boundary —
        a pure fallback, never a correctness difference."""
        assert not req.pages.pages and req.prefill_cursor == 0
        pages, hit = self.prefix_cache.lookup(req.prompt)
        if not pages:
            return
        req.pages.pages = pages
        if hit % self.pool.page_tokens:
            try:
                self.pool.cow(req.pages, len(pages) - 1)
            except PoolError:
                # no page for the copy, or the device copy itself failed
                # (PoolError wraps page_copier errors): hand the tail page
                # back and re-prefill its block — degraded, never wrong
                self.pool.free([req.pages.pages.pop()])
                hit = len(req.pages.pages) * self.pool.page_tokens
        req.prefill_cursor = hit
        req.len = hit

    def _pages_available(self, req: Request) -> bool:
        # num_available counts free pages plus cache-evictable ones (alloc
        # reclaims the latter on demand); the need is computed as if the
        # lookup misses.  Monolithic: a hit shrinks the need by exactly the
        # pages sharing pins (plus at most one CoW page, covered because
        # CoW only fires when >= 1 page was pinned), so the check stays
        # sufficient.  Chunked: the need covers the *next chunk* only and
        # does not shrink with the hit, while the hit may pin
        # previously-evictable pages — the watermark headroom can erode by
        # the hit size in the worst case.  That costs at most an avoidable
        # displacement on a later grow() (plan_chunks stalls, grow pauses/
        # preempts — all handled paths); admission itself stays safe
        # because the chunk's own pages were counted before any pinning.
        if self.eager:
            return self.pool.can_fit(req.kv_budget)
        # the watermark keeps headroom for already-running requests to grow;
        # with nothing running there is nobody to protect, so a solo request
        # may take the whole pool (this is what guarantees drain progress)
        reserve = self.watermark_pages if self.running else 0
        if self.chunk_tokens is not None:
            held = 0 if req.pages is None else len(req.pages.pages)
            first = min(req.prefill_cursor + self.chunk_tokens,
                        req.prompt_len)
            need = max(0, self.pool.pages_for(first) - held)
            return need + reserve <= self.pool.num_available
        return self.pool.pages_for(req.prompt_len) + reserve \
            <= self.pool.num_available

    def plan_chunks(self, budget: int) -> Dict[int, int]:
        """Assign this step's prompt chunk to every PREFILLING slot, oldest
        admission first: each gets ``min(chunk_tokens, remaining prompt,
        remaining budget)`` tokens and the pages to hold them.  On
        ``OutOfPages`` the slot **stalls** (it keeps its slot, cursor and
        pages, and simply contributes ``new_counts == 0`` this step) rather
        than stealing pages from decodes — except for the oldest prefill
        when nothing is decoding, which reclaims paused waiters' pages (and,
        failing that, pauses younger prefills so the *next* reclaim can take
        theirs) so the head of the line always makes progress.  Returns
        ``{slot: n}``."""
        plan: Dict[int, int] = {}
        if self.chunk_tokens is None:
            return plan
        prefilling = sorted(
            (r for r in self.running.values() if r.status == "prefilling"),
            key=lambda r: r.admit_seq)
        decoding = any(r.status == "running" for r in self.running.values())
        stalled = False
        for idx, req in enumerate(prefilling):
            if req.slot < 0 or req.status != "prefilling":
                continue                 # paused by an earlier reclaim pass
            want = min(self.chunk_tokens,
                       req.prompt_len - req.prefill_cursor)
            n = min(want, max(0, budget))
            if n < want:
                # budget-clamped: keep the cursor on a microkernel-tile
                # boundary so every later chunk still writes whole tiles
                # (only the final prompt-remainder chunk may be inexact)
                n -= n % self.chunk_align
            if n > 0:
                try:
                    req.pages.ensure(req.prefill_cursor + n)
                except OutOfPages:
                    if idx == 0 and not decoding:
                        self._reclaim_for(req, n)
                    n = min(n, req.pages.capacity - req.prefill_cursor)
            if n < want:
                stalled = True
            plan[req.slot] = n
            budget -= n
        if stalled:
            self.prefill_stall_steps += 1
        return plan

    def plan_segments(self, decode_counts: Dict[int, int],
                      budget: int) -> List[tuple]:
        """Flat-segment plan for one ``[1, W]`` step: decode rows first
        (each costs exactly its ``1 + granted_drafts`` real positions —
        token-exact, never budget-stalled), then prefill chunks from
        :meth:`plan_chunks` under the remaining budget (page bookkeeping,
        stalls, and reclaim fallbacks live there).  ``decode_counts``:
        ``{slot: 1 + k}`` for every decoding row.  Returns an ordered
        ``[(slot, kind, n)]`` list, ``kind in {"decode", "prefill"}``; the
        engine lays the segments out back-to-back in the flat stream."""
        ndecode = sum(decode_counts.values())
        plan = self.plan_chunks(budget - ndecode)
        segs: List[tuple] = []
        for slot in sorted(self.running):
            req = self.running[slot]
            if req.status == "running" and slot in decode_counts:
                segs.append((slot, "decode", decode_counts[slot]))
            elif req.status == "prefilling" and plan.get(slot, 0) > 0:
                segs.append((slot, "prefill", plan[slot]))
        return segs

    def _reclaim_for(self, req: Request, n: int) -> None:
        """Last-resort page recovery for the oldest prefill when nothing
        else is running: release paused waiters' pages (youngest admission
        first), pausing still-running younger prefills so the next reclaim
        can take theirs.  ``add``'s solo-fit assert guarantees this loop
        hands ``req`` enough pages eventually."""
        while True:
            try:
                req.pages.ensure(req.prefill_cursor + n)
                return
            except OutOfPages:
                if self._reclaim_one_paused():
                    continue
                younger = [r for r in self.running.values()
                           if r.status == "prefilling" and r is not req]
                if not younger:
                    return               # caller falls back to capacity
                self._pause(max(younger, key=lambda r: r.admit_seq))

    def grow(self, want: Optional[Dict[int, int]] = None) -> List[Request]:
        """Give every decoding request a KV slot for the position its next
        token writes (``len``), oldest admission first (PREFILLING slots get
        their pages chunk-wise in :meth:`plan_chunks` instead).  On pool
        exhaustion, displace the youngest-admitted running request and
        retry: a mid-prefill victim is *paused* (keeps pages + cursor, frees
        only its slot and its future chunk demand), a decoding victim is
        *preempted* (pages released, tokens folded, recompute).  When the
        growing request is its own youngest victim, paused waiters' pages
        are reclaimed first — self-preemption is the true last resort.

        ``want``: optional ``{slot: n}`` asking n >= 1 KV positions for a
        row this step — the speculative verify step writes 1 fed-back token
        plus up to k draft tokens.  Only the first position is mandatory:
        a speculative ask is shed (all-or-nothing, counted in
        ``spec_grow_fallbacks``) not just when it outsizes the free list
        but whenever granting it would eat into the pages the *other*
        running rows' mandatory one-token growth needs this step — a
        speculative grant must never be what forces a preemption (tokens
        it books may be rejected anyway), so the preemption loop only ever
        runs for the same one-token demand as plain decode and the
        termination proof is untouched.

        Returns the requests displaced this step (the engine masks their
        slots into the trash page for the in-flight decode).  No-op when
        admission was eager — capacity was reserved up front."""
        displaced: List[Request] = []
        for req in sorted(self.running.values(), key=lambda r: r.admit_seq):
            if req.status != "running":
                continue
            n = 1 if want is None else max(1, want.get(req.slot, 1))
            if n > 1:
                need = max(0, self.pool.pages_for(req.len + n)
                           - len(req.pages.pages))
                if need == 0:
                    continue     # slack in the held pages covers the ask
                if need <= self.pool.num_available \
                        - self._mandatory_growth_pages(req):
                    try:
                        req.pages.ensure(req.len + n)
                        continue
                    except OutOfPages:
                        pass
                self.spec_grow_fallbacks += 1
            while req.status == "running":
                try:
                    req.pages.ensure(req.len + 1)
                    break
                except OutOfPages:
                    victim = max(self.running.values(),
                                 key=lambda r: r.admit_seq)
                    if victim.status == "prefilling":
                        # frees no pages, but shrinks the victim set; the
                        # retry walks on to the next-youngest victim
                        self._pause(victim)
                    elif victim is req and self._reclaim_one_paused():
                        continue
                    else:
                        self._preempt(victim)
                    displaced.append(victim)
        return displaced

    def _mandatory_growth_pages(self, exclude: Request) -> int:
        """Pages the other decoding rows' mandatory one-token growth will
        demand this step (0 or 1 each — one token crosses at most one page
        boundary).  Rows grown earlier this pass already hold their page
        and contribute 0, so this is exactly the not-yet-served demand a
        speculative grant must leave room for."""
        return sum(1 for r in self.running.values()
                   if r is not exclude and r.status == "running"
                   and self.pool.pages_for(r.len + 1) > len(r.pages.pages))

    def _pause(self, req: Request) -> None:
        """Displace a mid-prefill request *without* losing its work: it
        keeps its pages (KV for prompt[0:prefill_cursor] stays valid — those
        pages cannot be handed to anyone else) and its cursor, returns only
        its slot, and waits at the queue front; re-admission resumes the
        prefill from the cursor instead of recomputing written chunks."""
        assert req.status == "prefilling"
        assert self.running.get(req.slot) is req
        self.obs.request_paused(req)       # before the slot clears: the
        del self.running[req.slot]         # instant lands on its track
        self._free_slots.append(req.slot)
        req.slot = -1
        req.status = "waiting"
        req.preempted = True
        req.num_pauses += 1
        self.num_pauses += 1
        self.waiting.appendleft(req)

    def _reclaim_one_paused(self, exclude: Optional[Request] = None) -> bool:
        """Release the pages of one paused waiting request (youngest
        admission first), resetting its cursor — a true preemption of a
        partial prefill, used only when running victims are exhausted.
        ``exclude`` protects the request the reclaim is *for* (releasing
        its own pages would grow, not shrink, its need).  Returns False
        when no other waiter holds pages."""
        holders = [r for r in self.waiting
                   if r is not exclude and r.pages is not None
                   and r.pages.pages]
        if not holders:
            return False
        victim = max(holders, key=lambda r: r.admit_seq)
        if self.prefix_cache is not None:
            # a reclaim is still a release-into-the-cache: the victim's
            # completed chunks stay findable (and instantly evictable if
            # the pressure that forced this reclaim needs them)
            self.prefix_cache.insert(victim.prompt, victim.pages.pages,
                                     min(victim.prefill_cursor,
                                         victim.prompt_len))
            victim.pages.release()
            victim.pages = None      # re-admission re-looks-up the prefix
        else:
            victim.pages.release()
        victim.prefill_cursor = 0
        victim.len = 0
        victim.reclaimed = True
        victim.num_preemptions += 1
        self.num_preemptions += 1
        self.obs.request_reclaimed(victim)
        return True

    def _preempt(self, req: Request) -> None:
        """Release everything and requeue at the front for recomputation:
        the generated-so-far tokens are folded into the prompt, so the
        re-admission prefill recomputes the KV the release threw away and
        the next pick continues the sequence exactly where it stopped.

        With a prefix cache, "release" means **release into the cache**:
        the fold runs first so the extended prompt keys the written full
        pages, those are inserted (the cache takes its own references), and
        only then are the request's references dropped — full pages survive
        for the re-admission lookup, the partial tail page returns to the
        free list, and the resume recomputes just the uncached suffix."""
        assert self.running.get(req.slot) is req
        self.obs.request_preempted(req)    # before the slot clears: the
        del self.running[req.slot]         # instant lands on its track
        self._free_slots.append(req.slot)
        req.slot = -1
        # fold only the tokens generated since the last admission — earlier
        # preemptions already folded their prefix (re-folding would duplicate
        # it and silently corrupt the recompute context)
        fresh = req.out_tokens[req.folded:]
        if fresh:
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fresh, np.int32)])
            req.folded = len(req.out_tokens)
        if self.prefix_cache is not None:
            # req.len positions hold committed KV (speculative rollbacks
            # already truncated rejected drafts, so nothing stale can leak)
            upto = min(req.len, req.prompt_len)
            self.prefix_cache.insert(req.prompt, req.pages.pages, upto)
            req.cached_upto = (upto // self.pool.page_tokens
                               * self.pool.page_tokens)
        req.pages.release()
        req.pages = None
        req.len = 0
        req.prefill_cursor = 0       # pages gone: re-prefill from the start
        req.status = "waiting"
        req.preempted = True
        req.num_preemptions += 1
        self.num_preemptions += 1
        # victims are preempted youngest-first, so successive appendlefts
        # leave the *oldest* victim at the head for re-admission
        self.waiting.appendleft(req)

    def finish(self, req: Request) -> None:
        """Evict: return the slot and the pages to the free lists."""
        assert self.running.get(req.slot) is req
        self.obs.request_finished(req)     # slot still valid: the decode
        del self.running[req.slot]         # span closes on its track
        req.pages.release()
        self._free_slots.append(req.slot)
        req.slot = -1
        req.status = "finished"

    def cancel(self, rid: int, reason: str = "cancelled", *,
               cache_pages: bool = True) -> Optional[Request]:
        """Retire request ``rid`` from *any* live state — queued (fresh,
        paused or preempted), prefilling, or decoding (including right
        after a speculative rollback: ``out_tokens``/``len`` only ever
        cover accepted tokens, so there is no partial state to corrupt).
        The slot (if held) is returned, pages are released — into the
        prefix cache when attached and ``cache_pages=True`` (the committed
        KV is valid; a later identical prompt may reuse it), straight to
        the free list when the KV is suspect (``cache_pages=False``: the
        engine's NaN-logit quarantine) — and the request finishes with
        ``finish_reason=reason`` (``"cancelled"`` | ``"timeout"`` |
        ``"error"``).  Returns the request, or ``None`` when ``rid`` is
        not live (already finished, never added, or shed at add)."""
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                del self.waiting[i]
                self.obs.request_cancelled(r, reason)
                return self._retire_cancelled(r, reason, cache_pages)
        for slot, r in list(self.running.items()):
            if r.rid == rid:
                del self.running[slot]
                self._free_slots.append(slot)
                self.obs.request_cancelled(r, reason)
                r.slot = -1
                return self._retire_cancelled(r, reason, cache_pages)
        return None

    def _retire_cancelled(self, req: Request, reason: str,
                          cache_pages: bool) -> Request:
        if req.pages is not None:
            if cache_pages and self.prefix_cache is not None \
                    and req.pages.pages:
                # same contract as preemption: positions 0..len-1 hold
                # committed KV (cursor for a mid-prefill victim), capped at
                # the prompt — the cache takes its references before ours
                # drop, so full pages survive for a future identical prompt
                upto = min(max(req.len, req.prefill_cursor), req.prompt_len)
                self.prefix_cache.insert(req.prompt, req.pages.pages, upto)
            req.pages.release()
            req.pages = None
        req.prefill_cursor = 0
        req.len = 0
        req.status = "finished"
        req.finish_reason = reason
        if reason == "timeout":
            self.num_timeouts += 1
        elif reason == "error":
            self.num_quarantines += 1
        else:
            self.num_cancels += 1
        return req

    def expire(self, now: Optional[float]) -> List[Request]:
        """Cancel-as-timeout every live request past its deadline at time
        ``now``: ``deadline_s`` bounds the whole lifetime in any state,
        ``max_queue_s`` only the wait before the first admission.  Run by
        the engine at the top of each step (before admission, so a doomed
        head never takes a slot).  ``now=None`` (an untimed drain) checks
        nothing — deadlines need the caller's clock."""
        if now is None:
            return []
        stale = [r for r in list(self.waiting) + list(self.running.values())
                 if (r.deadline_s is not None
                     and now - r.arrival >= r.deadline_s)
                 or (r.max_queue_s is not None and r.admit_seq < 0
                     and r.status == "waiting"
                     and now - r.arrival >= r.max_queue_s)]
        return [self.cancel(r.rid, "timeout") for r in stale]

    def stats(self) -> dict:
        """Scheduler-side counters (cumulative; pool stats live on the
        pool).  ``prefilling``/``decoding`` split the running set by state;
        ``prefill_stall_steps`` counts steps where some prefilling slot was
        assigned fewer chunk tokens than it asked for (pages or budget)."""
        running = list(self.running.values())
        return {
            "waiting": len(self.waiting),
            "running": len(running),
            "prefilling": sum(r.status == "prefilling" for r in running),
            "decoding": sum(r.status == "running" for r in running),
            "free_slots": len(self._free_slots),
            "peak_running": self.peak_running,
            "peak_waiting": self.peak_waiting,
            "num_preemptions": self.num_preemptions,
            "num_pauses": self.num_pauses,
            "prefill_stall_steps": self.prefill_stall_steps,
            "spec_grow_fallbacks": self.spec_grow_fallbacks,
            "chunk_tokens": self.chunk_tokens,
            "resumes": self.resumes,
            "resume_recompute_tokens": self.resume_recompute_tokens,
            "queue_limit": self.queue_limit,
            "queue_pages": self.queue_pages,
            "num_rejected": self.num_rejected,
            "num_timeouts": self.num_timeouts,
            "num_cancels": self.num_cancels,
            "num_quarantines": self.num_quarantines,
        }
