"""Serving engine: continuous batching over a paged, layout-aware KV cache.

The engine owns a fixed set of decode **slots** (the compiled decode step
shape never changes), a paged KV pool (page size = ``round_up(page_tokens,
m_r)`` of the active packed layout — KV pages are whole microkernel tiles),
and a FCFS :class:`~repro.serving.scheduler.Scheduler`.  Per engine step:

  1. admission: waiting requests take free slots when the pool has pages
     for their *prompt* (chunked: their *next chunk*) plus a small
     watermark (lazy allocation — no full-lifetime reservation); each is
     prefilled at its own (layout-bucketed) length — no cross-request
     prompt padding;
  2. growth: every decoding slot gets a KV page for the position this
     step's token writes (``Scheduler.grow``); on pool exhaustion the
     youngest-admitted request is displaced — a decoding victim is
     preempted (pages released, generated tokens folded into the prompt,
     re-admission recomputes the identical continuation), a mid-prefill
     victim is paused (keeps pages + cursor, resumes instead of redoing
     written chunks);
  3. decode: every running slot advances one token in a single fixed-shape
     batched ``paged_decode_step``.  Slots displaced in phase 2 (and free
     slots) are masked into the trash page mid-step: their rows carry
     ``new_counts == 0`` and an all-zero block table, so the in-flight step
     writes their K/V to page 0 and can never corrupt a live request;
  4. eviction: finished requests release slot + pages immediately.

With ``chunk_tokens`` set (pure-attention models), prefill and decode fuse
into a **single ragged step under a per-step token budget**: the batch is
the fixed shape ``[slots, c]`` whenever any slot is prefilling — ``c``
drawn from a short geometric ladder ``chunk_tokens, chunk_tokens/2, ..
m_r`` sized to the step's largest chunk — and ``[slots, 1]`` otherwise
(``log2(chunk/m_r)+2`` compiled shapes, still below the monolithic
policy's ``log2`` prompt buckets), and every active row contributes between 1
token (decoding) and ``chunk_tokens`` (prefilling) via per-row
``new_counts``/positions — the paper's fixed-shape-grid argument (fix the
tile grid once, let occupancy vary) applied to the serving step.  A long
(or recompute-folded, hence unbounded) admission is spread across steps at
``chunk_tokens`` per step and **never stalls running decodes** — the
Sarathi-style chunked prefill ROADMAP asks for; inter-token latency during
an admission is bounded by one fused-step time instead of one full-prompt
prefill.  Chunk sizes are rounded up to the layout's ``m_r`` so chunk
writes land on whole microkernel tiles, like the (``m_r``-aligned) pages
they fill.  The same ragged multi-position row is the verify-step
primitive speculative decode needs (score k draft tokens in one step).
Monolithic prefill (``chunk_tokens=None``, the default) and ``eager=True``
remain the PR-1/2 baseline policies for the benchmark A/B; recurrent-mixer
families (ssm/rwkv/hybrid) always use them — a scan carries state through
*every* row position, so padded chunk rows are not inert for them.

With ``spec_tokens=k`` (pure-attention models) the engine decodes
**speculatively**: a pluggable :class:`~repro.serving.speculative.Drafter`
proposes up to ``k`` guesses per decoding row, the row feeds
``[fed-back token, d_1 .. d_k]`` through the same fixed-shape paged step
(``new_counts`` = 1 + draft length — per-row draft lengths ride the ragged
step exactly like per-row chunk lengths, zero new traces), ``logits_idx``
reads the target logits at every draft position from that one call, and
the acceptance rule in :mod:`repro.serving.speculative` keeps outputs
token-identical to the non-speculative engine — greedy and sampled — while
each accepted draft advances a row one extra token per step.  Page growth
books the ``k+1``-token ask speculatively (shed under pressure, never
preempted-for); rejected positions are rolled back by truncating the block
table (:meth:`SequencePages.truncate`), and a preemption can never fold a
rejected draft because ``out_tokens`` only ever holds accepted tokens.

With ``prefix_cache=True`` (pure-attention models, lazy allocation) the
engine shares KV pages across requests through a **layout-keyed prefix
cache** (:mod:`repro.serving.prefix_cache`): admission starts prefill at
the longest cached page-chain prefix of the prompt (shared pages are
refcounted and read-only; the one place the cursor can land inside a
shared page — a fully-cached prompt — CoW-splits it first), prefill
inserts newly-completed full pages as it goes (chunked) or at completion
(monolithic), and preemption releases pages *into the cache* so
re-admission recomputes only the uncached suffix.  Cached KV is
bit-identical to recomputed KV (pages are immutable once full and keyed by
layout + exact token content), so outputs are token-identical to
``prefix_cache=False`` by construction — greedy and sampled, both prefill
policies, speculation on or off — while shared system prompts prefill once
per *content* instead of once per request and preemption stops costing a
full recompute.  ``Engine.stats()["prefix_cache"]`` reports hit rate,
shared pages, CoW copies and evictions.

Rows are mathematically independent (per-row attention over per-row pages,
per-row softmax/argmax), so a request's greedy output is identical whatever
else shares the batch — admission order cannot change results.

The decode KV pool is sequence-shardable over the model axis (pages are the
sequence chunks; ``repro.distributed.sharding.cache_specs``) and weights are
pre-packed once (``prepack_params``) — the paper's amortized standalone
packing (§4.1) — so decode steps stream packed tiles directly.

``generate`` is a thin compatibility wrapper over add_request/step; the
encoder-decoder and VLM families (per-request encoder state, patch-prefix
prefill) still use the static-batch path (``generate_static``).
"""

from __future__ import annotations

import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layout import ceil_div, round_up
from repro.core.linear import prepack_params
from repro.distributed import sharding
from repro.models.model import ReproModel
from repro.obs.telemetry import NULL as OBS_NULL
from repro.obs.telemetry import NullTelemetry, Telemetry
from repro.serving.faults import StallError
from repro.serving.kv_cache import (PagedKVPool, PoolError, copy_pages,
                                    fresh_slot_states, merge_slot,
                                    prefill_view)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (AdmissionError, Request, Scheduler,
                                     finish_reason_for)
from repro.serving.speculative import Drafter, NgramDrafter, accept_tokens

__all__ = ["Engine"]

_STATIC_FAMILIES = ("encdec", "vlm")


class Engine:
    def __init__(self, model: ReproModel, params, *, mesh=None,
                 prepack: bool = True, max_slots: Optional[int] = None,
                 page_tokens: int = 16, num_pages: Optional[int] = None,
                 eager: bool = False, watermark_pages: int = 1,
                 chunk_tokens: Optional[int] = None,
                 flat: Optional[bool] = None,
                 token_budget: Optional[int] = None,
                 spec_tokens: Optional[int] = None,
                 drafter: Optional[Drafter] = None,
                 prefix_cache: bool = False,
                 queue_limit: Optional[int] = None,
                 queue_pages: Optional[int] = None,
                 watchdog_steps: int = 64,
                 nan_guard: bool = True,
                 telemetry=False):
        self.model = model
        self.mesh = mesh
        # observability (repro.obs): ``telemetry=True`` builds a live
        # Telemetry (metrics + trace recorder), a Telemetry instance is
        # used as-is, and the default keeps the no-op NULL recorder —
        # every instrumentation point below is then a single no-op call
        self.obs = (telemetry if isinstance(telemetry, NullTelemetry)
                    else Telemetry() if telemetry else OBS_NULL)
        # the roofline-grounded per-family step cost model; built once in
        # warmup() when telemetry is live (repro.obs.attrib) — the
        # warmup-only contract: nothing per-step ever lowers or compiles
        self.cost_model = None
        self.params = (prepack_params(params, model.ctx)
                       if prepack and model.cfg.family != "encdec" else params)
        # static-batch path (encdec/vlm generate, throughput baselines);
        # prefill ([B, plen]) and decode ([B, 1]) are two traces of the one
        # model-cached jit — engines over the same model share compilations
        self._step = self._prefill = model.jit_step("decode")

        self.continuous = model.cfg.family not in _STATIC_FAMILIES
        self._next_rid = 0
        if not self.continuous:
            assert not flat, \
                f"{model.cfg.family} serves via generate_static; the flat " \
                f"token-level step needs the continuous paged path"
            assert chunk_tokens is None, \
                f"{model.cfg.family} serves via generate_static; chunked " \
                f"prefill needs the continuous paged path"
            assert spec_tokens is None and drafter is None, \
                f"{model.cfg.family} serves via generate_static; " \
                f"speculative decode needs the continuous paged path"
            assert not prefix_cache, \
                f"{model.cfg.family} serves via generate_static; the " \
                f"prefix cache shares paged KV, which the static path " \
                f"does not use"
            return

        layout = model.ctx.layout(model.compute_dtype)
        all_attn = all(t == "attn" for t in model.cfg.layer_types)
        self._bucket = layout.m_r if all_attn else 1
        self.slots = max_slots or model.shape.global_batch
        max_len = model.shape.seq_len
        page_tokens = round_up(page_tokens, layout.m_r)
        if chunk_tokens is not None:
            assert chunk_tokens >= 1, \
                f"chunk_tokens={chunk_tokens}: a chunk must carry at least " \
                f"one token or prefills can never advance"
            assert all_attn, \
                f"chunked prefill: {model.cfg.name} mixes recurrent layers " \
                f"({model.cfg.layer_types}) — an ssm/rwkv scan carries " \
                f"state through padded chunk rows, so only pure-attention " \
                f"models fuse prefill chunks into the decode step"
            # chunk writes land on whole microkernel tiles, like pages
            chunk_tokens = min(round_up(chunk_tokens, layout.m_r),
                               round_up(max_len, layout.m_r))
        self.chunk_tokens = chunk_tokens
        self.chunked = chunk_tokens is not None
        # flat token-level batching (the default whenever chunking is on):
        # the fused step becomes one [1, W] m_r-packed token stream with
        # per-position row ids — a decode row costs its real 1+k positions
        # instead of a padded chunk-width row.  flat=False keeps the dense
        # [slots, chunk] step as the A/B baseline.
        self.flat = self.chunked if flat is None else bool(flat)
        if self.flat:
            assert self.chunked, \
                "flat=True needs chunk_tokens: the flat token-level step " \
                "rides the chunked scheduler (segments are its chunks)"
        # the fused step is dense, so its device cost is set by the SHAPE
        # (slots x chunk_tokens), not by how many of those positions carry
        # tokens — the rational default budget is therefore shape-limited
        # (throttling below it wastes padded compute); pass a smaller
        # token_budget to bound page-allocation raggedness instead
        self.token_budget = (token_budget if token_budget is not None
                             else max(1, self.slots * (chunk_tokens or 1)))
        assert self.token_budget >= 1
        if self.chunked:
            # liveness: when nothing is decoding, the oldest prefill must
            # be grantable one whole tile (plan_chunks rounds budget-clamped
            # grants down to the tile, so a sub-tile budget would zero
            # every grant forever)
            assert self.token_budget >= layout.m_r, \
                f"token_budget={self.token_budget} is below one microkernel " \
                f"tile (m_r={layout.m_r}); chunked prefill could never advance"
        if num_pages is None:
            num_pages = 1 + self.slots * ceil_div(max_len, page_tokens)
        self.pool = PagedKVPool(num_pages, page_tokens)
        self.pool.obs = self.obs
        self.max_pages = ceil_div(max_len, self.pool.page_tokens)
        # layout-keyed prefix cache: pages are shared byte-for-byte across
        # requests, so the hash chain is rooted in the layout geometry — a
        # layout change can never alias stale KV (pure-attention only: a
        # shared page rebuilds attention state by table lookup, but
        # recurrent scan state cannot be restored from cached pages)
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            assert all_attn, \
                f"prefix cache: {model.cfg.name} mixes recurrent layers " \
                f"({model.cfg.layer_types}) — cached KV pages restore " \
                f"attention state by block-table lookup, but an ssm/rwkv " \
                f"scan state cannot be rebuilt from shared pages"
            assert not eager, \
                "prefix cache needs lazy allocation (eager=True reserves " \
                "full lifetimes, which refcounted shared pages would " \
                "double-count)"
            self.prefix_cache = PrefixCache(self.pool,
                                            layout_key=(layout.m_r,))
            self.prefix_cache.obs = self.obs
            self.pool.page_copier = self._copy_page
        self.scheduler = Scheduler(self.slots, self.pool, max_len,
                                   eager=eager,
                                   watermark_pages=watermark_pages,
                                   chunk_tokens=chunk_tokens,
                                   chunk_align=layout.m_r,
                                   prefix_cache=self.prefix_cache,
                                   queue_limit=queue_limit,
                                   queue_pages=queue_pages,
                                   telemetry=self.obs)
        # resilience ladder (overload + fault handling; faults.py injects,
        # this engine degrades): shed/cancelled requests leave through an
        # out-of-band finished buffer, a stuck drain trips the watchdog,
        # non-finite logits quarantine their row, and a failing drafter is
        # auto-disabled for the rest of the drain
        self.watchdog_steps = watchdog_steps
        self.nan_guard = nan_guard
        self._finished_oob: List[Request] = []
        self._retired_rids: set = set()    # every finished rid (analysis:
                                           # no retired rid may hold pages)
        self._no_progress_steps = 0
        self._watchdog_trips = 0
        self._drafter_errors = 0
        self._drafter_fail_streak = 0
        self._drafter_fail_limit = 3
        self._spec_disabled = False
        self._spec_auto_disables = 0
        # speculative decode (spec_tokens=k): every decode row may carry
        # 1 + k positions through the same fused ragged step
        self.spec_tokens = spec_tokens
        self.drafter: Optional[Drafter] = None
        if spec_tokens is not None:
            assert spec_tokens >= 1, \
                f"spec_tokens={spec_tokens}: speculation needs at least " \
                f"one draft position (use spec_tokens=None to disable)"
            assert all_attn, \
                f"speculative decode: {model.cfg.name} mixes recurrent " \
                f"layers ({model.cfg.layer_types}) — a rejected draft's " \
                f"KV rolls back by page truncation, but an ssm/rwkv scan " \
                f"state cannot un-absorb rejected positions"
            if self.chunked:
                assert self.chunk_tokens >= spec_tokens + 1, \
                    f"spec_tokens={spec_tokens} needs verify rows of " \
                    f"{spec_tokens + 1} positions, wider than " \
                    f"chunk_tokens={self.chunk_tokens} — the fused step's " \
                    f"shape ladder must cover the verify width"
            self.drafter = drafter if drafter is not None else NgramDrafter()
            self.drafter.attach(self)
            self.drafter.obs = self.obs
        else:
            assert drafter is None, "a drafter needs spec_tokens set"
        # step counters (Engine.stats)
        self._steps = 0
        self._step_time = 0.0
        self._active_rows = 0            # rows with new_counts > 0, summed
        self._mixed_steps = 0            # steps carrying >= 1 prefill chunk
        self._finished_count = 0
        self._finished_served = 0        # finished AND actually ran (was
                                         # admitted): the chunks-per-prompt
                                         # denominator — shed/expired-in-
                                         # queue rows never prefilled, so
                                         # counting them would understate it
        self._chunk_steps_total = 0      # prefill calls/chunks over finished
        self._prefill_tokens = 0         # prompt tokens actually computed
                                         # (cache hits skip theirs)
        # flat-step counters (token-exactness telemetry)
        self._flat_steps = 0
        self._flat_tokens = 0            # real tokens fed, summed over steps
        self._flat_width = 0             # compiled widths W, summed
        # speculative counters
        self._draft_time = 0.0           # host wall time inside the drafter
        self._drafted = 0                # draft tokens actually verified
        self._accepted = 0               # draft tokens accepted
        self._decode_tokens = 0          # tokens appended by decode rows
        self._decode_rows = 0            # decode row-steps (verify calls)
        self._spec_trims = 0             # draft lists trimmed by page caps
        self._rollback_pages = 0         # pages freed by rejected-KV truncate
        self.caches = model.init_paged_cache(num_pages, self.pool.page_tokens,
                                             self.slots)
        if mesh is not None:
            specs = sharding.cache_specs(self.caches, mesh, model.run,
                                         self.slots)
            self.caches = jax.device_put(self.caches,
                                         sharding.named(mesh, specs))
        self._paged_step = model.jit_step("paged")
        self._flat_step = model.jit_step("flat") if self.flat else None
        # opt-in runtime sanitizer (analysis.sanitize): wraps the jitted
        # steps with host-side pool-write contract checks — every written
        # page private (ref == 1), in range, never the trash page, and
        # every step width a declared ladder member
        self.sanitizer = None
        if os.environ.get("REPRO_SANITIZE", "0") not in ("", "0"):
            from repro.analysis.sanitize import install as _install_sanitizer
            _install_sanitizer(self)

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-side copy-on-write: duplicate page ``src`` into ``dst``
        across every layer group's K/V pool (installed as the pool's
        ``page_copier``; host bookkeeping lives in ``PagedKVPool.cow``)."""
        self.caches = copy_pages(self.caches, jnp.int32(src), jnp.int32(dst))

    # ------------------------------------------------------------------
    # continuous-batching API
    # ------------------------------------------------------------------
    def add_request(self, tokens, max_new: int, *, eos_id: Optional[int] = None,
                    arrival: float = 0.0, temperature: float = 1.0,
                    seed: Optional[int] = None,
                    deadline_s: Optional[float] = None,
                    max_queue_s: Optional[float] = None) -> int:
        """Queue one request.  Returns its request id.

        ``temperature``/``seed`` are per-request sampling params (one batch
        mixes them freely): ``temperature=0`` forces greedy for this
        request even in a sampled drain; ``seed=None`` inherits the step's
        seed.  Per-request keys are what make sampled decode reproducible
        under preemption and speculation alike.

        ``deadline_s``/``max_queue_s`` bound the request's wall-clock
        lifetime / queue wait relative to ``arrival``, enforced whenever
        ``step(now=...)`` carries a clock.  Under admission control
        (``queue_limit``/``queue_pages``) an over-capacity add is shed:
        the request finishes immediately with ``finish_reason="rejected"``
        (delivered by the next ``step``/``drain``) instead of queueing
        unboundedly — only an *impossible* request (its lifetime can never
        fit the pool) still raises :class:`AdmissionError`."""
        assert self.continuous, \
            f"{self.model.cfg.family} serves via generate_static"
        rid = self._next_rid
        self._next_rid += 1
        prompt = np.asarray(tokens, np.int32).reshape(-1)
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      eos_id=eos_id, arrival=arrival,
                      temperature=temperature, seed=seed,
                      deadline_s=deadline_s, max_queue_s=max_queue_s)
        try:
            self.scheduler.add(req)
        except AdmissionError as e:
            if e.kind == "impossible":
                raise              # a config error, not an overload signal
            req.status = "finished"
            req.finish_reason = "rejected"
            self.obs.request_shed(req, e.kind)
            self._finished_oob.append(req)
        return rid

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Cancel a live request from any lifecycle state (queued,
        prefilling, paused, decoding, mid-spec-rollback).  Its pages are
        released (into the prefix cache when one is attached) and it is
        delivered by the next ``step``/``drain`` with
        ``finish_reason=reason``.  Returns False if ``rid`` is not live."""
        req = self.scheduler.cancel(rid, reason)
        if req is None:
            return False
        self._finished_oob.append(req)
        return True

    @property
    def num_preemptions(self) -> int:
        return self.scheduler.num_preemptions

    @property
    def num_pauses(self) -> int:
        return self.scheduler.num_pauses

    def stats(self) -> dict:
        """Cumulative serving counters: per-step wall time, mean slot
        occupancy (active rows / slots, averaged over steps), prefill-stall
        steps, chunks-per-prompt over finished requests, displacements, XLA
        trace counts (zero growth after :meth:`warmup` is the no-recompile
        contract), plus scheduler and pool sub-stats."""
        assert self.continuous
        steps = max(1, self._steps)
        out = {
            "steps": self._steps,
            "mean_step_ms": 1e3 * self._step_time / steps,
            "mean_slot_occupancy": self._active_rows / (steps * self.slots),
            "mixed_steps": self._mixed_steps,
            "prefill_stall_steps": self.scheduler.prefill_stall_steps,
            "chunks_per_prompt": (self._chunk_steps_total
                                  / max(1, self._finished_served)),
            "finished": self._finished_count,
            "finished_served": self._finished_served,
            "num_preemptions": self.scheduler.num_preemptions,
            "num_pauses": self.scheduler.num_pauses,
            "prefill_tokens": self._prefill_tokens,
            "compiles": dict(self.model.trace_counts),
            "scheduler": self.scheduler.stats(),
            "pool": self.pool.stats(),
        }
        out["resilience"] = {
            "queue_depth": len(self.scheduler.waiting),
            "queue_limit": self.scheduler.queue_limit,
            "queue_pages": self.scheduler.queue_pages,
            "sheds": self.scheduler.num_rejected,
            "timeouts": self.scheduler.num_timeouts,
            "cancels": self.scheduler.num_cancels,
            "quarantines": self.scheduler.num_quarantines,
            "drafter_errors": self._drafter_errors,
            "spec_auto_disables": self._spec_auto_disables,
            "spec_disabled": self._spec_disabled,
            "watchdog_trips": self._watchdog_trips,
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self.obs.enabled:
            # the observability fragment of ROADMAP item 5: goodput
            # (tokens emitted inside deadline_s) and the headline p99s —
            # drain-scoped, like the registry metrics they read
            good = self.obs.c_goodput_tokens.value
            toks = self.obs.c_tokens_out.value
            lat = self.obs.latency_summary()
            out["slo"] = {
                "goodput_tokens": good,
                "tokens_out": toks,
                "goodput_ratio": good / max(1, toks),
                "ttft_p99_s": lat["ttft_s"]["p99"],
                "itl_p99_s": lat["itl_s"]["p99"],
                "e2e_p99_s": lat["e2e_s"]["p99"],
            }
        if self.flat:
            fs = max(1, self._flat_steps)
            out["flat"] = {
                "token_budget": self.token_budget,
                "steps": self._flat_steps,
                "mean_tokens": self._flat_tokens / fs,
                "mean_width": self._flat_width / fs,
                # real tokens per compiled position: the padding tax the
                # flat layout pays (1.0 = none; the dense [slots, chunk]
                # grid pays slots*chunk/real)
                "fill": self._flat_tokens / max(1, self._flat_width),
            }
        if self.spec_tokens is not None:
            out["speculative"] = {
                "spec_tokens": self.spec_tokens,
                "drafted": self._drafted,
                "accepted": self._accepted,
                "acceptance_rate": self._accepted / max(1, self._drafted),
                "accepted_per_step": self._accepted / steps,
                # decode tokens per decode-row activation: the speedup a
                # decode row sees from riding drafts (1.0 = no speculation)
                "decode_tokens_per_row_step": (self._decode_tokens
                                               / max(1, self._decode_rows)),
                "draft_time_ms": 1e3 * self._draft_time,
                "draft_overhead": (self._draft_time / self._step_time
                                   if self._step_time > 0 else 0.0),
                "spec_trims": self._spec_trims,
                "spec_grow_fallbacks": self.scheduler.spec_grow_fallbacks,
                "rollback_pages": self._rollback_pages,
                "drafter": self.drafter.stats(),
            }
        return out

    def telemetry(self, *, reset: bool = False) -> dict:
        """The unified observability view (continuous engine):

        - ``components`` — the classic per-component :meth:`stats` tree
          (engine/scheduler/pool/prefix-cache/drafter counters).  These
          are **lifetime**-cumulative and are never reset, with two
          documented exceptions that are per-drain by design
          (``spec_disabled`` and the drafter fail streak reset at the top
          of every :meth:`drain`) and one bounded window
          (``scheduler.resume_events``, a 256-entry deque).
        - ``metrics`` — the streaming registry snapshot (counters,
          gauges, histograms with p50/p95/p99); its ``_scope`` map labels
          each metric ``drain`` or ``lifetime``.
        - ``latency`` — the headline percentile summaries (TTFT, ITL,
          queue wait, e2e), empty when telemetry is off.
        - ``attribution`` — the per-drain roll-up from
          :mod:`repro.obs.attrib`: wall-time components, per-family
          predicted-vs-measured, MFU/MBU, padding waste, goodput.
        - ``alerts`` — the monitor bank's typed findings
          (:mod:`repro.obs.monitors`), as dicts, newest last.

        ``reset=True`` zeroes the **drain-scoped registry metrics and
        attribution aggregates only**, after the snapshot is taken — the
        explicit per-drain reset (see :mod:`repro.obs.metrics`); nothing
        resets implicitly, so two drains without a reset read as one
        window, never double-counted.  ``stats()`` counters, the cost
        model and the alert history are untouched by ``reset``."""
        obs = self.obs
        out = {
            "enabled": obs.enabled,
            "components": self.stats() if self.continuous else {},
            "metrics": (obs.registry.snapshot()
                        if obs.registry is not None else {}),
            "latency": obs.latency_summary() if obs.enabled else {},
            "attribution": obs.attribution_summary(),
            "alerts": [a.to_dict() for a in obs.alerts],
        }
        if reset:
            obs.reset_drain()
        return out

    def step(self, *, now: Optional[float] = None, greedy: bool = True,
             seed: int = 0) -> List[Request]:
        """One engine step: admit, grow (displacing on pool exhaustion),
        then one fixed-shape batched model call — monolithic policy: a
        per-admission prefill plus a ``[slots, 1]`` decode; chunked policy:
        a single fused ragged ``[slots, chunk_tokens]`` step in which every
        active row carries 1 (decoding) to ``chunk_tokens`` (prefilling)
        new positions.  Returns requests finished during this step —
        including requests shed at admission, cancelled via
        :meth:`cancel`, and (when ``now`` carries a clock) requests whose
        ``deadline_s``/``max_queue_s`` elapsed, with finish reasons
        ``rejected``/``cancelled``/``timeout``/``error``.

        The step is one ``engine.step`` span of the telemetry's
        (:meth:`repro.obs.telemetry.NullTelemetry.span`), its phases
        spans inside it."""
        t0 = time.perf_counter()
        with self.obs.span("engine.step"):
            self.obs.step_begin()
            if self.flat:
                return self._step_flat(t0, now, greedy, seed)
            finished = self._take_finished(now)
            if self.chunked:
                finished.extend(self._step_chunked(now, greedy, seed))
            else:
                finished.extend(self._step_monolithic(now, greedy, seed))
            self._end_step(t0, now, finished)
            return finished

    def _take_finished(self, now) -> List[Request]:
        """Requests shed or cancelled since the previous step, and those
        whose deadlines ``now`` passed."""
        finished = list(self._finished_oob)
        self._finished_oob.clear()
        if now is not None:
            finished.extend(self.scheduler.expire(now))
        return finished

    def _end_step(self, t0: float, now, finished: List[Request]) -> None:
        """The step's bookkeeping once its picks are made."""
        # idle ticks (an online replay polling before the next arrival) do
        # no work and must not dilute the per-step stats
        if self.scheduler.running or finished:
            self._steps += 1
            self._step_time += time.perf_counter() - t0
            self._no_progress_steps = 0
        else:
            self._watchdog(now)
        for req in finished:
            self._finished_count += 1
            if req.admit_seq >= 0:
                self._finished_served += 1
            self._chunk_steps_total += req.chunk_steps
            self._retired_rids.add(req.rid)
            if self.drafter is not None:
                self.drafter.forget(req.rid)
        self.obs.step_end(self.scheduler, self.pool, finished, now=now)

    def _watchdog(self, now) -> None:
        """A step that admitted, advanced and finished nothing while
        already-arrived work sat waiting is a stall symptom.  One is
        legal (a displacement can empty the running set for a step);
        after ``watchdog_steps`` consecutive ones the drain is provably
        stuck — the termination proof guarantees the waiting head is
        eventually admitted, so a persistent no-progress streak means
        that guarantee was broken (e.g. a fault left the pool
        unsatisfiable) — and the watchdog turns the silent spin into a
        diagnosable :class:`StallError` naming the non-advancing rids."""
        stuck = [r for r in self.scheduler.waiting
                 if now is None or r.arrival <= now]
        if not stuck:
            self._no_progress_steps = 0          # idle poll before arrivals
            return
        self._no_progress_steps += 1
        if self._no_progress_steps >= self.watchdog_steps:
            self._watchdog_trips += 1
            self._no_progress_steps = 0
            raise StallError(
                f"no request advanced for {self.watchdog_steps} "
                f"consecutive steps; waiting: " +
                ", ".join(f"rid {r.rid} ({r.status}, cursor "
                          f"{r.prefill_cursor}/{r.prompt_len})"
                          for r in stuck) +
                f"; pool: {self.pool.num_available} of "
                f"{self.pool.usable_pages} pages available")

    def _quarantine(self, req: Request, finished: List[Request]) -> None:
        """Degradation ladder, bottom rung: a poisoned row (non-finite
        logits, failed rollback) is retired alone — pages freed, nothing
        inserted into the prefix cache — instead of poisoning the batch
        or the cache.  Survivors are unaffected: rows are independent
        and picks are (seed, rid, position)-keyed."""
        self.scheduler.cancel(req.rid, "error", cache_pages=False)
        finished.append(req)

    def _step_monolithic(self, now, greedy: bool, seed: int) -> List[Request]:
        finished = []
        # one admission at a time: each prefill lands its pages in the
        # prefix cache before the next admission's lookup runs, so
        # same-step arrivals sharing a prompt prefix share pages too
        # (without a cache this is byte-identical to batch admission)
        while True:
            admitted = self.scheduler.admit(now, limit=1)
            if not admitted:
                break
            req = admitted[0]
            if not self._prefill_request(req, greedy, seed):
                finished.append(req)             # quarantined at prefill
                continue
            if req.done():
                self.scheduler.finish(req)
                finished.append(req)
        # growth runs oldest-admission-first, so a just-prefilled arrival is
        # the preferred preemption victim; a preempted request simply drops
        # out of `running`, leaving its decode row with new_counts == 0 and
        # a zero block table — the fixed-shape step masks it into the trash
        # page mid-step instead of recompiling to a smaller batch.  Drafts
        # are proposed first so growth can book the k+1-token speculative
        # ask (a preempted row's proposal is simply dropped with the row)
        drafts = self._draft_and_grow()
        running = self.scheduler.running
        if running:
            neff = self._grant_drafts(running, drafts)
            b, mp = self.slots, self.max_pages
            # two compiled decode shapes: [slots, 1] (no drafts anywhere
            # this step) and the verify shape [slots, spec_tokens+1]
            spec = max(neff.values()) > 1
            s = self.spec_tokens + 1 if spec else 1
            token = np.zeros((b, s), np.int32)
            lens = np.zeros((b,), np.int32)
            counts = np.zeros((b,), np.int32)
            bt = np.zeros((b, mp), np.int32)
            idx = np.zeros((b, s), np.int32) if spec else None
            for slot, req in running.items():
                self._fill_decode_row(slot, req, neff[slot], drafts,
                                      token, lens, counts, bt, idx)
            self._active_rows += len(running)
            rows = self._run_paged(token, bt, lens, counts, idx,
                                   prefill_tokens=0,
                                   decode_tokens=int(counts.sum()))
            self.obs.step_family(
                f"verify[{b},{s}]" if spec else f"decode[{b},1]",
                int(counts.sum()), b * s)
            for slot, req in list(running.items()):
                self._verify_decode_row(req, drafts.get(slot, []), rows[slot],
                                        neff[slot], greedy, seed, finished)
        return finished

    def _step_chunked(self, now, greedy: bool, seed: int) -> List[Request]:
        """The fused ragged step.  Decoding rows carry their fed-back token
        at position ``len`` (``new_counts == 1``); prefilling rows carry the
        next ``plan[slot]``-token slice of their prompt at positions
        ``prefill_cursor ..`` (``new_counts == n``); displaced/stalled/free
        rows are inert (``new_counts == 0``, zero block table — masked into
        the trash page).  Causal masking *within* a chunk against the paged
        past comes from the per-row 2-D positions the paged attention path
        already implements, so a chunk's logits at its last valid token
        equal the monolithic prefill's — chunking is invisible in the
        tokens (asserted by tests and the benchmark A/B)."""
        sched = self.scheduler
        finished = []
        sched.admit(now)
        # decode growth first: decodes are never stalled behind prefill work
        # (Sarathi's decode-prioritized schedule); a mid-prefill victim is
        # paused with its pages, not recomputed.  Speculation rides the
        # same fused step: a decode row's new_counts becomes 1 + its draft
        # length, pulled from the same shape ladder as prefill chunks
        drafts = self._draft_and_grow()
        running = sched.running
        if not running:
            return finished
        neff = self._grant_drafts(running, drafts)
        ndecode = sum(neff.values())
        plan = sched.plan_chunks(self.token_budget - ndecode)
        use_chunk = any(n > 0 for n in plan.values())
        b, mp = self.slots, self.max_pages
        widest = max(max(plan.values(), default=0),
                     max(neff.values(), default=0))
        s = self._chunk_shape(widest) if (use_chunk or widest > 1) else 1
        spec = any(n > 1 for n in neff.values())
        k1 = self.spec_tokens + 1 if spec else 1
        token = np.zeros((b, s), np.int32)
        lens = np.zeros((b,), np.int32)
        counts = np.zeros((b,), np.int32)
        bt = np.zeros((b, mp), np.int32)
        idx = np.zeros((b, k1), np.int32) if spec else None
        for slot, req in running.items():
            if req.status == "running":
                self._fill_decode_row(slot, req, neff[slot], drafts,
                                      token, lens, counts, bt, idx)
            else:
                n = plan.get(slot, 0)
                if n == 0:
                    continue              # stalled this step: inert row
                cur = req.prefill_cursor
                token[slot, :n] = req.prompt[cur:cur + n]
                lens[slot] = cur
                counts[slot] = n
                bt[slot] = req.pages.block_row(mp)
                if spec:
                    idx[slot] = n - 1     # its last chunk token, read at j=0
        total_new = int(counts.sum())
        if total_new == 0:
            self._watchdog_trips += 1
            raise StallError(
                "fused step scheduled zero tokens with live slots: " +
                ", ".join(f"rid {r.rid} ({r.status}, cursor "
                          f"{r.prefill_cursor}/{r.prompt_len}, len {r.len})"
                          for r in running.values()))
        # decodes (and their drafts) are unconditional; only prefill tokens
        # are budget-capped
        assert total_new <= max(self.token_budget, ndecode)
        self._active_rows += int((counts > 0).sum())
        self._mixed_steps += int(use_chunk)
        rows = self._run_paged(token, bt, lens, counts, idx,
                               prefill_tokens=total_new - ndecode,
                               decode_tokens=ndecode)
        self.obs.step_family(f"chunk[{b},{s}]" + ("/verify" if spec else ""),
                             total_new, b * s)
        for slot, req in list(running.items()):
            if req.status == "running":
                self._verify_decode_row(req, drafts.get(slot, []), rows[slot],
                                        neff[slot], greedy, seed, finished)
            else:
                n = plan.get(slot, 0)
                if n == 0:
                    continue
                if self.nan_guard and not np.isfinite(rows[slot]).all():
                    # before the cursor advance and the cache insert:
                    # a poisoned chunk's pages must never be shared
                    self._quarantine(req, finished)
                    continue
                req.prefill_cursor += n
                req.len = req.prefill_cursor
                req.chunk_steps += 1
                self._prefill_tokens += n
                self.obs.request_prefill_chunk(req, n)
                if self.prefix_cache is not None:
                    # write newly-completed full pages into the cache as
                    # the cursor advances — a later arrival (or this
                    # request's own preempt-resume) shares them mid-stream
                    self.prefix_cache.insert(req.prompt, req.pages.pages,
                                             req.prefill_cursor)
                if req.prefill_cursor < req.prompt_len:
                    continue              # more chunks to come
                # prefill complete: the logits at the last prompt token are
                # the first-token distribution, exactly as in monolithic
                req.status = "running"
                self.obs.request_prefill_done(req)
                req.out_tokens.append(
                    self._pick(rows[slot, 0], req, greedy, seed))
                if req.done():
                    sched.finish(req)
                    finished.append(req)
        return finished

    def _step_flat(self, t0: float, now, greedy: bool,
                   seed: int) -> List[Request]:
        """The flat token-level step (vLLM/Sarathi-style flat batching; the
        paper's fixed-shape-grid argument at token granularity).  One
        ``[1, W]`` stream — ``W`` from a geometric ladder over the token
        budget, ``m_r``-aligned — carries every scheduled row as a
        contiguous *segment*: per-position ``row_ids`` (-1 = padding) and
        absolute ``q_pos`` replace the dense step's per-row
        ``lens``/``new_counts``, and the segment-aware causal ragged
        attention (kernels/ragged_attn) reads each position's own row.  A
        decode row costs exactly its 1 + drafts real positions — no
        chunk-width padding tax — so the budget is token-exact.  Scheduling
        (admission, growth, chunk planning, stalls, preemption) is byte-
        identical to the dense chunked step; only the layout of the fed
        tokens changes, and outputs stay token-identical to both the dense
        and monolithic policies (asserted by tests/test_flat_step.py).

        Its phases are the spans ``engine.schedule``, ``engine.pack``,
        the three of :meth:`_call`, and ``engine.commit``, which ends
        with the step's bookkeeping (:meth:`_end_step`)."""
        sched = self.scheduler
        obs = self.obs
        segs = []
        with obs.span("engine.schedule"):
            finished = self._take_finished(now)
            sched.admit(now)
            drafts = self._draft_and_grow()
            running = sched.running
            if running:
                neff = self._grant_drafts(running, drafts)
                decode_counts = {s: n for s, n in neff.items() if n > 0}
                segs = sched.plan_segments(decode_counts, self.token_budget)
                total = sum(n for _, _, n in segs)
                if total == 0:
                    self._watchdog_trips += 1
                    raise StallError(
                        "flat step scheduled zero tokens with live slots: "
                        + ", ".join(f"rid {r.rid} ({r.status}, cursor "
                                    f"{r.prefill_cursor}/{r.prompt_len}, "
                                    f"len {r.len})"
                                    for r in running.values()))
                ndecode = sum(decode_counts.values())
                # decodes (and their drafts) are unconditional; only
                # prefill tokens are budget-capped — token-exact, not
                # shape-limited
                assert total <= max(self.token_budget, ndecode)
                prefill = sum(n for _, kind, n in segs if kind == "prefill")
        segrefs = []
        if segs:
            with obs.span("engine.pack"):
                w = self._flat_shape(total)
                spec = any(n > 1 for n in decode_counts.values())
                k1 = self.spec_tokens + 1 if spec else 1
                token = np.zeros((1, w), np.int32)
                row_ids = np.full((w,), -1, np.int32)
                q_pos = np.zeros((w,), np.int32)
                bt = np.zeros((self.slots, self.max_pages), np.int32)
                idx = np.zeros((self.slots * k1,), np.int32)
                pos = 0
                for slot, kind, n in segs:
                    req = running[slot]
                    if kind == "decode":
                        token[0, pos] = req.out_tokens[-1]
                        if n > 1:
                            token[0, pos + 1:pos + n] = drafts[slot]
                        q_pos[pos:pos + n] = req.len + np.arange(n)
                    else:
                        cur = req.prefill_cursor
                        token[0, pos:pos + n] = req.prompt[cur:cur + n]
                        q_pos[pos:pos + n] = cur + np.arange(n)
                    row_ids[pos:pos + n] = slot
                    bt[slot] = req.pages.block_row(self.max_pages)
                    # decode rows read logits after every fed position
                    # (clamped to their own width); prefill rows read
                    # their last chunk token
                    if kind == "decode":
                        idx[slot * k1:(slot + 1) * k1] = \
                            pos + np.minimum(np.arange(k1), n - 1)
                    else:
                        idx[slot * k1:(slot + 1) * k1] = pos + n - 1
                    segrefs.append((slot, kind, n, req))
                    pos += n
                self._active_rows += len(segrefs)
                self._mixed_steps += int(prefill > 0)
                self._flat_steps += 1
                self._flat_tokens += total
                self._flat_width += w
            rows = self._run_flat(token, bt, row_ids, q_pos, idx,
                                  prefill_tokens=prefill,
                                  decode_tokens=total - prefill)
        with obs.span("engine.commit"):
            if segrefs:
                obs.step_family(f"flat[1,{w}]/k{k1}", total, w)
                rows = rows.reshape(self.slots, k1, -1)
            for slot, kind, n, req in segrefs:
                if kind == "decode":
                    self._verify_decode_row(req, drafts.get(slot, []),
                                            rows[slot], n, greedy, seed,
                                            finished)
                    continue
                if self.nan_guard and not np.isfinite(rows[slot]).all():
                    self._quarantine(req, finished)  # before the insert
                    continue
                req.prefill_cursor += n
                req.len = req.prefill_cursor
                req.chunk_steps += 1
                self._prefill_tokens += n
                obs.request_prefill_chunk(req, n)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(req.prompt, req.pages.pages,
                                             req.prefill_cursor)
                if req.prefill_cursor < req.prompt_len:
                    continue              # more chunks to come
                req.status = "running"
                obs.request_prefill_done(req)
                req.out_tokens.append(
                    self._pick(rows[slot, 0], req, greedy, seed))
                if req.done():
                    sched.finish(req)
                    finished.append(req)
            self._end_step(t0, now, finished)
        return finished

    def _run_flat(self, token, bt, row_ids, q_pos, idx, *,
                  prefill_tokens: int, decode_tokens: int) -> np.ndarray:
        """One flat step; returns logits [K_out, V] at the flat ``idx``
        positions (K_out = slots * (spec_tokens+1 or 1)).  The token
        counts label the ``engine.dispatch`` span, with ``kv_pages``: the
        (position, live page) pairs the ragged-attention kernel fetches."""
        kv_pages = int(np.sum(q_pos[row_ids >= 0] // self.pool.page_tokens
                              + 1))
        logits, self.caches = self._call(
            self._flat_step, self.caches, (token, bt, row_ids, q_pos, idx),
            width=token.shape[1], prefill_tokens=prefill_tokens,
            decode_tokens=decode_tokens, kv_pages=kv_pages)
        return logits[0]

    def _call(self, step_fn, caches, inputs, **args):
        """Run the jitted ``step_fn(params, caches, *inputs)`` on host
        ``inputs`` and bring its logits to the host, in three spans:
        ``engine.dispatch`` (inputs sent, the program enqueued; ``args``
        and the engine's step count label it), ``engine.device_wait``
        (until the logits are ready) and ``engine.logits_to_host`` (the
        copy alone).  Returns (host logits, new caches)."""
        obs = self.obs
        with obs.span("engine.dispatch", step=self._steps, **args):
            logits, caches = step_fn(
                self.params, caches,
                *(None if a is None else jnp.asarray(a) for a in inputs))
        with obs.span("engine.device_wait"):
            jax.block_until_ready(logits)
        with obs.span("engine.logits_to_host", bytes=logits.nbytes):
            return np.asarray(logits), caches

    def _flat_shapes(self) -> List[int]:
        """The flat step's geometric width ladder, descending: the token
        budget's ``m_r``-aligned cap (raised to ``slots * (spec_tokens+1)``
        when speculation can outgrow the budget — decode tokens are
        unconditional) plus every power-of-two multiple of ``m_r`` below
        it.  A decode-only step rides a width near its real token count
        instead of the full budget; compile count stays logarithmic."""
        cap = round_up(max(self.token_budget,
                           self.slots * ((self.spec_tokens or 0) + 1)),
                       self._bucket)
        shapes = {cap}
        v = self._bucket
        while v < cap:
            shapes.add(v)
            v *= 2
        return sorted(shapes, reverse=True)

    def _flat_shape(self, n: int) -> int:
        """Smallest ladder width holding ``n`` flat tokens."""
        shapes = self._flat_shapes()
        s = shapes[0]
        for cand in shapes:
            if cand >= n:
                s = cand
        return s

    # ------------------------------------------------------------------
    # speculative decode plumbing (no-ops when spec_tokens is None: every
    # row proposes nothing, carries n_eff == 1, and the verify loop
    # degenerates to the baseline one-pick decode)
    # ------------------------------------------------------------------
    def _propose_drafts(self) -> dict:
        """``{slot: [draft tokens]}`` for decoding rows, trimmed so a draft
        can never outlive ``max_new`` (the final generated token is never
        fed back, so at most ``max_new - generated - 1`` fed positions
        remain useful).  Host wall time is accounted as draft overhead.

        Degradation ladder: a drafter exception costs only that step's
        drafts (rows decode one token, same acceptance path, identical
        tokens); ``_drafter_fail_limit`` *consecutive* failures
        auto-disable speculation for the rest of the drain — a broken
        drafter degrades throughput, never correctness or liveness."""
        if self.drafter is None or self._spec_disabled:
            return {}
        with self.obs.span("engine.draft"):
            t0 = time.perf_counter()
            jobs, slot_of = [], {}
            for slot, req in self.scheduler.running.items():
                if req.status != "running":
                    continue
                k = min(self.spec_tokens,
                        req.max_new - len(req.out_tokens) - 1)
                if k <= 0:
                    continue
                jobs.append((req, k))
                slot_of[req.rid] = slot
            drafts = {}
            if jobs:
                # one batched call for the whole step's rows — a model-backed
                # drafter runs one [slots, 1] step per draft position instead
                # of k sequential [1, 1] steps per row (Drafter.propose_all;
                # the base class degenerates to the per-row loop)
                try:
                    proposals = self.drafter.propose_all(jobs)
                except Exception:
                    self._drafter_errors += 1
                    self._drafter_fail_streak += 1
                    self.obs.drafter_error()
                    if self._drafter_fail_streak >= self._drafter_fail_limit:
                        self._spec_disabled = True
                        self._spec_auto_disables += 1
                    proposals = {}
                else:
                    self._drafter_fail_streak = 0
                for req, k in jobs:
                    d = [int(t) for t in proposals.get(req.rid, [])][:k]
                    if d:
                        drafts[slot_of[req.rid]] = d
            self._draft_time += time.perf_counter() - t0
            return drafts

    def _draft_and_grow(self):
        """Propose drafts, then grow with the per-row ``1 + draft length``
        speculative ask (``grow`` sheds an ask rather than letting it force
        a displacement).  Returns the proposals, keyed by slot."""
        drafts = self._propose_drafts()
        self.scheduler.grow(want={s: 1 + len(d) for s, d in drafts.items()}
                            if drafts else None)
        return drafts

    def _fill_decode_row(self, slot: int, req: Request, n: int, drafts: dict,
                         token, lens, counts, bt, idx) -> None:
        """One decode row of the fused batch: the fed-back token plus the
        row's granted drafts at positions ``req.len ..``; ``idx`` (when the
        step carries any drafted row) reads logits at each fed position,
        clamped to the row's own width."""
        token[slot, 0] = req.out_tokens[-1]
        if n > 1:
            token[slot, 1:n] = drafts[slot]
        lens[slot] = req.len
        counts[slot] = n
        bt[slot] = req.pages.block_row(bt.shape[1])
        if idx is not None:
            idx[slot] = np.minimum(np.arange(idx.shape[1]), n - 1)

    def _grant_drafts(self, running, drafts) -> dict:
        """Per-row verify width actually granted: the fed-back token plus
        as many drafts as the row's post-grow page capacity covers —
        ``grow`` sheds a speculative ask under pool pressure rather than
        preempting for tokens that may be rejected, and page rounding can
        cover a draft or two for free.  Trims ``drafts`` in place; returns
        ``{slot: n_eff}`` (0 for prefilling rows, whose widths come from
        ``plan_chunks``)."""
        neff = {}
        for slot, req in running.items():
            if req.status != "running":
                neff[slot] = 0
                continue
            n = 1
            d = drafts.get(slot)
            if d:
                n = max(1, min(1 + len(d), req.pages.capacity - req.len))
                if len(d) > n - 1:
                    self._spec_trims += 1
                    if n == 1:
                        del drafts[slot]
                    else:
                        drafts[slot] = d[:n - 1]
            neff[slot] = n
        return neff

    def _run_paged(self, token, bt, lens, counts, idx, *,
                   prefill_tokens: int, decode_tokens: int) -> np.ndarray:
        """One fused paged step; returns per-row logits [B, K, V] (K = 1
        without speculation).  The token counts label the
        ``engine.dispatch`` span."""
        logits, self.caches = self._call(
            self._paged_step, self.caches, (token, bt, lens, counts, idx),
            width=token.size, prefill_tokens=prefill_tokens,
            decode_tokens=decode_tokens)
        return logits

    def _verify_decode_row(self, req: Request, drafts: List[int],
                           rows_slot: np.ndarray, n: int, greedy: bool,
                           seed: int, finished: List[Request]) -> None:
        """Accept the row's draft prefix (token-identical rule — see
        :mod:`repro.serving.speculative`), advance the cache length by the
        tokens whose KV is now live, truncate the block table past them
        (rejected-KV rollback), and retire the request if it completed.
        A non-finite logits row is quarantined *before* any token is
        committed; a rollback whose CoW split fails is quarantined after
        (its block table no longer matches its committed length, so the
        next step could read rejected KV) — either way pages are freed
        and nothing reaches the prefix cache."""
        if self.nan_guard and not np.isfinite(rows_slot).all():
            self._quarantine(req, finished)
            return
        appended, accepted = accept_tokens(
            req, drafts, rows_slot, n,
            lambda row, rq: self._pick(row, rq, greedy, seed))
        req.len += appended
        self._decode_rows += 1
        self._decode_tokens += appended
        if n > 1:
            self._drafted += n - 1
            self._accepted += accepted
            try:
                freed = req.pages.truncate(req.len)
            except PoolError:
                self._quarantine(req, finished)
                return
            self._rollback_pages += freed
            if freed:
                self.obs.spec_rollback(req, freed)
            # mid-draft eos (or any early stop): the block table must end
            # exactly at the last committed token — a page past it could
            # carry rejected/post-eos draft KV into a later prefix-cache
            # insert (preemption inserts up to req.len, but only pages
            # that exist can ever be shared)
            assert len(req.pages.pages) == self.pool.pages_for(req.len), \
                f"rollback left {len(req.pages.pages)} pages for " \
                f"len={req.len} (expected {self.pool.pages_for(req.len)})"
        if req.done():
            self.scheduler.finish(req)
            finished.append(req)

    def drain(self, *, greedy: bool = True, seed: int = 0,
              now: Optional[float] = None) -> List[Request]:
        """Run steps until every queued request has finished (including
        shed/cancelled/expired ones, delivered with their finish
        reasons).  The speculative auto-disable ladder is per-drain: a
        fresh drain gets its drafter back."""
        finished = []
        while self.scheduler.has_work or self._finished_oob:
            finished.extend(self.step(now=now, greedy=greedy, seed=seed))
        self._spec_disabled = False
        self._drafter_fail_streak = 0
        return finished

    def _prefill_bucket(self, l: int) -> int:
        """Geometric (power-of-two tile-multiple) prefill bucket for a
        prompt of ``l`` tokens.  Preemption folds generated tokens into the
        prompt, so recompute prefills arrive at arbitrary lengths — linear
        ``round_up(l, m_r)`` bucketing would compile a fresh XLA program
        per distinct length, unbounded over a server's lifetime.  Geometric
        buckets cap the compile count at ``log2(max_len / m_r) + 1`` for at
        most 2x padded prefill compute (padding is masked into the trash
        page).  Only pure-attention models bucket (``_bucket > 1``):
        recurrent mixers carry state over *every* prefill token — padding
        is invisible to the KV mask but not to an ssm/rwkv scan — so hybrid
        archs prefill at exact length, as before."""
        if self._bucket == 1:
            return l
        b = self._bucket
        while b < l:
            b *= 2
        return min(b, round_up(self.scheduler.max_len, self._bucket))

    def _chunk_shapes(self) -> List[int]:
        """The fused step's geometric shape ladder: ``chunk_tokens`` halved
        down to the layout tile (``m_r``), descending.  A step only pays
        for the largest chunk it actually carries — a final remainder chunk
        or a short-prompt admission rides a half/quarter-size shape — while
        the compile count stays ``log2(chunk/m_r)+2`` with the ``[slots,1]``
        decode shape, still below the monolithic policy's prompt buckets."""
        shapes = [self.chunk_tokens]
        while (shapes[-1] % 2 == 0 and shapes[-1] // 2 >= self._bucket
               and (shapes[-1] // 2) % self._bucket == 0):
            shapes.append(shapes[-1] // 2)
        return shapes

    def _chunk_shape(self, n: int) -> int:
        """Smallest ladder shape holding an ``n``-token chunk."""
        s = self.chunk_tokens
        for cand in self._chunk_shapes():
            if cand >= n:
                s = cand
        return s

    def warmup(self) -> None:
        """Pre-compile every step shape this engine can hit before taking
        traffic (:meth:`_warmup_shapes`), then — when telemetry is live —
        build the roofline-grounded per-family step cost model
        (:func:`repro.obs.attrib.build_cost_model`): every just-compiled
        family is lowered once more with ``ShapeDtypeStruct`` stand-ins
        (fresh ``jax.jit`` wrappers, so the counted ``jit_step`` caches
        and the zero-post-warmup-trace invariant are untouched) and priced
        against the host's :class:`~repro.core.hardware.HardwareSpec`.
        This is the **warmup-only cost-model contract**: prediction
        happens here and only here; per-step attribution is dict lookups
        on the frozen model, nothing per-step ever lowers, compiles, or
        reaches a jitted function."""
        self._warmup_shapes()
        if self.obs.enabled:
            from repro.obs.attrib import build_cost_model
            self.cost_model = build_cost_model(self)
            self.obs.attach_cost_model(self.cost_model)

    def _warmup_shapes(self) -> None:
        """Pre-compile every step shape this engine can hit before taking
        traffic — chunked: the fused ``[slots, c]`` step for every ladder
        shape ``c`` (``chunk_tokens`` halved down to ``m_r``) plus the
        ``[slots, 1]`` decode step; monolithic: the
        decode step plus each geometric prefill bucket.  With speculation
        on, additionally the verify variants: each decode-capable shape
        with the ``[slots, spec_tokens+1]`` logits gather (drafted steps
        read k+1 positions per row), and the drafter's own step shapes
        (``Drafter.warmup``).  After warmup a
        trace with admissions, chunked prefills, growth, preemption and
        speculation triggers zero new XLA compilations (regression-tested
        via the model's trace counter).  Safe on an idle engine: the warmup
        calls run with ``new_counts == 0``, which routes every KV write to
        the trash page, so pool pages and live state are untouched."""
        assert self.continuous
        assert not self.scheduler.has_work, "warmup() needs an idle engine"
        if self.prefix_cache is not None:
            # prime the CoW page-copy program (trash page onto itself:
            # contents are garbage by definition, live pages untouched)
            self._copy_page(0, 0)
        zb = jnp.zeros((self.slots,), jnp.int32)
        btb = jnp.zeros((self.slots, self.max_pages), jnp.int32)
        if self.flat:
            # every ladder width × every logits-gather width (spec steps
            # read slots*(k+1) flat positions, draft-free steps slots*1);
            # all-padding streams (row_ids == -1) route writes to the trash
            # page, so live state is untouched
            k1s = [1] + ([self.spec_tokens + 1]
                         if self.spec_tokens is not None else [])
            for w in self._flat_shapes():
                pad = jnp.full((w,), -1, jnp.int32)
                qz = jnp.zeros((w,), jnp.int32)
                for k1 in k1s:
                    _, self.caches = self._flat_step(
                        self.params, self.caches,
                        jnp.zeros((1, w), jnp.int32), btb, pad, qz,
                        jnp.zeros((self.slots * k1,), jnp.int32))
            if self.spec_tokens is not None:
                self.drafter.warmup()
            return
        idxz = (None if self.spec_tokens is None else
                jnp.zeros((self.slots, self.spec_tokens + 1), jnp.int32))
        if self.chunked:
            for s in self._chunk_shapes() + [1]:
                _, self.caches = self._paged_step(
                    self.params, self.caches,
                    jnp.zeros((self.slots, s), jnp.int32), btb, zb, zb, None)
            if idxz is not None:
                # any ladder shape can carry drafted rows (verify width
                # rides the chunk ladder; [slots, 1] never does — a drafted
                # step is at least spec_tokens+1 wide)
                for s in self._chunk_shapes():
                    _, self.caches = self._paged_step(
                        self.params, self.caches,
                        jnp.zeros((self.slots, s), jnp.int32), btb, zb, zb,
                        idxz)
                self.drafter.warmup()
            return
        zero = jnp.zeros((1,), jnp.int32)
        bt1 = jnp.zeros((1, self.max_pages), jnp.int32)
        if self._bucket > 1:       # hybrids prefill at exact (unbounded)
            b, seen = self._bucket, set()    # lengths — nothing to pre-compile
            while True:
                bucket = self._prefill_bucket(b)
                if bucket in seen:
                    break
                seen.add(bucket)
                view = prefill_view(self.caches,
                                    fresh_slot_states(self.caches))
                _, updated = self._paged_step(
                    self.params, view, jnp.zeros((1, bucket), jnp.int32), bt1,
                    zero, zero, None)
                self.caches = merge_slot(self.caches, updated, 0)
                b = bucket + 1
        _, self.caches = self._paged_step(
            self.params, self.caches, jnp.zeros((self.slots, 1), jnp.int32),
            btb, zb, zb, None)
        if idxz is not None:       # the monolithic verify shape
            _, self.caches = self._paged_step(
                self.params, self.caches,
                jnp.zeros((self.slots, self.spec_tokens + 1), jnp.int32),
                btb, zb, zb, idxz)
            self.drafter.warmup()

    def _prefill_request(self, req: Request, greedy: bool, seed: int) -> bool:
        """Prefill one admitted request at its own length (rounded up to a
        geometric packed-tile bucket so prompt-length compilations stay
        bounded and amortize across requests; padded rows are masked into
        the trash page).  With a prefix cache, admission already parked the
        cursor at the hit, so only the uncached suffix is computed — the
        shared prefix pages enter the step read-only through the block
        table, exactly like a decode row's past (lens = cursor).  Returns
        False when the row was quarantined for non-finite logits (before
        its KV is merged or its pages reach the prefix cache)."""
        l = req.prompt_len
        start = req.prefill_cursor
        n = l - start
        bucket = self._prefill_bucket(n)
        token = np.zeros((1, bucket), np.int32)
        token[0, :n] = req.prompt[start:]
        bt = req.pages.block_row(self.max_pages)[None]
        view = prefill_view(self.caches, fresh_slot_states(self.caches))
        logits, updated = self._call(
            self._paged_step, view,
            (token, bt, np.full((1,), start, np.int32),
             np.full((1,), n, np.int32), None),
            width=bucket, prefill_tokens=n, decode_tokens=0)
        row = logits[0, 0, :]
        self.obs.step_family(f"prefill[1,{bucket}]", n, bucket)
        if self.nan_guard and not np.isfinite(row).all():
            self.scheduler.cancel(req.rid, "error", cache_pages=False)
            return False
        self.caches = merge_slot(self.caches, updated, req.slot)
        req.len = l
        req.prefill_cursor = l
        req.chunk_steps += 1        # a monolithic prefill is one big chunk
        self._prefill_tokens += n
        self.obs.request_prefill_chunk(req, n)
        self.obs.request_prefill_done(req)
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, req.pages.pages, l)
        req.out_tokens.append(self._pick(row, req, greedy, seed))
        return True

    def _pick(self, logits_row: np.ndarray, req: Request, greedy: bool,
              seed: int) -> int:
        if greedy or req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        # per-request, per-position key: sampling is reproducible and
        # independent of batch composition, like the greedy path — and of
        # speculation, whose acceptance rule recomputes exactly these picks
        s = seed if req.seed is None else req.seed
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(s), req.rid), len(req.out_tokens))
        row = jnp.asarray(logits_row)
        if req.temperature != 1.0:
            row = row / jnp.float32(req.temperature)
        return int(jax.random.categorical(key, row))

    # ------------------------------------------------------------------
    # batch API
    # ------------------------------------------------------------------
    def generate(self, batch: dict, max_new: int, *, greedy: bool = True,
                 seed: int = 0, eos_id: Optional[int] = None,
                 return_reasons: bool = False,
                 deadline_s: Optional[float] = None):
        """batch: {"tokens": [B, L] prompt, (+frames/patches)}.

        Returns [B, max_new] generated tokens; rows that finish early —
        ``eos_id`` hit, but also ``timeout``/``rejected``/``error`` under
        deadlines, admission control or quarantine — are padded to the
        full width exactly like eos rows (with ``eos_id``, or 0 when no
        eos is set), so rows never produce ragged lengths and the result
        always stacks.  With ``return_reasons=True`` also returns a
        length-B list of finish reasons ("eos" | "length" | "timeout" |
        "rejected" | "error").  ``deadline_s`` bounds each row's
        wall-clock lifetime (continuous engine only): the drain runs on a
        real clock and overdue rows finish with ``"timeout"``.
        Compatibility wrapper: for decoder-only families each row becomes
        a request served by the continuous engine (results are identical
        to serving it alone); encdec/vlm use the static path, where eos
        rows are truncated-and-padded post hoc.
        """
        if not self.continuous:
            assert deadline_s is None, \
                "deadline_s needs the continuous engine (the static path " \
                "decodes lock-step, with no per-request lifecycle)"
            # np.array: the static path hands back a buffer backed by a jax
            # array, which numpy imports read-only — copy before padding
            out = np.array(self.generate_static(batch, max_new, greedy=greedy,
                                                seed=seed))
            reasons = ["length"] * out.shape[0]
            if eos_id is not None:
                for i in range(out.shape[0]):
                    # one shared classification rule with the continuous
                    # path (scheduler.finish_reason_for) — the two can
                    # never drift: eos on the final token is "length"
                    kept, reasons[i] = finish_reason_for(out[i], max_new,
                                                         eos_id)
                    if reasons[i] == "eos":
                        out[i, kept - 1:] = eos_id
            return (out, reasons) if return_reasons else out
        assert not self.scheduler.has_work and not self._finished_oob, \
            "generate() needs an idle engine; use add_request/step instead"
        prompts = np.asarray(batch["tokens"])
        rids = [self.add_request(prompts[i], max_new, eos_id=eos_id,
                                 deadline_s=deadline_s)
                for i in range(prompts.shape[0])]
        if deadline_s is None:
            done = self.drain(greedy=greedy, seed=seed)
        else:
            done, t0 = [], time.perf_counter()
            while self.scheduler.has_work or self._finished_oob:
                done.extend(self.step(now=time.perf_counter() - t0,
                                      greedy=greedy, seed=seed))
            self._spec_disabled = False
            self._drafter_fail_streak = 0
        by_rid = {r.rid: r for r in done}
        pad = 0 if eos_id is None else eos_id
        rows, reasons = [], []
        for rid in rids:
            req = by_rid[rid]
            toks = req.out_tokens[:max_new]
            rows.append(toks + [pad] * (max_new - len(toks)))
            reasons.append(req.finish_reason)
        out = np.asarray(rows, np.int32)
        return (out, reasons) if return_reasons else out

    def generate_static(self, batch: dict, max_new: int, *,
                        greedy: bool = True, seed: int = 0) -> np.ndarray:
        """Static-batch generation (the pre-continuous-batching loop): every
        request in the batch shares one prompt length and decodes lock-step
        to ``max_new``.  Kept for encdec/vlm and as the benchmark baseline."""
        m = self.model
        prompts = jnp.asarray(batch["tokens"])
        b, plen = prompts.shape
        caches = m.prefill_cache(self.params, batch) if m.cfg.family == "encdec" \
            else m.init_cache(b, m.shape.seq_len)

        embeds = None
        if m.cfg.family == "vlm":
            embeds = m._embeds(self.params, batch)
            logits, caches = self._prefill(self.params, caches,
                                           jnp.zeros((b, embeds.shape[1]), jnp.int32),
                                           jnp.int32(0), embeds)
            pos = embeds.shape[1]
        else:
            logits, caches = self._prefill(self.params, caches, prompts,
                                           jnp.int32(0))
            pos = plen

        key = jax.random.PRNGKey(seed)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out = [tok]
        for i in range(max_new - 1):
            logits, caches = self._step(self.params, caches, tok,
                                        jnp.int32(pos + i))
            if greedy:
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            else:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits[:, -1])[:, None]
            out.append(tok.astype(jnp.int32))
        return np.asarray(jnp.concatenate(out, axis=1))
