"""Speculative decoding over the batched serving step: drafters + acceptance.

The fused ``[slots, s]`` ragged step (PR 3) lets one row carry several new
positions per engine tick — which is exactly the **verify** primitive
speculative decoding needs.  A *drafter* proposes up to ``k`` cheap guesses
for a decoding request's next tokens; the engine feeds the row
``[fed-back token, d_1 .. d_k]`` (``new_counts = k+1``) through the one
pre-compiled paged step, reads the target model's logits at every draft
position in that single call (``logits_idx``), and accepts the longest
draft prefix the target itself would have produced.  Per accepted draft
the request advances one extra token for the same number of step launches
— the paper's fixed-shape-grid argument (fix the compiled shape once, let
per-row occupancy vary) extended from chunked prefill to speculation: one
step shape serves *any* per-row draft length, zero new traces after
warmup.

**The acceptance rule is token-identical to the baseline by construction.**
This engine's sampling is deterministic given the request: greedy picks
``argmax``, and sampled picks draw from a (seed, rid, position)-derived
key (see ``Engine._pick``), so the baseline's next token is a pure
function of (target logits at that position, request, position).  The
lossless rule is therefore *exact match against the baseline's own pick*:
at each position, compute the pick the non-speculative engine would have
made from the verify step's target logits, accept the draft token iff it
equals that pick, and stop at the first mismatch — the computed pick IS
the correction token (speculation never costs a step: the mismatching
position still yields the token the baseline would have produced, and a
fully-accepted draft yields a bonus pick from the logits after the last
draft).  This is standard rejection sampling conditioned on the engine's
pre-committed randomness stream: with the per-position key fixed, the
target's categorical draw is a point mass, ``min(1, p/q)`` acceptance
degenerates to equality with that draw, and any other rule would break
token identity.  Greedy is the ``argmax`` special case.  Outputs are
asserted bit-identical to the non-speculative engine in
``tests/test_speculative.py`` — the drafter only ever changes
*throughput*, never tokens, so drafters are free to be wrong, stale, or
heuristic.

Two drafters ship:

- :class:`NgramDrafter` — prompt-lookup / self-ngram speculation: match
  the request's trailing n-gram against earlier positions of its own
  prompt + generated text and propose the historical continuation.  No
  extra model, no state, no device work; strong on repetitive or
  copy-heavy continuations (summarization, code, the loops greedy toy
  models settle into), silent otherwise (an empty proposal degenerates the
  row to plain decode).
- :class:`DraftModelDrafter` — a smaller :class:`~repro.models.model.
  ReproModel` sharing the target's tokenizer (vocab) drafts greedily from
  its own dense per-request KV cache.  Catch-up tokens (prompt at first
  sight, then each step's correction/bonus) are fed in power-of-two binary
  decomposition chunks so the compile count stays ``log2(max_len)`` with
  no padded garbage writes; rejected speculative positions in the draft
  cache are reconciled by token comparison on the next propose, and a
  target-side preemption is invisible here (:func:`request_context` is
  fold-invariant, so the stream's content only ever grows).

Rollback of rejected KV lives with the engine: the verify step wrote K/V
for every fed position, so after acceptance the engine truncates the row's
block table back to the accepted length
(:meth:`~repro.serving.kv_cache.SequencePages.truncate`) — whole trailing
pages return to the pool through the double-free-checked allocator, stale
positions inside the kept last page are masked by ``lens + new_counts``
until the next write overwrites them.  Under the prefix cache the same
call upholds the sharing invariants: a shared trailing page merely loses
this request's reference, and a shared *kept* tail page is CoW-split
before the next verify step writes into it — rollback can never mutate a
page another request (or the cache) still reads.  Preemption composes for
free: ``out_tokens`` only ever holds accepted tokens, so a fold after a
verify step can never leak a rejected draft into the recompute prompt —
nor, for the same reason, can a rejected draft ever be inserted into the
prefix cache (preemption inserts only committed-KV pages).
"""

from __future__ import annotations

from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.layout import round_up
from repro.core.linear import prepack_params
from repro.obs.telemetry import NULL as _NULL_OBS
from repro.serving.scheduler import Request

__all__ = ["Drafter", "NgramDrafter", "DraftModelDrafter", "accept_tokens",
           "request_context"]


def request_context(req: Request) -> np.ndarray:
    """The request's true token stream: the (possibly fold-extended)
    prompt plus the generated tokens **not yet folded into it**.  A
    preemption *copies* ``out_tokens[:folded]`` into the prompt
    (``Scheduler._preempt``) and leaves ``out_tokens`` whole, so naively
    concatenating prompt + out_tokens would duplicate the folded prefix —
    corrupting n-gram lookups and a draft model's cache context.  With the
    ``folded`` watermark respected, the stream's content is invariant
    under preemption and only ever grows."""
    return np.concatenate([req.prompt,
                           np.asarray(req.out_tokens[req.folded:],
                                      np.int32)])


def accept_tokens(req: Request, drafts: List[int], logits_rows: np.ndarray,
                  n_eff: int, pick) -> Tuple[int, int]:
    """The acceptance rule (correctness-critical — see the module
    docstring for why exact-match against the engine's own deterministic
    pick is the lossless rule here).

    ``logits_rows``: [K, V] target logits from the verify step; row ``j``
    is the distribution after the row's j-th fed token (j=0: the fed-back
    token, j>=1: draft ``drafts[j-1]``).  ``n_eff`` fed tokens means rows
    ``0 .. n_eff-1`` are meaningful and ``drafts[:n_eff-1]`` were fed.
    ``pick(logits_row, req)`` must be the engine's baseline pick — it reads
    ``len(req.out_tokens)`` for the position key, so appends must happen
    here, between picks, exactly as the baseline interleaves them.

    Appends the accepted prefix plus the correction/bonus pick to
    ``req.out_tokens`` (stopping early at eos/max_new exactly where the
    baseline would) and returns ``(appended, accepted)``:
    ``appended - accepted`` is always 1 except on an early stop, and
    ``req.len`` is NOT advanced — the engine owns cache-length accounting.
    """
    # typed, -O-proof: a wrong verify width here would silently corrupt
    # the identity contract, not just crash — never let it be stripped
    if not 1 <= n_eff <= logits_rows.shape[0]:
        raise ValueError(
            f"accept_tokens: n_eff={n_eff} outside the verify rows "
            f"[1, {logits_rows.shape[0]}] for rid {req.rid}")
    if len(drafts) < n_eff - 1:
        raise ValueError(
            f"accept_tokens: {len(drafts)} drafts cannot cover "
            f"n_eff={n_eff} fed tokens for rid {req.rid}")
    appended = accepted = 0
    for j in range(n_eff):
        tok = pick(logits_rows[j], req)
        req.out_tokens.append(tok)
        appended += 1
        matched = j < n_eff - 1 and tok == drafts[j]
        if matched:
            accepted += 1
        if req.done() or not matched:
            break
    return appended, accepted


class Drafter:
    """Pluggable draft-token source for speculative decoding.

    Contract: :meth:`propose` returns up to ``k`` int token guesses for the
    continuation of ``req`` after ``req.out_tokens[-1]`` — fewer (or none)
    whenever it has nothing confident to say; a wrong guess costs only the
    padded verify compute, never a token (the acceptance rule is lossless).
    Drafters may keep per-request state keyed by ``req.rid``; the engine
    calls :meth:`forget` when a request finishes and :meth:`warmup` from
    ``Engine.warmup()`` so a stateful drafter can pre-compile its own step
    shapes (the zero-recompile-after-warmup contract covers the drafter
    too).
    """

    # telemetry (repro.obs): the engine swaps in its live recorder after
    # attach(); the class default keeps standalone drafters silent
    obs = _NULL_OBS

    def attach(self, engine) -> None:
        """Bind engine-derived sizing/validation (called from Engine)."""

    def warmup(self) -> None:
        """Pre-compile any drafter-side step shapes."""

    def propose(self, req: Request, k: int) -> List[int]:
        raise NotImplementedError

    def propose_all(self, jobs: List[Tuple[Request, int]]) -> dict:
        """``{rid: drafts}`` for one engine step's decoding rows at once.
        The base implementation loops :meth:`propose`; a model-backed
        drafter overrides it to batch rows through its own step (one
        ``[slots, 1]`` call per draft position instead of ``k`` sequential
        ``[1, 1]`` calls per row)."""
        out = {req.rid: self.propose(req, k) for req, k in jobs}
        self.obs.draft_batch(len(jobs), sum(len(d) for d in out.values()))
        return out

    def forget(self, rid: int) -> None:
        """Drop per-request state (the request finished)."""

    def stats(self) -> dict:
        return {}


class NgramDrafter(Drafter):
    """Prompt-lookup / self-ngram speculation (no draft model).

    Matches the trailing ``n``-gram of the request's own context (prompt +
    generated tokens) against every earlier position, longest ``n`` first,
    most recent match wins, and proposes the tokens that followed the
    match.  This is the assisted-generation "prompt lookup" trick: on
    copy-heavy continuations the context is its own excellent draft model,
    and it costs a numpy sliding-window compare per step — no weights, no
    device work, no per-request state.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        assert 1 <= min_ngram <= max_ngram
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.proposals = 0           # propose() calls that returned tokens
        self.misses = 0              # propose() calls with no match

    def propose(self, req: Request, k: int) -> List[int]:
        ctx = request_context(req)
        size = int(ctx.shape[0])
        for n in range(min(self.max_ngram, size - 1), self.min_ngram - 1, -1):
            tail = ctx[size - n:]
            win = np.lib.stride_tricks.sliding_window_view(ctx, n)
            # candidate starts end strictly before the tail's own window
            hits = np.flatnonzero((win[:size - n] == tail).all(axis=1))
            if hits.size:
                start = int(hits[-1]) + n       # most recent occurrence
                self.proposals += 1
                return [int(t) for t in ctx[start:start + k]]
        self.misses += 1
        return []

    def stats(self) -> dict:
        return {"drafter": "ngram", "max_ngram": self.max_ngram,
                "proposals": self.proposals, "misses": self.misses}


class DraftModelDrafter(Drafter):
    """Greedy draft proposals from a smaller model sharing the tokenizer.

    Keeps one dense ``[1, max_len]`` KV cache per live request (the draft
    model is small — that is the point — so dense per-request state is
    cheap where the target's must be paged).  Each :meth:`propose`:

      1. reconciles: positions the previous propose wrote speculatively are
         kept only while their tokens match what the target actually
         accepted (rejected positions are simply re-fed — the dense cache's
         next write at a position overwrites the stale K/V and the
         ``cache_pos``-derived mask hides anything beyond);
      2. catches up: feeds context tokens the draft cache hasn't seen
         (the whole prompt on first sight; afterwards the correction/bonus
         token(s) of the last verify) in **binary-decomposition chunks** —
         widths are the powers of two in the remainder, so every width is
         one of ``log2(max_len)`` pre-compiled shapes and nothing is ever
         padded;
      3. drafts: ``k`` greedy single-token steps (``[1, 1]``), returning
         the argmax chain.

    The draft model must be pure-attention (a recurrent scan could not
    reconcile rejected speculative state by overwrite) and share the
    target's vocab.  Wall-clock spent here is the "draft overhead" the
    engine reports; acceptance quality is whatever the small model earns —
    the rule in :func:`accept_tokens` keeps tokens identical regardless.
    """

    def __init__(self, model, params, *, prepack: bool = True):
        assert all(t == "attn" for t in model.cfg.layer_types), \
            f"draft model {model.cfg.name}: recurrent mixers cannot " \
            f"reconcile rejected speculative state by overwrite — " \
            f"speculative drafting needs a pure-attention draft model"
        self.model = model
        self.params = (prepack_params(params, model.ctx) if prepack
                       else params)
        self._step = model.jit_step("decode")
        self.max_len = model.shape.seq_len
        self._state: dict = {}       # rid -> {caches, ctx_len, spec}
        self.draft_steps = 0         # draft-model step launches
        # batched (attached) mode: one paged draft cache shared by every
        # live request — one page per draft row, rid -> row map below
        self._paged = None
        self._caches = None
        self._rows: dict = {}        # rid -> draft row
        self._lru: dict = {}         # rid -> last propose tick
        self._tick = 0

    def attach(self, engine) -> None:
        assert self.model.cfg.vocab == engine.model.cfg.vocab, \
            f"draft model vocab {self.model.cfg.vocab} != target vocab " \
            f"{engine.model.cfg.vocab} — drafter and target must share " \
            f"the tokenizer"
        # widest context the draft cache must hold: the target's context
        # limit plus the final pick plus k-1 speculative writes
        self.max_len = engine.scheduler.max_len + engine.spec_tokens + 1
        # batched drafting state: the draft model's own *paged* step (its
        # per-row lens are what let rows at different positions share one
        # call), one page per draft row sized to hold a whole stream, and
        # a [rows, 1] static block table (row r -> page 1 + r; page 0
        # stays the trash page for inert rows)
        self._slots = engine.slots
        layout = self.model.ctx.layout(self.model.compute_dtype)
        self._page_tokens = round_up(self.max_len, layout.m_r)
        self._paged = self.model.jit_step("paged")
        self._caches = None          # device alloc deferred to first use

    def _widths(self) -> List[int]:
        w, out = 1, []
        while w <= self.max_len:
            out.append(w)
            w *= 2
        return out

    def warmup(self) -> None:
        """Compile every catch-up width — batched (attached): the
        ``[rows, w]`` ragged paged shapes, ``[rows, 1]`` included (w=1);
        standalone: the dense ``[1, w]`` shapes against a scratch cache."""
        if self._paged is not None:
            self._ensure_caches()
            zb = jnp.zeros((self._slots,), jnp.int32)
            btz = jnp.zeros((self._slots, 1), jnp.int32)
            for w in self._batch_widths():
                _, self._caches = self._paged(
                    self.params, self._caches,
                    jnp.zeros((self._slots, w), jnp.int32), btz, zb, zb,
                    None)
            return
        for w in self._widths():
            caches = self.model.init_cache(1, self.max_len)
            self._step(self.params, caches,
                       jnp.zeros((1, w), jnp.int32), jnp.int32(0))

    def _batch_widths(self) -> List[int]:
        """Batched catch-up widths: powers of two up to the pow2 *ceiling*
        of ``max_len`` — the batched path feeds the whole widest catch-up
        in one ragged call (per-row padding goes to the trash page), so
        the top width can exceed ``max_len``, unlike the per-row binary
        decomposition whose widths never do."""
        w, out = 1, []
        while True:
            out.append(w)
            if w >= self.max_len:
                return out
            w *= 2

    def _ensure_caches(self) -> None:
        if self._caches is None:
            self._caches = self.model.init_paged_cache(
                1 + self._slots, self._page_tokens, self._slots)

    def _row_for(self, rid: int, job_rids: set) -> int:
        """The draft row (page) backing ``rid``, allocating on first sight.
        When every row is taken, evict the least-recently-proposing state
        that is *not* in this step's jobs (it re-feeds its context on next
        sight — stale page KV is invisible behind its fresh lens).  A
        victim always exists: live jobs never exceed the engine's slots."""
        if rid in self._rows:
            return self._rows[rid]
        taken = set(self._rows.values())
        free = [r for r in range(self._slots) if r not in taken]
        if free:
            row = free[0]
        else:
            victim = min((r for r in self._rows if r not in job_rids),
                         key=lambda r: self._lru.get(r, -1))
            row = self._rows.pop(victim)
            self._state.pop(victim, None)
            self._lru.pop(victim, None)
        self._rows[rid] = row
        return row

    def propose_all(self, jobs: List[Tuple[Request, int]]) -> dict:
        """Batched drafting (attached engines): every decoding row's
        catch-up rides ONE ragged ``[rows, w]`` paged call (per-row lens;
        padding routed to the trash page), then each draft position is ONE
        batched ``[rows, 1]`` greedy step — ``1 + (k-1)`` device launches
        per engine step instead of the per-row loop's
        ``rows * (catchup + k - 1)``.  Tokens are identical to the per-row
        path: same reconcile, same greedy argmax chain, row-independent
        attention."""
        if self._paged is None or not jobs:
            return super().propose_all(jobs)
        self._ensure_caches()
        self._tick += 1
        job_rids = {req.rid for req, _ in jobs}
        plans = []
        for req, k in jobs:
            row = self._row_for(req.rid, job_rids)
            self._lru[req.rid] = self._tick
            st = self._state.get(req.rid)
            if st is None:
                st = {"ctx_len": 0, "spec": np.zeros((0,), np.int32)}
                self._state[req.rid] = st
            ctx = request_context(req)
            size = int(ctx.shape[0])
            # reconcile + the start-one-token-early trick, exactly as in
            # the per-row path (see propose)
            base, spec = st["ctx_len"], st["spec"]
            m = 0
            while (m < spec.shape[0] and base + m < size
                   and spec[m] == ctx[base + m]):
                m += 1
            start = min(base + m, size - 1)
            plans.append({"row": row, "req": req, "k": k, "ctx": ctx,
                          "size": size, "start": start, "st": st})
        # one ragged catch-up call at the pow2 width of the widest row
        maxn = max(p["size"] - p["start"] for p in plans)
        w = 1
        while w < maxn:
            w *= 2
        rows_n = self._slots
        token = np.zeros((rows_n, w), np.int32)
        lens = np.zeros((rows_n,), np.int32)
        counts = np.zeros((rows_n,), np.int32)
        bt = np.zeros((rows_n, 1), np.int32)
        for p in plans:
            r, n = p["row"], p["size"] - p["start"]
            token[r, :n] = p["ctx"][p["start"]:p["size"]]
            lens[r] = p["start"]
            counts[r] = n
            bt[r, 0] = 1 + r
        logits = self._run_batch(token, bt, lens, counts)
        drafted = {p["row"]: [] for p in plans}
        kmax = max(p["k"] for p in plans)
        for j in range(kmax):
            for p in plans:
                if j < p["k"]:
                    drafted[p["row"]].append(
                        int(np.argmax(logits[p["row"], 0])))
            if j == kmax - 1:
                break                # the last draft's KV is never needed
            token = np.zeros((rows_n, 1), np.int32)
            lens = np.zeros((rows_n,), np.int32)
            counts = np.zeros((rows_n,), np.int32)
            for p in plans:
                r = p["row"]
                if j + 1 >= p["k"]:
                    continue         # this row is done: inert this call
                token[r, 0] = drafted[r][-1]
                lens[r] = p["size"] + j
                counts[r] = 1
            logits = self._run_batch(token, bt, lens, counts)
        out = {}
        for p in plans:
            d = drafted[p["row"]]
            st = p["st"]
            st["ctx_len"] = p["size"]
            # positions written beyond the committed context: all but the
            # last proposed token
            st["spec"] = np.asarray(d[:-1], np.int32)
            out[p["req"].rid] = d
        self.obs.draft_batch(len(jobs), sum(len(d) for d in out.values()))
        return out

    def _run_batch(self, token, bt, lens, counts) -> np.ndarray:
        logits, self._caches = self._paged(
            self.params, self._caches, jnp.asarray(token), jnp.asarray(bt),
            jnp.asarray(lens), jnp.asarray(counts), None)
        self.draft_steps += 1
        return np.asarray(logits)

    def propose(self, req: Request, k: int) -> List[int]:
        ctx = request_context(req)
        size = int(ctx.shape[0])
        st = self._state.get(req.rid)
        if st is None:
            st = {"caches": self.model.init_cache(1, self.max_len),
                  "ctx_len": 0, "spec": np.zeros((0,), np.int32)}
            self._state[req.rid] = st
        # reconcile: speculative positions survive while their tokens match
        # the context the target actually committed
        base, spec = st["ctx_len"], st["spec"]
        m = 0
        while (m < spec.shape[0] and base + m < size
               and spec[m] == ctx[base + m]):
            m += 1
        valid = base + m
        # start one token early when the speculative cache already covers
        # the whole context (the engine shed/trimmed a draft whose tokens
        # it then committed anyway): logits from the previous propose were
        # discarded, so re-feed the final context token — an identical
        # overwrite of its KV — to recover the distribution to draft from
        start = min(valid, size - 1)
        caches, pos = st["caches"], start
        logits = None
        i = start
        while i < size:                      # catch-up, binary decomposition
            w = 1
            while w * 2 <= size - i:
                w *= 2
            tok = jnp.asarray(ctx[None, i:i + w])
            logits, caches = self._step(self.params, caches, tok,
                                        jnp.int32(pos))
            self.draft_steps += 1
            pos += w
            i += w
        drafted: List[int] = []
        for j in range(k):
            t = int(np.argmax(np.asarray(logits[0, -1])))
            drafted.append(t)
            if j == k - 1:
                break                # the last draft's KV is never needed
            logits, caches = self._step(self.params, caches,
                                        jnp.asarray([[t]]), jnp.int32(pos))
            self.draft_steps += 1
            pos += 1
        st["caches"] = caches
        st["ctx_len"] = size
        # positions written beyond the committed context: all but the last
        st["spec"] = np.asarray(drafted[:-1], np.int32)
        return drafted

    def forget(self, rid: int) -> None:
        self._state.pop(rid, None)
        self._rows.pop(rid, None)
        self._lru.pop(rid, None)

    def stats(self) -> dict:
        return {"drafter": "draft-model", "model": self.model.cfg.name,
                "draft_steps": self.draft_steps,
                "live_states": len(self._state)}
