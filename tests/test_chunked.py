"""Chunked prefill under a per-step token budget (the flat step).

Contracts covered:
  - chunked prefill is token-identical to monolithic prefill for chunk
    sizes of one page, a non-divisor of the prompt length, and larger than
    the whole prompt — greedy and seeded-sampled;
  - identity holds through preemption: a pool too small for the working
    set forces folds/pauses and the recomputed outputs still match;
  - a paused mid-prefill request resumes from its cursor with the pages it
    still holds — already-written chunks are never recomputed;
  - chunked admission books pages for the next chunk only (not the whole
    prompt), and chunk sizes round up to the layout's m_r;
  - the token budget caps concurrent prefill tokens per step, never decode
    progress;
  - after Engine.warmup() a trace with admissions, chunked prefills,
    growth and preemption triggers zero new XLA traces (the
    compile-counting hook in ReproModel.jit_step);
  - recurrent-mixer families refuse chunk_tokens (padded chunk rows are
    not inert for a scan).
"""

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.core.layout import ceil_div
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import PagedKVPool
from repro.serving.scheduler import Request, Scheduler

RUN = RunConfig(param_dtype="float32", compute_dtype="float32", remat=False)


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced_config(get_config("smollm2-135m"), layers=2)
    shape = ShapeSpec("serve", 64, 3, "decode")
    m = build_model(cfg, RUN, shape)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _prompts(cfg, lens, seed=1):
    key = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(key, i), (l,),
                                          0, cfg.vocab))
            for i, l in enumerate(lens)]


def _drain(eng, reqs, **kw):
    rids = [eng.add_request(p, n) for p, n in reqs]
    fin = {r.rid: r for r in eng.drain(**kw)}
    assert sorted(fin) == sorted(rids)
    return [fin[rid] for rid in rids]


# ---------------------------------------------------------------------------
# chunk-boundary correctness: chunked == monolithic, token for token
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mono_outputs(smollm):
    """Monolithic-prefill reference over prompts chosen so every chunk size
    below hits a boundary case (13 and 21 are non-divisors of 8 and 16; 3
    is smaller than any chunk)."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, [13, 21, 3, 16]), [8, 6, 10, 7]))
    eng = Engine(m, params, max_slots=3)
    greedy = [r.out_tokens for r in _drain(eng, reqs)]
    eng = Engine(m, params, max_slots=3)
    sampled = [r.out_tokens for r in _drain(eng, reqs, greedy=False, seed=7)]
    return reqs, greedy, sampled


@pytest.mark.parametrize("chunk", [8, 16, 40])
def test_chunked_matches_monolithic(smollm, mono_outputs, chunk):
    """chunk=8: exactly one page; chunk=16: non-divisor of the 13/21-token
    prompts (final partial chunk); chunk=40: larger than every prompt
    (prefill completes in one fused step)."""
    cfg, m, params = smollm
    reqs, greedy, sampled = mono_outputs
    # an unthrottling budget keeps chunks whole, so every prompt takes
    # exactly ceil(len / chunk) fused steps — no chunk is ever re-run (a
    # tighter budget splits chunks across steps, changing pacing, never
    # tokens: test_chunked_budget_through_engine)
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=chunk,
                 token_budget=1000)
    got = _drain(eng, reqs)
    assert [r.out_tokens for r in got] == greedy
    assert eng.pool.num_used == 0
    for r, (p, _) in zip(got, reqs):
        assert r.chunk_steps == ceil_div(p.shape[0], eng.chunk_tokens)


def test_chunked_matches_monolithic_sampled(smollm, mono_outputs):
    """Sampling keys are (seed, rid, position)-derived, so chunking must be
    invisible to sampled continuations too."""
    cfg, m, params = smollm
    reqs, _, sampled = mono_outputs
    eng = Engine(m, params, max_slots=3, chunk_tokens=16)
    assert [r.out_tokens for r in
            _drain(eng, reqs, greedy=False, seed=7)] == sampled


@pytest.mark.slow
def test_chunked_preemption_token_identical(smollm):
    """A pool at ~half the working set forces preemptions (folds) and
    pauses mid-prefill; the chunked engine must still reproduce the
    ample-pool monolithic outputs exactly, and balance the pool."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, [4, 25, 6, 30, 4, 5], seed=3),
                    [16, 10, 16, 8, 16, 16]))
    ample = Engine(m, params, max_slots=3, page_tokens=8)
    want = [r.out_tokens for r in _drain(ample, reqs)]

    tight = Engine(m, params, max_slots=3, page_tokens=8, num_pages=1 + 6,
                   chunk_tokens=8)
    got = _drain(tight, reqs)
    assert [r.out_tokens for r in got] == want
    assert tight.num_preemptions >= 1
    assert tight.pool.num_used == 0
    assert tight.pool.total_allocs == tight.pool.total_frees
    assert tight.scheduler.num_free_slots == 3


# ---------------------------------------------------------------------------
# pause/resume: a displaced mid-prefill request keeps its pages + cursor
# ---------------------------------------------------------------------------

def _req(rid, plen, max_new, arrival=0.0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new=max_new, arrival=arrival)


def test_pause_keeps_pages_and_cursor():
    """grow() displacing a mid-prefill victim must pause it — slot
    returned, pages and cursor intact — and walk on to a decoding victim
    for the actual pages (pausing frees none); re-admission then resumes
    from the cursor with the very same pages."""
    pool = PagedKVPool(1 + 8, 8)
    sched = Scheduler(max_slots=3, pool=pool, max_len=64, chunk_tokens=8)
    a, c, b = _req(0, 8, 40), _req(1, 8, 9), _req(2, 40, 4)
    for r in (a, c, b):
        sched.add(r)
    assert len(sched.admit()) == 3
    assert {r.status for r in (a, c, b)} == {"prefilling"}
    assert pool.num_used == 0            # chunked admission books nothing yet

    # a and c finish their one-chunk prompts and decode; b is mid-prefill
    # with two chunks written (2 pages, cursor 16)
    assert sched.plan_chunks(100) == {a.slot: 8, c.slot: 8, b.slot: 8}
    for r in (a, c):
        r.prefill_cursor = r.len = 8
        r.status = "running"
        r.out_tokens.append(7)
    b.prefill_cursor = b.len = 8         # the engine advances cursors
    assert sched.plan_chunks(100) == {b.slot: 8}
    b.prefill_cursor = b.len = 16
    held = list(b.pages.pages)
    assert pool.num_used == 4 and len(held) == 2

    # a grows to 41 tokens: 5 new pages against 4 free.  The youngest
    # victim is b, mid-prefill: paused (pages kept) — then c, decoding:
    # preempted (pages released) — and a's growth succeeds.
    a.len, a.out_tokens = 40, [7] * 33
    displaced = sched.grow()
    assert displaced == [b, c]
    assert b.status == "waiting" and b.preempted and b.slot == -1
    assert b.num_pauses == 1 and sched.num_pauses == 1
    assert b.num_preemptions == 0        # b's pages were NOT released
    assert b.prefill_cursor == 16 and b.pages.pages == held
    assert c.status == "waiting" and c.pages is None     # true preemption
    assert sched.num_preemptions == 1
    assert a.status == "running" and len(a.pages.pages) == 6
    assert sched.waiting[0] is c and sched.waiting[1] is b

    # once a finishes, both resume; b picks up from its cursor with the
    # same pages and books only the next chunk
    sched.finish(a)
    assert sched.admit() == [c, b]
    assert b.status == "prefilling" and b.prefill_cursor == 16
    assert b.pages.pages == held
    plan = sched.plan_chunks(100)
    assert plan[b.slot] == 8
    assert b.pages.pages[:2] == held and len(b.pages.pages) == 3


def test_reclaim_releases_paused_pages_when_solo():
    """Termination fallback: when the sole running request cannot grow and
    the remaining pages belong to a paused waiter, the waiter's pages are
    reclaimed (cursor reset — a true preemption) rather than deadlocking or
    self-preempting."""
    pool = PagedKVPool(1 + 4, 8)
    sched = Scheduler(max_slots=2, pool=pool, max_len=64, chunk_tokens=8)
    a, b = _req(0, 4, 29), _req(1, 24, 4)
    sched.add(a)
    sched.add(b)
    assert len(sched.admit()) == 2
    assert sched.plan_chunks(100) == {a.slot: 4, b.slot: 8}
    a.prefill_cursor = a.len = 4
    a.status = "running"
    a.out_tokens.append(7)
    b.prefill_cursor = b.len = 8         # the engine advances cursors

    # a needs 3 new pages against 2 free: b (youngest, mid-prefill) is
    # paused — which frees nothing — leaving a as its own youngest victim,
    # so the fallback reclaims the paused b's page (cursor reset, a true
    # preemption) instead of self-preempting the oldest request
    a.len, a.out_tokens = 24, [7] * 21
    assert sched.grow() == [b]
    assert b.status == "waiting" and b.num_pauses == 1
    assert a.status == "running" and len(a.pages.pages) == 4
    assert b.pages.pages == [] and b.prefill_cursor == 0 and b.len == 0
    assert b.num_preemptions == 1 and sched.num_preemptions == 1


def test_admission_reclaims_paused_pages_when_idle():
    """Liveness hole regression: with nothing running and every page held
    by paused waiters, admit() must reclaim behind the queue head (never
    the head itself — its held pages reduce its need) instead of hanging a
    drain forever.  Needs a chunk spanning >1 page so the head's next
    chunk can outsize the free list."""
    pool = PagedKVPool(1 + 4, 16)                # 4 usable pages = 64 tokens
    sched = Scheduler(max_slots=2, pool=pool, max_len=64, chunk_tokens=32)
    a, b = _req(0, 48, 4), _req(1, 33, 4)
    sched.add(a)
    sched.add(b)
    assert len(sched.admit()) == 2
    assert sched.plan_chunks(100) == {a.slot: 32, b.slot: 32}
    a.prefill_cursor = a.len = 32                # 2 pages each: pool full
    b.prefill_cursor = b.len = 32
    sched._pause(b)
    sched._pause(a)
    assert not sched.running and pool.num_free == 0
    assert [r.rid for r in sched.waiting] == [0, 1]

    # head a needs 1 more page for its final chunk; only paused b holds
    # pages — admission must reclaim b (cursor reset), keep a's pages, and
    # resume a from its cursor
    held = list(a.pages.pages)
    assert sched.admit() == [a]
    assert a.prefill_cursor == 32 and a.pages.pages == held
    assert b.pages.pages == [] and b.prefill_cursor == 0
    assert b.num_preemptions == 1
    assert sched.plan_chunks(100) == {a.slot: 16}    # final 48-32 remainder


def test_pause_resume_through_engine_no_rework(smollm):
    """End to end: a long prompt whose chunked prefill stalls behind a
    decode-heavy neighbour must finish in exactly ceil(len/chunk) fused
    steps — stall-and-resume keeps the cursor and never re-runs a written
    chunk — with outputs identical to the ample-pool monolithic run."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, [6, 40], seed=5), [10, 4]))
    ample = Engine(m, params, max_slots=2, page_tokens=8)
    want = [r.out_tokens for r in _drain(ample, reqs)]

    eng = Engine(m, params, max_slots=2, page_tokens=8, num_pages=1 + 6,
                 chunk_tokens=8, token_budget=100)   # page-driven stalls only
    got = _drain(eng, reqs)
    assert [r.out_tokens for r in got] == want
    long = got[1]
    assert long.num_preemptions == 0, \
        "sizing drifted: the long prompt should stall/pause, not recompute"
    assert long.chunk_steps == ceil_div(40, 8)
    assert eng.scheduler.prefill_stall_steps >= 1 or long.num_pauses >= 1


# ---------------------------------------------------------------------------
# admission, alignment, budget
# ---------------------------------------------------------------------------

def test_chunk_tokens_rounds_to_m_r(smollm):
    cfg, m, params = smollm
    lay = m.ctx.layout(m.compute_dtype)
    eng = Engine(m, params, chunk_tokens=3)     # deliberately unaligned
    assert eng.chunk_tokens % lay.m_r == 0 and eng.chunk_tokens >= 3
    assert eng.scheduler.chunk_tokens == eng.chunk_tokens
    with pytest.raises(AssertionError, match="at least one token"):
        Engine(m, params, chunk_tokens=0)       # would wedge every prefill


def test_chunked_admission_books_first_chunk_only():
    """Chunked admission must not require (or take) pages for the whole
    prompt: a long prompt admits into a pool that could never hold it all
    at once, and pages arrive chunk by chunk."""
    sched = Scheduler(max_slots=1, pool=PagedKVPool(1 + 6, 8), max_len=64,
                      chunk_tokens=8)
    r = _req(0, 40, 4)                           # prompt alone needs 5 pages
    sched.add(r)
    assert [q.rid for q in sched.admit()] == [0]
    assert sched.pool.num_used == 0              # nothing booked up front
    assert sched.plan_chunks(100) == {r.slot: 8}
    assert sched.pool.num_used == 1              # first chunk's page only
    # monolithic lazy admission books the whole prompt at once
    mono = Scheduler(max_slots=1, pool=PagedKVPool(1 + 6, 8), max_len=64)
    mono.add(_req(0, 40, 4))
    mono.admit()
    assert mono.pool.num_used == 5


def test_token_budget_caps_concurrent_prefill():
    """Two prefilling slots under a budget of one chunk: the older gets the
    full chunk, the younger stalls (0 tokens) — and decodes are never
    budget-stalled (they are subtracted before the plan)."""
    pool = PagedKVPool(1 + 8, 8)
    sched = Scheduler(max_slots=2, pool=pool, max_len=64, chunk_tokens=8)
    a, b = _req(0, 24, 4), _req(1, 24, 4)
    sched.add(a)
    sched.add(b)
    sched.admit()
    assert sched.plan_chunks(8) == {a.slot: 8, b.slot: 0}
    assert sched.prefill_stall_steps == 1
    a.prefill_cursor = a.len = 8
    b.prefill_cursor = b.len = 0
    # a bigger budget feeds both, clipped to the remaining prompt
    assert sched.plan_chunks(12) == {a.slot: 8, b.slot: 4}

    # with a tile alignment (the engine passes the layout m_r), a
    # budget-clamped chunk rounds DOWN so the cursor stays on a tile
    # boundary — a remainder too small for a whole tile stalls instead
    pool2 = PagedKVPool(1 + 8, 8)
    tiled = Scheduler(max_slots=2, pool=pool2, max_len=64,
                      chunk_tokens=16, chunk_align=8)
    c, d = _req(0, 32, 4), _req(1, 32, 4)
    tiled.add(c)
    tiled.add(d)
    tiled.admit()
    assert tiled.plan_chunks(20) == {c.slot: 16, d.slot: 0}   # not 4


def test_chunked_budget_through_engine(smollm):
    """The budget knob must not change tokens, only pacing: serving with a
    budget of one chunk per step equals the unbounded-budget outputs."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, [13, 21, 9], seed=9), [6, 5, 7]))
    wide = Engine(m, params, max_slots=3, chunk_tokens=8)
    want = [r.out_tokens for r in _drain(wide, reqs)]
    narrow = Engine(m, params, max_slots=3, chunk_tokens=8,
                    token_budget=8 + 3)
    assert [r.out_tokens for r in _drain(narrow, reqs)] == want


def test_hybrid_families_refuse_chunking(smollm):
    cfg = reduced_config(get_config("rwkv6-1.6b"))
    shape = ShapeSpec("serve", 64, 2, "decode")
    m = build_model(cfg, RUN, shape)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(AssertionError, match="chunked prefill"):
        Engine(m, params, chunk_tokens=8)


# ---------------------------------------------------------------------------
# warmup: the no-recompile contract
# ---------------------------------------------------------------------------

def test_no_compiles_after_warmup_chunked(smollm):
    """The chunked engine's step shapes are the flat width ladder
    (Engine._flat_shapes); after warmup, a trace with admissions, chunked
    prefills, stalls, growth and preemption must trigger zero new XLA
    traces."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, num_pages=1 + 6,
                 chunk_tokens=8)
    eng.warmup()
    assert eng.pool.num_used == 0 and eng.pool.total_allocs == 0
    before = dict(m.trace_counts)
    reqs = list(zip(_prompts(cfg, [4, 25, 6, 30], seed=3), [16, 10, 16, 8]))
    fin = _drain(eng, reqs)
    assert eng.num_preemptions + eng.num_pauses >= 1
    assert sum(len(r.out_tokens) for r in fin) == 16 + 10 + 16 + 8
    assert dict(m.trace_counts) == before, \
        "Engine.step compiled a new shape after warmup()"
    assert eng.stats()["compiles"] == before


def test_no_compiles_after_warmup_monolithic(smollm):
    """The baseline policy keeps its contract too: geometric buckets plus
    the decode step cover every monolithic trace, including recompute
    prefills of fold-extended prompts."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, num_pages=1 + 6)
    eng.warmup()
    before = dict(m.trace_counts)
    reqs = list(zip(_prompts(cfg, [4, 25, 6, 30], seed=3), [16, 10, 16, 8]))
    _drain(eng, reqs)
    assert eng.num_preemptions >= 1
    assert dict(m.trace_counts) == before
