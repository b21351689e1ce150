"""Speculative decoding over the batched serving step.

Contracts covered:
  - spec-on outputs are token-identical to the non-speculative baseline —
    greedy and seeded-sampled — for k in {1, 2, page-straddling}, with the
    n-gram drafter, a draft model, a perfect (oracle) drafter and an
    always-wrong drafter alike (the acceptance rule is lossless, so the
    drafter can only change throughput, never tokens);
  - acceptance stats: an oracle drafter accepts everything, an
    anti-oracle accepts nothing, and the engine's counters say so;
  - KV rollback: rejected draft positions are truncated from the block
    table — whole trailing pages return to the pool, alloc/free stays
    balanced, double-free checks intact (SequencePages.truncate unit);
  - zero new XLA traces after Engine.warmup() with speculation on —
    monolithic and chunked, target and draft model;
  - speculation composes with preemption: a tight pool forces folds and
    the folded prompt only ever contains accepted tokens (a rejected
    draft can never leak into a recompute prompt);
  - constructor validation: hybrids refuse spec (recurrent state cannot
    roll back), a drafter without spec_tokens is rejected, the chunk
    ladder must cover the verify width.
"""

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import PagedKVPool, SequencePages
from repro.serving.scheduler import Request, Scheduler
from repro.serving.speculative import (Drafter, DraftModelDrafter,
                                       NgramDrafter, accept_tokens,
                                       request_context)

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


REQS = ([5, 11, 8, 3], [16, 12, 20, 14])


@pytest.fixture(scope="module")
def baseline(smollm):
    """Non-speculative reference outputs, greedy and sampled."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    eng = Engine(m, params, max_slots=3)
    greedy = [r.out_tokens for r in _drain(eng, reqs)]
    eng = Engine(m, params, max_slots=3)
    sampled = [r.out_tokens for r in _drain(eng, reqs, greedy=False, seed=7)]
    return reqs, greedy, sampled


class OracleDrafter(Drafter):
    """Proposes the baseline's own continuation: 100% acceptance.  With
    ``offset`` it proposes baseline+offset instead: 0% acceptance.  Either
    way the outputs must not move — the strongest possible statement of
    the lossless-acceptance contract."""

    def __init__(self, outs, offset=0, vocab=512):
        self.outs = outs             # rid -> full baseline out_tokens
        self.offset = offset
        self.vocab = vocab

    def propose(self, req, k):
        done = len(req.out_tokens)
        nxt = self.outs[req.rid][done:done + k]
        return [(t + self.offset) % self.vocab for t in nxt]


# ---------------------------------------------------------------------------
# token identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 5])
def test_spec_greedy_matches_baseline(smollm, baseline, k):
    """k=1: minimal verify width; k=2: partial accepts; k=5: with 8-token
    pages and full oracle acceptance a verify step writes 6 positions, so
    steps straddle page boundaries — growth books multi-page asks and
    rollback crosses pages."""
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    eng = Engine(m, params, max_slots=3, page_tokens=8, spec_tokens=k,
                 drafter=OracleDrafter(dict(enumerate(greedy))))
    got = _drain(eng, reqs)
    assert [r.out_tokens for r in got] == greedy
    st = eng.stats()["speculative"]
    assert st["acceptance_rate"] == 1.0
    assert st["decode_tokens_per_row_step"] > 1.0
    assert eng.pool.num_used == 0


def test_spec_ngram_matches_baseline_greedy_and_sampled(smollm, baseline):
    """The shipped prompt-lookup drafter: partial, input-dependent
    acceptance — tokens still identical, greedy and sampled (the sampled
    acceptance rule recomputes the (seed, rid, position)-keyed picks)."""
    cfg, m, params = smollm
    reqs, greedy, sampled = baseline
    eng = Engine(m, params, max_slots=3, spec_tokens=2)
    assert [r.out_tokens for r in _drain(eng, reqs)] == greedy
    # greedy toy decodes loop, so self-ngram lookup must land some drafts
    assert eng.stats()["speculative"]["accepted"] > 0
    eng = Engine(m, params, max_slots=3, spec_tokens=2)
    assert [r.out_tokens for r in
            _drain(eng, reqs, greedy=False, seed=7)] == sampled


def test_spec_draft_model_matches_baseline(smollm, baseline):
    """A 1-layer draft model sharing the target's vocab: acceptance is
    whatever the small model earns (possibly none — its weights are
    unrelated), outputs must be bit-identical regardless, and the draft
    model's dense cache must survive reconcile/rollback across steps."""
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    dcfg = reduced_config(get_config("smollm2-135m"), layers=1)
    dm = build_model(dcfg, RUN, ShapeSpec("serve", 64, 3, "decode"))
    dparams = dm.init(jax.random.PRNGKey(3))
    eng = Engine(m, params, max_slots=3, spec_tokens=2,
                 drafter=DraftModelDrafter(dm, dparams))
    assert [r.out_tokens for r in _drain(eng, reqs)] == greedy
    st = eng.stats()["speculative"]
    assert st["drafter"]["drafter"] == "draft-model"
    assert st["drafter"]["live_states"] == 0      # forget() on finish
    assert st["drafted"] > 0


def test_spec_chunked_matches_baseline(smollm, baseline):
    """Speculation through the flat step: verify segments ride the same
    width ladder as prefill chunks."""
    cfg, m, params = smollm
    reqs, greedy, sampled = baseline
    eng = Engine(m, params, max_slots=3, chunk_tokens=8, spec_tokens=2)
    assert [r.out_tokens for r in _drain(eng, reqs)] == greedy
    eng = Engine(m, params, max_slots=3, chunk_tokens=8, spec_tokens=2)
    assert [r.out_tokens for r in
            _drain(eng, reqs, greedy=False, seed=7)] == sampled


# ---------------------------------------------------------------------------
# acceptance accounting + rollback
# ---------------------------------------------------------------------------

def test_rejected_drafts_roll_back_pages(smollm, baseline):
    """An anti-oracle (every draft wrong): every verify step writes k
    rejected positions that must be rolled back.  Outputs unchanged,
    acceptance 0, truncation frees real pages, and the pool balances."""
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    eng = Engine(m, params, max_slots=3, page_tokens=8, spec_tokens=5,
                 drafter=OracleDrafter(dict(enumerate(greedy)), offset=1,
                                       vocab=cfg.vocab))
    got = _drain(eng, reqs)
    assert [r.out_tokens for r in got] == greedy
    st = eng.stats()["speculative"]
    assert st["drafted"] > 0 and st["accepted"] == 0
    assert st["acceptance_rate"] == 0.0
    assert st["decode_tokens_per_row_step"] == 1.0
    assert st["rollback_pages"] > 0, \
        "6-wide verify rows against 8-token pages must straddle a page " \
        "boundary sometimes — rejection should return whole pages"
    assert eng.pool.num_used == 0
    assert eng.pool.total_allocs == eng.pool.total_frees


def test_speculative_grow_sheds_instead_of_preempting():
    """A speculative page ask must never be what forces a displacement:
    when granting an older row's k+1 ask would consume the page a younger
    row's mandatory one-token growth needs this step, the ask is shed
    (counted) and the younger row grows exactly as it would under plain
    decode — zero preemptions."""
    pool = PagedKVPool(1 + 5, 8)
    sched = Scheduler(max_slots=2, pool=pool, max_len=64)
    a = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=30)
    b = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=30)
    sched.add(a)
    sched.add(b)
    assert len(sched.admit()) == 2           # one prompt page each
    a.len, a.out_tokens = 14, [1] * 7
    b.len, b.out_tokens = 16, [2] * 9
    a.pages.ensure(16)                       # 2 pages each: one page left
    b.pages.ensure(16)
    assert pool.num_free == 1
    # a (older) asks for 3 positions -> len 17 -> a 3rd page; b's mandatory
    # ensure(17) needs that same last page
    displaced = sched.grow(want={a.slot: 3, b.slot: 1})
    assert displaced == [] and sched.num_preemptions == 0
    assert sched.spec_grow_fallbacks == 1
    assert a.pages.capacity == 16            # ask shed: no page taken
    assert b.pages.capacity == 24            # mandatory growth got the page
    # with room for everyone, the same ask is granted
    pool2 = PagedKVPool(1 + 6, 8)
    sched2 = Scheduler(max_slots=2, pool=pool2, max_len=64)
    c = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=30)
    d = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=30)
    sched2.add(c)
    sched2.add(d)
    sched2.admit()
    c.len, c.out_tokens = 14, [1] * 7
    d.len, d.out_tokens = 16, [2] * 9
    c.pages.ensure(16)
    d.pages.ensure(16)
    assert sched2.grow(want={c.slot: 3, d.slot: 1}) == []
    assert c.pages.capacity == 24 and d.pages.capacity == 24
    assert sched2.spec_grow_fallbacks == 0

    # an ask covered by the row's own last-page slack needs no free pages
    # and must not be counted as shed, however tight the pool
    pool3 = PagedKVPool(1 + 5, 8)
    sched3 = Scheduler(max_slots=2, pool=pool3, max_len=64)
    e = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=30)
    f = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=30)
    sched3.add(e)
    sched3.add(f)
    sched3.admit()
    e.len, e.out_tokens = 9, [1] * 2
    f.len, f.out_tokens = 16, [2] * 9
    e.pages.ensure(16)                       # slack covers len 9 + 3
    f.pages.ensure(16)
    assert sched3.grow(want={e.slot: 3, f.slot: 1}) == []
    assert sched3.spec_grow_fallbacks == 0 and sched3.num_preemptions == 0
    assert e.pages.capacity == 16 and f.pages.capacity == 24


def test_sequence_pages_truncate_unit():
    pool = PagedKVPool(1 + 6, 8)
    seq = SequencePages(pool)
    seq.ensure(20)                       # 3 pages
    assert len(seq.pages) == 3 and pool.num_used == 3
    assert seq.truncate(17) == 0         # 17 tokens still need 3 pages
    assert seq.truncate(9) == 1          # drop to 2 pages
    assert len(seq.pages) == 2 and pool.num_used == 2
    assert seq.truncate(0) == 2          # full rollback
    assert pool.num_used == 0
    assert pool.total_allocs == pool.total_frees
    # the freed pages are genuinely reusable (no double-free later)
    seq.ensure(48)
    seq.release()
    assert pool.num_used == 0


def test_accept_tokens_rule_unit():
    """The acceptance rule in isolation: accept while the pick equals the
    draft, emit the pick at the first mismatch, bonus pick after a full
    accept, stop at eos exactly where the baseline would."""
    def pick_argmax(row, req):
        return int(np.argmax(row))

    def logits(*winners, vocab=8):
        out = np.zeros((len(winners), vocab), np.float32)
        for i, w in enumerate(winners):
            out[i, w] = 1.0
        return out

    r = Request(rid=0, prompt=np.zeros(2, np.int32), max_new=10)
    # picks: 3, 5, 6; drafts [3, 5] — full accept + bonus
    appended, accepted = accept_tokens(r, [3, 5], logits(3, 5, 6), 3,
                                       pick_argmax)
    assert (appended, accepted) == (3, 2) and r.out_tokens == [3, 5, 6]
    # picks: 2, 7, ...; drafts [2, 4] — mismatch at j=1: 7 is the correction
    r2 = Request(rid=1, prompt=np.zeros(2, np.int32), max_new=10)
    appended, accepted = accept_tokens(r2, [2, 4], logits(2, 7, 6), 3,
                                       pick_argmax)
    assert (appended, accepted) == (2, 1) and r2.out_tokens == [2, 7]
    # eos mid-accept: stop immediately even though drafts keep matching
    r3 = Request(rid=2, prompt=np.zeros(2, np.int32), max_new=10, eos_id=5)
    appended, accepted = accept_tokens(r3, [3, 5], logits(3, 5, 6), 3,
                                       pick_argmax)
    assert (appended, accepted) == (2, 2) and r3.out_tokens == [3, 5]
    assert r3.finish_reason == "eos"
    # n_eff == 1 degenerates to plain decode
    r4 = Request(rid=3, prompt=np.zeros(2, np.int32), max_new=10)
    assert accept_tokens(r4, [], logits(4), 1, pick_argmax) == (1, 0)
    assert r4.out_tokens == [4]


def test_ngram_drafter_unit():
    d = NgramDrafter(max_ngram=3)
    r = Request(rid=0, prompt=np.asarray([1, 2, 3, 9, 1, 2, 3], np.int32),
                max_new=8)
    r.out_tokens = []
    # trailing [1,2,3] recurs at the start; the continuation there was 9
    assert d.propose(r, 2) == [9, 1]
    # most recent match wins: trailing [7] matches the later 7
    r2 = Request(rid=1, prompt=np.asarray([7, 4, 7, 5, 7], np.int32),
                 max_new=8)
    assert d.propose(r2, 2) == [5, 7]
    # generated tokens are part of the lookup context
    r3 = Request(rid=2, prompt=np.asarray([3, 4], np.int32), max_new=8)
    r3.out_tokens = [5, 3, 4]
    assert d.propose(r3, 3) == [5, 3, 4]
    # no repeat anywhere -> silence, and the stats notice
    r4 = Request(rid=3, prompt=np.asarray([1, 2, 3, 4, 5], np.int32),
                 max_new=8)
    assert d.propose(r4, 2) == []
    assert d.stats()["misses"] == 1 and d.stats()["proposals"] == 3


def test_request_context_is_fold_invariant():
    """Preemption COPIES out_tokens[:folded] into the prompt and keeps
    out_tokens whole (that is what kv_budget and re-folds rely on), so the
    drafters' context helper must skip the folded prefix — concatenating
    the full out_tokens would duplicate it, mis-aiming ngram lookups and
    feeding a draft model a corrupted (and over-long) stream."""
    r = Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32), max_new=10)
    r.out_tokens = [7, 8, 9]
    assert request_context(r).tolist() == [1, 2, 3, 7, 8, 9]
    # after a fold of the first two generated tokens
    r.prompt = np.asarray([1, 2, 3, 7, 8], np.int32)
    r.folded = 2
    assert request_context(r).tolist() == [1, 2, 3, 7, 8, 9]
    # the ngram drafter sees the true stream, not a duplicated seam: on
    # the true [1,2,9,1,2] the trailing [1,2] recurs at 0 followed by 9;
    # the buggy doubled stream [1,2,9,1,2,1,2] would match the phantom
    # copy at 3 instead and propose [1,2]
    d = NgramDrafter(max_ngram=3)
    rf = Request(rid=1, prompt=np.asarray([1, 2, 9, 1, 2], np.int32),
                 max_new=10)
    rf.out_tokens = [1, 2]
    rf.folded = 2                 # prompt tail [1, 2] is the fold copy
    assert d.propose(rf, 2) == [9, 1]


# ---------------------------------------------------------------------------
# warmup / no-recompile, preemption, validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 8])
def test_no_compiles_after_warmup_with_spec(smollm, chunk):
    """Zero-recompile contract with speculation on: warmup covers the
    verify shapes (and the drafter's), then a trace with admissions,
    drafted/undrafted steps, growth and displacement compiles nothing."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, num_pages=1 + 6,
                 chunk_tokens=chunk, spec_tokens=2)
    eng.warmup()
    assert eng.pool.num_used == 0 and eng.pool.total_allocs == 0
    before = dict(m.trace_counts)
    reqs = list(zip(_prompts(cfg, [4, 25, 6, 30], seed=3), [16, 10, 16, 8]))
    fin = _drain(eng, reqs)
    assert eng.num_preemptions + eng.num_pauses >= 1
    assert sum(len(r.out_tokens) for r in fin) == 16 + 10 + 16 + 8
    assert dict(m.trace_counts) == before, \
        "speculative Engine.step compiled a new shape after warmup()"


def test_spec_preemption_never_folds_rejected_tokens(smollm):
    """Speculation under page pressure: outputs identical to the ample
    non-spec baseline through preemptions, and every folded prompt is
    original prompt + an accepted-output prefix — a rejected draft can
    never reach a recompute prompt because out_tokens never holds one."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, [6, 5])
    news = [12, 12]
    ample = Engine(m, params, max_slots=2, page_tokens=8)
    rids = [ample.add_request(p, n) for p, n in zip(prompts, news)]
    want = {r.rid: r.out_tokens for r in ample.drain()}

    for greedy in (True, False):
        w = want
        if not greedy:
            b = Engine(m, params, max_slots=2, page_tokens=8)
            for p, n in zip(prompts, news):
                b.add_request(p, n)
            w = {r.rid: r.out_tokens for r in b.drain(greedy=False, seed=5)}
        tight = Engine(m, params, max_slots=2, page_tokens=8,
                       num_pages=1 + 4, spec_tokens=2)
        for p, n in zip(prompts, news):
            tight.add_request(p, n)
        fin = {r.rid: r for r in tight.drain(greedy=greedy, seed=5)}
        assert {rid: r.out_tokens for rid, r in fin.items()} == w
        assert tight.num_preemptions >= 1
        assert tight.pool.num_used == 0
        assert tight.pool.total_allocs == tight.pool.total_frees
        for rid, r in fin.items():
            orig = prompts[rid].tolist()
            folded = r.prompt.tolist()
            assert folded[:len(orig)] == orig
            assert folded[len(orig):] == w[rid][:len(folded) - len(orig)]


def test_spec_constructor_validation(smollm):
    cfg, m, params = smollm
    with pytest.raises(AssertionError, match="at least one draft"):
        Engine(m, params, spec_tokens=0)
    with pytest.raises(AssertionError, match="drafter needs spec_tokens"):
        Engine(m, params, drafter=NgramDrafter())
    with pytest.raises(AssertionError, match="shape ladder"):
        Engine(m, params, chunk_tokens=8, spec_tokens=8)
    with pytest.raises(AssertionError, match="vocab"):
        import dataclasses
        odd = dataclasses.replace(cfg, vocab=cfg.vocab * 2, name="odd-vocab")
        om = build_model(odd, RUN, ShapeSpec("serve", 64, 2, "decode"))
        Engine(m, params, spec_tokens=2,
               drafter=DraftModelDrafter(om, om.init(jax.random.PRNGKey(0))))


def test_draft_model_reconcile_when_speculation_covered_context(smollm):
    """Shed-draft regression: the engine may trim away a proposal (page
    pressure / same-step preemption) and then commit the very token the
    drafter speculated.  The drafter's cache then already covers the whole
    context at the next propose — it must re-derive the last position's
    logits (identical KV overwrite) instead of crashing with nothing to
    draft from, and keep proposing the same chain it would have fresh."""
    cfg, m, params = smollm
    dcfg = reduced_config(get_config("smollm2-135m"), layers=1)
    dm = build_model(dcfg, RUN, ShapeSpec("serve", 64, 3, "decode"))
    d = DraftModelDrafter(dm, dm.init(jax.random.PRNGKey(3)))
    r = Request(rid=0, prompt=np.asarray([5, 9, 2, 7], np.int32), max_new=10)
    r.out_tokens = [3]
    first = d.propose(r, 2)
    assert len(first) == 2
    # the engine sheds the draft but its own pick matches the speculation:
    # context grows by exactly the token the drafter already wrote KV for
    r.out_tokens.append(first[0])
    second = d.propose(r, 2)
    fresh = DraftModelDrafter(dm, dm.init(jax.random.PRNGKey(3)))
    assert second == fresh.propose(r, 2), \
        "reconciled propose must equal a from-scratch propose"


def test_hybrid_families_refuse_spec():
    cfg = reduced_config(get_config("rwkv6-1.6b"))
    m = build_model(cfg, RUN, ShapeSpec("serve", 64, 2, "decode"))
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(AssertionError, match="speculative decode"):
        Engine(m, params, spec_tokens=2)
    with pytest.raises(AssertionError, match="pure-attention draft"):
        DraftModelDrafter(m, params)
