"""Overload and fault-tolerance contracts (PR 8).

Covers the resilience layer end to end:
  - identity under chaos: for seeded fault plans (OutOfPages spikes,
    drafter failures mid-spec, NaN-logit injection, page-copier
    failures), every surviving request's tokens are bit-identical to the
    fault-free run — the flat step, greedy and sampled picks,
    prefix cache on and off — and the allocator is balanced afterwards;
  - cancellation from every lifecycle state releases every page
    (property test interleaving admit/chunk/spec/preempt/cancel over the
    real Scheduler, extending the PR-5 allocator property);
  - deadlines and admission control: ``deadline_s``/``max_queue_s``
    produce ``timeout`` rows, a bounded queue produces fast ``rejected``
    rows, and ``drain``/``generate`` pad both exactly like eos rows;
  - the degradation ladder: repeated drafter failure auto-disables
    speculation for the drain, a NaN row is quarantined without
    poisoning the prefix cache, and a stuck drain raises a diagnosable
    ``StallError`` naming the non-advancing rids;
  - ``FaultPlan`` replayability: same seed, same events.
"""

import random

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis.aliasing import check_pool_consistency
from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.faults import (FaultEvent, FaultPlan, InjectedFault,
                                  StallError)
from repro.serving.kv_cache import OutOfPages, PagedKVPool, PoolError
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import AdmissionError, Request, Scheduler

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


def _drain_outputs(engine, prompts, news, *, greedy=True, plan=None, seed=0):
    for p, n in zip(prompts, news):
        engine.add_request(p, n)
    if plan is not None:
        with plan.on(engine):
            fin = engine.drain(greedy=greedy, seed=seed)
    else:
        fin = engine.drain(greedy=greedy, seed=seed)
    return {r.rid: (list(r.out_tokens), r.finish_reason) for r in fin}


# ---------------------------------------------------------------------------
# identity under chaos (tentpole headline invariant)
# ---------------------------------------------------------------------------

_PLANS = {
    "oom-spike": lambda: FaultPlan([FaultEvent(1, "oom"), FaultEvent(2, "oom"),
                                    FaultEvent(4, "oom")]),
    "drafter-mid-spec": lambda: FaultPlan(
        [FaultEvent(s, "drafter") for s in (1, 2, 3, 5, 7)]),
    "nan-quarantine": lambda: FaultPlan([FaultEvent(3, "nan")]),
    "copier-failure": lambda: FaultPlan([FaultEvent(1, "copier"),
                                         FaultEvent(3, "copier")]),
}

_CONFIGS = {
    "flat-greedy-cache": (dict(chunk_tokens=8, prefix_cache=True), True),
    "flat-sampled": (dict(chunk_tokens=8), False),
    "flat-spec-greedy-cache": (dict(chunk_tokens=8, spec_tokens=3,
                                    prefix_cache=True), True),
}


@pytest.mark.parametrize("config", sorted(_CONFIGS))
def test_chaos_identity(smollm, config):
    """Every surviving request of a faulted drain is token-identical to
    the fault-free drain, and the allocator audits clean afterwards."""
    cfg, m, params = smollm
    kwargs, greedy = _CONFIGS[config]
    lens, news = [5, 11, 8, 3], [6, 4, 9, 7]
    prompts = _prompts(cfg, lens)

    clean = _drain_outputs(Engine(m, params, max_slots=3, **kwargs),
                           prompts, news, greedy=greedy)
    assert all(reason in ("length", "eos") for _, reason in clean.values())

    for name, make_plan in sorted(_PLANS.items()):
        eng = Engine(m, params, max_slots=3, **kwargs)
        plan = make_plan()
        out = _drain_outputs(eng, prompts, news, greedy=greedy, plan=plan)
        assert set(out) == set(clean), f"{name}: lost requests"
        for rid, (toks, reason) in out.items():
            if reason == "error":
                continue                       # quarantined casualty
            assert (toks, reason) == clean[rid], \
                f"{name}: surviving rid {rid} diverged from the clean run"
        # allocator balanced: no leaked pages, ledger consistent, no
        # retired rid holding pages
        assert not check_pool_consistency(eng, f"chaos:{name}")
        live = sum(len(s.pages) for s in eng.pool.sequences())
        cached = (len(set(eng.prefix_cache.pages()))
                  if eng.prefix_cache is not None else 0)
        assert eng.pool.num_used == live + cached == cached


def test_chaos_zero_retrace_after_warmup(smollm):
    """Fault handling must ride the warmed shapes: quarantine, rollback
    and preemption change host bookkeeping, never the compiled step."""
    cfg, m, params = smollm
    lens, news = [5, 11, 8, 3], [6, 4, 9, 7]
    prompts = _prompts(cfg, lens)
    eng = Engine(m, params, max_slots=3, chunk_tokens=8, spec_tokens=3,
                 prefix_cache=True)
    eng.warmup()
    before = sum(m.trace_counts.values())
    plan = FaultPlan([FaultEvent(1, "oom"), FaultEvent(2, "drafter"),
                      FaultEvent(3, "nan"), FaultEvent(4, "copier"),
                      FaultEvent(5, "drafter")])
    _drain_outputs(eng, prompts, news, plan=plan)
    assert sum(m.trace_counts.values()) == before, \
        "a faulted drain recompiled after warmup"


def test_nan_quarantine_frees_pages_and_skips_cache(smollm):
    """The quarantined row finishes with ``error``, its pages are freed,
    and the prefix cache gains nothing from it."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, [9, 6])
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, prefix_cache=True)
    plan = FaultPlan([FaultEvent(1, "nan")])
    out = _drain_outputs(eng, prompts, [5, 5], plan=plan)
    dead = [rid for rid, (_, reason) in out.items() if reason == "error"]
    assert len(dead) == 1 and plan.fired["nan"] == 1
    toks, _ = out[dead[0]]
    assert eng.stats()["resilience"]["quarantines"] == 1
    assert not check_pool_consistency(eng, "nan-quarantine")
    # no sequence of the dead rid holds pages
    assert not any(s.pages for s in eng.pool.sequences()
                   if s.owner == dead[0])


def test_drafter_auto_disable_counts_and_resets(smollm):
    """Three consecutive drafter failures disable speculation for the
    rest of the drain; the next drain gets the drafter back."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, [5, 8])
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, spec_tokens=3)
    plan = FaultPlan([FaultEvent(s, "drafter") for s in range(1, 12)])
    out = _drain_outputs(eng, prompts, [8, 8], plan=plan)
    res = eng.stats()["resilience"]
    assert res["spec_auto_disables"] == 1
    assert res["drafter_errors"] == eng._drafter_fail_limit, \
        "auto-disable must stop calling the broken drafter"
    assert not res["spec_disabled"], "the disable is per-drain"
    assert all(reason == "length" for _, reason in out.values())
    # a fresh drain speculates again, exactly as much as a fault-free
    # engine given the same request (16 tokens: the smoke model's output
    # must run long enough to repeat before the n-gram drafter matches)
    prompt = _prompts(cfg, [7])
    before = eng._drafted
    _drain_outputs(eng, prompt, [16])
    control = Engine(m, params, max_slots=2, chunk_tokens=8, spec_tokens=3)
    _drain_outputs(control, prompt, [16])
    assert control._drafted > 0
    assert eng._drafted - before == control._drafted


# ---------------------------------------------------------------------------
# deadlines / admission control / padding
# ---------------------------------------------------------------------------

def test_deadline_and_max_queue_timeouts(smollm):
    cfg, m, params = smollm
    prompts = _prompts(cfg, [6, 6, 6])
    eng = Engine(m, params, max_slots=2, chunk_tokens=8)
    eng.add_request(prompts[0], 4)
    eng.add_request(prompts[1], 4, deadline_s=0.5, arrival=0.0)
    eng.add_request(prompts[2], 4, max_queue_s=0.25, arrival=0.0)
    fin = {}
    # t=0: all live, third may admit or queue; t=1.0: both bounds elapsed
    for now in (0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        for r in eng.step(now=now):
            fin[r.rid] = r.finish_reason
    while eng.scheduler.has_work:
        for r in eng.step(now=9.0):
            fin[r.rid] = r.finish_reason
    assert fin[0] == "length"
    assert fin[1] in ("timeout", "length")       # raced its own decode
    res = eng.stats()["resilience"]
    assert res["timeouts"] >= 1
    assert not check_pool_consistency(eng, "timeouts")


def test_bounded_queue_sheds_with_typed_rejections(smollm):
    cfg, m, params = smollm
    prompts = _prompts(cfg, [4] * 6)
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, queue_limit=2)
    out = _drain_outputs(eng, prompts, [3] * 6)
    reasons = [reason for _, reason in out.values()]
    assert reasons.count("rejected") == 4, \
        "adds beyond queue_limit=2 must shed (none were admitted yet)"
    assert eng.stats()["resilience"]["sheds"] == 4
    # rejected rows never touched the pool
    assert not check_pool_consistency(eng, "shed")

    # the page-demand signal sheds on predicted demand, typed kind
    sched = Scheduler(2, PagedKVPool(1 + 4, 8), 48, queue_pages=2)
    sched.add(Request(rid=0, prompt=np.zeros(16, np.int32), max_new=4))
    with pytest.raises(AdmissionError) as e:
        sched.add(Request(rid=1, prompt=np.zeros(16, np.int32), max_new=4))
    assert e.value.kind == "page-demand" and e.value.rid == 1
    # an impossible request still raises out of Engine.add_request
    eng2 = Engine(m, params, max_slots=2, chunk_tokens=8, queue_limit=2)
    with pytest.raises(AdmissionError) as e2:
        eng2.add_request(np.zeros(80, np.int32), 60)
    assert e2.value.kind == "impossible"


def test_generate_pads_timeout_and_rejected_rows(smollm):
    """The PR-2 ragged ``np.stack`` fix extended: timeout/rejected/error
    rows pad to full width exactly like eos rows, and the undisturbed
    continuous result agrees with ``generate_static``."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, [6] * 4)
    batch = {"tokens": np.stack(prompts)}

    eng = Engine(m, params, max_slots=2, chunk_tokens=8, queue_limit=1)
    out, reasons = eng.generate(batch, 5, eos_id=7, return_reasons=True)
    assert out.shape == (4, 5), "shed rows must not produce ragged output"
    for i, reason in enumerate(reasons):
        if reason == "rejected":
            assert (out[i] == 7).all(), "shed rows pad with eos_id"
    # all four are added before any step runs, so one queues and the
    # rest shed at the bounded queue
    assert reasons.count("rejected") == 3

    # a deadline that can never fire leaves generate() == the static path
    eng2 = Engine(m, params, max_slots=4, chunk_tokens=8)
    timed = eng2.generate(batch, 5, deadline_s=3600.0)
    static = np.asarray(eng2.generate_static(batch, 5))
    np.testing.assert_array_equal(timed, static)

    # an already-elapsed deadline times every row out, still full width
    eng3 = Engine(m, params, max_slots=4, chunk_tokens=8)
    out3, reasons3 = eng3.generate(batch, 5, eos_id=7, deadline_s=0.0,
                                   return_reasons=True)
    assert out3.shape == (4, 5) and set(reasons3) == {"timeout"}
    assert (out3 == 7).all()
    assert not check_pool_consistency(eng3, "all-timeout")


def test_cancel_from_queued_prefilling_and_decoding(smollm):
    cfg, m, params = smollm
    prompts = _prompts(cfg, [20, 6, 5])
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, token_budget=8)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, (6, 6, 6))]
    assert eng.cancel(rids[2])                   # queued (never admitted)
    fin0 = eng.step()                            # delivers the cancel
    assert eng.scheduler.running, "admission should have happened"
    statuses = {r.rid: r.status for r in eng.scheduler.running.values()}
    assert statuses.get(rids[0]) == "prefilling", \
        "the 20-token prompt must still be mid-chunk at an 8-token budget"
    assert eng.cancel(rids[0])                   # prefilling, pages held
    fin = {r.rid: r.finish_reason for r in fin0 + eng.drain()}
    assert fin[rids[0]] == "cancelled" and fin[rids[2]] == "cancelled"
    assert fin[rids[1]] == "length"              # decodes to completion
    assert not eng.cancel(rids[1]), "finished rids are not cancellable"
    res = eng.stats()["resilience"]
    assert res["cancels"] == 2 and eng.pool.num_used == 0
    assert not check_pool_consistency(eng, "cancel-states")


def test_watchdog_turns_stuck_drain_into_stall_error(smollm):
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, watchdog_steps=5)
    orig_alloc = eng.pool.alloc

    def dead_alloc(*a, **k):
        raise OutOfPages("wedged pool (test)")
    eng.pool.alloc = dead_alloc
    rid = eng.add_request(_prompts(cfg, [6])[0], 4)
    with pytest.raises(StallError) as e:
        eng.drain()
    assert f"rid {rid}" in str(e.value)
    assert eng.stats()["resilience"]["watchdog_trips"] == 1
    eng.pool.alloc = orig_alloc
    fin = eng.drain()                            # recovers once unwedged
    assert [r.finish_reason for r in fin] == ["length"]


def test_fault_plan_is_replayable():
    a, b = FaultPlan.random(11, steps=20), FaultPlan.random(11, steps=20)
    assert a.events == b.events
    assert FaultPlan.random(12, steps=20).events != a.events
    with pytest.raises(ValueError):
        FaultPlan([FaultEvent(1, "segfault")])


# ---------------------------------------------------------------------------
# cancellation property (extends the PR-5 allocator property)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6), usable=st.integers(5, 12))
def test_property_cancel_interleaving_keeps_invariants(seed, usable):
    """Any interleaving of admit / chunked prefill / decode growth /
    spec-rollback / preempt / cancel over the real Scheduler keeps
    allocs+shares balanced against frees, refcounts >= 1 for live pages,
    and no cancelled request's pages live — from *any* lifecycle state."""
    rng = random.Random(seed)
    t = 8
    pool = PagedKVPool(1 + usable, t)
    pool.page_copier = lambda src, dst: None
    cache = PrefixCache(pool, layout_key=(4,))
    sched = Scheduler(3, pool, 6 * t, chunk_tokens=t, chunk_align=4,
                      prefix_cache=cache, queue_limit=6)
    next_rid = [0]
    retired = set()

    def tok(n):
        g = np.random.Generator(np.random.Philox(rng.randrange(999)))
        return g.integers(1, 50, size=n).astype(np.int32)

    def add():
        plen = rng.randrange(2, 3 * t)
        req = Request(rid=next_rid[0], prompt=tok(plen),
                      max_new=rng.randrange(1, 2 * t))
        try:
            sched.add(req)
            next_rid[0] += 1
        except AdmissionError:
            pass

    def admit():
        sched.admit()

    def chunk():
        for slot, n in list(sched.plan_chunks(t).items()):
            req = sched.running.get(slot)
            if req is None or n == 0 or req.status != "prefilling":
                continue
            req.prefill_cursor += n
            req.len = req.prefill_cursor
            cache.insert(req.prompt, req.pages.pages, req.prefill_cursor)
            if req.prefill_cursor >= req.prompt_len:
                req.status = "running"
                req.out_tokens.append(1)
                if req.done():
                    sched.finish(req)
                    retired.add(req.rid)

    def decode():
        sched.grow()
        for slot, req in list(sched.running.items()):
            if req.status != "running" or req.pages.capacity <= req.len:
                continue
            req.len += 1
            req.out_tokens.append(1)
            if req.done():
                sched.finish(req)
                retired.add(req.rid)

    def spec():
        rows = [(s, r) for s, r in sched.running.items()
                if r.status == "running"]
        if not rows:
            return
        slot, req = rng.choice(rows)
        sched.grow(want={slot: 3})               # speculative 1 + 2 ask
        if sched.running.get(slot) is not req:
            return                               # displaced by its own ask
        if req.pages.capacity > req.len:
            req.len += 1
            req.out_tokens.append(1)
        try:
            req.pages.truncate(req.len)          # rejected-draft rollback
        except PoolError:
            sched.cancel(req.rid, "error", cache_pages=False)
            retired.add(req.rid)
            return
        if req.done():
            sched.finish(req)
            retired.add(req.rid)

    def cancel():
        live = ([r.rid for r in sched.waiting]
                + [r.rid for r in sched.running.values()])
        if not live:
            return
        rid = rng.choice(live)
        reason = rng.choice(["cancelled", "timeout", "error"])
        sched.cancel(rid, reason, cache_pages=reason != "error")
        retired.add(rid)

    ops = [add, add, admit, chunk, decode, spec, cancel]
    for _ in range(80):
        rng.choice(ops)()
        assert all(v >= 1 for v in pool._ref.values())
        assert pool.num_used + pool.num_free == pool.usable_pages
        live_refs = sum(pool._ref.values())
        assert (pool.total_allocs + pool.total_shares
                == pool.total_frees + live_refs)
        for s in pool.sequences():
            assert not (s.owner in retired and s.pages), \
                f"retired rid {s.owner} still holds {s.pages}"

    for r in list(sched.waiting) + list(sched.running.values()):
        sched.cancel(r.rid)
    cache.clear()
    assert pool.num_used == 0
    assert pool.total_allocs + pool.total_shares == pool.total_frees
