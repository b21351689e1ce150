"""Flat token-level serving step (the [1, budget] packed layout).

Contracts covered:
  - the flat step is token-identical to both the dense chunked step and
    the monolithic baseline — greedy and seeded-sampled — and stays so
    under speculation (n-gram and draft-model), a prefix cache, and a
    pool tight enough to force preemptions and mid-prefill pauses;
  - after Engine.warmup() a flat drain with speculation and prefix-cache
    hits triggers zero new XLA traces on the target AND the draft model;
  - budget exactness: no flat step ever carries more real tokens than
    the token budget (decode tokens excepted — they are unconditional),
    and every decoding row appears in every step (decode never stalls
    behind prefill);
  - the width ladder is m_r-aligned, descending, and _flat_shape picks
    the smallest width that holds the step;
  - the Pallas ragged-attention kernel (interpret mode) matches the jnp
    reference oracle on mixed decode/prefill segments with padding rows;
  - eos classification is one shared rule (scheduler.finish_reason_for)
    across the continuous and static paths: eos strictly before the last
    position is "eos", eos AS the last position is "length";
  - mid-draft eos regression: a draft that runs past eos is truncated —
    the block table ends at the eos position and no post-eos draft KV
    can reach the prefix cache (a second identical request must hit the
    cache and still reproduce the baseline).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.kernels.ragged_attn.kernel import (block_pages,
                                              ragged_attention_kernel_call)
from repro.kernels.ragged_attn.ref import \
    ragged_attention_ref as ragged_attention_reference
from repro.models import attention
from repro.models.attention import KVPageLayout
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.scheduler import Request, finish_reason_for
from repro.serving.speculative import (Drafter, DraftModelDrafter,
                                       NgramDrafter)

RUN = RunConfig(param_dtype="float32", compute_dtype="float32", remat=False)


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced_config(get_config("smollm2-135m"), layers=2)
    shape = ShapeSpec("serve", 64, 3, "decode")
    m = build_model(cfg, RUN, shape)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


@pytest.fixture(scope="module")
def draft(smollm):
    cfg, _, _ = smollm
    dcfg = reduced_config(cfg, layers=1)
    dm = build_model(dcfg, RUN, ShapeSpec("serve", 64, 3, "decode"))
    return dm, dm.init(jax.random.PRNGKey(3))


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


REQS = ([13, 21, 3, 16], [8, 6, 10, 7])


@pytest.fixture(scope="module")
def baseline(smollm):
    """Monolithic-prefill reference outputs, greedy and sampled."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    eng = Engine(m, params, max_slots=3)
    greedy = [r.out_tokens for r in _drain(eng, reqs)]
    eng = Engine(m, params, max_slots=3)
    sampled = [r.out_tokens for r in _drain(eng, reqs, greedy=False, seed=7)]
    return reqs, greedy, sampled


# ---------------------------------------------------------------------------
# token identity: flat == dense chunked == monolithic
# ---------------------------------------------------------------------------

def test_flat_matches_chunked_and_monolithic(smollm, baseline):
    """The tentpole identity: same prompts, three engines (flat, dense
    chunked, monolithic), one token stream.  The budget (24) is a
    non-divisor of most prompts so segments split mid-chunk."""
    cfg, m, params = smollm
    reqs, greedy, sampled = baseline
    flat = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                  token_budget=24)
    assert flat.flat            # flat defaults on whenever chunking is on
    got = _drain(flat, reqs)
    assert [r.out_tokens for r in got] == greedy
    assert flat.pool.num_used == 0
    st = flat.stats()["flat"]
    assert st["steps"] > 0 and st["token_budget"] == 24

    dense = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                   token_budget=24, flat=False)
    assert not dense.flat
    assert [r.out_tokens for r in _drain(dense, reqs)] == greedy


def test_flat_matches_baseline_sampled(smollm, baseline):
    """Sampling keys are (seed, rid, position)-derived: the flat layout
    must be invisible to sampled continuations too."""
    cfg, m, params = smollm
    reqs, _, sampled = baseline
    eng = Engine(m, params, max_slots=3, chunk_tokens=16, token_budget=24)
    assert [r.out_tokens for r in
            _drain(eng, reqs, greedy=False, seed=7)] == sampled


def test_flat_requires_chunking(smollm):
    cfg, m, params = smollm
    with pytest.raises(AssertionError):
        Engine(m, params, max_slots=3, flat=True)


def test_flat_preemption_token_identical(smollm):
    """A pool at ~half the working set forces folds and mid-prefill
    pauses; the flat engine must still reproduce the ample-pool
    monolithic outputs exactly and balance the pool."""
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


# ---------------------------------------------------------------------------
# speculation and prefix cache over the flat step
# ---------------------------------------------------------------------------

def test_flat_spec_ngram_matches_baseline(smollm, baseline):
    cfg, m, params = smollm
    reqs, greedy, sampled = baseline
    for gr, seed, want in [(True, 0, greedy), (False, 7, sampled)]:
        eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                     token_budget=24, spec_tokens=2, drafter=NgramDrafter())
        assert eng.flat
        got = _drain(eng, reqs, greedy=gr, seed=seed)
        assert [r.out_tokens for r in got] == want
        assert eng.pool.num_used == 0


def test_flat_spec_draft_model_matches_baseline(smollm, draft, baseline):
    """Draft-model speculation over the flat step — exercises the batched
    propose_all path (one [slots, 1] draft call per position, not one
    [1, 1] call per row per position)."""
    cfg, m, params = smollm
    dm, dparams = draft
    reqs, greedy, _ = baseline
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, spec_tokens=3,
                 drafter=DraftModelDrafter(dm, dparams))
    got = _drain(eng, reqs)
    assert [r.out_tokens for r in got] == greedy
    sp = eng.stats()["speculative"]
    st = sp["drafter"]
    assert st["drafter"] == "draft-model"
    assert st["live_states"] == 0            # forget() ran for every rid
    assert sp["drafted"] > 0
    # batching: the drafter launches O(positions) batched steps per engine
    # step, never O(rows * positions) single-row steps — with 3 slots and
    # k=3 a per-row drafter needs ~3x the launches of a batched one
    assert st["draft_steps"] <= eng.stats()["steps"] * (eng.spec_tokens + 1)


def test_flat_prefix_cache_hits_and_identity(smollm, baseline):
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, prefix_cache=True)
    assert [r.out_tokens for r in _drain(eng, reqs)] == greedy
    # identical prompts again: served from cached pages, same tokens
    assert [r.out_tokens for r in _drain(eng, reqs)] == greedy
    st = eng.stats()["prefix_cache"]
    assert st["hits"] >= 1
    eng.prefix_cache.clear()
    assert eng.pool.num_used == 0


# ---------------------------------------------------------------------------
# zero recompiles after warmup
# ---------------------------------------------------------------------------

def test_flat_zero_recompile_after_warmup(smollm, draft):
    """warmup() compiles the whole flat width ladder (x verify widths) and
    the draft model's batch widths; a subsequent drain with speculation,
    prefix-cache hits and chunked prefill must trace nothing new on the
    target or the draft model."""
    cfg, m, params = smollm
    dm, dparams = draft
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, spec_tokens=2, prefix_cache=True,
                 drafter=DraftModelDrafter(dm, dparams))
    eng.warmup()
    before_t = dict(m.trace_counts)
    before_d = dict(dm.trace_counts)
    reqs = list(zip(_prompts(cfg, [13, 21, 3, 16, 13]), [8, 6, 10, 7, 8]))
    _drain(eng, reqs)
    assert dict(m.trace_counts) == before_t, \
        f"target retraced: {before_t} -> {dict(m.trace_counts)}"
    assert dict(dm.trace_counts) == before_d, \
        f"draft retraced: {before_d} -> {dict(dm.trace_counts)}"


# ---------------------------------------------------------------------------
# budget exactness and the width ladder
# ---------------------------------------------------------------------------

def test_flat_budget_exactness(smollm):
    """Spy on the flat launch: (a) real (non-pad) tokens never exceed the
    budget, (b) every slot that is decoding when the step launches has at
    least one position in the step — decode never stalls on prefill
    backlog, (c) the width is the smallest ladder rung holding the real
    count."""
    cfg, m, params = smollm
    budget = 16
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=8,
                 token_budget=budget)
    seen = []
    orig = eng._run_flat

    def spy(token, bt, row_ids, q_pos, idx, **counts):
        decoding = {s for s, r in eng.scheduler.running.items()
                    if r.status == "running"}
        real = row_ids[row_ids >= 0]
        seen.append((int(real.size), set(int(x) for x in np.unique(real)),
                     decoding, row_ids.size))
        return orig(token, bt, row_ids, q_pos, idx, **counts)

    eng._run_flat = spy
    reqs = list(zip(_prompts(cfg, [13, 21, 3, 16]), [8, 6, 10, 7]))
    _drain(eng, reqs)
    assert seen
    for real, rows, decoding, width in seen:
        assert 0 < real <= budget
        assert decoding <= rows, f"decoding slots {decoding} stalled ({rows})"
        assert width == eng._flat_shape(real)
    # at least one step must actually mix prefill and decode segments
    assert any(len(rows) > 1 for _, rows, _, _ in seen)


def test_flat_width_ladder(smollm):
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=8,
                 token_budget=24)
    ladder = eng._flat_shapes()
    mr = eng._bucket
    assert ladder == sorted(ladder, reverse=True)
    assert all(w % mr == 0 for w in ladder)
    assert ladder[0] >= 24 and ladder[-1] == mr
    # the chosen width is the smallest rung that fits
    for n in range(1, ladder[0] + 1):
        w = eng._flat_shape(n)
        assert w >= n and all(r < n for r in ladder if r < w)
    # speculation raises the cap so a full verify burst always fits
    eng2 = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=8,
                  token_budget=8, spec_tokens=5,
                  drafter=NgramDrafter())
    assert eng2._flat_shapes()[0] >= 3 * 6


# ---------------------------------------------------------------------------
# Pallas kernel vs reference oracle
# ---------------------------------------------------------------------------

def _pool(key, layers, pages, hkv, dh, t, dtype):
    shape = (layers, pages) + KVPageLayout(hkv, dh, t).page_shape
    return jax.random.normal(key, shape).astype(dtype)


# segment mixes: page tokens, block table width MP, and each segment's
# (row, first q_pos, positions); row -1 is padding.  SHORT: a decode row at
# 17, a mid-prefill chunk at 8..12, a fresh 4-token prefill.  BLOCKS, at
# 16-token pages where the kernel copies 8-page blocks and MP 27 is not a
# multiple of 8: a decode row whose last live page (19) ends mid-block, a
# pad, a decode row whose last live page (15) ends block 1, a chunk at
# 120..140 across the block boundary at page 8, and a decode row on the
# table's last page (26, a 3-page block); tails of the table are page 0
SHORT = (8, 3, [(0, 17, 1), (1, 8, 5), (2, 0, 4)])
BLOCKS = (16, 27, [(0, 309, 1), (-1, 0, 1), (1, 255, 1), (2, 120, 21),
                   (3, 431, 1)])


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("hq,hkv,dh,dtype,tol,mix", [
    (4, 2, 8, jnp.float32, 1e-5, SHORT),
    # smollm2-135m's heads in bf16: both sides round once to bf16, so one
    # ulp (2^-6 at |x| < 4) is the most they may differ by
    (9, 3, 64, jnp.bfloat16, 2 ** -6, SHORT),
    # olmo-1b's head width: one token a 128-lane row
    (4, 2, 128, jnp.float32, 1e-5, SHORT),
    (9, 3, 64, jnp.bfloat16, 2 ** -6, BLOCKS),
    (16, 16, 128, jnp.float32, 1e-5, BLOCKS),
], ids=["4-2-8-f32", "9-3-64-bf16", "4-2-128-f32", "9-3-64-bf16-blocks",
        "16-16-128-f32-blocks"])
def test_ragged_kernel_matches_reference(hq, hkv, dh, dtype, tol, mix, layer):
    """Interpret-mode Pallas kernel vs the jnp oracle on mixed segments
    and -1 padding, read from the first or the last layer of a stacked
    pool.  Decode rows and chunks end on odd in-page offsets, so both lane
    halves of a folded row are live; BLOCKS spans several page blocks."""
    t, mp, segs = mix
    w = 16 if mix is SHORT else 32
    key = jax.random.PRNGKey(0)
    n_rows = max(r for r, _, _ in segs) + 1
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (w, hq, dh)).astype(dtype)
    row_ids = np.full(w, -1, np.int32)
    q_pos = np.zeros(w, np.int32)
    pos = 0
    for row, first, n in segs:
        row_ids[pos:pos + n], q_pos[pos:pos + n] = row, first + np.arange(n)
        pos += n
    if mix is SHORT:
        # every slot of the table a real page, shuffled
        layers, pages = 3, 9
        bt = np.asarray(jax.random.permutation(jax.random.PRNGKey(5),
                                               pages)[: n_rows * mp],
                        np.int32).reshape(n_rows, mp)
    else:
        # each row's live pages shuffled over the pool, page 0 after them
        live = [int(q_pos[row_ids == r].max()) // t + 1 for r in range(n_rows)]
        layers, pages = 2, 1 + sum(live)
        perm = np.asarray(jax.random.permutation(jax.random.PRNGKey(5),
                                                 pages - 1), np.int32) + 1
        bt = np.zeros((n_rows, mp), np.int32)
        for r, n in enumerate(live):
            bt[r, :n], perm = perm[:n], perm[n:]
    k_pages = _pool(ks[1], layers, pages, hkv, dh, t, dtype)
    v_pages = _pool(ks[2], layers, pages, hkv, dh, t, dtype)
    args = dict(layer=jnp.int32(0 if layer == "first" else layers - 1),
                block_tables=jnp.asarray(bt), row_ids=jnp.asarray(row_ids),
                q_pos=jnp.asarray(q_pos))
    ref = ragged_attention_reference(q, k_pages, v_pages, **args)
    out = ragged_attention_kernel_call(q, k_pages, v_pages, interpret=True,
                                       **args)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32)[row_ids >= 0],
                               np.asarray(ref, np.float32)[row_ids >= 0],
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("hkv,dh,dtype,mp,want", [
    (3, 64, jnp.bfloat16, 64, 8),       # smollm2-135m's cell
    (16, 128, jnp.bfloat16, 128, 8),    # olmo-1b's cell
    (16, 128, jnp.float32, 128, 8),     # 4 MiB of buffers exactly
    (32, 256, jnp.float32, 128, 2),     # a 512 KiB page
    (3, 64, jnp.bfloat16, 3, 3),        # a table narrower than a block
])
def test_block_pages_rule(hkv, dh, dtype, mp, want):
    """K, the pages a kernel block copies, from the shapes alone: about
    128 tokens of 16-token pages, fewer where the page buffers would pass
    the VMEM budget, never more than the table holds."""
    lay = KVPageLayout(hkv, dh, 16)
    page_bytes = int(np.prod(lay.page_shape)) * jnp.dtype(dtype).itemsize
    assert block_pages(page_bytes, 16, mp) == want


@pytest.mark.parametrize("dh", [64, 128])
def test_flat_scatter_round_trip(dh):
    """A token scattered at (layer, page, off) is the one the oracle and
    the kernel read at that position.  Keys are zero, so each query
    averages the values of its row's positions up to its own; values are
    the writing stream position's id, so the average names what was read.
    Row 1 writes odd offsets alone, and a second step writes the partner
    of an earlier token: a folded row keeps the lanes the step does not
    write."""
    hkv, hq, t, layers, pages = 2, 4, 16, 2, 6
    lay = KVPageLayout(hkv, dh, t)
    bt = jnp.asarray([[3, 1], [5, 2]], jnp.int32)
    zero = jnp.zeros((layers, pages) + lay.page_shape)
    # step 1: row 0 writes positions 5..20 (pages 3 and 1), row 1 the odd
    # positions 9 and 19; padding writes the trash page.  step 2: row 1
    # writes position 8, beside the 9 of step 1
    steps = [([0] * 16 + [1, 1, -1, -1], list(range(5, 21)) + [9, 19, 0, 0]),
             ([1, -1, -1, -1], [8, 0, 0, 0])]
    for layer in range(layers):
        cache = {"k_pages": zero, "v_pages": zero}
        written, base = {}, 0
        for rows, pos in steps:
            row_ids = jnp.asarray(rows, jnp.int32)
            q_pos = jnp.asarray(pos, jnp.int32)
            w = len(rows)
            ids = base + jnp.arange(w, dtype=jnp.float32) + 1.0
            base += w
            v = jnp.broadcast_to(ids[None, :, None, None], (1, w, hkv, dh))
            cache = attention.flat_paged_kv_update(
                cache, jnp.zeros((1, w, hkv, dh)), v,
                layer=jnp.int32(layer), block_tables=bt, row_ids=row_ids,
                q_pos=q_pos)
            written.update({(r, p): float(i) for r, p, i
                            in zip(rows, pos, ids) if r >= 0})
        vp = cache["v_pages"]
        assert not np.asarray(vp[1 - layer]).any()   # other layer untouched
        toks = lay.tokens(vp[layer])                 # [P, T, Hkv, dh]
        for (r, p), i in written.items():
            np.testing.assert_array_equal(
                np.asarray(toks[int(bt[r, p // t]), p % t]), i)
        keys = sorted(written)
        row_ids = jnp.asarray([r for r, _ in keys], jnp.int32)
        q_pos = jnp.asarray([p for _, p in keys], jnp.int32)
        q = jnp.zeros((len(keys), hq, dh))
        sel = dict(layer=jnp.int32(layer), block_tables=bt, row_ids=row_ids,
                   q_pos=q_pos)
        ref = ragged_attention_reference(q, cache["k_pages"], vp, **sel)
        out = ragged_attention_kernel_call(q, cache["k_pages"], vp,
                                           interpret=True, **sel)
        want = [np.mean([written.get((r, j), 0.0) for j in range(p + 1)])
                for r, p in keys]
        for got in (ref, out):
            np.testing.assert_allclose(np.asarray(got)[:, 0, 0], want,
                                       rtol=1e-6)


# ---------------------------------------------------------------------------
# eos: one classification rule across continuous and static paths
# ---------------------------------------------------------------------------

def test_finish_reason_rule():
    """eos strictly before the final position is "eos"; eos AS the final
    position is "length" (the row used its whole allowance)."""
    assert finish_reason_for([1, 9, 2, 3], 4, 9) == (2, "eos")
    assert finish_reason_for([1, 2, 3, 9], 4, 9) == (4, "length")
    assert finish_reason_for([1, 2, 3, 4], 4, 9) == (4, "length")
    assert finish_reason_for([9, 1, 2], 4, 9) == (1, "eos")
    assert finish_reason_for([1, 2], 4, None) == (2, "length")
    assert finish_reason_for([9], 1, 9) == (1, "length")   # eos at the cap


def test_request_done_uses_shared_rule():
    r = Request(rid=0, prompt=np.zeros(3, np.int32), max_new=4, eos_id=9,
                arrival=0.0)
    r.out_tokens = [1, 2, 3, 9]
    assert r.done() and r.finish_reason == "length"
    r2 = Request(rid=1, prompt=np.zeros(3, np.int32), max_new=4, eos_id=9,
                 arrival=0.0)
    r2.out_tokens = [1, 9]
    assert r2.done() and r2.finish_reason == "eos"


def test_continuous_and_static_eos_agree(smollm, baseline):
    """Both generate() paths must classify identically: run the continuous
    path with an eos drawn from the baseline stream and check every row's
    reason against finish_reason_for applied to its no-eos stream."""
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    eos = greedy[0][2]          # row 0 finishes early; others data-dependent
    max_new = 8
    eng = Engine(m, params, max_slots=3)
    out, reasons = eng.generate(
        {"tokens": np.stack([np.resize(r[0], 13) for r in reqs[:2]])},
        max_new, eos_id=eos, return_reasons=True)
    for i in range(out.shape[0]):
        row = list(out[i])
        kept, want = finish_reason_for(row[:max_new], max_new, eos)
        assert reasons[i] == want
        if want == "eos":
            assert all(t == eos for t in row[kept - 1:])


# ---------------------------------------------------------------------------
# mid-draft eos regression
# ---------------------------------------------------------------------------

class TruthDrafter(Drafter):
    """Proposes the request's true greedy continuation, INCLUDING tokens
    past eos — every draft position verifies as accepted, so a draft burst
    deliberately writes KV beyond end-of-sequence.  The engine must roll
    that KV back when it cuts the stream at eos."""

    def __init__(self, outs_by_prompt):
        self.outs = outs_by_prompt      # prompt bytes -> full greedy stream

    def propose(self, req, k):
        done = len(req.out_tokens)
        nxt = self.outs[np.asarray(req.prompt).tobytes()][done:done + k]
        return [int(t) for t in nxt]


@pytest.mark.parametrize("use_cache", [False, True])
def test_mid_draft_eos_truncates_kv(smollm, baseline, use_cache):
    """eos arrives mid-draft (the oracle keeps proposing past it, and the
    target accepts everything): outputs must stop exactly at eos, the
    block table must shrink to the kept length (the in-step assert in
    _verify_decode_row guards this), the pool must balance, and with a
    prefix cache a rerun of the same prompt must hit the cache and still
    match — proof no post-eos draft KV was inserted."""
    cfg, m, params = smollm
    reqs, greedy, _ = baseline
    # eos = the 4th baseline token of row 0: eos lands mid-stream, and with
    # k=4 the oracle drafts through and past it in one burst
    eos = greedy[0][3]
    outs = {np.asarray(p).tobytes(): toks
            for (p, _), toks in zip(reqs, greedy)}
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, spec_tokens=4, prefix_cache=use_cache,
                 drafter=TruthDrafter(outs))
    rids = [eng.add_request(p, n, eos_id=eos) for p, n in reqs]
    fin = {r.rid: r for r in eng.drain()}
    for i, rid in enumerate(rids):
        req = fin[rid]
        kept, reason = finish_reason_for(greedy[i], reqs[i][1], eos)
        assert req.out_tokens == greedy[i][:kept]
        assert req.finish_reason == reason
    assert eng.pool.total_allocs == eng.pool.total_frees
    if use_cache:
        # rerun: the cached pages must reproduce the same truncated stream
        rids = [eng.add_request(p, n, eos_id=eos) for p, n in reqs]
        fin = {r.rid: r for r in eng.drain()}
        for i, rid in enumerate(rids):
            kept, _ = finish_reason_for(greedy[i], reqs[i][1], eos)
            assert fin[rid].out_tokens == greedy[i][:kept]
        assert eng.stats()["prefix_cache"]["hits"] >= 1
        eng.prefix_cache.clear()
    assert eng.pool.num_used == 0
