"""Serving throughput: continuous batching vs static batching at mixed
prompt lengths / token budgets; scalable vs fixed layout policy; lazy page
allocation vs eager full-lifetime reservation on a long-tail trace;
chunked prefill vs monolithic prefill on a mixed long/short-prompt trace
(time-to-first-token and inter-token latency percentiles); speculative
decoding vs plain decode on an n-gram-friendly trace (token-identical
outputs asserted for greedy and sampled, decode tokens per row-step as the
speedup measure); and the prefix cache vs cache-off on a shared-system-
prompt trace (token-identical outputs asserted across greedy/sampled,
monolithic/chunked and spec-on at <= 0.5x the prefill tokens computed,
plus a tight-pool run showing preempt-resume recomputing only the
uncached suffix); and an overload section replaying arrivals at 130% of
the calibrated capacity with an unbounded vs bounded wait queue
(bounded admission sheds typed ``rejected`` rows and holds p95 TTFT for
the admitted requests — shed rate recorded, surviving outputs asserted
token-identical to the offline drain).

Results are also written machine-readable to ``BENCH_serving.json`` (see
``--json-out``) so the repo's perf trajectory is tracked across PRs.

Workload: N requests with mixed prompt lengths and per-request budgets,
all available at t=0 (offline throughput).

  - static: requests are grouped into arrival-order batches of ``--slots``;
    each batch pads every prompt to the batch max and decodes lock-step to
    the batch-max budget (tokens past a request's own budget are waste —
    that, plus prompt padding, is exactly the cost continuous batching
    removes).  Padded prompts make static outputs approximate; this is a
    throughput comparison, correctness equivalence is proven in
    tests/test_scheduler.py.
  - continuous: every request is admitted into a paged-KV slot as one frees,
    prefilled at its own (m_r-bucketed) length, and retired the step its own
    budget completes.

Useful tokens are identical in both modes (each request's own budget), so
throughput ratios are directly comparable.  Each mode runs once untimed
(compile warmup) and once timed.

The **long-tail section** replays a trace where most requests have short
output budgets and a tail runs to the context limit, against a KV pool
sized at 50% of what eager reservation would need to keep every slot busy.
Eager admission serializes behind the tail's reservations; lazy allocation
admits by actual prompt size, grows pages per decode step, and preempts
(by recomputation) when the pool runs dry — same pool, higher mean slot
occupancy and 1.4-2x the throughput at the default sizes (CPU-host timing
is noisy; the occupancy gap is the stable signal), with bit-identical
greedy outputs (asserted against the eager baseline).  A chunked row runs
the same trace through the fused ragged step — outputs must again be
bit-identical, through folds, pauses and stalls.

The **chunked-prefill section** replays a mixed trace — decode-heavy short
requests punctuated by long prompts — at a fixed offered load (95% of the
calibrated monolithic capacity, the serving-benchmark standard) and
compares monolithic prefill (every admission freezes all decode slots for
one full-prompt forward) against the fused chunked step (each admission is
spread across steps at ``chunk_tokens`` per step while every decode row
keeps advancing).  The headline is the p95 inter-token latency at equal
delivered throughput: under monolithic prefill the p95 ITL *is* the
long-prompt prefill time; chunked bounds it near one fused step — >= 2x
better at the default sizes (90%+ offered load, 0.95 in the default run).
Outputs are asserted token-identical, so the latency win is free.

Run:  PYTHONPATH=src python benchmarks/bench_serving.py
Toy:  PYTHONPATH=src python benchmarks/bench_serving.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import time
from datetime import datetime, timezone

import jax
import numpy as np

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.core.layout import ceil_div, round_up
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.speculative import DraftModelDrafter


def make_workload(cfg, n, max_prompt, max_new, seed=0):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(2, max_prompt + 1))
        prompt = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (plen,), 0, cfg.vocab))
        reqs.append((prompt, int(rng.integers(2, max_new + 1))))
    return reqs


def run_static(engine: Engine, reqs, slots: int) -> int:
    """Arrival-order batches, prompts padded to the batch max, lock-step
    decode to the batch-max budget.  Returns useful token count."""
    useful = 0
    for i in range(0, len(reqs), slots):
        chunk = reqs[i:i + slots]
        plen = max(p.shape[0] for p, _ in chunk)
        budget = max(n for _, n in chunk)
        toks = np.zeros((len(chunk), plen), np.int32)
        for j, (p, _) in enumerate(chunk):
            toks[j, :p.shape[0]] = p
        engine.generate_static({"tokens": toks}, budget)
        useful += sum(n for _, n in chunk)
    return useful


def run_continuous(engine: Engine, reqs) -> int:
    for p, n in reqs:
        engine.add_request(p, n)
    finished = engine.drain()
    return sum(len(r.out_tokens) for r in finished)


def bench(model, params, reqs, slots, mode) -> tuple[float, int]:
    runner = {"static": lambda e: run_static(e, reqs, slots),
              "continuous": lambda e: run_continuous(e, reqs)}[mode]
    runner(Engine(model, params, max_slots=slots))      # compile warmup
    eng = Engine(model, params, max_slots=slots)
    t0 = time.perf_counter()
    useful = runner(eng)
    return time.perf_counter() - t0, useful


# ---------------------------------------------------------------------------
# long-tail trace: lazy allocation vs eager reservation at the same pool size
# ---------------------------------------------------------------------------

def make_longtail_workload(cfg, n, max_prompt, max_new, max_len, seed=0):
    """Short prompts; most requests want a short continuation but every 4th
    runs to the context limit — the output-length distribution where eager
    full-lifetime reservation idles most of a pool sized for the average
    (the reservation is all *future* tokens, which lazy allocation defers)."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(2, max(3, max_prompt // 4) + 1))
        prompt = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (plen,), 0, cfg.vocab))
        budget = (max_len - plen) if i % 4 == 3 \
            else int(rng.integers(2, max_new + 1))
        reqs.append((prompt, budget))
    return reqs


def run_longtail(model, params, reqs, slots, *, eager, num_pages,
                 page_tokens=16, chunk_tokens=None):
    # flat=False: this section compares allocation policies over the dense
    # chunked step; the flat layout gets its own A/B in bench_flat
    eng = Engine(model, params, max_slots=slots, eager=eager,
                 num_pages=num_pages, page_tokens=page_tokens,
                 chunk_tokens=chunk_tokens, flat=False)
    eng.warmup()       # compile decode + every prefill bucket before timing
    rids = [eng.add_request(p, n) for p, n in reqs]
    t0 = time.perf_counter()
    fin, steps = {}, 0
    while eng.scheduler.has_work:
        fin.update((r.rid, r) for r in eng.step())
        steps += 1
    dt = time.perf_counter() - t0
    assert sorted(fin) == sorted(rids), "drain lost requests"
    outs = [fin[rid].out_tokens for rid in rids]
    return eng, outs, dt, steps


def bench_longtail(model, params, reqs, slots, chunk_tokens):
    # page size the engine will actually use (16 rounded up to the layout m_r)
    pt = round_up(16, model.ctx.layout(model.compute_dtype).m_r)
    per_req = [ceil_div(p.shape[0] + n - 1, pt) for p, n in reqs]
    eager_pages = slots * max(per_req)     # eager never page-blocked
    half = 1 + eager_pages // 2            # +1: trash page
    total_new = sum(n for _, n in reqs)
    print(f"[bench_serving] long-tail: {len(reqs)} requests, "
          f"{total_new} tokens, {slots} slots, page={pt} tok; "
          f"eager requirement {eager_pages} pages, pool capped at "
          f"{half - 1} (50%)")

    base_eng, base_out, base_dt, base_steps = run_longtail(
        model, params, reqs, slots, eager=True, num_pages=1 + eager_pages,
        page_tokens=pt)
    rows = [("eager/full", base_eng, base_out, base_dt, base_steps,
             1 + eager_pages)]
    policies = [("eager/half", True, None), ("lazy/half", False, None)]
    if all(t == "attn" for t in model.cfg.layer_types):
        # hybrids keep monolithic prefill (scan state is not inert on
        # padded chunk rows) — no chunked row for them
        policies.append(("lazy/half/chunked", False, chunk_tokens))
    for label, eager, chunk in policies:
        eng, outs, dt, steps = run_longtail(model, params, reqs, slots,
                                            eager=eager, num_pages=half,
                                            page_tokens=pt,
                                            chunk_tokens=chunk)
        rows.append((label, eng, outs, dt, steps, half))
    record = {}
    for label, eng, outs, dt, steps, pages in rows:
        s = eng.scheduler
        # mean slot occupancy: tokens produced per engine step — eager
        # reservation idles slots behind long-tail page reservations
        print(f"  {label:<17} {total_new / dt:8.1f} tok/s ({dt:.2f}s)  "
              f"concurrency={total_new / steps:.2f} avg / "
              f"{s.peak_running} peak  "
              f"preemptions={s.num_preemptions} pauses={s.num_pauses}  "
              f"peak_pages={eng.pool.peak_used}/{pages - 1}")
        # the tentpole contract: whatever the policy — eager or lazy,
        # monolithic or chunked, through folds/pauses/stalls — the tokens
        # are identical
        assert outs == base_out, \
            f"{label}: outputs diverged from the eager baseline"
        assert eng.pool.num_used == 0, f"{label}: leaked pages"
        record[label] = {"tok_per_s": total_new / dt, "steps": steps,
                         "preemptions": s.num_preemptions,
                         "pauses": s.num_pauses,
                         "peak_pages": eng.pool.peak_used}
    lazy_eng, lazy_steps = rows[2][1], rows[2][4]
    eager_half_steps = rows[1][4]
    assert lazy_eng.scheduler.num_preemptions >= 1, \
        "long-tail trace at 50% pool should force at least one preemption"
    ratio = eager_half_steps / lazy_steps
    record["lazy_vs_eager_concurrency"] = ratio
    record["chunk_tokens"] = chunk_tokens   # per-section provenance
    print(f"  lazy/eager mean concurrency at the same pool = {ratio:.2f}x; "
          f"outputs token-identical across all {len(rows)} runs")
    return record


# ---------------------------------------------------------------------------
# chunked prefill vs monolithic: TTFT and inter-token latency percentiles
# ---------------------------------------------------------------------------

def make_mixed_trace(cfg, n, max_len, seed=0):
    """Decode-heavy short requests punctuated by long prompts (every 3rd):
    the workload where a monolithic prefill freezes every running decode
    for one full-prompt forward, so the long prompts' admissions *are* the
    monolithic p95 inter-token latency.  Long prompts sit just past the
    half-context power-of-two boundary: the monolithic policy's geometric
    bucket pads them to a full ``max_len`` forward (the compile-count
    compromise recompute-prefills force on it), while the chunked policy
    pays exact ``ceil(len/chunk)`` chunks — bucket padding is a real cost
    of the monolithic design, not a benchmark artifact."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        if i % 3 == 2:
            plen = int(rng.integers(max_len // 2 + 2, max_len * 9 // 16 + 2))
            budget = int(rng.integers(4, 9))
        else:
            plen = int(rng.integers(2, 9))
            budget = int(rng.integers(12, 25))
        prompt = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (plen,), 0, cfg.vocab))
        reqs.append((prompt, budget))
    return reqs


def run_traced(model, params, reqs, slots, *, chunk_tokens, num_pages=None,
               page_tokens=16, arrivals=None, flat=False):
    """Serve ``reqs`` recording a wall-clock stamp per generated token.
    ``arrivals`` (seconds, per request) replays an online offered load —
    ``Engine.step(now=...)`` gates admission by wall time; ``None`` drains
    offline.  Returns (outputs, per-request token-time lists, wall seconds,
    engine).  ``flat`` is passed explicitly (default dense): the engine's
    own default turns the flat step on with chunking, and the A/B sections
    here need the dense [slots, chunk] grid as a named baseline."""
    eng = Engine(model, params, max_slots=slots, num_pages=num_pages,
                 page_tokens=page_tokens, chunk_tokens=chunk_tokens,
                 flat=flat)
    eng.warmup()
    compiles = dict(model.trace_counts)
    arr = arrivals or [0.0] * len(reqs)
    rids = [eng.add_request(p, n, arrival=a)
            for (p, n), a in zip(reqs, arr)]
    times = {rid: [] for rid in rids}
    seen, fin = {}, {}
    t0 = time.perf_counter()
    while eng.scheduler.has_work:
        now = time.perf_counter() - t0
        done = eng.step(now=now if arrivals is not None else None)
        t = time.perf_counter() - t0
        fin.update((r.rid, r) for r in done)
        for r in list(eng.scheduler.running.values()) + done:
            have = seen.get(r.rid, 0)
            if len(r.out_tokens) > have:
                times[r.rid].extend([t] * (len(r.out_tokens) - have))
                seen[r.rid] = len(r.out_tokens)
        if not eng.scheduler.running and not done:
            time.sleep(5e-4)             # idle gap before the next arrival
    dt = time.perf_counter() - t0
    assert dict(model.trace_counts) == compiles, \
        "step() compiled a new XLA program after warmup()"
    assert sorted(fin) == sorted(rids), "drain lost requests"
    outs = [fin[rid].out_tokens for rid in rids]
    return outs, [times[rid] for rid in rids], dt, eng


def _latency_metrics(token_times, dt, total_new, arrivals=None):
    arr = arrivals or [0.0] * len(token_times)
    ttft = [ts[0] - a for ts, a in zip(token_times, arr) if ts]
    itl = [b - a for ts in token_times for a, b in zip(ts, ts[1:])]
    pct = lambda xs, q: float(np.percentile(np.asarray(xs), q)) if xs else 0.0
    return {"tok_per_s": total_new / dt, "wall_s": dt,
            "ttft_p50_ms": 1e3 * pct(ttft, 50),
            "ttft_p95_ms": 1e3 * pct(ttft, 95),
            "itl_p50_ms": 1e3 * pct(itl, 50),
            "itl_p95_ms": 1e3 * pct(itl, 95)}


def bench_chunked(model, params, reqs, slots, chunk_tokens, load=0.95,
                  repeats=4):
    """Monolithic vs fused-chunked prefill at a fixed offered load (the
    serving-benchmark standard: calibrate capacity offline, then replay the
    same arrival schedule at ``load`` x capacity under both policies) —
    identical tokens asserted, p95 ITL and throughput compared.  Ratios are
    medians of per-round pairs, so host drift cancels.  Target: >= 2x p95
    ITL improvement at equal-or-better throughput.

    Why online: in an offline drain the queue is permanently backlogged, so
    spreading a prefill across steps defers its decode phase and stretches
    the makespan (~0.95x on this CPU toy — recorded as
    ``offline_throughput_ratio``); under an offered load the schedule
    absorbs that slack and the stall removal is visible where it matters,
    in the inter-token tail at the same delivered throughput."""
    total_new = sum(n for _, n in reqs)
    nlong = sum(1 for i in range(len(reqs)) if i % 3 == 2)
    # calibrate: one warm pass per policy (also compiles), then a timed
    # offline drain per policy — monolithic's sets the offered load
    run_traced(model, params, reqs, slots, chunk_tokens=None)
    base_out, _, dt_m, _ = run_traced(model, params, reqs, slots,
                                      chunk_tokens=None)
    run_traced(model, params, reqs, slots, chunk_tokens=chunk_tokens)
    outs, _, dt_c, _ = run_traced(model, params, reqs, slots,
                                  chunk_tokens=chunk_tokens)
    assert outs == base_out, \
        "chunked outputs diverged from monolithic prefill (offline)"
    cap = total_new / dt_m
    arrivals = (np.cumsum([n for _, n in reqs]) / (load * cap)).tolist()
    print(f"[bench_serving] chunked prefill: {len(reqs)} requests "
          f"({nlong} long prompts), {total_new} tokens, {slots} slots, "
          f"chunk={chunk_tokens}; offered load = {load:.2f} x "
          f"{cap:.0f} tok/s monolithic capacity")
    if repeats < 1:        # smoke: the offline equality assert is the point
        ratio = (total_new / dt_c) / cap
        print(f"  outputs token-identical offline at {ratio:.2f}x the "
              f"monolithic drain throughput (smoke skips the online rounds)")
        return {"offline_throughput_ratio": ratio, "capacity_tok_s": cap,
                "chunk_tokens": chunk_tokens}

    rounds = {"monolithic": [], "chunked": []}
    for _ in range(repeats):
        for label, chunk in (("monolithic", None),
                             ("chunked", chunk_tokens)):
            outs, times, dt, eng = run_traced(
                model, params, reqs, slots, chunk_tokens=chunk,
                arrivals=arrivals)
            assert outs == base_out, \
                f"{label}: online outputs diverged (admission timing must " \
                f"not change tokens — rows are independent)"
            m = _latency_metrics(times, dt, total_new, arrivals)
            st = eng.stats()
            m.update(mean_slot_occupancy=st["mean_slot_occupancy"],
                     prefill_stall_steps=st["prefill_stall_steps"],
                     chunks_per_prompt=st["chunks_per_prompt"],
                     preemptions=st["num_preemptions"],
                     pauses=st["num_pauses"])
            rounds[label].append(m)

    med = lambda runs, k: float(np.median([r[k] for r in runs]))
    record = {}
    for label, runs in rounds.items():
        m = {k: med(runs, k) for k in runs[0] if isinstance(runs[0][k],
                                                           (int, float))}
        record[label] = m
        print(f"  {label:<11} {m['tok_per_s']:8.1f} tok/s  "
              f"ttft p50/p95 = {m['ttft_p50_ms']:6.1f}/{m['ttft_p95_ms']:6.1f} ms  "
              f"itl p50/p95 = {m['itl_p50_ms']:5.1f}/{m['itl_p95_ms']:6.1f} ms")
    pair = zip(rounds["monolithic"], rounds["chunked"])
    ratios = [(mm["itl_p95_ms"] / max(1e-9, mc["itl_p95_ms"]),
               mc["tok_per_s"] / max(1e-9, mm["tok_per_s"]))
              for mm, mc in pair]
    itl_ratio = float(np.median([r[0] for r in ratios]))
    thr_ratio = float(np.median([r[1] for r in ratios]))
    record["itl_p95_improvement"] = itl_ratio
    record["throughput_ratio"] = thr_ratio
    record["offered_load"] = load
    record["chunk_tokens"] = chunk_tokens   # per-section provenance
    record["offline_throughput_ratio"] = (total_new / dt_c) / cap
    tag = ("OK (>= 2x, throughput >= 1x)"
           if itl_ratio >= 2.0 and thr_ratio >= 0.98 else "BELOW TARGET")
    print(f"  p95 ITL improvement = {itl_ratio:.2f}x at "
          f"{thr_ratio:.2f}x delivered throughput (offline drain "
          f"{record['offline_throughput_ratio']:.2f}x)  [{tag}]; "
          f"outputs token-identical")
    return record


def run_overload(model, params, reqs, slots, *, chunk_tokens, arrivals,
                 queue_limit=None, page_tokens=16):
    """Online replay that feeds each request to ``Engine.add_request`` only
    once its arrival time has passed, so admission control sheds against
    the queue the server actually has at that moment (enqueueing the whole
    trace up-front would let it reject against requests that haven't
    arrived yet).  Returns (finished Requests in trace order — shed rows
    carry ``finish_reason == "rejected"`` and no tokens — per-request
    token-time lists, wall seconds, engine)."""
    eng = Engine(model, params, max_slots=slots, page_tokens=page_tokens,
                 chunk_tokens=chunk_tokens, flat=False,
                 queue_limit=queue_limit)
    eng.warmup()
    compiles = dict(model.trace_counts)
    order = sorted(range(len(reqs)), key=lambda i: arrivals[i])
    times = [[] for _ in reqs]
    fin, seen, by_rid, nxt = {}, {}, {}, 0
    t0 = time.perf_counter()
    while len(fin) < len(reqs):
        now = time.perf_counter() - t0
        while nxt < len(order) and arrivals[order[nxt]] <= now:
            i = order[nxt]
            rid = eng.add_request(reqs[i][0], reqs[i][1],
                                  arrival=arrivals[i])
            by_rid[rid] = i
            nxt += 1
        done = eng.step(now=now)
        t = time.perf_counter() - t0
        fin.update((by_rid[r.rid], r) for r in done)
        for r in list(eng.scheduler.running.values()) + done:
            i = by_rid[r.rid]
            have = seen.get(i, 0)
            if len(r.out_tokens) > have:
                times[i].extend([t] * (len(r.out_tokens) - have))
                seen[i] = len(r.out_tokens)
        if not eng.scheduler.running and not done:
            time.sleep(5e-4)             # idle gap before the next arrival
    dt = time.perf_counter() - t0
    assert dict(model.trace_counts) == compiles, \
        "overload step() compiled a new XLA program after warmup()"
    assert eng.pool.num_used == 0, "leaked pages"
    return [fin[i] for i in range(len(reqs))], times, dt, eng


def bench_overload(model, params, reqs, slots, chunk_tokens, load=1.3,
                   repeats=3):
    """Overload: the trace replayed at ``load`` x the calibrated offline
    capacity — an arrival rate the engine cannot sustain — with an
    unbounded wait queue vs the bounded one (``queue_limit = slots``: one
    queued request per busy slot).  Unbounded, every arrival is eventually
    served but the backlog (and thus TTFT) grows for the whole burst;
    bounded, ``Scheduler.add`` sheds arrivals over the limit as typed
    ``rejected`` rows in O(1) and admitted requests keep a bounded wait.
    The headline: bounded p95 TTFT over *admitted* requests <= unbounded,
    with the shed rate recorded — the requests the bounded queue turned
    away are exactly the latency the unbounded queue makes everyone pay.
    Admitted outputs are asserted token-identical to the offline drain:
    admission timing and shedding must not change surviving tokens."""
    total_new = sum(n for _, n in reqs)
    # calibrate: one warm pass (compiles), then a timed offline drain
    run_traced(model, params, reqs, slots, chunk_tokens=chunk_tokens)
    base_out, _, dt_off, _ = run_traced(model, params, reqs, slots,
                                        chunk_tokens=chunk_tokens)
    cap = total_new / dt_off
    arrivals = (np.cumsum([n for _, n in reqs]) / (load * cap)).tolist()
    qlim = max(1, slots)
    print(f"[bench_serving] overload: {len(reqs)} requests, {total_new} "
          f"tokens, {slots} slots, chunk={chunk_tokens}; offered load = "
          f"{load:.2f} x {cap:.0f} tok/s capacity; bounded "
          f"queue_limit={qlim}")

    rounds = {"unbounded": [], "bounded": []}
    for _ in range(repeats):
        for label, ql in (("unbounded", None), ("bounded", qlim)):
            fin, times, dt, eng = run_overload(
                model, params, reqs, slots, chunk_tokens=chunk_tokens,
                arrivals=arrivals, queue_limit=ql)
            admitted = [i for i, r in enumerate(fin)
                        if r.finish_reason != "rejected"]
            shed = len(reqs) - len(admitted)
            for i in admitted:
                assert fin[i].out_tokens == base_out[i], \
                    f"{label}: admitted request {i} diverged under " \
                    f"overload (shedding must not change survivors)"
            assert eng.stats()["resilience"]["sheds"] == shed, \
                "shed count disagrees with the resilience counters"
            served = sum(len(fin[i].out_tokens) for i in admitted)
            m = _latency_metrics([times[i] for i in admitted], dt, served,
                                 [arrivals[i] for i in admitted])
            m["shed_rate"] = shed / len(reqs)
            m["admitted"] = len(admitted)
            rounds[label].append(m)

    med = lambda runs, k: float(np.median([r[k] for r in runs]))
    record = {"offered_load": load, "queue_limit": qlim,
              "capacity_tok_s": cap, "chunk_tokens": chunk_tokens}
    for label, runs in rounds.items():
        m = {k: med(runs, k) for k in runs[0]}
        record[label] = m
        print(f"  {label:<10} ttft p50/p95 = {m['ttft_p50_ms']:6.1f}/"
              f"{m['ttft_p95_ms']:7.1f} ms  {m['tok_per_s']:8.1f} tok/s "
              f"(admitted)  shed rate {m['shed_rate']:.2f}")
    ratios = [b["ttft_p95_ms"] / max(1e-9, u["ttft_p95_ms"])
              for u, b in zip(rounds["unbounded"], rounds["bounded"])]
    ratio = float(np.median(ratios))
    record["ttft_p95_bounded_vs_unbounded"] = ratio
    if record["bounded"]["shed_rate"] == 0:
        # nothing was shed, so both runs served the identical schedule —
        # the ratio is host noise, not an admission-control signal
        tag = "NO SHEDS (queue never filled at this scale)"
    elif ratio <= 1.0:
        tag = "OK (<= 1x)"
    else:
        tag = "ABOVE UNBOUNDED"
    print(f"  bounded/unbounded p95 TTFT = {ratio:.2f}x at shed rate "
          f"{record['bounded']['shed_rate']:.2f}  [{tag}]; admitted "
          f"outputs token-identical to the offline drain")
    return record


def bench_flat(model, params, reqs, slots, chunk_tokens, smoke, repeats=3):
    """Flat [1, budget] token-level step vs the dense [slots, chunk] grid
    and the monolithic baseline, offline drains.  The contract half (what
    ``tier1.sh --bench-smoke`` buys): all three drains must produce
    token-identical outputs — a flat-vs-chunked mismatch fails the run.
    The perf half: the flat step computes only its real tokens plus
    m_r-ladder padding where the dense grid always pays slots x chunk
    positions, so its offline throughput should sit at or above the dense
    step's (target >= 0.99x monolithic); ``fill`` reports real tokens per
    compiled position (the padding tax)."""
    total_new = sum(n for _, n in reqs)
    print(f"[bench_serving] flat step: {len(reqs)} requests, "
          f"{total_new} tokens, {slots} slots, chunk={chunk_tokens}")
    # one warm pass per policy (compiles), then timed offline drains
    run_traced(model, params, reqs, slots, chunk_tokens=None)
    run_traced(model, params, reqs, slots, chunk_tokens=chunk_tokens,
               flat=False)
    run_traced(model, params, reqs, slots, chunk_tokens=chunk_tokens,
               flat=True)
    ratios_m, ratios_c, st = [], [], None
    for _ in range(1 if smoke else repeats):
        base_out, _, dt_m, _ = run_traced(model, params, reqs, slots,
                                          chunk_tokens=None)
        dense_out, _, dt_c, _ = run_traced(model, params, reqs, slots,
                                           chunk_tokens=chunk_tokens,
                                           flat=False)
        flat_out, _, dt_f, eng = run_traced(model, params, reqs, slots,
                                            chunk_tokens=chunk_tokens,
                                            flat=True)
        assert flat_out == dense_out, \
            "flat step outputs diverged from the dense chunked step"
        assert flat_out == base_out, \
            "flat step outputs diverged from monolithic prefill"
        ratios_m.append(dt_m / dt_f)
        ratios_c.append(dt_c / dt_f)
        st = eng.stats()["flat"]
    record = {
        "chunk_tokens": chunk_tokens,
        "token_budget": st["token_budget"],
        "offline_throughput_ratio": float(np.median(ratios_m)),
        "flat_vs_chunked_ratio": float(np.median(ratios_c)),
        "fill": st["fill"],
        "mean_tokens_per_step": st["mean_tokens"],
        "mean_width": st["mean_width"],
    }
    tag = ("OK (>= 0.99x)" if record["offline_throughput_ratio"] >= 0.99
           else "BELOW 0.99x TARGET")
    print(f"  flat drain {record['offline_throughput_ratio']:.3f}x "
          f"monolithic ({record['flat_vs_chunked_ratio']:.2f}x the dense "
          f"chunked step), fill={record['fill']:.2f}  [{tag}]; outputs "
          f"token-identical across flat/chunked/monolithic")
    return record


# ---------------------------------------------------------------------------
# prefix cache: shared-system-prompt trace, cache-on vs cache-off
# ---------------------------------------------------------------------------

def make_prefix_trace(cfg, n, sys_tokens, max_new, seed=0):
    """Every request = one shared system prompt + a short unique suffix —
    the prompt-caching workload (few-shot headers, agent scaffolds) where
    a prefix cache pays: the shared pages are computed once per *content*
    and every later arrival prefills only its own suffix.  Arrivals are
    staggered so admissions see earlier requests' pages (concurrent
    admissions of a cold prefix cannot share — someone must compute it)."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    sysp = np.asarray(jax.random.randint(key, (sys_tokens,), 0, cfg.vocab))
    reqs = []
    for i in range(n):
        sfx = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                            (int(rng.integers(2, 7)),), 0,
                                            cfg.vocab))
        reqs.append((np.concatenate([sysp, sfx]),
                     int(rng.integers(3, max_new + 1))))
    return reqs


def run_prefix(model, params, reqs, slots, *, prefix_cache, chunk_tokens=None,
               spec_tokens=None, num_pages=None, greedy=True, seed=0,
               page_tokens=16):
    """Warmed, staggered drain with the zero-recompile assert and (cache
    on) the end-of-drain balance check: clearing the cache must return the
    pool to zero used pages with allocs+shares == frees."""
    # flat=False keeps the "chunked/..." rows on the dense grid they name
    eng = Engine(model, params, max_slots=slots, page_tokens=page_tokens,
                 num_pages=num_pages, chunk_tokens=chunk_tokens,
                 spec_tokens=spec_tokens, prefix_cache=prefix_cache,
                 flat=False)
    eng.warmup()
    compiles = dict(model.trace_counts)
    rids = [eng.add_request(p, n, arrival=float(2 * i))
            for i, (p, n) in enumerate(reqs)]
    clock, fin = 0.0, {}
    t0 = time.perf_counter()
    while eng.scheduler.has_work:
        fin.update((r.rid, r) for r in eng.step(now=clock, greedy=greedy,
                                                seed=seed))
        clock += 1.0
    dt = time.perf_counter() - t0
    assert dict(model.trace_counts) == compiles, \
        "prefix-cache step() compiled a new XLA program after warmup()"
    assert sorted(fin) == sorted(rids), "drain lost requests"
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    assert eng.pool.num_used == 0, "leaked pages"
    assert eng.pool.total_allocs + eng.pool.total_shares \
        == eng.pool.total_frees, "alloc/share/free imbalance"
    return eng, [fin[rid].out_tokens for rid in rids], dt


def bench_prefix(model, params, reqs, slots, chunk_tokens, spec_tokens,
                 smoke):
    """Cache-on vs cache-off on the shared-prefix trace.  The contract
    half (what ``tier1.sh --bench-smoke`` buys): outputs token-identical
    across greedy/sampled, monolithic/chunked and spec-on, at <= 0.5x the
    prefill tokens computed.  The perf half: prefill tokens saved, plus a
    tight-pool run where preemption releases pages into the cache — the
    resume recompute is bounded by tokens generated since admission + one
    partial page, against the PR-2 baseline's full-reprefill recompute."""
    total_prompt = sum(p.shape[0] for p, _ in reqs)
    print(f"[bench_serving] prefix cache: {len(reqs)} requests sharing one "
          f"system prompt ({total_prompt} prompt tokens total), "
          f"{slots} slots")
    base, base_out, base_dt = run_prefix(model, params, reqs, slots,
                                         prefix_cache=False)
    _, base_out_s, _ = run_prefix(model, params, reqs, slots,
                                  prefix_cache=False, greedy=False, seed=13)
    off_tokens = base.stats()["prefill_tokens"]
    record = {"requests": len(reqs), "prompt_tokens": total_prompt,
              "prefill_tokens_off": off_tokens}
    rows = [("mono/greedy", dict()),
            ("chunked/greedy", dict(chunk_tokens=chunk_tokens)),
            ("mono/sampled", dict(greedy=False, seed=13)),
            ("spec/greedy", dict(spec_tokens=spec_tokens))]
    if not smoke:
        rows += [("chunked/sampled", dict(chunk_tokens=chunk_tokens,
                                          greedy=False, seed=13)),
                 ("spec/sampled", dict(spec_tokens=spec_tokens,
                                       greedy=False, seed=13)),
                 ("chunked+spec/greedy", dict(chunk_tokens=chunk_tokens,
                                              spec_tokens=spec_tokens))]
    for label, kw in rows:
        eng, outs, dt = run_prefix(model, params, reqs, slots,
                                   prefix_cache=True, **kw)
        want = base_out_s if kw.get("greedy") is False else base_out
        assert outs == want, \
            f"prefix cache ({label}) outputs diverged from cache-off"
        st = eng.stats()
        pc = st["prefix_cache"]
        on_tokens = st["prefill_tokens"]
        assert on_tokens <= 0.5 * off_tokens, \
            f"{label}: prefill {on_tokens} tokens > 0.5x cache-off " \
            f"{off_tokens} on a shared-prefix trace"
        record[label] = {"prefill_tokens": on_tokens,
                         "prefill_ratio": on_tokens / off_tokens,
                         "hit_rate": pc["hit_rate"],
                         "hit_tokens": pc["hit_tokens"],
                         "cow_copies": pc["cow_copies"],
                         "evictions": pc["evictions"],
                         "tok_per_s": sum(len(o) for o in outs) / dt}
        print(f"  {label:<19} prefill {on_tokens:>5}/{off_tokens} tokens "
              f"({on_tokens / off_tokens:.2f}x)  hit rate {pc['hit_rate']:.2f}"
              f"  cow={pc['cow_copies']} evictions={pc['evictions']}")

    # preempt-resume: short prompts with long budgets on a pool sized well
    # below the concurrent working set, so *growth* (not admission) hits
    # OutOfPages and preempts.  With the cache, the victim's pages go into
    # the cache and its resume recomputes only the uncached suffix; the
    # PR-2 baseline re-prefills the whole folded prompt
    pt = round_up(16, model.ctx.layout(model.compute_dtype).m_r)
    key = jax.random.PRNGKey(17)
    cfg_vocab = int(model.cfg.vocab)
    preqs = [(np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                            (pt // 2 + i % 3,), 0,
                                            cfg_vocab)),
              2 * pt - 3 * (i % 3))
             for i in range(2 * slots)]
    per_req = max(ceil_div(p.shape[0] + n - 1, pt) for p, n in preqs)
    tight_pages = 1 + max(per_req + 1, (slots * per_req) * 2 // 3)
    tight_kw = dict(num_pages=tight_pages, page_tokens=pt)
    _, ample_out, _ = run_prefix(model, params, preqs, slots,
                                 prefix_cache=False)
    off_eng, off_out, _ = run_prefix(model, params, preqs, slots,
                                     prefix_cache=False, **tight_kw)
    on_eng, on_out, _ = run_prefix(model, params, preqs, slots,
                                   prefix_cache=True, **tight_kw)
    assert on_out == ample_out and off_out == ample_out, \
        "tight-pool outputs diverged (preemption must not change tokens)"
    assert off_eng.num_preemptions >= 1, \
        "the tight pool should force at least one preemption"
    total_pprompt = sum(p.shape[0] for p, _ in preqs)
    off_recompute = off_eng.stats()["prefill_tokens"] - total_pprompt
    on_sched = on_eng.scheduler.stats()
    record["preempt_resume"] = {
        "pool_pages": tight_pages - 1,
        "preemptions_off": off_eng.num_preemptions,
        "preemptions_on": on_eng.num_preemptions,
        "recompute_tokens_off": off_recompute,
        "resumes_on": on_sched["resumes"],
        "recompute_tokens_on": on_sched["resume_recompute_tokens"],
    }
    for e in on_eng.scheduler.resume_events:
        # reclaims and pool-pressure evictions legitimately lose the cached
        # prefix before the resume; every other resume must hit it
        assert e["reclaimed"] or e["evicted"] or \
            e["recompute"] <= e["generated_since"] + pt, \
            f"resume recomputed past the uncached suffix: {e}"
    print(f"  preempt-resume at {tight_pages - 1} pages: cache-off "
          f"recomputed {off_recompute} tokens "
          f"({off_eng.num_preemptions} preemptions); cache-on recomputed "
          f"{on_sched['resume_recompute_tokens']} over "
          f"{on_sched['resumes']} resumes "
          f"({on_eng.num_preemptions} preemptions) — bounded by "
          f"generated-since-admission + one partial page")
    print(f"  outputs token-identical to cache-off for all "
          f"{len(rows)} cache-on configs (greedy + sampled)")
    return record


# ---------------------------------------------------------------------------
# speculative decoding: drafted verify steps vs one-token decode steps
# ---------------------------------------------------------------------------

def make_spec_trace(cfg, n, max_new, seed=0):
    """Decode-heavy, n-gram-friendly trace: every prompt tiles a short
    random motif (prompt-lookup's best case — the context is its own draft
    model) and budgets run long, so greedy decodes of the toy model settle
    into loops the self-ngram drafter also predicts.  This is the honest
    *favourable* workload for speculation, the way the long-tail trace is
    the favourable workload for lazy allocation: acceptance on
    repetition-free traffic would be near zero (and tokens still
    identical, just without the speedup)."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        motif = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                              (4,), 0, cfg.vocab))
        prompt = np.tile(motif, int(rng.integers(2, 5)))[:16]
        reqs.append((prompt, int(rng.integers(max(2, max_new // 2),
                                              max_new + 1))))
    return reqs


def run_spec(model, params, reqs, slots, *, spec_tokens=None, drafter=None,
             greedy=True, seed=0):
    """Warmed drain with step counting and the zero-recompile assert."""
    eng = Engine(model, params, max_slots=slots, spec_tokens=spec_tokens,
                 drafter=drafter)
    eng.warmup()
    compiles = dict(model.trace_counts)
    rids = [eng.add_request(p, n) for p, n in reqs]
    t0 = time.perf_counter()
    fin, steps = {}, 0
    while eng.scheduler.has_work:
        fin.update((r.rid, r) for r in eng.step(greedy=greedy, seed=seed))
        steps += 1
    dt = time.perf_counter() - t0
    assert dict(model.trace_counts) == compiles, \
        "speculative step() compiled a new XLA program after warmup()"
    assert sorted(fin) == sorted(rids), "drain lost requests"
    assert eng.pool.num_used == 0, "leaked pages"
    return eng, [fin[rid].out_tokens for rid in rids], dt, steps


def bench_spec(model, params, reqs, slots, spec_tokens, smoke):
    """Speculative vs plain decode on the n-gram-friendly trace.  The
    contract half: spec-on outputs are asserted token-identical to spec-off
    for greedy AND sampled decode (a mismatch fails the run — this is what
    ``tier1.sh --bench-smoke`` buys).  The perf half: decode tokens per
    decode-row-step — how many tokens a decoding row advances per verify
    launch, the step-shape-independent speedup measure — targets >= 1.3x
    at the n-gram acceptance this trace earns; wall-clock is recorded
    honestly (a CPU toy pays the padded verify width in real FLOPs, so its
    wall win trails what per-step accounting promises on real hardware)."""
    total_new = sum(n for _, n in reqs)
    print(f"[bench_serving] speculative: {len(reqs)} requests, "
          f"{total_new} tokens, {slots} slots, k={spec_tokens} "
          f"(n-gram drafter)")
    base_eng, base_out, base_dt, base_steps = run_spec(
        model, params, reqs, slots)
    _, base_out_s, _, _ = run_spec(model, params, reqs, slots,
                                   greedy=False, seed=13)
    record = {"spec_tokens": spec_tokens,
              "baseline": {"tok_per_s": total_new / base_dt,
                           "steps": base_steps}}
    rows = [("ngram", None)]
    if not smoke:
        dcfg = reduced_config(get_config("smollm2-135m"), layers=1)
        dm = build_model(dcfg, RunConfig(param_dtype="float32",
                                         compute_dtype="float32",
                                         remat=False),
                         ShapeSpec("serve", model.shape.seq_len, slots,
                                   "decode"))
        dparams = dm.init(jax.random.PRNGKey(7))
        rows.append(("draft-model",
                     lambda: DraftModelDrafter(dm, dparams)))
    for label, mk in rows:
        drafter = mk() if mk else None
        eng, outs, dt, steps = run_spec(model, params, reqs, slots,
                                        spec_tokens=spec_tokens,
                                        drafter=drafter)
        assert outs == base_out, \
            f"speculative ({label}) greedy outputs diverged from baseline"
        drafter_s = mk() if mk else None
        _, outs_s, _, _ = run_spec(model, params, reqs, slots,
                                   spec_tokens=spec_tokens,
                                   drafter=drafter_s, greedy=False, seed=13)
        assert outs_s == base_out_s, \
            f"speculative ({label}) sampled outputs diverged from baseline"
        st = eng.stats()["speculative"]
        tps = st["decode_tokens_per_row_step"]
        record[label] = {
            "tok_per_s": total_new / dt, "steps": steps,
            "acceptance_rate": st["acceptance_rate"],
            "accepted_per_step": st["accepted_per_step"],
            "decode_tokens_per_row_step": tps,
            "step_ratio": base_steps / steps,
            "wall_ratio": base_dt / dt,
            "draft_overhead": st["draft_overhead"],
            "rollback_pages": st["rollback_pages"],
        }
        tag = ("OK (>= 1.3x)" if label == "ngram" and tps >= 1.3
               else "" if label != "ngram" else "BELOW 1.3x TARGET")
        print(f"  {label:<12} accept={st['acceptance_rate']:.2f}  "
              f"decode tok/row-step={tps:.2f}  steps {base_steps}->{steps} "
              f"({base_steps / steps:.2f}x)  wall {base_dt / dt:.2f}x  "
              f"draft overhead {st['draft_overhead']:.2f}  {tag}")
    print(f"  outputs token-identical to non-speculative decode "
          f"(greedy + sampled) for all {len(rows)} drafters")
    return record


def bench_attrib(model, params, reqs, slots, chunk_tokens):
    """Attribution section (repro.obs.attrib): two telemetry-on drains —
    flat token-level and dense chunked — with the warmup-built roofline
    cost model attached, recording MFU/MBU, padding-waste ratio and the
    per-family predicted-vs-measured ratio.  These land in
    ``BENCH_serving.json`` and are regression-gated by
    ``scripts/bench_check.py`` (MFU/MBU dropping or the padding-waste
    ratio rising by >15% vs the history median fails the gate)."""
    out = {}
    for mode, flat in (("flat", True), ("chunked", False)):
        eng = Engine(model, params, max_slots=slots,
                     chunk_tokens=chunk_tokens, flat=flat, telemetry=True)
        eng.warmup()
        for p, n in reqs:
            eng.add_request(p, n)
        eng.drain()
        at = eng.telemetry()["attribution"]
        tot = at["totals"]
        out[mode] = {
            "mfu": at["mfu"],
            "mbu": at["mbu"],
            "padding_waste_ratio": at["padding_waste_ratio"],
            "roofline_fraction": at["roofline_fraction"],
            "achieved_tokens_per_s": at["achieved_tokens_per_s"],
            "device_fraction": tot["call_s"] / max(tot["wall_s"], 1e-12),
            "families": {
                label: {"steps": f["steps"], "fill": f["fill"],
                        "predicted_vs_measured": f["predicted_vs_measured"]}
                for label, f in sorted(at["families"].items())},
        }
        print(f"  attribution / {mode:<8} mfu {at['mfu']:.2e}  "
              f"mbu {at['mbu']:.2e}  padding waste "
              f"{at['padding_waste_ratio']:.3f} of call  "
              f"roofline fraction {at['roofline_fraction']:.3f}  "
              f"({len(at['families'])} families)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm2-135m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=40)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policies", default="scalable,fixed",
                    help="comma-separated layout policies to sweep")
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="fused-step chunk size for the chunked sections "
                    "(rounded up to the layout m_r; smaller chunks bound "
                    "ITL tighter, larger ones amortize per-step dispatch "
                    "— 16 balances both on a CPU host via the geometric "
                    "shape ladder)")
    ap.add_argument("--spec-tokens", type=int, default=3,
                    help="draft tokens per verify step for the speculative "
                    "section (k drafts ride one fused row per step)")
    ap.add_argument("--skip-longtail", action="store_true")
    ap.add_argument("--skip-throughput", action="store_true")
    ap.add_argument("--skip-itl", action="store_true",
                    help="skip the chunked-vs-monolithic latency section")
    ap.add_argument("--skip-spec", action="store_true",
                    help="skip the speculative-decoding section")
    ap.add_argument("--skip-overload", action="store_true",
                    help="skip the overload/admission-control section")
    ap.add_argument("--skip-prefix", action="store_true",
                    help="skip the prefix-cache section")
    ap.add_argument("--sys-tokens", type=int, default=48,
                    help="shared system-prompt length for the prefix-cache "
                    "trace (3 pages at the default page size: long enough "
                    "that sharing dominates, short enough for CPU smoke)")
    ap.add_argument("--json-out", default=None,
                    help="write machine-readable results here (default: "
                    "BENCH_serving.json at the repo root; '-' disables)")
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes (2 slots, tiny pool) for CI smoke: "
                    "surfaces allocator and chunked-vs-monolithic output "
                    "regressions, not perf numbers")
    args = ap.parse_args(argv)
    if args.smoke:
        # 8 requests → two long-tail requests overlap on the 2 slots, so
        # the 50% pool provably forces a preemption even at toy sizes
        args.requests, args.slots = 8, 2
        args.max_prompt, args.max_new, args.max_len = 10, 6, 48
        args.policies = "scalable"
        args.chunk_tokens = 8

    cfg = reduced_config(get_config(args.arch))
    shape = ShapeSpec("serve", args.max_len, args.slots, "decode")
    reqs = make_workload(cfg, args.requests, args.max_prompt, args.max_new,
                         args.seed)
    total_prompt = sum(p.shape[0] for p, _ in reqs)
    total_new = sum(n for _, n in reqs)
    policies = [p for p in args.policies.split(",") if p]
    print(f"[bench_serving] {cfg.name}: {len(reqs)} requests, "
          f"prompts 2..{args.max_prompt} ({total_prompt} tok), "
          f"budgets 2..{args.max_new} ({total_new} tok), {args.slots} slots")

    results = {}
    models = {}
    for policy in policies:
        if args.skip_throughput and policy != policies[0]:
            continue        # only policies[0] feeds the long-tail section
        run = RunConfig(layout_policy=policy, param_dtype="float32",
                        compute_dtype="float32", remat=False)
        model = build_model(cfg, run, shape)
        params = model.init(jax.random.PRNGKey(args.seed))
        models[policy] = (model, params)
        if args.skip_throughput:
            continue
        for mode in ("static", "continuous"):
            dt, useful = bench(model, params, reqs, args.slots, mode)
            assert useful == total_new, (useful, total_new)
            results[(policy, mode)] = total_new / dt
            print(f"  {policy:>8} / {mode:<10} {total_new / dt:8.1f} tok/s "
                  f"({dt:.2f}s)")

    if not args.skip_throughput:
        for policy in policies:
            ratio = results[(policy, "continuous")] / results[(policy, "static")]
            tag = "OK (>= 1.3x)" if ratio >= 1.3 else "BELOW 1.3x TARGET"
            print(f"  {policy:>8}: continuous/static = {ratio:.2f}x  [{tag}]")
        if "scalable" in policies and "fixed" in policies:
            ps = (results[("scalable", "continuous")]
                  / results[("fixed", "continuous")])
            print(f"  continuous: scalable/fixed = {ps:.2f}x")

    report = {"arch": cfg.name, "slots": args.slots,
              "requests": args.requests, "max_len": args.max_len,
              "chunk_tokens": args.chunk_tokens, "smoke": args.smoke}
    if not args.skip_throughput:
        report["throughput"] = {f"{p}/{m}": v
                                for (p, m), v in results.items()}

    if not args.skip_longtail:
        model, params = models[policies[0]]
        # 2x the request count: the admission gap needs a sustained stream
        # of short requests contending with the long tail, not a drain-down
        lt = make_longtail_workload(cfg, 2 * args.requests, args.max_prompt,
                                    args.max_new, args.max_len, args.seed)
        report["longtail"] = bench_longtail(model, params, lt, args.slots,
                                            args.chunk_tokens)
        results["longtail_concurrency_ratio"] = \
            report["longtail"]["lazy_vs_eager_concurrency"]

    if not args.skip_itl and all(t == "attn" for t in cfg.layer_types):
        model, params = models[policies[0]]
        mixed = make_mixed_trace(cfg,
                                 args.requests if args.smoke
                                 else 2 * args.requests,
                                 args.max_len, args.seed)
        report["chunked"] = bench_chunked(model, params, mixed, args.slots,
                                          args.chunk_tokens,
                                          repeats=0 if args.smoke else 4)
        if "itl_p95_improvement" in report["chunked"]:
            results["itl_p95_improvement"] = \
                report["chunked"]["itl_p95_improvement"]
        report["flat"] = bench_flat(model, params, mixed, args.slots,
                                    args.chunk_tokens, args.smoke)
        results["flat_offline_throughput_ratio"] = \
            report["flat"]["offline_throughput_ratio"]

    if all(t == "attn" for t in cfg.layer_types):
        model, params = models[policies[0]]
        report["attribution"] = bench_attrib(model, params, reqs,
                                             args.slots, args.chunk_tokens)
        results["attrib_flat_mfu"] = report["attribution"]["flat"]["mfu"]

    if not args.skip_spec and all(t == "attn" for t in cfg.layer_types):
        model, params = models[policies[0]]
        spec_reqs = make_spec_trace(cfg, 6 if args.smoke else 16,
                                    12 if args.smoke else 32, args.seed)
        report["speculative"] = bench_spec(model, params, spec_reqs,
                                           args.slots, args.spec_tokens,
                                           args.smoke)
        results["spec_decode_tokens_per_row_step"] = \
            report["speculative"]["ngram"]["decode_tokens_per_row_step"]

    if not args.skip_overload and all(t == "attn" for t in cfg.layer_types):
        model, params = models[policies[0]]
        ov = make_workload(cfg, args.requests if args.smoke
                           else 2 * args.requests, args.max_prompt,
                           args.max_new, args.seed + 1)
        report["overload"] = bench_overload(model, params, ov, args.slots,
                                            args.chunk_tokens,
                                            repeats=1 if args.smoke else 3)
        results["overload_ttft_ratio"] = \
            report["overload"]["ttft_p95_bounded_vs_unbounded"]

    if not args.skip_prefix and all(t == "attn" for t in cfg.layer_types):
        model, params = models[policies[0]]
        prefix_reqs = make_prefix_trace(cfg, 6 if args.smoke else 12,
                                        32 if args.smoke else args.sys_tokens,
                                        6 if args.smoke else args.max_new,
                                        args.seed)
        report["prefix_cache"] = bench_prefix(model, params, prefix_reqs,
                                              args.slots, args.chunk_tokens,
                                              args.spec_tokens, args.smoke)
        results["prefix_prefill_ratio"] = \
            report["prefix_cache"]["mono/greedy"]["prefill_ratio"]

    if args.json_out != "-" and not (args.smoke and args.json_out is None):
        # smoke runs don't clobber the tracked perf trajectory unless asked.
        # The file keeps the trajectory, not just the last run: "latest" is
        # the rolling merged view (partial --skip-* runs update only their
        # sections), "history" appends one timestamped entry per invocation
        # so perf across PRs stays recoverable
        path = args.json_out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_serving.json")
        data = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}
        if not isinstance(data, dict):
            data = {}
        if "history" not in data:
            # legacy layout: a flat section dict — keep it as the seed of
            # the trajectory rather than losing it
            data = {"latest": data,
                    "history": ([{"timestamp": None, "report": data}]
                                if data else [])}
        data.setdefault("latest", {}).update(report)
        data["history"].append({
            "timestamp": datetime.now(timezone.utc).isoformat(
                timespec="seconds"),
            "report": report,
        })
        with open(path, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        print(f"[bench_serving] wrote {path} "
              f"({len(data['history'])} history entries)")
    return results


if __name__ == "__main__":
    main()
