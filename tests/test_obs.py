"""Serving telemetry (repro.obs).

Contracts covered:
  - telemetry is an observer, never a participant: a drain with
    ``telemetry=True`` is token-identical to the same drain with it off
    — chunked and flat, greedy and seeded-sampled — and a post-warmup
    drain with tracing enabled triggers zero new XLA traces;
  - streaming histograms report percentiles within the geometric-bucket
    error bound (factor 2**0.25 → ≤ ~19% relative) without retaining
    samples, and exact count/mean/min/max;
  - registry reset semantics: ``reset("drain")`` zeroes drain-scoped
    series only — lifetime counters and momentary gauges survive;
  - the exported Chrome trace is schema-valid: metadata first, ts
    monotone per track, X spans with non-negative dur, b/e async pairs
    balanced per (cat, id), and a tight-pool prefix-cache drain shows
    queue/prefill/decode spans per request plus at least one ``preempt``
    and one ``prefix_hit`` instant;
  - a chaos drain (seeded FaultPlan + bounded queue) lands
    ``fault:nan`` / ``quarantine`` / ``shed`` events in the trace and
    the matching counters in the registry;
  - ``Engine.telemetry()`` exposes TTFT/ITL percentiles and honours the
    explicit per-drain reset;
  - under ``jax.profiler`` each flat step is one ``engine.step`` span
    holding its six phases in order, ``engine.dispatch`` carries the
    step's planned width, token counts and kernel page fetches, and the
    traced drain is token-identical to an untraced one with zero
    post-warmup retraces.
"""

import json

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.models.model import build_model
from repro.obs import (NULL, Histogram, MetricsRegistry, NullTelemetry,
                       Telemetry, TraceRecorder)
from repro.serving.engine import Engine
from repro.serving.faults import FaultEvent, FaultPlan

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


REQS = ([13, 21, 3, 16], [8, 6, 10, 7])


# ---------------------------------------------------------------------------
# streaming histograms and registry scopes (no engine, no jax tracing)
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    """Geometric buckets at factor 2**0.25 bound relative error by ~19%;
    on a lognormal latency-like distribution the estimate lands far
    inside it.  count/mean/min/max are exact (not bucketed)."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-4.0, sigma=1.0, size=5000)
    h = Histogram("lat")
    for x in xs:
        h.observe(float(x))
    snap = h.snapshot()
    assert snap["count"] == len(xs)
    assert snap["mean"] == pytest.approx(xs.mean())
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["max"] == pytest.approx(xs.max())
    for q, key in [(0.50, "p50"), (0.95, "p95"), (0.99, "p99")]:
        want = float(np.quantile(xs, q))
        got = snap[key]
        assert abs(got - want) / want < 0.19, (key, got, want)
    # the median of a heavy sample should be much tighter than the bound
    assert abs(snap["p50"] / float(np.quantile(xs, 0.5)) - 1) < 0.05
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_histogram_edge_cases():
    h = Histogram("x")
    assert h.snapshot()["count"] == 0          # empty: no crash
    h.observe(-1.0)                            # clamped, not dropped
    h.observe(0.0)
    h.observe(1e9)                             # beyond hi: overflow bucket
    s = h.snapshot()
    assert s["count"] == 3 and s["min"] == 0.0 and s["max"] == 1e9
    assert s["p99"] <= s["max"]                # clamped to observed range


def test_registry_reset_scopes():
    r = MetricsRegistry()
    per_drain = r.counter("tokens_out")                  # default scope
    forever = r.counter("requests_total", scope="lifetime")
    g = r.gauge("queue_depth")
    h = r.histogram("ttft_s")
    per_drain.inc(7)
    forever.inc(3)
    g.set(5)
    h.observe(0.25)
    r.reset("drain")
    snap = r.snapshot()
    assert snap["tokens_out"] == 0                       # drain: zeroed
    assert snap["requests_total"] == 3                   # lifetime: kept
    assert snap["queue_depth"] == 5                      # gauge: momentary
    assert snap["ttft_s"]["count"] == 0                  # drain histogram
    assert snap["_scope"]["tokens_out"] == "drain"
    assert snap["_scope"]["requests_total"] == "lifetime"
    # asking for an existing series under a different kind/scope is a bug
    with pytest.raises(AssertionError):
        r.counter("tokens_out", scope="lifetime")
    with pytest.raises(AssertionError):
        r.gauge("tokens_out")


def test_null_telemetry_is_inert():
    """The default recorder never touches a clock or allocates — every
    event hook is a no-op and a span is a bare profiler annotation."""
    from jax.profiler import TraceAnnotation
    assert not NULL.enabled
    assert NULL.registry is None and NULL.tracer is None
    assert type(NULL.span("engine.step", step=1)) is TraceAnnotation
    NULL.step_begin()
    NULL.step_end(None, None, [])              # no attribute access at all
    assert isinstance(Telemetry(), NullTelemetry)   # engines accept both


def test_trace_recorder_schema_and_bounds(tmp_path):
    clk = iter(x * 1e-3 for x in range(100))
    rec = TraceRecorder(clock=lambda: next(clk), max_events=6)
    rec.complete("slot 0", "prefill", 0.001, 0.003, {"tokens": 16})
    rec.async_begin("scheduler", "queue", 7)
    rec.async_end("scheduler", "queue", 7)
    rec.instant("pool", "cow")
    rec.counter("pool", "pages", {"used": 3, "free": 5})
    assert rec.dropped >= 1                    # 3 M-records + 5 events > 6
    doc = rec.to_json()
    evs = doc["traceEvents"]
    phs = [e["ph"] for e in evs]
    assert phs == sorted(phs, key=lambda p: p != "M")   # metadata first
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts)
    rec.export(tmp_path / "t.json")
    assert json.loads((tmp_path / "t.json").read_text()) == doc


# ---------------------------------------------------------------------------
# the observer effect: telemetry on == telemetry off, zero retraces
# ---------------------------------------------------------------------------

def test_telemetry_token_identity_chunked_and_flat(smollm):
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    for kw in [dict(chunk_tokens=16, flat=False),              # dense chunked
               dict(chunk_tokens=16, token_budget=24)]:        # flat [1, W]
        for greedy, seed in [(True, 0), (False, 7)]:
            plain = Engine(m, params, max_slots=3, page_tokens=8, **kw)
            want = [r.out_tokens
                    for r in _drain(plain, reqs, greedy=greedy, seed=seed)]
            obs = Engine(m, params, max_slots=3, page_tokens=8,
                         telemetry=True, **kw)
            got = [r.out_tokens
                   for r in _drain(obs, reqs, greedy=greedy, seed=seed)]
            assert got == want, (kw, greedy)
            assert obs.obs.enabled and obs.obs.tracer.events()


def test_telemetry_zero_retrace_after_warmup(smollm):
    """Tracing is pure host-side bookkeeping: with telemetry enabled, a
    warmed flat engine drains without a single new XLA trace."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, prefix_cache=True, telemetry=True)
    eng.warmup()
    before = dict(m.trace_counts)
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    _drain(eng, reqs)
    assert dict(m.trace_counts) == before, \
        f"telemetry retraced: {before} -> {dict(m.trace_counts)}"
    assert eng.obs.registry.snapshot()["steps"] > 0


# ---------------------------------------------------------------------------
# step phases in the profiler's trace
# ---------------------------------------------------------------------------

PHASES = ("engine.schedule", "engine.pack", "engine.dispatch",
          "engine.device_wait", "engine.logits_to_host", "engine.commit")


@pytest.fixture(scope="module")
def profiled(smollm, tmp_path_factory):
    """A warmed flat engine drained under ``jax.profiler``, with the plan
    of every step (its segments and ladder width) recorded on the side;
    returns (engine, finished, plans, trace spans, retraces)."""
    import glob

    from jax.profiler import ProfileData
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24)
    eng.warmup()
    plans = []
    plan_segments = eng.scheduler.plan_segments

    def spy(decode_counts, budget):
        segs = plan_segments(decode_counts, budget)
        total = sum(n for _, _, n in segs)
        # each fed position fetches its row's pages up to its own
        t = eng.pool.page_tokens
        kv_pages = 0
        for slot, kind, n in segs:
            req = eng.scheduler.running[slot]
            first = req.len if kind == "decode" else req.prefill_cursor
            kv_pages += sum((first + j) // t + 1 for j in range(n))
        plans.append({"width": eng._flat_shape(total),
                      "prefill_tokens": sum(n for _, k, n in segs
                                            if k == "prefill"),
                      "decode_tokens": sum(n for _, k, n in segs
                                           if k == "decode"),
                      "kv_pages": kv_pages})
        return segs
    eng.scheduler.plan_segments = spy
    before = dict(m.trace_counts)
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        fin = _drain(eng, list(zip(_prompts(cfg, REQS[0]), REQS[1])))
    finally:
        jax.profiler.stop_trace()
    retraces = {k: v - before[k] for k, v in m.trace_counts.items()}
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    return eng, fin, plans, sorted(spans, key=lambda e: e[1]), retraces


def test_flat_step_phases_nest_in_order(profiled):
    """Per step: one ``engine.step`` whose six phases appear in order,
    do not overlap and lie inside it."""
    eng, _, _, spans, _ = profiled
    steps = [e for e in spans if e[0] == "engine.step"]
    assert len(steps) == eng._steps > 0
    for name, lo, hi, _ in steps:
        inner = [e for e in spans if lo <= e[1] < hi and e[0] in PHASES]
        assert tuple(e[0] for e in inner) == PHASES
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        assert lo <= inner[0][1] and inner[-1][2] <= hi


def test_dispatch_args_are_the_planned_step(profiled):
    eng, _, plans, spans, _ = profiled
    got = [e[3] for e in spans if e[0] == "engine.dispatch"]
    assert [a.pop("step") for a in got] == list(range(eng._steps))
    assert got == plans
    assert any(p["prefill_tokens"] == 0 for p in plans)
    assert any(p["prefill_tokens"] and p["decode_tokens"] for p in plans)
    assert all(p["kv_pages"] >= p["prefill_tokens"] + p["decode_tokens"]
               for p in plans)
    copies = [e[3]["bytes"] for e in spans if e[0] == "engine.logits_to_host"]
    assert copies and all(b > 0 for b in copies)


def test_profiled_drain_is_token_identical_without_retraces(smollm,
                                                            profiled):
    cfg, m, params = smollm
    _, fin, _, _, retraces = profiled
    plain = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                   token_budget=24)
    want = [r.out_tokens for r in
            _drain(plain, list(zip(_prompts(cfg, REQS[0]), REQS[1])))]
    assert [r.out_tokens for r in fin] == want
    assert not any(retraces.values()), retraces


# ---------------------------------------------------------------------------
# exported trace: schema + lifecycle coverage under pressure
# ---------------------------------------------------------------------------

def _validate_trace(doc):
    """Chrome trace_event JSON-flavour schema checks; returns the event
    list for content assertions."""
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    by_track = {}
    open_async = {}
    for e in evs:
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        if e["ph"] == "M":
            assert e["name"] == "thread_name" and e["args"]["name"]
            continue
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        by_track.setdefault(e["tid"], []).append(e["ts"])
        if e["ph"] == "X":
            assert e["dur"] >= 0
        elif e["ph"] in ("b", "e"):
            key = (e["cat"], e["id"])
            if e["ph"] == "b":
                open_async[key] = open_async.get(key, 0) + 1
            else:
                assert open_async.get(key, 0) > 0, f"orphan end {key}"
                open_async[key] -= 1
        elif e["ph"] == "i":
            assert e["s"] == "t"
        elif e["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in e["args"].values())
        else:
            raise AssertionError(f"unexpected phase {e['ph']!r}")
    for tid, ts in by_track.items():
        assert ts == sorted(ts), f"track {tid} not monotone"
    assert all(v == 0 for v in open_async.values()), \
        f"unclosed async spans: {open_async}"
    return evs


def test_trace_export_covers_lifecycle_under_pressure(smollm, tmp_path):
    """The acceptance drain: a pool at ~half the working set plus a
    prefix cache and a duplicated prompt — the exported trace must be
    schema-valid and contain queue/prefill/decode spans per request,
    ≥ 1 ``preempt`` instant, and ≥ 1 ``prefix_hit`` instant."""
    cfg, m, params = smollm
    lens = [4, 25, 6, 30, 4, 5]
    prompts = _prompts(cfg, lens, seed=3)
    prompts.append(prompts[1])                 # duplicate → prefix hit
    reqs = list(zip(prompts, [16, 10, 16, 8, 16, 16, 10]))
    eng = Engine(m, params, max_slots=3, page_tokens=8, num_pages=1 + 8,
                 chunk_tokens=8, prefix_cache=True, telemetry=True)
    fin = _drain(eng, reqs)
    assert eng.num_preemptions + eng.num_pauses >= 1, \
        "config failed to create pressure — tighten the pool"
    assert eng.stats()["prefix_cache"]["hits"] >= 1

    path = tmp_path / "drain.trace.json"
    eng.obs.export_trace(path)
    doc = json.loads(path.read_text())
    evs = _validate_trace(doc)

    names = {e["name"] for e in evs}
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"scheduler", "pool"} <= tracks
    assert any(t.startswith("slot ") for t in tracks)
    # lifecycle spans: every request waits in queue (async), prefills and
    # decodes (X spans on its slot track)
    queues = [e for e in evs if e["ph"] == "b" and e["name"] == "queue"]
    assert {e["id"] for e in queues} >= {r.rid for r in fin}
    for span in ("prefill", "decode"):
        assert span in names, f"missing {span} spans"
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert "preempt" in instants or "pause" in instants
    assert "preempt" in instants, "acceptance requires a preemption"
    assert "prefix_hit" in instants, "acceptance requires a cache hit"
    # counters sampled: pool pages + scheduler load
    assert {e["name"] for e in evs if e["ph"] == "C"} >= {"pages", "load"}
    # the request-lifecycle journal mirrors the trace
    marks = [ev[0] for ev in fin[0].obs_events]
    assert marks[0] == "queued" and marks[-1] == "finished"
    assert "prefill_chunk" in marks and "prefill_done" in marks


def test_chaos_drain_lands_fault_events_in_trace(smollm):
    """A seeded NaN fault plus a bounded queue: the quarantine and the
    sheds are visible both as registry counters and as trace instants,
    and survivors still finish."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, [4] * 6, seed=5)
    eng = Engine(m, params, max_slots=2, chunk_tokens=8, queue_limit=2,
                 telemetry=True)
    rids = [eng.add_request(p, 3) for p in prompts]
    plan = FaultPlan([FaultEvent(1, "nan")])
    with plan.on(eng):
        fin = {r.rid: r for r in eng.drain()}
    assert sorted(fin) == sorted(rids)
    assert plan.fired["nan"] == 1
    reasons = [fin[r].finish_reason for r in rids]
    assert reasons.count("rejected") == 4
    assert reasons.count("error") == 1

    snap = eng.obs.registry.snapshot()
    assert snap["quarantines"] == 1 and snap["sheds"] == 4
    assert snap["faults_injected"] == 1
    evs = _validate_trace(eng.obs.tracer.to_json())
    instants = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"fault:nan", "quarantine", "shed"} <= instants


# ---------------------------------------------------------------------------
# Engine.telemetry(): percentiles and the explicit per-drain reset
# ---------------------------------------------------------------------------

def test_engine_telemetry_percentiles_and_reset(smollm):
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    _drain(eng, reqs)

    tel = eng.telemetry(reset=True)
    assert tel["enabled"]
    lat = tel["latency"]
    assert lat["ttft_s"]["count"] == len(reqs)
    assert lat["e2e_s"]["count"] == len(reqs)
    assert lat["itl_s"]["count"] > 0
    for series in ("ttft_s", "itl_s", "queue_wait_s", "e2e_s"):
        s = lat[series]
        assert 0 <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    met = tel["metrics"]
    assert met["requests_finished"] == len(reqs)
    assert met["tokens_out"] == sum(n for _, n in reqs)
    assert met["step_wall_s"]["count"] == met["steps"] > 0
    # call time is a subset of wall time, measured per step
    assert met["step_call_s"]["count"] == met["steps"]

    # the reset zeroed the drain scope; a second drain starts clean
    after = eng.telemetry()
    assert after["metrics"]["tokens_out"] == 0
    assert after["latency"]["ttft_s"]["count"] == 0
    _drain(eng, reqs)
    again = eng.telemetry()
    assert again["metrics"]["requests_finished"] == len(reqs), \
        "second drain must not double-count the first"


def test_telemetry_disabled_reports_so(smollm):
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=2, chunk_tokens=8)
    tel = eng.telemetry()
    assert not tel["enabled"]
    assert tel["metrics"] == {} and tel["latency"] == {}
    assert tel["components"]["finished"] == 0   # stats still reported
