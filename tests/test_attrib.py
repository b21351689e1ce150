"""Performance attribution layer (repro.obs.attrib / monitors).

Contracts covered:
  - attribution completeness: per step record the four wall components
    (``sched + call + draft + host``) sum back to the measured wall
    within float tolerance — chunked and flat, speculation on and off —
    and the drain totals inherit the identity;
  - warmup-only cost model: ``Engine.warmup()`` with telemetry on builds
    a :class:`StepCostModel` whose family labels are exactly the engine's
    compiled ladder, attribution stays observer-grade (token identity vs
    a telemetry-off drain, zero post-warmup XLA traces), and without
    telemetry no model is built;
  - anomaly monitors: a vanishing ITL SLO target forces a ``slo-burn``
    alert exactly once per excursion, and the alert rides the telemetry
    dict + the counter;
  - the new obs modules stay clean under the repo's AST invariant lint
    (monotonic clocks, no unseeded randomness).
"""

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.models.model import build_model
from repro.obs.attrib import (StepCostModel, fresh_totals, summarize,
                              update_aggregates)
from repro.obs.monitors import Monitors
from repro.serving.engine import Engine

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

# engine grids under test: flat with a prefix cache, flat token-level,
# and flat with an n-gram drafter (speculation exercises the draft span)
GRIDS = [dict(chunk_tokens=16, token_budget=24, prefix_cache=True),
         dict(chunk_tokens=16, token_budget=24),
         dict(chunk_tokens=16, token_budget=24, spec_tokens=2)]


# ---------------------------------------------------------------------------
# attribution completeness: components sum to wall
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", GRIDS,
                         ids=["flat-cache", "flat", "flat-spec"])
def test_attribution_components_sum_to_wall(smollm, kw):
    """The headline property: every per-step attribution record's four
    components reconstruct the measured wall.  The split is exact by
    construction (host is the remainder), so tolerance only covers float
    rounding — parts-per-million of the wall, not a loose bound."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, telemetry=True,
                 **kw)
    eng.warmup()
    _drain(eng, list(zip(_prompts(cfg, REQS[0]), REQS[1])))
    recs = list(eng.obs.step_records)
    assert recs, "drain produced no attribution records"
    for rec in recs:
        parts = rec["sched"] + rec["call"] + rec["draft"] + rec["host"]
        assert abs(parts - rec["wall"]) <= 1e-9 + 1e-6 * rec["wall"], rec
        assert rec["sched"] >= 0 and rec["host"] >= 0
        assert rec["families"], "every moving step is family-tagged"
        assert rec["call"] > 0, "every moving step calls the model"
    # the drain totals inherit the identity
    tot = eng.obs.attribution_summary()["totals"]
    comp = tot["sched_s"] + tot["call_s"] + tot["draft_s"] + tot["host_s"]
    assert comp == pytest.approx(tot["wall_s"], rel=1e-6)
    assert tot["steps"] == len(recs)
    if "spec_tokens" in kw:
        assert tot["draft_s"] > 0, "speculative drain must record drafting"


def test_summarize_matches_incremental_aggregation(smollm):
    """The one-shot ``summarize`` over the record window equals the
    telemetry's incremental aggregates (same fold, different order)."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    eng.warmup()
    _drain(eng, list(zip(_prompts(cfg, REQS[0]), REQS[1])))
    live = eng.obs.attribution_summary()
    redo = summarize(list(eng.obs.step_records), eng.cost_model,
                     goodput_tokens=live["goodput_tokens"],
                     tokens_out=live["tokens_out"])
    assert redo["totals"] == pytest.approx(live["totals"])
    assert set(redo["families"]) == set(live["families"])
    for label, f in redo["families"].items():
        assert f == pytest.approx(live["families"][label])
    for key in ("mfu", "mbu", "padding_waste_ratio", "goodput_ratio"):
        assert redo[key] == pytest.approx(live[key])
    # utilizations are physical: strictly positive, nowhere near 1 on CPU
    assert 0 < live["mfu"] < 1 and 0 < live["mbu"]
    assert 0 <= live["padding_waste_ratio"]
    assert live["goodput_ratio"] == 1.0        # no deadlines -> all good


def test_update_aggregates_survives_window_eviction():
    """The running aggregates are independent of the bounded record
    window: folding records one at a time (then discarding them) yields
    the same totals as keeping all of them."""
    recs = [{"wall": 0.5 + i * 0.01, "sched": 0.1, "call": 0.3,
             "draft": 0.0, "host": 0.1 + i * 0.01,
             "families": (("decode[3,1]", 2 + (i % 2), 3, 0.3),)}
            for i in range(10)]
    tot, fams = fresh_totals(), {}
    for rec in recs:
        update_aggregates(tot, fams, rec, None)    # no cost model needed
    assert tot["steps"] == 10
    assert tot["wall_s"] == pytest.approx(sum(r["wall"] for r in recs))
    assert tot["real_tokens"] == sum(r["families"][0][1] for r in recs)
    assert fams["decode[3,1]"]["padded_tokens"] == 30
    assert fams["decode[3,1]"]["predicted_s"] == 0.0   # model-less fold


# ---------------------------------------------------------------------------
# warmup-only cost model + the observer effect
# ---------------------------------------------------------------------------

def test_cost_model_built_at_warmup_only(smollm):
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    assert eng.cost_model is None              # nothing before warmup
    eng.warmup()
    cm = eng.cost_model
    assert isinstance(cm, StepCostModel)
    assert cm is eng.obs.cost_model            # attached to telemetry
    assert cm.peak_flops > 0 and cm.hbm_bw > 0
    assert cm.flops_per_token == 2.0 * cfg.param_counts()["active"]
    for label, fc in cm.families.items():
        assert fc.predicted_s == max(fc.compute_s, fc.memory_s) > 0
        assert fc.per_token_s == pytest.approx(
            fc.predicted_s / max(1, fc.width))
        assert fc.bottleneck in ("compute", "memory")
        assert fc.kv_gather_bytes > 0          # paged caches are gathered
    # flat ladder families are in the model under the engine's labels
    assert any(l.startswith("flat[1,") for l in cm.families)
    # telemetry off -> no model is ever built
    plain = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                   token_budget=24)
    plain.warmup()
    assert plain.cost_model is None


def test_attribution_is_an_observer(smollm):
    """Token identity and the zero-retrace invariant survive the
    attribution layer: the warmup cost-model build uses fresh jit
    wrappers, so the model's counted caches see no new traces, and a
    telemetry-on drain emits the same tokens as a telemetry-off one."""
    cfg, m, params = smollm
    reqs = list(zip(_prompts(cfg, REQS[0]), REQS[1]))
    plain = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                   token_budget=24)
    plain.warmup()
    want = [r.out_tokens for r in _drain(plain, reqs)]

    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    eng.warmup()                               # builds the cost model too
    before = dict(m.trace_counts)
    got = [r.out_tokens for r in _drain(eng, reqs)]
    assert got == want
    assert dict(m.trace_counts) == before, \
        f"attribution retraced: {before} -> {dict(m.trace_counts)}"


# ---------------------------------------------------------------------------
# anomaly monitors
# ---------------------------------------------------------------------------

def test_slo_burn_alert_fires_once_per_excursion(smollm):
    """An unmeetable ITL target trips ``slo-burn`` — exactly once, not
    once per step (the re-arm contract) — and the alert is visible in
    ``Engine.telemetry()`` and the alert counter."""
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    eng.obs.monitors.slo_itl_s = 1e-12         # every emission violates
    eng.warmup()
    _drain(eng, list(zip(_prompts(cfg, REQS[0]), REQS[1])))
    burns = [a for a in eng.obs.alerts if a.kind == "slo-burn"]
    assert len(burns) == 1, [a.message for a in eng.obs.alerts]
    a = burns[0]
    assert a.severity == "crit" and a.value > a.threshold
    assert "itl" in a.message
    tel = eng.telemetry()
    assert any(d["kind"] == "slo-burn" for d in tel["alerts"])
    assert eng.obs.registry.snapshot()["alerts_emitted"] >= 1


def test_monitor_rules_standalone():
    """Rule-level checks without an engine: a synthetic scheduler drives
    the preemption-storm and queue-growth detectors."""
    class FakeSched:
        max_slots = 2
        num_preemptions = 0
        waiting: list = []

    class FakeReg:
        class _C:
            value = 0
        def counter(self, name):
            return self._C()

    class FakeTel:
        registry = FakeReg()

    mon = Monitors(window=4)
    sched, tel = FakeSched(), FakeTel()
    fired = []
    for i in range(6):
        sched.num_preemptions += 2             # storm: 8 > 2 within window
        sched.waiting = list(range(3 * (i + 1)))   # monotone growth >= 2
        fired += mon.observe_step(t=float(i), scheduler=sched,
                                  telemetry=tel, families=[],
                                  call_s=0.0)
    kinds = [a.kind for a in fired]
    assert kinds.count("preempt-storm") == 1   # re-armed only on clearing
    assert kinds.count("queue-growth") == 1
    # clearing re-arms: a calm stretch then a second storm fires again
    sched.waiting = []
    for i in range(6):
        fired += mon.observe_step(t=10.0 + i, scheduler=sched,
                                  telemetry=tel, families=[], call_s=0.0)
    sched.num_preemptions += 20
    fired += mon.observe_step(t=20.0, scheduler=sched, telemetry=tel,
                              families=[], call_s=0.0)
    assert [a.kind for a in fired].count("preempt-storm") == 2


def test_step_outlier_detects_spike_per_family():
    class FakeSched:
        max_slots = 2
        num_preemptions = 0
        waiting: list = []

    class FakeReg:
        class _C:
            value = 0
        def counter(self, name):
            return self._C()

    class FakeTel:
        registry = FakeReg()

    mon = Monitors(outlier_min=8)
    sched, tel = FakeSched(), FakeTel()
    for i in range(10):                        # warm the rolling median
        mon.observe_step(t=float(i), scheduler=sched, telemetry=tel,
                         families=[("flat[1,16]/k1", 8, 16, 0.010)],
                         call_s=0.010)
    fired = mon.observe_step(t=11.0, scheduler=sched, telemetry=tel,
                             families=[("flat[1,16]/k1", 8, 16, 0.100)],
                             call_s=0.100)
    assert [a.kind for a in fired] == ["step-outlier"]
    assert "flat[1,16]/k1" in fired[0].message
    # a different family with no warm window never alerts
    fired = mon.observe_step(t=12.0, scheduler=sched, telemetry=tel,
                             families=[("prefill[1,48]", 8, 48, 0.500)],
                             call_s=0.500)
    assert fired == []


# ---------------------------------------------------------------------------
# goodput + stats surface
# ---------------------------------------------------------------------------

def _timed_drain(eng, dt=1.0):
    """Drive ``step(now=...)`` with an advancing synthetic clock (deadline
    expiry needs a clock; ``drain()``'s default ``now=None`` is untimed)."""
    t, fin = 0.0, []
    while eng.scheduler.has_work or eng._finished_oob:
        t += dt
        fin.extend(eng.step(now=t))
    return fin


def test_goodput_counts_only_in_deadline_tokens(smollm):
    """Goodput is judged on the engine clock: a request expired before
    its first emission contributes nothing, tokens emitted *before* the
    timeout still count (the cut does not retro-revoke them), and
    ``Engine.stats()['slo']`` reports the ledger."""
    cfg, m, params = smollm
    prompts = _prompts(cfg, REQS[0])
    # deadline shorter than the first step: timeout before any emission
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    eng.warmup()
    rids = [eng.add_request(p, n, deadline_s=0.5)
            for p, n in zip(prompts, REQS[1])]
    fin = {r.rid: r for r in _timed_drain(eng)}
    assert sorted(fin) == sorted(rids)
    assert all(fin[r].finish_reason == "timeout" for r in rids)
    slo = eng.stats()["slo"]
    assert slo["tokens_out"] == 0 and slo["goodput_tokens"] == 0
    assert slo["goodput_ratio"] == 0.0
    assert slo["ttft_p99_s"] >= 0.0            # empty histogram, no crash
    # a mid-drain deadline: some requests are cut short, but every token
    # they emitted while alive stays goodput
    eng2 = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                  token_budget=24, telemetry=True)
    eng2.warmup()
    rids = [eng2.add_request(p, n, deadline_s=3.5)
            for p, n in zip(prompts, REQS[1])]
    fin2 = {r.rid: r for r in _timed_drain(eng2)}
    assert sorted(fin2) == sorted(rids)
    assert any(r.finish_reason == "timeout" for r in fin2.values())
    slo2 = eng2.stats()["slo"]
    assert slo2["tokens_out"] > 0
    assert slo2["goodput_tokens"] == slo2["tokens_out"]
    assert slo2["goodput_ratio"] == 1.0
    # and without deadlines everything is goodput
    eng3 = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                  token_budget=24, telemetry=True)
    _drain(eng3, list(zip(prompts, REQS[1])))
    slo3 = eng3.stats()["slo"]
    assert slo3["goodput_ratio"] == 1.0
    assert slo3["goodput_tokens"] == slo3["tokens_out"] > 0


def test_telemetry_reset_clears_attribution(smollm):
    cfg, m, params = smollm
    eng = Engine(m, params, max_slots=3, page_tokens=8, chunk_tokens=16,
                 token_budget=24, telemetry=True)
    eng.warmup()
    _drain(eng, list(zip(_prompts(cfg, REQS[0]), REQS[1])))
    assert eng.obs.attribution_summary()["totals"]["steps"] > 0
    eng.telemetry(reset=True)
    after = eng.obs.attribution_summary()
    assert after["totals"] == fresh_totals()
    assert after["families"] == {}
    assert len(eng.obs.step_records) == 0
    assert eng.cost_model is not None          # the model survives resets


# ---------------------------------------------------------------------------
# AST invariant lint coverage for the new modules
# ---------------------------------------------------------------------------

def test_obs_attrib_modules_pass_ast_lint():
    from pathlib import Path

    from repro.analysis.ast_lint import lint_paths

    repo = Path(__file__).resolve().parent.parent
    obs = repo / "src" / "repro" / "obs"
    serving = repo / "src" / "repro" / "serving"
    targets = [obs / "attrib.py", obs / "monitors.py", obs / "telemetry.py"]
    assert all(p.exists() for p in targets)
    findings = lint_paths(targets, serving_root=serving,
                          clock_roots=(serving, obs))
    assert findings == [], [f.format() for f in findings]
