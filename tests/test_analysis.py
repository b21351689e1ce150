"""Layout-contract analyzer (src/repro/analysis/).

Two halves, mirroring how a verifier earns trust:

* **green**: every pass runs clean on the engine configurations the repo
  actually ships (monolithic / chunked / flat / spec / prefix-cache),
  with the sanitizer installed and real traffic — the analyzer gating CI
  must not cry wolf;
* **seeded bugs**: each contract is deliberately broken — a mis-aligned
  chunk width, an in-place write to a shared page, a post-warmup retrace
  via a leaked python scalar, a direct free-list append in a scratch
  module — and the owning pass must catch exactly it, with a diagnostic
  naming the offending width/page/argument/line.
"""

import numpy as np
import pytest

from repro.analysis import (RetraceDetector, SanitizerError,
                            check_pool_consistency, install,
                            lint_engine_aliasing, lint_engine_shapes,
                            lint_kernel_oracles, lint_paths, run_ast_lint)
from repro.analysis.aliasing import lint_kv_writes, taint_step
from repro.analysis.runner import CONFIG_MATRIX, analyze_engine, build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import PagedKVPool, SequencePages

pytestmark = pytest.mark.analysis


@pytest.fixture(scope="module")
def smollm():
    return build_model()


def _drain_traffic(engine, seed=0):
    rng = np.random.Generator(np.random.Philox(seed))
    shared = rng.integers(1, 50, size=12).astype(np.int32)
    for p, n in [(np.concatenate([shared,
                                  rng.integers(1, 50, size=5)]).astype(
                      np.int32), 6),
                 (rng.integers(1, 50, size=21).astype(np.int32), 5),
                 (np.concatenate([shared,
                                  rng.integers(1, 50, size=2)]).astype(
                      np.int32), 4)]:
        engine.add_request(p, n)
    return engine.drain(greedy=True, seed=seed)


# ---------------------------------------------------------------------------
# green: the shipped configurations pass every static pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("label,kwargs", CONFIG_MATRIX,
                         ids=[c[0] for c in CONFIG_MATRIX])
def test_static_passes_green(smollm, label, kwargs):
    """Shape-ladder algebra + KV-write aliasing are clean on every config
    (jaxpr tracing included for one config to keep the default run fast —
    the full matrix traces in tier1.sh --analyze)."""
    model, params = smollm
    engine = Engine(model, params, **kwargs)
    findings = lint_engine_shapes(engine, label, trace=(label == "flat"))
    findings += lint_engine_aliasing(engine, label)
    assert not findings, "\n".join(f.format() for f in findings)


def test_sanitized_traffic_green(smollm):
    """A sanitized drain with prefix-cache sharing, growth and retrace
    watching stays clean — and the sanitizer demonstrably inspected the
    steps it certified."""
    model, params = smollm
    engine = Engine(model, params, chunk_tokens=16, prefix_cache=True)
    san = install(engine)
    det = RetraceDetector(model)
    engine.warmup()
    det.mark()
    out = _drain_traffic(engine)
    assert len(out) == 3 and all(r.out_tokens for r in out)
    assert san.checks > 0 and san.pages_checked > 0
    assert det.findings() == []
    assert check_pool_consistency(engine) == []


def test_ast_lint_green_on_tree():
    report = run_ast_lint()
    assert report.ok, report.format()


# ---------------------------------------------------------------------------
# seeded bug 1: mis-aligned chunk width -> shape-ladder linter
# ---------------------------------------------------------------------------

def test_seeded_misaligned_chunk(smollm):
    """chunk_tokens hacked to a non-m_r multiple after construction: the
    linter names the width and m_r."""
    model, params = smollm
    engine = Engine(model, params, chunk_tokens=16)
    engine.chunk_tokens = 11          # m_r = 8: not tile-aligned
    findings = lint_engine_shapes(engine, "seeded", trace=False)
    rules = {f.rule for f in findings}
    assert "chunk-align" in rules, findings
    msg = next(f for f in findings if f.rule == "chunk-align").message
    assert "11" in msg and "m_r" in msg


def test_seeded_broken_flat_ladder(smollm):
    """A width pushed onto the flat ladder that the declared geometric
    ladder doesn't contain is caught by ladder re-derivation."""
    model, params = smollm
    engine = Engine(model, params, chunk_tokens=16)
    real = engine._flat_shapes()

    engine._flat_shapes = lambda: sorted(set(real) | {24}, reverse=True)
    findings = lint_engine_shapes(engine, "seeded", trace=False)
    assert any(f.rule == "flat-ladder" and "24" in f.message
               for f in findings), findings


# ---------------------------------------------------------------------------
# seeded bug 2: in-place write to a shared page -> sanitizer
# ---------------------------------------------------------------------------

def test_seeded_shared_page_write(smollm):
    """Force ref > 1 on the page a decode row is about to write: the
    sanitizer must refuse the step, naming page, refcount and owner."""
    model, params = smollm
    engine = Engine(model, params, chunk_tokens=16)
    install(engine)
    engine.warmup()
    engine.add_request(np.arange(1, 14, dtype=np.int32), 24)
    engine.step()                     # admit + start prefill
    req = next(iter(engine.scheduler.running.values()))
    T = engine.pool.page_tokens

    def pos():
        return len(req.prompt) + len(req.out_tokens)

    # decode to a mid-page position so the next few writes stay inside
    # one page (no boundary crossing into a freshly allocated page)
    while not req.out_tokens or not 2 <= pos() % T <= T - 3:
        engine.step()
    target = req.pages.pages[pos() // T]
    engine.pool.share([target])       # simulate a missing cow()
    with pytest.raises(SanitizerError) as ei:
        for _ in range(3):
            engine.step()
    msg = str(ei.value)
    assert f"page {target}" in msg
    assert "ref=2" in msg
    assert str(req.rid) in msg        # owner named via pool.holders


def test_sanitizer_write_to_freed_page():
    """The page-level check alone (no engine): a block table referencing
    a freed page fails with ref=0."""
    pool = PagedKVPool(6, 8)
    seq = SequencePages(pool, owner=7)
    seq.ensure(8)
    page = seq.pages[0]
    pool.free([page])

    class _E:                        # minimal engine stand-in
        def __init__(self):
            self.pool = pool
            self._bucket = 8
            self.chunked = False
            self.spec_tokens = None

        def _prefill_bucket(self, l):
            return 8

    from repro.analysis.sanitize import StepSanitizer
    san = StepSanitizer(_E())
    with pytest.raises(SanitizerError, match=rf"page {page} \(ref=0\)"):
        san.check_paged(np.zeros((1, 1), np.int32),
                        np.full((1, 2), page, np.int32),
                        np.zeros((1,), np.int32), np.ones((1,), np.int32))


# ---------------------------------------------------------------------------
# seeded bug 3: post-warmup retrace via a leaked python scalar
# ---------------------------------------------------------------------------

def test_seeded_weak_type_retrace(smollm):
    """Warm the static decode step with a strong int32 position, then call
    it with a raw python 0 — the detector must attribute the retrace to
    the pos argument's weak_type flip."""
    import jax.numpy as jnp
    model, params = smollm
    step = model.jit_step("decode")
    caches = model.init_cache(1, 16)
    tok = jnp.zeros((1, 1), jnp.int32)
    _, caches = step(params, caches, tok, jnp.int32(0))      # "warmup"
    det = RetraceDetector(model)
    n0 = model.trace_counts["decode"]
    _, caches = step(params, caches, tok, 0)                 # the leak
    assert model.trace_counts["decode"] == n0 + 1
    findings = det.findings()
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "post-warmup-trace"
    assert "pos" in f.message and "weak_type" in f.message, f.message


def test_retrace_detector_quiet_on_cache_hit(smollm):
    """Replaying a warmed signature must not produce findings."""
    import jax.numpy as jnp
    model, params = smollm
    step = model.jit_step("decode")
    caches = model.init_cache(1, 16)
    tok = jnp.zeros((1, 1), jnp.int32)
    _, caches = step(params, caches, tok, jnp.int32(0))
    det = RetraceDetector(model)
    _, caches = step(params, caches, tok, jnp.int32(1))      # same signature
    assert det.findings() == []


# ---------------------------------------------------------------------------
# seeded bug 4: allocator mutation in a scratch module -> AST lint
# ---------------------------------------------------------------------------

def test_seeded_free_list_mutation(tmp_path):
    scratch = tmp_path / "scratch.py"
    scratch.write_text(
        "def leak(pool, p):\n"
        "    pool._free.append(p)      # bypasses the double-free check\n"
        "    pool._ref[p] = 1\n")
    findings = lint_paths([scratch])
    assert len(findings) == 2
    assert all(f.rule == "allocator-privacy" for f in findings)
    assert "scratch.py:2" in findings[0].where
    assert "_free" in findings[0].message
    assert "scratch.py:3" in findings[1].where


def test_seeded_raw_capacity_assert(tmp_path):
    serving = tmp_path / "serving"
    serving.mkdir()
    bad = serving / "sched_patch.py"
    bad.write_text(
        "def admit(pool, need):\n"
        "    assert need <= pool.free_pages\n")
    findings = lint_paths([serving], serving_root=serving)
    assert [f.rule for f in findings] == ["capacity-asserts"]
    assert "free_pages" in findings[0].message


def test_seeded_raw_capacity_raise_guard(tmp_path):
    """The PR-8 typed-exception conversion must not be a lint escape
    hatch: an `if <raw capacity>: raise ...` guard is flagged exactly
    like the assert it replaced, while guards on num_available pass."""
    serving = tmp_path / "serving"
    serving.mkdir()
    bad = serving / "sched_patch.py"
    bad.write_text(
        "def admit(pool, need):\n"
        "    if need > pool.num_free:\n"
        "        raise AdmissionError(0, 'page-demand', 'full')\n"
        "    if need > pool.num_available:\n"
        "        raise AdmissionError(0, 'page-demand', 'full')\n")
    findings = lint_paths([serving], serving_root=serving)
    assert [f.rule for f in findings] == ["capacity-asserts"]
    assert "num_free" in findings[0].message
    assert "sched_patch.py:2" in findings[0].where


def test_seeded_unseeded_randomness(tmp_path):
    bad = tmp_path / "noise.py"
    bad.write_text(
        "import random\n"
        "import numpy as np\n"
        "jitter = random.random()\n"
        "noise = np.random.randn(4)\n"
        "rng = np.random.default_rng()\n"
        "ok = np.random.Generator(np.random.Philox(0))\n"
        "ok2 = np.random.default_rng(7)\n")
    findings = lint_paths([bad])
    assert [f.rule for f in findings] == ["unseeded-randomness"] * 3
    lines = {int(f.where.rsplit(":", 1)[1]) for f in findings}
    assert lines == {3, 4, 5}        # the two seeded constructions pass


def test_seeded_wall_clock_in_serving(tmp_path):
    """Seeded bug for the monotonic-clock rule: a timing patch in a
    clock-ruled tree (serving/obs) that reads ``time.time()`` — via the
    module, an alias, or ``from time import time`` — is flagged, while
    ``perf_counter`` and deadline math on a caller-supplied ``now=``
    stay clean."""
    obs = tmp_path / "obs"
    obs.mkdir()
    bad = obs / "timing_patch.py"
    bad.write_text(
        "import time\n"
        "import time as walltime\n"
        "from time import time as tt\n"
        "def span():\n"
        "    t0 = time.time()\n"
        "    t1 = walltime.time()\n"
        "    t2 = tt()\n"
        "    ok = time.perf_counter()\n"
        "    return t1 - t0, t2, ok\n"
        "def expired(req, now):\n"
        "    return now >= req.deadline\n")
    findings = lint_paths([obs], clock_roots=(obs,))
    assert [f.rule for f in findings] == ["monotonic-clock"] * 3
    lines = {int(f.where.rsplit(":", 1)[1]) for f in findings}
    assert lines == {5, 6, 7}        # perf_counter and now= math pass
    assert "perf_counter" in findings[0].message

    # outside the clock roots the same file is none of the lint's
    # business — scripts and tests may read the wall clock freely
    assert lint_paths([obs]) == []


def test_kernel_oracle_rule(tmp_path):
    kernels = tmp_path / "kernels"
    (kernels / "fancy").mkdir(parents=True)
    (kernels / "fancy" / "kernel.py").write_text("def k():\n    pass\n")
    (kernels / "fancy" / "ref.py").write_text("def fancy_ref():\n    pass\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_none.py").write_text("import math\n")
    findings = lint_kernel_oracles(kernels, tests)
    assert [f.rule for f in findings] == ["kernel-oracle"]
    assert "fancy" in findings[0].message

    (tests / "test_none.py").write_text(
        "from repro.kernels.fancy.ref import fancy_ref\n")
    assert lint_kernel_oracles(kernels, tests) == []


# ---------------------------------------------------------------------------
# the aliasing pass sees and judges real write sites
# ---------------------------------------------------------------------------

def test_aliasing_flags_unguarded_write():
    """A scatter addressed without the trash-guard/where must be flagged —
    the pass proves the guard, it doesn't assume it."""
    import jax
    import jax.numpy as jnp

    def bad_update(pages, idx, val):
        return pages.at[idx].set(val)          # no validity route, no guard

    S = jax.ShapeDtypeStruct
    walker = taint_step(
        bad_update,
        (S((8, 4), jnp.float32), S((2,), jnp.int32), S((2, 4), jnp.float32)),
        {0: "pages", 1: "block_tables"})       # indices lack trash0
    findings = lint_kv_writes(walker, "seeded-bad-update")
    assert any(f.rule == "unguarded-write" and "trash0" in f.message
               for f in findings), findings


def test_aliasing_accepts_guarded_write():
    """The real guard shape — jnp.where(valid, bt-gathered page, 0) —
    earns both labels and passes."""
    import jax
    import jax.numpy as jnp

    def good_update(pages, bt, counts, val):
        pos = jnp.arange(val.shape[0], dtype=jnp.int32)
        valid = pos < counts
        page = jnp.where(valid, bt[pos], 0)
        return pages.at[page].set(val)

    S = jax.ShapeDtypeStruct
    walker = taint_step(
        good_update,
        (S((8, 4), jnp.float32), S((6,), jnp.int32), S((), jnp.int32),
         S((6, 4), jnp.float32)),
        {0: "pages", 1: "block_tables", 2: "validity"})
    findings = lint_kv_writes(walker, "guarded-update")
    assert findings == [], [f.format() for f in findings]


def test_pool_ledger_catches_stale_refcount(smollm):
    model, params = smollm
    engine = Engine(model, params, chunk_tokens=16)
    engine.warmup()
    engine.add_request(np.arange(1, 14, dtype=np.int32), 4)
    engine.drain()
    assert check_pool_consistency(engine) == []
    leaked = SequencePages(engine.pool, owner=99)
    leaked.pages.append(3)            # holds page 3 without a reference
    findings = check_pool_consistency(engine)
    assert any(f.rule == "ledger-mismatch" and "page 3" in f.message
               for f in findings), findings
    leaked.pages.clear()


# ---------------------------------------------------------------------------
# the full driver (slow: every config, traced + traffic)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_run_all_green(smollm):
    from repro.analysis import run_all
    report = run_all()
    assert report.ok, report.format()
    assert len(report.sections) >= 2 + 4 * len(CONFIG_MATRIX)
