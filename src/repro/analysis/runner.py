"""Analyzer driver: all four passes over the engine configuration matrix.

``run_all`` is what ``scripts/analyze.py`` (and therefore
``scripts/tier1.sh --analyze``) executes: the AST lint over the serving
and kernel trees, then — for each engine configuration the serving stack
actually ships (monolithic, monolithic+speculative, flat,
flat+speculative, flat+prefix-cache) — the shape-ladder linter, the
KV-write aliasing pass, and (with ``traffic=True``) a sanitized warm
drain followed by the recompile-hazard report and the pool-ledger
audit.  One reduced model backs every engine, so jit programs are
shared and the whole matrix runs in test time.
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional

import numpy as np

from repro.analysis import aliasing, ast_lint, retrace, sanitize, shapes
from repro.analysis.report import AnalysisReport

__all__ = ["build_model", "analyze_engine", "run_all", "CONFIG_MATRIX"]

# every serving configuration family the repo ships; chunk/budget values
# are reduced-scale but exercise full ladders (the flat ladder has cap +
# sub-widths, the monolithic one its prefill buckets and verify shape)
CONFIG_MATRIX = [
    ("monolithic", dict()),
    ("monolithic-spec", dict(spec_tokens=2)),
    ("flat", dict(chunk_tokens=16)),
    ("flat-spec", dict(chunk_tokens=16, spec_tokens=2)),
    ("flat-prefix", dict(chunk_tokens=16, prefix_cache=True)),
]


def build_model(arch: str = "smollm2-135m", *, layers: int = 2,
                slots: int = 2, max_len: int = 64):
    """The reduced model + params every analyzed engine shares."""
    import jax
    from repro.configs import (RunConfig, ShapeSpec, get_config,
                               reduced_config)
    from repro.models.model import build_model as _build

    cfg = reduced_config(get_config(arch), layers=layers)
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    remat=False)
    model = _build(cfg, run, ShapeSpec("serve", max_len, slots, "decode"))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _traffic(engine, *, seed: int = 0) -> None:
    """A small deterministic drain exercising admission, chunking, growth,
    (pool permitting) preemption and mid-drain cancellation — mixed prompt
    lengths, shared prefix for the cache configs.  The cancel retires one
    request while another is mid-flight, so the sanitizer's retired-rid
    and zero-leak checks run against real traffic, not just unit tests."""
    rng = np.random.Generator(np.random.Philox(seed))
    shared = rng.integers(1, 50, size=12).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(1, 50, size=5).astype(np.int32)]),
        rng.integers(1, 50, size=21).astype(np.int32),
        np.concatenate([shared, rng.integers(1, 50, size=2).astype(np.int32)]),
        rng.integers(1, 50, size=3).astype(np.int32),
    ]
    budgets = [6, 5, 7, 4]
    rids = [engine.add_request(p, n) for p, n in zip(prompts, budgets)]
    engine.step(greedy=True, seed=seed)
    engine.cancel(rids[2])          # mid-drain: others must be unaffected
    engine.drain(greedy=True, seed=seed)


def analyze_engine(model, params, label: str, engine_kwargs: dict, *,
                   traffic: bool = True, trace: bool = True,
                   report: Optional[AnalysisReport] = None) -> AnalysisReport:
    """Passes 1–3 (+ sanitizer + ledger audit) on one configuration."""
    from repro.serving.engine import Engine

    report = report if report is not None else AnalysisReport()
    engine = Engine(model, params, **engine_kwargs)
    report.extend(shapes.lint_engine_shapes(engine, label, trace=trace),
                  section=f"{label}:shapes")
    report.extend(aliasing.lint_engine_aliasing(engine, label),
                  section=f"{label}:aliasing")
    if traffic:
        sanitize.install(engine)
        det = retrace.RetraceDetector(model)
        engine.warmup()
        det.mark()
        _traffic(engine)
        report.extend(det.findings(label), section=f"{label}:retrace")
        report.extend(aliasing.check_pool_consistency(engine, label),
                      section=f"{label}:pool-ledger")
    return report


def _repo_dirs():
    # repro is a namespace package (no __init__), so anchor on a module
    src_pkg = Path(__file__).resolve().parent.parent
    repo = src_pkg.parent.parent
    return (src_pkg / "serving", src_pkg / "kernels", src_pkg / "obs",
            repo / "tests")


def run_ast_lint(report: Optional[AnalysisReport] = None) -> AnalysisReport:
    """Pass 4 standalone (also reached via ``scripts/lint_invariants.py``)."""
    report = report if report is not None else AnalysisReport()
    serving_dir, kernels_dir, obs_dir, tests_dir = _repo_dirs()
    report.extend(ast_lint.lint_paths([serving_dir, kernels_dir, obs_dir],
                                      serving_root=serving_dir,
                                      clock_roots=(serving_dir, obs_dir)),
                  section="ast-lint:src")
    if tests_dir.is_dir():
        report.extend(ast_lint.lint_kernel_oracles(kernels_dir, tests_dir),
                      section="ast-lint:kernel-oracles")
    return report


def run_all(*, traffic: bool = True, trace: bool = True,
            matrix=None, log=None) -> AnalysisReport:
    report = AnalysisReport()
    run_ast_lint(report)
    model, params = build_model()
    for label, kwargs in (matrix if matrix is not None else CONFIG_MATRIX):
        if log:
            log(f"analyzing {label} ...")
        analyze_engine(model, params, label, kwargs,
                       traffic=traffic, trace=trace, report=report)
    return report
