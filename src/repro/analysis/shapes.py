"""Pass 1 — the shape-ladder linter.

The paper's codegen argument needs a *finite, m_r-aligned, geometric*
set of step shapes: prepacking is amortized only if tile geometry never
changes, and the zero-retrace contract holds only if every runtime shape
is a member of the warmed ladder.  This pass checks that contract twice:

* **Ladder algebra** (no tracing): re-derive each declared ladder from
  the scheduler contract — flat ladder = ``m_r``-aligned budget cap
  plus the powers of two of ``m_r`` below it; monolithic prefill
  buckets = geometric ``m_r``-multiples — and diff it against what the
  engine actually computes (`_flat_shapes`/`_prefill_bucket`).  A
  drifted implementation (e.g. a mis-aligned ``chunk_tokens`` hacked in
  after construction) is caught here with the exact offending width.

* **Jaxpr audit** (`jax.make_jaxpr` on the real step functions with
  ``ShapeDtypeStruct`` stand-ins, one trace per step family × ladder
  shape, mirroring ``Engine.warmup``'s enumeration): every aval dim of
  every eqn — including inside ``scan``/``pjit`` sub-jaxprs — must be a
  concrete Python int.  A data-dependent or symbolic dim anywhere in a
  compiled step family breaks the fixed-grid argument; the finding names
  the eqn's primitive and user call site.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_tools import eqn_where, iter_eqns
from repro.analysis.report import Finding
from repro.core.layout import round_up
from repro.serving.kv_cache import fresh_slot_states, prefill_view

__all__ = ["step_families", "lint_engine_shapes", "check_static_dims"]

_PASS = "shape-ladder"


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def step_families(engine) -> List[Tuple[str, object, tuple]]:
    """Every compiled step family × ladder shape this engine can hit,
    as ``(label, step_fn, abstract_args)`` — the same enumeration
    ``Engine.warmup`` compiles, but with ``ShapeDtypeStruct`` stand-ins
    so the linter traces without touching device state."""
    model = engine.model
    params = _sds(engine.params)
    caches = _sds(engine.caches)
    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    b, mp = engine.slots, engine.max_pages
    fams = []
    if engine.chunked:
        k1s = [1] + ([engine.spec_tokens + 1]
                     if engine.spec_tokens is not None else [])
        for w in engine._flat_shapes():
            for k1 in k1s:
                fams.append((f"flat[1,{w}]/k{k1}", model.flat_decode_step,
                             (params, caches, S((1, w), i32), S((b, mp), i32),
                              S((w,), i32), S((w,), i32), S((b * k1,), i32))))
        return fams
    # monolithic: geometric prefill buckets (batch-1 slot view) + decode
    if engine._bucket > 1:
        view = _sds(prefill_view(engine.caches,
                                 fresh_slot_states(engine.caches)))
        l, seen = engine._bucket, set()
        while True:
            bucket = engine._prefill_bucket(l)
            if bucket in seen:
                break
            seen.add(bucket)
            fams.append((f"prefill[1,{bucket}]", model.paged_decode_step,
                         (params, view, S((1, bucket), i32), S((1, mp), i32),
                          S((1,), i32), S((1,), i32), None)))
            l = bucket + 1
    fams.append((f"decode[{b},1]", model.paged_decode_step,
                 (params, caches, S((b, 1), i32), S((b, mp), i32),
                  S((b,), i32), S((b,), i32), None)))
    if engine.spec_tokens is not None:
        k1 = engine.spec_tokens + 1
        fams.append((f"verify[{b},{k1}]", model.paged_decode_step,
                     (params, caches, S((b, k1), i32), S((b, mp), i32),
                      S((b,), i32), S((b,), i32), S((b, k1), i32))))
    return fams


def check_static_dims(closed, family: str) -> List[Finding]:
    """Assert every aval dim in the jaxpr (sub-jaxprs included) is a
    concrete int — no data-dependent / symbolic shapes in a step family."""
    findings = []
    for path, eqn in iter_eqns(closed):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", ())
            for d in shape:
                if not isinstance(d, (int, np.integer)):
                    findings.append(Finding(
                        _PASS, "static-dims",
                        f"{eqn.primitive.name} @ {eqn_where(eqn)}",
                        f"{family}: non-static dim {d!r} in shape "
                        f"{tuple(shape)} (jaxpr path {path}) — every "
                        f"compiled step shape must be a concrete int or "
                        f"the fixed-grid/zero-retrace contract is void"))
    return findings


def _declared_flat_ladder(engine) -> set:
    cap = round_up(max(engine.token_budget,
                       engine.slots * ((engine.spec_tokens or 0) + 1)),
                   engine._bucket)
    ladder = {cap}
    v = engine._bucket
    while v < cap:
        ladder.add(v)
        v *= 2
    return ladder


def lint_engine_shapes(engine, label: str = "engine", *,
                       trace: bool = True,
                       max_traces: Optional[int] = None) -> List[Finding]:
    """Run pass 1 on one engine configuration.  ``trace=False`` skips the
    jaxpr audit (ladder algebra only — cheap enough for every test)."""
    f: List[Finding] = []
    m_r = engine._bucket
    here = f"{label} ({engine.model.cfg.name})"

    if engine.pool.page_tokens % max(m_r, 1) != 0:
        f.append(Finding(_PASS, "page-align", here,
                         f"page_tokens={engine.pool.page_tokens} is not a "
                         f"multiple of m_r={m_r} — pages must be whole "
                         f"microkernel tiles"))
    if engine.chunked:
        if engine.chunk_tokens % m_r != 0:
            f.append(Finding(_PASS, "chunk-align", here,
                             f"chunk_tokens={engine.chunk_tokens} is not "
                             f"m_r-aligned (m_r={m_r}) — chunk writes "
                             f"would straddle tiles",
                             detail={"chunk_tokens": engine.chunk_tokens,
                                     "m_r": m_r}))
        if engine.token_budget < m_r:
            f.append(Finding(_PASS, "budget-liveness", here,
                             f"token_budget={engine.token_budget} < m_r="
                             f"{m_r}: plan_chunks rounds grants down to "
                             f"the tile, so prefill could never advance"))
        if (engine.spec_tokens is not None
                and engine.chunk_tokens < engine.spec_tokens + 1):
            f.append(Finding(_PASS, "verify-width", here,
                             f"chunk_tokens={engine.chunk_tokens} cannot "
                             f"hold the [{engine.spec_tokens + 1}]-wide "
                             f"verify row"))
        declared = _declared_flat_ladder(engine)
        actual = set(engine._flat_shapes())
        for w in sorted(actual):
            if w % m_r != 0:
                f.append(Finding(_PASS, "flat-align", here,
                                 f"flat width {w} is not m_r-aligned "
                                 f"(m_r={m_r}) — tile writes would be "
                                 f"partial",
                                 detail={"width": w, "m_r": m_r}))
        if actual != declared:
            f.append(Finding(_PASS, "flat-ladder", here,
                             f"flat ladder {sorted(actual)} != declared "
                             f"{sorted(declared)}"))
        for n in {1, m_r, m_r + 1, max(declared), engine.token_budget}:
            if n < 1 or n > max(declared):
                continue
            w = engine._flat_shape(n)
            fits = sorted(x for x in declared if x >= n)
            if w not in declared or w < n or (fits and w != fits[0]):
                f.append(Finding(_PASS, "flat-pick", here,
                                 f"_flat_shape({n}) = {w}, expected the "
                                 f"smallest ladder member >= {n} "
                                 f"({fits[0] if fits else '??'})"))
    elif m_r > 1:
        cap = round_up(engine.scheduler.max_len, m_r)
        l, seen = m_r, set()
        while True:
            b = engine._prefill_bucket(l)
            if b in seen:
                break
            seen.add(b)
            ok_geo = b == cap or (b % m_r == 0
                                  and (b // m_r & (b // m_r - 1)) == 0)
            if b < l or b > cap or not ok_geo:
                f.append(Finding(_PASS, "prefill-bucket", here,
                                 f"_prefill_bucket({l}) = {b}: must be a "
                                 f"geometric m_r-multiple in [{l}, {cap}]"))
            l = b + 1

    if trace:
        fams = step_families(engine)
        if max_traces is not None:
            fams = fams[:max_traces]
        for fam, fn, abstract_args in fams:
            closed = jax.make_jaxpr(fn)(*abstract_args)
            f.extend(check_static_dims(closed, f"{here} {fam}"))
    return f
