"""Performance attribution: a roofline-grounded per-family step cost model.

The paper's whole pitch is *predictability*: packed layouts exist so tile
shapes — and therefore step cost — are known before execution.  This
module joins the repo's two halves of that story.  At ``Engine.warmup()``
time (and **only** then — the warmup-only contract below) it builds a
:class:`StepCostModel`: for every compiled shape family on the engine's
ladder (monolithic prefill buckets, flat widths, verify widths — the
exact enumeration :func:`repro.analysis.shapes.step_families` derives
from the warmup loop), the step function is lowered with
``ShapeDtypeStruct`` stand-ins and compiled, XLA's ``cost_analysis()`` is
normalized via :func:`repro.roofline.hlo_cost.xla_cost_dict`, the
while-aware HLO parse re-derives dot FLOPs and HBM bytes, and the result
is priced against a :class:`repro.core.hardware.HardwareSpec`:

    compute_s   = dot_flops / peak_flops(compute dtype)
    memory_s    = hbm_bytes / hbm_bw
    predicted_s = max(compute_s, memory_s)        (the roofline)

KV-page **gather** bytes are additionally counted explicitly from the
engine's own cache geometry (rows x block-table window x per-token KV
bytes summed over the paged pools) — the paged-attention traffic term the
serving dry-run cell (``launch/dryrun.py --serving``) reports before
launch.

Per-step attribution then happens entirely on the telemetry side
(:mod:`repro.obs.telemetry`): each measured step is tagged with the
family label(s) it executed, its wall time is split into
``sched + call + draft + host`` (exact by construction — the split is
derived from the step's own span timestamps, so the components sum to the
measured wall; asserted within tolerance in ``tests/test_attrib.py``),
and *padding waste* prices the flat step's ``fill`` in time units:
``(width - real_tokens) * per_token_s`` of the family's roofline cost.
Per-drain rollups (:func:`summarize`) report MFU/MBU, achieved- vs
roofline-tokens/s, padding-waste ratio and goodput.

Warmup-only contract: nothing in this module runs per step.  The cost
model is a frozen dict after ``build_cost_model`` returns; the per-step
hot path only ever does a dict lookup and float arithmetic on the host.
Lowering here uses *fresh* ``jax.jit`` wrappers around the raw step
functions, so the model's counted ``jit_step`` caches — and with them the
zero-post-warmup-trace invariant — are untouched.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

__all__ = ["FamilyCost", "StepCostModel", "build_cost_model",
           "kv_page_bytes_per_token", "fresh_totals", "update_aggregates",
           "finalize_summary", "summarize"]


@dataclasses.dataclass(frozen=True)
class FamilyCost:
    """Predicted cost of one compiled step family (one ladder shape)."""

    label: str                 # e.g. "flat[1,64]/k1", "verify[4,3]"
    width: int                 # padded token positions per step (the grid)
    flops: float               # while-aware dot FLOPs per step
    hbm_bytes: float           # while-aware HBM traffic per step
    kv_gather_bytes: float     # block-table-window KV gather traffic
    compute_s: float           # flops / peak_flops(dtype)
    memory_s: float            # hbm_bytes / hbm_bw
    kv_gather_s: float         # kv_gather_bytes / hbm_bw
    predicted_s: float         # max(compute_s, memory_s) — the roofline
    per_token_s: float         # predicted_s / width (padding-waste price)
    bottleneck: str            # "compute" | "memory"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class StepCostModel:
    """The per-family roofline table, frozen at warmup.

    ``families`` maps the family label (the same string the engine tags
    measured steps with) to its :class:`FamilyCost`.  ``hw``/``dtype``
    record what the prediction was priced against; ``flops_per_token`` is
    the model-FLOPs rate used for MFU (2·N_active per token)."""

    hw_name: str
    dtype: str
    peak_flops: float
    hbm_bw: float
    flops_per_token: float
    families: Dict[str, FamilyCost]

    def get(self, label: str) -> Optional[FamilyCost]:
        return self.families.get(label)

    def to_dict(self) -> dict:
        return {
            "hw": self.hw_name, "dtype": self.dtype,
            "peak_flops": self.peak_flops, "hbm_bw": self.hbm_bw,
            "flops_per_token": self.flops_per_token,
            "families": {k: v.to_dict() for k, v in self.families.items()},
        }


def kv_page_bytes_per_token(caches, num_pages: int, page_tokens: int) -> float:
    """Bytes of paged K/V per cached token, summed over every page-pool
    leaf — the ``*_pages`` leaves, ``[layers, num_pages, ...]`` with
    ``page_tokens`` tokens a page (models/attention.py ``KVPageLayout``).
    Per-slot recurrent state is excluded: it is not gathered through the
    block table."""
    import jax
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        if not jax.tree_util.keystr(path).endswith("_pages']"):
            continue
        nbytes = float(np.dtype(leaf.dtype).itemsize)
        for d in leaf.shape:
            nbytes *= int(d)
        total += nbytes / (num_pages * page_tokens)
    return total


def _family_geometry(label: str, engine) -> tuple:
    """(padded token positions, gathering rows) of a family, parsed from
    its label — the same grammar ``analysis.shapes.step_families`` emits:
    ``flat[1,W]/kK`` | ``prefill[1,L]`` |
    ``decode[B,1]`` | ``verify[B,K]``."""
    dims = label.split("[", 1)[1].split("]", 1)[0]
    a, b = (int(x) for x in dims.split(","))
    width = a * b
    rows = engine.slots if label.startswith("flat") else a
    return width, rows


def build_cost_model(engine, hw=None) -> StepCostModel:
    """Lower + compile every step family with abstract stand-ins and price
    it against ``hw`` (default: :func:`repro.core.hardware.query`).  Runs
    once, at warmup — see the module docstring for the contract."""
    import jax

    from repro.analysis.shapes import step_families
    from repro.core.hardware import query
    from repro.roofline.hlo_cost import parse_hlo, xla_cost_dict

    hw = hw if hw is not None else query()
    dtype = engine.model.compute_dtype
    peak = hw.peak_flops(dtype)
    kv_per_token = kv_page_bytes_per_token(
        engine.caches, engine.pool.num_pages, engine.pool.page_tokens)
    window_tokens = engine.max_pages * engine.pool.page_tokens

    families: Dict[str, FamilyCost] = {}
    for label, fn, abstract_args in step_families(engine):
        compiled = jax.jit(fn).lower(*abstract_args).compile()
        cost = xla_cost_dict(compiled.cost_analysis())
        parsed = parse_hlo(compiled.as_text())
        flops = float(parsed.dot_flops) or float(cost.get("flops", 0.0))
        nbytes = float(parsed.hbm_bytes) \
            or float(cost.get("bytes accessed", 0.0))
        width, rows = _family_geometry(label, engine)
        gather = rows * window_tokens * kv_per_token
        compute_s = flops / peak
        memory_s = nbytes / hw.hbm_bw
        predicted = max(compute_s, memory_s)
        families[label] = FamilyCost(
            label=label, width=width, flops=flops, hbm_bytes=nbytes,
            kv_gather_bytes=gather, compute_s=compute_s, memory_s=memory_s,
            kv_gather_s=gather / hw.hbm_bw, predicted_s=predicted,
            per_token_s=predicted / max(1, width),
            bottleneck="compute" if compute_s >= memory_s else "memory")

    n_active = engine.model.cfg.param_counts()["active"]
    return StepCostModel(hw_name=hw.name, dtype=str(dtype), peak_flops=peak,
                         hbm_bw=hw.hbm_bw,
                         flops_per_token=2.0 * n_active,
                         families=families)


def fresh_totals() -> dict:
    """A zeroed drain-total accumulator (see :func:`update_aggregates`)."""
    return {"steps": 0, "wall_s": 0.0, "sched_s": 0.0, "call_s": 0.0,
            "draft_s": 0.0, "host_s": 0.0, "predicted_s": 0.0,
            "padding_waste_s": 0.0, "real_tokens": 0, "padded_tokens": 0}


def update_aggregates(tot: dict, fams: Dict[str, dict], rec: dict,
                      cost_model: Optional[StepCostModel]) -> None:
    """Fold one per-step attribution record into the running drain
    aggregates (mutates ``tot``/``fams`` in place).  Incremental so the
    telemetry's bounded per-step window can drop old records without the
    drain summary losing them."""
    tot["steps"] += 1
    tot["wall_s"] += rec["wall"]
    tot["sched_s"] += rec["sched"]
    tot["call_s"] += rec["call"]
    tot["draft_s"] += rec["draft"]
    tot["host_s"] += rec["host"]
    for label, real, width, call_s in rec["families"]:
        f = fams.setdefault(label, {
            "steps": 0, "real_tokens": 0, "padded_tokens": 0,
            "call_s": 0.0, "predicted_s": 0.0, "padding_waste_s": 0.0})
        f["steps"] += 1
        f["real_tokens"] += real
        f["padded_tokens"] += width
        f["call_s"] += call_s
        fc = cost_model.get(label) if cost_model is not None else None
        if fc is not None:
            f["predicted_s"] += fc.predicted_s
            f["padding_waste_s"] += (width - real) * fc.per_token_s
        tot["real_tokens"] += real
        tot["padded_tokens"] += width
        tot["predicted_s"] += fc.predicted_s if fc is not None else 0.0
        tot["padding_waste_s"] += ((width - real) * fc.per_token_s
                                   if fc is not None else 0.0)


def finalize_summary(tot: dict, fams: Dict[str, dict],
                     cost_model: Optional[StepCostModel], *,
                     goodput_tokens: int = 0,
                     tokens_out: int = 0) -> dict:
    """The per-drain attribution view over the running aggregates:
    component totals, per-family predicted-vs-measured, MFU/MBU, padding
    waste, achieved- vs roofline-tokens/s and goodput.

    MFU uses *useful* model FLOPs (real tokens x 2·N_active) over
    measured wall x peak; MBU uses the families' modelled HBM bytes over
    wall x bandwidth — both are honest about padding (padded positions
    burn wall time but earn no useful FLOPs, so waste lowers MFU exactly
    as it should)."""
    fams = {label: dict(f) for label, f in fams.items()}
    for f in fams.values():
        f["fill"] = f["real_tokens"] / max(1, f["padded_tokens"])
        f["predicted_vs_measured"] = (f["predicted_s"] / f["call_s"]
                                      if f["call_s"] > 0 else 0.0)
    wall = tot["wall_s"]
    out = {"totals": dict(tot), "families": fams}
    if cost_model is not None and wall > 0:
        useful_flops = tot["real_tokens"] * cost_model.flops_per_token
        modelled_bytes = sum(
            f["steps"] * cost_model.get(l).hbm_bytes
            for l, f in fams.items() if cost_model.get(l) is not None)
        out["mfu"] = useful_flops / (wall * cost_model.peak_flops)
        out["mbu"] = modelled_bytes / (wall * cost_model.hbm_bw)
        out["padding_waste_ratio"] = (tot["padding_waste_s"]
                                      / max(tot["call_s"], 1e-12))
        out["achieved_tokens_per_s"] = tot["real_tokens"] / wall
        out["roofline_tokens_per_s"] = (
            tot["real_tokens"] / tot["predicted_s"]
            if tot["predicted_s"] > 0 else math.inf)
        out["roofline_fraction"] = (tot["predicted_s"] / wall
                                    if wall > 0 else 0.0)
    out["goodput_tokens"] = goodput_tokens
    out["tokens_out"] = tokens_out
    out["goodput_ratio"] = goodput_tokens / max(1, tokens_out)
    return out


def summarize(step_records: List[dict], cost_model: Optional[StepCostModel],
              *, goodput_tokens: int = 0, tokens_out: int = 0) -> dict:
    """One-shot :func:`finalize_summary` over a list of step records
    (the standalone path; the live telemetry aggregates incrementally)."""
    tot, fams = fresh_totals(), {}
    for rec in step_records:
        update_aggregates(tot, fams, rec, cost_model)
    return finalize_summary(tot, fams, cost_model,
                            goodput_tokens=goodput_tokens,
                            tokens_out=tokens_out)
