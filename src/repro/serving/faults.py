"""Deterministic fault injection for the serving engine.

The resilience contract is **identity under chaos**: for any seeded
:class:`FaultPlan`, every request that survives the plan produces tokens
bit-identical to the fault-free run, and the allocator is balanced once
the drain ends.  The engine can promise this because its failure
handling only ever *removes* work — shed at admission, quarantine a
poisoned row, preempt-and-recompute a displaced one — and rows are
mathematically independent with every pick keyed by
``(seed, rid, position)``, so a survivor cannot observe a casualty.

A :class:`FaultPlan` is pure host-side instrumentation.  ``install``
wraps the engine's step entry point to track the step number and arms
one-shot faults at the planned steps:

  - ``"oom"``     — the next ``pool.alloc`` raises ``OutOfPages``
    (exercises the admission-rollback and growth-preemption paths);
  - ``"drafter"`` — the next ``Drafter.propose_all`` raises (exercises
    the speculative degradation ladder up to auto-disable);
  - ``"nan"``     — one live row of the step's logits is overwritten
    with NaN on the host *after* the device call (exercises the
    quarantine path; device state is untouched, so the zero-recompile
    contract is preserved under injection);
  - ``"copier"``  — the next ``page_copier`` call raises (exercises the
    CoW failure paths: prefix-hit fallback and rollback quarantine).

Event schedules derive from the plan's seed via ``np.random.Philox`` —
the same plan replays the same faults at the same steps, which is what
lets the chaos smoke diff a faulted drain against a clean one.

:class:`StallError` lives here too: it is the watchdog's terminal
diagnosis when a drain stops advancing, and fault plans are the main way
to provoke one on purpose.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.telemetry import NULL as _NULL_OBS
from repro.serving.kv_cache import OutOfPages

__all__ = ["FAULT_KINDS", "FaultEvent", "FaultPlan", "InjectedFault",
           "StallError"]

FAULT_KINDS = ("oom", "drafter", "nan", "copier")


class StallError(RuntimeError):
    """A drain stopped advancing: the fused step scheduled zero tokens
    while slots were live, or admissible work sat unadmitted for
    ``watchdog_steps`` consecutive idle ticks.  The message names the
    non-advancing rids and their lifecycle states so a stuck server is
    diagnosable instead of silently spinning."""


class InjectedFault(RuntimeError):
    """Raised only by injected drafter/copier faults, never by real
    code — test assertions can tell an injection apart from an organic
    failure."""


@dataclass(frozen=True)
class FaultEvent:
    """One planned fault: at engine step ``step`` (0-based, counted over
    ``Engine.step`` calls), arm a one-shot fault of ``kind``."""
    step: int
    kind: str


class FaultPlan:
    """A seeded, replayable schedule of injected faults.

    ``install(engine)`` monkey-patches the engine instance (never the
    classes); ``uninstall()`` restores every patched attribute, so a
    plan can be applied to one drain of a long-lived engine.  The
    ``on(engine)`` context manager pairs the two.
    """

    def __init__(self, events: Sequence[FaultEvent], *, seed: int = 0):
        for e in events:
            if e.kind not in FAULT_KINDS:
                raise ValueError(f"unknown fault kind {e.kind!r} "
                                 f"(expected one of {FAULT_KINDS})")
            if e.step < 0:
                raise ValueError(f"fault step must be >= 0, got {e.step}")
        self.events: List[FaultEvent] = sorted(
            events, key=lambda e: (e.step, e.kind))
        self.seed = seed
        self.fired = {k: 0 for k in FAULT_KINDS}
        self._armed: List[str] = []
        self._step_no = 0
        self._installed = None
        self._undo: List[Tuple[object, str, object, bool]] = []
        self._obs = _NULL_OBS          # the engine's recorder, on install

    @classmethod
    def random(cls, seed: int, *, steps: int = 32, num_events: int = 4,
               kinds: Sequence[str] = FAULT_KINDS) -> "FaultPlan":
        """A seeded random plan: ``num_events`` faults over engine steps
        ``[1, steps)``, kinds drawn uniformly.  Same seed, same plan."""
        rng = np.random.Generator(np.random.Philox(seed))
        events = [FaultEvent(int(rng.integers(1, max(2, steps))),
                             kinds[int(rng.integers(len(kinds)))])
                  for _ in range(num_events)]
        return cls(events, seed=seed)

    # ------------------------------------------------------------------
    # arming
    # ------------------------------------------------------------------
    def _take(self, kind: str) -> bool:
        """Consume one armed fault of ``kind`` (one-shot per event)."""
        if kind in self._armed:
            self._armed.remove(kind)
            self.fired[kind] += 1
            self._obs.fault(kind, self._step_no - 1)
            return True
        return False

    @staticmethod
    def _victim_slot(engine) -> Optional[int]:
        """Deterministic NaN victim: the smallest decoding slot, else the
        smallest live slot (a prefilling row), else None (fault wasted —
        an idle step has no logits row to poison)."""
        running = engine.scheduler.running
        decoding = [s for s, r in running.items() if r.status == "running"]
        if decoding:
            return min(decoding)
        return min(running) if running else None

    # ------------------------------------------------------------------
    # install / uninstall
    # ------------------------------------------------------------------
    def _wrap(self, obj, name: str, wrapper) -> None:
        had = name in obj.__dict__
        self._undo.append((obj, name, getattr(obj, name) if had else None,
                           had))
        setattr(obj, name, wrapper)

    def install(self, engine) -> "FaultPlan":
        if self._installed is not None:
            raise RuntimeError("FaultPlan is already installed")
        self._installed = engine
        self._step_no = 0
        self._obs = getattr(engine, "obs", _NULL_OBS)
        plan = self

        orig_step = engine.step

        def step(*, now=None, greedy=True, seed=0):
            plan._armed = [e.kind for e in plan.events
                           if e.step == plan._step_no]
            plan._step_no += 1
            try:
                return orig_step(now=now, greedy=greedy, seed=seed)
            finally:
                plan._armed = []
        self._wrap(engine, "step", step)

        pool = engine.pool
        orig_alloc = pool.alloc

        def alloc(*a, **k):
            if plan._take("oom"):
                raise OutOfPages("injected OutOfPages spike (FaultPlan "
                                 f"seed={plan.seed}, step {plan._step_no - 1})")
            return orig_alloc(*a, **k)
        self._wrap(pool, "alloc", alloc)

        if pool.page_copier is not None:
            orig_copier = pool.page_copier

            def copier(src, dst):
                if plan._take("copier"):
                    raise InjectedFault(
                        f"injected page_copier failure ({src} -> {dst}, "
                        f"FaultPlan seed={plan.seed})")
                return orig_copier(src, dst)
            self._wrap(pool, "page_copier", copier)

        if getattr(engine, "drafter", None) is not None:
            orig_propose = engine.drafter.propose_all

            def propose_all(jobs):
                if plan._take("drafter"):
                    raise InjectedFault(
                        f"injected drafter failure (FaultPlan "
                        f"seed={plan.seed}, step {plan._step_no - 1})")
                return orig_propose(jobs)
            self._wrap(engine.drafter, "propose_all", propose_all)

        orig_paged = engine._run_paged

        def run_paged(token, bt, lens, counts, idx, **counts_kw):
            rows = orig_paged(token, bt, lens, counts, idx, **counts_kw)
            if "nan" in plan._armed:
                slot = plan._victim_slot(engine)
                if slot is not None and plan._take("nan"):
                    rows = np.array(rows)
                    rows[slot] = np.nan
            return rows
        self._wrap(engine, "_run_paged", run_paged)

        if getattr(engine, "_flat_step", None) is not None:
            orig_flat = engine._run_flat

            def run_flat(token, bt, row_ids, q_pos, idx, **counts_kw):
                out = orig_flat(token, bt, row_ids, q_pos, idx, **counts_kw)
                if "nan" in plan._armed:
                    slot = plan._victim_slot(engine)
                    if slot is not None and plan._take("nan"):
                        out = np.array(out)
                        k1 = out.shape[0] // engine.slots
                        out[slot * k1:(slot + 1) * k1] = np.nan
                return out
            self._wrap(engine, "_run_flat", run_flat)
        return self

    def uninstall(self) -> None:
        for obj, name, orig, had in reversed(self._undo):
            if had:
                setattr(obj, name, orig)
            else:
                delattr(obj, name)
        self._undo = []
        self._armed = []
        self._installed = None

    @contextlib.contextmanager
    def on(self, engine):
        """``with plan.on(engine): engine.drain()`` — install for the
        block, restore afterwards even if the drain raises."""
        self.install(engine)
        try:
            yield self
        finally:
            self.uninstall()

    def stats(self) -> dict:
        return {
            "seed": self.seed,
            "events": [(e.step, e.kind) for e in self.events],
            "fired": dict(self.fired),
        }
