"""The engine's step phases, as the program marks them in the profiler's
trace.

``Engine.step`` opens one ``engine.step`` span per step, holding in order
``engine.schedule``, ``engine.pack``, ``engine.dispatch``,
``engine.device_wait``, ``engine.logits_to_host`` and ``engine.commit``.
``engine.dispatch`` carries the step's ``step``, ``width``,
``prefill_tokens`` and ``decode_tokens`` as event stats.  ``xtrace.load``
keeps each host event's name and times only, so the stats are read here
from the run's trace file again, and that file is checked to be the one
the observation holds.  A program that marks no such spans gives no
steps, and the readers of these phases then report nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import sys
from typing import Dict, List

from bench import run, xtrace

STEP = "engine.step"
CHILDREN = ("engine.schedule", "engine.pack", "engine.dispatch",
            "engine.device_wait", "engine.logits_to_host", "engine.commit")


@dataclasses.dataclass
class EngineStep:
    start: float                 # the engine.step span (ns)
    end: float
    phase_ns: Dict[str, float]   # child name -> its time in this step (ns)
    args: dict                   # engine.dispatch's stats; {} if none
    children: List[xtrace.Op] = dataclasses.field(default_factory=list)


def read_file(path: str) -> List[EngineStep]:
    """The ``engine.step`` spans of a trace file, in order, each with its
    direct children (by the rule that a child starts inside its step)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    steps, children = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == STEP:
                    steps.append(EngineStep(float(ev.start_ns),
                                            float(ev.end_ns), {}, {}))
                elif name in CHILDREN:
                    children.append((xtrace.Op(name, float(ev.start_ns),
                                               float(ev.end_ns)),
                                     dict(ev.stats)
                                     if name == "engine.dispatch" else None))
    steps.sort(key=lambda s: s.start)
    starts = [s.start for s in steps]
    for op, stats in sorted(children, key=lambda c: c[0].start):
        j = bisect.bisect_right(starts, op.start) - 1
        if j < 0 or op.start >= steps[j].end:
            continue
        st = steps[j]
        st.children.append(op)
        st.phase_ns[op.name] = st.phase_ns.get(op.name, 0.0) \
            + (op.end - op.start)
        if stats is not None and not st.args:
            st.args = stats
    return steps


def steps(obs) -> List[EngineStep]:
    """The traced run's engine steps; read once per observation, with a
    line on standard error about how well the trace covers them."""
    if obs.trace is None:
        return []
    got = getattr(obs, "_engine_steps", None)
    if got is None:
        got = read_file(xtrace.find_xplane(str(run.TRACE_DIR)))
        held = xtrace.spans(obs.trace, STEP)
        if [(s.start, s.end) for s in got] != held:
            raise RuntimeError(f"{run.TRACE_DIR} holds {len(got)} engine "
                               f"steps; the observed trace {len(held)}")
        obs._engine_steps = got
        if got:
            print(coverage_line(obs.trace, got), file=sys.stderr, flush=True)
    return got


def mean_ms(obs, name: str):
    """Mean over the traced engine steps of the time in phase ``name``."""
    st = steps(obs)
    if not st:
        return None
    return sum(s.phase_ns.get(name, 0.0) for s in st) / len(st) * 1e-6


def decode_steps(obs) -> List[EngineStep]:
    """The steps whose ``engine.dispatch`` fed no prefill token."""
    return [s for s in steps(obs) if s.args.get("prefill_tokens") == 0
            and "decode_tokens" in s.args]


def coverage_line(trace: xtrace.Trace, st: List[EngineStep]) -> str:
    """What the trace shows of the engine steps: the share of each
    step's span its six phases cover, the steps outside any
    ``bench.step``, and the share of ``engine.device_wait`` time in which
    a device operation ran (low means the profiler lost device events,
    not that the device idled)."""
    covered = [sum(s.phase_ns.values()) / (s.end - s.start) for s in st
               if s.end > s.start] or [0.0]
    bench = xtrace.merge(xtrace.spans(trace, "bench.step"))
    starts = [lo for lo, _ in bench]
    outside = 0
    for s in st:
        j = bisect.bisect_right(starts, s.start) - 1
        outside += j < 0 or s.end > bench[j][1]
    waits = [(o.start, o.end) for s in st for o in s.children
             if o.name == "engine.device_wait"]
    wait_ns = sum(e - b for b, e in waits)
    busy_ns = sum(xtrace.busy_in(trace.device, waits))
    copy_ns = sum(s.phase_ns.get("engine.logits_to_host", 0.0) for s in st)
    share = 100.0 * busy_ns / wait_ns if wait_ns else float("nan")
    return (f"[trace] engine spans: {len(st)} steps, phases cover "
            f"{100 * min(covered)!r}-{100 * max(covered)!r}% of each step;"
            f" {outside} steps outside bench.step; engine.device_wait "
            f"{wait_ns * 1e-9!r} s, device busy in {share!r}% of it; "
            f"engine.logits_to_host {copy_ns * 1e-9!r} s")
