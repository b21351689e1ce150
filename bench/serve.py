"""Drive the serving engine with a traffic mix and record what users see.

The loop calls ``Engine.add_request`` when a request is due and
``Engine.step`` while there is work, and stamps each token with the end of
the step that returned it (the step copies its logits to the host and
picks on the host, so the token exists when ``step`` returns).  Phases on
one clock:

  ramp    traffic runs for ``ramp_s`` seconds, and with a head start
          (``bench/traffic.py``) until every head-start request has its
          first token, so the batch is in its steady mix when the window
          opens; counted as set-up
  window  ``seconds`` long; what the metrics read
  tail    open loop only: stepping goes on, with arrivals still on their
          schedule, until every request due in the window has its first
          token, for at most ``TAIL_S`` seconds

A closed loop keeps ``outstanding_per_slot * slots`` requests queued or
running and replaces each as it finishes.  Admission is read from
``Request.admit_seq``: a request admitted by a step was admitted at that
step's start.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

from bench import traffic as traffic_mod

TAIL_S = 60.0


@dataclasses.dataclass
class Tracked:
    draw: traffic_mod.Draw
    due: float
    rid: int
    admitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    reason: Optional[str] = None      # finish reason once finished
    out_tokens: Optional[List[int]] = None   # once finished, or at the end


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    rows: List[Tuple[int, int]]       # (first query position, queries)
    emitted: int
    traced: bool


@dataclasses.dataclass
class Record:
    requests: Dict[int, Tracked]
    steps: List[Step]
    origin: float
    open: float
    close: float
    end: float
    stats_open: dict
    stats_close: dict
    compiles_in_window: Dict[str, int]
    closed_loop: bool
    late_max_s: float = 0.0     # open loop: how late the generator ran


def run(engine, cell, seed: int, seconds: float, *, trace_dir=None,
        trace_s: float = 0.0) -> Record:
    """Serve ``cell``'s traffic from ``seed`` and return the record.  With
    ``trace_dir`` the profiler traces the window's first ``trace_s``
    seconds, with each step in a ``bench.step`` annotation."""
    tr = cell.traffic
    clock = time.perf_counter
    serving = cell.config["bench"]["serving"]
    arrivals = tr["arrivals"]
    closed = arrivals["kind"] == "closed"
    draws = traffic_mod.requests(tr, seed,
                                 vocab=cell.config["bench"]["vocab_tokens"],
                                 max_len=serving["max_len"],
                                 slots=engine.slots)
    n_head = traffic_mod.head_start_count(tr, engine.slots)
    sched = engine.scheduler
    model = engine.model
    reqs: Dict[int, Tracked] = {}
    steps: List[Step] = []
    head: List[Tracked] = []

    def add(draw, due):
        rid = engine.add_request(draw.prompt, draw.max_new)
        reqs[rid] = Tracked(draw=draw, due=due, rid=rid)
        if len(head) < n_head:
            head.append(reqs[rid])

    origin = clock()
    open_t = origin + float(tr["ramp_s"])
    close_t = open_t + seconds
    if closed:
        for _ in range(int(arrivals["outstanding_per_slot"] * engine.slots)):
            add(next(draws), origin)
        next_due = None
    else:
        pending = next(draws)
        next_due = origin + pending.gap_s
    phase = "ramp"
    stats_open = stats_close = None
    compiles_open = None
    tracing = False
    window_ann = None
    trace_stop = open_t + trace_s
    late_max = 0.0

    def ann(name):
        if tracing:
            from jax.profiler import TraceAnnotation
            return TraceAnnotation(name)
        return contextlib.nullcontext()

    while True:
        now = clock()
        if (phase == "ramp" and now >= open_t
                and all(t.times or t.reason for t in head)):
            phase = "window"
            open_t = now
            close_t = open_t + seconds
            trace_stop = open_t + trace_s
            stats_open = engine.stats()
            compiles_open = dict(model.trace_counts)
            if trace_dir is not None and trace_s > 0:
                import jax
                # Python tracing would slow the host loop being measured
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                tracing = True
                window_ann = ann("bench.window")
                window_ann.__enter__()
        if tracing and now >= trace_stop:
            window_ann.__exit__(None, None, None)
            import jax
            jax.profiler.stop_trace()
            tracing = False
        if phase == "window" and now >= close_t:
            phase = "tail"
            stats_close = engine.stats()
            compiles = {k: v - compiles_open[k]
                        for k, v in model.trace_counts.items()}
        if phase == "tail" and not tracing:
            if closed:
                break
            waiting = [t for t in reqs.values()
                       if open_t <= t.due < close_t and not t.times
                       and t.reason is None]
            if not waiting or now > close_t + TAIL_S:
                break
        while next_due is not None and next_due <= now:
            late_max = max(late_max, now - next_due)
            add(pending, next_due)
            pending = next(draws)
            next_due += pending.gap_s
        if not sched.has_work and not engine._finished_oob:
            with ann("bench.idle"):
                time.sleep(max(0.0, min(next_due - clock(), 1e-3))
                           if next_due is not None else 1e-3)
            continue
        before = {r.rid: (r.status, r.prefill_cursor, r.len)
                  for r in sched.running.values()}
        with ann("bench.step"):
            t0 = clock()
            finished = engine.step()
            t1 = clock()
        with ann("bench.record"):
            rows, emitted = [], 0
            done_rids = {r.rid for r in finished}
            for r in list(sched.running.values()) + finished:
                t = reqs.get(r.rid)
                if t is None:
                    continue
                status, cur, ln = before.get(r.rid, ("waiting", 0, 0))
                if t.admitted is None and r.admit_seq >= 0:
                    t.admitted = t0
                if r.prefill_cursor > cur:
                    rows.append((cur, r.prefill_cursor - cur))
                elif status == "running" and r.len > ln:
                    rows.append((ln, r.len - ln))
                new = len(r.out_tokens) - len(t.times)
                if new > 0:
                    t.times.extend([t1] * new)
                    emitted += new
                if r.rid in done_rids:
                    t.reason = r.finish_reason
                    t.out_tokens = list(r.out_tokens)
            steps.append(Step(t0, t1, rows, emitted, tracing))
            if closed:
                for _ in finished:
                    add(next(draws), t1)
    for r in sched.running.values():       # cut by the end of the run
        if r.rid in reqs:
            reqs[r.rid].out_tokens = list(r.out_tokens)
    return Record(requests=reqs, steps=steps, origin=origin, open=open_t,
                  close=close_t, end=clock(), stats_open=stats_open,
                  stats_close=stats_close, compiles_in_window=compiles,
                  closed_loop=closed, late_max_s=late_max)
