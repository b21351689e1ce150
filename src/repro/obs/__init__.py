"""``repro.obs`` — serving observability: step-phase spans in the
profiler's trace, lifecycle tracing, streaming metrics, performance
attribution, anomaly monitors, Perfetto trace export.

Strictly host-side: every event is a Python method call timed with
``time.perf_counter()`` (step phases are also
``jax.profiler.TraceAnnotation`` spans); nothing here touches a jitted
code path, a device array, or the token math.  The
standing serving invariants therefore hold by construction — telemetry
on/off is token-identical, adds zero post-warmup XLA traces, and the
disabled default (:data:`~repro.obs.telemetry.NULL`) costs one no-op
call per event with no clock reads (all checked in ``tests/test_obs.py``).

Event taxonomy
==============

**Request lifecycle** (per-request; mirrors the scheduler's state
machine, see :mod:`repro.serving.scheduler`).  Durations render as
spans, transitions as instants; each also lands in the request's own
``obs_events`` list as ``(label, t)``:

========================  ==========================================
event                     meaning
========================  ==========================================
``queued``                entered the scheduler's waiting queue
``admitted``              took a slot (queue-wait span closes)
``prefill_chunk``         one chunk of prompt KV written (span per
                          chunk on the slot's track)
``prefill_done``          prompt KV complete; decode span opens
``preempted``             pages released, tokens folded, requeued
``paused``                mid-prefill victim: slot surrendered,
                          pages + cursor kept, requeued
``reclaimed``             a paused request's pages were reclaimed
``finished``              happy-path exit (eos | length)
``cancelled:<reason>``    retired early: ``timeout`` | ``cancelled``
                          | ``error`` (the NaN-logit quarantine)
``shed:<kind>``           rejected at ``add()`` by admission control
                          (never queued)
========================  ==========================================

**Step phases** (per engine step, in the profiler's own trace, on the
device's clock): ``Engine.step`` marks each phase with
``telemetry.span(name, **args)``, a ``jax.profiler.TraceAnnotation``
whether telemetry is on or off.  One ``engine.step`` span holds, in
order, ``engine.schedule`` (expiry, admission, ``engine.draft``, growth,
segment planning), ``engine.pack`` (the step's input arrays),
``engine.dispatch`` (inputs sent and the jitted step enqueued; args
``step``, ``width``, ``prefill_tokens``, ``decode_tokens``),
``engine.device_wait`` (until the logits are ready),
``engine.logits_to_host`` (the copy; arg ``bytes``) and
``engine.commit`` (picks, cursor advance, cache insert, finishing).
The flat policy takes all six; the monolithic policy the three around
its device calls.  With no profiler session running a
span costs about a microsecond.

**Component instants**: ``cow`` (copy-on-write page split),
``prefix_hit`` / ``prefix_evict`` (prefix cache), ``spec_rollback``
(rejected speculative pages truncated), ``drafter_error``,
``fault:<kind>`` (injected by :mod:`repro.serving.faults`).

Streaming metrics
=================

A :class:`~repro.obs.metrics.MetricsRegistry` of counters, gauges, and
fixed-bucket geometric histograms (p50/p95/p99 without retaining
samples): TTFT, ITL, queue wait, e2e latency, and the per-step
wall/host/call/draft breakdown.  Reset semantics are explicit and
documented in :mod:`repro.obs.metrics` — drain-scoped metrics reset
only via ``Engine.telemetry(reset=True)``; lifetime metrics never.
``Engine.telemetry()`` is the one unified view: components' classic
``stats()`` dicts + the registry snapshot + headline percentiles.

Performance attribution
=======================

:mod:`repro.obs.attrib` grounds the measured numbers in the paper's
predictability story.  ``Engine.warmup()`` (telemetry on) builds a
:class:`~repro.obs.attrib.StepCostModel` — one roofline-priced
:class:`~repro.obs.attrib.FamilyCost` per compiled shape family on the
engine's ladder, from abstract ``lower().compile()`` + XLA cost
analysis plus an explicit KV-page-gather traffic term — and freezes it
(the warmup-only contract: the per-step hot path only ever does dict
lookups).  Each measured step is tagged with its family label(s) and
its wall split into ``sched + call + draft + host`` — complete by
construction, the components sum back to the wall (asserted in
``tests/test_attrib.py``).  Drain roll-ups report MFU/MBU, padding
waste (padded-minus-real grid positions priced at the family's
roofline per-token cost), predicted-vs-measured per family, achieved-
vs roofline-tokens/s, and goodput (tokens emitted inside
``deadline_s``, surfaced via ``Engine.stats()["slo"]``).

Anomaly monitors
================

:mod:`repro.obs.monitors` runs five host-side online detectors once per
step — ``step-outlier`` (per-family call time vs rolling median),
``preempt-storm``, ``prefix-churn``, ``queue-growth``, and ``slo-burn``
(TTFT/ITL target violation rate) — emitting typed
:class:`~repro.obs.monitors.Alert`\\ s that land in
``Engine.telemetry()["alerts"]``, the ``alerts_emitted`` counter, and
the ``monitor`` trace track.  One alert per excursion (re-arm on
clearing), bounded retention.

Trace file format
=================

Chrome ``trace_event`` JSON (the ``{"traceEvents": [...]}`` flavour),
loadable in Perfetto or ``chrome://tracing``; microsecond timestamps
relative to recorder birth.  One track (thread) per serving slot plus
``engine`` / ``scheduler`` / ``pool`` tracks; ``"X"`` complete spans
for prefill chunks and decode runs; ``"b"``/``"e"``
async spans for (overlapping) queue waits keyed by rid; ``"i"``
instants for the transition events above; ``"C"`` counters for pool
occupancy and scheduler load.  Details in :mod:`repro.obs.trace`.
"""

from repro.obs.attrib import (FamilyCost, StepCostModel, build_cost_model,
                              summarize)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.monitors import Alert, Monitors
from repro.obs.telemetry import NULL, NullTelemetry, Telemetry
from repro.obs.trace import TraceRecorder

__all__ = [
    "Alert", "Counter", "FamilyCost", "Gauge", "Histogram",
    "MetricsRegistry", "Monitors", "NULL", "NullTelemetry",
    "StepCostModel", "Telemetry", "TraceRecorder", "build_cost_model",
    "summarize",
]
