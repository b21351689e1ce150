"""Ragged-arrival serving example: continuous batching over a paged KV cache
with pre-packed weights (the paper's amortized standalone packing, §4.1).

Requests with mixed prompt lengths and budgets arrive over time; the engine
admits each into a free decode slot as soon as one opens (no lock-step
batch), allocates KV pages *lazily* as sequences grow (tile-aligned to the
active packed layout), and retires each request the step it completes.  With
``--pool-pages`` set below the working set, the scheduler preempts the
youngest request on exhaustion and transparently recomputes it — outputs
are unchanged (try it: results are identical either way).  With
``--chunk-tokens`` (pure-attention models), prefill fuses into the decode
step under a per-step token budget: long admissions are spread across
steps instead of stalling running decodes, again without changing a single
token.  With ``--spec-tokens k``, a prompt-lookup n-gram drafter rides up
to k guesses per decode row through the same fused step and the engine
accepts the prefix the target model agrees with — once more without
changing a single token, greedy or sampled (the acceptance rule replays
the engine's own deterministic picks).  With ``--prefix-cache``, every
request shares one system prompt and the layout-keyed prefix cache serves
the shared pages byte-for-byte: later arrivals prefill only their own
suffix, preemptions release pages into the cache instead of recomputing,
and — once more — not a single token changes.  With ``--queue-limit``,
admission control bounds the wait queue: arrivals past the limit are shed
as typed ``rejected`` rows instead of waiting forever.  With
``--deadline``, each request carries an SLO measured on the trace clock;
requests that overrun finish as ``timeout`` with their pages released.
``Engine.stats()`` counters (step wall time, slot occupancy, prefill
stalls, chunks per prompt, acceptance rate, draft overhead, hit rate, CoW
copies, compile counts, plus the resilience block — sheds, timeouts,
cancels, quarantines, watchdog trips) are printed at the end.

With ``--trace-out PATH`` / ``--metrics-json PATH`` the drain runs with
the :mod:`repro.obs` telemetry subsystem live: the former writes a
Chrome ``trace_event`` JSON (open in Perfetto or ``chrome://tracing`` —
one track per slot plus scheduler/pool), the latter dumps the
full streaming-metrics registry snapshot; either flag also prints a
compact TTFT/ITL percentile table at the end of the drain.  Telemetry
is host-side only — the tokens are identical with it on or off.

Run:  PYTHONPATH=src python examples/serve_decode.py --arch smollm2-135m
Fused:                     ... serve_decode.py --chunk-tokens 16
Speculative:               ... serve_decode.py --spec-tokens 3
Prompt caching:            ... serve_decode.py --prefix-cache
Overload:                  ... serve_decode.py --queue-limit 2 --deadline 8
Telemetry:                 ... serve_decode.py --trace-out /tmp/serve.json
"""

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import RunConfig, ShapeSpec, get_config, reduced_config
from repro.models.model import build_model
from repro.serving.engine import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm2-135m")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool size in pages (default: ample); small "
                    "values exercise preemption-by-recomputation")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="fuse prefill into the decode step in chunks of "
                    "this many tokens (pure-attention models; rounded up "
                    "to the layout m_r)")
    ap.add_argument("--spec-tokens", type=int, default=None,
                    help="speculative decode with an n-gram (prompt-lookup) "
                    "drafter proposing up to this many tokens per decode "
                    "row (pure-attention models; outputs are unchanged — "
                    "accepted drafts only save steps)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share KV pages across requests via the layout-"
                    "keyed prefix cache (pure-attention models); the trace "
                    "prepends a common system prompt so later arrivals hit "
                    "the cache — outputs are unchanged, prefill work drops")
    ap.add_argument("--sys-tokens", type=int, default=32,
                    help="shared system-prompt length for --prefix-cache")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the wait queue: arrivals past this depth "
                    "are shed as typed 'rejected' rows (admission control) "
                    "instead of queueing without bound")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request SLO on the trace clock (1 tick per "
                    "step): requests still unfinished this long after "
                    "arrival finish as 'timeout' with their pages released")
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="run with telemetry on and write a Chrome "
                    "trace_event JSON (Perfetto / chrome://tracing) of "
                    "the drain: per-slot prefill/decode spans, queue "
                    "waits, preempt/pause/shed instants")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="run with telemetry on and dump the full "
                    "streaming-metrics registry snapshot (counters, "
                    "gauges, latency histograms) as JSON")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="TTFT SLO target in seconds: arms the slo-burn "
                    "monitor and defines goodput for first tokens")
    ap.add_argument("--slo-itl", type=float, default=None,
                    help="ITL SLO target in seconds (slo-burn monitor)")
    args = ap.parse_args()
    want_obs = (args.trace_out is not None or args.metrics_json is not None
                or args.slo_ttft is not None or args.slo_itl is not None)

    cfg = reduced_config(get_config(args.arch))
    shape = ShapeSpec("serve", args.max_len, args.slots, "decode")
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    remat=False)
    model = build_model(cfg, run, shape)
    params = model.init(jax.random.PRNGKey(0))

    engine = Engine(model, params, max_slots=args.slots,  # weights pre-packed
                    num_pages=args.pool_pages,
                    chunk_tokens=args.chunk_tokens,
                    spec_tokens=args.spec_tokens,
                    prefix_cache=args.prefix_cache,
                    queue_limit=args.queue_limit,
                    telemetry=want_obs)
    if want_obs and engine.continuous:
        engine.obs.monitors.slo_ttft_s = args.slo_ttft
        engine.obs.monitors.slo_itl_s = args.slo_itl
        # warmup builds the per-family roofline cost model the
        # attribution summary prices padding waste / MFU / MBU against
        engine.warmup()
    rng = np.random.default_rng(1)
    key = jax.random.PRNGKey(1)

    if not engine.continuous:  # encdec/vlm: static-batch path
        batch = {"tokens": jax.random.randint(
            key, (args.slots, args.max_prompt), 0, cfg.vocab)}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                key, (args.slots, args.max_len // cfg.audio_downsample,
                      cfg.d_model))
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                key, (args.slots, cfg.vision_tokens, cfg.d_model))
        out = engine.generate_static(batch, args.new_tokens,
                                     greedy=not args.sample)
        print(f"[serve] {cfg.name} (static batch): generated {out.shape}")
        print(out[:, :12])
        return

    # a ragged arrival trace: request i arrives at step 2*i; with the
    # prefix cache on, everyone shares one system prompt (the cache's
    # bread-and-butter workload) ahead of their own ragged suffix
    sysp = (np.asarray(jax.random.randint(key, (args.sys_tokens,), 0,
                                          cfg.vocab))
            if args.prefix_cache else np.zeros((0,), np.int32))
    trace = []
    for i in range(args.requests):
        plen = int(rng.integers(2, args.max_prompt + 1))
        prompt = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (plen,), 0, cfg.vocab))
        trace.append((2.0 * i, np.concatenate([sysp, prompt]),
                      int(rng.integers(2, args.new_tokens + 1))))

    # feed each request in as its arrival tick passes (rather than
    # enqueueing the whole trace up-front) so --queue-limit sheds against
    # the queue the server actually has at that moment; every add happens
    # in an iteration that also steps, so shed rows are always collected
    t0 = time.perf_counter()
    pending = list(trace)
    clock, finished = 0.0, []
    while pending or engine.scheduler.has_work:
        while pending and pending[0][0] <= clock:
            arrival, prompt, max_new = pending.pop(0)
            engine.add_request(prompt, max_new, arrival=arrival,
                               deadline_s=args.deadline)
        finished += engine.step(now=clock, greedy=not args.sample)
        clock += 1.0
    dt = time.perf_counter() - t0

    total = sum(len(r.out_tokens) for r in finished)
    st = engine.pool.stats()
    es = engine.stats()
    mode = (f"fused chunk={engine.chunk_tokens}" if engine.chunked
            else "monolithic prefill")
    if engine.spec_tokens is not None:
        mode += f" + spec k={engine.spec_tokens}"
    if engine.prefix_cache is not None:
        mode += " + prefix cache"
    print(f"[serve] {cfg.name}: {len(finished)} ragged requests ({mode}), "
          f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s on CPU host; "
          f"page={st['page_tokens']} tok — m_r-aligned; "
          f"peak {st['peak_used']}/{st['num_pages'] - 1} pages, "
          f"{engine.num_preemptions} preemptions, "
          f"{engine.num_pauses} prefill pauses)")
    print(f"[serve] stats: {es['steps']} steps @ {es['mean_step_ms']:.2f} ms, "
          f"occupancy {es['mean_slot_occupancy']:.2f}, "
          f"{es['mixed_steps']} mixed steps, "
          f"{es['prefill_stall_steps']} prefill-stall steps, "
          f"{es['chunks_per_prompt']:.2f} chunks/prompt, "
          f"compiles {es['compiles']}")
    if "prefix_cache" in es:
        pc = es["prefix_cache"]
        print(f"[serve] prefix cache: hit rate {pc['hit_rate']:.2f} "
              f"({pc['hits']}/{pc['lookups']} lookups, "
              f"{pc['hit_tokens']} prompt tokens served from cache), "
              f"prefill computed {es['prefill_tokens']} tokens, "
              f"{pc['entries']} cached pages "
              f"({pc['shared_pages']} currently shared), "
              f"{pc['cow_copies']} CoW copies, {pc['evictions']} evictions")
    res = es["resilience"]
    print(f"[serve] resilience: {res['sheds']} shed "
          f"(queue_limit={res['queue_limit']}), {res['timeouts']} timeouts "
          f"(deadline={args.deadline}), {res['cancels']} cancels, "
          f"{res['quarantines']} quarantined, "
          f"{res['drafter_errors']} drafter errors "
          f"({res['spec_auto_disables']} spec auto-disables), "
          f"{res['watchdog_trips']} watchdog trips")
    if "speculative" in es:
        sp = es["speculative"]
        print(f"[serve] speculation: accepted {sp['accepted']}/{sp['drafted']} "
              f"drafts ({sp['acceptance_rate']:.2f}), "
              f"{sp['decode_tokens_per_row_step']:.2f} decode tokens/row-step, "
              f"{sp['accepted_per_step']:.2f} accepted/step, "
              f"draft overhead {sp['draft_overhead']:.2f}, "
              f"{sp['rollback_pages']} pages rolled back "
              f"({sp['drafter']})")
    if want_obs:
        tel = engine.telemetry()
        slo = es["slo"]
        print(f"[serve] slo: goodput {slo['goodput_tokens']}/"
              f"{slo['tokens_out']} tokens inside deadline "
              f"({slo['goodput_ratio']:.2f}), p99s: "
              f"ttft {slo['ttft_p99_s']:.4f}s, itl {slo['itl_p99_s']:.4f}s, "
              f"e2e {slo['e2e_p99_s']:.4f}s")
        at = tel["attribution"]
        if "mfu" in at:
            t = at["totals"]
            print(f"[serve] attribution: wall {t['wall_s']:.3f}s = "
                  f"sched {t['sched_s']:.3f} + call {t['call_s']:.3f} "
                  f"+ draft {t['draft_s']:.3f} + host {t['host_s']:.3f}; "
                  f"mfu {at['mfu']:.2e}, mbu {at['mbu']:.2e}, "
                  f"padding waste {at['padding_waste_ratio']:.2f} of "
                  f"call, roofline fraction {at['roofline_fraction']:.3f}")
        if tel["alerts"]:
            print(f"[serve] alerts ({len(tel['alerts'])}):")
            for a in tel["alerts"]:
                print(f"  [{a['severity']}] {a['kind']} @ step {a['step']}: "
                      f"{a['message']}")
        print("[serve] latency percentiles (s):")
        print(f"  {'':<14}{'count':>6}{'p50':>10}{'p95':>10}{'p99':>10}"
              f"{'max':>10}")
        for name, snap in tel["latency"].items():
            print(f"  {name:<14}{snap['count']:>6}{snap['p50']:>10.4f}"
                  f"{snap['p95']:>10.4f}{snap['p99']:>10.4f}"
                  f"{snap['max']:>10.4f}")
        if args.metrics_json:
            with open(args.metrics_json, "w") as f:
                json.dump(tel["metrics"], f, indent=2, sort_keys=True)
            print(f"[serve] metrics snapshot -> {args.metrics_json}")
        if args.trace_out:
            engine.obs.export_trace(args.trace_out)
            n = len(engine.obs.tracer.events())
            print(f"[serve] Perfetto trace ({n} events) -> {args.trace_out}")
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"  rid={r.rid} arrive@{r.arrival:>4.0f} prompt={r.prompt_len:>3} "
              f"-> {len(r.out_tokens):>2} tokens ({r.finish_reason}): "
              f"{r.out_tokens[:10]}")


if __name__ == "__main__":
    main()
