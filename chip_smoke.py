#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

Serves smollm2-135m at its published widths in bf16 (random weights from a
seed) through ``build_model`` -> ``Engine`` -> ``add_request``/``drain``,
the calls ``repro.launch.serve`` makes, with the flat token-level step and
its Pallas ragged-attention kernel.  Phases, each raising on failure:

  device   platform, device_kind and count; a non-TPU platform is an error
           unless ``--reduced`` is given
  kernel   the compiled kernel vs ``ragged_attention_reference`` at the
           model's head geometry, bf16 and f32, on a decode row, a
           mid-prefill chunk, a fresh prefill and padding
  serve    warmup, then 8 seeded requests drained: zero post-warmup traces,
           every request finishes ``length``, the pool's free pages return
           to where they started, ``tpu_custom_call`` in the flat step
  cross    last-position logits of the flat step (kernel) vs the
           monolithic step (XLA attention) for the first prompts

The last line of stdout is one JSON object naming the device.

    python chip_smoke.py                               # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

``--reduced`` serves the reduced config and runs the kernel in the Pallas
interpreter (``use_kernel=True, interpret=True``).  The script starts no
process and sets neither ``JAX_PLATFORMS`` nor ``XLA_FLAGS``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import RunConfig, get_config, reduced_config  # noqa: E402
from repro.configs.base import ShapeSpec  # noqa: E402
from repro.kernels.ragged_attn import (ragged_attention,  # noqa: E402
                                       ragged_attention_reference)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.kv_cache import fresh_slot_states, prefill_view  # noqa: E402

SLOTS, MAX_LEN, PAGE_TOKENS, CHUNK_TOKENS = 4, 1024, 16, 64
REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (17, 300), (16, 32)
CROSS_PROMPTS = 3

# Kernel vs oracle (oracle at "highest" matmul precision, so it is exact f32).
# f32: the two differ only in summation order (online vs one-pass softmax),
# ~1e-6; 1e-4 is far below the ~1e-2 a single bf16 matmul pass would leave.
# bf16: both round the f32 result to bf16 once; one bf16 ulp at |x| < 4 is
# 2^-6 ~= 0.016, so 0.03 admits a one-ulp flip and nothing larger.
KERNEL_TOL = {"float32": 1e-4, "bfloat16": 0.03}
# Flat (kernel) vs monolithic (XLA attention) logits in bf16, as a share of
# the largest reference logit: the two attention outputs differ by bf16
# rounding flips (~2^-8 relative), which 30 layers of bf16 residual stream
# grow to a few percent; a wrong mask or page would be of order one.
CROSS_TOL = 0.05


def _segment_mix(hq, hkv, dh, dtype, seed):
    """Kernel inputs: a decode row at position 17, a 5-token chunk at 8..12,
    a fresh 4-token prefill and padding, over a shuffled 16-token-page
    pool."""
    t, pages, mp, w = PAGE_TOKENS, 9, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (w, hq, dh)).astype(dtype)
    k_pages = jax.random.normal(ks[1], (pages, t, hkv, dh)).astype(dtype)
    v_pages = jax.random.normal(ks[2], (pages, t, hkv, dh)).astype(dtype)
    bt = jax.random.permutation(ks[3], pages)[: 3 * mp].reshape(3, mp)
    row_ids = np.full(w, -1, np.int32)
    q_pos = np.zeros(w, np.int32)
    row_ids[0], q_pos[0] = 0, 17
    row_ids[1:6], q_pos[1:6] = 1, np.arange(8, 13)
    row_ids[6:10], q_pos[6:10] = 2, np.arange(4)
    args = dict(block_tables=bt.astype(jnp.int32),
                row_ids=jnp.asarray(row_ids), q_pos=jnp.asarray(q_pos))
    return q, k_pages, v_pages, args, row_ids >= 0


def check_kernel(cfg, on_tpu, seed):
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    for dtype in ("bfloat16", "float32"):
        q, k, v, args, live = _segment_mix(hq, hkv, dh, jnp.dtype(dtype), seed)
        out = ragged_attention(q, k, v, use_kernel=True,
                               interpret=not on_tpu, **args)
        with jax.default_matmul_precision("highest"):
            ref = ragged_attention_reference(q, k, v, **args)
        err = float(np.max(np.abs(np.asarray(out, np.float32)[live]
                                  - np.asarray(ref, np.float32)[live])))
        print(f"[kernel] {hq}/{hkv}/{dh} {dtype}: max abs error vs reference "
              f"{err!r} (tolerance {KERNEL_TOL[dtype]})", flush=True)
        if not err <= KERNEL_TOL[dtype]:
            raise AssertionError(f"kernel error {err} above "
                                 f"{KERNEL_TOL[dtype]} in {dtype}")


def make_requests(vocab, seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(rng.integers(PROMPT_LENS[0],
                                                     PROMPT_LENS[1] + 1)),
                          dtype=np.int32),
             int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for _ in range(REQUESTS)]


def serve(engine, requests, on_tpu):
    model = engine.model
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    traces = dict(model.trace_counts)
    free = engine.pool.num_free
    t0 = time.perf_counter()
    for prompt, max_new in requests:
        engine.add_request(prompt, max_new)
    finished = engine.drain()
    drain_s = time.perf_counter() - t0
    served = sum(len(r.out_tokens) for r in finished)
    print(f"[serve] compile seconds {compile_s!r}", flush=True)
    print(f"[serve] drain seconds {drain_s!r}", flush=True)
    print(f"[serve] tokens served {served}", flush=True)
    mem = jax.devices()[0].memory_stats()
    print(f"[serve] peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use') if mem else 'not reported'}",
          flush=True)
    new_traces = {k: model.trace_counts[k] - traces[k] for k in traces}
    print(f"[serve] post-warmup traces {new_traces}", flush=True)
    if any(new_traces.values()):
        raise AssertionError(f"drain traced after warmup: {new_traces}")
    reasons = sorted((r.rid, r.finish_reason, len(r.out_tokens), r.max_new)
                     for r in finished)
    print(f"[serve] finished (rid, reason, tokens, max_new) {reasons}",
          flush=True)
    if len(finished) != len(requests) or any(
            why != "length" or n != m for _, why, n, m in reasons):
        raise AssertionError("not every request finished 'length'")
    print(f"[serve] free pages {engine.pool.num_free} (before {free})",
          flush=True)
    if engine.pool.num_free != free:
        raise AssertionError("the pool's free pages did not return")

    w = engine._flat_shapes()[0]
    hlo = model.jit_step("flat").lower(
        engine.params, engine.caches, jnp.zeros((1, w), jnp.int32),
        jnp.zeros((engine.slots, engine.max_pages), jnp.int32),
        jnp.full((w,), -1, jnp.int32), jnp.zeros((w,), jnp.int32),
        jnp.zeros((engine.slots,), jnp.int32)).as_text()
    has_kernel = "tpu_custom_call" in hlo
    print(f"[serve] tpu_custom_call in flat step: {has_kernel}", flush=True)
    if on_tpu and not has_kernel:
        raise AssertionError("the flat step does not hold the kernel")


def cross_check(engine, requests):
    """Last-position logits of one prompt fed as a flat segment vs as a
    monolithic prefill, each into fresh pages 1..MP."""
    model, params = engine.model, engine.params
    w, mp = engine._flat_shapes()[0], engine.max_pages
    flat_step, paged_step = model.jit_step("flat"), model.jit_step("paged")
    fresh = lambda: model.init_paged_cache(  # noqa: E731
        engine.pool.num_pages, engine.pool.page_tokens, engine.slots)
    worst, agree = 0.0, []
    for prompt, _ in requests[:CROSS_PROMPTS]:
        prompt = prompt[:w]
        n = len(prompt)
        token = np.zeros((1, w), np.int32)
        token[0, :n] = prompt
        row_ids = np.where(np.arange(w) < n, 0, -1).astype(np.int32)
        q_pos = np.where(np.arange(w) < n, np.arange(w), 0).astype(np.int32)
        bt = np.zeros((engine.slots, mp), np.int32)
        bt[0] = 1 + np.arange(mp)
        idx = np.full((engine.slots,), n - 1, np.int32)
        flat, _ = flat_step(params, fresh(), jnp.asarray(token),
                            jnp.asarray(bt), jnp.asarray(row_ids),
                            jnp.asarray(q_pos), jnp.asarray(idx))
        caches = fresh()
        mono, _ = paged_step(params, prefill_view(caches,
                                                  fresh_slot_states(caches)),
                             jnp.asarray(token), jnp.asarray(bt[:1]),
                             jnp.zeros((1,), jnp.int32),
                             jnp.full((1,), n, jnp.int32), None)
        a = np.asarray(flat, np.float32)[0, 0]
        b = np.asarray(mono, np.float32)[0, 0]
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        lo, hi = np.sort(b)[-2:]
        worst = max(worst, diff / scale)
        agree.append(bool(np.argmax(a) == np.argmax(b)))
        # random weights leave near-ties: greedy agreement is expected only
        # where the reference's top-2 gap exceeds the diff
        print(f"[cross] prompt {n} tokens: max abs logit diff {diff!r}, "
              f"largest logit {scale!r}, top-2 gap {float(hi - lo)!r}, "
              f"greedy first tokens agree {agree[-1]}", flush=True)
    print(f"[cross] worst diff / largest logit {worst!r} (tolerance "
          f"{CROSS_TOL}); greedy agreement {sum(agree)}/{len(agree)}",
          flush=True)
    if not worst <= CROSS_TOL:
        raise AssertionError(f"flat vs monolithic logits differ by {worst} "
                             f"of the largest logit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal: reduced config, kernel interpreted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[device] {device}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print("[device] no TPU found; pass --reduced for the CPU rehearsal",
              file=sys.stderr)
        return 1
    enable_compile_cache()

    cfg = get_config("smollm2-135m")
    if args.reduced:
        cfg = reduced_config(cfg)
    check_kernel(cfg, on_tpu, args.seed)

    run = RunConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                    remat=False)
    model = build_model(cfg, run, ShapeSpec("serve", MAX_LEN, SLOTS, "decode"))
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = Engine(model, params, max_slots=SLOTS, page_tokens=PAGE_TOKENS,
                    chunk_tokens=CHUNK_TOKENS)
    requests = make_requests(cfg.vocab, args.seed)
    serve(engine, requests, on_tpu)
    cross_check(engine, requests)

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
