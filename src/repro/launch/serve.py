"""Serving launcher: continuous-batching generation with packed weights.

Requests arrive with ragged prompt lengths and per-request token budgets;
the engine admits them into decode slots over a paged KV cache and streams
per-request completions (``--static`` runs the old lock-step batch loop for
comparison).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch smollm2-135m \
        --reduced --dtype float32 --requests 8 --slots 4 --new 32
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import RunConfig, get_config, reduced_config
from repro.configs.base import ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serving.engine import Engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm2-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max prompt length (lengths are mixed up to this)")
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool size in pages (default: ample); undersized "
                    "pools are served via preemption-by-recomputation")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill through the flat token-level step "
                    "(and the ragged-attention kernel on TPU); default: "
                    "monolithic prefill")
    ap.add_argument("--eager", action="store_true",
                    help="reserve each request's full KV lifetime at "
                    "admission (the pre-lazy baseline policy)")
    ap.add_argument("--policy", default="scalable")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="bfloat16",
                    help="param and compute dtype")
    ap.add_argument("--static", action="store_true",
                    help="static-batch baseline (one shared prompt length)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    shape = ShapeSpec("serve", args.max_len, args.slots, "decode")
    run = RunConfig(layout_policy=args.policy, param_dtype=args.dtype,
                    compute_dtype=args.dtype, remat=False)
    model = build_model(cfg, run, shape)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = Engine(model, params, max_slots=args.slots,
                    page_tokens=args.page_tokens, num_pages=args.pool_pages,
                    eager=args.eager, chunk_tokens=args.chunk_tokens)

    key = jax.random.PRNGKey(args.seed + 1)
    if args.static or not engine.continuous:
        batch = {"tokens": jax.random.randint(
            key, (args.slots, args.prompt_len), 0, cfg.vocab)}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                key, (args.slots, args.max_len // cfg.audio_downsample,
                      cfg.d_model))
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                key, (args.slots, cfg.vision_tokens, cfg.d_model))
        out = engine.generate_static(batch, args.new)
        print(f"[serve] {cfg.name} (static): generated {out.shape} tokens")
        print(out[:, :16])
        return out

    rng = np.random.default_rng(args.seed + 2)
    for i in range(args.requests):
        plen = int(rng.integers(2, args.prompt_len + 1))
        prompt = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (plen,), 0, cfg.vocab))
        engine.add_request(prompt, int(rng.integers(1, args.new + 1)))
    finished = engine.drain()
    total = sum(len(r.out_tokens) for r in finished)
    print(f"[serve] {cfg.name}: {len(finished)} requests, {total} tokens "
          f"(paged KV: {engine.pool.page_tokens} tok/page, "
          f"{engine.pool.num_pages} pages, peak {engine.pool.peak_used} "
          f"used, {engine.num_preemptions} preemptions)")
    for r in sorted(finished, key=lambda r: r.rid)[:8]:
        print(f"  rid={r.rid} prompt={r.prompt_len:>3} "
              f"new={len(r.out_tokens):>3} [{r.finish_reason}] "
              f"{r.out_tokens[:8]}")
    return finished


if __name__ == "__main__":
    main()
