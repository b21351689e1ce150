"""Decoder LM assembly: blocks, scanned layer groups, logits.

Layers are grouped into the repeating pattern period (e.g. jamba's
[mamba x4, attn, mamba x3] with MoE on every 2nd layer => period 8) and the
group stack is a single ``lax.scan`` over stacked parameters — keeping HLO
size independent of depth (94-layer MoE compiles as one group body) and
giving remat a natural checkpoint boundary.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.core.linear import MatmulContext, linear_init, linear_apply
from repro.core.propagation import PackedArray
from repro.models import attention, mamba, moe, rwkv6
from repro.models import mlp as mlp_mod
from repro.models.common import (Stream, constrain_stream, embed_apply,
                                 embed_init, maybe_pack, maybe_unpack,
                                 norm_apply, norm_init, stream_add)

Array = jnp.ndarray

__all__ = ["pattern_period", "block_init", "block_apply", "group_init",
           "layers_init", "layers_apply", "lm_init", "lm_apply", "logits_apply",
           "init_layer_caches", "init_paged_caches", "AUX_ZERO"]

AUX_ZERO = {"load_balance": jnp.float32(0), "router_z": jnp.float32(0),
            "dropped_frac": jnp.float32(0)}


def pattern_period(cfg: ModelConfig) -> int:
    p = len(cfg.block_pattern)
    if cfg.moe:
        p = math.lcm(p, cfg.moe_every)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    return p


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, pos: int, dtype, *, cross: bool = False) -> dict:
    t = cfg.layer_types[pos]
    use_moe = cfg.moe_on_layer(pos)
    ks = jax.random.split(key, 5)
    p = {"ln1": norm_init(cfg.norm, cfg.d_model, dtype)}
    if t == "attn":
        p["mixer"] = attention.attn_init(ks[0], cfg, dtype)
    elif t == "mamba":
        p["mixer"] = mamba.mamba_init(ks[0], cfg, dtype)
    elif t == "rwkv":
        p["mixer"] = rwkv6.rwkv_tm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(t)
    if cross:
        p["ln_c"] = norm_init(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attention.attn_init(ks[1], cfg, dtype, cross=True)
    p["ln2"] = norm_init(cfg.norm, cfg.d_model, dtype)
    if use_moe:
        p["ffn"] = moe.moe_init(ks[2], cfg, dtype)
    elif t == "rwkv":
        p["ffn"] = rwkv6.rwkv_cm_init(ks[2], cfg, dtype)
    else:
        p["ffn"] = mlp_mod.mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg, dtype,
                                    bias=cfg.attn_bias and cfg.family == "encdec")
    return p


def _as_stream_like(out, like: Stream, ctx: MatmulContext) -> Stream:
    if isinstance(like, PackedArray) and not isinstance(out, PackedArray):
        return maybe_pack(out, ctx)
    if not isinstance(like, PackedArray) and isinstance(out, PackedArray):
        return out.unpack()
    return out


def block_apply(p: dict, x: Stream, ctx: MatmulContext, cfg: ModelConfig, pos: int,
                *, positions: Array, causal: bool = True,
                cache: Optional[dict] = None, cache_pos: Optional[Array] = None,
                enc_out: Optional[Array] = None,
                cross_kv: Optional[dict] = None,
                paged: Optional[dict] = None) -> Tuple[Stream, Optional[dict], dict]:
    """Pre-norm residual block.  Returns (x', cache', aux)."""
    t = cfg.layer_types[pos]
    use_moe = cfg.moe_on_layer(pos)
    aux = dict(AUX_ZERO)
    keep = isinstance(x, PackedArray)

    h = norm_apply(p["ln1"], x, cfg.norm)
    new_cache: dict = {}
    if t == "attn":
        mix_cache = None if cache is None else cache.get("kv")
        with jax.named_scope("attention"):
            out, kv = attention.attn_apply(
                p["mixer"], h, ctx, cfg, positions=positions, causal=causal,
                kv_cache=mix_cache, cache_pos=cache_pos, keep_packed=keep,
                paged=paged)
        if cache is not None:
            new_cache["kv"] = kv
    elif t == "mamba":
        mix_cache = None if cache is None else cache.get("mamba")
        out, mc = mamba.mamba_apply(p["mixer"], h, ctx, cfg, cache=mix_cache)
        if cache is not None:
            new_cache["mamba"] = mc
    else:  # rwkv
        mix_cache = None if cache is None else \
            {"tm_shift": cache["tm_shift"], "state": cache["state"]}
        out, rc = rwkv6.rwkv_tm_apply(p["mixer"], h, ctx, cfg, cache=mix_cache)
        if cache is not None:
            new_cache.update(rc)
    x = stream_add(x, _as_stream_like(out, x, ctx))

    if "cross" in p:
        hc = norm_apply(p["ln_c"], x, cfg.norm)
        if cross_kv is not None:
            q = maybe_unpack(linear_apply(p["cross"]["wq"], hc, ctx))
            b, sq = q.shape[0], q.shape[1]
            q = q.reshape(b, sq, cfg.n_heads, cfg.d_head)
            if cfg.qk_norm:
                q = norm_apply(p["cross"]["q_norm"], q, "rmsnorm")
            o = attention.core_attention(
                q, cross_kv["k"], cross_kv["v"], causal=False,
                q_pos=jnp.zeros((sq,), jnp.int32))
            out = linear_apply(p["cross"]["wo"], o.reshape(b, sq, -1), ctx,
                               keep_packed=keep)
        else:
            out, _ = attention.attn_apply(
                p["cross"], hc, ctx, cfg, positions=positions, causal=False,
                kv_source=enc_out, keep_packed=keep)
        x = stream_add(x, _as_stream_like(out, x, ctx))

    h2 = norm_apply(p["ln2"], x, cfg.norm)
    if use_moe:
        out2, aux = moe.moe_apply(p["ffn"], h2, ctx, cfg)
    elif t == "rwkv":
        cm_cache = None if cache is None else {"cm_shift": cache["cm_shift"]}
        out2, cmc = rwkv6.rwkv_cm_apply(p["ffn"], h2, ctx, cfg, cache=cm_cache)
        if cache is not None:
            new_cache.update(cmc)
    else:
        with jax.named_scope("mlp"):
            out2 = mlp_mod.mlp_apply(p["ffn"], h2, ctx, cfg,
                                     keep_packed=keep)
    x = stream_add(x, _as_stream_like(out2, x, ctx))
    return x, (new_cache if cache is not None else None), aux


# ---------------------------------------------------------------------------
# layer stack as scan over pattern groups
# ---------------------------------------------------------------------------

def group_init(key, cfg: ModelConfig, dtype, *, cross: bool = False) -> dict:
    period = pattern_period(cfg)
    ks = jax.random.split(key, period)
    return {f"p{i}": block_init(ks[i], cfg, i, dtype, cross=cross)
            for i in range(period)}


def layers_init(key, cfg: ModelConfig, dtype, *, cross: bool = False) -> dict:
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    ks = jax.random.split(key, groups)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[group_init(k, cfg, dtype, cross=cross) for k in ks])
    return stacked


def init_layer_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Stacked [G, ...] decode caches, structure matching each pattern slot."""
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    one = {}
    for i in range(period):
        t = cfg.layer_types[i]
        c: dict = {}
        if t == "attn":
            c["kv"] = attention.init_kv_cache(cfg, batch, max_len, dtype)
        elif t == "mamba":
            c["mamba"] = mamba.init_mamba_cache(cfg, batch, dtype)
        else:
            c.update(rwkv6.init_rwkv_cache(cfg, batch, dtype))
        one[f"p{i}"] = c
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (groups,) + x.shape), one)


def init_paged_caches(cfg: ModelConfig, num_pages: int, page_tokens: int,
                      slots: int, dtype) -> dict:
    """Stacked [G, ...] caches for continuous-batching decode.

    Attention K/V lives in a shared paged pool indexed by per-request block
    tables ([G, P, Hkv, T/f, f*dh], pages laid out by
    :class:`attention.KVPageLayout`; page ids are shared across groups and
    pattern slots — one logical page holds a token range's KV for every
    attention layer).  Recurrent mixer state is O(1)/sequence and stays
    per-slot dense ([G, slots, ...])."""
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    one = {}
    for i in range(period):
        t = cfg.layer_types[i]
        c: dict = {}
        if t == "attn":
            c["kv"] = attention.init_paged_kv_cache(cfg, num_pages,
                                                    page_tokens, dtype)
        elif t == "mamba":
            c["mamba"] = mamba.init_mamba_cache(cfg, slots, dtype)
        else:
            c.update(rwkv6.init_rwkv_cache(cfg, slots, dtype))
        one[f"p{i}"] = c
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (groups,) + x.shape), one)


def layers_apply(params_groups: dict, x: Stream, ctx: MatmulContext,
                 cfg: ModelConfig, run: RunConfig, *, positions: Array,
                 causal: bool = True, caches: Optional[dict] = None,
                 cache_pos: Optional[Array] = None,
                 enc_out: Optional[Array] = None,
                 cross_kv: Optional[dict] = None,
                 paged: Optional[dict] = None):
    """Returns (x', new_caches, aux).

    Modes: train/prefill (``caches=None``; ``enc_out`` optionally closed over
    for cross-attention) and decode (``caches`` stacked [G, ...]; whisper
    decode additionally passes per-layer precomputed ``cross_kv``; paged
    continuous-batching decode passes ``paged`` block-table state shared by
    every group, and each group's attention reads and writes the stacked
    page pools at its own layer index).  The paged mode is fully ragged
    per row — each row's ``positions``/``new_counts`` place anywhere from
    0 to S new tokens at its own offset, which is what lets the serving
    engine fuse chunked prefill and decode into one fixed-shape step (and
    what a speculative verify step will reuse).  NOTE: only attention
    layers are inert on padded row positions (their writes land in the
    trash page and the causal mask hides them); mamba/rwkv scans carry
    state across every position, so ragged multi-token rows are
    pure-attention-only — hybrids keep exact-length monolithic prefill.
    """
    period = pattern_period(cfg)

    def apply_group(x, gp, gc, gkv, paged):
        x = constrain_stream(x, ctx)
        new_gc = {}
        aux_g = dict(AUX_ZERO)
        for i in range(period):
            x, nc, aux = block_apply(
                gp[f"p{i}"], x, ctx, cfg, i, positions=positions, causal=causal,
                cache=None if gc is None else gc[f"p{i}"], cache_pos=cache_pos,
                enc_out=enc_out,
                cross_kv=None if gkv is None else gkv[f"p{i}"],
                paged=paged)
            if gc is not None:
                new_gc[f"p{i}"] = nc
            aux_g = {k: aux_g[k] + aux[k] for k in aux_g}
        return x, (new_gc if gc is not None else None), aux_g

    if caches is None:
        def body(carry, gp):
            x, aux_acc = carry
            x, _, aux_g = apply_group(x, gp, None, None, None)
            return (x, {k: aux_acc[k] + aux_g[k] for k in aux_acc}), None
        if run.remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, dict(AUX_ZERO)), params_groups)
        return x, None, aux

    if paged is not None:
        # the page pools ride in the carry, each layer scattering into and
        # reading its own [layer] slice in place: as scanned xs/ys every
        # layer would slice its pool out and update it back into a fresh
        # stacked buffer, moving the whole pool each step
        pools, slot_states = _split_pools(caches)

        def paged_body(carry, xs_):
            x, pools = carry
            gp, gc, layer = xs_
            xo, ngc, _ = apply_group(x, gp, _join_pools(gc, pools), None,
                                     {**paged, "layer": layer})
            pools, ngc = _split_pools(ngc)
            return (xo, pools), ngc

        (x, pools), new_states = jax.lax.scan(
            paged_body, (x, pools),
            (params_groups, slot_states,
             jnp.arange(cfg.n_layers // period, dtype=jnp.int32)))
        return x, _join_pools(new_states, pools), dict(AUX_ZERO)

    xs = ((params_groups, caches) if cross_kv is None
          else (params_groups, caches, cross_kv))

    def body(x, xs_):
        gp, gc = xs_[0], xs_[1]
        gkv = xs_[2] if len(xs_) == 3 else None
        xo, ngc, _ = apply_group(x, gp, gc, gkv, None)
        return xo, ngc

    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches, dict(AUX_ZERO)


def _split_pools(caches: dict):
    """Paged caches ``{p<i>: {"kv": pool, ...}}`` -> (``{p<i>: pool}`` for
    the attention slots, the per-slot recurrent state without them)."""
    pools = {k: c["kv"] for k, c in caches.items() if "kv" in c}
    rest = {k: {n: v for n, v in c.items() if n != "kv"}
            for k, c in caches.items()}
    return pools, rest


def _join_pools(states: dict, pools: dict) -> dict:
    return {k: ({**c, "kv": pools[k]} if k in pools else c)
            for k, c in states.items()}


# ---------------------------------------------------------------------------
# full decoder LM
# ---------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig, run: RunConfig) -> dict:
    dtype = jnp.dtype(run.param_dtype)
    ks = jax.random.split(key, 4)
    p = {"embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
         "groups": layers_init(ks[1], cfg, dtype),
         "ln_f": norm_init(cfg.norm, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab, dtype=dtype,
                                   scale=cfg.d_model ** -0.5)
    if cfg.family == "vlm":
        p["vision_proj"] = linear_init(ks[3], cfg.d_model, cfg.d_model, dtype=dtype)
    return p


def logits_apply(params: dict, x: Stream, ctx: MatmulContext, cfg: ModelConfig) -> Array:
    # vocab-parallel head: logits sharded over the model axis; the fp32
    # softmax/CE over the sharded vocab dim lowers to a distributed
    # reduction under GSPMD.
    if cfg.tie_embeddings:
        w = params["embed"]["e"].T
        return maybe_unpack(linear_apply({"w": w}, x, ctx, tp="col"))
    return maybe_unpack(linear_apply(params["lm_head"], x, ctx, tp="col"))


def lm_apply(params: dict, embeds: Array, ctx: MatmulContext, cfg: ModelConfig,
             run: RunConfig, *, positions: Array, caches=None, cache_pos=None,
             last_only: bool = False, paged=None,
             logits_at: Optional[Array] = None):
    """embeds: [B, S, D] input embeddings (token and/or stub-modality).

    Returns (logits [B,S,V] (or [B,1,V] when ``last_only`` — the serving
    prefill path, which skips the full-sequence vocab projection), caches,
    aux).  ``logits_at``: [B] per-row position — emit logits for that
    position only (ragged prefill: each row's last *valid* token differs)
    — or [B, K] per-row positions, emitting [B, K, V] (the speculative
    verify step reads logits at each of a row's k draft positions from one
    fused call while the head still projects K << S positions).
    """
    x: Stream = maybe_pack(embeds, ctx)
    x, new_caches, aux = layers_apply(params["groups"], x, ctx, cfg, run,
                                      positions=positions, caches=caches,
                                      cache_pos=cache_pos, paged=paged)
    x = norm_apply(params["ln_f"], x, cfg.norm)
    if logits_at is not None:
        idx = logits_at if logits_at.ndim == 2 else logits_at[:, None]
        x = jnp.take_along_axis(maybe_unpack(x),
                                idx[:, :, None].astype(jnp.int32),
                                axis=1)
        x = maybe_pack(x, ctx)
    elif last_only:
        x = maybe_unpack(x)[:, -1:, :]
        x = maybe_pack(x, ctx)
    with jax.named_scope("lm_head"):
        logits = logits_apply(params, x, ctx, cfg)
    return logits, new_caches, aux
