"""Grouped-query attention with packed-layout projections.

The QKV/O *weight* matmuls run through the packed-layout pipeline (the
paper's scope); the score/context matmuls (`QKᵀ`, `PV`) are
activation-by-activation contractions left to native XLA einsum — the same
boundary the paper draws (DESIGN.md §4).

Supports: GQA/MQA/MHA, qk-norm (qwen3), QKV bias (qwen2/chatglm), partial 2d
RoPE (chatglm), bidirectional (whisper encoder), cross-attention (whisper
decoder), KV-cache decode with a sequence-shardable cache.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.linear import MatmulContext, linear_init, linear_apply
from repro.models.common import Stream, apply_rope, maybe_unpack, norm_apply, norm_init

Array = jnp.ndarray

__all__ = ["attn_init", "attn_apply", "init_kv_cache", "init_paged_kv_cache",
           "KVPageLayout", "core_attention", "paged_kv_update",
           "flat_paged_kv_update"]


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32, *, cross: bool = False) -> dict:
    d, dh = cfg.d_model, cfg.d_head
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    bias = cfg.attn_bias
    p = {
        "wq": linear_init(ks[0], d, hq * dh, bias=bias, dtype=dtype),
        "wk": linear_init(ks[1], d, hkv * dh, bias=bias, dtype=dtype),
        "wv": linear_init(ks[2], d, hkv * dh, bias=bias, dtype=dtype),
        "wo": linear_init(ks[3], hq * dh, d, dtype=dtype,
                          scale=(hq * dh) ** -0.5 / max(1, cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init("rmsnorm", dh, dtype)
        p["k_norm"] = norm_init("rmsnorm", dh, dtype)
    del cross
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    shp = (batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


class KVPageLayout(NamedTuple):
    """Where each token's K (or V) lives inside one page of the paged pool.

    A page is head-major and lane-dense: ``[Hkv, T/f, f*dh]`` with
    ``f = gcd(128 // dh, T)`` consecutive tokens of one kv head side by side
    in a row of at most 128 lanes, so token ``t`` sits at row ``t // f``,
    lanes ``(t % f)*dh ..``.  At ``dh >= 128`` (``f = 1``) a page is simply
    ``[Hkv, T, dh]``.  A minor dim of 128 lanes is one the chip keeps
    row-major, which is what the ragged-attention kernel reads; a minor dim
    of 64 (``[T, Hkv, 64]``) makes the compiler re-tile the pool around
    every kernel call.  The pool is ``[G, P, *page_shape]``: page axis 1,
    which copy-on-write and page sharding index.  The scatters, the kernel,
    its oracle and the analyses all address pages through this class."""
    n_kv_heads: int
    d_head: int
    page_tokens: int

    @classmethod
    def of_pool(cls, pages, d_head: int) -> "KVPageLayout":
        """The layout of a pool (or page) array ``[..., Hkv, T/f, f*dh]``."""
        hkv, rows, lanes = pages.shape[-3:]
        return cls(hkv, d_head, rows * lanes // d_head)

    @property
    def fold(self) -> int:
        """``f``: tokens of one kv head side by side in a page row."""
        dh = self.d_head
        if dh % 128 == 0:
            return 1
        if 128 % dh:
            raise ValueError(f"d_head {dh} neither divides nor fills 128 "
                             f"lanes")
        return math.gcd(128 // dh, self.page_tokens)

    @property
    def page_shape(self) -> tuple:
        f = self.fold
        return (self.n_kv_heads, self.page_tokens // f, f * self.d_head)

    def tokens(self, pages):
        """``[..., Hkv, T/f, f*dh]`` pages -> ``[..., T, Hkv, dh]`` tokens."""
        hkv, rows, _ = pages.shape[-3:]
        lead = pages.shape[:-3]
        x = pages.reshape(*lead, hkv, rows, self.fold, self.d_head)
        x = jnp.moveaxis(x, -4, -2)                 # [..., rows, f, Hkv, dh]
        return x.reshape(*lead, rows * self.fold, hkv, self.d_head)

    def scatter(self, pool, layer, page, off, x):
        """Write the token stream ``x`` [N, Hkv, dh] at ``pool[layer,
        page[n], :, token off[n]]`` of a ``[G, P, *page_shape]`` pool, in
        place (page 0, the trash page, may take any number of writes).

        Each (token, head) writes its whole row of ``f*dh`` lanes: the
        row's other tokens come from this stream where it holds them and
        from the pool otherwise, so tokens sharing a row write the same
        value.  A narrower window makes the chip's compiler scatter one
        window at a time in a loop, and a window across heads (strided in
        a head-major page) makes it re-tile the pool around the kernel."""
        f, dh, hkv = self.fold, self.d_head, self.n_kv_heads
        n = x.shape[0]
        row = off // f
        x = x.astype(pool.dtype)
        if f > 1:
            want = row[:, None] * f + jnp.arange(f)             # [N, f]
            hit = ((page[None, None, :] == page[:, None, None])
                   & (off[None, None, :] == want[:, :, None]))   # [N, f, N]
            new = jnp.moveaxis(x[jnp.argmax(hit, axis=-1)], 1, 2)
            heads = jnp.arange(hkv)[None, :]
            old = pool[layer, page[:, None], heads, row[:, None]]
            old = old.reshape(n, hkv, f, dh)
            x = jnp.where(jnp.any(hit, axis=-1)[:, None, :, None], new, old)
        at = lambda a: jnp.broadcast_to(a[:, None], (n, hkv))  # noqa: E731
        idx = jnp.stack([jnp.full((n, hkv), layer), at(page),
                         jnp.broadcast_to(jnp.arange(hkv), (n, hkv)),
                         at(row)], axis=-1).astype(jnp.int32)   # [N, Hkv, 4]
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(2,), inserted_window_dims=(0, 1, 2, 3),
            scatter_dims_to_operand_dims=(0, 1, 2, 3))
        return jax.lax.scatter(pool, idx, x.reshape(n, hkv, f * dh), dnums,
                               mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_tokens: int,
                        dtype) -> dict:
    """Paged pool: KV lives in ``num_pages`` pages of ``page_tokens`` tokens,
    shared by all sequences via per-request block tables (continuous
    batching), each page laid out by :class:`KVPageLayout`.  Page 0 is
    reserved as the trash page — writes for padded / inactive positions are
    routed there so they can never corrupt a live request."""
    lay = KVPageLayout(cfg.n_kv_heads, cfg.d_head, page_tokens)
    shp = (num_pages,) + lay.page_shape
    return {"k_pages": jnp.zeros(shp, dtype), "v_pages": jnp.zeros(shp, dtype)}


def paged_kv_update(cache: dict, k: Array, v: Array, *, layer: Array,
                    block_tables: Array, lens: Array, new_counts: Array):
    """Scatter this step's K/V into layer ``layer`` of the page pool, gather
    each row's logical KV stream back out.

    cache: {"k_pages","v_pages"} [G, P, *page_shape] — every layer's pool
    (page 0 = trash; :class:`KVPageLayout`), updated in place.
    k, v: [B, S, Hkv, dh] new keys/values; row b's token s sits at logical
    position ``lens[b] + s`` and is valid iff ``s < new_counts[b]`` (prefill
    rows are padded up to a layout-aligned bucket; invalid writes go to the
    trash page).  Rows are fully ragged: one fused step may mix decode rows
    (``new_counts == 1``), chunked-prefill rows (a ``chunk``-token slice of
    a prompt at ``lens[b] = cursor``), and inert rows (``new_counts == 0``)
    — the engine's single fixed-shape step under a token budget, and the
    verify-step shape for speculative decode.
    block_tables: [B, MP] page ids per row, in logical order.
    Returns (new_cache, k_all [B, MP*T, Hkv, dh], v_all, kv_len_mask [B, MP*T]).

    The gathered stream is masked to ``lens + new_counts`` positions, and
    ``core_attention``'s per-row 2-D ``q_pos`` gives causality *within* the
    freshly-written chunk against the paged past — query ``lens[b]+s``
    sees kv positions ``<= lens[b]+s`` only, so chunked prefill logits are
    bitwise those of a monolithic prefill at the same positions.
    """
    kp, vp = cache["k_pages"], cache["v_pages"]
    b, s, hkv, dh = k.shape
    lay = KVPageLayout.of_pool(kp, dh)
    t = lay.page_tokens
    pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)        # [B,S]
    valid = jnp.arange(s)[None, :] < new_counts[:, None]
    slot = jnp.minimum(pos // t, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, slot, axis=1)
    page = jnp.where(valid, page, 0).reshape(-1)
    off = jnp.where(valid, pos % t, 0).reshape(-1)
    kp = lay.scatter(kp, layer, page, off, k.reshape(b * s, hkv, dh))
    vp = lay.scatter(vp, layer, page, off, v.reshape(b * s, hkv, dh))
    k_all = lay.tokens(kp[layer, block_tables]).reshape(b, -1, hkv, dh)
    v_all = lay.tokens(vp[layer, block_tables]).reshape(b, -1, hkv, dh)
    mask = jnp.arange(k_all.shape[1])[None, :] < (lens + new_counts)[:, None]
    return {"k_pages": kp, "v_pages": vp}, k_all, v_all, mask


def flat_paged_kv_update(cache: dict, k: Array, v: Array, *, layer: Array,
                         block_tables: Array, row_ids: Array, q_pos: Array):
    """Scatter one flat ``[1, W]`` token stream's K/V into layer ``layer``
    of the page pool.

    Flat-segment layout contract (the token-level analogue of
    :func:`paged_kv_update`'s row contract): position ``i`` of the stream
    belongs to engine row ``row_ids[i]`` (``-1`` = padding, routed to the
    trash page) and sits at absolute position ``q_pos[i]`` of that row, so
    its page is ``block_tables[row_ids[i], q_pos[i] // T]`` at offset
    ``q_pos[i] % T``.  Rows are fully ragged: one step may interleave
    decode segments (1+k positions), chunked-prefill segments, and padding
    up to the ``m_r``-aligned width W.

    cache: {"k_pages","v_pages"} [G, P, *page_shape], updated in place;
    k, v: [1, W, Hkv, dh]; block_tables: [B, MP]; row_ids, q_pos: [W].
    Returns the new cache — the gather side lives in the ragged-attention
    op, which reads each query's own page stream of layer ``layer``
    (kernels/ragged_attn)."""
    kp, vp = cache["k_pages"], cache["v_pages"]
    lay = KVPageLayout.of_pool(kp, k.shape[-1])
    t = lay.page_tokens
    valid = row_ids >= 0
    row = jnp.maximum(row_ids, 0)
    slot = jnp.minimum(q_pos // t, block_tables.shape[1] - 1)
    page = jnp.where(valid, block_tables[row, slot], 0)
    off = jnp.where(valid, q_pos % t, 0)
    kp = lay.scatter(kp, layer, page, off, k[0])
    vp = lay.scatter(vp, layer, page, off, v[0])
    return {"k_pages": kp, "v_pages": vp}


def core_attention(q: Array, k: Array, v: Array, *, causal: bool,
                   q_pos: Array, kv_len_mask: Optional[Array] = None) -> Array:
    """q: [B,Sq,Hq,dh]; k,v: [B,Skv,Hkv,dh].  fp32 softmax; GQA grouping.

    ``q_pos``: [Sq] (shared across batch — train/prefill) or [B,Sq] (decode)
    absolute query positions for the causal mask against kv positions
    0..Skv-1.  Keeping the shared-position case 2-D matters: a
    batch-independent additive mask stays [Sq,Skv] and is fused/hoisted
    cheaply, instead of materializing a [B,h,g,Sq,Skv] predicate in the
    layer-scan carry (§Perf iteration 1).
    ``kv_len_mask``: [B,Skv] optional validity mask (decode: cache slots
    beyond the current position are invalid).
    """
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(skv)
    neg = jnp.float32(-1e30)
    if causal:
        if q_pos.ndim == 1:  # additive 2-D mask, batch-independent
            bias = jnp.where(q_pos[:, None] >= kv_pos[None, :], 0.0, neg)
            scores = scores + bias[None, None, None, :, :]
        else:
            m = q_pos[:, None, None, :, None] >= kv_pos[None, None, None, None, :]
            scores = jnp.where(m, scores, neg)
    if kv_len_mask is not None:
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      q_pos: Array, chunk: int = 512) -> Array:
    """Memory-linear attention: scan over query chunks (scores are
    [B,h,g,chunk,Skv] instead of [B,h,g,Sq,Skv]), each chunk rematerialized
    on the backward pass.  O(chunk*Skv) live score memory — what makes the
    32k prefill and 4k train cells fit HBM (§Perf iteration 2).  Numerics
    identical to :func:`core_attention` (same fp32 softmax)."""
    b, sq, hq, dh = q.shape
    if sq <= chunk or sq % chunk != 0 or q_pos.ndim != 1:
        return core_attention(q, k, v, causal=causal, q_pos=q_pos)
    n = sq // chunk
    qs = q.reshape(b, n, chunk, hq, dh).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(n, chunk)

    @jax.checkpoint
    def one(args):
        q_c, p_c = args
        return core_attention(q_c, k, v, causal=causal, q_pos=p_c)

    out = jax.lax.map(one, (qs, ps))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dh)


def attn_apply(params: dict, x: Stream, ctx: MatmulContext, cfg: ModelConfig, *,
               positions: Array, causal: bool = True,
               kv_cache: Optional[dict] = None, cache_pos: Optional[Array] = None,
               kv_source: Optional[Array] = None,
               keep_packed: bool = False, paged: Optional[dict] = None):
    """Returns (out_stream, new_kv_cache).

    Modes:
      - train/prefill: ``kv_cache=None`` — full-sequence attention.
      - decode: ``kv_cache`` given, ``cache_pos`` scalar — writes the new
        K/V at ``cache_pos`` then attends over the cache.
      - paged decode/prefill (continuous batching): ``paged`` given —
        ``kv_cache`` is every layer's page pool [G, P, ...] and ``paged``
        carries {layer, block_tables [B,MP], lens [B], new_counts [B]}
        (``layer`` indexes the pool's G axis); every row sits at
        its own position (``positions`` is [B,S]), K/V are scattered into
        the row's pages and attention reads the gathered page stream.
      - flat paged (token-level batching): ``paged`` carries
        {layer, block_tables [B,MP], row_ids [W], q_pos [W]} and x is one
        ``[1, W]`` stream — per-position scatter, then the segment-masked
        ragged-attention op (kernels/ragged_attn) gathers each query's own
        row.
      - cross-attention: ``kv_source`` [B,S_enc,D] — K/V from the encoder
        output (positions/causality ignored; no cache mutation here, whisper
        cross K/V are precomputed per request by the serving engine).
    """
    dh = cfg.d_head
    hq, hkv = cfg.n_heads, cfg.n_kv_heads

    q = maybe_unpack(linear_apply(params["wq"], x, ctx, tp="col"))
    kv_in = x if kv_source is None else kv_source
    kv_tp = "col" if cfg.n_kv_heads >= ctx.tp_size else None
    k = maybe_unpack(linear_apply(params["wk"], kv_in, ctx, tp=kv_tp))
    v = maybe_unpack(linear_apply(params["wv"], kv_in, ctx, tp=kv_tp))

    b, sq = q.shape[0], q.shape[1]
    skv = k.shape[1]
    mdl = ctx.tp_axis
    q = ctx.constrain(q.reshape(b, sq, hq, dh), (None, mdl, None))
    k = k.reshape(b, skv, hkv, dh)
    v = v.reshape(b, skv, hkv, dh)
    if kv_tp == "col":
        k = ctx.constrain(k, (None, mdl, None))
        v = ctx.constrain(v, (None, mdl, None))

    if cfg.qk_norm:
        q = norm_apply(params["q_norm"], q, "rmsnorm")
        k = norm_apply(params["k_norm"], k, "rmsnorm")

    if cfg.rope != "none" and kv_source is None:
        pct = cfg.rope_pct if cfg.rope == "partial2d" else 1.0
        q, k = apply_rope(q, k, positions, theta=cfg.rope_theta, pct=pct)

    new_cache = kv_cache
    kv_len_mask = None
    if paged is not None and "row_ids" in paged:
        from repro.kernels.ragged_attn import ragged_attention
        with jax.named_scope("kv_update"):
            new_cache = flat_paged_kv_update(
                kv_cache, k, v, layer=paged["layer"],
                block_tables=paged["block_tables"],
                row_ids=paged["row_ids"], q_pos=paged["q_pos"])
        out = ragged_attention(
            q[0], new_cache["k_pages"], new_cache["v_pages"],
            layer=paged["layer"], block_tables=paged["block_tables"],
            row_ids=paged["row_ids"], q_pos=paged["q_pos"])[None]
        out = ctx.constrain(out, (None, mdl, None)).reshape(b, sq, hq * dh)
        out = linear_apply(params["wo"], out, ctx, keep_packed=keep_packed,
                           tp="row")
        return out, new_cache
    if paged is not None:
        with jax.named_scope("kv_update"):
            new_cache, k, v, kv_len_mask = paged_kv_update(
                kv_cache, k, v, layer=paged["layer"],
                block_tables=paged["block_tables"],
                lens=paged["lens"], new_counts=paged["new_counts"])
    elif kv_cache is not None:
        # decode: insert this step's K/V at cache_pos, attend over the cache
        kc = jax.lax.dynamic_update_slice(kv_cache["k"], k.astype(kv_cache["k"].dtype),
                                          (0, cache_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(kv_cache["v"], v.astype(kv_cache["v"].dtype),
                                          (0, cache_pos, 0, 0))
        new_cache = {"k": kc, "v": vc}
        k, v = kc, vc
        kv_len_mask = (jnp.arange(k.shape[1]) < cache_pos + sq)[None, :]
        kv_len_mask = jnp.broadcast_to(kv_len_mask, (b, k.shape[1]))

    # positions stay 1-D when shared across the batch (train/prefill):
    # the causal mask then stays 2-D instead of [B,h,g,Sq,Skv] (§Perf it. 1)
    if kv_cache is None and kv_source is None and sq > 512:
        out = chunked_attention(q, k, v, causal=causal, q_pos=positions)
    else:
        out = core_attention(q, k, v, causal=causal and kv_source is None,
                             q_pos=positions, kv_len_mask=kv_len_mask)
    out = ctx.constrain(out, (None, mdl, None)).reshape(b, sq, hq * dh)
    out = linear_apply(params["wo"], out, ctx, keep_packed=keep_packed, tp="row")
    return out, new_cache
