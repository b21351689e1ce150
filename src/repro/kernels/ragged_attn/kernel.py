"""Pallas TPU kernel for segment-masked ragged paged attention.

One program per flat query position: program ``i`` folds query ``i``'s
live pages (``q_pos[i] // T + 1`` of them, none where ``row_ids[i] < 0``)
of layer ``layer`` into an online-softmax accumulator, a block of ``K``
pages at a time, applies the segment causal mask ``kv_pos <= q_pos[i]``,
then normalises and writes the output row.  A block wholly past the
query's last live page costs nothing, no copy and no compute, and in the
last live block only the live pages are copied.

The stacked pools stay in HBM (``memory_space=ANY``) where the layer scan
keeps them and updates them in place; the program copies page
``block_tables[row, p]`` of layer ``layer`` into a VMEM buffer
``[2, Hkv, K, T/f, f*dh]`` for K and one for V with its own DMAs.  A
BlockSpec over the pool would need one grid step per page slot, live or
not.  While a block is folded the copies of the next live block are in
flight in the other half of the buffer: the same query's next block, or
the first block of query ``i + 1`` (a query whose first block nobody
started starts it itself).  So up to ``K`` pages of K and V are in flight
instead of one.

``K`` (:func:`block_pages`) comes from the shapes alone: the largest power
of two with ``K * T`` at most 128 tokens whose four page buffers (K and V,
two halves) fit a fixed VMEM budget, and no more than the block table's
``MP``.  At 16-token pages both benchmark models get ``K = 8``.

Pages arrive as :class:`~repro.models.attention.KVPageLayout` lays them
out, ``[Hkv, T/f, f*dh]``: row ``r``'s lane slice ``j`` holds token
``r*f + j``.  Each page lands head-major in its block's slot, so a block
reads as ``[Hkv, K*T/f, f*dh]`` with row ``r`` holding tokens
``b*K*T + r*f + j`` and is folded whole: the body scores each of the
``f`` lane slices against the query and accumulates ``PV`` per slice.
Slots of a block past the query's last live page keep the pages they
held before (zeros at first) and are masked out.

The caller splits the head axis into ``[Hkv, g]`` before the call, so
each query block is ``(1, Hkv, g, dh)`` with its last two dims whole.
Splitting it inside the kernel is a shape cast Mosaic refuses whenever
``g * dh`` does not fall on the dtype's sublane tiling (bf16 at
``dh=64``: smollm2-135m's 9/3/64, whisper-small's 12/12/64).

The numpy-level oracle is :mod:`repro.kernels.ragged_attn.ref`; this
kernel is flash-style (online softmax) so it matches the oracle to
tolerance, not bitwise — the serving engine dispatches to the oracle off
TPU (see ops.py), where bitwise identity with the dense step is the
contract under test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.attention import KVPageLayout

__all__ = ["ragged_attention_kernel_call", "block_pages"]

# a block of pages holds about this many tokens
BLOCK_TOKENS = 128
# VMEM for the page buffers: K and V, two halves each
BLOCK_VMEM_BYTES = 4 << 20


def block_pages(page_bytes: int, page_tokens: int, max_pages: int) -> int:
    """``K``, the pages one block copies: the largest power of two with
    ``K * page_tokens <= BLOCK_TOKENS`` and ``4 * K * page_bytes <=
    BLOCK_VMEM_BYTES`` (at least 1), and at most ``max_pages``."""
    k = 1
    while (2 * k * page_tokens <= BLOCK_TOKENS
           and 8 * k * page_bytes <= BLOCK_VMEM_BYTES):
        k *= 2
    return min(k, max_pages)


def _kernel(row_ids_ref, q_pos_ref, bt_ref, layer_ref, q_ref, k_hbm, v_hbm,
            out_ref, k_buf, v_buf, sems, slot_ref, started_ref, m_ref, l_ref,
            acc_ref, *, t: int, f: int, dh: int, kb: int):
    i = pl.program_id(0)
    w = pl.num_programs(0)
    mp = bt_ref.shape[1]
    layer = layer_ref[0]

    def live_pages(pos):
        n = jnp.minimum(q_pos_ref[pos] // t + 1, mp)
        return jnp.where(row_ids_ref[pos] < 0, 0, n)

    def dma(pos, blk, slot, wait=False):
        """Start (or wait for) the K and V copies of block ``blk`` of query
        ``pos``'s live pages into buffer half ``slot``."""
        row = jnp.maximum(row_ids_ref[pos], 0)

        def one(j, carry):
            page = bt_ref[row, blk * kb + j]
            for n, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                c = pltpu.make_async_copy(src.at[layer, page],
                                          buf.at[slot, :, j],
                                          sems.at[n, slot])
                if wait:
                    c.wait()
                else:
                    c.start()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(live_pages(pos) - blk * kb, kb),
                          one, 0)

    @pl.when(i == 0)
    def _first():
        slot_ref[0] = 0
        started_ref[0] = -1          # the query whose first block is in flight
        # a block's slots past the query's last live page keep what they
        # held; masked out, that must be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    n_live = live_pages(i)
    qp = q_pos_ref[i]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((n_live > 0) & (started_ref[0] != i))
    def _own_first():
        dma(i, 0, slot_ref[0])

    # Mosaic's default f32 matmul is one bf16 pass (~1e-2 error); f32
    # inputs ask for full f32 precision, bf16 inputs are exact in one pass
    # except for the probabilities, rounded to bf16 as in flash attention
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    q = q_ref[0].astype(jnp.float32)                        # [Hkv, g, dh]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kb * t // f), 2)

    def fold(b, k, v):
        """Fold block ``b`` (K and V ``[Hkv, K*T/f, f*dh]``, f32): row ``r``
        lane slice ``j`` holds kv position ``b*K*T + r*f + j``."""
        s, live = [], []
        for j in range(f):
            s_j = jnp.einsum("hgd,hrd->hgr", q, k[:, :, j * dh:(j + 1) * dh],
                             precision=prec,
                             preferred_element_type=jnp.float32) * dh ** -0.5
            live.append(b * kb * t + row * f + j <= qp)
            s.append(jnp.where(live[j], s_j, -jnp.inf))     # [Hkv, g, K*T/f]
        m_prev = m_ref[...]
        m_new = m_prev
        for s_j in s:
            m_new = jnp.maximum(m_new, jnp.max(s_j, axis=-1))
        alpha = jnp.where(jnp.isfinite(m_new),
                          jnp.exp(m_prev - m_new), jnp.zeros_like(m_new))
        l_new = l_ref[...] * alpha
        acc = acc_ref[...] * alpha[..., None]
        for j, s_j in enumerate(s):
            e = jnp.exp(s_j - m_new[..., None])
            e = jnp.where(live[j], e, jnp.zeros_like(e))
            l_new = l_new + jnp.sum(e, axis=-1)
            acc = acc + jnp.einsum("hgr,hrd->hgd", e,
                                   v[:, :, j * dh:(j + 1) * dh],
                                   precision=prec,
                                   preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    def block(b, carry):
        slot = slot_ref[0]
        last = b * kb + kb >= n_live

        @pl.when(jnp.logical_not(last))
        def _next_block():
            dma(i, b + 1, 1 - slot)

        nxt = jnp.minimum(i + 1, w - 1)

        @pl.when(last & (i + 1 < w) & (live_pages(nxt) > 0))
        def _next_query():
            dma(nxt, 0, 1 - slot)
            started_ref[0] = i + 1

        dma(i, b, slot, wait=True)
        hkv, _, rows, lanes = k_buf.shape[1:]
        fold(b, k_buf[slot].astype(jnp.float32).reshape(hkv, kb * rows, lanes),
             v_buf[slot].astype(jnp.float32).reshape(hkv, kb * rows, lanes))
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_live, kb), block, 0)
    l = jnp.maximum(l_ref[...], jnp.float32(1e-30))
    out_ref[0] = (acc_ref[...] / l[..., None]).astype(out_ref.dtype)


def ragged_attention_kernel_call(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray, *, layer: jnp.ndarray,
                                 block_tables: jnp.ndarray,
                                 row_ids: jnp.ndarray, q_pos: jnp.ndarray,
                                 interpret: bool = False) -> jnp.ndarray:
    """q: [W, Hq, dh]; pages: [G, P, *page_shape] (``KVPageLayout``), read
    at layer ``layer``; block_tables: [B, MP]; row_ids/q_pos: [W].
    Returns [W, Hq, dh]."""
    w, hq, dh = q.shape
    lay = KVPageLayout.of_pool(k_pages, dh)
    hkv = lay.n_kv_heads
    g = hq // hkv
    mp = block_tables.shape[1]
    page_bytes = math.prod(lay.page_shape) * k_pages.dtype.itemsize
    kb = block_pages(page_bytes, lay.page_tokens, mp)
    # head-major blocks: a page's [T/f, f*dh] per head lands at slot j
    buf = pltpu.VMEM((2, hkv, kb) + lay.page_shape[1:], k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, dh), lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            buf,                                      # K pages, two halves
            buf,                                      # V pages, two halves
            pltpu.SemaphoreType.DMA((2, 2)),          # [K/V, half]
            pltpu.SMEM((1,), jnp.int32),              # half of the next block
            pltpu.SMEM((1,), jnp.int32),              # query started ahead
            pltpu.VMEM((hkv, g), jnp.float32),        # running max
            pltpu.VMEM((hkv, g), jnp.float32),        # running denominator
            pltpu.VMEM((hkv, g, dh), jnp.float32),    # unnormalised context
        ],
    )
    kernel = functools.partial(_kernel, t=lay.page_tokens, f=lay.fold, dh=dh,
                               kb=kb)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, hkv, g, dh), q.dtype),
        # the copies run ahead across programs: one core walks the grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_attn",
    )(row_ids.astype(jnp.int32), q_pos.astype(jnp.int32), block_tables,
      jnp.reshape(layer, (1,)).astype(jnp.int32), q.reshape(w, hkv, g, dh),
      k_pages, v_pages)
    return out.reshape(w, hq, dh)
