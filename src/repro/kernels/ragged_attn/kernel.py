"""Pallas TPU kernel for segment-masked ragged paged attention.

One grid step per (flat query position, page): program ``(i, p)`` loads
query ``i``'s row page ``p`` straight from the pool via scalar-prefetched
``block_tables[row_ids[i], p]`` (PrefetchScalarGridSpec — the page id is
known before the body runs, so the K/V block DMA is index-driven, the
paged-attention pattern), applies the segment causal mask
``p*T + t <= q_pos[i]``, and folds the page into an online-softmax
accumulator.  The last page normalises and writes the output row.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_attention_kernel_call"]


def _kernel(row_ids_ref, q_pos_ref, bt_ref, q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref, *, t: int, dh: int):
    i = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)
    qp = q_pos_ref[i]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Mosaic's default f32 matmul is one bf16 pass (~1e-2 error); f32
    # inputs ask for full f32 precision, bf16 inputs are exact in one pass
    # except for the probabilities, rounded to bf16 as in flash attention
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    @pl.when(p * t <= qp)  # pages fully past the query hold nothing visible
    def _fold():
        q = q_ref[0].astype(jnp.float32)                    # [Hkv, g, dh]
        k = k_ref[0].astype(jnp.float32)                    # [T, Hkv, dh]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.einsum("hgd,thd->hgt", q, k, precision=prec,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        kv_pos = p * t + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
        s = jnp.where(kv_pos <= qp, s, -jnp.inf)            # [Hkv, g, T]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.where(jnp.isfinite(m_new),
                          jnp.exp(m_prev - m_new), jnp.zeros_like(m_new))
        e = jnp.exp(s - m_new[..., None])
        e = jnp.where(kv_pos <= qp, e, jnp.zeros_like(e))
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(e, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jnp.einsum("hgt,thd->hgd", e, v, precision=prec,
                                     preferred_element_type=jnp.float32))

    @pl.when(p == np_ - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        out = acc_ref[...] / l[..., None]
        out_ref[0] = out.astype(out_ref.dtype)


def ragged_attention_kernel_call(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray, *,
                                 block_tables: jnp.ndarray,
                                 row_ids: jnp.ndarray, q_pos: jnp.ndarray,
                                 interpret: bool = False) -> jnp.ndarray:
    """q: [W, Hq, dh]; pages: [P, T, Hkv, dh]; block_tables: [B, MP];
    row_ids/q_pos: [W].  Returns [W, Hq, dh]."""
    w, hq, dh = q.shape
    t, hkv = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    mp = block_tables.shape[1]
    row_ids = jnp.maximum(row_ids.astype(jnp.int32), 0)
    q_pos = q_pos.astype(jnp.int32)

    def page_map(i, p, row_ids_ref, q_pos_ref, bt_ref):
        del q_pos_ref
        return (bt_ref[row_ids_ref[i], p], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(w, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh), lambda i, p, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, t, hkv, dh), page_map),
            pl.BlockSpec((1, t, hkv, dh), page_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, dh), lambda i, p, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),        # running max
            pltpu.VMEM((hkv, g), jnp.float32),        # running denominator
            pltpu.VMEM((hkv, g, dh), jnp.float32),    # unnormalised context
        ],
    )
    kernel = functools.partial(_kernel, t=t, dh=dh)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(row_ids, q_pos, block_tables, q.reshape(w, hkv, g, dh), k_pages,
      v_pages)
    return out.reshape(w, hq, dh)
