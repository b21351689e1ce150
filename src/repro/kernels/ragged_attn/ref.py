"""Pure-jnp oracle for the segment-masked ragged paged-attention kernel.

Flat token-level batching (vLLM/Sarathi-style): queries arrive as one
``[W, Hq, dh]`` stream where position ``i`` belongs to engine row
``row_ids[i]`` and sits at absolute sequence position ``q_pos[i]`` of that
row.  Each query gathers its own row's page stream from the pool and
attends causally within its segment (``kv_pos <= q_pos[i]``) — the
segment-aware causal mask that makes one fixed ``[1, W]`` shape serve any
mix of decode / chunked-prefill / speculative-verify rows.

Numerics mirror :func:`repro.models.attention.core_attention` exactly
(fp32 scores and softmax, same contraction order, same ``-1e30`` masking)
so the flat step stays bitwise identical to the dense ``[slots, chunk]``
step on the same tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import KVPageLayout

__all__ = ["ragged_attention_ref", "flat_write_destinations"]


def flat_write_destinations(block_tables: np.ndarray, row_ids: np.ndarray,
                            q_pos: np.ndarray, page_tokens: int):
    """Host-side mirror of the flat scatter's addressing rule
    (:func:`repro.models.attention.flat_paged_kv_update`): position ``i``
    of the stream writes page ``block_tables[row_ids[i], q_pos[i] // T]``
    at token offset ``q_pos[i] % T`` (of the step's layer; ``KVPageLayout``
    places the offset in the page); ``row_ids[i] < 0`` routes to trash
    page 0.
    Returns ``(pages, offsets, valid)``, each ``[W]``.

    This is the write-side half of the contract the oracle above reads
    back, kept beside it so the two can't drift — the runtime sanitizer
    (``analysis.sanitize``) recomputes every step's destinations through
    this function and asserts each written page is private (``ref == 1``)
    and inside the pool."""
    bt = np.asarray(block_tables)
    row_ids = np.asarray(row_ids)
    q_pos = np.asarray(q_pos)
    valid = row_ids >= 0
    row = np.maximum(row_ids, 0)
    slot = np.minimum(q_pos // page_tokens, bt.shape[1] - 1)
    pages = np.where(valid, bt[row, slot], 0)
    offsets = np.where(valid, q_pos % page_tokens, 0)
    return pages, offsets, valid


def ragged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                         v_pages: jnp.ndarray, *, layer: jnp.ndarray,
                         block_tables: jnp.ndarray, row_ids: jnp.ndarray,
                         q_pos: jnp.ndarray) -> jnp.ndarray:
    """q: [W, Hq, dh]; k_pages/v_pages: [G, P, *page_shape] pools of every
    layer (:class:`~repro.models.attention.KVPageLayout`, page 0 = trash),
    read at layer ``layer``; block_tables: [B, MP]; row_ids: [W] int32
    (-1 = padding — clamped to row 0, output garbage, caller discards);
    q_pos: [W] absolute positions.  Returns [W, Hq, dh] in q.dtype."""
    w, hq, dh = q.shape
    lay = KVPageLayout.of_pool(k_pages, dh)
    hkv = lay.n_kv_heads
    g = hq // hkv
    bt = block_tables[jnp.maximum(row_ids, 0)]                 # [W, MP]
    k_all = lay.tokens(k_pages[layer, bt]).reshape(w, -1, hkv, dh)
    v_all = lay.tokens(v_pages[layer, bt]).reshape(w, -1, hkv, dh)
    qg = q.reshape(w, hkv, g, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("qhgd,qkhd->qhgk", qg.astype(jnp.float32),
                        k_all.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(k_all.shape[1])
    neg = jnp.float32(-1e30)
    m = kv_pos[None, :] <= q_pos[:, None]                      # [W, MP*T]
    scores = jnp.where(m[:, None, None, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("qhgk,qkhd->qhgd", probs, v_all.astype(jnp.float32))
    return out.reshape(w, hq, dh).astype(q.dtype)
