"""Dispatch wrapper for segment-masked ragged paged attention.

On TPU the compiled Pallas kernel always runs: asking for the oracle there
is an error, so a chip run can never silently measure it.  Off TPU the jnp
oracle runs by default — the serving engine's bitwise flat-vs-dense
identity contract is verified against it — and ``use_kernel=True,
interpret=True`` runs the kernel body in the Pallas interpreter instead.
The dispatch happens at trace time (``jax.default_backend()``): the caller
(models/attention.py) is already inside the engine's jit.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ragged_attn.ref import ragged_attention_ref

__all__ = ["ragged_attention", "ragged_attention_reference"]


def ragged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                     v_pages: jnp.ndarray, *, block_tables: jnp.ndarray,
                     row_ids: jnp.ndarray, q_pos: jnp.ndarray,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """q: [W, Hq, dh] flat queries; k_pages/v_pages: [P, T, Hkv, dh] pool;
    block_tables: [B, MP]; row_ids: [W] (-1 = pad); q_pos: [W].
    Returns [W, Hq, dh]."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if on_tpu and not use_kernel:
        raise ValueError("ragged_attention: on TPU the kernel runs; call "
                         "ragged_attention_reference for the oracle")
    if use_kernel:
        from repro.kernels.ragged_attn.kernel import ragged_attention_kernel_call
        return ragged_attention_kernel_call(
            q, k_pages, v_pages, block_tables=block_tables,
            row_ids=row_ids, q_pos=q_pos, interpret=interpret)
    return ragged_attention_ref(q, k_pages, v_pages,
                                block_tables=block_tables,
                                row_ids=row_ids, q_pos=q_pos)


def ragged_attention_reference(q, k_pages, v_pages, *, block_tables,
                               row_ids, q_pos):
    return ragged_attention_ref(q, k_pages, v_pages,
                                block_tables=block_tables,
                                row_ids=row_ids, q_pos=q_pos)
