"""The chip's peaks and the work a step needs, counted from its shapes.

Peaks of one TPU v5e chip are Google Cloud's published figures ("TPU v5e"
documentation): 197 TFLOP/s in bfloat16, 819 GB/s of HBM bandwidth, 16 GB
of HBM.  A device kind missing from the table is an error, never a default.

Work is counted from what the rows need, never from a kernel's grid, so
any implementation of the same work is read alike:

  attention   a query at position ``p`` attends ``p + 1`` keys:
              ``4 * (p + 1) * n_heads * d_head`` FLOPs per layer (QK^T and
              PV).  Bytes per layer: each row's live K/V pages once
              (``ceil(ctx / T) * T * n_kv_heads * d_head * 2`` elements for
              a row whose last query sees ``ctx`` keys), plus its queries
              read and its outputs written.
  model       ``2 * matmul parameters`` FLOPs per fed token (the Q/K/V/O
              and MLP weights of every layer), plus the attention above,
              plus ``2 * d_model * vocab`` for each token whose logits are
              read to emit a token.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/cost.py")
    return PEAKS[device_kind]


class Shape(NamedTuple):
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    glu: bool
    page_tokens: int
    dtype_bytes: int


def shape(config: dict) -> Shape:
    m, s = config["bench"]["model"], config["bench"]["serving"]
    return Shape(n_layers=m["n_layers"], d_model=m["d_model"],
                 n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                 d_head=m["d_head"], d_ff=m["d_ff"], vocab=m["vocab"],
                 glu=m.get("glu", True), page_tokens=s["page_tokens"],
                 dtype_bytes=2 if config["bench"]["dtype"] == "bfloat16"
                 else 4)


def matmul_params_per_layer(s: Shape) -> int:
    attn = s.d_model * s.d_head * (2 * s.n_heads + 2 * s.n_kv_heads)
    mlp = (3 if s.glu else 2) * s.d_model * s.d_ff
    return attn + mlp


def attention_flops(s: Shape, rows: Iterable[Tuple[int, int]]) -> float:
    """FLOPs of one layer's attention over rows of ``(first query
    position, query count)``."""
    total = 0
    for p0, n in rows:
        # sum over j < n of (p0 + j + 1) keys
        total += n * p0 + n * (n + 1) // 2
    return 4.0 * total * s.n_heads * s.d_head


def attention_bytes(s: Shape, rows: Iterable[Tuple[int, int]]) -> float:
    """Bytes one layer's attention must move over the same rows."""
    t = s.page_tokens
    kv = q = 0
    for p0, n in rows:
        kv += -(-(p0 + n) // t) * t
        q += n
    return float(s.dtype_bytes * (kv * s.n_kv_heads * s.d_head * 2
                                  + q * s.n_heads * s.d_head * 2))


def attention_least_s(s: Shape, rows, pk: dict) -> float:
    """Least time of one step's attention kernel calls, over all layers."""
    rows = list(rows)
    f = attention_flops(s, rows) / pk["flops_bf16"]
    b = attention_bytes(s, rows) / pk["hbm_bytes_per_s"]
    return s.n_layers * max(f, b)


def model_flops(s: Shape, rows, emitted: int) -> float:
    """FLOPs one step needs for its real tokens."""
    rows = list(rows)
    fed = sum(n for _, n in rows)
    return (2.0 * fed * s.n_layers * matmul_params_per_layer(s)
            + s.n_layers * attention_flops(s, rows)
            + 2.0 * emitted * s.d_model * s.vocab)
