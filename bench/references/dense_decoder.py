"""Plain float32 forward of a dense pre-norm decoder, the benchmark's reference.

Embedding lookup; per layer a norm (RMSNorm with gain, or OLMo's
non-parametric LayerNorm), Q/K/V projections, rotary embedding on the two
halves of each head (GPT-NeoX / Hugging Face ``rotate_half``), causal
softmax attention with ``n_heads / n_kv_heads`` query heads per key head,
the output projection and a residual add; then a norm, a SwiGLU MLP
(``silu(x Wg) * (x Wu)`` projected by ``Wd``) and a residual add; a final
norm and logits against the tied embedding.  Nothing is cached, batched
across requests or fused: each sequence is recomputed from its first
token, at ``Precision.HIGHEST``.

The weights are drawn again from the seed (:mod:`bench.weights`), one
layer at a time, so the reference never holds the whole model in float32
beside anything else.

``control=True`` computes the same forward with every weight matmul's two
operands rounded to float8 e4m3 (per output channel for weights, per row
for activations, scaled so the largest magnitude is 448), accumulating in
float32: the precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 256
_HEAD_BLOCK = 256


class Dims(NamedTuple):
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    norm: str
    eps: float
    rope_theta: float


def dims(config: dict) -> Dims:
    """The reference's sizes, read from a configuration file."""
    m = config["bench"]["model"]
    return Dims(n_layers=m["n_layers"], d_model=m["d_model"],
                n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                d_head=m["d_head"], d_ff=m["d_ff"], vocab=m["vocab"],
                norm=m["norm"], eps=config["bench"]["norm_eps"],
                rope_theta=m["rope_theta"])


def fp8_e4m3(x, axis):
    """``x`` rounded to float8 e4m3 (3 mantissa bits, largest 448, smallest
    normal 2^-6), scaled per slice along ``axis``, back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / scale
    a = jnp.abs(y)
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3)
    q = jnp.minimum(jnp.round(a / step) * step, 448.0)
    return jnp.sign(y) * q * scale


def _mm(x, w, control: bool):
    if control:
        x, w = fp8_e4m3(x, -1), fp8_e4m3(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _norm(x, d: Dims, g=None):
    if d.norm == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + d.eps)
        return y * g
    if d.norm == "layernorm_np":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + d.eps)
    raise ValueError(f"reference has no norm {d.norm!r}")


def _rope(x, theta: float):
    """x: [N, L, H, dh] at positions 0..L-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _layer(x, w, *, d: Dims, control: bool):
    n, L, _ = x.shape
    hq, hkv, dh = d.n_heads, d.n_kv_heads, d.d_head
    g = hq // hkv
    h = _norm(x, d, w.get("ln1"))
    q = _rope(_mm(h, w["wq"], control).reshape(n, L, hq, dh), d.rope_theta)
    k = _rope(_mm(h, w["wk"], control).reshape(n, L, hkv, dh), d.rope_theta)
    v = _mm(h, w["wv"], control).reshape(n, L, hkv, dh)
    q = q.reshape(n, L, hkv, g, dh)
    kv_pos = jnp.arange(L)
    outs = []
    for s in range(0, L, _Q_BLOCK):
        qb = q[:, s:s + _Q_BLOCK]
        sc = jnp.einsum("nqhgd,nkhd->nhgqk", qb, k,
                        precision=HIGHEST) * dh ** -0.5
        q_pos = s + jnp.arange(qb.shape[1])
        sc = jnp.where(kv_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("nhgqk,nkhd->nqhgd", p, v, precision=HIGHEST))
    o = jnp.concatenate(outs, 1).reshape(n, L, hq * dh)
    x = x + _mm(o, w["wo"], control)
    h = _norm(x, d, w.get("ln2"))
    m = jax.nn.silu(_mm(h, w["wg"], control)) * _mm(h, w["wu"], control)
    return x + _mm(m, w["wd"], control)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _head(h, e, tokens, *, d: Dims, control: bool):
    """Per row: the largest logit, the logit of ``tokens``, the argmax."""
    logits = _mm(h, e.T, control)
    top = jnp.argmax(logits, -1)
    at = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return jnp.max(logits, -1), at, top


_LAYER_LEAVES = {"ln1": "groups/p0/ln1/g", "wq": "groups/p0/mixer/wq/w",
                 "wk": "groups/p0/mixer/wk/w", "wv": "groups/p0/mixer/wv/w",
                 "wo": "groups/p0/mixer/wo/w", "ln2": "groups/p0/ln2/g",
                 "wg": "groups/p0/ffn/wg/w", "wu": "groups/p0/ffn/wu/w",
                 "wd": "groups/p0/ffn/wd/w"}


def _layer_weights(d: Dims, seed: int, layer: int, dtype) -> dict:
    D, hq, hkv, dh, ff = d.d_model, d.n_heads, d.n_kv_heads, d.d_head, d.d_ff
    shapes = {"ln1": (D,), "ln2": (D,), "wq": (D, hq * dh),
              "wk": (D, hkv * dh), "wv": (D, hkv * dh), "wo": (hq * dh, D),
              "wg": (D, ff), "wu": (D, ff), "wd": (ff, D)}
    if d.norm == "layernorm_np":
        del shapes["ln1"], shapes["ln2"]
    return {k: weights.reference_leaf(seed, _LAYER_LEAVES[k], s, d.n_layers,
                                      dtype, layer=layer)
            for k, s in shapes.items()}


class Rows(NamedTuple):
    """Per compared position: the reference's largest logit, its logit of
    the served token, and its logit of the token the control puts first
    (NaN without a control)."""
    ref_max: np.ndarray
    ref_served: np.ndarray
    ref_at_control: np.ndarray


def compare_rows(config: dict, seed: int, seqs: Sequence[np.ndarray],
                 rows: List[tuple], *, length: int, batch: int = 0,
                 control: bool = False) -> Rows:
    """Run the reference over ``seqs`` (token ids, each padded to
    ``length``, and padded with empty sequences to ``batch``) and read it
    at ``rows``: ``(seq index, position, served token)``, where the served
    token is the one the program emitted from the logits at that
    position."""
    d = dims(config)
    dtype = jnp.dtype(config["bench"]["dtype"])
    toks = np.zeros((max(batch, len(seqs)), length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        e = weights.reference_leaf(seed, "embed/e", (d.vocab, d.d_model),
                                   d.n_layers, dtype)
        x = jnp.take(e, jnp.asarray(toks), axis=0)
        xc = x if control else None
        for layer in range(d.n_layers):
            w = _layer_weights(d, seed, layer, dtype)
            x = _layer(x, w, d=d, control=False)
            if control:
                xc = _layer(xc, w, d=d, control=True)
            del w
        ln_f = (None if d.norm == "layernorm_np" else
                weights.reference_leaf(seed, "ln_f/g", (d.d_model,),
                                       d.n_layers, dtype))
        m = len(rows)
        mp = -(-max(m, 1) // _HEAD_BLOCK) * _HEAD_BLOCK
        sel = np.zeros((mp, 2), np.int32)
        served = np.zeros(mp, np.int32)
        sel[:m] = [(i, p) for i, p, _ in rows]
        served[:m] = [t for _, _, t in rows]
        out = {"max": [], "served": [], "ctl": []}
        for b in range(0, mp, _HEAD_BLOCK):
            i, p = sel[b:b + _HEAD_BLOCK, 0], sel[b:b + _HEAD_BLOCK, 1]
            h = _norm(x[i, p], d, ln_f)
            mx, at, _ = _head(h, e, jnp.asarray(served[b:b + _HEAD_BLOCK]),
                              d=d, control=False)
            out["max"].append(np.asarray(mx))
            out["served"].append(np.asarray(at))
            if control:
                _, _, top = _head(_norm(xc[i, p], d, ln_f), e, jnp.asarray(
                    served[b:b + _HEAD_BLOCK]), d=d, control=True)
                out["ctl"].append(np.asarray(
                    _head(h, e, top, d=d, control=False)[1]))
    cat = {k: np.concatenate(v)[:m] if v else np.full(m, np.nan, np.float32)
           for k, v in out.items()}
    return Rows(cat["max"], cat["served"], cat["ctl"])


def widest_gap(ref_max: np.ndarray, ref_at: np.ndarray) -> float:
    """The widest gap by which a chosen token's reference logit lies below
    the reference's largest logit at the same position."""
    return float(np.max(ref_max - ref_at)) if len(ref_max) else float("nan")
