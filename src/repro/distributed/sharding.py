"""Sharding rules: DP / TP / EP / FSDP / sequence-sharded KV.

Tile-aligned discipline for packed tensors (DESIGN.md §5): packed weights
``w_pack [N_o, K_o, n_r, k_r]`` are sharded on **outer tile dims only**
(``N_o`` over model, ``K_o`` over data) so no collective ever splits a
hardware tile — the distributed extension of the paper's layout contract.
Unpacked weights shard on the corresponding logical dims; GSPMD padding
handles non-divisible extents (e.g. 28 heads on 16-way TP).

The rule engine maps parameter *paths* to PartitionSpecs:
  - column-parallel (wq/wk/wv/wu/wg, embed, lm_head): out-dim over "model",
    in-dim over "data" when FSDP;
  - row-parallel (wo/wd): in-dim over "model";
  - expert stacks [E, in, out]: E over "model" (expert parallelism), in-dim
    over "data" when FSDP;
  - everything small (norms, biases, scalars): replicated.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.launch.mesh import dp_axes

__all__ = ["param_specs", "batch_specs", "cache_specs", "state_specs",
           "named", "tree_paths"]


def tree_paths(tree) -> dict:
    """Flatten a pytree into {'a/b/c': leaf}."""
    out = {}

    def rec(prefix, t):
        if isinstance(t, dict):
            for k in sorted(t):
                rec(f"{prefix}/{k}" if prefix else str(k), t[k])
        else:
            out[prefix] = t

    rec("", tree)
    return out


_COL = re.compile(r"(wq|wk|wv|wu|wg|wr|in_proj|x_proj|frontend_proj|"
                  r"vision_proj|lm_head)/w$")
_ROW = re.compile(r"(wo|wd|out_proj|wv)/w$")  # wv matched by _COL first
_EMBED = re.compile(r"embed/e$")


def _spec_for_param(path: str, leaf, run: RunConfig, fsdp_axis) -> P:
    nd = getattr(leaf, "ndim", 0)
    if nd < 2:
        return P()
    # scan-stacked layer groups carry a leading [G] dim: never sharded
    # (it is the lax.scan axis), so rules apply to the remaining dims.
    stacked = "groups/" in path or path.startswith("groups")
    lead: tuple = (None,) if stacked else ()
    nd_eff = nd - len(lead)

    def spec(*rest):
        return P(*lead, *rest)

    if path.endswith("/b") or nd_eff < 2:
        return spec(*(None,) * nd_eff)  # biases / vectors: replicated
    if re.search(r"(wu|wg|wd)/w$", path) and nd_eff == 3:
        # expert stack [E, d_in, d_out]: EP over model + FSDP over data
        return spec("model", fsdp_axis, None)
    if _EMBED.search(path):
        # vocab over data(FSDP) only: GSPMD mis-partitions the token gather
        # against a model-sharded feature dim (SPMD dynamic-slice verifier
        # failure, olmo/chatglm shapes); table is small per-chip, and the
        # tied logits head re-shards compute-side (tp="col").
        return P(fsdp_axis, None)
    if re.search(r"(wo|wd|out_proj)/w$", path):
        return spec("model", fsdp_axis)
    if _COL.search(path):
        return spec(fsdp_axis, "model")
    if re.search(r"router/w$", path):
        return spec(fsdp_axis, None)
    if re.search(r"(pe_enc|pe_dec)$", path):
        return P(None, "model")
    if re.search(r"(w_a|w_b|a_log|conv_w|mu(/.*)?|u|dt_proj/w)$", path):
        return spec(*(None,) * nd_eff)  # small mixer params: replicated
    if nd_eff == 2:
        return spec(fsdp_axis, "model")  # default 2-D weight: col + FSDP
    return spec(*(None,) * nd_eff)


def _sanitize(spec: P, leaf, mesh) -> P:
    """jit argument shardings must divide exactly: drop mesh axes from dims
    they don't divide (e.g. whisper vocab 51865 on a 16-way axis)."""
    shape = getattr(leaf, "shape", ())
    if len(spec) > len(shape):
        return P(*(None,) * len(shape))
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(ax if shape[i] % size == 0 else None)
    out += [None] * (len(shape) - len(spec))
    return P(*out)


def param_specs(params, run: RunConfig, mesh) -> dict:
    """PartitionSpec pytree matching ``params``."""
    fsdp_axis = "data" if run.fsdp and "data" in mesh.axis_names else None
    flat = tree_paths(params)
    specs = {p: _sanitize(_spec_for_param(p, l, run, fsdp_axis), l, mesh)
             for p, l in flat.items()}
    return _unflatten_like(params, specs)


def _unflatten_like(tree, flat_specs: dict, prefix=""):
    if isinstance(tree, dict):
        return {k: _unflatten_like(v, flat_specs,
                                   f"{prefix}/{k}" if prefix else str(k))
                for k, v in tree.items()}
    return flat_specs[prefix]


def batch_specs(batch_like, mesh) -> dict:
    """Inputs: leading batch dim over the DP axes (pod x data)."""
    dp = dp_axes(mesh)

    def spec(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd == 0:
            return P()
        b = leaf.shape[0]
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if b % dp_size == 0 and b >= dp_size:
            return P(dp, *(None,) * (nd - 1))
        return P(*(None,) * nd)

    return jax.tree.map(spec, batch_like)


def cache_specs(caches, mesh, run: RunConfig, global_batch: int) -> dict:
    """Decode caches: batch over DP when divisible; KV sequence over "model"
    (distributed flash-decode); batch=1 long-context shards the sequence
    over everything available."""
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_ok = global_batch % dp_size == 0 and global_batch >= dp_size
    bspec = dp if batch_ok else None
    seq_axes = ("model",) if batch_ok else tuple([*dp, "model"])

    flat = tree_paths(caches)

    def spec(path, leaf):
        nd = leaf.ndim
        if path.endswith(("/k_pages", "/v_pages")):
            # paged pool [G, P, Hkv, T/f, f*dh] (models/attention.py
            # KVPageLayout): the page dim is the (chunked) sequence dim —
            # shard it over "model" (distributed flash-decode over page
            # shards); a head's page tile [T/f, f*dh] is never split, the
            # distributed extension of the layout contract.
            lead = (None,) * (nd - 4)
            if run.seq_shard_kv:
                return P(*lead, "model", None, None, None)
            return P(*lead, None, "model", None, None)
        if path.endswith("/k") or path.endswith("/v"):
            # [G, B, S, Hkv, dh] (stacked) or [B, S, Hkv, dh]
            lead = (None,) * (nd - 4)
            if run.seq_shard_kv:
                return P(*lead, bspec, seq_axes, None, None)
            return P(*lead, bspec, None, "model", None)
        if path.endswith("ssm"):          # [G, B, di, N]
            return P(*(None,) * (nd - 3), bspec, "model", None)
        if path.endswith("conv"):         # [G, B, W, di]
            return P(*(None,) * (nd - 3), bspec, None, "model")
        if path.endswith("state"):        # [G, B, H, dh, dh]
            return P(*(None,) * (nd - 4), bspec, "model", None, None)
        if path.endswith(("tm_shift", "cm_shift")):  # [G, B, D]
            return P(*(None,) * (nd - 2), bspec, "model")
        return P(*(None,) * nd)

    specs = {p: _sanitize(spec(p, l), l, mesh) for p, l in flat.items()}
    return _unflatten_like(caches, specs)


def state_specs(state_like, run: RunConfig, mesh):
    """TrainState sharding: params & optimizer moments follow param rules;
    8-bit moment *scales* follow their param minus the quantized last axis
    (so the quantized state stays FSDP/TP-sharded exactly like the param)."""
    from repro.training.train_state import TrainState

    p_specs = param_specs(state_like.params, run, mesh)
    is_spec = lambda x: isinstance(x, P)

    def drop_last(spec):
        return P(*tuple(spec)[:-1]) if len(tuple(spec)) else P()

    opt_specs = {}
    for k, tree in state_like.opt_state.items():
        if k in ("m", "v", "err", "m_q", "v_q"):
            opt_specs[k] = p_specs
        elif k in ("m_s", "v_s"):
            opt_specs[k] = jax.tree.map(drop_last, p_specs, is_leaf=is_spec)
        else:
            opt_specs[k] = jax.tree.map(lambda _: P(), tree)
    return TrainState(step=P(), params=p_specs, opt_state=opt_specs)


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
