"""Model facade: init / forward / loss / decode_step / input_specs per family.

This is the single interface consumed by the trainer, the serving engine,
the dry-run launcher and the benchmarks.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig, ShapeSpec
from repro.core.hardware import HardwareSpec, query
from repro.core.linear import MatmulContext, linear_apply
from repro.core.layout import LayoutPolicy
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models.common import embed_apply

Array = jnp.ndarray

__all__ = ["ReproModel", "build_model"]


_TRACE_LOG_TREE_CAP = 8   # args with more leaves are summarized as one entry


def _describe_trace_args(names, args, kwargs) -> dict:
    """Per-argument (shape, dtype, weak_type) signatures of one trace,
    keyed by ``argname`` + pytree path.  Large pytrees (params) collapse
    to one summary entry — retrace attribution needs "which argument
    changed", not five hundred weight leaves."""
    desc = {}
    items = list(zip(names, args)) + sorted(kwargs.items())
    for name, val in items:
        leaves, _ = jax.tree_util.tree_flatten_with_path(val)
        sigs = []
        for path, leaf in leaves:
            aval = getattr(leaf, "aval", None)
            if aval is not None:
                sigs.append((jax.tree_util.keystr(path), tuple(aval.shape),
                             str(aval.dtype),
                             bool(getattr(aval, "weak_type", False))))
            else:
                sigs.append((jax.tree_util.keystr(path), "static",
                             repr(type(leaf).__name__), False))
        if len(sigs) > _TRACE_LOG_TREE_CAP:
            desc[name] = (f"<pytree:{len(sigs)} leaves>",
                          f"sig_hash={hash(tuple(sigs)) & 0xffffffff:#x}",
                          False)
        else:
            for p, shp, dt, weak in sigs:
                desc[name + p] = (shp, dt, weak)
    return desc


def _xent(logits: Array, labels: Array, z_loss: float) -> Tuple[Array, dict]:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - ll)
    zl = jnp.mean(lse ** 2)
    return nll + z_loss * zl, {"nll": nll, "z_loss": zl}


class ReproModel:
    """Family-dispatched model with a uniform train/serve interface."""

    def __init__(self, cfg: ModelConfig, run: RunConfig, shape: ShapeSpec,
                 hw: Optional[HardwareSpec] = None, mesh=None):
        self.cfg = cfg
        self.run = run
        self.shape = shape
        mesh_axes = None
        dp_size = tp_size = 1
        if mesh is not None:
            mesh_axes = tuple(mesh.axis_names)
            tp_size = mesh.shape.get("model", 1)
            dp_size = 1
            for a in ("pod", "data"):
                dp_size *= mesh.shape.get(a, 1)
        self.ctx = MatmulContext(policy=LayoutPolicy(run.layout_policy),
                                 hw=hw or query(), propagate=run.propagate,
                                 mesh_axes=mesh_axes, dp_size=dp_size,
                                 tp_size=tp_size,
                                 moe_local=run.moe_local_dispatch)
        self.compute_dtype = jnp.dtype(run.compute_dtype)

    # ------------------------------------------------------------------
    # shapes
    # ------------------------------------------------------------------
    @property
    def text_len(self) -> int:
        s = self.shape.seq_len
        if self.cfg.family == "vlm":
            return s - self.cfg.vision_tokens
        return s

    @property
    def enc_len(self) -> int:
        return self.shape.seq_len // self.cfg.audio_downsample

    def input_specs(self, kind: Optional[str] = None) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        kind = kind or self.shape.kind
        b, s = self.shape.global_batch, self.shape.seq_len
        i32 = jnp.int32
        f = self.compute_dtype
        d = self.cfg.d_model
        sds = jax.ShapeDtypeStruct
        if kind in ("train", "prefill"):
            specs = {"tokens": sds((b, self.text_len), i32)}
            if kind == "train":
                specs["labels"] = sds((b, self.text_len), i32)
            if self.cfg.family == "encdec":
                specs["frames"] = sds((b, self.enc_len, d), f)
            if self.cfg.family == "vlm":
                specs["patches"] = sds((b, self.cfg.vision_tokens, d), f)
            return specs
        # decode: one new token against a seq_len cache
        caches = jax.eval_shape(lambda: self.init_cache(b, s))
        return {"caches": caches,
                "token": sds((b, 1), i32),
                "pos": sds((), i32)}

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        if self.cfg.family == "encdec":
            return encdec_mod.encdec_init(key, self.cfg, self.run,
                                          max_src=max(self.enc_len, 8),
                                          max_tgt=max(self.shape.seq_len, 8))
        return tfm.lm_init(key, self.cfg, self.run)

    # ------------------------------------------------------------------
    # forward / loss
    # ------------------------------------------------------------------
    def _embeds(self, params: dict, batch: dict) -> Array:
        from repro.models.common import constrain_stream
        x = embed_apply(params["embed"], batch["tokens"]).astype(self.compute_dtype)
        # anchor the gather output (batch over DP, features replicated):
        # without this GSPMD can demand a model-sharded feature dim from the
        # token gather and trip its own partitioner (verifier failure)
        x = constrain_stream(x, self.ctx)
        if self.cfg.family == "vlm":
            vis = linear_apply(params["vision_proj"],
                               batch["patches"].astype(self.compute_dtype), self.ctx)
            x = jnp.concatenate([vis, x], axis=1)
        return x

    def forward(self, params: dict, batch: dict,
                last_only: bool = False) -> Tuple[Array, dict]:
        """Full-sequence forward.  Returns (logits, aux).

        ``last_only``: serving prefill — emit logits for the final position
        only (skips the [B,S,vocab] projection; §Perf iteration 3).
        """
        if self.cfg.family == "encdec":
            logits = encdec_mod.encdec_forward(params, batch, self.ctx, self.cfg,
                                               self.run)
            if last_only:
                logits = logits[:, -1:]
            return logits, dict(tfm.AUX_ZERO)
        x = self._embeds(params, batch)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        logits, _, aux = tfm.lm_apply(params, x, self.ctx, self.cfg, self.run,
                                      positions=positions, last_only=last_only)
        return logits, aux

    def loss(self, params: dict, batch: dict) -> Tuple[Array, dict]:
        logits, aux = self.forward(params, batch)
        if self.cfg.family == "vlm":
            logits = logits[:, self.cfg.vision_tokens:]
        loss, metrics = _xent(logits, batch["labels"], self.run.z_loss)
        if self.cfg.moe:
            loss = (loss
                    + self.cfg.router_aux_weight * aux["load_balance"]
                    + self.cfg.router_z_weight * aux["router_z"])
            metrics.update(aux)
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        dt = self.compute_dtype
        if self.cfg.family == "encdec":
            layers = tfm.init_layer_caches(self.cfg, batch, max_len, dt)
            hkv, dh = self.cfg.n_kv_heads, self.cfg.d_head
            period = tfm.pattern_period(self.cfg)
            groups = self.cfg.n_layers // period
            enc_l = max_len // self.cfg.audio_downsample
            cross = {f"p{i}": {"k": jnp.zeros((groups, batch, enc_l, hkv, dh), dt),
                               "v": jnp.zeros((groups, batch, enc_l, hkv, dh), dt)}
                     for i in range(period)}
            return {"layers": layers, "cross": cross}
        return tfm.init_layer_caches(self.cfg, batch, max_len, dt)

    def init_paged_cache(self, num_pages: int, page_tokens: int,
                         slots: int) -> dict:
        """Continuous-batching caches: shared attention page pool + per-slot
        recurrent state (see :func:`transformer.init_paged_caches`)."""
        assert self.cfg.family != "encdec", "paged serving: decoder-only LMs"
        return tfm.init_paged_caches(self.cfg, num_pages, page_tokens, slots,
                                     self.compute_dtype)

    def paged_decode_step(self, params: dict, caches: dict, token: Array,
                          block_tables: Array, lens: Array,
                          new_counts: Array,
                          logits_idx: Optional[Array] = None) -> Tuple[Array, dict]:
        """Continuous-batching token step: every row advances from its own
        position.  ``token``: [B, s] (s=1 decode; s>1 the fused ragged step
        — rows mix decoding (1 new token) and chunked prefill (up to s
        prompt tokens at positions ``lens[b]..``) freely; rows padded past
        ``new_counts`` are inert).  Causality *within* a row's chunk against
        its paged past falls out of the per-row 2-D positions; the same
        ragged multi-position row doubles as the speculative-decode verify
        step (score k draft tokens in one call).  ``block_tables``:
        [B, MP] page ids; ``lens``: [B] tokens already in cache; ``new_counts``:
        [B] valid new tokens this step (0 = inactive slot).
        ``logits_idx``: optional [B, K] within-chunk positions to read
        logits at (the verify step needs every draft position, not just the
        last — K bounds the head projection at k+1 however wide the fused
        chunk is); ``None`` reads each row's last valid token.  Returns
        (logits [B, K, V] (K=1 when ``logits_idx`` is None), caches')."""
        with jax.named_scope("paged_step"):
            x = embed_apply(params["embed"], token).astype(self.compute_dtype)
            positions = lens[:, None] + jnp.arange(token.shape[1],
                                                   dtype=jnp.int32)
            paged = {"block_tables": block_tables, "lens": lens,
                     "new_counts": new_counts}
            logits_at = (jnp.maximum(new_counts - 1, 0)
                         if logits_idx is None else logits_idx)
            logits, new_caches, _ = tfm.lm_apply(
                params, x, self.ctx, self.cfg, self.run, positions=positions,
                caches=caches, paged=paged, logits_at=logits_at)
        return logits, new_caches

    def flat_decode_step(self, params: dict, caches: dict, token: Array,
                         block_tables: Array, row_ids: Array, q_pos: Array,
                         logits_idx: Array) -> Tuple[Array, dict]:
        """Flat token-level continuous-batching step (the paper's
        fixed-shape-grid argument at token granularity): one ``[1, W]``
        stream where position ``i`` is token ``q_pos[i]`` of engine row
        ``row_ids[i]`` (``-1`` = padding).  Rows become variable-length
        *segments* of the stream — a decode row costs exactly its 1+k real
        positions instead of a padded chunk-width row, so the token budget
        is token-exact.  Attention is the segment-masked ragged op over the
        page pool (kernels/ragged_attn).

        ``block_tables``: [B, MP] per-row page ids; ``logits_idx``: [K]
        flat positions to read logits at (each row's last position per
        draft slot — fixed K keeps the head projection and the step shape
        static).  Returns (logits [1, K, V], caches')."""
        with jax.named_scope("flat_step"):
            x = embed_apply(params["embed"], token).astype(self.compute_dtype)
            paged = {"block_tables": block_tables, "row_ids": row_ids,
                     "q_pos": q_pos}
            logits, new_caches, _ = tfm.lm_apply(
                params, x, self.ctx, self.cfg, self.run,
                positions=q_pos[None, :], caches=caches, paged=paged,
                logits_at=logits_idx[None, :])
        return logits, new_caches

    def prefill_cache(self, params: dict, batch: dict) -> dict:
        """Serving-side: build a cache for decode (whisper: run the encoder
        and materialize cross K/V)."""
        b = batch["tokens"].shape[0]
        max_len = self.shape.seq_len
        caches = self.init_cache(b, max_len)
        if self.cfg.family == "encdec":
            enc_out = encdec_mod.encode(params, batch["frames"], self.ctx,
                                        self.cfg, self.run)
            caches["cross"] = encdec_mod.compute_cross_kv(params, enc_out,
                                                          self.ctx, self.cfg)
        return caches

    @property
    def trace_counts(self) -> dict:
        """Per-kind count of XLA traces (= compilations) of the jitted
        steps.  The wrapped step function body runs exactly once per
        (shape, dtype) cache miss, so a Python-side increment there is a
        compile counter — the hook Engine.warmup's no-recompile-after-warmup
        contract is regression-tested against."""
        if not hasattr(self, "_trace_counts"):
            self._trace_counts = {"decode": 0, "paged": 0, "flat": 0}
        return self._trace_counts

    @property
    def trace_log(self) -> list:
        """One entry per XLA trace of a jitted step: ``{"kind", "args"}``
        where ``args`` maps argument (pytree-path) names to (shape, dtype,
        weak_type).  ``trace_counts`` answers *whether* a retrace happened;
        this log answers *which argument caused it* — the recompile-hazard
        analyzer (:mod:`repro.analysis.retrace`) diffs post-warmup entries
        against earlier same-kind signatures and names the leaf that
        differs (e.g. a python scalar leaking in as a weak-typed 0 where
        warmup traced a strong ``int32``)."""
        if not hasattr(self, "_trace_log"):
            self._trace_log = []
        return self._trace_log

    def jit_step(self, kind: str = "decode"):
        """Cached jitted step (donating the cache): shared by every Engine
        built over this model, so serving sessions amortize compilations the
        way prepacking amortizes packing — re-jitting per engine would
        recompile identical programs."""
        if not hasattr(self, "_jit_cache"):
            self._jit_cache = {}
        if kind not in self._jit_cache:
            fn = {"decode": self.decode_step,
                  "paged": self.paged_decode_step,
                  "flat": self.flat_decode_step}[kind]
            counts = self.trace_counts
            log = self.trace_log
            names = [p.name for p in
                     inspect.signature(fn).parameters.values()]

            def counted(*args, _fn=fn, _kind=kind, **kwargs):
                counts[_kind] += 1       # runs at trace time only
                try:
                    log.append({"kind": _kind,
                                "args": _describe_trace_args(names, args,
                                                             kwargs)})
                except Exception:        # the recorder must never be the
                    pass                 # reason a trace fails
                return _fn(*args, **kwargs)

            self._jit_cache[kind] = jax.jit(counted, donate_argnums=(1,))
        return self._jit_cache[kind]

    def decode_step(self, params: dict, caches: dict, token: Array, pos: Array,
                    embeds: Optional[Array] = None) -> Tuple[Array, dict]:
        """Token step(s) against the cache.  ``token``: [B, s] (s=1 decode;
        s>1 = chunked prefill into the cache).  ``embeds`` overrides token
        embeddings (vlm prefill with patch embeddings).  Returns
        (logits [B,s,V], caches')."""
        if self.cfg.family == "encdec":
            return encdec_mod.encdec_decode_step(params, caches, token, pos,
                                                 self.ctx, self.cfg, self.run)
        if embeds is None:
            x = embed_apply(params["embed"], token).astype(self.compute_dtype)
        else:
            x = embeds.astype(self.compute_dtype)
        b, s = x.shape[0], x.shape[1]
        positions = pos + jnp.arange(s, dtype=jnp.int32)  # 1-D: shared batch
        logits, new_caches, _ = tfm.lm_apply(params, x, self.ctx, self.cfg,
                                             self.run, positions=positions,
                                             caches=caches, cache_pos=pos)
        return logits, new_caches


def build_model(cfg: ModelConfig, run: RunConfig, shape: ShapeSpec,
                hw: Optional[HardwareSpec] = None, mesh=None) -> ReproModel:
    return ReproModel(cfg, run, shape, hw, mesh=mesh)
