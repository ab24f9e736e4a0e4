"""Model builder: one composable stack covering all assigned families.

The layer list is compressed into a *repeating unit* (period of the
block-type/MoE/local-window pattern) and parameters are stacked over repeats,
so the forward pass is a single ``lax.scan`` over repeats with a rematerialized
body — compact HLO (important when lowering 94-layer models against a
512-device mesh) and bounded activation memory.

Entry points (all functional):
  init(key)                     -> params            (smoke/small scale only)
  abstract_params()             -> ShapeDtypeStruct pytree (dry-run)
  param_logical()               -> Axes pytree (for sharding)
  forward_train(params, batch)  -> (loss, metrics)
  prefill(params, batch)        -> (logits_last, cache)
  decode_step(params, batch)    -> (logits, new_cache)
  init_cache(batch, seq)        -> cache pytree; cache_logical() for sharding
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.runtime.kv_cache import PagedSpec, scatter_prompt_into_pool
from repro.models.layers import (
    Axes,
    cross_entropy_loss,
    dense,
    embed_lookup,
    init_embedding,
    init_mlp,
    init_norm,
    is_axes,
    logits_from_embedding,
    mlp,
    rms_norm,
    softcap,
)

VOCAB_PAD = 256
CE_CHUNK = 512


# ---------------------------------------------------------------------------
# Layer spec / repeating unit
# ---------------------------------------------------------------------------


def layer_specs(cfg: ModelConfig) -> List[Tuple[str, bool, bool]]:
    """Per-layer (block_type, is_moe, is_local_window)."""

    specs = []
    for i, blk in enumerate(cfg.blocks):
        local = bool(cfg.sliding_window) and (
            (i % 2 == 0) if cfg.local_global_alternating else True
        )
        specs.append((blk, cfg.is_moe_layer(i), local))
    return specs


def unit_period(specs: List[Tuple[str, bool, bool]]) -> int:
    n = len(specs)
    for p in range(1, n + 1):
        if n % p == 0 and all(specs[i] == specs[i % p] for i in range(n)):
            return p
    return n


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ModelConfig, impl: str = "xla", moe_impl: str = "dense",
                 windowed_cache: bool = False, causal_skip: bool = False,
                 cache_cross_kv: bool = False):
        self.cfg = cfg
        self.impl = impl
        self.moe_impl = moe_impl  # "dense" (baseline) | "capacity" (§Perf)
        # §Perf: ring-buffer KV caches sized to each layer's attention window
        # (vs. baseline full-sequence caches read+masked every step)
        self.windowed_cache = windowed_cache
        # §Perf: skip fully-masked k-blocks in chunked prefill (causal sum
        # instead of the full S^2 rectangle)
        self.causal_skip = causal_skip
        # §Perf (enc-dec): compute cross-attention K/V once at prefill and
        # cache them (baseline recomputes them from enc_out every token)
        self.cache_cross_kv = cache_cross_kv
        self.specs = layer_specs(cfg)
        self.period = unit_period(self.specs)
        self.repeats = cfg.num_layers // self.period
        self.unit = self.specs[: self.period]
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        if cfg.encoder_decoder:
            self.enc_repeats = cfg.num_encoder_layers

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_block(self, key, spec, cross: bool):
        blk, is_moe, _ = spec
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 6)
        params: Dict[str, Any] = {}
        logical: Dict[str, Any] = {}
        params["norm1"], logical["norm1"] = init_norm(cfg.d_model, dt)
        if blk == "attn":
            params["attn"], logical["attn"] = attn.init_attention(ks[0], cfg, dt)
            if cross:
                params["xnorm"], logical["xnorm"] = init_norm(cfg.d_model, dt)
                params["xattn"], logical["xattn"] = attn.init_attention(ks[1], cfg, dt, cross=True)
        elif blk == "mamba":
            params["mamba"], logical["mamba"] = ssm_lib.init_mamba(ks[0], cfg, dt)
        elif blk == "mlstm":
            params["mlstm"], logical["mlstm"] = xlstm_lib.init_mlstm(ks[0], cfg, dt)
        elif blk == "slstm":
            params["slstm"], logical["slstm"] = xlstm_lib.init_slstm(ks[0], cfg, dt)
        else:
            raise ValueError(blk)
        if cfg.d_ff > 0:
            params["norm2"], logical["norm2"] = init_norm(cfg.d_model, dt)
            if is_moe:
                params["moe"], logical["moe"] = moe_lib.init_moe(ks[2], cfg, dt)
            else:
                params["mlp"], logical["mlp"] = init_mlp(
                    ks[2], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dt
                )
        return params, logical

    def _init_stack(self, key, unit, repeats, cross=False, abstract=False):
        """Stacked-over-repeats params for one repeating unit."""

        params, logical = [], []
        for j, spec in enumerate(unit):
            kj = jax.random.fold_in(key, j)
            pj1, lj = self._init_block(kj, spec, cross)
            if abstract:
                pj = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct((repeats,) + tuple(a.shape), a.dtype), pj1
                )
            else:
                keys = jax.random.split(kj, repeats)
                pj = jax.vmap(lambda k: self._init_block(k, spec, cross)[0])(keys)
            lj = jax.tree.map(lambda a: Axes((None,) + a.names), lj, is_leaf=is_axes)
            params.append(pj)
            logical.append(lj)
        return params, logical

    def init(self, key) -> Dict[str, Any]:
        return self._init_with(key, abstract=False)[0]

    def param_logical(self):
        from repro.models.layers import abstract_init

        with abstract_init():
            return self._init_with(jax.random.PRNGKey(0), abstract=True)[1]

    def shard_params(self, params, mesh):
        """``params`` placed on ``mesh`` by their logical axes (a no-op for
        params already placed so)."""

        from repro.launch.sharding import named_sharding

        return jax.tree.map(
            lambda ax, p: jax.device_put(
                p, named_sharding(mesh, p.shape, ax.names)
            ),
            self.param_logical(), params, is_leaf=is_axes,
        )

    def abstract_params(self):
        from repro.models.layers import abstract_init

        with abstract_init():
            params = self._init_with(jax.random.PRNGKey(0), abstract=True)[0]
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), params
        )

    def _init_with(self, key, abstract: bool):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 8)
        params: Dict[str, Any] = {}
        logical: Dict[str, Any] = {}
        from repro.models.layers import init_dense

        params["embed"], logical["embed"] = init_embedding(
            ks[0], cfg.vocab_size, cfg.d_model, dt
        )
        if cfg.modality in ("vision", "audio") and not cfg.encoder_decoder:
            # stub frontend projector: precomputed patch/frame embeddings ->
            # d_model (the frontend itself is out of scope per the carve-out)
            params["mod_proj"], logical["mod_proj"] = init_dense(
                ks[1], cfg.d_model, cfg.d_model, dt, "embed", "embed"
            )
        params["unit"], logical["unit"] = self._init_stack(
            ks[2], self.unit, self.repeats, cross=cfg.encoder_decoder, abstract=abstract
        )
        params["final_norm"], logical["final_norm"] = init_norm(cfg.d_model, dt)
        if not cfg.tie_embeddings:
            vpad = -(-cfg.vocab_size // VOCAB_PAD) * VOCAB_PAD
            params["lm_head"], logical["lm_head"] = init_dense(
                ks[3], cfg.d_model, vpad, dt, "embed", "vocab"
            )
        if cfg.encoder_decoder:
            params["enc_unit"], logical["enc_unit"] = self._init_stack(
                ks[4], [("attn", False, False)], cfg.num_encoder_layers, abstract=abstract
            )
            params["enc_norm"], logical["enc_norm"] = init_norm(cfg.d_model, dt)
        return params, logical

    # ------------------------------------------------------------------
    # block forward
    # ------------------------------------------------------------------

    def _window_for(self, spec, seq_len: int) -> int:
        cfg = self.cfg
        _, _, local = spec
        if local:
            return cfg.sliding_window
        # beyond-window long-context serving mode for global layers
        if seq_len > cfg.long_context_window and cfg.subquadratic_decode:
            return cfg.long_context_window
        return 0

    def _block_seq(self, spec, p, x, positions, cache, enc_out=None, enc_pos=None,
                   chunked=False):
        """Full-sequence block apply.  Returns (x, new_cache, aux)."""

        x, new_cache = self._block_mix_seq(
            spec, p, x, positions, cache, enc_out, enc_pos, chunked=chunked
        )
        x, aux = self._block_ffn(spec, p, x)
        return x, new_cache, aux

    def _block_mix_seq(self, spec, p, x, positions, cache, enc_out=None,
                       enc_pos=None, chunked=False):
        """The mixer half of ``_block_seq`` (everything before the FFN/MoE
        sub-block).  Split out so the partition executor's gather/scatter
        expert mode can interpose the channel at the MoE seam; ``_block_seq``
        recomposes the two halves, so the fused and split forms trace the
        same jaxpr."""

        cfg = self.cfg
        blk, is_moe, _ = spec
        window = self._window_for(spec, x.shape[1])
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        dummy = isinstance(cache, dict) and "_" in cache
        new_cache = cache
        if blk == "attn":
            out = attn.attention_forward(
                h, p["attn"], cfg, None, positions, window, impl=self.impl,
                chunked=chunked, causal_skip=self.causal_skip,
            )
            if not dummy and cache is not None and "k" in cache:
                # prefill: write k/v into the cache for subsequent decode
                b, s = x.shape[0], x.shape[1]
                hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
                k = (h @ p["attn"]["wk"].astype(h.dtype)).reshape(b, s, nkv, hd)
                k = attn.rope(k, positions, cfg.rope_theta)
                v = (h @ p["attn"]["wv"].astype(h.dtype)).reshape(b, s, nkv, hd)
                new_cache = dict(cache)
                new_cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
                )
                new_cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
                )
        elif blk == "mamba":
            out, st = ssm_lib.mamba_forward(h, p["mamba"], cfg, state=None, impl=self.impl)
            if not dummy:
                new_cache = st
        elif blk == "mlstm":
            out, st = xlstm_lib.mlstm_forward(h, p["mlstm"], cfg)
            if not dummy:
                new_cache = st
        elif blk == "slstm":
            out, st = xlstm_lib.slstm_forward(h, p["slstm"], cfg)
            if not dummy:
                new_cache = st
        x = x + out
        if blk == "attn" and enc_out is not None:
            hx = rms_norm(x, p["xnorm"], cfg.norm_eps)
            out = attn.attention_forward(
                hx, p["xattn"], cfg, None, positions, 0,
                kv_override=(enc_out, enc_pos), impl="xla", chunked=chunked,
            )
            x = x + out
            if not dummy and isinstance(new_cache, dict) and "xk" in new_cache:
                # §Perf: cache cross-attention K/V for the decode phase
                b2, se = enc_out.shape[0], enc_out.shape[1]
                hd2, nkv2 = cfg.resolved_head_dim, cfg.num_kv_heads
                xk = (enc_out @ p["xattn"]["wk"].astype(enc_out.dtype)).reshape(b2, se, nkv2, hd2)
                xv = (enc_out @ p["xattn"]["wv"].astype(enc_out.dtype)).reshape(b2, se, nkv2, hd2)
                new_cache = dict(new_cache)
                new_cache["xk"] = xk.astype(new_cache["xk"].dtype)
                new_cache["xv"] = xv.astype(new_cache["xv"].dtype)
        return x, new_cache

    def _block_ffn(self, spec, p, x):
        """The FFN/MoE half of a block: norm2 + (expert mixture | MLP) +
        residual.  Returns (x, aux)."""

        cfg = self.cfg
        _, is_moe, _ = spec
        aux = jnp.zeros((), jnp.float32)
        if cfg.d_ff > 0:
            h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            if is_moe:
                moe_fn = (
                    moe_lib.moe_forward_capacity
                    if self.moe_impl == "capacity"
                    else moe_lib.moe_forward
                )
                out2, aux = moe_fn(h2, p["moe"], cfg)
            else:
                out2 = mlp(h2, p["mlp"], cfg.mlp_activation, cfg.gated_mlp)
            x = x + out2
        return x, aux

    def _moe_pre_dispatch(self, p, x):
        """Edge-side half of a gather/scatter MoE split: norm2 + router.

        Returns ``(h2, combine)`` — the hidden states and top-k combine
        weights a gather/scatter partition ships cloudward, where
        ``moe_lib.moe_apply_experts`` finishes the mixture.  Chaining the
        two reproduces the dense ``moe_forward`` op-for-op (the aux loss is
        inference-irrelevant and dropped)."""

        cfg = self.cfg
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        combine, _ = moe_lib.router_probs(
            h2, p["moe"]["router"], cfg.moe.num_experts_per_tok
        )
        combine = shard(combine, "batch", "act_seq", None)
        return h2, combine

    def _block_step(self, spec, p, x, cache, cache_len, enc_out=None, enc_pos=None,
                    paged=None, layer=None):
        """Single-token decode block apply.

        ``paged``: a ``(page_table [B, MAXP], cap [B])`` pair when the cache
        holds paged attention entries (``kp``/``vp`` page pools) instead of
        dense per-slot slabs; non-attention block state is identical in both
        modes.  Dense mode (``paged=None``) is the parity oracle.  ``layer``:
        the pools are every layer's, stacked, and this block is that layer.
        """

        x, new_cache = self._block_mix_step(
            spec, p, x, cache, cache_len, enc_out, enc_pos, paged=paged, layer=layer
        )
        x, _ = self._block_ffn(spec, p, x)
        return x, new_cache

    def _block_mix_step(self, spec, p, x, cache, cache_len, enc_out=None,
                        enc_pos=None, paged=None, layer=None):
        """Mixer half of ``_block_step`` (pre-FFN) — see ``_block_mix_seq``."""

        cfg = self.cfg
        blk, is_moe, _ = spec
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if blk == "attn" and "kp" in cache:
            page_table, cap = paged
            capacity = page_table.shape[1] * cache["kp"].shape[-3]
            window = self._window_for(spec, capacity)
            out, kp, vp = attn.attention_decode_step_paged(
                h, p["attn"], cfg, cache["kp"], cache["vp"],
                page_table, cache_len, cap, window, layer=layer,
            )
            new_cache = dict(cache)
            new_cache["kp"], new_cache["vp"] = kp, vp
        elif blk == "attn":
            window = self._window_for(spec, cache["k"].shape[1] if "k" in cache else 0)
            out, ck, cv = attn.attention_decode_step(
                h, p["attn"], cfg, cache["k"], cache["v"], cache_len, window,
                impl=self.impl, ring=self.windowed_cache,
            )
            new_cache = dict(cache)
            new_cache["k"], new_cache["v"] = ck, cv
        elif blk == "mamba":
            out, new_cache = ssm_lib.mamba_decode_step(h, p["mamba"], cfg, cache)
        elif blk == "mlstm":
            out, new_cache = xlstm_lib.mlstm_forward(h, p["mlstm"], cfg, state=cache, step=True)
        elif blk == "slstm":
            out, new_cache = xlstm_lib.slstm_forward(h, p["slstm"], cfg, state=cache, step=True)
        x = x + out
        if blk == "attn" and (enc_out is not None or "xk" in cache):
            hx = rms_norm(x, p["xnorm"], cfg.norm_eps)
            if "xk" in cache:
                out = attn.cross_attention_cached(
                    hx, p["xattn"], cfg, cache["xk"], cache["xv"]
                )
            else:
                pos = jnp.broadcast_to(jnp.atleast_1d(cache_len), (x.shape[0],))[:, None]
                out = attn.attention_forward(
                    hx, p["xattn"], cfg, None, pos, 0, kv_override=(enc_out, enc_pos), impl="xla"
                )
            x = x + out
        return x, new_cache

    # ------------------------------------------------------------------
    # stacks
    # ------------------------------------------------------------------

    # repeats above this are scanned in √-remat segments.  Disabled (set
    # beyond any real depth): on the CPU backend the segmented form *adds*
    # memory (param-slice copies + per-segment loop double-buffers); the
    # flat scan + microbatching is the better trade.  Kept for TPU tuning.
    SEGMENT = 1_000_000

    def _run_unit_seq(self, params_unit, x, positions, cache_unit, enc_out=None, enc_pos=None,
                      unit=None, chunked=False):
        """lax.scan over repeats; python loop over unit positions inside.

        Two-level rematerialization: repeats are split into SEGMENT-sized
        scans, each wrapped in jax.checkpoint, so the forward saves only
        segment-boundary activations (O(R/SEGMENT)) and each segment's
        per-layer inputs are re-stacked transiently during its backward.
        """

        unit = unit or self.unit

        def body(carry, xs):
            x, aux = carry
            p_list, c_list = xs
            new_c = []
            for j, spec in enumerate(unit):
                x, cj, a = self._block_seq(
                    spec, p_list[j], x, positions, c_list[j], enc_out, enc_pos,
                    chunked=chunked,
                )
                new_c.append(cj)
                aux = aux + a
            return (x, aux), tuple(new_c)

        body = jax.checkpoint(body)

        def run_segment(carry, p_seg, c_seg):
            return jax.lax.scan(body, carry, (p_seg, c_seg))

        r = self.repeats
        seg = self.SEGMENT
        carry = (x, jnp.zeros((), jnp.float32))
        if r <= seg:
            carry, new_cache = run_segment(carry, tuple(params_unit), tuple(cache_unit))
            (x, aux) = carry
            return x, aux, list(new_cache)

        run_segment_ckpt = jax.checkpoint(run_segment)
        cache_parts = []
        for lo in range(0, r, seg):
            hi = min(lo + seg, r)
            p_seg = jax.tree.map(lambda a: a[lo:hi], tuple(params_unit))
            c_seg = jax.tree.map(lambda a: a[lo:hi], tuple(cache_unit))
            carry, seg_cache = run_segment_ckpt(carry, p_seg, c_seg)
            cache_parts.append(seg_cache)
        (x, aux) = carry
        new_cache = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *cache_parts)
        return x, aux, list(new_cache)

    # decode-step repeats at or below this are fully unrolled: the scan's
    # per-iteration param slicing + while-loop bookkeeping costs more than a
    # shallow stack's whole step (the serving engine decodes thousands of
    # single tokens); deep stacks keep the rolled scan for bounded HLO
    STEP_UNROLL_MAX = 8

    def _run_unit_step(self, params_unit, x, cache_unit, cache_len, enc_out=None, enc_pos=None,
                       paged=None):
        # page pools ride the layer scan's carry, stacked: each layer writes
        # its token into its own pool in place and the kernel reads it
        # there.  As scan inputs and outputs, each layer's pool would be
        # copied out and back at every decode step.
        whole = [paged is not None and "kp" in c for c in cache_unit]

        def body(carry, xs):
            x, pools = carry
            p_list, c_list, layer = xs
            new_c, new_pools = [], []
            for j, spec in enumerate(self.unit):
                x, cj = self._block_step(
                    spec, p_list[j], x, pools[j] if whole[j] else c_list[j], cache_len,
                    enc_out, enc_pos, paged=paged, layer=layer if whole[j] else None,
                )
                new_pools.append(cj if whole[j] else None)
                new_c.append(None if whole[j] else cj)
            return (x, tuple(new_pools)), tuple(new_c)

        pools = tuple(c if w else None for c, w in zip(cache_unit, whole))
        per_layer = tuple(None if w else c for c, w in zip(cache_unit, whole))
        (x, pools), new_cache = jax.lax.scan(
            body, (x, pools),
            (tuple(params_unit), per_layer, jnp.arange(self.repeats, dtype=jnp.int32)),
            unroll=self.repeats <= self.STEP_UNROLL_MAX,
        )
        return x, [p if w else c for p, c, w in zip(pools, new_cache, whole)]

    # ------------------------------------------------------------------
    # embeddings / inputs
    # ------------------------------------------------------------------

    def _embed_inputs(self, params, batch):
        """tokens [B,S_text] (+ 'frontend' [B,P,D] stub embeddings) -> x [B,S,D]."""

        cfg = self.cfg
        x = embed_lookup(batch["tokens"], params["embed"], cfg.d_model, cfg.scale_embeddings)
        x = x.astype(self.dtype)
        if "frontend" in batch and not cfg.encoder_decoder:
            fe = batch["frontend"].astype(self.dtype)
            fe = dense(fe, params["mod_proj"])
            x = jnp.concatenate([fe, x], axis=1)
        return shard(x, "batch", "act_seq", "act_embed")

    def _logits(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return logits_from_embedding(
                x, params["embed"]["table"], cfg.vocab_size, cfg.final_logit_softcap
            )
        logits = dense(x, params["lm_head"])
        logits = softcap(logits, cfg.final_logit_softcap)
        vpad = params["lm_head"]["w"].shape[1]
        if vpad != cfg.vocab_size:
            logits = jnp.where(jnp.arange(vpad) >= cfg.vocab_size, -1e9, logits)
        return logits

    # ------------------------------------------------------------------
    # encoder (enc-dec only)
    # ------------------------------------------------------------------

    def _encode(self, params, frames, chunked=False):
        cfg = self.cfg
        from repro.models.layers import sinusoidal_positions

        x = frames.astype(self.dtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model, self.dtype)[None]
        x = shard(x, "batch", "act_seq", "act_embed")
        pos = jnp.arange(x.shape[1])[None, :]
        enc_unit = [("attn", False, False)]
        # encoder is non-causal: reuse _block_seq with a no-window non-causal
        # attention by overriding positions trickery is messy; do it inline.
        def body(carry, p):
            x, _ = carry
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            out = attn.attention_forward(
                h, p["attn"], cfg, None, pos, 0,
                kv_override=(h, pos), impl="xla", chunked=chunked,
            )
            x = x + out
            h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            x = x + mlp(h2, p["mlp"], cfg.mlp_activation, cfg.gated_mlp)
            return (x, jnp.zeros((), jnp.float32)), ()

        body = jax.checkpoint(body)
        (x, _), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["enc_unit"][0]
        )
        return rms_norm(x, params["enc_norm"], cfg.norm_eps), pos

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------

    def forward(self, params, batch, cache_unit=None):
        """Full-sequence forward -> (hidden [B,S,D], new_cache, aux)."""

        cfg = self.cfg
        enc_out = enc_pos = None
        if cfg.encoder_decoder:
            enc_out, enc_pos = self._encode(params, batch["frontend"])
        x = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        if cache_unit is None:
            cache_unit = [self._dummy_cache(spec) for spec in self.unit]
        x, aux, new_cache = self._run_unit_seq(
            params["unit"], x, positions, cache_unit, enc_out, enc_pos
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, new_cache, aux

    def _dummy_cache(self, spec):
        # zero-size placeholder so scan structures line up when no cache kept
        return {"_": jnp.zeros((self.repeats,), jnp.float32)}

    def loss_fn(self, params, batch):
        """Next-token CE over text positions; returns (loss, metrics)."""

        cfg = self.cfg
        x, _, aux = self.forward(params, batch)
        labels = batch["labels"]
        # only score text positions (tail of the sequence for VLM/audio stubs)
        s_text = labels.shape[1]
        x_text = x[:, -s_text:]

        # chunked CE to avoid materializing [B,S,V] in f32
        b, s, d = x_text.shape
        n_chunks = max(s // CE_CHUNK, 1)
        ck = min(CE_CHUNK, s)
        xs = x_text[:, : n_chunks * ck].reshape(b, n_chunks, ck, d)
        ys = labels[:, : n_chunks * ck].reshape(b, n_chunks, ck)
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32)
        ms = mask[:, : n_chunks * ck].reshape(b, n_chunks, ck)

        @jax.checkpoint  # recompute chunk logits in bwd: saving them stacks
        def ce_chunk(carry, inp):  # [n_chunks, B, ck, V/shard] f32 otherwise
            xc, yc, mc = inp  # [B,ck,D], [B,ck], [B,ck]
            logits = self._logits(params, xc)
            lz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits.astype(jnp.float32), yc[..., None], axis=-1
            )[..., 0]
            tot, cnt = carry
            return (tot + jnp.sum((lz - gold) * mc), cnt + jnp.sum(mc)), ()

        (total, count), _ = jax.lax.scan(
            ce_chunk,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(ys, 1, 0), jnp.moveaxis(ms, 1, 0)),
        )
        loss = total / jnp.maximum(count, 1.0)
        if cfg.moe is not None and cfg.moe.num_experts:
            loss = loss + cfg.moe.router_aux_loss * aux / max(cfg.num_layers, 1)
        return loss, {"ce": loss, "aux": aux}

    def prefill(self, params, batch, extra: int = 0):
        """Run the prompt, fill caches -> (last-token logits, cache).

        ``extra``: additional KV-cache slots reserved for subsequent
        decode_step calls (cache size = prompt + extra).
        """

        b, s = batch["tokens"].shape[0], self._total_seq(batch)
        cache = self.init_cache(b, s + extra)
        if self.cfg.encoder_decoder:
            enc_out, enc_pos = self._encode(params, batch["frontend"], chunked=True)
            cache["enc_out"], cache["enc_pos"] = enc_out, enc_pos
        x = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        x, aux, new_unit = self._run_unit_seq(
            params["unit"], x, positions, cache["unit"],
            cache.get("enc_out"), cache.get("enc_pos"), chunked=True,
        )
        cache["unit"] = new_unit
        cache["len"] = jnp.asarray(x.shape[1], jnp.int32)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return self._logits(params, x[:, -1:]), cache

    def decode_step(self, params, token, cache):
        """token [B,1] -> (logits [B,1,V], new cache).

        Paged caches (built by ``init_paged_cache`` + ``cache_to_paged``)
        carry a ``pt`` page table and per-row ``cap``; attention blocks then
        read/write the shared page pool instead of dense per-slot slabs.
        """

        cfg = self.cfg
        x = embed_lookup(token, params["embed"], cfg.d_model, cfg.scale_embeddings)
        x = x.astype(self.dtype)
        x = shard(x, "batch", None, "act_embed")
        paged = (cache["pt"], cache["cap"]) if "pt" in cache else None
        x, new_unit = self._run_unit_step(
            params["unit"], x, cache["unit"], cache["len"],
            cache.get("enc_out"), cache.get("enc_pos"), paged=paged,
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)
        new_cache = dict(cache)
        new_cache["unit"] = new_unit
        new_cache["len"] = cache["len"] + 1
        return logits, new_cache

    def decode_chunk(self, params, logits, cache, n_steps: int, token_floor: int = 0):
        """Fused greedy decode of ``n_steps`` tokens, fully on device.

        Replaces the serving per-token Python loop (one jitted call plus a
        host↔device sync per token) with a single ``lax.scan``: mask logits
        to ids >= ``token_floor`` (the action-bin range for VLA serving),
        argmax, feed the token back through ``decode_step``, repeat.  With a
        [B]-vector ``cache["len"]`` the same scan serves ragged
        continuous-batching rounds.

        Returns (tokens [B, n_steps], next logits [B,1,V], cache).
        """

        def step(carry, _):
            logits, cache = carry
            ls = logits[:, -1]
            if token_floor:
                ls = ls.at[..., :token_floor].set(-1e9)
            tok = jnp.argmax(ls, axis=-1)[:, None]
            logits, cache = self.decode_step(params, tok, cache)
            return (logits, cache), tok[:, 0]

        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), None, length=n_steps
        )
        return jnp.moveaxis(toks, 0, 1), logits, cache

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------

    def _total_seq(self, batch) -> int:
        s = batch["tokens"].shape[1]
        if "frontend" in batch and not self.cfg.encoder_decoder:
            s += batch["frontend"].shape[1]
        return s

    def init_cache(self, batch: int, seq: int, paged=None):
        """Decode cache pytree.  ``paged``: a ``PagedSpec`` switches attention
        entries from dense [r,B,S,KV,D] slabs to shared page pools (``seq``
        is then ignored for attention — capacity comes from the spec)."""

        if paged is not None:
            return self.init_paged_cache(batch, paged)
        unit = [self._init_block_cache(spec, batch, seq) for spec in self.unit]
        cache = {"unit": unit, "len": jnp.zeros((), jnp.int32)}
        if self.cfg.encoder_decoder:
            cache["enc_out"] = jnp.zeros((batch, seq, self.cfg.d_model), self.dtype)
            cache["enc_pos"] = jnp.arange(seq)[None, :]
        return cache

    # ------------------------------------------------------------------
    # paged caches (the serving KV substrate)
    # ------------------------------------------------------------------

    def init_paged_cache(self, batch: int, spec):
        """Paged decode cache: attention blocks hold (pool, page-table,
        cache_len) triples drawn from one shared ``PagedSpec`` geometry.

        Per attention layer the pool is ``[repeats, P+1, page, KV, D]`` —
        one extra trash page absorbs writes from idle/over-capacity rows.
        The page table (``pt`` [B, MAXP]) and per-row token capacity
        (``cap`` [B]) are shared by every layer: sequences own the same
        page ids at each depth, exactly like production paged-attention
        engines.  Non-attention block state (Mamba/xLSTM) is O(1) per row
        and stays dense.  Rows with ``cap == 0`` are inactive.
        """

        cfg, r = self.cfg, self.repeats
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        unit = []
        for s in self.unit:
            if s[0] == "attn":
                # distinct K/V buffers: donated decode calls alias each
                # output over its own input, which a shared zeros array
                # (donated twice) would break
                shape = (r, spec.num_pages + 1, spec.page_size, nkv, hd)
                # under a mesh context the pool shards over its global page
                # dim ("pages" -> the data axis): each shard owns a
                # contiguous block of page ids, matching the host
                # allocator's shard-aware free lists
                unit.append({
                    "kp": shard(jnp.zeros(shape, self.dtype),
                                None, "pages", None, None, None),
                    "vp": shard(jnp.zeros(shape, self.dtype),
                                None, "pages", None, None, None),
                })
            else:
                unit.append(self._init_block_cache(s, batch, spec.tokens_per_seq))
        return {
            "unit": unit,
            "len": shard(jnp.zeros((batch,), jnp.int32), "batch"),
            "pt": shard(jnp.zeros((batch, spec.max_pages_per_seq), jnp.int32),
                        "batch", None),
            "cap": shard(jnp.zeros((batch,), jnp.int32), "batch"),
        }

    def cache_to_paged(self, cache, paged, page_table, caps, lens=None):
        """Scatter a dense prefilled ``cache`` into ``paged`` pools.

        ``page_table`` [B, MAXP] / ``caps`` [B] come from the host-side page
        allocator; ``lens`` defaults to the prefill length broadcast over the
        batch.  Jit-friendly (static shapes, traced indices); the returned
        pytree drives ``decode_step``/``decode_chunk`` in paged mode and is
        bit-identical to continuing from the dense cache.
        """

        pt = jnp.asarray(page_table, jnp.int32)
        b = pt.shape[0]
        if lens is None:
            lens = jnp.broadcast_to(jnp.atleast_1d(cache["len"]), (b,))
        lens = jnp.asarray(lens, jnp.int32)
        scatter = jax.vmap(scatter_prompt_into_pool, in_axes=(0, 0, None, None))
        unit = []
        for entry_d, entry_p, spec in zip(cache["unit"], paged["unit"], self.unit):
            if spec[0] == "attn":
                e = {
                    "kp": scatter(entry_p["kp"], entry_d["k"], pt, lens),
                    "vp": scatter(entry_p["vp"], entry_d["v"], pt, lens),
                }
                if "xk" in entry_d:  # enc-dec cached cross K/V stays dense
                    e["xk"], e["xv"] = entry_d["xk"], entry_d["xv"]
                unit.append(e)
            else:
                unit.append(entry_d)
        out = {
            "unit": unit,
            "len": lens,
            "pt": pt,
            "cap": jnp.asarray(caps, jnp.int32),
        }
        if "enc_out" in cache:
            out["enc_out"], out["enc_pos"] = cache["enc_out"], cache["enc_pos"]
        return out

    def merge_prefill_into_paged(
        self, cache, paged, page_table, row_idx, lens, caps
    ):
        """Merge an admission batch's dense prefill into the live paged cache.

        ``cache`` is a fresh dense prefill over ``n`` new sequences;
        ``row_idx`` [n] names the batch rows they take over (out-of-range
        rows — admission padding — are dropped), ``page_table`` [n, MAXP]
        their newly allocated pages, ``lens``/``caps`` [n] their prompt
        lengths and token capacities (0 for padding rows, which routes every
        write to the trash page).  The continuous-batching scheduler calls
        this under one jit per admission-bucket size.
        """

        pt_new = jnp.asarray(page_table, jnp.int32)
        row_idx = jnp.asarray(row_idx, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        caps = jnp.asarray(caps, jnp.int32)
        scatter = jax.vmap(scatter_prompt_into_pool, in_axes=(0, 0, None, None))
        unit = []
        for entry_d, entry_p, spec in zip(cache["unit"], paged["unit"], self.unit):
            if spec[0] == "attn":
                unit.append({
                    "kp": scatter(entry_p["kp"], entry_d["k"], pt_new, lens),
                    "vp": scatter(entry_p["vp"], entry_d["v"], pt_new, lens),
                })
            else:
                # per-row dense state: overwrite the claimed rows (axis 1 is
                # batch under the stacked repeats dim)
                unit.append(jax.tree.map(
                    lambda live, new: live.at[:, row_idx].set(
                        new.astype(live.dtype), mode="drop"
                    ),
                    entry_p, entry_d,
                ))
        return {
            "unit": unit,
            "len": paged["len"].at[row_idx].set(lens, mode="drop"),
            "pt": paged["pt"].at[row_idx].set(pt_new, mode="drop"),
            "cap": paged["cap"].at[row_idx].set(caps, mode="drop"),
        }

    def _init_block_cache(self, spec, batch: int, seq: int):
        cfg, r = self.cfg, self.repeats
        blk = spec[0]
        if blk == "attn":
            hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
            window = self._window_for(spec, seq)
            s_cache = seq
            if self.windowed_cache and window:
                s_cache = min(seq, window)  # ring buffer (see decode path)
            z = jnp.zeros((r, batch, s_cache, nkv, hd), self.dtype)
            # constrain the internally-created cache: XLA otherwise chooses
            # (often replicates) the layout of these multi-GB zeros when
            # prefill allocates them under jit (§Perf iteration C2)
            z = shard(z, None, "batch", "kv_seq", "kv_heads", None)
            c = {"k": z, "v": z}
            if self.cfg.encoder_decoder and self.cache_cross_kv:
                zx = jnp.zeros((r, batch, seq, nkv, hd), self.dtype)
                zx = shard(zx, None, "batch", "kv_seq", "kv_heads", None)
                c["xk"], c["xv"] = zx, zx
            return c
        if blk == "mamba":
            st = ssm_lib.init_mamba_state(cfg, batch, dtype=self.dtype)
            st = jax.tree.map(lambda a: jnp.broadcast_to(a, (r,) + a.shape), st)
            st["h"] = shard(st["h"], None, "batch", "heads", None, None)
            st["conv"] = shard(st["conv"], None, "batch", None, "state")
            return st
        if blk == "mlstm":
            st = xlstm_lib.init_mlstm_state(cfg, batch)
            return tuple(jnp.broadcast_to(a, (r,) + a.shape) for a in st)
        if blk == "slstm":
            st = xlstm_lib.init_slstm_state(cfg, batch)
            return tuple(jnp.broadcast_to(a, (r,) + a.shape) for a in st)
        raise ValueError(blk)

    def cache_logical(self, batch: int, seq: int):
        """Axes pytree matching init_cache structure (for dry-run sharding)."""

        def for_block(spec):
            blk = spec[0]
            if blk == "attn":
                ax = Axes((None, "batch", "kv_seq", "kv_heads", None))
                c = {"k": ax, "v": ax}
                if self.cfg.encoder_decoder and self.cache_cross_kv:
                    c["xk"], c["xv"] = ax, ax
                return c
            if blk == "mamba":
                return {
                    "h": Axes((None, "batch", "heads", None, None)),
                    "conv": Axes((None, "batch", None, "state")),
                }
            if blk == "mlstm":
                return (
                    Axes((None, "batch", None, None, None)),
                    Axes((None, "batch", None, None)),
                    Axes((None, "batch", None)),
                )
            if blk == "slstm":
                ax = Axes((None, "batch", "state"))
                return (ax, ax, ax, ax)
            raise ValueError(blk)

        cache = {"unit": [for_block(s) for s in self.unit], "len": Axes(())}
        if self.cfg.encoder_decoder:
            cache["enc_out"] = Axes(("batch", "act_seq", "act_embed"))
            cache["enc_pos"] = Axes((None, None))
        return cache
