"""Attention: GQA/MQA/MHA with RoPE, sliding windows, softcaps, KV cache.

The default implementation is pure jnp (what the dry-run lowers and the
roofline sees).  ``impl="pallas"`` routes prefill through the flash-attention
Pallas kernel and decode through the GQA decode kernel (TPU fast path,
validated in interpret mode by the kernel tests).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard
from repro.models.layers import Axes, _normal, softcap

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: [B, S, H, Dh]; positions: [B, S] or [S]."""

    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [B,S,half]
    cos = jnp.cos(ang)[..., None, :]  # [B,S,1,half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    params = {
        "wq": _normal(kq, (d, nh * hd), dtype, d**-0.5),
        "wk": _normal(kk, (d, nkv * hd), dtype, d**-0.5),
        "wv": _normal(kv, (d, nkv * hd), dtype, d**-0.5),
        "wo": _normal(ko, (nh * hd, d), dtype, (nh * hd) ** -0.5),
    }
    logical = {
        "wq": Axes(("embed", "qkv_features")),
        "wk": Axes(("embed", "qkv_features")),
        "wv": Axes(("embed", "qkv_features")),
        "wo": Axes(("qkv_features", "embed")),
    }
    return params, logical


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def attention_mask(
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool,
    window: int,
) -> jax.Array:
    """Boolean mask [*, Sq, Sk]; True = attend."""

    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, jnp.bool_)
    if causal:
        ok &= diff >= 0
    if window:
        ok &= diff < window
    return ok


# ---------------------------------------------------------------------------
# Core attention (jnp reference path)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, logit_cap: float) -> jax.Array:
    """q:[B,Sq,H,Dh] k,v:[B,Sk,KV,Dh] mask:[B,1,Sq,Sk] or [B,Sq,Sk]."""

    b, sq, nh, dh = q.shape
    nkv = k.shape[2]
    groups = nh // nkv
    qg = q.reshape(b, sq, nkv, groups, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits = logits * (dh**-0.5)
    logits = softcap(logits, logit_cap)
    if mask.ndim == 3:
        mask = mask[:, None, None]  # [B,1,1,Sq,Sk]
    else:
        mask = mask[:, :, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, nh, dh)


def _sdpa_chunked(
    q, k, v, q_pos, k_pos, causal: bool, window: int, logit_cap: float,
    blk_q: int = 512, blk_k: int = 1024, causal_skip: bool = False,
) -> jax.Array:
    """Flash-style blockwise attention in pure jnp (prefill path: no grad).

    Scans q blocks; an inner k-block loop carries online-softmax (m, l, acc)
    so the [Sq, Sk] score matrix never materializes — required for the 32k+
    prefill shapes.  q_pos/k_pos: [B, Sq]/[B, Sk] positions for masking.

    causal_skip (§Perf): bound the inner k loop to the causal (and windowed)
    extent of each q block instead of the full rectangle — executed FLOPs
    drop from S² to the causal sum (~2×; more with a window).  Baseline
    keeps the full rectangle (matching the baseline cost model).
    """

    b, sq, nh, dh = q.shape
    sk = k.shape[1]
    nkv = k.shape[2]
    g = nh // nkv
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    assert sq % blk_q == 0 and sk % blk_k == 0, (sq, blk_q, sk, blk_k)
    nq, nk = sq // blk_q, sk // blk_k
    scale = dh**-0.5

    qb = jnp.moveaxis(q.reshape(b, nq, blk_q, nkv, g, dh), 1, 0)      # [nq,B,blk,KV,G,D]
    qpb = jnp.moveaxis(q_pos.reshape(b, nq, blk_q), 1, 0)             # [nq,B,blk]

    def q_block(carry, inp):
        qi, qpi = inp  # [B,blk,KV,G,D], [B,blk]

        def k_block(ki, state):
            m_run, l_run, acc = state
            ks = jax.lax.dynamic_slice_in_dim(k, ki * blk_k, blk_k, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, ki * blk_k, blk_k, 1)
            kps = jax.lax.dynamic_slice_in_dim(k_pos, ki * blk_k, blk_k, 1)
            s = jnp.einsum(
                "bqkgd,bskd->bkgqs", qi.astype(jnp.float32), ks.astype(jnp.float32)
            ) * scale
            s = softcap(s, logit_cap)
            diff = qpi[:, None, None, :, None] - kps[:, None, None, None, :]
            ok = jnp.ones(diff.shape, jnp.bool_)
            if causal:
                ok &= diff >= 0
            if window:
                ok &= diff < window
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, -1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l_run * alpha + jnp.sum(p, -1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vs.astype(jnp.float32)
            )
            return m_new, l_new, acc

        m0 = jnp.full((b, nkv, g, blk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, nkv, g, blk_q), jnp.float32)
        a0 = jnp.zeros((b, nkv, g, blk_q, dh), jnp.float32)
        k_lo = jnp.int32(0)
        k_hi = jnp.int32(nk)
        if causal_skip:
            q_max = jnp.max(qpi)  # positions are per-block contiguous
            if causal:
                k_hi = jnp.minimum((q_max.astype(jnp.int32) // blk_k) + 1, nk)
            if window:
                q_min = jnp.min(qpi).astype(jnp.int32)
                k_lo = jnp.maximum((q_min - window) // blk_k, 0)
        m_f, l_f, acc = jax.lax.fori_loop(k_lo, k_hi, k_block, (m0, l0, a0))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        out = jnp.moveaxis(out, (1, 2), (2, 3))  # [B,blk,KV,G,D]
        return carry, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, (), (qb, qpb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, nh, dh)
    return out


import functools


@functools.lru_cache(maxsize=64)
def _make_strip_vjp(causal: bool, window: int, logit_cap: float):
    """One q-block attention strip with a flash-style custom VJP.

    The naive softmax backward materializes ~6 [Sq,Sk]-sized f32 buffers
    (≈26 GB/device for the 64-head configs at 4k).  The custom VJP
    recomputes scores blockwise in the backward instead.  Crucially the
    VJP wraps a SINGLE q-block strip and the blocking scan lives OUTSIDE:
    if positions/masks were computed inside a differentiated scan, jax's
    partial evaluation would hoist the (non-differentiable) mask
    computation into a "known" pass that stacks a [nq, ..., Sk] boolean
    across all blocks — a 17 GB/device constant.  Inside the opaque custom
    fwd/bwd bodies, masks live and die per block.

    Positions are f32 (exact integers ≤ 2^24) so the VJP can return zero
    cotangents without float0 bookkeeping.
    """

    def _mask_bias(qp, kp):
        """Additive f32 mask bias [B,1,1,Lq,Sk] (0 = attend, NEG_INF = not).

        Additive-f32 rather than boolean-where: a known boolean predicate
        feeding a where() gets broadcast to the [.,KV,G,.,.] score shape and
        stacked across the q-block scan by partial evaluation (64x larger).
        """

        diff = qp[:, None, None, :, None] - kp[:, None, None, None, :]
        bias = jnp.zeros(diff.shape, jnp.float32)
        if causal:
            bias = jnp.where(diff >= 0, bias, NEG_INF)
        if window:
            bias = jnp.where(diff < window, bias, NEG_INF)
        return bias

    def _fwd_math(qi, k, v, qp, kp):
        scale = qi.shape[-1] ** -0.5
        s = jnp.einsum(
            "bkgqd,bskd->bkgqs",
            qi.astype(jnp.float32),
            k.astype(jnp.float32),
        ) * scale
        s = softcap(s, logit_cap)
        bias = _mask_bias(qp, kp)
        s = s + bias
        m = jnp.maximum(jnp.max(s, -1, keepdims=True), -1e30)
        p = jnp.exp(s - m)
        l = jnp.sum(p, -1, keepdims=True)
        out = jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v.astype(jnp.float32)
        ) / jnp.maximum(l, 1e-30)
        lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
        return out, lse

    @jax.custom_vjp
    def strip(qi, k, v, qp, kp):
        """qi [B,KV,G,Lq,D]; k/v [B,Sk,KV,D]; qp [B,Lq]; kp [B,Sk] (f32)."""

        out, _ = _fwd_math(qi, k, v, qp, kp)
        return out.astype(qi.dtype)

    def strip_fwd(qi, k, v, qp, kp):
        out, lse = _fwd_math(qi, k, v, qp, kp)
        return out.astype(qi.dtype), (qi, k, v, qp, kp, out, lse)

    def strip_bwd(res, dout):
        qi, k, v, qp, kp, out, lse = res
        scale = qi.shape[-1] ** -0.5
        kk = k.astype(jnp.float32)
        vv = v.astype(jnp.float32)
        qf = qi.astype(jnp.float32)
        do = dout.astype(jnp.float32)
        s = jnp.einsum("bkgqd,bskd->bkgqs", qf, kk) * scale
        sc = softcap(s, logit_cap)
        p = jnp.exp(sc + _mask_bias(qp, kp) - lse[..., None])
        dv = jnp.einsum("bkgqs,bkgqd->bskd", p, do)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do, vv)
        delta = jnp.sum(do * out, -1, keepdims=True)
        ds = p * (dp - delta)
        if logit_cap:
            ds = ds * (1.0 - jnp.square(sc / logit_cap))
        ds = ds * scale
        dq = jnp.einsum("bkgqs,bskd->bkgqd", ds, kk)
        dk = jnp.einsum("bkgqs,bkgqd->bskd", ds, qf)
        return (
            dq.astype(qi.dtype),
            dk.astype(k.dtype),
            dv.astype(v.dtype),
            jnp.zeros_like(qp),
            jnp.zeros_like(kp),
        )

    strip.defvjp(strip_fwd, strip_bwd)
    return strip


def flash_attention_jnp(q, k, v, q_pos, k_pos, *, causal, window, logit_cap,
                        blk_q: int = 128):
    """Differentiable, memory-bounded attention (train path).

    Scans q blocks through a custom-VJP strip; grads w.r.t. the
    scan-invariant k/v accumulate through the scan's own transpose.
    """

    b, sq, nh, dh = q.shape
    bq = min(blk_q, sq)
    if sq % bq:
        # ragged fallback: exact path (small sequences only)
        mask = attention_mask(q_pos, k_pos, causal, window)
        return _sdpa(q, k, v, mask, logit_cap)
    nq = sq // bq
    nkv = k.shape[2]
    g = nh // nkv
    strip = _make_strip_vjp(causal, window, logit_cap)
    qpf = q_pos.astype(jnp.float32)
    kpf = jnp.broadcast_to(k_pos, (b, k.shape[1])).astype(jnp.float32)

    qb = jnp.moveaxis(
        jnp.moveaxis(q.reshape(b, nq, bq, nkv, g, dh), (3, 4), (2, 3)), 1, 0
    )  # [nq, B, KV, G, bq, D]
    qpb = jnp.moveaxis(qpf.reshape(b, nq, bq), 1, 0)

    def step(_, inp):
        qi, qpi = inp
        return (), strip(qi, k, v, qpi, kpf)

    _, outs = jax.lax.scan(step, (), (qb, qpb))
    out = jnp.moveaxis(outs, 0, 1)  # [B,nq,KV,G,bq,D]
    out = jnp.moveaxis(out, (2, 3), (3, 4)).reshape(b, sq, nh, dh)
    return out


def attention_forward(
    x: jax.Array,
    params,
    cfg: ModelConfig,
    layer_idx_is_local,
    positions: jax.Array,
    window: int,
    kv_override: Optional[tuple] = None,
    impl: str = "xla",
    chunked: bool = False,
    causal_skip: bool = False,
) -> jax.Array:
    """Full-sequence (train/prefill) self- or cross-attention.

    kv_override: (k_states, k_positions) for cross attention.
    chunked: blockwise online-softmax path (no [Sq,Sk] materialization) —
    the prefill/serving path for 32k+ contexts.
    """

    b, s, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, nh, hd)
    if kv_override is None:
        k = (x @ params["wk"].astype(x.dtype)).reshape(b, s, nkv, hd)
        v = (x @ params["wv"].astype(x.dtype)).reshape(b, s, nkv, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        k_pos = positions
        causal = True
    else:
        src, k_pos = kv_override
        k = (src @ params["wk"].astype(x.dtype)).reshape(b, src.shape[1], nkv, hd)
        v = (src @ params["wv"].astype(x.dtype)).reshape(b, src.shape[1], nkv, hd)
        causal = False
        window = 0
    # residual-stream sequence parallelism: shard q on seq; k/v replicated
    # on seq (GSPMD all-gathers them once per layer)
    q = shard(q, "batch", "act_seq", None, None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    qp = positions if positions.ndim == 2 else positions[None, :]
    kp = k_pos if k_pos.ndim == 2 else k_pos[None, :]
    qp = jnp.broadcast_to(qp, (b, s))
    kp = jnp.broadcast_to(kp, (b, k.shape[1]))

    if impl == "pallas" and kv_override is None and not chunked:
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=True, window=window, logit_cap=cfg.attn_logit_softcap
        )
    elif chunked:
        out = _sdpa_chunked(
            q, k, v, qp, kp, causal, window, cfg.attn_logit_softcap,
            causal_skip=causal_skip,
        )
    else:
        # train path: flash-style custom-VJP attention (naive softmax bwd
        # materializes ~6 [Sq,Sk] f32 buffers — OOM at 64 heads / 4k)
        out = flash_attention_jnp(
            q, k, v, qp, kp, causal=causal, window=window,
            logit_cap=cfg.attn_logit_softcap,
        )
    out = shard(out, "batch", "act_seq", None, None)
    return out.reshape(b, s, nh * hd) @ params["wo"].astype(x.dtype)


def attention_decode_step(
    x: jax.Array,
    params,
    cfg: ModelConfig,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_len: jax.Array,
    window: int,
    impl: str = "xla",
    ring: bool = False,
):
    """One-token decode.  x:[B,1,D]; cache_k/v:[B,S,KV,Dh].

    ``cache_len`` may be a scalar (the whole batch at the same depth — the
    single-robot serving loop) or a [B] vector (continuous batching: each
    slot at its own decode depth).  The vector path writes each sequence's
    token at its own slot and masks per-sequence lengths, so ragged batches
    share one decode step.

    ring=False (baseline): plain append at position ``cache_len``; the full
    cache is read and masked every step.
    ring=True (§Perf): the cache length equals the layer's attention window
    and writes wrap (pos % S).  Keys are stored RoPE'd at absolute
    positions, so relative offsets survive the wrap; every resident slot is
    in-window by construction, so no window mask (and no beyond-window
    reads) remain.

    Returns (out [B,1,D], new_cache_k, new_cache_v).
    """

    b, _, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    s_cache = cache_k.shape[1]
    pos = cache_len  # scalar or [B]
    ragged = jnp.ndim(pos) >= 1
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, 1, nh, hd)
    k = (x @ params["wk"].astype(x.dtype)).reshape(b, 1, nkv, hd)
    v = (x @ params["wv"].astype(x.dtype)).reshape(b, 1, nkv, hd)
    q = rope(q, pos_b[:, None], cfg.rope_theta)
    k = rope(k, pos_b[:, None], cfg.rope_theta)
    if ragged:
        # per-sequence append slots (continuous batching)
        idx_b = jnp.asarray(pos_b, jnp.int32)
        slot_b = jnp.remainder(idx_b, s_cache) if ring else jnp.minimum(idx_b, s_cache - 1)
        cache_k = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice(c, t, (i, 0, 0))
        )(cache_k, k.astype(cache_k.dtype), slot_b)
        cache_v = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice(c, t, (i, 0, 0))
        )(cache_v, v.astype(cache_v.dtype), slot_b)
    else:
        # append position (same for the whole batch)
        idx = jnp.asarray(pos, jnp.int32).reshape(())
        slot = jnp.remainder(idx, s_cache) if ring else idx
        cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))

    if impl == "pallas" and not ring and not ragged:
        from repro.kernels import ops as kops

        out = kops.decode_attention(
            q[:, 0],
            cache_k,
            cache_v,
            cache_len=idx + 1,
            window=window,
            logit_cap=cfg.attn_logit_softcap,
        )[:, None]
    else:
        k_pos = jnp.arange(s_cache)
        valid = k_pos[None, :] <= jnp.asarray(pos_b, jnp.int32)[:, None]  # [B,S]
        if window and not ring:
            valid &= k_pos[None, :] > jnp.asarray(pos_b, jnp.int32)[:, None] - window
        mask = valid[:, None, :]  # [B,1,S]
        out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask, cfg.attn_logit_softcap)
    out = out.reshape(b, 1, nh * hd) @ params["wo"].astype(x.dtype)
    return out, cache_k, cache_v


def attention_decode_step_paged(
    x: jax.Array,
    params,
    cfg: ModelConfig,
    k_pool: jax.Array,      # [P+1, page, KV, Dh] shared pool; last page = trash
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, MAXP] int32
    cache_len: jax.Array,   # [B] (or scalar) tokens already resident per row
    cap: jax.Array,         # [B] token capacity per row (0 = inactive row)
    window: int,
    layer: jax.Array | None = None,
):
    """One-token decode against the shared KV page pool.  x: [B,1,D].

    The paged twin of ``attention_decode_step``: each row's new K/V lands at
    the flat slot its page table maps ``cache_len`` to, then the batch
    attends through ``ops.paged_decode_attention`` (Pallas on TPU, the exact
    jnp gather oracle on CPU).  Rows at/over ``cap`` — idle scheduler rows,
    rows decoding past their chunk — write the pool's trash page and attend
    over at most ``cap`` tokens, so they can never corrupt live sequences.

    With ``layer``, the pools are stacked over the layers (``[L, P+1, page,
    KV, Dh]``): the token lands in that layer's pool in place and the
    kernel reads it there.

    Returns (out [B,1,D], new_k_pool, new_v_pool).
    """

    from repro.kernels import ops as kops

    b = x.shape[0]
    hd, nh, nkv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    n_pages, page = k_pool.shape[-4] - 1, k_pool.shape[-3]
    maxp = page_table.shape[1]
    pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_len), (b,)).astype(jnp.int32)
    cap_b = jnp.broadcast_to(jnp.atleast_1d(cap), (b,)).astype(jnp.int32)
    # the projections stay 2-D up to a layout barrier: reshaped to heads
    # first, the heads' layout passes back through each dot, which then
    # reads a transposed copy of the layer's weight slice made at every
    # step; behind the barrier each dot streams its slice from the stacked
    # weight, as the FFN's dots do
    h = x[:, 0]
    q, k, v = jax.lax.optimization_barrier(tuple(
        h @ params[w].astype(x.dtype) for w in ("wq", "wk", "wv")
    ))
    q = q.reshape(b, 1, nh, hd)
    k = k.reshape(b, 1, nkv, hd)
    v = v.reshape(b, 1, nkv, hd)
    q = rope(q, pos_b[:, None], cfg.rope_theta)
    k = rope(k, pos_b[:, None], cfg.rope_theta)

    page_idx = jnp.minimum(pos_b // page, maxp - 1)
    slot = page_table[jnp.arange(b), page_idx] * page + pos_b % page
    slot = jnp.where(pos_b < cap_b, slot, n_pages * page)  # trash when full
    flat_shape = k_pool.shape[:-4] + ((n_pages + 1) * page, nkv, hd)
    at = slot if layer is None else (layer, slot)
    k_pool = (
        k_pool.reshape(flat_shape).at[at].set(k[:, 0].astype(k_pool.dtype))
    ).reshape(k_pool.shape)
    v_pool = (
        v_pool.reshape(flat_shape).at[at].set(v[:, 0].astype(v_pool.dtype))
    ).reshape(v_pool.shape)

    lens_eff = jnp.minimum(pos_b + 1, cap_b)
    # the pools go in whole: a [:n_pages] slice is a copy of each pool per
    # call, and no table names the trash page
    out = kops.paged_decode_attention(
        q[:, 0], k_pool, v_pool, page_table, lens_eff,
        window=window, logit_cap=cfg.attn_logit_softcap, layer=layer,
    )[:, None]
    out = out.reshape(b, 1, nh * hd) @ params["wo"].astype(x.dtype)
    return out, k_pool, v_pool


def cross_attention_cached(
    x: jax.Array,
    params,
    cfg: ModelConfig,
    xk: jax.Array,  # [B, S_enc, KV, Dh] cached cross keys
    xv: jax.Array,
) -> jax.Array:
    """Cross-attention using prefill-cached K/V (§Perf enc-dec path).

    The baseline recomputes k/v projections over all encoder states for
    every decoded token; with caching, decode touches only q/out projections
    plus the attention reads.
    """

    b, s, d = x.shape
    hd, nh = cfg.resolved_head_dim, cfg.num_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, nh, hd)
    mask = jnp.ones((b, s, xk.shape[1]), jnp.bool_)  # non-causal, all valid
    out = _sdpa(q, xk.astype(q.dtype), xv.astype(q.dtype), mask, cfg.attn_logit_softcap)
    return out.reshape(b, s, nh * hd) @ params["wo"].astype(x.dtype)
