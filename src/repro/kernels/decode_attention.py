"""GQA decode attention Pallas TPU kernel.

One new query token per sequence attends over a [S, KV, D] KV cache —
the serving hot loop for ``decode_32k`` / ``long_500k``.  The cache is
streamed through VMEM in [BLK_S] tiles with online-softmax accumulation;
queries for all heads of one sequence stay resident (they are tiny).

Masking: positions >= cache_len are invalid; an optional sliding window
drops positions < cache_len - window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLK_S = 512
NEG_INF = -1e30


def _kernel(
    len_ref,               # scalar prefetch: [1] int32 cache length
    q_ref,                 # [1, H, D]
    k_ref, v_ref,          # [1, BLK_S, KV, D]
    o_ref,                 # [1, H, D]
    m_scr, l_scr, acc_scr,  # [H,1], [H,1], [H,D]
    *,
    blk_s: int,
    num_s_blocks: int,
    sm_scale: float,
    window: int,
    logit_cap: float,
    groups: int,
):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # [H, D]
    k = k_ref[0].astype(jnp.float32)          # [BLK_S, KV, D]
    v = v_ref[0].astype(jnp.float32)
    h, d = q.shape
    kv = k.shape[1]

    # logits[h, s] with GQA head->kv mapping via reshape to [KV, G, D]
    qg = q.reshape(kv, groups, d)
    s = jnp.einsum("kgd,skd->kgs", qg, k).reshape(h, blk_s) * sm_scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)

    cache_len = len_ref[0]
    pos = si * blk_s + jax.lax.broadcasted_iota(jnp.int32, (h, blk_s), 1)
    mask = pos < cache_len
    if window:
        mask &= pos >= cache_len - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)  # [H, BLK_S]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pg = p.reshape(kv, groups, blk_s)
    acc = jnp.einsum("kgs,skd->kgd", pg, v).reshape(h, d)
    acc_scr[...] = acc_scr[...] * alpha + acc
    m_scr[...] = m_cur

    @pl.when(si == num_s_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "logit_cap", "blk_s", "interpret"),
)
def decode_attention(
    q: jax.Array,        # [B, H, D] — one token per sequence
    cache_k: jax.Array,  # [B, S, KV, D]
    cache_v: jax.Array,
    *,
    cache_len,           # scalar int32 (traced ok)
    window: int = 0,
    logit_cap: float = 0.0,
    blk_s: int = DEFAULT_BLK_S,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    s = cache_k.shape[1]
    kv = cache_k.shape[2]
    groups = h // kv
    blk_s = min(blk_s, s)
    assert s % blk_s == 0
    ns = s // blk_s

    kernel = functools.partial(
        _kernel,
        blk_s=blk_s,
        num_s_blocks=ns,
        sm_scale=d**-0.5,
        window=window,
        logit_cap=logit_cap,
        groups=groups,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, si, len_ref: (bi, 0, 0)),
            pl.BlockSpec((1, blk_s, kv, d), lambda bi, si, len_ref: (bi, si, 0, 0)),
            pl.BlockSpec((1, blk_s, kv, d), lambda bi, si, len_ref: (bi, si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, si, len_ref: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(cache_len, jnp.int32).reshape(1), q, cache_k, cache_v)
    return out