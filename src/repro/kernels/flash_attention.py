"""Flash attention (prefill) Pallas TPU kernel.

Blockwise online-softmax attention with causal masking, optional sliding
window, optional attention-logit softcap, and GQA head mapping — the cloud
prefill hot spot for every attention architecture in the zoo.

Tiling: grid = (batch, q_heads, num_q_blocks, num_k_blocks), k innermost.
Each program holds a [BLK_Q, HEAD_DIM] query tile and one [BLK_K, HEAD_DIM]
key/value tile in VMEM, with running (max, denom, accum) scratch carried
across the k dimension — the standard TPU flash schedule (never materializes
the [S, S] score matrix in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLK_Q = 256
DEFAULT_BLK_K = 256
NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref,  # [BLK_Q, D], [BLK_K, D], [BLK_K, D]
    o_ref,                # [BLK_Q, D]
    m_scr, l_scr, acc_scr,  # VMEM scratch
    *,
    blk_q: int,
    blk_k: int,
    num_k_blocks: int,
    sm_scale: float,
    causal: bool,
    window: int,
    logit_cap: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones((blk_q, blk_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    # explicit re-mask: for fully-masked rows s - m_cur == 0 would exp to 1
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
    l_cur = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_cap", "blk_q", "blk_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KV, D]
    v: jax.Array,  # [B, S, KV, D]
    *,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    blk_q: int = DEFAULT_BLK_Q,
    blk_k: int = DEFAULT_BLK_K,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    assert s % blk_q == 0 and s % blk_k == 0, (s, blk_q, blk_k)
    nq, nk = s // blk_q, s // blk_k

    qt = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
    kt = jnp.moveaxis(k, 2, 1)  # [B, KV, S, D]
    vt = jnp.moveaxis(v, 2, 1)

    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _kernel,
        blk_q=blk_q,
        blk_k=blk_k,
        num_k_blocks=nk,
        sm_scale=d**-0.5,
        causal=causal,
        window=window,
        logit_cap=logit_cap,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)  # [B, S, H, D]