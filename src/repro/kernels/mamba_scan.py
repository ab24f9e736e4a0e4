"""Chunked SSD (Mamba-2 style) selective-scan Pallas TPU kernel.

TPU adaptation of the Mamba recurrence (DESIGN.md §2): intra-chunk work is
a masked quadratic form (MXU matmuls over [CHUNK, CHUNK] decay kernels),
inter-chunk state is carried sequentially in VMEM scratch across the chunk
grid dimension.  Head tiles ride the second grid dimension so the per-head
decay tensors stay VMEM-sized.

Grid: (batch, head_blocks, num_chunks) — chunks innermost ("arbitrary"
semantics; the state scratch carries across them, re-initialized per
(batch, head_block)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 256
DEFAULT_BLK_H = 8


def _kernel(
    x_ref,    # [1, L, BLK_H, P]
    dt_ref,   # [1, L, BLK_H]
    a_ref,    # [BLK_H]
    bm_ref,   # [1, L, N]
    c_ref,    # [1, L, N]
    y_ref,    # [1, L, BLK_H, P]
    hT_ref,   # [1, BLK_H, P, N]
    h_scr,    # VMEM [BLK_H, P, N]
    *,
    chunk: int,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)        # [L, H, P]
    dt = dt_ref[0].astype(jnp.float32)      # [L, H]
    a = a_ref[...].astype(jnp.float32)      # [H]
    bm = bm_ref[0].astype(jnp.float32)      # [L, N]
    c = c_ref[0].astype(jnp.float32)        # [L, N]

    loga = dt * a[None, :]                  # [L, H], <= 0
    cum = jnp.cumsum(loga, axis=0)          # inclusive

    # ---- intra-chunk quadratic form ----
    g = jax.lax.dot_general(
        c, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # [L, L] = C_t · B_s
    m = cum[:, None, :] - cum[None, :, :]    # [t, s, H]
    tril = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(tril[:, :, None], jnp.exp(m), 0.0)
    w = g[:, :, None] * m * dt[None, :, :]   # [t, s, H]
    y = jnp.einsum("tsh,shp->thp", w, x)

    # ---- carried-state contribution ----
    h_prev = h_scr[...]                      # [H, P, N]
    decay_from_start = jnp.exp(cum)          # [L, H]
    y += jnp.einsum("tn,hpn,th->thp", c, h_prev, decay_from_start)
    y_ref[0] = y.astype(y_ref.dtype)

    # ---- state update ----
    decay_to_end = jnp.exp(cum[-1][None, :] - cum)   # [L, H]
    s_c = jnp.einsum("sh,sn,shp->hpn", decay_to_end * dt, bm, x)
    h_scr[...] = h_prev * jnp.exp(cum[-1])[:, None, None] + s_c

    @pl.when(ci == num_chunks - 1)
    def _finish():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "blk_h", "interpret"))
def mamba_scan(
    x: jax.Array,    # [B, S, H, P] f32
    dt: jax.Array,   # [B, S, H] f32 (post-softplus)
    a: jax.Array,    # [H] f32 negative
    bm: jax.Array,   # [B, S, N]
    c: jax.Array,    # [B, S, N]
    *,
    chunk: int = DEFAULT_CHUNK,
    blk_h: int = DEFAULT_BLK_H,
    interpret: bool = False,
):
    """Returns (y [B,S,H,P], h_final [B,H,P,N]).  Zero initial state."""

    b, s, h, p = x.shape
    n = bm.shape[-1]
    chunk = min(chunk, s)
    blk_h = min(blk_h, h)
    assert s % chunk == 0 and h % blk_h == 0, (s, chunk, h, blk_h)
    nc, nh = s // chunk, h // blk_h

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, blk_h, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, blk_h), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((blk_h,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, blk_h, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, blk_h, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_h, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, bm, c)
    return y, hT