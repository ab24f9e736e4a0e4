"""Paged GQA decode attention Pallas TPU kernel (ragged batches).

Generalizes ``decode_attention.py`` from "one scalar ``cache_len`` shared by
the whole batch" to continuous-batching serving: each sequence carries its
own length (``cache_lens`` [B]) and its KV lives in fixed-size *pages* drawn
from one shared pool, addressed through a per-sequence page table.  Requests
that arrived at different times — and therefore sit at different decode
depths — share a single kernel launch.

Layout:
  q           [B, H, D]           one new query token per sequence
  k/v pages   [P, page, KV, D]    global page pool (all sequences share it)
  page_table  [B, MAXP] int32     page_table[b, i] = pool page holding
                                  tokens [i*page, (i+1)*page) of sequence b
  cache_lens  [B] int32           valid tokens per sequence

The grid is (B, MAXP); the page-table entry is read in the BlockSpec
``index_map`` via scalar prefetch, so each step DMAs exactly the page the
sequence needs — the online-softmax accumulation is identical to the dense
decode kernel.  Pages past ``ceil(len/page)`` are masked out (their table
entries may point anywhere valid, conventionally page 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGE = 128
NEG_INF = -1e30


def _kernel(
    lens_ref,              # scalar prefetch: [B] int32 per-seq cache length
    table_ref,             # scalar prefetch: [B, MAXP] int32 page table
    q_ref,                 # [1, H, D]
    k_ref, v_ref,          # [1, PAGE, KV, D] — the page picked by index_map
    o_ref,                 # [1, H, D]
    m_scr, l_scr, acc_scr,  # [H,1], [H,1], [H,D]
    *,
    page: int,
    num_pages: int,
    sm_scale: float,
    window: int,
    logit_cap: float,
    groups: int,
):
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # [H, D]
    k = k_ref[0].astype(jnp.float32)          # [PAGE, KV, D]
    v = v_ref[0].astype(jnp.float32)
    h, d = q.shape
    kv = k.shape[1]

    qg = q.reshape(kv, groups, d)
    s = jnp.einsum("kgd,skd->kgs", qg, k).reshape(h, page) * sm_scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)

    cache_len = lens_ref[bi]
    pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (h, page), 1)
    mask = pos < cache_len
    if window:
        mask &= pos >= cache_len - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)  # [H, PAGE]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pg = p.reshape(kv, groups, page)
    acc = jnp.einsum("kgs,skd->kgd", pg, v).reshape(h, d)
    acc_scr[...] = acc_scr[...] * alpha + acc
    m_scr[...] = m_cur

    @pl.when(pi == num_pages - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "logit_cap", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,           # [B, H, D]
    k_pages: jax.Array,     # [P, page, KV, D]
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, MAXP] int32
    cache_lens: jax.Array,  # [B] int32
    *,
    window: int = 0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, page, kv, _ = k_pages.shape
    maxp = page_table.shape[1]
    groups = h // kv

    kernel = functools.partial(
        _kernel,
        page=page,
        num_pages=maxp,
        sm_scale=d**-0.5,
        window=window,
        logit_cap=logit_cap,
        groups=groups,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, pi, lens, table: (bi, 0, 0)),
            pl.BlockSpec(
                (1, page, kv, d), lambda bi, pi, lens, table: (table[bi, pi], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, page, kv, d), lambda bi, pi, lens, table: (table[bi, pi], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, pi, lens, table: (bi, 0, 0)),
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
    )(
        jnp.asarray(cache_lens, jnp.int32),
        jnp.asarray(page_table, jnp.int32),
        q,
        k_pages,
        v_pages,
    )
    return out


def paged_decode_attention_sharded(
    q: jax.Array,           # [B, H, D]; B divisible by the mesh "data" size
    k_pages: jax.Array,     # [P, page, KV, D] (replicated per shard)
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, MAXP] int32 (global page ids)
    cache_lens: jax.Array,  # [B] int32
    *,
    mesh,
    window: int = 0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """The kernel with its rows sharded over the mesh ``data`` axis.

    Mosaic kernels cannot be partitioned by GSPMD, so a jitted program that
    spans several devices must call the kernel inside a ``shard_map``.  Each
    shard runs it over its row slice against a full view of the page pools
    — page ids stay global, so no table translation is needed (the ``P()``
    pool specs gather a page-sharded pool onto every device).  Decode
    attention is per-row math with no cross-row reduction, so the sharded
    launch computes the same rows as the single-device one.  Rows are
    padded to a multiple of the ``data`` axis with empty (length-0) rows.
    """

    from jax.sharding import PartitionSpec as P

    b = q.shape[0]
    pad = -b % mesh.shape["data"]
    page_table = jnp.asarray(page_table, jnp.int32)
    cache_lens = jnp.asarray(cache_lens, jnp.int32)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        page_table = jnp.pad(page_table, ((0, pad), (0, 0)))
        cache_lens = jnp.pad(cache_lens, (0, pad))

    def local(q_, kp_, vp_, pt_, lens_):
        return paged_decode_attention(
            q_, kp_, vp_, pt_, lens_, window=window, logit_cap=logit_cap,
            interpret=interpret,
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P(), P(), P("data"), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )
    return fn(q, k_pages, v_pages, page_table, cache_lens)[:b]
