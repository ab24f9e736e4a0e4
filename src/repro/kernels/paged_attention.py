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

Grid.  One step per row (``(B,)``) when the pools are *resident*, or per
row and block of its pages (``(B, ceil(MAXP / pages_per_block))``) when
they are *streamed*.  A block is ``pages_per_block`` pages; a row attends
block by block with one online-softmax update per block, carried across a
row's blocks (in values when resident, in VMEM scratch when streamed).

Block size.  ``pages_per_block`` follows from the shapes alone: the VMEM
bytes of one page (``page x KV x D``, padded to the (sublane, 128) tile of
the pool's dtype) against ``VMEM_KV_BUDGET``, which holds two buffers of
K and V blocks, capped at MAXP.  At danube3's widths (KV 8, D 120, page 16,
bf16) a block holds 16 pages, so a 5-page serving row is one block and a
4096-token sliding window spans 17.

Resident pools.  Pools whose tile-padded K and V together fit
``VMEM_POOL_BUDGET`` (danube3's cell: 81 pages, 10.1 MiB) are read where
they live: the kernel takes them in VMEM (``memory_space=VMEM``; inside a
decode step XLA keeps a layer's freshly written pool there already) and
each row loads its blocks' pages by page id from the scalar-prefetched
table.  No copy is made per page, so no step waits on one.  A copy the
kernel starts itself (``make_async_copy`` out of a pool left in HBM)
would need a slice of the pool whose last dimension, the head (120 or
96), is not a multiple of 128, which Mosaic refuses.

Streamed pools.  Larger pools stay in HBM.  Each page slot of a block is
its own pipelined operand (the pool passed once per slot, for K and V)
with a ``(1, page, KV, D)`` block whose ``index_map`` reads the page id
from the table.  The Pallas pipeline double-buffers them across grid
steps, so the next step's pages, the next row's included, are in flight
while a step computes.  It copies a slot only when its index changes: a
slot with no live page names the page it already holds
(``_page_index_map``), so it costs no copy.

Skipped work.  A page is *live* if it holds a token in
``[max(len - window, 0), len)`` (``[0, len)`` without a window).  A row
attends only to the blocks that hold its live pages; table entries past
them are never used as page ids.  A row of length 0 reads no page and
writes zeros.  Slots of a row's last block past its live pages hold some
real pool page, masked out of the scores.

Arithmetic.  Per block, one pass over all its tokens: every query head
scores every (token, KV head) pair in one ``[H, D] x [T * KV, D]``
product, pairs outside the head's group masked (so GQA needs no
transpose), then one online-softmax update and the ``[H, T * KV] x
[T * KV, D]`` PV product.  K and V are upcast from the pool's dtype;
queries, scores, probabilities and the accumulator are float32, so the
result matches the float32 oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM for a block's K and V pages, two buffers of each
VMEM_KV_BUDGET = 4 * 1024 * 1024
# pools (K and V, tile-padded) up to this size are read in place from VMEM
VMEM_POOL_BUDGET = 32 * 1024 * 1024
# the scoped VMEM Mosaic gives a kernel by default (v5e)
SCOPED_VMEM = 16 * 1024 * 1024


def _page_vmem_bytes(page: int, kv: int, d: int, dtype) -> int:
    """VMEM bytes of one ``[page, KV, D]`` page, padded to the dtype's tile."""

    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    return page * -(-kv // sublanes) * sublanes * -(-d // 128) * 128 * itemsize


def pages_per_block(maxp: int, page: int, kv: int, d: int, dtype) -> int:
    """Pages per block: as many as fit ``VMEM_KV_BUDGET``, <= MAXP."""

    page_bytes = _page_vmem_bytes(page, kv, d, dtype)
    return max(1, min(maxp, VMEM_KV_BUDGET // (4 * page_bytes)))


def _live_pages(n, *, page: int, window: int):
    """(first live page, live page count) of a row of length ``n``."""

    first = jnp.maximum(n - window, 0) // page if window else 0
    return first, (n + page - 1) // page - first


def _attend(q, k, v, start, n, m, l, acc, *, groups, sm_scale, window, logit_cap):
    """One online-softmax update over a block: q [H, D] float32; k, v
    [T, KV, D] float32 hold the tokens from position ``start``.  Returns
    the new (m [H, 1], l [H, 1], acc [H, D])."""

    h, d = q.shape
    t, kv, _ = k.shape
    k = k.reshape(t * kv, d)   # row c is token c // KV, KV head c % KV
    v = v.reshape(t * kv, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                # [H, T * KV]
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    pos = start + col // kv
    mask = (pos < n) & (col % kv == head // groups)
    if window:
        mask &= pos >= n - window
    s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_cur, l, acc


def _resident_kernel(
    lens_ref,              # scalar prefetch: [B] int32 per-seq cache length
    table_ref,             # scalar prefetch: [B, MAXP] int32 page table
    q_ref,                 # [1, H, D]
    k_ref, v_ref,          # [P, page, KV, D] whole pools in VMEM
    o_ref,                 # [1, H, D]
    *,
    page: int,
    ppb: int,
    window: int,
    **attend,
):
    b = pl.program_id(0)
    maxp = table_ref.shape[1]
    h, d = q_ref.shape[1:]
    n = lens_ref[b]
    first, count = _live_pages(n, page=page, window=window)
    q = q_ref[0].astype(jnp.float32)

    def block(i, carry):
        ids = [table_ref[b, jnp.minimum(first + i * ppb + j, maxp - 1)] for j in range(ppb)]
        k = jnp.concatenate([k_ref[p].astype(jnp.float32) for p in ids])
        v = jnp.concatenate([v_ref[p].astype(jnp.float32) for p in ids])
        start = (first + i * ppb) * page
        return _attend(q, k, v, start, n, *carry, window=window, **attend)

    init = (
        jnp.full((h, 1), NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, d), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, (count + ppb - 1) // ppb, block, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _page_index_map(j: int, *, page: int, window: int, ppb: int):
    """Pool page that page slot ``j`` of grid step (row, block) holds.

    A live slot names its page.  Any other slot names the page it held at
    the grid step before, so the pipeline, which copies a block only when
    its index changes, fetches nothing for it: the row's last block that
    had slot ``j`` live, else the nearest earlier row with such a block,
    else page 0 (fetched once, at the first step).
    """

    def index_map(bi, blk, lens, table):
        def span(r):
            return _live_pages(lens[r], page=page, window=window)

        first, count = span(bi)
        here = table[bi, jnp.minimum(first + blk * ppb + j, table.shape[1] - 1)]
        r = jax.lax.while_loop(
            lambda r: (r >= 0) & (span(jnp.maximum(r, 0))[1] <= j), lambda r: r - 1, bi
        )
        first_r, count_r = span(jnp.maximum(r, 0))
        last_blk = jnp.maximum(count_r - 1 - j, 0) // ppb
        held = jnp.where(r >= 0, table[jnp.maximum(r, 0), first_r + last_blk * ppb + j], 0)
        return jnp.where(blk * ppb + j < count, here, held), 0, 0, 0

    return index_map


def _streamed_kernel(
    lens_ref,              # scalar prefetch: [B] int32 per-seq cache length
    table_ref,             # scalar prefetch: [B, MAXP] int32 page table
    q_ref,                 # [1, H, D]
    *refs,                 # PPB K pages, PPB V pages [1, page, KV, D]; o_ref
                           # [1, H, D]; m, l, acc scratch [H,1], [H,1], [H,D]
    page: int,
    ppb: int,
    window: int,
    **attend,
):
    k_refs, v_refs = refs[:ppb], refs[ppb : 2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb :]
    b = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n = lens_ref[b]
    first, count = _live_pages(n, page=page, window=window)

    @pl.when(blk * ppb < count)
    def _block():
        k = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs])
        v = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs])
        start = (first + blk * ppb) * page
        m_scr[...], l_scr[...], acc_scr[...] = _attend(
            q_ref[0].astype(jnp.float32), k, v, start, n,
            m_scr[...], l_scr[...], acc_scr[...], window=window, **attend,
        )

    @pl.when(blk == pl.num_programs(1) - 1)
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
    pool, page, kv, _ = k_pages.shape
    maxp = page_table.shape[1]
    ppb = pages_per_block(maxp, page, kv, d, k_pages.dtype)
    pool_bytes = 2 * pool * _page_vmem_bytes(page, kv, d, k_pages.dtype)
    kw = dict(
        page=page, ppb=ppb, window=window, groups=h // kv, sm_scale=d**-0.5,
        logit_cap=logit_cap,
    )
    row_spec = pl.BlockSpec((1, h, d), lambda bi, *_: (bi, 0, 0))
    if pool_bytes <= VMEM_POOL_BUDGET:
        kernel = functools.partial(_resident_kernel, **kw)
        pool_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        grid, scratch, semantics = (b,), [], ("parallel",)
        pools = (k_pages, v_pages)
        # the pools on top of the scoped VMEM Mosaic gives a kernel by default
        params = dict(vmem_limit_bytes=pool_bytes + SCOPED_VMEM)
    else:
        kernel = functools.partial(_streamed_kernel, **kw)
        slots = [
            pl.BlockSpec((1, page, kv, d), _page_index_map(j, page=page, window=window, ppb=ppb))
            for j in range(ppb)
        ]
        pool_specs = slots * 2
        grid, semantics = (b, pl.cdiv(maxp, ppb)), ("parallel", "arbitrary")
        scratch = [
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ]
        pools = (k_pages,) * ppb + (v_pages,) * ppb
        params = {}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[row_spec, *pool_specs],
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics, **params),
        interpret=interpret,
    )(
        jnp.asarray(cache_lens, jnp.int32),
        jnp.asarray(page_table, jnp.int32),
        q,
        *pools,
    )


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
