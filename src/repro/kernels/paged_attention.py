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
table.  No copy is made per page, so no step waits on one.  Of pools
stacked over the layers (``[L, P, page, KV, D]`` with ``layer``) the
kernel takes that layer's, sliced out of the stack.  A copy the
kernel starts itself (``make_async_copy`` out of a pool left in HBM)
would need a slice of the pool whose last dimension, the head (120 or
96), is not a multiple of 128, which Mosaic refuses.

Streamed pools.  Larger pools stay in HBM.  Each page slot of a block is
its own pipelined operand (the pool passed once per slot, for K and V)
with a ``(1, page, KV, D)`` block whose ``index_map`` reads the page id
from a per-call slot table (``_slot_pages``, scalar-prefetched in the
table's place).  The Pallas pipeline double-buffers them across grid
steps, so the next step's pages, the next row's included, are in flight
while a step computes.  It copies a slot only when its index changes: a
slot with no live page names the page it already holds, so it costs no
copy.  The table is computed once per call, vectorized: an index map that
searched back through the rows for that page would cost O(rows) scalar
work per slot and step.  Stacked pools are streamed from that layer
where they lie, so a decode step copies no pool out of the stack.

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


def _slot_pages(lens, table, *, page: int, window: int, ppb: int):
    """[B * NB * PPB] pool page that page slot ``j`` of grid step ``(row,
    block)`` holds, at ``(row * NB + block) * PPB + j`` (NB blocks a row).

    A live slot names its page.  Any other slot names the page it held at
    the grid step before, so the pipeline, which copies a block only when
    its index changes, fetches nothing for it: the latest live page of
    slot ``j`` in grid order, else page 0 (fetched once, at the first
    step).  Computed once per call, vectorized, so each index map is one
    lookup.
    """

    b, maxp = table.shape
    nb = -(-maxp // ppb)
    first, count = _live_pages(lens, page=page, window=window)
    first = jnp.broadcast_to(first, lens.shape)
    k = jnp.arange(nb * ppb)                       # page of the row's live span
    live = (k[None, :] < count[:, None]).reshape(b * nb, ppb)
    idx = jnp.minimum(first[:, None] + k[None, :], maxp - 1)
    pages = jnp.take_along_axis(table, idx, axis=1).reshape(b * nb, ppb)
    steps = jnp.arange(b * nb)[:, None]
    last = jax.lax.cummax(jnp.where(live, steps, -1), axis=0)
    held = jnp.take_along_axis(pages, jnp.maximum(last, 0), axis=0)
    return jnp.where(last >= 0, held, 0).reshape(-1)


def _streamed_kernel(
    lens_ref,              # scalar prefetch: [B] int32 per-seq cache length
    slots_ref,             # scalar prefetch: [B * NB * PPB] int32 (_slot_pages)
    layer_ref,             # scalar prefetch: [1] int32 layer of the stacked pools
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


def pool_streamed(k_pages) -> bool:
    """Whether pools shaped like ``k_pages`` (``[..., P, page, KV, D]``, K
    and V alike) are too large for the kernel to read in place from VMEM,
    so that their pages are streamed."""

    pool, page, kv, d = k_pages.shape[-4:]
    return 2 * pool * _page_vmem_bytes(page, kv, d, k_pages.dtype) > VMEM_POOL_BUDGET


@functools.partial(
    jax.jit,
    static_argnames=("window", "logit_cap", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,           # [B, H, D]
    k_pages: jax.Array,     # [P, page, KV, D], or [L, P, page, KV, D] with ``layer``
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, MAXP] int32
    cache_lens: jax.Array,  # [B] int32
    layer: jax.Array | None = None,  # int32 scalar: the layer of stacked pools
    *,
    window: int = 0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    pool, page, kv, _ = k_pages.shape[-4:]
    maxp = page_table.shape[1]
    ppb = pages_per_block(maxp, page, kv, d, k_pages.dtype)
    kw = dict(
        page=page, ppb=ppb, window=window, groups=h // kv, sm_scale=d**-0.5,
        logit_cap=logit_cap,
    )
    row_spec = pl.BlockSpec((1, h, d), lambda bi, *_: (bi, 0, 0))
    lens = jnp.asarray(cache_lens, jnp.int32)
    if not pool_streamed(k_pages):
        if layer is not None:  # the layer's pool, sliced out of the stack
            k_pages, v_pages = k_pages[layer], v_pages[layer]
        kernel = functools.partial(_resident_kernel, **kw)
        pool_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        grid, scratch, semantics = (b,), [], ("parallel",)
        pools = (k_pages, v_pages)
        # the pools on top of the scoped VMEM Mosaic gives a kernel by default
        pool_bytes = 2 * pool * _page_vmem_bytes(page, kv, d, k_pages.dtype)
        params = dict(vmem_limit_bytes=pool_bytes + SCOPED_VMEM)
        prefetch = (lens, jnp.asarray(page_table, jnp.int32))
    else:
        # stacked pools are read where they lie: no copy of a layer's pool
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        kernel = functools.partial(_streamed_kernel, **kw)
        nb = pl.cdiv(maxp, ppb)
        slots = [
            pl.BlockSpec(
                (None, 1, page, kv, d),
                lambda bi, blk, lens, pages, layer, j=j: (
                    layer[0], pages[(bi * nb + blk) * ppb + j], 0, 0, 0),
            )
            for j in range(ppb)
        ]
        pool_specs = slots * 2
        grid, semantics = (b, nb), ("parallel", "arbitrary")
        scratch = [
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ]
        pools = (k_pages,) * ppb + (v_pages,) * ppb
        params = {}
        prefetch = (
            lens,
            _slot_pages(lens, jnp.asarray(page_table, jnp.int32),
                        page=page, window=window, ppb=ppb),
            jnp.asarray(layer, jnp.int32).reshape(1),
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
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
    )(*prefetch, q, *pools)


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
