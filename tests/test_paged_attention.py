"""Paged decode kernel parity sweeps + page allocator / paged cache units."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import (
    _page_vmem_bytes,
    _slot_pages,
    paged_decode_attention,
    pages_per_block,
    pool_streamed,
)
from repro.runtime.kv_cache import OutOfPages, PageAllocator, PagedKVCache

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# kernel vs oracle across ragged batches / GQA / window / logit cap
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # b, h, kv, d, page, pool, maxp, lens, window, cap, pool dtype
    (3, 8, 2, 64, 64, 32, 6, (1, 200, 330), 0, 0.0, jnp.float32),
    (2, 4, 4, 32, 32, 16, 4, (128, 7), 0, 0.0, jnp.float32),   # MHA, page-aligned len
    (4, 16, 1, 64, 64, 40, 8, (512, 13, 256, 100), 0, 0.0, jnp.float32),  # MQA, heavy ragged
    (2, 8, 2, 64, 64, 16, 4, (250, 199), 96, 0.0, jnp.float32),  # sliding window
    (2, 6, 3, 32, 128, 8, 2, (255, 17), 0, 30.0, jnp.float32),  # logit cap
    (3, 8, 4, 64, 64, 24, 5, (320, 1, 77), 64, 50.0, jnp.float32),  # window + cap
    # danube3-4b's serving step: 16 rows of up to 5 pages over an 81-page
    # bf16 pool, idle rows among them
    (16, 32, 8, 120, 16, 81, 5,
     (0, 1, 16, 17, 70, 0, 33, 48, 64, 69, 5, 0, 31, 70, 2, 50), 0, 0.0, jnp.bfloat16),
    # phi-3-vision's MHA widths
    (4, 32, 32, 96, 16, 21, 5, (70, 0, 17, 40), 0, 0.0, jnp.bfloat16),
    # the same widths over a pool too large to read in place (STREAMED_POOL):
    # the phi3v16 cell's path, one block of 5 page slots per row
    (6, 32, 32, 96, 16, 130, 5, (70, 0, 17, 40, 1, 64), 0, 0.0, jnp.bfloat16),
    # rows of 26 and 19 live pages: more than one 16-page block each, from
    # a pool read in place from VMEM and from one too large for that
    (3, 8, 2, 64, 16, 121, 40, (600, 0, 300), 400, 30.0, jnp.float32),
    (3, 8, 2, 64, 16, 300, 40, (600, 0, 300), 400, 30.0, jnp.float32),
]


def _case_id(i, case):
    parts = [f"lens{i}" if isinstance(v, tuple) else str(v) for v in case[:-1]]
    if case[-1] != jnp.float32:
        parts.append(jnp.dtype(case[-1]).name)
    return "-".join(parts)


@pytest.mark.parametrize(
    "b,h,kv,d,page,pool,maxp,lens,window,cap,dtype", PAGED_CASES,
    ids=[_case_id(i, c) for i, c in enumerate(PAGED_CASES)],
)
def test_paged_decode_matches_ref(b, h, kv, d, page, pool, maxp, lens, window, cap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kp = jax.random.normal(ks[1], (pool, page, kv, d), dtype)
    vp = jax.random.normal(ks[2], (pool, page, kv, d), dtype)
    rng = np.random.default_rng(b * 100 + h)
    table = rng.permutation(pool)[: b * maxp].reshape(b, maxp).astype(np.int32)
    # entries past a row's pages point at page 0 or at another row's live page
    for i, n in enumerate(lens):
        dead = np.arange(maxp) >= -(-n // page)
        table[i, dead] = np.where(np.arange(dead.sum()) % 2, table[(i + 1) % b, 0], 0)
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(
        q, kp, vp, jnp.asarray(table), lens,
        window=window, logit_cap=cap, interpret=True,
    )
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    want = ref.paged_decode_attention_ref(
        f32(q), f32(kp), f32(vp), jnp.asarray(table), lens,
        window=window, logit_cap=cap,
    )
    out, live = np.asarray(f32(out)), np.asarray(lens) > 0
    tol = 1e-4 if dtype == jnp.float32 else 1e-2  # bf16 output rounding
    np.testing.assert_allclose(out[live], np.asarray(want)[live], atol=tol, rtol=tol)
    # a row of length 0 fetches nothing and comes back as zeros
    assert np.all(np.isfinite(out)) and not np.any(out[~live])


@pytest.mark.parametrize("window", [0, 40])
def test_streamed_slots_copy_only_live_pages(window):
    """A streamed page slot's block index, step by step in grid order: a
    live slot names its page; any other slot repeats the index it had at
    the step before, so the pipeline copies nothing for it."""

    page, ppb, maxp = 16, 2, 5
    nb = -(-maxp // ppb)
    lens = jnp.asarray([0, 70, 17, 0, 0, 33, 1, 80], jnp.int32)
    table = jnp.arange(lens.size * maxp, dtype=jnp.int32).reshape(-1, maxp) + 1
    slots = np.asarray(_slot_pages(lens, table, page=page, window=window, ppb=ppb))
    assert slots.shape == (lens.size * nb * ppb,)
    prev = [None] * ppb
    copies = live_pages = 0
    for bi in range(lens.size):
        n = int(lens[bi])
        first = max(n - window, 0) // page if window else 0
        count = -(-n // page) - first
        live_pages += count
        for blk in range(nb):
            for j in range(ppb):
                idx = int(slots[(bi * nb + blk) * ppb + j])
                if blk * ppb + j < count:
                    assert idx == int(table[bi, first + blk * ppb + j])
                else:
                    assert prev[j] is None or idx == prev[j]
                copies += idx != prev[j]
                prev[j] = idx
    # every live page once, plus the first step's copy into each slot
    # (row 0 is idle: page 0)
    assert copies == live_pages + ppb


@pytest.mark.parametrize("pool", [21, 130])   # read in place; streamed
def test_paged_decode_reads_one_layer_of_stacked_pools(pool):
    """Pools stacked over 3 layers, read at layer 1, give what that layer's
    pool alone gives, at phi-3-vision's MHA widths."""

    b, h, kv, d, page, maxp = 4, 32, 32, 96, 16, 5
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (3, pool, page, kv, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (3, pool, page, kv, d), jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(pool).permutation(pool)[: b * maxp]
                        .reshape(b, maxp), jnp.int32)
    lens = jnp.asarray([70, 0, 17, 40], jnp.int32)
    got = paged_decode_attention(q, kp, vp, table, lens, jnp.int32(1), interpret=True)
    want = paged_decode_attention(q, kp[1], vp[1], table, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mha_cases_over_the_budget_are_streamed():
    """The MHA 32x96 bf16 case of 130 pages, and the phi3v16 cell's pool of
    96 requests (480 pages and the trash page), exceed the budget for
    pools read in place, so both take the streamed path with all 5 of a
    row's pages in one block."""

    assert _page_vmem_bytes(16, 32, 96, jnp.bfloat16) == 128 * 1024  # 96 lanes padded to 128
    for pool, streamed in ((128, False), (130, True), (481, True)):
        shape = jax.ShapeDtypeStruct((16, pool, 16, 32, 96), jnp.bfloat16)
        assert pool_streamed(shape) is streamed
    assert pages_per_block(5, 16, 32, 96, jnp.bfloat16) == 5


def test_paged_matches_dense_decode_ref():
    """Gathering pages must reproduce dense decode attention exactly."""

    b, h, kv, d, page, maxp = 2, 8, 2, 64, 32, 4
    pool = b * maxp
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    dense_k = jax.random.normal(ks[1], (b, maxp * page, kv, d))
    dense_v = jax.random.normal(ks[2], (b, maxp * page, kv, d))
    # lay the dense caches out in (shuffled) pages
    rng = np.random.default_rng(0)
    table = rng.permutation(pool).reshape(b, maxp).astype(np.int32)
    kp = np.zeros((pool, page, kv, d), np.float32)
    vp = np.zeros_like(kp)
    for i in range(b):
        for j in range(maxp):
            kp[table[i, j]] = np.asarray(dense_k[i, j * page : (j + 1) * page])
            vp[table[i, j]] = np.asarray(dense_v[i, j * page : (j + 1) * page])
    lens = jnp.asarray([100, 77], jnp.int32)
    out = paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table), lens,
        interpret=True,
    )
    want = ref.decode_attention_ref(
        q, dense_k, dense_v, cache_len=lens[:, None, None]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_reuse():
    a = PageAllocator(4)
    first = a.alloc(3)
    assert len(set(first)) == 3 and a.num_free == 1
    a.free(first[:2])
    assert a.num_free == 3
    again = a.alloc(3)
    assert a.num_free == 0
    assert set(again) <= set(range(4))
    # freed pages must be reusable
    assert set(first[:2]) <= set(again) | {first[2]} | set(a._free)


def test_allocator_out_of_pages():
    a = PageAllocator(2)
    a.alloc(2)
    with pytest.raises(OutOfPages):
        a.alloc(1)


def test_allocator_double_free_rejected():
    a = PageAllocator(2)
    pages = a.alloc(1)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)


# ---------------------------------------------------------------------------
# paged cache manager end-to-end
# ---------------------------------------------------------------------------


def test_paged_cache_append_attend_matches_dense():
    kvh, d, page = 2, 32, 16
    cache = PagedKVCache(
        num_pages=24, page_size=page, num_kv_heads=kvh, head_dim=d,
        max_pages_per_seq=8,
    )
    rng = np.random.default_rng(1)
    dense = {}
    for sid, plen in [(0, 5), (1, 33), (2, 16)]:
        cache.add_seq(sid)
        k = jnp.asarray(rng.normal(size=(plen, kvh, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(plen, kvh, d)), jnp.float32)
        cache.write_prompt(sid, k, v)
        dense[sid] = [np.asarray(k), np.asarray(v)]
    for _ in range(20):  # decode appends crossing page boundaries
        ids = cache.seq_ids
        k1 = jnp.asarray(rng.normal(size=(len(ids), kvh, d)), jnp.float32)
        v1 = jnp.asarray(rng.normal(size=(len(ids), kvh, d)), jnp.float32)
        cache.append(ids, k1, v1)
        for i, sid in enumerate(ids):
            dense[sid][0] = np.concatenate([dense[sid][0], np.asarray(k1[i])[None]])
            dense[sid][1] = np.concatenate([dense[sid][1], np.asarray(v1[i])[None]])
    q = jnp.asarray(rng.normal(size=(3, 8, d)), jnp.float32)
    out = np.asarray(cache.attend(q))
    s_max = max(v[0].shape[0] for v in dense.values())
    ck = np.zeros((3, s_max, kvh, d), np.float32)
    cv = np.zeros_like(ck)
    lens = []
    for i, sid in enumerate(cache.seq_ids):
        length = dense[sid][0].shape[0]
        ck[i, :length] = dense[sid][0]
        cv[i, :length] = dense[sid][1]
        lens.append(length)
    want = np.asarray(
        ref.decode_attention_ref(
            q, jnp.asarray(ck), jnp.asarray(cv),
            cache_len=jnp.asarray(lens)[:, None, None],
        )
    )
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


def test_paged_cache_free_and_reuse():
    cache = PagedKVCache(
        num_pages=4, page_size=8, num_kv_heads=1, head_dim=8, max_pages_per_seq=4
    )
    cache.add_seq(0)
    cache.write_prompt(0, jnp.zeros((20, 1, 8)), jnp.zeros((20, 1, 8)))
    assert cache.allocator.num_free == 1  # 20 tokens -> 3 pages
    cache.free_seq(0)
    assert cache.allocator.num_free == 4
    cache.add_seq(1)
    cache.write_prompt(1, jnp.zeros((32, 1, 8)), jnp.zeros((32, 1, 8)))
    assert cache.seq_len(1) == 32


def test_paged_cache_out_of_pages():
    cache = PagedKVCache(
        num_pages=2, page_size=4, num_kv_heads=1, head_dim=8, max_pages_per_seq=4
    )
    cache.add_seq(0)
    assert not cache.can_admit(12)
    with pytest.raises(OutOfPages):
        cache.write_prompt(0, jnp.zeros((12, 1, 8)), jnp.zeros((12, 1, 8)))
    # per-sequence page-table ceiling is enforced separately from the pool
    big = PagedKVCache(
        num_pages=16, page_size=4, num_kv_heads=1, head_dim=8, max_pages_per_seq=2
    )
    big.add_seq(0)
    with pytest.raises(OutOfPages):
        big.write_prompt(0, jnp.zeros((12, 1, 8)), jnp.zeros((12, 1, 8)))
