"""Public jit'd entry points for the Pallas kernels.

On a TPU backend the kernels run compiled; on CPU (this container) they run
in ``interpret=True`` mode, which executes the kernel body in Python —
correct but slow, so models default to their pure-jnp paths and these ops
are exercised by the kernel test sweeps and available via
``Model(cfg, impl="pallas")`` for TPU deployments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import paged_attention as _pa
from repro.kernels import rolling_stats as _rs
from repro.kernels import ref as _ref
from repro.launch.sharding import active_mesh


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, logit_cap=0.0,
                    blk_q=None, blk_k=None):
    s = q.shape[1]
    kw = {}
    if blk_q:
        kw["blk_q"] = blk_q
    if blk_k:
        kw["blk_k"] = blk_k
    # block sizes must divide S; fall back to the oracle for odd lengths
    bq = kw.get("blk_q", min(_fa.DEFAULT_BLK_Q, s))
    bk = kw.get("blk_k", min(_fa.DEFAULT_BLK_K, s))
    if s % bq or s % bk:
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap
        )
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        interpret=_interpret(), **kw,
    )


def decode_attention(q, cache_k, cache_v, *, cache_len, window=0,
                     logit_cap=0.0, blk_s=None):
    s = cache_k.shape[1]
    bs = blk_s or min(_dec.DEFAULT_BLK_S, s)
    if s % bs:
        return _ref.decode_attention_ref(
            q, cache_k, cache_v, cache_len=cache_len, window=window,
            logit_cap=logit_cap,
        )
    return _dec.decode_attention(
        q, cache_k, cache_v, cache_len=cache_len, window=window,
        logit_cap=logit_cap, blk_s=bs, interpret=_interpret(),
    )


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_lens, *,
                           window=0, logit_cap=0.0, layer=None):
    """Ragged-batch decode over the shared page pool (serving hot path).

    Compiled Pallas on TPU; inside a multi-device ``sharding_rules`` mesh
    the kernel runs under ``shard_map`` over the ``data`` axis, since
    GSPMD cannot partition a Mosaic kernel.  On CPU the kernel only runs in
    interpret mode (kernel body executed in Python — far too slow for the
    decode hot loop), so this op routes to the vectorized jnp
    gather-then-attend reference, which mirrors the dense ``_sdpa`` math
    bit for bit; the Pallas kernel itself stays covered by the
    interpret-mode parity sweeps in ``tests/test_paged_attention.py``.

    With ``layer``, the pools are stacked ``[L, P, page, KV, D]`` and the op
    reads that layer's.
    """

    mesh = active_mesh()
    if layer is not None and (_interpret() or (mesh is not None and mesh.size > 1)):
        k_pages, v_pages, layer = k_pages[layer], v_pages[layer], None
    if _interpret():
        return _ref.paged_decode_attention_ref(
            q, k_pages, v_pages, page_table, cache_lens,
            window=window, logit_cap=logit_cap,
        )
    if mesh is not None and mesh.size > 1:
        return _pa.paged_decode_attention_sharded(
            q, k_pages, v_pages, page_table, cache_lens, mesh=mesh,
            window=window, logit_cap=logit_cap,
        )
    return _pa.paged_decode_attention(
        q, k_pages, v_pages, page_table, cache_lens, layer,
        window=window, logit_cap=logit_cap, interpret=False,
    )


def rolling_stats(m_acc, tau_pow, **kw):
    kw.setdefault("interpret", _interpret())
    return _rs.rolling_stats(m_acc, tau_pow, **kw)


def mamba_scan(x, dt, a, bm, c, h0=None, chunk=None, blk_h=None):
    s, h = x.shape[1], x.shape[2]
    ck = chunk or min(_ms.DEFAULT_CHUNK, s)
    bh = blk_h or min(_ms.DEFAULT_BLK_H, h)
    if h0 is not None or s % ck or h % bh:
        # decode-continuation (h0) and ragged shapes use the jnp oracle
        return _ref.mamba_scan_ref(x, dt, a, bm, c, h0=h0)
    return _ms.mamba_scan(
        x, dt, a, bm, c, chunk=ck, blk_h=bh, interpret=_interpret()
    )