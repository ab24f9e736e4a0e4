"""Model-level paged KV substrate: dense-vs-paged decode parity.

The paged decode mode (``Model.init_paged_cache`` + ``cache_to_paged`` +
``decode_chunk`` over page pools) must be *bit-identical* to the dense
per-slot-slab mode — greedy chunks, every architecture family: GQA groups,
sliding windows, logit softcaps, MoE blocks, Mamba/xLSTM recurrent state,
enc-dec cross-attention, ragged per-row cache lengths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.models import attention as attn
from repro.models.model import Model
from repro.runtime.kv_cache import PagedSpec, scatter_prompt_into_pool

N_STEPS = 10
PROMPT = 14


def _stack(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _batch_for(cfg, model, rng, b):
    tok = EpisodeTokenizer(cfg.vocab_size)
    obs = rng.integers(tok.state_base, tok.action_base, (b, PROMPT))
    batch = {"tokens": jnp.asarray(obs)}
    if cfg.encoder_decoder:
        batch["frontend"] = jnp.asarray(
            rng.normal(0, 0.02, (b, 16, cfg.d_model)), jnp.float32
        )
    elif cfg.modality != "text":
        batch["frontend"] = jnp.asarray(
            rng.normal(0, 0.02, (b, cfg.num_modality_tokens, cfg.d_model)),
            jnp.float32,
        )
    return batch, tok


# ---------------------------------------------------------------------------
# fused chunk decode: paged == dense, all 11 architectures
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_paged_decode_chunk_bit_identical_to_dense(arch):
    """Same prefill, then N greedy tokens through both KV substrates."""

    cfg, model, params = _stack(arch)
    rng = np.random.default_rng(0)
    b = 2
    batch, tok = _batch_for(cfg, model, rng, b)
    total = model._total_seq(batch)

    logits_d, cache_d = jax.jit(
        lambda p, bt: model.prefill(p, bt, extra=N_STEPS)
    )(params, batch)
    toks_dense, _, _ = jax.jit(
        lambda p, l, c: model.decode_chunk(p, l, c, N_STEPS, tok.action_base)
    )(params, logits_d, cache_d)

    page = 8
    maxp = -(-(total + N_STEPS) // page)
    spec = PagedSpec(num_pages=b * maxp, page_size=page, max_pages_per_seq=maxp)
    pt = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    caps = np.full((b,), maxp * page, np.int32)

    def paged_run(p, bt):
        logits, dcache = model.prefill(p, bt, extra=0)
        pcache = model.init_paged_cache(b, spec)
        pcache = model.cache_to_paged(
            dcache, pcache, jnp.asarray(pt), jnp.asarray(caps)
        )
        return model.decode_chunk(p, logits, pcache, N_STEPS, tok.action_base)[0]

    toks_paged = jax.jit(paged_run)(params, batch)
    np.testing.assert_array_equal(np.asarray(toks_dense), np.asarray(toks_paged))


def _scans(jaxpr):
    """Every ``scan`` equation of ``jaxpr``, nested ones included."""

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _scans(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _scans(sub)


@pytest.mark.parametrize("arch", [
    "phi-3-vision-4.2b", "h2o-danube-3-4b", "gemma2-9b", "jamba-1.5-large-398b",
    "seamless-m4t-medium",
])
def test_paged_decode_chunk_stacked_pools_ride_the_layer_scan_carry(arch):
    """The layer scan of a paged decode step carries the page pools stacked
    over the layers, so each layer writes and reads its own in place: a
    pool handed to the scan as an input and output would be copied out
    and back at every step."""

    cfg, model, params = _stack(arch)
    b, page, maxp = 2, 8, 4
    spec = PagedSpec(num_pages=b * maxp, page_size=page, max_pages_per_seq=maxp)
    cache = model.init_paged_cache(b, spec)
    pools = {a.shape for c in cache["unit"] if "kp" in c for a in (c["kp"], c["vp"])}
    assert pools
    batch, tok = _batch_for(cfg, model, np.random.default_rng(0), b)
    logits = jax.eval_shape(lambda p, bt: model.prefill(p, bt, extra=0)[0], params, batch)
    jaxpr = jax.make_jaxpr(
        lambda p, l, c: model.decode_chunk(p, l, c, 2, tok.action_base)
    )(params, logits, cache).jaxpr
    carried = 0
    for eqn in _scans(jaxpr):
        k, n = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = [tuple(v.aval.shape[1:]) for v in eqn.invars[k + n:]]
        assert not any(s[-4:] == pool[-4:] for s in xs for pool in pools)
        carried += sum(v.aval.shape in pools for v in eqn.invars[k:k + n])
    assert carried >= len(pools)


# ---------------------------------------------------------------------------
# single-step paged attention: ragged lengths, windows, trash isolation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,window", [
    ("openvla-7b", 0),
    ("gemma2-9b", 0),
    ("gemma2-9b", 8),
])
def test_paged_step_matches_dense_ragged(arch, window):
    """attention_decode_step_paged == attention_decode_step at mixed depths."""

    cfg, model, params = _stack(arch)
    unit_idx = next(j for j, s in enumerate(model.unit) if s[0] == "attn")
    p0 = jax.tree.map(lambda a: a[0], params["unit"][unit_idx])["attn"]
    b, page, maxp = 3, 8, 4
    s_cache = maxp * page
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    rng = np.random.default_rng(3)
    lens = np.asarray([0, 5, 17], np.int32)

    ck = jnp.asarray(rng.normal(0, 1, (b, s_cache, nkv, hd)), model.dtype)
    cv = jnp.asarray(rng.normal(0, 1, (b, s_cache, nkv, hd)), model.dtype)
    x = jnp.asarray(rng.normal(0, 1, (b, 1, cfg.d_model)), model.dtype)

    out_d, nk_d, nv_d = attn.attention_decode_step(
        x, p0, cfg, ck, cv, jnp.asarray(lens), window
    )

    # lay the same caches out in (shuffled) pool pages
    pool_pages = b * maxp
    table = rng.permutation(pool_pages).reshape(b, maxp).astype(np.int32)
    kp = jnp.zeros((pool_pages + 1, page, nkv, hd), model.dtype)
    vp = jnp.zeros_like(kp)
    full = np.full((b,), s_cache, np.int32)  # lay out every slot incl. empties
    kp = scatter_prompt_into_pool(kp, ck, jnp.asarray(table), jnp.asarray(full))
    vp = scatter_prompt_into_pool(vp, cv, jnp.asarray(table), jnp.asarray(full))
    caps = np.full((b,), s_cache, np.int32)

    out_p, nkp, nvp = attn.attention_decode_step_paged(
        x, p0, cfg, kp, vp, jnp.asarray(table), jnp.asarray(lens),
        jnp.asarray(caps), window,
    )
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))

    # each row's new K landed at its own logical slot in its own page
    nkp = np.asarray(nkp, np.float32)
    kp0 = np.asarray(kp, np.float32)
    for i, l in enumerate(lens):
        pg, off = table[i, l // page], l % page
        assert np.any(nkp[pg, off] != kp0[pg, off]), f"row {i} missing write"


def test_paged_step_capacity_protects_live_pages():
    """A row at/over its cap writes the trash page, not pool pages."""

    cfg, model, params = _stack("openvla-7b")
    p0 = jax.tree.map(lambda a: a[0], params["unit"][0])["attn"]
    b, page, maxp = 2, 8, 2
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    rng = np.random.default_rng(5)
    kp = jnp.asarray(rng.normal(0, 1, (b * maxp + 1, page, nkv, hd)), model.dtype)
    vp = jnp.zeros_like(kp)
    table = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    x = jnp.asarray(rng.normal(0, 1, (b, 1, cfg.d_model)), model.dtype)
    lens = jnp.asarray([3, 9], jnp.int32)
    caps = jnp.asarray([0, 0], jnp.int32)  # both rows inactive
    _, nkp, _ = attn.attention_decode_step_paged(
        x, p0, cfg, kp, vp, jnp.asarray(table), lens, caps, 0
    )
    np.testing.assert_array_equal(
        np.asarray(nkp[:-1], np.float32), np.asarray(kp[:-1], np.float32)
    )


# ---------------------------------------------------------------------------
# cache plumbing
# ---------------------------------------------------------------------------


def test_init_cache_paged_flag():
    _, model, _ = _stack("openvla-7b")
    spec = PagedSpec(num_pages=6, page_size=8, max_pages_per_seq=3)
    cache = model.init_cache(2, 64, paged=spec)
    assert cache["pt"].shape == (2, 3) and cache["cap"].shape == (2,)
    entry = cache["unit"][0]
    assert entry["kp"].shape[1:3] == (7, 8)  # num_pages + trash, page_size


def test_merge_prefill_drops_padding_rows():
    """Out-of-range admission rows must not touch live state."""

    cfg, model, params = _stack("openvla-7b")
    spec = PagedSpec(num_pages=8, page_size=8, max_pages_per_seq=4)
    paged = model.init_paged_cache(2, spec)
    batch = {"tokens": jnp.zeros((2, PROMPT), jnp.int32)}
    _, dcache = jax.jit(lambda p, b: model.prefill(p, b, extra=0))(params, batch)
    pt = np.zeros((2, 4), np.int32)
    pt[0] = (0, 1, 2, 3)
    merged = model.merge_prefill_into_paged(
        dcache, paged,
        jnp.asarray(pt),
        jnp.asarray([0, 2], jnp.int32),          # row 2 is out of range
        jnp.asarray([PROMPT, 0], jnp.int32),
        jnp.asarray([32, 0], jnp.int32),
    )
    assert int(merged["len"][0]) == PROMPT and int(merged["cap"][0]) == 32
    assert int(merged["len"][1]) == 0 and int(merged["cap"][1]) == 0


@pytest.mark.parametrize("arch", ["phi-3-vision-4.2b", "h2o-danube-3-4b"])
def test_cloud_policy_step_logits_dense_vs_paged(arch):
    """``CloudPolicy.step_logits``: the paged policy, teacher-forced with
    the dense policy's greedy tokens, gives the same logits at every step
    (bit for bit on the CPU, where paged attention is the jnp oracle), and
    the greedy tokens are the fused chunk decode's own."""

    from repro.launch.serve import CloudPolicy

    cfg, model, params = _stack(arch)
    tok = EpisodeTokenizer(cfg.vocab_size)
    rng = np.random.default_rng(3)
    qd, tau = rng.normal(0, 0.5, (2, 2, 7)).astype(np.float32)
    dense = CloudPolicy(model, params, tok)
    want, fed = dense.step_logits(qd, tau, 4)
    got, fed_p = CloudPolicy(model, params, tok, paged=True).step_logits(
        qd, tau, 4, tokens=fed
    )
    assert want.shape == got.shape and want.shape[:2] == (2, 5)
    np.testing.assert_array_equal(fed_p, fed)
    np.testing.assert_array_equal(got, want)
    chunk = dense(qd, tau).reshape(2, -1)
    np.testing.assert_array_equal(chunk[:, :4], tok.decode_action(fed))
