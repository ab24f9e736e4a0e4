"""Plain float32 reference of the served decoder, and its fp8 control.

Written from the equations, not from ``src/repro/models``: token embedding,
then per layer ``x += Attn(RMSNorm(x))`` and ``x += MLP(RMSNorm(x))``, a
final RMSNorm and the LM head.  Attention is causal (and windowed where the
configuration says so) with rotary embeddings on half-split head dims and
grouped K/V heads; the MLP is SwiGLU; RMSNorm multiplies by ``1 + scale``.
Everything runs in float32 under ``default_matmul_precision("highest")``,
one jitted layer at a time, so a model whose bf16 weights fill most of the
chip still fits beside the reference.

The control is the same forward with every matrix product taken in fp8
(``float8_e4m3fn``, one absmax scale per tensor, on both operands): the
next precision below the bf16 the configurations serve in.

The comparison reads, at every served position, how far the served token's
reference logit lies below the reference's best action token (``gap``).
A greedy bf16 program stays within rounding of the best; a wrong KV page, a
dropped layer or a lower precision does not.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        a, w = _fp8(a), _fp8(w)
    return a @ w


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """x [B, S, H, D]; positions 0..S-1; rotate the two halves of D."""

    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, unit, r, shape, fp8):
    """One transformer layer ``r`` of the stacked ``unit`` params."""

    nh, nkv, hd, window, theta, eps = shape
    p = jax.tree.map(lambda a: a[r].astype(jnp.float32), unit)
    b, s, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = _rms(x, p["norm1"]["scale"], eps)
        a = p["attn"]
        q = _rope(_mm(h, a["wq"], fp8).reshape(b, s, nh, hd), theta)
        k = _rope(_mm(h, a["wk"], fp8).reshape(b, s, nkv, hd), theta)
        v = _mm(h, a["wv"], fp8).reshape(b, s, nkv, hd)
        g = nh // nkv
        qg = q.reshape(b, s, nkv, g, hd)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * hd ** -0.5
        i = jnp.arange(s)
        ok = i[:, None] >= i[None, :]
        if window:
            ok &= i[:, None] - i[None, :] < window
        logits = jnp.where(ok, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, nh * hd)
        x = x + _mm(o, a["wo"], fp8)
        h = _rms(x, p["norm2"]["scale"], eps)
        m = p["mlp"]
        up = _mm(h, m["up"]["w"], fp8)
        gate = jax.nn.silu(_mm(h, m["gate"]["w"], fp8))
        return x + _mm(gate * up, m["down"]["w"], fp8)


@partial(jax.jit, static_argnames=("vocab", "eps", "fp8"))
def _head(x, final_scale, head_w, vocab, eps, fp8):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, final_scale.astype(jnp.float32), eps)
        return _mm(x, head_w[:, :vocab].astype(jnp.float32), fp8)


def forward(cfg: dict, params, tokens: np.ndarray, fp8: bool = False):
    """Logits [B, S, vocab] (float32) of ``tokens`` [B, S]."""

    unit = params["unit"]
    if len(unit) != 1 or "attn" not in unit[0]:
        raise NotImplementedError("the reference covers stacks of attention layers")
    shape = (
        cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
        cfg["sliding_window"], float(cfg["rope_theta"]), float(cfg["norm_eps"]),
    )
    x = params["embed"]["table"][jnp.asarray(tokens)].astype(jnp.float32)
    for r in range(cfg["num_layers"]):
        x = _layer(x, unit[0], r, shape, fp8)
    return _head(
        x, params["final_norm"]["scale"], params["lm_head"]["w"],
        cfg["vocab_size"], float(cfg["norm_eps"]), fp8,
    )


def served_gaps(ref_logits, served, floor: int) -> np.ndarray:
    """[B, T] gap between the best action logit and the served token's, at
    every served position.  ``ref_logits`` [B, T, V] predict ``served``."""

    ref = np.asarray(ref_logits, np.float32)[..., floor:]
    got = np.take_along_axis(ref, (served - floor)[..., None], -1)[..., 0]
    return ref.max(-1) - got


def first_choice(logits, floor: int) -> np.ndarray:
    """[B, T] the action token ``logits`` [B, T, V] put first."""

    return np.asarray(logits, np.float32)[..., floor:].argmax(-1) + floor
