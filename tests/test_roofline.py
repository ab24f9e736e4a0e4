"""Roofline machinery tests: analytic cost model vs XLA cost analysis, and
the HLO collective parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.models.model import Model
from repro.roofline.analysis import _shape_bytes, collective_bytes_from_hlo
from repro.roofline.costmodel import estimate, forward_flops


def test_shape_bytes_parser():
    assert _shape_bytes("bf16[16,4096,3584]") == 16 * 4096 * 3584 * 2
    assert _shape_bytes("f32[8]") == 32
    assert _shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
    assert _shape_bytes("pred[10]") == 10


def test_collective_parser_counts_and_scales():
    hlo = """
%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag.1 = f32[16,128]{1,0} all-gather(%x), dimensions={0}
}
%main (p: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  %ar.2 = f32[32]{0} all-reduce(%y), to_apply=%sum
}
"""
    out = collective_bytes_from_hlo(hlo, loop_trip=10)
    assert out["all-gather"] == 16 * 128 * 4 * 10  # scaled by trip count
    assert out["all-reduce"] == 32 * 4
    assert out["total"] == out["all-gather"] + out["all-reduce"]


@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "starcoder2-3b"])
def test_costmodel_matches_xla_on_unrolled_forward(arch):
    """Analytic forward FLOPs vs XLA cost_analysis on a single-device,
    loop-free lowering of a smoke config (where cost_analysis is exact).

    Tolerance is loose (35%): XLA counts every op (norms, softmax, rope)
    while the model counts matmuls + attention + masks — the dominant terms.
    """

    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 64
    batch = {"tokens": jnp.zeros((b, s), jnp.int32)}

    def fwd(p, tk):
        x, _, _ = model.forward(p, tk)
        return model._logits(params, x)

    compiled = jax.jit(fwd).lower(params, batch).compile()
    xla_flops = float(compiled.cost_analysis().get("flops", 0.0))
    # forward_flops counts the full masked rectangle = what _sdpa computes
    ours = forward_flops(cfg, b, s, optimized=False)
    assert xla_flops > 0
    # scan over layers: xla counts the body once -> scale by repeats
    # (smoke configs have repeats<=2 and period covering all layers)
    ratio = ours / xla_flops
    assert 0.5 < ratio < 2.2, (arch, ours, xla_flops, ratio)


def test_optimized_estimates_improve_the_right_terms():
    from repro.configs import INPUT_SHAPES, get_config

    # MoE: optimized cuts compute, not memory
    cfg = get_config("qwen3-moe-235b-a22b")
    b0 = estimate(cfg, INPUT_SHAPES["train_4k"])
    o0 = estimate(cfg, INPUT_SHAPES["train_4k"], optimized=True)
    assert o0.flops < 0.2 * b0.flops
    # windowed decode: optimized cuts memory
    cfg2 = get_config("gemma2-9b")
    b1 = estimate(cfg2, INPUT_SHAPES["long_500k"])
    o1 = estimate(cfg2, INPUT_SHAPES["long_500k"], optimized=True)
    assert o1.hbm_bytes < 0.25 * b1.hbm_bytes


def test_model_flops_definition():
    from repro.configs import INPUT_SHAPES, get_config

    cfg = get_config("h2o-danube-3-4b")
    sh = INPUT_SHAPES["train_4k"]
    est = estimate(cfg, sh)
    expect = 6.0 * cfg.param_counts()["active"] * sh.global_batch * sh.seq_len
    assert abs(est.flops_model - expect) / expect < 1e-9
