"""Operation and byte counts against hand-computed shapes; the peaks."""

import json
from pathlib import Path

import pytest

from costs import paged_attn_cost, peaks, roofline_s, token_flops

BENCH = Path(__file__).resolve().parents[1]

# phi-3-vision-4.2b's widths at 16 layers: multi-head attention, 32 of 96
PHI = {"num_layers": 16, "d_model": 3072, "num_heads": 32, "num_kv_heads": 32,
       "head_dim": 96, "d_ff": 8192, "vocab_size": 32064}
DANUBE = json.loads((BENCH / "configs" / "danube3-4b.json").read_text())


def test_token_flops_phi3v_by_hand():
    # per layer: q,k,v,o 4 * 3072 * 3072, MLP 3 * 3072 * 8192
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    head = 3072 * 32064
    attn = 4 * 32 * 96 * 70
    assert token_flops(PHI, 70) == 2 * 16 * layer + 2 * head + 16 * attn


def test_token_flops_danube_gqa_by_hand():
    # q and o 3840 x 3840; k and v 3840 x (8 * 120); MLP 3 * 3840 * 10240
    layer = 2 * 3840 * 3840 + 2 * 3840 * 960 + 3 * 3840 * 10240
    assert token_flops(DANUBE, 1) == (2 * 24 * layer + 2 * 3840 * 32000
                                      + 24 * 4 * 32 * 120)


def test_paged_attention_cost_by_hand():
    # two rows at contexts 15 and 70: K and V of 32 heads of 96, bf16,
    # plus q in and out
    flops, nbytes = paged_attn_cost(PHI, [15, 70])
    assert flops == 4 * 32 * 96 * (15 + 70)
    assert nbytes == 2 * (15 + 70) * 32 * 96 * 2 + 2 * (2 * 32 * 96 * 2)
    flops, nbytes = paged_attn_cost(DANUBE, [64])
    assert nbytes == 2 * 64 * 8 * 120 * 2 + 2 * 32 * 120 * 2


def test_roofline_takes_the_binding_bound():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert roofline_s(197e12, 1.0, v5e) == pytest.approx(1.0)
    assert roofline_s(1.0, 819e9, v5e) == pytest.approx(1.0)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks("cpu")
