"""Operations and bytes the decode needs, and the chip's peaks.

All counts come from the configuration's sizes alone.  A decode step of a
dense decoder layer for one token at context ``c`` (the token attends to
``c`` keys, itself included) needs:

- matrix products: 2 operations per weight, over ``wq wk wv wo`` and the
  gated MLP's ``up gate down``; the LM head adds ``2 * d_model * vocab``;
- attention: ``QK`` and ``PV``, ``2 * heads * head_dim * c`` each.

The paged attention kernel reads each key and value once (bf16, two
bytes) and reads ``q`` and writes its output once.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""

    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def layer_weights(cfg: dict) -> int:
    """Weights of one layer's matrix products."""

    d, hd = cfg["d_model"], cfg["head_dim"]
    q = cfg["num_heads"] * hd
    kv = cfg["num_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * cfg["d_ff"]


def attn_flops(cfg: dict, ctx: int) -> int:
    """One token's ``QK`` and ``PV`` at context ``ctx``, one layer."""

    return 4 * cfg["num_heads"] * cfg["head_dim"] * ctx


def token_flops(cfg: dict, ctx: int) -> int:
    """One decoded token through the whole model at context ``ctx``."""

    n = cfg["num_layers"]
    return (2 * n * layer_weights(cfg) + 2 * cfg["d_model"] * cfg["vocab_size"]
            + n * attn_flops(cfg, ctx))


def paged_attn_cost(cfg: dict, ctxs: Iterable[int]):
    """(operations, bytes) of one kernel call over rows at contexts ``ctxs``."""

    hd, nh, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    flops = nbytes = 0
    for c in ctxs:
        flops += attn_flops(cfg, c)
        nbytes += 2 * c * nkv * hd * BF16 + 2 * nh * hd * BF16
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs for ``flops`` and ``nbytes``."""

    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
