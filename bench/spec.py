"""What a cell is made of, found by name in ``BENCHMARK.json``.

A cell names a configuration (``configs[].file``, under ``bench/configs``)
and a traffic mix (``bench/traffic/<traffic>.json``); each per-layer metric
is a reader ``bench/metrics/<name>.py``.  Adding a cell, a configuration, a
traffic mix or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# the model sizes the configuration file fixes, by the program's names
SIZE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
             "d_ff", "vocab_size", "rope_theta", "sliding_window", "norm_eps")
# the program's settings the plain reference assumes
REFERENCE_ASSUMES = {
    "attn_logit_softcap": 0.0, "final_logit_softcap": 0.0, "gated_mlp": True,
    "mlp_activation": "silu", "tie_embeddings": False, "scale_embeddings": False,
    "encoder_decoder": False, "moe": None, "block_pattern": None,
    "local_global_alternating": False, "dtype": "bfloat16", "param_dtype": "bfloat16",
}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in reported]
    return Cell(name, w["chips"], config, traffic, e2e, layer)


def reader(metric: str):
    """The ``read(run)`` function of per-layer metric ``metric``."""

    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stated_sizes(config: dict) -> Dict[str, object]:
    """The configuration file's sizes in the program's spelling: a
    ``sliding_window`` of null (global attention) is the program's 0."""

    sizes = {k: config[k] for k in SIZE_KEYS}
    if sizes["sliding_window"] is None:
        sizes["sliding_window"] = 0
    return sizes


def model_config(config: dict, rehearse: bool):
    """The program's ``ModelConfig`` that the configuration file states
    (the program's smoke preset of the same architecture on ``rehearse``),
    and its sizes as the reference reads them."""

    from repro.configs.base import get_config, get_smoke_config

    stated = stated_sizes(config)
    if rehearse:
        cfg = get_smoke_config(config["arch"])
    else:
        cfg = get_config(config["arch"]).replace(**stated)
    for k, v in REFERENCE_ASSUMES.items():
        if getattr(cfg, k) != v:
            raise NotImplementedError(f"the reference assumes {k}={v!r}, "
                                      f"{cfg.name} has {getattr(cfg, k)!r}")
    sizes: Dict[str, object] = {k: getattr(cfg, k) for k in SIZE_KEYS}
    sizes["head_dim"] = cfg.resolved_head_dim
    if not rehearse:
        wrong = {k: (stated[k], sizes[k]) for k in SIZE_KEYS if stated[k] != sizes[k]}
        if wrong:
            raise ValueError(f"program config differs from the file: {wrong}")
    return cfg, sizes
