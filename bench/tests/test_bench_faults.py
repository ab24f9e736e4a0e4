"""The check that decides ``correct`` catches a broken timed path.

Each test drives a whole rehearsal run (smoke preset, CPU) with one fault
planted in the program underneath, and sees ``correct`` come out false.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.models.model import Model

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def run_once(capsys, cell, seed=5):
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                     "--trace", "0", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced(cell, capsys, monkeypatch):
    decode_chunk = Model.decode_chunk

    def altered(self, params, logits, cache, n_steps, token_floor=0):
        toks, logits, cache = decode_chunk(self, params, logits, cache, n_steps, token_floor)
        # the next action bin up: in range, but not the greedy token
        bins = self.cfg.vocab_size - token_floor
        return token_floor + (toks - token_floor + 1) % bins, logits, cache

    monkeypatch.setattr(Model, "decode_chunk", altered)
    res = run_once(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]
    assert res["checks"]["tokens_out_of_range"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_decode_step_that_returns_its_state_unchanged(cell, capsys, monkeypatch):
    decode_step = Model.decode_step

    def stale(self, params, token, cache):
        logits, new = decode_step(self, params, token, cache)
        # the KV pools as they came in: the new token's keys never land
        return logits, {**new, "unit": cache["unit"]}

    monkeypatch.setattr(Model, "decode_step", stale)
    res = run_once(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_the_sound_program_passes(capsys):
    res = run_once(capsys, CELLS[0], seed=2**31 + 99)
    assert res["correct"] is True
    assert np.isfinite(res["checks"]["logit_gap"]["value"])
