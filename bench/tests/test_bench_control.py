"""The fp8 control, at a size a test run holds.

The control puts, in the served tokens' place, the tokens the float32
reference computed in fp8 puts first, and sends them through the same
judgement as a run.  On the chip, at each cell's own size and load, it
comes out not correct on every seed (PERF.md).  Here, at the smoke preset
(d_model 256, 2 layers), the same paths run end to end on the CPU.
"""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_far_above_the_program(cell, capsys):
    assert load_run().main(["--workload", cell, "--seconds", "2", "--readings", "11,12",
                            "--rehearse"]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["seed"] for r in rows] == [11, 12]
    for r in rows:
        prog, ctl = r["program"], r["control"]
        assert prog["correct"] is True and ctl["correct"] is False
        assert prog["tokens_out_of_range"] == 0 and prog["chunks_checked"] > 0
        assert ctl["logit_gap"] > 5 * prog["logit_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_run_is_not_correct(cell, capsys):
    assert load_run().main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
                            "--trace", "0", "--control", "--rehearse"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["chunks_checked"]["value"] == res["checks"]["chunks_checked"]["limit"]
