"""``chip_smoke.py`` rehearsed on the CPU at the smoke preset.

Keeps the chip smoke's phases and its last-line contract from rotting
between chip runs: the same phases run in process, on the CPU, and the
result line names the CPU.
"""

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_passes_and_prints_the_contract_line(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    for tag in ("[a] passed", "[b] passed", "[c] passed", "compile:"):
        assert any(line.startswith(tag) for line in out), tag
    last = json.loads(out[-1])
    assert set(last) == {"ok", "device"}
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"


def test_without_a_tpu_it_fails_before_any_phase(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert '"ok"' not in captured.out
