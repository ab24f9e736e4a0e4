"""``bench/run.py --rehearse``: the whole run on the smoke preset, CPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]) if lines else None


def test_rehearsal_prints_a_result_line(capsys):
    run = load_run()
    rc = run.main(["--workload", CELLS[0], "--seed", str(2**31 + 7), "--seconds", "2",
                   "--trace", "0", "--rehearse"])
    assert rc == 0
    res = last_line(capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["attempted"] > 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["kind"] != "tpu"
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_rehearsal_reports_the_trace_window(capsys):
    run = load_run()
    rc = run.main(["--workload", CELLS[-1], "--seed", "3", "--seconds", "2",
                   "--trace", "1", "--rehearse"])
    assert rc == 0
    res = last_line(capsys)
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device trace: no device metric is printed
    assert "device_idle_share" not in res["metrics"]
    assert "queue_wait_p50_ms" in res["metrics"]


def test_without_a_tpu_the_run_fails_and_prints_nothing(capsys):
    run = load_run()
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_without_the_program_the_run_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_sweep_serves_each_fleet_size(capsys):
    run = load_run()
    rc = run.main(["--workload", CELLS[0], "--seed", "4", "--seconds", "2",
                   "--sweep", "16,48", "--rehearse"])
    assert rc == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["robots"] for r in rows] == [16, 48]
    assert rows[1]["attempted"] > rows[0]["attempted"] > 0
    for r in rows:
        assert 0.0 <= r["on_time_share"] <= 1.0 and r["compiles"] == 0
