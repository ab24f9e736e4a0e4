"""The per-layer metrics read from the scheduler's own registry: boundary
host spans and live-row counters."""

import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.obs import MetricsRegistry
from spec import reader

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run(registry):
    return SimpleNamespace(obs=registry)


def test_boundary_host_ms_sums_three_spans_per_dispatch():
    m = MetricsRegistry()
    for name, values in (("sched.admit", [3.0, 1.0, 0.5]),   # one idle admit
                         ("sched.dispatch", [2.0, 4.0]),
                         ("sched.sync", [400.0, 390.0]),      # not host work
                         ("sched.harvest", [6.0, 8.0])):
        for v in values:
            m.histogram("span_ms", span=name).observe(v)
    assert reader("boundary_host_ms")(_run(m)) == pytest.approx((4.5 + 6.0 + 14.0) / 2)


def test_live_row_share_over_all_four_states():
    m = MetricsRegistry()
    for state, n in (("live", 600), ("past", 100), ("idle", 250), ("cancelled", 50)):
        m.counter("sched.row_tokens", state=state).inc(n)
    assert reader("live_row_share")(_run(m)) == pytest.approx(60.0)


def test_readers_find_nothing_without_spans_or_counters():
    empty = _run(MetricsRegistry())
    assert reader("boundary_host_ms")(empty) is None
    assert reader("live_row_share")(empty) is None
    # a registry with other metrics (a program without the spans) reads nothing too
    m = MetricsRegistry()
    m.histogram("serve.queue_wait_ms").observe(1.0)
    m.histogram("span_ms", span="sched.admit").observe(1.0)
    m.counter("sched.row_tokens", state="live")
    assert reader("boundary_host_ms")(_run(m)) is None
    assert reader("live_row_share")(_run(m)) is None


def test_traced_rehearsal_prints_both(capsys):
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2",
                   "--trace", "1", "--rehearse"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["boundary_host_ms"]["value"] > 0
    assert res["metrics"]["boundary_host_ms"]["unit"] == "ms"
    assert 0 < res["metrics"]["live_row_share"]["value"] <= 100
    assert res["metrics"]["live_row_share"]["unit"] == "%"
