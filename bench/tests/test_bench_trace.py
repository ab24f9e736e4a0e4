"""The reduction from trace events to per-layer numbers."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import tracing
from costs import paged_attn_cost, token_flops
from spec import reader

BENCH = Path(__file__).resolve().parents[1]

MS = 1_000_000  # ns


def test_busy_union_merges_overlaps_and_counts_gaps_once():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert tracing.union(ev) == [(0, 15), (30, 35)]
    assert tracing.busy_ns(ev) == 20


def test_clip_cuts_events_to_the_window():
    ev = [("a", -5, 10), ("b", 8, 10), ("c", 20, 3)]
    assert tracing.clip(ev, 0, 12) == [("a", 0, 5), ("b", 8, 4)]


def test_window_groups_and_the_gaps_between_them():
    # two windows of two programs each, an admission program between them
    mods = [("jit_window", 0, 10 * MS), ("jit_fleet", 10 * MS, 5 * MS),
            ("jit_admit", 16 * MS, 2 * MS),
            ("jit_window", 19 * MS, 10 * MS), ("jit_fleet", 29 * MS, 5 * MS),
            ("jit_window", 40 * MS, 10 * MS)]
    groups = tracing.window_groups(mods, ["jit_window", "jit_fleet"])
    assert groups == [(0, 15 * MS), (19 * MS, 34 * MS), (40 * MS, 50 * MS)]
    assert tracing.gaps_between(groups) == [4 * MS, 6 * MS]


def test_leaves_drop_a_loop_that_spans_its_body():
    ops = [("while", 0, 10), ("fusion", 1, 3), ("kernel", 5, 4), ("copy", 12, 1)]
    assert tracing.leaves(ops) == [("fusion", 1, 3), ("kernel", 5, 4), ("copy", 12, 1)]
    assert tracing.top_ops(ops, 1) == [["kernel", pytest.approx(4e-9)]]


def test_top_ops_and_idle_gaps_named_by_host_span():
    ops = [("fusion", 0, 4), ("kernel", 4, 2), ("fusion", 10, 4)]
    assert tracing.top_ops(ops, 1) == [["fusion", pytest.approx(8e-9)]]
    host = [("bench.window", 0, 20), ("bench.step", 5, 6), ("bench.sleep", 14, 6)]
    gaps = tracing.idle_gaps(ops, host, 0, 20)
    assert gaps == [["sleep", pytest.approx(6e-9)], ["step", pytest.approx(4e-9)]]


def test_readers_on_a_two_window_trace():
    """Two 100 ms windows 20 ms apart, each with 40 ms in the kernel, over
    a 250 ms traced window; 16 rows live at context 40."""

    cfg = json.loads((BENCH / "configs" / "danube3-4b.json").read_text())
    programs = json.loads((BENCH / "programs.json").read_text())
    kern = "custom-call.1 custom-call " + programs["paged_kernel"]
    modules = [("jit_window", 0, 100 * MS), ("jit_admit", 105 * MS, 5 * MS),
               ("jit_window", 120 * MS, 100 * MS)]
    ops = [("while.1 while", 0, 100 * MS), ("fusion.1 fusion", 0, 60 * MS),
           (kern, 60 * MS, 40 * MS), ("fusion.2 fusion", 105 * MS, 5 * MS),
           ("while.1 while", 120 * MS, 100 * MS), ("fusion.1 fusion", 120 * MS, 60 * MS),
           (kern, 180 * MS, 40 * MS)]
    ctx = np.full(16 * 2, 40, np.int64)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = SimpleNamespace(trace={"modules": modules, "ops": ops, "chips": 1},
                          t0=0, t1=250 * MS, cfg=cfg, peak=peak, windows=2,
                          programs=programs, useful_ctx=ctx)
    assert reader("decode_window_ms")(run) == pytest.approx(100.0)
    assert reader("window_gap_ms")(run) == pytest.approx(20.0)
    assert reader("device_idle_share")(run) == pytest.approx(100 * (1 - 205 / 250))
    flops = 32 * token_flops(cfg, 40)
    assert reader("decode_mfu")(run) == pytest.approx(100 * flops / (0.2 * 197e12))
    f, b = paged_attn_cost(cfg, ctx.tolist())
    roof = max(24 * f / 197e12, 24 * b / 819e9)
    assert reader("paged_attn_roofline")(run) == pytest.approx(100 * roof / 0.08)
