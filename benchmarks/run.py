"""Benchmark harness: one entry per paper table/figure + the roofline table.

Prints ``name,us_per_call,derived`` CSV per the repo contract, followed by
the detailed rows for each table.  ``python -m benchmarks.run [--fast]``.
"""

from __future__ import annotations

import argparse
import sys
import time


def _timed(name, fn, detail=True):
    t0 = time.time()
    rows, derived = fn()
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived}")
    if detail:
        for r in rows:
            print("   ", r)
    sys.stdout.flush()
    return rows, derived


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true", help="skip the Table II training run")
    p.add_argument("--no-detail", action="store_true")
    args = p.parse_args(argv)
    detail = not args.no_detail

    from benchmarks import tables
    from benchmarks.roofline_table import perf_deltas, roofline_rows

    print("name,us_per_call,derived")
    # serving engine: runs in --fast mode too (tracks the perf trajectory)
    from benchmarks import serving_bench

    _timed(
        "serving_engine_tok_s_8req",
        lambda: serving_bench.bench_rows()[:2], detail,
    )
    # paged engine: slot-bounded vs page-bounded admission concurrency
    _timed("paged_engine_concurrency", serving_bench.bench_paged_rows, detail)
    # mesh-sharded decode parity + trajectory (skips on one host device)
    _timed(
        "sharded_decode",
        lambda: serving_bench.bench_sharded_rows()[:2], detail,
    )

    # closed-loop redundancy-aware fleet vs always-offload (live engine)
    from benchmarks import trigger_bench

    _timed("trigger_decode_round_reduction", trigger_bench.bench_rows, detail)

    # partition planner: all architectures x network profiles (analytic)
    from benchmarks import partition_bench

    _timed("partition_planner_split_cells", partition_bench.bench_rows, detail)
    _timed("table1_vision_noise_degradation", tables.table1_vision_noise, detail)
    _timed("table3_simulation_speedup", tables.table3_simulation, detail)
    _timed("table4_realworld_speedup", tables.table4_real_world, detail)
    _timed("table5_ablation_rapid_ms", tables.table5_ablation, detail)
    _timed("hyperparam_optimum_theta", tables.hyperparameter_sweep, detail)
    if not args.fast:
        _timed("table2_redundancy_torque_corr", tables.table2_redundancy, detail)
    _timed(
        "roofline_baselines_n",
        lambda: ((roofline_rows() if detail else []), len(roofline_rows())),
        False,
    )
    _timed("perf_deltas_n", lambda: (perf_deltas() if detail else [], len(perf_deltas())), detail)

    from benchmarks.arch_report import arch_serving_rows

    _timed(
        "arch_serving_feasible_fixed_edge",
        lambda: (
            arch_serving_rows(),
            sum(1 for r in arch_serving_rows() if r["fixed_meets_400ms"]),
        ),
        detail,
    )


if __name__ == "__main__":
    main()
