"""Partition-planner sweep: every zoo architecture × network profile.

For each of the 11 assigned architectures and each channel regime
(LAN / WAN / congested), build the block-level inference graph, run the
cut-point planner under the simulated RAPID trigger's offload fraction and
a Jetson-class 8 GB edge budget, and record the chosen deployment against
the two single-device anchors.  The planner is analytic (graph + calibrated
latency model), so the full 33-cell sweep costs milliseconds.

Emits the ``name,us_per_call,derived`` CSV contract and writes
``BENCH_partition.json``; ``derived`` is the number of cells where a
genuine SPLIT (layers on both sides) is optimal.

Each (arch, profile) additionally gets a HETEROGENEOUS row: a 6-robot
fleet whose realized offload fractions spread around the trigger-sim base
is assigned per-robot cuts (``assign_cuts``, per-cut staleness pricing,
k_max 3) and compared against the best single global cut at the same
telemetry — the assignment is never worse by construction, and the row
records how much the frontier saves.

Every cell also carries the 2-D plan — (cut layer x placement): expert
offload, monitor-resident prefixes, encoder staging — plus its executable
restriction (plain cuts + expert-offload lanes).  Both are never worse
than the 1-D plan because the 1-D cuts are a subset of the 2-D space;
``plan2d_moved_cells`` counts the cells a placement moves off
``cloud_only`` to a strictly faster deployment.

    PYTHONPATH=src python benchmarks/partition_bench.py
    PYTHONPATH=src python benchmarks/partition_bench.py --check-2d-never-worse
"""

from __future__ import annotations

import json
import os
import time

# deterministic per-robot spread for the heterogeneous fleet row: scaled
# multiples of the measured base fraction, spanning an always-offload robot
# down to a near-fully-redundant one (clipped into [0.02, 1])
HETERO_FLEET_SPREAD = (3.0, 2.0, 1.0, 0.5, 0.2, 0.065)


def _offload_fraction() -> float:
    """The live kinematic trigger's simulated offload rate (arch-independent)."""

    from repro.partition.planner import DEFAULT_OFFLOAD_FRACTION

    try:
        from repro.runtime.engine import evaluate_strategy

        return float(evaluate_strategy("rapid")["offload_fraction"])
    except Exception:
        return DEFAULT_OFFLOAD_FRACTION


def bench_rows(offload_fraction=None, out_path=None):
    from repro.configs import ARCH_IDS, get_config
    from repro.partition.graph import build_graph
    from repro.partition.planner import (
        NETWORK_PROFILES, assign_cuts, plan_partition,
    )

    if offload_fraction is None:
        offload_fraction = _offload_fraction()
    fleet = [
        min(max(offload_fraction * s, 0.02), 1.0) for s in HETERO_FLEET_SPREAD
    ]

    out = {"offload_fraction": round(offload_fraction, 4)}
    rows = []
    n_split = 0
    n_hetero = 0
    n_2d_better = 0
    n_2d_moved = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        graph = build_graph(cfg)
        cells = []
        hetero_cells = []
        cells_2d = []
        for profile, channel in NETWORK_PROFILES.items():
            plan = plan_partition(
                cfg, channel=channel,
                offload_fraction=offload_fraction, graph=graph,
            )
            pipe = plan_partition(
                cfg, channel=channel,
                offload_fraction=offload_fraction, graph=graph, pipelined=True,
            )
            plan2d = plan_partition(
                cfg, channel=channel,
                offload_fraction=offload_fraction, graph=graph, plan_2d=True,
            )
            plan2d_exec = plan_partition(
                cfg, channel=channel,
                offload_fraction=offload_fraction, graph=graph, plan_2d=True,
                executable_only=True,
            )
            n_split += plan.mode == "split"
            n_2d_better += plan2d.total_ms < plan.total_ms - 1e-9
            moved = (
                plan.mode == "cloud_only"
                and plan2d.mode != "cloud_only"
                and plan2d.total_ms < plan.total_ms - 1e-9
            )
            n_2d_moved += moved
            out[f"{arch}|{profile}"] = {
                "mode": plan.mode,
                "pipelined_mode": pipe.mode,
                "pipelined_total_ms": round(pipe.total_ms, 2),
                "cut": plan.cut,
                "cut_layer": plan.cut_layer,
                "edge_gb": round(plan.edge_gb, 3),
                "cloud_gb": round(plan.cloud_gb, 3),
                "total_ms": round(plan.total_ms, 2),
                "edge_ms": round(plan.edge_ms, 2),
                "net_ms": round(plan.net_ms, 2),
                "cloud_ms": round(plan.cloud_ms, 2),
                "edge_only_ms": (
                    round(plan.edge_only_ms, 2)
                    if plan.edge_only_ms is not None else None
                ),
                "cloud_only_ms": (
                    round(plan.cloud_only_ms, 2)
                    if plan.cloud_only_ms is not None else None
                ),
                # 2-D plan: the (cut layer x placement) optimum and the
                # executable restriction serving realizes (never worse
                # than the 1-D total above, by construction)
                "plan2d_mode": plan2d.mode,
                "plan2d_placement": plan2d.placement,
                "plan2d_cut_layer": plan2d.cut_layer,
                "plan2d_expert_offload": list(plan2d.expert_offload),
                "plan2d_total_ms": round(plan2d.total_ms, 2),
                "plan2d_net_expert_ms": round(plan2d.net_expert_ms, 2),
                "plan2d_moved_off_cloud_only": moved,
                "plan2d_exec_mode": plan2d_exec.mode,
                "plan2d_exec_total_ms": round(plan2d_exec.total_ms, 2),
            }
            cells.append(f"{profile}:{plan.mode}@{plan.total_ms:.0f}ms")
            tag = plan2d.placement or plan2d.mode
            cells_2d.append(
                f"{profile}:{tag}@{plan2d.total_ms:.0f}ms"
                f"({plan2d.total_ms - plan.total_ms:+.0f})"
            )

            # heterogeneous fleet row: per-robot cuts vs the best single
            # global cut at the same (spread) telemetry
            a = assign_cuts(
                fleet, k_max=3, cfg=cfg, graph=graph, channel=channel,
            )
            n_hetero += len(a.frontier) >= 2
            out[f"hetero|{arch}|{profile}"] = {
                "fractions": [round(f, 4) for f in a.fractions],
                "cuts": list(a.cuts),
                "cut_layers": list(a.cut_layers),
                "frontier": list(a.frontier),
                "fleet_total_ms": round(a.total_ms, 2),
                "best_single_cut": a.best_single_cut,
                "best_single_ms": round(a.best_single_ms, 2),
                "saved_ms": round(a.best_single_ms - a.total_ms, 2),
            }
            hetero_cells.append(
                f"{profile}:{len(a.frontier)}cuts"
                f"{'+' if len(a.frontier) >= 2 else '='}"
                f"{a.best_single_ms - a.total_ms:.0f}ms"
            )
        rows.append(f"{arch}: " + " ".join(cells))
        rows.append(f"{arch} [2-D]: " + " ".join(cells_2d))
        rows.append(f"{arch} [hetero fleet]: " + " ".join(hetero_cells))

    if out_path is None:
        out_path = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "BENCH_partition.json")
        )
    out["hetero_frontier_cells"] = n_hetero
    out["plan2d_better_cells"] = n_2d_better
    out["plan2d_moved_cells"] = n_2d_moved
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    return rows, n_split


def check_2d_never_worse() -> int:
    """CI gate: the 2-D plan (and its executable restriction) must be no
    worse than the 1-D plan on every architecture x profile cell.

    Analytic — no model build — so the full 33-cell sweep gates in
    milliseconds.  Returns a process exit code (0 = all cells hold).
    """

    from repro.configs import ARCH_IDS, get_config
    from repro.partition.graph import build_graph
    from repro.partition.planner import NETWORK_PROFILES, plan_partition

    f = _offload_fraction()
    bad = []
    n_cells = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        graph = build_graph(cfg)
        for profile, channel in NETWORK_PROFILES.items():
            n_cells += 1
            p1 = plan_partition(
                cfg, channel=channel, offload_fraction=f, graph=graph,
            )
            for exec_only in (False, True):
                p2 = plan_partition(
                    cfg, channel=channel, offload_fraction=f, graph=graph,
                    plan_2d=True, executable_only=exec_only,
                )
                if p2.total_ms > p1.total_ms + 1e-9:
                    bad.append(
                        f"{arch}|{profile}"
                        f"{' (executable)' if exec_only else ''}: "
                        f"2-D {p2.total_ms:.2f}ms > 1-D {p1.total_ms:.2f}ms"
                    )
    if bad:
        print(f"2-D never-worse VIOLATED on {len(bad)} cell(s):")
        for b in bad:
            print("   ", b)
        return 1
    print(f"2-D never-worse holds on all {n_cells} cells "
          f"(plain and executable-only plans, f={f:.4f})")
    return 0


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--check-2d-never-worse", action="store_true",
                   help="CI gate: assert the 2-D plan is never worse than "
                        "the 1-D plan on every arch x profile cell")
    args = p.parse_args(argv)
    if args.check_2d_never_worse:
        raise SystemExit(check_2d_never_worse())
    print("name,us_per_call,derived")
    t0 = time.time()
    rows, derived = bench_rows()
    print(f"partition_planner_split_cells,{(time.time() - t0) * 1e6:.0f},{derived}")
    for r in rows:
        print("   ", r)


if __name__ == "__main__":
    main()
