"""Fleet-scale serving benchmark: 1k+ robots per host, trace-driven load.

Two measurements:

  * **tick scaling** — host tick overhead of ``serve_fleet`` (``host_s`` =
    wall − decision core − engine) at 64 / 256 / 1024 robots against a
    fixed decode pool, which should grow sublinearly in fleet size;
  * **trace-driven SLO run** — ``runtime/fleet.py`` drives the real
    ``ContinuousBatchingScheduler`` with a Poisson or bursty arrival
    trace, episode churn, and full SLO accounting through the
    observability layer; percentiles land in ``BENCH_fleet.json``.

Run on the CPU, both are CPU timings, not chip speed.

Emits the ``name,us_per_call,derived`` CSV contract and merges raw
numbers into ``BENCH_fleet.json`` (keys carry the fleet size, so the CI
smoke at 256 robots never clobbers the committed 1k-robot record).

    PYTHONPATH=src python benchmarks/fleet_bench.py [--fleet 1024]
    PYTHONPATH=src python benchmarks/fleet_bench.py --smoke
"""

from __future__ import annotations

import json
import os

import jax

from repro.obs.clock import clock

SCAN_ROUNDS = 4


def _stack():
    from repro.configs import get_smoke_config
    from repro.data.pipeline import EpisodeTokenizer
    from repro.models.model import Model

    cfg = get_smoke_config("openvla-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return model, params, tok


def _update_json(path, out):
    path = os.path.abspath(path)
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged.update(
        {k: (round(v, 3) if isinstance(v, float) else v) for k, v in out.items()}
    )
    with open(path, "w") as f:
        json.dump(merged, f, indent=2)


def bench_scaling_rows(fleets=(64, 256, 1024), steps: int = 40):
    """Host tick overhead of ``serve_fleet`` as the fleet grows 16x.

    The decode pool is fixed, so ``host_s`` growth (wall minus decision
    core minus engine) is pure orchestration cost.  ``sublinear_ratio`` =
    (host-ms-per-tick growth) / (fleet growth); < 1 means the tick scales
    sublinearly in fleet size — the PR's win condition.
    """

    from repro.launch.serve import serve_fleet

    model, params, tok = _stack()
    out = {}
    rows = []
    ms = {}
    for n in fleets:
        common = dict(
            n_robots=n, max_steps=steps, max_slots=8,
            scan_rounds=SCAN_ROUNDS, trigger="rapid", seed=0, verbose=False,
        )
        serve_fleet(model, params, tok, **{**common, "max_steps": 12})  # warm
        res = serve_fleet(model, params, tok, **common)
        ms[n] = res["host_s"] / res["steps"] * 1e3
        out[f"f{n}_host_ms_tick"] = ms[n]
        rows.append(f"fleet={n}: {ms[n]:.3f} host-ms/tick")
    lo, hi = min(fleets), max(fleets)
    ratio = (ms[hi] / ms[lo]) / (hi / lo)
    out["tick_sublinear_ratio"] = ratio
    rows.append(
        f"host tick overhead grew {ms[hi] / ms[lo]:.2f}x over a "
        f"{hi // lo}x fleet (sublinear_ratio={ratio:.3f} — <1 is sublinear)"
    )
    return rows, out


def bench_trace_rows(
    n_robots: int = 1024,
    horizon: int = 320,
    arrivals: str = "poisson",
    mean_dwell: float = 240.0,
):
    """Trace-driven fleet SLO run against the real scheduler."""

    from repro.obs import Observability
    from repro.runtime.fleet import make_trace, serve_trace

    model, params, tok = _stack()
    trace = make_trace(
        n_robots, horizon, arrivals=arrivals, mean_dwell=mean_dwell, seed=0
    )
    res = serve_trace(
        model, params, tok, trace, horizon=horizon,
        max_slots=16, scan_rounds=SCAN_ROUNDS, trigger="rapid",
        obs=Observability(trace=False), verbose=False,
    )
    slo = res["slo"]
    pre = f"f{n_robots}_{arrivals}"
    out = {
        f"{pre}_horizon": horizon,
        f"{pre}_ticks_per_s": res["ticks_per_s"],
        f"{pre}_joined": res["joined"],
        f"{pre}_left": res["left"],
        f"{pre}_churn_cancels": res["churn_cancels"],
        f"{pre}_peak_active_robots": res["peak_active_robots"],
        f"{pre}_peak_batch": res["peak_batch"],
        f"{pre}_completions": res["completions"],
        f"{pre}_chunk_p50_ms": slo["chunk_latency_ms"].get("p50", 0.0),
        f"{pre}_chunk_p90_ms": slo["chunk_latency_ms"].get("p90", 0.0),
        f"{pre}_chunk_p99_ms": slo["chunk_latency_ms"].get("p99", 0.0),
        f"{pre}_queue_wait_p50_ms": slo["queue_wait_ms"].get("p50", 0.0),
        f"{pre}_queue_wait_p99_ms": slo["queue_wait_ms"].get("p99", 0.0),
        f"{pre}_goodput_chunks_s": slo["goodput_chunks_s"],
        f"{pre}_cancel_rate": slo["cancel_rate"],
        f"{pre}_replay_fraction": slo["replay_fraction"],
        f"{pre}_pool_high_water": slo["pool_high_water"],
        "fleet_n_robots": n_robots,
    }
    rows = [
        f"{arrivals} arrivals, {n_robots} robots over {horizon} ticks "
        f"(joined={res['joined']} left={res['left']} "
        f"churn_cancels={res['churn_cancels']}): "
        f"{res['ticks_per_s']:.1f} ticks/s, "
        f"{res['completions']} chunks completed",
        f"SLO: chunk p50/p99={out[f'{pre}_chunk_p50_ms']:.0f}/"
        f"{out[f'{pre}_chunk_p99_ms']:.0f}ms "
        f"queue p50/p99={out[f'{pre}_queue_wait_p50_ms']:.0f}/"
        f"{out[f'{pre}_queue_wait_p99_ms']:.0f}ms "
        f"goodput={out[f'{pre}_goodput_chunks_s']:.2f} chunks/s "
        f"cancel_rate={out[f'{pre}_cancel_rate']:.3f} "
        f"pool_high_water={out[f'{pre}_pool_high_water']}",
    ]
    return rows, out


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--fleet", type=int, default=1024,
                   help="fleet size for the trace run")
    p.add_argument("--horizon", type=int, default=320,
                   help="trace-run length in control ticks")
    p.add_argument("--arrivals", choices=("poisson", "bursty"),
                   default="poisson")
    p.add_argument("--smoke", action="store_true",
                   help="CI shape: 256 robots, short horizon, 64->256 scaling")
    p.add_argument("--skip-scaling", action="store_true")
    args = p.parse_args(argv)
    if args.smoke:
        args.fleet = min(args.fleet, 256)
        args.horizon = min(args.horizon, 160)

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_fleet.json")
    print("name,us_per_call,derived")

    if not args.skip_scaling:
        fleets = (64, 256) if args.smoke else (64, 256, 1024)
        t0 = clock()
        rows, scale_out = bench_scaling_rows(fleets=fleets)
        _update_json(path, scale_out)
        print(f"fleet_tick_scaling,{(clock() - t0) * 1e6:.0f},"
              f"{round(scale_out['tick_sublinear_ratio'], 3)}")
        for r in rows:
            print("   ", r)

    t0 = clock()
    rows, trace_out = bench_trace_rows(
        n_robots=args.fleet, horizon=args.horizon, arrivals=args.arrivals
    )
    _update_json(path, trace_out)
    print(f"fleet_trace_slo,{(clock() - t0) * 1e6:.0f},{args.fleet}")
    for r in rows:
        print("   ", r)


if __name__ == "__main__":
    main()
