"""End-to-end edge-cloud co-inference with a REAL model in the loop.

The RAPID dispatcher monitors simulated manipulator kinematics; every
dispatch runs an actual prefill + autoregressive action-token decode through
the OpenVLA-style backbone (the smoke preset; ``--arch X --full`` serves X at
its published widths, ``--layers N`` cuts its depth).  The chunk decode is
a single fused on-device ``lax.scan`` — no per-token host syncs.

With ``--fleet N`` the same cloud engine serves N robots through the
continuous-batching scheduler: dispatch triggers become requests that join
in-flight decode batches, and chunks arrive back a few rounds later.  The
engine runs on the paged KV substrate — admission is bounded by free KV
pages, not a slot count (``--paged`` probes the same substrate for a single
robot).

With ``--partition auto`` the partition planner picks the
compatibility-optimal edge-cloud cut for the full architecture and the
episode is served through the split executor (edge prefix -> shipped cut
activations -> cloud suffix) whenever the plan keeps layers on both sides.
Combined with ``--fleet N`` it serves a MIXED fleet: every second robot goes
through the split, and their cloud suffixes share decode rounds (and KV
pages) with the cloud-only robots.

With ``--assign-cuts`` the loop closes HETEROGENEOUSLY: episode 1 gathers
each robot's realized offload fraction, ``assign_cuts`` maps every robot to
its own cut from a small frontier (high-redundancy robots get deeper edge
prefixes), and episode 2 serves the fleet with per-robot cuts — several
distinct cuts decode in the same scheduler rounds against one KV page pool.

With ``--arrivals poisson|bursty`` the fleet is served through the
trace-driven harness instead: robots join at sampled arrival ticks, dwell
for an exponential episode length, and leave — in-flight work is cancelled
and KV pages are reclaimed without an engine reset.

    PYTHONPATH=src python examples/ecc_serving.py --task drawer_open
    PYTHONPATH=src python examples/ecc_serving.py --fleet 4
    PYTHONPATH=src python examples/ecc_serving.py --fleet 64 --arrivals poisson
    PYTHONPATH=src python examples/ecc_serving.py --partition auto --network lan
    PYTHONPATH=src python examples/ecc_serving.py --fleet 4 --partition auto --network lan
    PYTHONPATH=src python examples/ecc_serving.py --fleet 6 --trigger rapid --assign-cuts
    PYTHONPATH=src python examples/ecc_serving.py --fleet 4 --scan-rounds 4 --profile /tmp/trace
    PYTHONPATH=src python examples/ecc_serving.py --fleet 4 --scan-rounds 4 \
        --trace-out trace.json --metrics-json metrics.json
"""

import argparse

import jax
import numpy as np

from repro.data.pipeline import EpisodeTokenizer
from repro.launch.serve import (
    add_model_args,
    build_policy,
    serve_episode,
    serve_fleet,
    serving_config,
)
from repro.models.model import Model


def main(argv=None):
    p = argparse.ArgumentParser()
    add_model_args(p)
    p.add_argument("--task", default="pick_place",
                   choices=["pick_place", "drawer_open", "peg_insertion"])
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--fleet", type=int, default=0,
                   help="serve N robots through the continuous-batching scheduler")
    p.add_argument("--partition", default="none",
                   help="'none', 'auto' (partition planner), or edge layer count")
    p.add_argument("--network", default="wan", choices=["lan", "wan", "congested"],
                   help="channel regime the partition planner prices")
    p.add_argument("--plan-2d", action="store_true",
                   help="plan over (cut layer x placement): expert offload "
                        "+ encoder/monitor staging; MoE fleets also serve "
                        "an expert-offload lane alongside the planned cut")
    p.add_argument("--paged", action="store_true",
                   help="single-robot decode through the paged KV substrate")
    p.add_argument("--arrivals", default=None, choices=["poisson", "bursty"],
                   help="serve --fleet N through the trace-driven churn "
                        "harness (robots join/leave mid-run) instead of a "
                        "fixed fleet")
    p.add_argument("--mean-dwell", type=float, default=240.0,
                   help="mean episode dwell in ticks for --arrivals runs")
    p.add_argument("--trigger", default="always", choices=["always", "rapid"],
                   help="fleet dispatch policy: always-offload or the "
                        "closed-loop redundancy-aware RAPID trigger")
    p.add_argument("--assign-cuts", action="store_true",
                   help="re-assign per-robot cuts from episode 1's realized "
                        "offload fractions and serve episode 2 with a "
                        "heterogeneous cut frontier")
    p.add_argument("--k-max", type=int, default=3,
                   help="max distinct concurrently-active cuts")
    p.add_argument("--defer-hot", type=float, default=None,
                   help="cancellation-aware admission: preempt-rate "
                        "threshold above which a preempting robot's "
                        "admission is held one round")
    p.add_argument("--scan-rounds", type=int, default=1,
                   help="decode rounds per jitted scan window; >1 keeps the "
                        "decode loop device-resident between host syncs")
    p.add_argument("--sharded", action="store_true",
                   help="shard the cloud engine (page pools, decode rows, "
                        "params) over every host device; test multi-device "
                        "on CPU with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N")
    p.add_argument("--disaggregate-prefill", action="store_true",
                   help="run prompt prefill on its own device, handing off "
                        "to the decode pool via the paged cache at window "
                        "boundaries")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="wrap the fleet serve loop in jax.profiler.trace "
                        "writing to DIR, and print per-window host-gap time")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome-trace/Perfetto JSON of request "
                        "lifecycles (fleet mode; load in ui.perfetto.dev)")
    p.add_argument("--metrics-json", default=None, metavar="PATH",
                   help="dump the fleet run's metrics registry as flat JSON")
    args = p.parse_args(argv)

    cfg = serving_config(args.arch, args.full, args.layers)
    print(f"cloud model: {cfg.name} ({cfg.num_layers}L d={cfg.d_model})")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)

    if args.fleet and args.arrivals:
        # trace-driven churn harness: robots join, dwell, and leave; the
        # engine reclaims their pages without a reset between episodes
        from repro.obs import Observability
        from repro.partition.planner import NETWORK_PROFILES
        from repro.runtime.fleet import make_trace, serve_trace

        trace = make_trace(
            args.fleet, args.steps, args.arrivals,
            mean_dwell=args.mean_dwell, seed=0,
        )
        obs = Observability(trace=False) if args.metrics_json else None
        out = serve_trace(
            model, params, tok, trace, args.steps,
            trigger=args.trigger,
            channel=NETWORK_PROFILES[args.network],
            scan_rounds=args.scan_rounds, obs=obs,
        )
        print(f"churn: {out['joined']} joined, {out['left']} left early "
              f"({out['churn_cancels']} in-flight cancels), peak "
              f"{out['peak_active_robots']} active robots")
        print(f"served {out['completions']} chunks at "
              f"{out['ticks_per_s']:.1f} ticks/s")
        if out["slo"] is not None:
            p99 = out["slo"]["chunk_latency_ms"]["p99"]
            print(f"chunk latency p99: {p99:.1f} ms")
        print(f"kv pages: high-water {out['pool'].high_water}, "
              f"in use after drain {out['pool'].pages_in_use}")
        if args.metrics_json:
            import json

            with open(args.metrics_json, "w") as f:
                json.dump(obs.metrics.to_json(), f, indent=1)
            print(f"metrics: -> {args.metrics_json}")
        return

    if args.fleet:
        from repro.launch.serve import fleet_executor
        from repro.obs import Observability
        from repro.partition.planner import NETWORK_PROFILES

        want_obs = bool(args.trace_out or args.metrics_json)
        mk_obs = (
            (lambda: Observability(trace=args.trace_out is not None))
            if want_obs else (lambda: None)
        )
        executor = None
        split = []
        robot_cuts = None
        if args.partition != "none":
            executor = fleet_executor(
                model, params, args.arch, args.partition, args.network,
                plan_2d=args.plan_2d,
            )
            if executor is not None:
                split = list(range(1, args.fleet, 2))
                print(f"mixed fleet: robots {split} serve through the split")
            if args.plan_2d and executor is not None and split:
                # 2-D serving on MoE archs: alternate split robots between
                # the planned cut lane and the best expert-offload point
                from repro.launch.serve import plan_expert_lane

                lane = plan_expert_lane(
                    model, params, args.arch, args.network, base=executor
                )
                if lane is not None and lane.lane_key != executor.lane_key:
                    robot_cuts = {
                        r: (executor.lane_key if i % 2 == 0 else lane.lane_key)
                        for i, r in enumerate(split)
                    }
                    exp = [r for r, c in robot_cuts.items()
                           if isinstance(c, tuple)]
                    print(f"expert-offload lane robots: {exp}")
        import contextlib

        mesh = prefill_group = None
        if args.disaggregate_prefill:
            from repro.launch.mesh import split_device_groups

            prefill_group, decode_group = split_device_groups(prefill=1)
            print(f"disaggregated prefill: {prefill_group[0]}")
        if args.sharded:
            from repro.launch.mesh import make_host_mesh, make_test_mesh

            if prefill_group is not None and len(decode_group) < len(jax.devices()):
                # shard decode over its own group; prefill keeps its device
                mesh = make_test_mesh(data=len(decode_group), devices=decode_group)
            else:
                mesh = make_host_mesh()
            print(f"sharded engine: mesh {dict(mesh.shape)}")
        profiling = (
            jax.profiler.trace(args.profile)
            if args.profile else contextlib.nullcontext()
        )
        with profiling:
            out = serve_fleet(
                model, params, tok, n_robots=args.fleet, max_steps=args.steps,
                channel=NETWORK_PROFILES[args.network],
                partition_executor=executor, split_robots=split,
                robot_cuts=robot_cuts,
                trigger=args.trigger, defer_hot_admission=args.defer_hot,
                scan_rounds=args.scan_rounds, obs=mk_obs(),
                mesh=mesh, prefill_group=prefill_group,
            )
        if args.assign_cuts:
            # close the loop heterogeneously: per-robot cuts from episode
            # 1's realized fractions, served in episode 2 on a cut frontier
            from repro.launch.serve import assign_fleet_cuts

            executor2, robot_cuts, assignment = assign_fleet_cuts(
                model, params, args.arch, out["telemetry"], args.network,
                k_max=args.k_max,
            )
            if robot_cuts:
                out = serve_fleet(
                    model, params, tok, n_robots=args.fleet,
                    max_steps=args.steps,
                    channel=NETWORK_PROFILES[args.network],
                    partition_executor=executor2, robot_cuts=robot_cuts,
                    trigger=args.trigger,
                    defer_hot_admission=args.defer_hot,
                    scan_rounds=args.scan_rounds, obs=mk_obs(),
                    mesh=mesh, prefill_group=prefill_group,
                )
                print(f"episode 2 robot cuts: {out['robot_cuts']} "
                      f"({len(out['active_cuts'])} distinct; "
                      f"{out['hetero_rounds']} hetero decode rounds)")
        obs = out.get("obs")
        if obs is not None:
            if args.trace_out:
                obs.trace.write(args.trace_out)
                print(f"trace: {obs.trace.n_events} events -> {args.trace_out}")
            if args.metrics_json:
                import json

                with open(args.metrics_json, "w") as f:
                    json.dump(obs.metrics.to_json(), f, indent=1)
                print(f"metrics: -> {args.metrics_json}")
        served = len(out["service_rounds"])
        pool = out["pool"]
        tel = out["telemetry"]
        print(f"chunks served: {served} (peak decode batch {out['peak_batch']}, "
              f"{out['decode_rounds']} decode rounds)")
        if args.profile or args.scan_rounds > 1:
            print(f"host orchestration: {out['scan_windows']} scan windows, "
                  f"{out['host_gap_ms']:.2f} ms host gap per window "
                  f"({args.scan_rounds} rounds/window)")
        if args.profile:
            print(f"profiler trace written to {args.profile}")
        print(f"kv pages: high-water {pool.high_water}"
              f"/{pool.pages_in_use + pool.pages_free}")
        if args.trigger == "rapid":
            print(f"redundancy-aware loop: {int(tel.replays.sum())} cached-chunk "
                  f"replays, {int(tel.cancels.sum())} in-flight cancels, "
                  f"realized f_off={tel.fleet_offload_fraction():.2f} "
                  f"(per-robot {[round(float(f), 2) for f in tel.offload_fractions()]})")
        if split or out["split_robots"]:
            print(f"rounds with both kinds decoding: {out['mixed_rounds']}")
        if out["deferred"]:
            print(f"cancellation-aware admission: {out['deferred']} deferred")
        print(f"mean offload net: {np.mean(out['offload_ms']):.1f} ms (jittered)"
              if out["offload_ms"] else "no offloads")
        print(f"actions executed: {out['actions'].shape}")
        if args.trigger == "rapid" and args.partition != "none":
            # close the planner loop: re-price the cut with the fleet's
            # realized offload fraction instead of the trigger-sim constant
            from repro.launch.serve import replan_from_telemetry

            replan_from_telemetry(args.arch, tel, args.network)
        return

    policy, _ = build_policy(
        model, params, tok, args.arch, args.partition, args.network,
        paged=args.paged,
    )
    out = serve_episode(policy, task=args.task, max_steps=args.steps)
    frac = out["offloads"] / max(out["steps"] // 8, 1)
    print(f"offload fraction: {frac:.2f} of chunk decisions")
    net_log = getattr(policy, "net_ms_log", None)
    if net_log:
        print(f"modeled channel cost: {np.mean(net_log):.1f} ms per offload")
    print(f"actions executed: {out['actions'].shape}")


if __name__ == "__main__":
    main()
