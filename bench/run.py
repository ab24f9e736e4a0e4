"""One run of one benchmark cell: an open-loop robot fleet against the
cloud engine on one TPU chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --workload <name> --rehearse ...   # smoke preset, CPU
    python bench/run.py --workload <name> --seed <n> --seconds <s> --control
    python bench/run.py --workload <name> --readings 1,2,3 --seconds 5
    python bench/run.py --workload <name> --sweep 400,800 --seconds 8

The system under test is ``ContinuousBatchingScheduler``, driven through
``submit_batch`` / ``cancel_batch`` / ``step``.  Set-up makes the weights
from the seed, builds the engine, runs every admission size and then the
cell's own traffic to warm every program, and only then opens the measured
window.  After the window it checks a sample of the served chunks against
the plain float32 reference (``reference.py``).

The last line of standard output is the result JSON.  Without a TPU (and
without ``--rehearse``) the run exits non-zero before any result.
``--control`` judges, in the served tokens' place, the tokens the fp8
control puts first (``correct`` must come out false).  ``--readings``
prints, per seed, the program's check beside the control's; ``--sweep``
serves several fleet sizes in one process to find the knee.  Neither of
these two prints a result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

N_STATE_BINS, N_ACTION_BINS, STATE_CLIP = 128, 256, 4.0


class CompileCount:
    """Programs compiled or loaded from the persistent cache."""

    def __init__(self, jax):
        self.jax = jax
        self.n = 0

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def __enter__(self):
        self.jax.monitoring.register_event_duration_secs_listener(self._duration)
        self.jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        self.jax.monitoring.unregister_event_duration_listener(self._duration)
        self.jax.monitoring.unregister_event_listener(self._event)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compile_cache(jax) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/`` in the
    checkout; every program is kept, the small eager ones too."""

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def encode_prompt(qd: np.ndarray, tau: np.ndarray, vocab: int) -> np.ndarray:
    """The 14 state tokens of one observation, as the engine encodes them:
    each joint's velocity, then its torque, in 128 bins below the action
    bins at the top of the vocabulary."""

    base = vocab - N_ACTION_BINS - N_STATE_BINS
    z = np.clip(np.concatenate([qd, tau], -1) / STATE_CLIP, -1.0, 1.0)
    return base + ((z + 1.0) / 2.0 * (N_STATE_BINS - 1)).astype(np.int64)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def build_engine(model, params, tok, serving: dict, traffic: dict):
    """The scheduler: ``rows`` decode rows over a pool of ``pool_requests``
    requests' pages."""

    from repro.runtime.scheduler import ContinuousBatchingScheduler

    chunk, joints = traffic["chunk_len"], traffic["n_joints"]
    per_req = -(-(2 * joints + chunk * joints) // serving["page_size"])
    return ContinuousBatchingScheduler(
        model, params, tok, max_slots=serving["rows"], chunk_len=chunk,
        n_joints=joints, page_size=serving["page_size"],
        num_pages=per_req * serving["pool_requests"],
        scan_rounds=serving["scan_rounds"],
    )


def warm_shapes(sched, sch, pool_requests: int) -> None:
    """Every admission size the window can meet: powers of two up to the
    pool's request count, then the full pool."""

    sizes, n = [], 1
    while n < pool_requests:
        sizes.append(n)
        n *= 2
    sizes.append(pool_requests)
    for n in sizes:
        ids = np.arange(n)
        obs = ids % len(sch)
        sched.submit_batch(ids, sch.qd[obs], sch.tau[obs])
        sched.drain()
    sched.reset()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Tracer:
    """The profiler, on from set-up until every counted fire is resolved.

    Starting and stopping the profiler stalls the host for seconds, so both
    fall outside the counted fires: the start before the traffic begins,
    the stop after the window's grace.  The host keeps only its annotated
    spans (the benchmark's own among them), and no Python tracer runs.  The ``bench.window`` host span marks
    the measured window, from the first window boundary at or after
    ``t_from`` to the first at or after ``t_to``; the per-layer metrics read
    that span alone.  The scheduler's histograms are stamped from the first
    fire and read from a fresh registry swapped in when the span opens.
    """

    def __init__(self, jax, sched, t_from: float, t_to: float):
        from repro.obs import Observability

        self.jax, self.sched = jax, sched
        self.t_from, self.t_to = t_from, t_to
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        # no Python tracer: it would record every call the driver makes
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        log(f"profiler started in {time.perf_counter() - t:.3f} s (set-up)")
        sched.obs = Observability(trace=False)
        self.state = "waiting"
        self.ann = None

    def span(self, name):
        if self.state != "on":
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def __call__(self, now: float) -> None:
        """At a window boundary: open or close the ``bench.window`` span."""

        s = self.sched
        if self.state == "waiting" and now >= self.t_from:
            from repro.obs import Observability

            self.ann = self.jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            s.obs = Observability(trace=False)
            self.start = (now, s.round, s.windows)
            self.state = "on"
        elif self.state == "on" and now >= self.t_to:
            self.ann.__exit__(None, None, None)
            self.obs = s.obs
            self.stop = (now, s.round, s.windows)
            self.state = "closed"

    def finish(self, now: float) -> None:
        """Close the span if the run ended first, then stop the profiler."""

        if self.state == "on":
            self(max(now, self.t_to))
        t = time.perf_counter()
        self.jax.profiler.stop_trace()
        log(f"profiler stopped in {time.perf_counter() - t:.3f} s (after the window)")
        self.sched.obs = None


def useful_ctx(outcome, start_round: int, stop_round: int, scan_rounds: int,
               block: int, prompt: int) -> np.ndarray:
    """Context length of every decode step that served a delivered chunk
    inside windows dispatched in ``(start_round, stop_round]``."""

    out = []
    for adm, _done in outcome.rounds.values():
        for k in range(2):  # a 56-token chunk takes two 28-token windows
            r = adm + k * scan_rounds
            if start_round < r <= stop_round:
                first = prompt + 1 + k * scan_rounds * block
                out.extend(range(first, first + scan_rounds * block))
    return np.asarray(out, np.int64)


def end_to_end(outcome, sch, t_from: float, seconds: float, deadline_s: float):
    """The window's end-to-end numbers.  Latency percentiles run over every
    fire due in the window: a fire whose chunk never came (cancelled, or
    still out a deadline after the window) counts as one that arrived
    after the deadline plus that grace."""

    from driver import CANCELLED, DELIVERED, EXPIRED

    win = (sch.due_s >= t_from) & (sch.due_s < t_from + seconds)
    st = outcome.status[win]
    got = st == DELIVERED
    lat_ok = outcome.latency_s[win][got] * 1e3
    lat = np.where(got, outcome.latency_s[win] * 1e3, 2 * deadline_s * 1e3)
    attempted = int(win.sum())
    failed = int(((st == CANCELLED) | (st == EXPIRED)).sum())
    on_time = int((lat_ok <= deadline_s * 1e3).sum())
    lag = outcome.lag_s[win] * 1e3
    lag = lag[np.isfinite(lag)]
    info = {
        "attempted": attempted, "delivered": int(got.sum()),
        "cancelled": int((st == CANCELLED).sum()), "expired": int((st == EXPIRED).sum()),
        "on_time": on_time,
        "lag_ms_p50": float(np.percentile(lag, 50)) if lag.size else 0.0,
        "lag_ms_p99": float(np.percentile(lag, 99)) if lag.size else 0.0,
        "lag_ms_max": float(lag.max()) if lag.size else 0.0,
    }
    metrics = {}
    if lat.size:
        metrics["chunk_p50_ms"] = float(np.percentile(lat, 50))
        metrics["chunk_p95_ms"] = float(np.percentile(lat, 95))
    metrics["goodput_chunks_s"] = on_time / seconds
    return attempted, failed, metrics, info, win


def serve_window(args, cell, model, params, tok, sizes, seed, n_robots, jax,
                 trace: bool):
    """Set-up after the weights, warm-up traffic, then the measured window.

    Returns a namespace with the schedule, the outcome, the window's
    compile count and (when tracing) the tracer."""

    from fleet import build_schedule
    from driver import OpenLoop

    tr = cell.traffic
    sv = {**cell.config["serving"], **tr["engine"]}
    warm_s = tr["warmup_s"]
    deadline_s = tr["deadline_ticks"] / tr["control_hz"]
    ticks = int(np.ceil((warm_s + args.seconds) * tr["control_hz"])) + 1
    sch = build_schedule(tr, n_robots, ticks, seed)
    sched = build_engine(model, params, tok, sv, tr)
    warm_shapes(sched, sch, sv["pool_requests"])
    tracer = Tracer(jax, sched, warm_s, warm_s + args.seconds) if trace else None
    loop = OpenLoop(sched, sch, counted_from=warm_s, origin=time.perf_counter())
    # the cell's own traffic warms what the sizes above did not reach; the
    # window opens in its steady state
    loop.serve(warm_s)
    sched.allocator.reset_high_water()
    t_window = time.perf_counter()
    with CompileCount(jax) as cc:
        loop.serve(warm_s + args.seconds, deadline_s, on_boundary=tracer,
                   tracer=tracer.span if tracer else None)
    if tracer is not None:
        tracer.finish(loop.now())
    return SimpleNamespace(sch=sch, sched=sched, out=loop.out,
                           compiles=cc.n, tracer=tracer, t_window=t_window,
                           warm_s=warm_s, deadline_s=deadline_s)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample_chunks(outcome, win_idx, k: int, seed: int):
    """``k`` delivered chunks of the window drawn from the seed."""

    rng = np.random.default_rng([int(seed), 7])
    pool = np.asarray(sorted(i for i in outcome.tokens if i in win_idx), np.int64)
    return sorted(int(i) for i in rng.choice(pool, min(k, pool.size), replace=False))


def checked_tokens(sizes, params, sch, outcome, picks, control: bool):
    """The sampled chunks' tokens and the float32 reference's logits that
    predict them.

    The tokens are those the program served; with ``control`` they are,
    at each position of the same prompts and served tokens, the token the
    fp8 control puts first: the reference in the program's place, one
    precision below the configuration's."""

    import reference

    vocab = sizes["vocab_size"]
    prompts = encode_prompt(sch.qd[picks], sch.tau[picks], vocab)
    served = np.stack([outcome.tokens[i] for i in picks])
    seq = np.concatenate([prompts, served], 1)
    p = prompts.shape[1]
    ref = np.asarray(reference.forward(sizes, params, seq))[:, p - 1:-1]
    if control:
        ctl = np.asarray(reference.forward(sizes, params, seq, fp8=True))[:, p - 1:-1]
        served = reference.first_choice(ctl, vocab - N_ACTION_BINS)
    return served, ref


def judge(sizes, check: dict, served, ref):
    """``correct`` and the numbers compared, each beside its limit, for the
    checked tokens ``served`` (None when no chunk was delivered)."""

    import reference

    vocab = sizes["vocab_size"]
    floor = vocab - N_ACTION_BINS
    gap, bad, n = None, 0, 0
    if served is not None:
        bad = int(((served < floor) | (served >= vocab)).sum())
        gap = float(reference.served_gaps(ref, np.clip(served, floor, vocab - 1),
                                          floor).max())
        n = served.shape[0]
    limit = check["logit_gap_limit"]
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "tokens_out_of_range": {"value": bad, "limit": 0},
        "chunks_checked": {"value": n, "limit": check["sample_chunks"]},
    }
    correct = gap is not None and gap <= limit and bad == 0 and n == check["sample_chunks"]
    return bool(correct), checks


def check_sample(sizes, params, check, sch, outcome, win_idx, seed, control: bool):
    """Draw the sample from the seed, run the reference, judge."""

    picks = sample_chunks(outcome, win_idx, check["sample_chunks"], seed)
    served, ref = (checked_tokens(sizes, params, sch, outcome, picks, control)
                   if picks else (None, None))
    return judge(sizes, check, served, ref)


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def per_layer(cell, w, sizes, peak, mem_peak):
    import tracing

    tr = w.tracer
    programs = json.loads((BENCH / "programs.json").read_text())
    data = tracing.collect(tr.dir, [programs["paged_kernel"]])
    t0, t1 = tracing.window_of(data["host"])
    data = {k: (tracing.clip(v, t0, t1) if isinstance(v, list) else v)
            for k, v in data.items()}
    traffic = cell.traffic
    sv = {**cell.config["serving"], **traffic["engine"]}
    block = traffic["n_joints"]
    ctx = useful_ctx(w.out, tr.start[1], tr.stop[1], sv["scan_rounds"], block,
                     2 * traffic["n_joints"])
    run = SimpleNamespace(
        trace=data, t0=t0, t1=t1, cfg=sizes, serving=sv, peak=peak,
        programs=programs, obs=tr.obs.metrics, windows=tr.stop[2] - tr.start[2],
        pool_pages=w.sched.paged_spec.num_pages,
        pool_high_water=w.sched.pool_stats().high_water,
        useful_ctx=ctx, memory_peak_bytes=mem_peak,
    )
    from spec import reader

    metrics = {}
    for m in cell.per_layer:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {
        "busy_s": tracing.busy_ns(data["ops"]) * 1e-9 / max(data["chips"], 1),
        "window_s": (t1 - t0) * 1e-9,
    }
    log(f"trace: {len(data['modules'])} programs ({tracing.busy_ns(data['modules']) * 1e-9:.4f} s "
        f"busy), {len(data['ops'])} operations ({device['busy_s']:.4f} s busy) in "
        f"{device['window_s']:.4f} s; {tr.stop[2] - tr.start[2]} windows")
    breakdown = {
        "device_ops": tracing.top_ops(data["ops"]),
        "idle_gaps": tracing.idle_gaps(data["ops"], data["host"], t0, t1),
    }
    shutil.rmtree(tr.dir, ignore_errors=True)
    return metrics, device, breakdown


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="the same path on the smoke preset, on the CPU")
    p.add_argument("--control", action="store_true",
                   help="judge the fp8 control's tokens in the served tokens' place")
    p.add_argument("--readings", default=None,
                   help="comma-separated seeds: the program's check and the control's")
    p.add_argument("--sweep", default=None,
                   help="comma-separated fleet sizes served in turn")
    args = p.parse_args(argv)

    from spec import load_cell, model_config

    cell = load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif "cpu" not in os.environ.get("JAX_PLATFORMS", "cpu"):
        # the robots' side (episodes, trigger) runs on the host's CPU device
        os.environ["JAX_PLATFORMS"] += ",cpu"
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            log(f"--rehearse runs on the CPU, found {platform}")
            return 2
    elif platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} {platform} device(s)")
        return 2
    from costs import peaks

    peak = None if args.rehearse else peaks(devices[0].device_kind)
    if not args.rehearse:
        log(f"compile cache: {compile_cache(jax)}")
    if args.rehearse:
        rehearse = dict(cell.config["rehearse"])
        check = {**cell.config["check"], **rehearse.pop("check", {})}
        cell.config = {**cell.config, "check": check}
        cell.traffic = {**cell.traffic, **rehearse}

    from repro.data.pipeline import EpisodeTokenizer
    from repro.models.model import Model
    from weights import make_weights

    cfg, sizes = model_config(cell.config, args.rehearse)
    model = Model(cfg)
    tok = EpisodeTokenizer(cfg.vocab_size)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    log(f"model {cfg.name}: d_model {cfg.d_model}, {cfg.num_heads} heads of "
        f"{cfg.resolved_head_dim} (kv {cfg.num_kv_heads}), FFN {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.num_layers} layers, {cfg.dtype}")

    if args.readings:
        return readings(args, cell, model, tok, sizes, like, jax)
    if args.sweep:
        return sweep(args, cell, model, tok, sizes, like, jax)

    params = make_weights(like, args.seed)
    jax.block_until_ready(params)
    n_robots = cell.traffic["robots"]
    w = serve_window(args, cell, model, params, tok, sizes, args.seed, n_robots,
                     jax, trace=bool(args.trace))
    setup_s = w.t_window - T_START
    attempted, failed, e2e, info, win = end_to_end(
        w.out, w.sch, w.warm_s, args.seconds, w.deadline_s)
    log(f"set-up {setup_s:.3f} s; window {args.seconds} s: {info}; end to end: {e2e}; "
        f"programs compiled or loaded inside "
        f"the window: {w.compiles}; cancels the engine found nothing for: "
        f"{w.out.lost_cancels}")
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    result = {}
    if args.trace:
        metrics, dev_t, breakdown = per_layer(cell, w, sizes, peak, mem_peak)
        device.update(dev_t)
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: {"value": (setup_s if m["name"] == "setup_s"
                                         else e2e.get(m["name"])), "unit": m["unit"]}
                   for m in cell.end_to_end}
        metrics = {k: v for k, v in metrics.items() if v["value"] is not None}

    # the engine's state goes before the reference runs beside the weights
    win_idx = set(np.flatnonzero(win).tolist())
    out, sch = w.out, w.sch
    del w
    gc.collect()
    check = cell.config["check"]
    correct, checks = check_sample(sizes, params, check, sch, out, win_idx, args.seed,
                                   args.control)
    what = "the fp8 control's first choices" if args.control else "served tokens"
    log(f"checked {checks['chunks_checked']['value']} chunks ({what}) against the "
        f"float32 reference")
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **result, "checks": checks}
    print(json.dumps(result))
    return 0


def readings(args, cell, model, tok, sizes, like, jax) -> int:
    """On each seed, at the cell's size and load: the program's check and
    the fp8 control's, both judged as a run judges, for setting
    ``logit_gap_limit``."""

    from weights import make_weights

    check = cell.config["check"]
    for seed in (int(s) for s in args.readings.split(",")):
        params = make_weights(like, seed)
        w = serve_window(args, cell, model, params, tok, sizes, seed,
                         cell.traffic["robots"], jax, trace=False)
        _, _, e2e, info, win = end_to_end(w.out, w.sch, w.warm_s, args.seconds,
                                          w.deadline_s)
        win_idx = set(np.flatnonzero(win).tolist())
        out, sch = w.out, w.sch
        del w
        gc.collect()
        row = {"seed": seed, "delivered": info["delivered"]}
        for name, control in (("program", False), ("control", True)):
            correct, checks = check_sample(sizes, params, check, sch, out, win_idx,
                                           seed, control)
            row[name] = {"correct": correct,
                         **{k: v["value"] for k, v in checks.items()}}
        print(json.dumps(row), flush=True)
        del params
        gc.collect()
    return 0


def sweep(args, cell, model, tok, sizes, like, jax) -> int:
    """On-time share and latency at each fleet size, one process."""

    from weights import make_weights

    params = make_weights(like, args.seed)
    for n in (int(s) for s in args.sweep.split(",")):
        w = serve_window(args, cell, model, params, tok, sizes, args.seed, n, jax,
                         trace=False)
        attempted, failed, e2e, info, _ = end_to_end(
            w.out, w.sch, w.warm_s, args.seconds, w.deadline_s)
        print(json.dumps({"robots": n, "on_time_share": info["on_time"] / max(attempted, 1),
                          **e2e, **info, "compiles": w.compiles}), flush=True)
        del w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
