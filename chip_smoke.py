"""On-chip smoke of the RAPID serving path.

    python chip_smoke.py              # one TPU chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four TPU chips: the multi-chip phase only
    python chip_smoke.py --rehearse   # phases (a)-(c) on the smoke preset, CPU

Serves phi-3-vision-4.2b at its published widths (d_model 3072, 32 MHA
heads of 96, FFN 8192, vocab 32064), cut to ``LAYERS`` of its 32 layers,
with random weights from ``--seed``, through the normal serving entry
points: ``CloudPolicy`` and ``serve_fleet`` over
``ContinuousBatchingScheduler`` + ``PartitionExecutor``.

  (a) The compiled Pallas ``paged_decode_attention`` against the jnp oracle
      of ``kernels/ref.py`` at the model's head widths.
  (b) One request through ``CloudPolicy`` dense and ``paged=True``: the
      prefill logits and the first decode-step logits agree.
  (c) An 8-robot fleet under the RAPID trigger with 4-round scan windows,
      every second robot on a split lane at an explicit edge cut: every
      robot is served, the action tokens are in range, the page pool drains
      to zero, and the decode-window program holds the compiled kernel.

``--chips 4`` serves the phase-(c) fleet on one chip, on a 4-chip ``data``
mesh, and sharded over 3 chips with prompt prefill on the 4th, and compares
the three runs' chunks.

Every phase is fatal: a failed check raises, and the script exits non-zero
without a result line.  Without a TPU (and without ``--rehearse``) it exits
non-zero before any phase.  Times printed here are a smoke, not a
benchmark.  The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.pipeline import EpisodeTokenizer  # noqa: E402
from repro.kernels import paged_attention as pa  # noqa: E402
from repro.kernels.ref import paged_decode_attention_ref  # noqa: E402
from repro.launch.serve import CloudPolicy, serve_fleet, serving_config  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.obs import Observability  # noqa: E402
from repro.partition.executor import PartitionExecutor  # noqa: E402
from repro.robotics.episodes import generate_episode  # noqa: E402

ARCH = "phi-3-vision-4.2b"
# 16 of 32 layers: a fleet with a split lane holds the weights twice (the
# stacked params plus the executor's per-layer slices), 14.9 GB at 32
# layers before any KV page or program.  At 16 layers one TPU v5e peaks at
# 7.65 GiB of 15.75, and device 0 of the 4-chip phase at 14.64 GiB
LAYERS = 16
N_ROBOTS = 8
TICKS = 60
SCAN_ROUNDS = 4
DECODE_STEPS = 4  # decode-step logits compared in (b)

# (a) bf16 kernel inputs against the float32 oracle: the kernel accumulates
# in float32 and rounds its output to bf16 (2^-9 relative), so 1e-2 holds
# with margin while a wrong page, mask or scale is off by O(1)
KERNEL_ATOL = KERNEL_RTOL = 1e-2
# (b) dense vs paged bf16 logits (unit-scale: std ~1, max ~4.5): the two
# paths differ only in how attention accumulates.  The whole bf16-vs-f32
# gap of this stack measured 0.045 at 8 layers (smaller widths, CPU), so
# 0.125 leaves room for 16 layers while a wrong KV read moves logits by
# O(1).  Measured on a TPU v5e at 16 layers: 0.047
LOGIT_ATOL = 0.125
# --chips 4: share of matched chunk tokens allowed to differ from the
# single-chip run.  Splitting rows over chips changes bf16 gemm shapes, so
# a near-tied greedy token can flip and the rest of its chunk follows
# (0.163 on a 4-chip and 0.071 on a 3 + 1-chip TPU v5e mesh at 16 layers;
# 0.087 on a forced 4-device CPU mesh at the smoke preset); a wrong page or
# row would make nearly every token differ
MULTICHIP_TOKEN_TOL = 0.25


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend compile seconds and persistent-cache hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself);
    otherwise a fixed directory in the checkout, so a rerun hits."""

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Prints one line per phase: wall seconds and compile seconds in it."""

    t0, c0 = time.perf_counter(), clock.seconds
    print(f"[{name}] ...", flush=True)
    yield
    print(f"[{name}] passed in {time.perf_counter() - t0:.1f} s "
          f"(compile {clock.seconds - c0:.1f} s)", flush=True)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def kernel_phase(cfg, on_chip: bool, seed: int) -> None:
    """(a) Pallas paged decode vs the float32 oracle, at the head widths."""

    rng = np.random.default_rng(seed)
    h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    b, page, maxp, pool = 8, 16, 8, 80
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((pool, page, kv, d)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((pool, page, kv, d)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pool)[: b * maxp].reshape(b, maxp), jnp.int32)
    # ragged: an empty-but-one row, a full row, a page boundary, the rest random
    lens = rng.integers(1, maxp * page + 1, b)
    lens[:3] = 1, maxp * page, 2 * page
    lens = jnp.asarray(lens, jnp.int32)

    args = (q, kp, vp, table, lens)
    if on_chip:
        compiled = pa.paged_decode_attention.lower(*args).compile()
        check(has_kernel(compiled), "(a) kernel did not compile to tpu_custom_call")
        got = compiled(*args)
    else:
        got = pa.paged_decode_attention(*args, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_attention_ref(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), table, lens,
        )
    got, want = np.asarray(got, np.float32), np.asarray(want)
    err = float(np.abs(got - want).max())
    print(f"(a) paged_decode_attention H={h} KV={kv} D={d} page={page}: "
          f"max |err| {err:.2e} (tolerance {KERNEL_ATOL} + {KERNEL_RTOL}*|ref|)")
    check(
        np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL),
        f"(a) kernel vs oracle max |err| {err}",
    )


def policy_phase(model, params, tok, seed: int) -> None:
    """(b) one request, dense vs paged: prefill + decode-step logits."""

    ep = generate_episode("pick_place", seed=seed)
    qd, tau = ep.qd[10:11], ep.tau[10:11]
    dense = CloudPolicy(model, params, tok)
    paged = CloudPolicy(model, params, tok, paged=True)
    want, fed = dense.step_logits(qd, tau, DECODE_STEPS)
    got, _ = paged.step_logits(qd, tau, DECODE_STEPS, tokens=fed)
    v = model.cfg.vocab_size
    err = np.abs(got[..., :v] - want[..., :v]).max(axis=(0, 2))
    print(f"(b) dense vs paged logits (max |logit| {np.abs(want[..., :v]).max():.2f}): "
          f"max |err| prefill {err[0]:.4f}, decode steps "
          + " ".join(f"{e:.4f}" for e in err[1:]) + f" (tolerance {LOGIT_ATOL})")
    check(bool(np.all(np.isfinite(got))), "(b) paged logits not finite")
    check(float(err.max()) <= LOGIT_ATOL, f"(b) dense vs paged logits differ by {err.max()}")
    for pol in (dense, paged):
        chunk = pol(qd, tau)
        check(chunk.shape == (1, pol.chunk_len, pol.n_joints), f"(b) chunk {chunk.shape}")
        check(bool(np.all(np.abs(chunk) <= tok.action_clip)),
              "(b) action chunk out of range")


def build_stack(model, seed: int, mesh=None):
    """Weights from ``seed``, laid out on ``mesh`` as the scheduler lays
    them out, and the split executor over them.

    The executor's per-layer slices are the second copy of the weights, so
    every fleet run of the multi-chip phase builds its own stack and drops
    it before the next: three stacks would not fit device 0.
    """

    params = model.init(jax.random.PRNGKey(seed))
    if mesh is not None:
        params = model.shard_params(params, mesh)
    executor = PartitionExecutor(model, params, max(model.cfg.num_layers // 8, 1))
    jax.block_until_ready(executor.split_params)
    return params, executor


def run_fleet(model, params, executor, tok, seed: int, mesh=None,
              prefill_group=None):
    """The phase-(c) fleet through ``serve_fleet``, then drained.

    Returns ``(out, chunks)``: ``serve_fleet``'s result and every chunk as
    ``{(robot, submission round): tokens}``, the drained ones included.
    """

    out = serve_fleet(
        model, params, tok, n_robots=N_ROBOTS, seed=seed, max_steps=TICKS,
        partition_executor=executor, split_robots=list(range(1, N_ROBOTS, 2)),
        trigger="rapid", scan_rounds=SCAN_ROUNDS, obs=Observability(trace=False),
        mesh=mesh, prefill_group=prefill_group, verbose=False,
    )
    sched = out["scheduler"]
    drained = sched.drain()
    chunks = {(r, s): t for r, s, t in out["chunks"]}
    chunks.update({(c.robot_id, c.submitted_round): c.tokens for c in drained})
    served = np.bincount([r for r, _, _ in out["chunks"]], minlength=N_ROBOTS)
    check(bool(np.all(served > 0)), f"(c) chunks per robot {served.tolist()}")
    toks = np.concatenate(list(chunks.values()))
    check(
        bool(np.all((toks >= tok.action_base) & (toks < model.cfg.vocab_size))),
        "(c) action tokens out of range",
    )
    pool = sched.pool_stats()
    check(pool.pages_in_use == 0 and sched.n_active == 0,
          f"(c) {pool.pages_in_use} pages in use after the drain")
    return out, chunks


def fleet_phase(model, params, executor, tok, on_chip: bool, seed: int) -> None:
    """(c) the 8-robot mixed fleet, one chip."""

    out, chunks = run_fleet(model, params, executor, tok, seed)
    slo = out["slo"]
    lat = slo["chunk_latency_ms"]
    print(f"(c) fleet of {N_ROBOTS} (split robots {out['split_robots']} at edge "
          f"cut {executor.cut_layer}), {out['steps']} ticks: {len(chunks)} chunks, "
          f"{out['decode_rounds']} decode rounds, {out['mixed_rounds']} mixed, "
          f"{out['telemetry'].cancels.sum()} cancels, pool high-water "
          f"{out['pool'].high_water} pages")
    print(f"(c) smoke, not a benchmark: chunk latency p50 {lat.get('p50', 0):.1f} ms "
          f"p99 {lat.get('p99', 0):.1f} ms, goodput {slo['goodput_chunks_s']:.2f} "
          f"chunks/s over {out['wall_s']:.1f} s wall (compiles included)")
    if on_chip:
        check(has_kernel(out["scheduler"].compiled_decode_window()),
              "(c) decode-window program holds no tpu_custom_call")
        print("(c) decode-window program holds the compiled kernel (tpu_custom_call)")


def multichip_phase(model, tok, on_chip: bool, seed: int) -> None:
    """The phase-(c) fleet: one chip vs a 4-chip data mesh vs 3 + 1 chips."""

    from repro.launch.mesh import make_host_mesh, make_test_mesh, split_device_groups

    check(len(jax.devices()) >= 4, f"--chips 4 found {len(jax.devices())} devices")
    out, base = run_fleet(model, *build_stack(model, seed), tok, seed)
    del out  # its scheduler holds the single-chip stack
    prefill, decode = split_device_groups(prefill=1)
    runs = {
        "data=4": dict(mesh=make_host_mesh()),
        "data=3 + prefill chip": dict(
            mesh=make_test_mesh(data=len(decode), devices=decode),
            prefill_group=prefill,
        ),
    }
    for name, kw in runs.items():
        gc.collect()  # the previous run's stack
        out, chunks = run_fleet(
            model, *build_stack(model, seed, kw["mesh"]), tok, seed, **kw
        )
        if on_chip:
            check(has_kernel(out["scheduler"].compiled_decode_window()),
                  f"[{name}] decode-window program holds no tpu_custom_call")
        del out
        keys = sorted(set(base) & set(chunks))
        check(len(keys) >= len(base) // 2,
              f"[{name}] only {len(keys)} of {len(base)} chunks matched")
        a = np.stack([base[k] for k in keys])
        b = np.stack([chunks[k] for k in keys])
        diff = float(np.mean(a != b))
        print(f"[{name}] {len(keys)} chunks matched the single-chip run "
              f"({len(base)} there, {len(chunks)} here): {diff:.4f} of tokens "
              f"differ (tolerance {MULTICHIP_TOKEN_TOL})")
        check(diff <= MULTICHIP_TOKEN_TOL, f"[{name}] {diff} of tokens differ")
        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            print(f"[{name}] device 0 peak so far "
                  f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the multi-chip phase")
    p.add_argument("--rehearse", action="store_true",
                   help="run the phases on the smoke preset on the CPU")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse and platform != "cpu":
        print(f"chip_smoke: --rehearse runs on the CPU, found {platform}",
              file=sys.stderr)
        return 2
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform})", file=sys.stderr)
        return 2
    on_chip = not args.rehearse
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device}")

    if on_chip:
        print(f"compile cache: {enable_compile_cache()}")
        cfg = serving_config(ARCH, full=True, layers=LAYERS)
    else:
        cfg = serving_config(ARCH)
    print(f"model: {cfg.name} d_model {cfg.d_model}, {cfg.num_heads} heads of "
          f"{cfg.resolved_head_dim} (kv {cfg.num_kv_heads}), FFN {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, depth {cfg.num_layers} layers, "
          f"{cfg.dtype}, random weights (seed {args.seed})")
    tok = EpisodeTokenizer(cfg.vocab_size)
    model = Model(cfg)
    with CompileClock() as clock:
        if args.chips == 4:
            with phase("4 chips", clock):
                multichip_phase(model, tok, on_chip, args.seed)
        else:
            t0 = time.perf_counter()
            params, executor = build_stack(model, args.seed)
            n_params = sum(a.size for a in jax.tree.leaves(params))
            print(f"weights: {n_params / 1e9:.3f} B parameters + the split "
                  f"executor's per-layer copy, built in "
                  f"{time.perf_counter() - t0:.1f} s")
            with phase("a", clock):
                kernel_phase(cfg, on_chip, args.seed)
            with phase("b", clock):
                policy_phase(model, params, tok, args.seed)
            with phase("c", clock):
                fleet_phase(model, params, executor, tok, on_chip, args.seed)
    print(f"compile: {clock.programs} programs, {clock.seconds:.1f} s backend "
          f"compile, {clock.cache_hits} persistent-cache hits")
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device 0 memory: peak {stats['peak_bytes_in_use'] / 2**30:.2f} GiB"
              f" of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
