"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the edge-cloud co-inference loop end to end with a real cloud VLA:
the RAPID dispatcher monitors simulated robot kinematics; on dispatch, the
*actual model* (prefill + decode of action tokens through the KV cache)
produces the chunk.  The model is the smoke preset of ``--arch`` unless
``--full`` asks for its published widths (``--layers`` cuts the depth);
``chip_smoke.py`` at the repository root drives this path on a TPU.

Two serving modes:
  * ``serve_episode`` — one robot, one ``CloudPolicy``; the action chunk is
    decoded by a single fused on-device ``lax.scan`` (no per-token host
    syncs).  ``--paged`` decodes through the paged KV substrate instead of
    dense per-slot slabs (bit-identical greedy chunks).
  * ``serve_fleet`` — many robots sharing one cloud engine through the
    continuous-batching scheduler (``runtime/scheduler.py``): dispatch
    triggers become requests that join in-flight decode batches (admission
    bounded by free KV pages), and chunks arrive back asynchronously a few
    scheduler rounds later.  ``--trigger rapid`` runs the closed-loop
    redundancy-aware policy (cache replay on redundant depletions,
    in-flight cancellation on contact-phase preemption) instead of
    always-offload.

``--partition auto`` plans the compatibility-optimal edge-cloud cut for the
full architecture (``repro.partition``) and serves the episode through the
split executor when the plan keeps layers on both sides.  Combined with
``--fleet`` it serves a mixed fleet: partitioned robots' cloud suffixes
share decode rounds and KV pages with the cloud-only robots.
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.dispatcher import DispatcherConfig, dispatcher_init, dispatcher_step
from repro.core.kinematics import KinematicFrame
from repro.core.trigger import TriggerConfig
from repro.data.pipeline import EpisodeTokenizer
from repro.models.model import Model
from repro.obs import Observability, build_slo_report
from repro.obs.clock import clock
from repro.robotics.episodes import generate_episode
from repro.runtime.channel import ChannelConfig, sample_latency_ms_batch
from repro.runtime.policy import FleetTelemetry, PolicyConfig
from repro.runtime import policy as rpolicy


class CloudPolicy:
    """Batched VLA serving: observation tokens -> k-step action chunk.

    The whole ``chunk_len * n_joints`` token chunk decodes in one jitted
    ``lax.scan`` with zero host↔device syncs.

    ``paged=True`` decodes through the model's paged KV mode — prompt KV is
    scattered into a page pool after prefill and attention reads go through
    ``ops.paged_decode_attention`` — the single-request probe of the serving
    engine's KV substrate, bit-identical to the dense path.
    """

    def __init__(self, model: Model, params, tokenizer: EpisodeTokenizer,
                 chunk_len: int = 8, n_joints: int = 7,
                 paged: bool = False, page_size: int = 16):
        self.model = model
        self.params = params
        self.tok = tokenizer
        self.chunk_len = chunk_len
        self.n_joints = n_joints
        self.paged = paged
        self.page_size = page_size
        n_steps = chunk_len * n_joints
        self.n_steps = n_steps
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, extra=n_steps)
        )
        self._decode_chunk = jax.jit(
            lambda p, logits, cache: model.decode_chunk(
                p, logits, cache, n_steps, tokenizer.action_base
            )[0]
        )
        self._paged_fns = {}
        self._logit_fns = {}

    def _paged_cache(self, dcache, b: int, prompt: int):
        """Scatter a dense prefill cache into a fresh page pool (traced)."""

        from repro.runtime.kv_cache import PagedSpec

        page = self.page_size
        maxp = -(-(prompt + self.n_steps) // page)
        spec = PagedSpec(
            num_pages=b * maxp, page_size=page, max_pages_per_seq=maxp
        )
        pt = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
        caps = np.full((b,), maxp * page, np.int32)
        pcache = self.model.init_paged_cache(b, spec)
        return self.model.cache_to_paged(
            dcache, pcache, jnp.asarray(pt), jnp.asarray(caps)
        )

    def _paged_chunk_for(self, b: int, prompt: int):
        """Jitted prefill -> page scatter -> paged chunk decode, per shape."""

        key = (b, prompt)
        fn = self._paged_fns.get(key)
        if fn is None:
            def run(p, tokens):
                logits, dcache = self.model.prefill(
                    p, {"tokens": tokens}, extra=0
                )
                pcache = self._paged_cache(dcache, b, prompt)
                return self.model.decode_chunk(
                    p, logits, pcache, self.n_steps, self.tok.action_base
                )[0]

            fn = jax.jit(run)
            self._paged_fns[key] = fn
        return fn

    def step_logits(self, qd: np.ndarray, tau: np.ndarray, n_steps: int,
                    tokens: Optional[np.ndarray] = None):
        """Prefill, then ``n_steps`` single-token decode steps -> logits.

        Returns ``(logits, fed)``: float32 logits [B, n_steps + 1, V] (the
        prefill's last-token logits, then each decode step's) and the
        tokens fed to the decode steps [B, n_steps].  ``tokens`` teacher-
        forces the decode, so the dense and paged policies can be compared
        step for step on the same inputs; by default each step feeds this
        policy's own greedy action token.  Compare logits, not sampled
        tokens: with random weights the top logit can flip on rounding.
        """

        if not 0 < n_steps <= self.n_steps:
            raise ValueError(f"n_steps must be in [1, {self.n_steps}]")
        obs = np.concatenate(
            [self.tok.encode_state(qd), self.tok.encode_state(tau)], axis=1
        )
        b, prompt = obs.shape
        base = self.tok.action_base

        def run(p, obs_toks, forced):
            logits, cache = self.model.prefill(
                p, {"tokens": obs_toks}, extra=0 if self.paged else n_steps
            )
            if self.paged:
                cache = self._paged_cache(cache, b, prompt)

            def step(carry, f):
                lg, c = carry
                if f is None:
                    ls = lg[:, -1].at[..., :base].set(-1e9)
                    tok = jnp.argmax(ls, axis=-1)[:, None]
                else:
                    tok = f[:, None]
                lg, c = self.model.decode_step(p, tok, c)
                return (lg, c), (lg[:, 0], tok[:, 0])

            xs = None if forced is None else jnp.swapaxes(forced, 0, 1)
            _, (lgs, toks) = jax.lax.scan(
                step, (logits, cache), xs, length=n_steps
            )
            out = jnp.concatenate([logits[:, -1:], jnp.swapaxes(lgs, 0, 1)], 1)
            return out.astype(jnp.float32), jnp.swapaxes(toks, 0, 1)

        key = (b, prompt, n_steps)
        fn = self._logit_fns.get(key)
        if fn is None:
            fn = self._logit_fns[key] = jax.jit(run)
        forced = None if tokens is None else jnp.asarray(tokens, jnp.int32)
        logits, fed = fn(self.params, jnp.asarray(obs), forced)
        return np.asarray(logits), np.asarray(fed)

    def __call__(self, qd: np.ndarray, tau: np.ndarray) -> np.ndarray:
        """qd/tau [B, N] -> action chunk [B, k, N] via autoregressive decode."""

        obs = np.concatenate(
            [self.tok.encode_state(qd), self.tok.encode_state(tau)], axis=1
        )
        tokens = jnp.asarray(obs)
        if self.paged:
            toks = self._paged_chunk_for(*obs.shape)(self.params, tokens)
        else:
            logits, cache = self._prefill(self.params, {"tokens": tokens})
            toks = self._decode_chunk(self.params, logits, cache)
        return self.tok.decode_action(np.asarray(toks)).reshape(
            -1, self.chunk_len, self.n_joints
        )


def serve_episode(
    policy: CloudPolicy,
    task: str = "pick_place",
    seed: int = 0,
    dcfg: Optional[DispatcherConfig] = None,
    max_steps: int = 400,
    verbose: bool = True,
):
    """Closed loop: dispatcher decides, the real model serves chunks."""

    ep = generate_episode(task, seed=seed)
    dcfg = dcfg or DispatcherConfig(chunk_len=policy.chunk_len, action_dim=policy.n_joints)
    state = dispatcher_init(dcfg, batch_shape=())
    step_fn = jax.jit(lambda s, f, c: dispatcher_step(s, f, c, dcfg))

    n_off = 0
    cloud_ms = []
    zero_chunk = jnp.zeros((dcfg.chunk_len, dcfg.action_dim), jnp.float32)
    actions = []
    t_len = min(max_steps, ep.q.shape[0])
    cached_chunk = zero_chunk
    for t in range(t_len):
        frame = KinematicFrame(
            q=jnp.asarray(ep.q[t]), qd=jnp.asarray(ep.qd[t]), tau=jnp.asarray(ep.tau[t])
        )
        # peek: would the dispatcher offload? run step with the cached chunk;
        # if it dispatched, charge a real cloud inference for the fresh chunk
        state, out = step_fn(state, frame, cached_chunk)
        if bool(out.offloaded):
            t0 = clock()
            fresh = policy(ep.qd[t : t + 1], ep.tau[t : t + 1])[0]
            cloud_ms.append((clock() - t0) * 1e3)
            cached_chunk = jnp.asarray(fresh)
            n_off += 1
        actions.append(np.asarray(out.action))
    if verbose:
        print(
            f"task={task} steps={t_len} offloads={n_off} "
            f"cloud_ms(host)={np.mean(cloud_ms) if cloud_ms else 0:.1f}"
        )
    return {
        "offloads": n_off,
        "steps": t_len,
        "actions": np.stack(actions),
        "cloud_ms": cloud_ms,
    }


def serve_fleet(
    model: Model,
    params,
    tokenizer: EpisodeTokenizer,
    n_robots: int = 4,
    tasks: Optional[List[str]] = None,
    seed: int = 0,
    chunk_len: int = 8,
    n_joints: int = 7,
    max_steps: int = 300,
    max_slots: int = 8,
    channel: Optional[ChannelConfig] = None,
    partition_executor=None,
    split_robots: Optional[List[int]] = None,
    robot_cuts: Optional[Dict[int, int]] = None,
    defer_hot_admission: Optional[float] = None,
    num_pages: Optional[int] = None,
    scan_rounds: int = 1,
    mesh=None,
    prefill_group=None,
    trigger: str = "always",
    trigger_cfg: Optional[TriggerConfig] = None,
    record_streams: bool = False,
    obs: Optional[Observability] = None,
    verbose: bool = True,
):
    """A robot fleet served by one continuous-batching cloud engine.

    Each control tick the fleet's batched decision core runs
    (``runtime/policy.py`` — the same ``trigger_step`` the offline engine
    scans); triggered robots submit chunk requests, the scheduler advances
    one decode round, and finished chunks land back in the robots' queues —
    possibly several ticks after the trigger, so the fleet genuinely
    exercises ragged in-flight batches.

    ``trigger`` selects the dispatch policy:

      * ``"always"`` — every queue depletion forces a cloud fetch (the
        always-offload serving mode of PRs 1-3);
      * ``"rapid"``  — the closed-loop redundancy-aware mode: redundant
        steps REPLAY the cached chunk and never touch the scheduler, only
        kinematic trigger fires offload, and a fire while a previous
        request is still decoding CANCELS the in-flight sequence
        (``scheduler.cancel`` frees its pool pages / split-lane row) and
        resubmits against the fresh observation.

    With ``partition_executor`` set, robots listed in ``split_robots`` serve
    through the edge-cloud split: their edge prefix runs per robot and the
    cloud suffix joins the same paged decode rounds (and the same KV page
    pool) as the cloud-only robots.

    ``robot_cuts`` generalizes ``split_robots`` to a HETEROGENEOUS fleet:
    a ``{robot_id: cut_layer}`` map (e.g. from ``assign_fleet_cuts``) serves
    each listed robot through its own cut — one scheduler lane per distinct
    cut, sliced from ``partition_executor`` via ``with_cut`` — while robots
    absent from the map stay cloud-only.  Values may also be full lane
    keys: a ``(cut, expert_offload)`` tuple routes the robot through a
    gather/scatter expert-offload lane (edge runs attention + router, the
    listed MoE layers' expert FFNs run cloud-side), coexisting with plain
    cut lanes.  All lanes still share decode rounds and the single page
    allocator.

    ``scan_rounds=R`` runs the scheduler's device-resident decode windows:
    each dispatch jits R decode rounds into one ``lax.scan`` (donated KV
    pool, no per-round host sync) and admission / harvest / cancellation
    land only at window boundaries.  ``telemetry.scan_windows`` counts the
    dispatched windows and ``telemetry.host_gap_ms()`` the mean host
    milliseconds each boundary cost — the orchestration overhead that
    per-round stepping pays R times over.

    ``defer_hot_admission`` (a preempt-rate threshold, e.g. ``0.2``) turns
    on cancellation-aware admission: when a robot fires a mid-chunk preempt
    and its realized preempt rate runs above the threshold, the resubmitted
    request's ADMISSION (not its FIFO slot) is held back one round — if the
    trigger fires again immediately, the cancel removes a queued request
    instead of throwing away a paid batched prefill.

    The returned ``telemetry`` (``FleetTelemetry``) carries per-robot
    realized offload fractions — feed them back into
    ``plan_partition(offload_fraction=...)`` (see ``replan_from_telemetry``)
    to re-price partition cuts with the fleet's actual redundancy instead of
    the global trigger-sim constant.

    ``obs`` (an ``Observability``) turns on end-to-end request tracing and
    SLO accounting: the scheduler stamps every chunk's lifecycle at its
    host-owned boundaries, the decision core feeds fleet counters, and the
    run's ``SLOReport`` is printed (verbose) and returned under ``"slo"``.
    Decoded actions are byte-identical with and without ``obs`` — no extra
    host↔device syncs are introduced.

    Each tick is array-at-a-time: episode frames are stacked once to
    ``(T, R, N)`` and sliced per tick, the scheduler takes one
    ``submit_batch`` and one ``cancel_batch`` call per tick, in-flight /
    split / defer bookkeeping lives in ``[R]`` arrays, and each harvest
    makes one batched ``decode_action`` and one batched jitter draw.  Host
    tick overhead is O(triggered robots) numpy work, not O(fleet) Python.
    Jitter draws are keyed per (robot, offload ordinal), so a robot's
    latency stream does not depend on the order chunks complete in.
    """

    from repro.runtime.scheduler import ContinuousBatchingScheduler, _lane_order

    if trigger not in ("always", "rapid"):
        raise ValueError(f"trigger must be 'always' or 'rapid', got {trigger!r}")
    all_tasks = tasks or ["pick_place", "drawer_open", "peg_insertion"]
    eps = [
        generate_episode(all_tasks[i % len(all_tasks)], seed=seed + i)
        for i in range(n_robots)
    ]
    t_len = min(max_steps, min(ep.q.shape[0] for ep in eps))

    if trigger_cfg is None:
        # rapid serving default: dispatch cadence aligned with the chunk
        # horizon.  The trigger re-arms one step after the cooldown hits
        # zero, so C = k-1 makes sustained-contact refreshes land exactly on
        # chunk boundaries — no gratuitous mid-chunk preemption jerk and no
        # stale replay step between consecutive fires.
        cooldown = max(chunk_len - 1, 1) if trigger == "rapid" else 8
        trigger_cfg = TriggerConfig(n_joints=n_joints, cooldown_steps=cooldown)
    pcfg = PolicyConfig(
        trigger=trigger_cfg,
        chunk_len=chunk_len,
        on_empty="cloud" if trigger == "always" else "reuse",
    )
    state = rpolicy.trigger_init(pcfg, (n_robots,))
    step_fn = jax.jit(lambda s, f: rpolicy.trigger_step(s, f, pcfg))
    telemetry = FleetTelemetry(n_robots, record_streams=record_streams, obs=obs)

    # ``mesh`` shards the engine's page pools / decode rows / params over
    # the mesh's data axis (tokens bit-identical for f32 models);
    # ``prefill_group`` disaggregates prompt prefill onto its own device
    # group, handing off through the paged cache at window boundaries
    sched = ContinuousBatchingScheduler(
        model, params, tokenizer,
        max_slots=max_slots, chunk_len=chunk_len, n_joints=n_joints,
        num_pages=num_pages, scan_rounds=scan_rounds, obs=obs,
        mesh=mesh, prefill_group=prefill_group,
    )
    if robot_cuts is None:
        robot_cuts = (
            {r: partition_executor.cut_layer for r in (split_robots or [])}
            if partition_executor is not None else {}
        )
    else:
        robot_cuts = dict(robot_cuts)
    if partition_executor is not None and robot_cuts:
        # values are lane keys: plain int cuts or (cut, expert_offload)
        # tuples routing robots to expert-offload lanes at the same cut
        for c in sorted(set(robot_cuts.values()), key=_lane_order):
            if isinstance(c, tuple):
                sched.attach_partition(
                    partition_executor.with_cut(
                        int(c[0]), expert_offload=tuple(c[1])
                    )
                )
            else:
                sched.attach_partition(partition_executor.with_cut(c))
    else:
        robot_cuts = {}
    split_set = set(robot_cuts)

    cached = np.zeros((n_robots, chunk_len, n_joints), np.float32)
    actions = np.zeros((t_len, n_robots, n_joints), np.float32)
    n_off = np.zeros(n_robots, np.int64)
    wait_rounds: List[int] = []
    # harvested chunks in harvest order:
    # (robot id, submission round, action tokens)
    chunks: List[Tuple[int, int, np.ndarray]] = []
    # stochastic channel: every completed offload draws a jittered latency.
    # Keys fold in (robot id, per-robot offload ordinal), so each robot's
    # latency stream is reproducible across processes and fleet compositions
    # regardless of the order chunks happen to complete in.
    channel = channel or ChannelConfig()
    net_key = jax.random.PRNGKey(seed + 7919)
    offload_ms: List[float] = []
    offload_ms_by_robot: List[List[float]] = [[] for _ in range(n_robots)]
    rows = np.arange(n_robots)
    # host-overhead accounting: per-tick wall decomposes into the jitted
    # decision core (dispatch + forcing its outputs to host), the engine's
    # ``sched.step`` (prefill + decode windows), and everything else — the
    # HOST tick overhead (frame building, trigger bookkeeping, submits,
    # harvest handling).  ``sched.step`` is clocked per tick for boundary
    # telemetry anyway, so only the core timer adds clock reads (two a tick).
    core_s = 0.0
    engine_s = 0.0
    # host-gap accounting per scan window: step() host time accumulates
    # until the window CLOSES (the sync), so with scan_rounds > 1 the
    # boundary sample includes the closing call — previously only the
    # dispatch call was recorded and a prefill stall inside the window's
    # sync was invisible to ``host_gap_ms``
    window_host_ms = 0.0
    prev_closes = 0
    t_start = clock()

    # Frames are slices of (T, R, N) arrays stacked once, trigger
    # bookkeeping lives in [R] boolean/int arrays, and each tick makes at
    # most one cancel_batch + one submit_batch scheduler call and one
    # batched decode/jitter call per harvest.  Within a tick every cancel
    # lands before any submit: a cancel touches only its own robot's
    # request, so the queues, the global FIFO ``order`` stamps and the
    # telemetry counters read as if each robot cancelled then submitted
    # in ascending robot order.
    q_all = np.stack([ep.q[:t_len] for ep in eps], axis=1)
    qd_all = np.stack([ep.qd[:t_len] for ep in eps], axis=1)
    tau_all = np.stack([ep.tau[:t_len] for ep in eps], axis=1)
    in_flight_mask = np.zeros(n_robots, bool)
    split_mask = np.zeros(n_robots, bool)
    # lane keys, not plain ints: an expert-offload robot carries a
    # (cut, offload) tuple, so the per-robot routing array is object
    cut_arr = np.full(n_robots, None, object)
    for r, c in robot_cuts.items():
        split_mask[r] = True
        cut_arr[r] = c
    # per-robot offload ordinal == len(offload_ms_by_robot[r]); kept as
    # an array so the jitter keys batch without touching the lists
    n_done = np.zeros(n_robots, np.int64)
    for t in range(t_len):
        frame = KinematicFrame(
            q=jnp.asarray(q_all[t]),
            qd=jnp.asarray(qd_all[t]),
            tau=jnp.asarray(tau_all[t]),
        )
        c0 = clock()
        state, dec = step_fn(state, frame)
        trig = np.asarray(dec.offload)
        pre = np.asarray(dec.preempt)
        slot = np.asarray(dec.slot)
        core_s += clock() - c0
        telemetry.observe(dec)
        # execute before this round's completions land: a chunk arriving
        # in round t is first executable at t+1, as the dispatcher did
        actions[t] = cached[rows, slot]
        if trigger == "rapid":
            # contact-phase preemption, batched: every firing robot with
            # stale in-flight work cancels before the fresh submit
            cancel_ids = np.flatnonzero(trig & in_flight_mask)
            if cancel_ids.size:
                hits = sched.cancel_batch(cancel_ids)
                telemetry.note_cancels(cancel_ids[hits])
                in_flight_mask[cancel_ids] = False
            ids = np.flatnonzero(trig)
        else:
            # "always": fires landing while a request is in flight are
            # skipped
            ids = np.flatnonzero(trig & ~in_flight_mask)
        if ids.size:
            defer = None
            if defer_hot_admission is not None:
                # cancellation-aware admission: a preempting robot whose
                # trigger runs hot has its admission (not its queue slot)
                # held one round, so an immediate re-fire cancels a queued
                # request instead of a paid batched prefill
                defer = (
                    pre[ids]
                    & (
                        telemetry.preempts[ids]
                        / np.maximum(telemetry.fires[ids], 1)
                        >= defer_hot_admission
                    )
                ).astype(np.int64)
            sched.submit_batch(
                ids, qd_all[t][ids], tau_all[t][ids],
                partitioned=split_mask[ids],
                cuts=cut_arr[ids],
                defer_rounds=defer,
            )
            in_flight_mask[ids] = True
            n_off[ids] += 1
        t0 = clock()
        results = sched.step()
        step_s = clock() - t0
        engine_s += step_s
        window_host_ms += step_s * 1e3
        if sched.window_closes > prev_closes:
            telemetry.note_boundary(window_host_ms)
            window_host_ms = 0.0
            prev_closes = sched.window_closes
        if results:
            # at most one outstanding request per robot, so a harvest
            # never carries duplicate robot ids — batched scatter is safe
            res_ids = np.fromiter(
                (res.robot_id for res in results), np.int64,
                count=len(results),
            )
            toks = np.stack([res.tokens for res in results])
            chunks.extend(
                (res.robot_id, res.submitted_round, res.tokens)
                for res in results
            )
            cached[res_ids] = tokenizer.decode_action(toks).reshape(
                len(results), chunk_len, n_joints
            )
            in_flight_mask[res_ids] = False
            telemetry.note_completions(res_ids)
            wait_rounds.extend(
                res.completed_round - res.submitted_round for res in results
            )
            ms = sample_latency_ms_batch(
                channel, chunk_len, net_key, res_ids, n_done[res_ids]
            )
            n_done[res_ids] += 1
            offload_ms.extend(ms)
            for i, r in enumerate(res_ids):
                offload_ms_by_robot[r].append(ms[i])

    wall_s = clock() - t_start
    pool = sched.pool_stats()
    slo = None
    if obs is not None:
        obs.metrics.gauge("serve.wall_s").set(wall_s)
        slo = build_slo_report(obs.metrics)
    if verbose:
        print(
            f"fleet={n_robots} steps={t_len} trigger={trigger} "
            f"offloads={int(n_off.sum())} "
            f"replays={int(telemetry.replays.sum())} "
            f"cancels={int(telemetry.cancels.sum())} "
            f"f_off={telemetry.fleet_offload_fraction():.2f} "
            f"mean_service_rounds={np.mean(wait_rounds) if wait_rounds else 0:.1f} "
            f"decode_rounds={sched.decode_rounds} "
            f"scan_windows={telemetry.scan_windows} "
            f"host_gap_ms={telemetry.host_gap_ms():.2f} "
            f"peak_batch={sched.peak_active} "
            f"kv_pages={pool.pages_in_use}/{pool.pages_in_use + pool.pages_free} "
            f"(high-water {pool.high_water}) "
            + (f"mixed_rounds={sched.mixed_rounds} " if split_set else "")
            + (
                f"cuts={sorted(set(robot_cuts.values()), key=_lane_order)} "
                f"hetero_rounds={sched.hetero_rounds} "
                if len(set(robot_cuts.values())) > 1 else ""
            )
            + (f"deferred={sched.deferred} " if sched.deferred else "")
            + f"net_ms={np.mean(offload_ms) if offload_ms else 0:.1f}"
            f"±{np.std(offload_ms) if offload_ms else 0:.1f}"
        )
        if slo is not None:
            for line in slo.lines():
                print(line)
    return {
        "slo": slo.to_json() if slo is not None else None,
        "obs": obs,
        "offloads": n_off,
        "steps": t_len,
        "wall_s": wall_s,
        # wall decomposition: jitted decision core, engine (sched.step), and
        # host orchestration — the serving-tick overhead around both
        "core_s": core_s,
        "engine_s": engine_s,
        "host_s": max(wall_s - core_s - engine_s, 0.0),
        "actions": actions,
        "chunks": chunks,
        "scheduler": sched,
        "service_rounds": wait_rounds,
        "offload_ms": offload_ms,
        "offload_ms_by_robot": offload_ms_by_robot,
        "peak_batch": sched.peak_active,
        "pool": pool,
        "mixed_rounds": sched.mixed_rounds,
        "hetero_rounds": sched.hetero_rounds,
        "decode_rounds": sched.decode_rounds,
        "scan_windows": telemetry.scan_windows,
        "host_gap_ms": telemetry.host_gap_ms(),
        "cancelled": sched.cancelled,
        "deferred": sched.deferred,
        "split_robots": sorted(split_set),
        "robot_cuts": dict(sorted(robot_cuts.items())),
        "active_cuts": sorted(set(robot_cuts.values()), key=_lane_order),
        "trigger": trigger,
        "telemetry": telemetry,
        "offload_fraction": telemetry.fleet_offload_fraction(),
    }


def _map_expert_offload(model: Model, cut: int, n_full_offload: int):
    """Map a full-arch offloaded-expert count onto ``model``'s edge prefix.

    The planner offloads the TRAILING ``n_full_offload`` edge MoE blocks
    (deepest first — see ``enumerate_cuts_2d``); mirror that choice on the
    smoke stack: the trailing ``min(n, #edge MoE layers)`` MoE layers below
    ``cut``.  Returns ``()`` when the edge prefix has no MoE layers.
    """

    moe_edge = [l for l in range(cut) if model.specs[l][1]]
    j = min(n_full_offload, len(moe_edge))
    return tuple(moe_edge[-j:]) if j else ()


def plan_fleet_partition(model: Model, params, arch: str,
                         network: str = "wan", verbose: bool = True,
                         plan_2d: bool = False):
    """Plan the full-arch cut and build a split executor over ``model``.

    Returns ``(executor_or_None, plan)``.  Only a genuine split runs through
    the executor: cloud-only and edge-only are single-device plans (and the
    executor's ping-pong decode would misprice them), enc-dec stacks aren't
    splittable yet — those return ``None`` and serving stays unpartitioned.
    The plan's layer fraction is mapped onto this — possibly smoke-scale —
    model (node cut 1, a stem-only edge, maps to layer cut 0: embedding on
    the edge, every layer in the cloud).

    ``plan_2d=True`` plans over (cut layer x placement).  The returned
    ``plan`` is the headline 2-D optimum; when it picks a priced-only
    placement (monitor-resident prefix, encoder staging), serving realizes
    the best EXECUTABLE 2-D plan instead — plain cuts and expert-offload
    lanes, still never worse than 1-D.  An ``expert_split`` realization
    maps both coordinates onto ``model``: the cut by layer fraction and
    the offloaded-expert set onto the trailing MoE layers of the edge
    prefix (``_map_expert_offload``).
    """

    from repro.partition.executor import PartitionExecutor
    from repro.partition.planner import NETWORK_PROFILES, plan_partition

    cfg = model.cfg
    channel = NETWORK_PROFILES[network]
    full_cfg = get_config(arch)
    plan = plan_partition(full_cfg, channel=channel, plan_2d=plan_2d)
    if verbose:
        print(f"partition plan [{network}]:", plan.summary())
    exec_plan = plan
    if plan_2d and plan.placement not in ("", "experts_cloud"):
        # monitor / encoder placements are priced by the planner but have
        # no split-executor realization yet: serve the best plan over the
        # executable placements instead
        exec_plan = plan_partition(
            full_cfg, channel=channel, plan_2d=True, executable_only=True
        )
        if verbose:
            print(f"  executable 2-D plan:", exec_plan.summary())
    if exec_plan.mode not in ("split", "expert_split") or cfg.encoder_decoder:
        if verbose:
            why = (
                "encoder-decoder split execution not supported"
                if exec_plan.mode in ("split", "expert_split")
                else f"planner chose {exec_plan.mode}"
            )
            print(f"{why}: serving unpartitioned")
        return None, plan
    frac = exec_plan.cut_layer / max(full_cfg.num_layers, 1)
    cut = int(round(frac * cfg.num_layers))
    offload = (
        _map_expert_offload(model, cut, len(exec_plan.expert_offload))
        if exec_plan.expert_offload else ()
    )
    if verbose:
        off = f", experts of layers {list(offload)} cloud-side" if offload else ""
        print(f"split execution: {cut}/{cfg.num_layers} layers on the edge{off}")
    return PartitionExecutor(model, params, cut, channel=channel,
                             expert_offload=offload), plan


def plan_expert_lane(model: Model, params, arch: str, network: str = "wan",
                     base=None, verbose: bool = True):
    """Build the 2-D plan's best expert-offload lane, mapped onto ``model``.

    Scores the full ``arch``'s (cut x expert placement) space and picks the
    best FEASIBLE ``experts_cloud`` point — the coordinate that moves MoE
    expert residency cloudward at the smallest gather/scatter price.
    Expert offload is a memory-feasibility axis: each offloaded block pays
    per-token channel legs, so it rarely wins total latency outright —
    mixed fleets therefore serve it ALONGSIDE the planned layer cut, and
    the scheduler shares decode rounds across both lane kinds.

    Returns a ``PartitionExecutor`` whose ``lane_key`` is the
    ``(cut, offload)`` tuple, or ``None`` when the arch (or the smoke
    model's edge prefix) has no MoE blocks to offload.  ``base`` shares its
    parameter slices via ``with_cut``.
    """

    from repro.partition.executor import PartitionExecutor
    from repro.partition.graph import build_graph
    from repro.partition.planner import NETWORK_PROFILES, enumerate_cuts_2d
    from repro.runtime.latency import arch_hardware_model

    cfg = model.cfg
    if cfg.encoder_decoder or cfg.moe is None:
        return None
    channel = NETWORK_PROFILES[network]
    full_cfg = get_config(arch)
    graph = build_graph(full_cfg)
    hw = arch_hardware_model(int(graph.total_param_bytes))
    cand = [
        e for e in enumerate_cuts_2d(graph, hw, channel)
        if e.feasible and e.placement == "experts_cloud"
    ]
    if not cand:
        return None
    best = min(cand, key=lambda e: e.total_ms)
    full_layers = max(full_cfg.num_layers, 1)
    cut = min(
        max(int(round(graph.cut_layers(best.cut) / full_layers
                      * cfg.num_layers)), 1),
        cfg.num_layers,
    )
    offload = _map_expert_offload(model, cut, len(best.expert_offload))
    if not offload:
        return None
    if verbose:
        print(
            f"expert-offload lane [{network}]: cut {cut}, experts of layers "
            f"{list(offload)} cloud-side (full-arch: "
            f"{len(best.expert_offload)} MoE block(s) at cut {best.cut}, "
            f"{best.total_ms:.1f}ms, +{best.net_expert_ms:.1f}ms legs)"
        )
    if base is not None:
        return base.with_cut(cut, expert_offload=offload)
    return PartitionExecutor(model, params, cut, channel=channel,
                             expert_offload=offload)


def assign_fleet_cuts(model: Model, params, arch: str, telemetry,
                      network: str = "wan", k_max: int = 3,
                      verbose: bool = True):
    """Per-robot cut assignment from realized telemetry, mapped onto ``model``.

    Plans the heterogeneous frontier for the FULL ``arch`` config at each
    robot's realized offload fraction (``partition.assign_cuts`` — monotone:
    higher-redundancy robots never get shallower edge prefixes), then maps
    the assigned full-arch edge layer counts onto this — possibly
    smoke-scale — model by layer fraction, keeping distinct full cuts
    distinct on the smaller stack where it has enough layers.

    Returns ``(executor_or_None, robot_cuts, assignment)``: a base
    ``PartitionExecutor`` (``serve_fleet`` derives per-cut siblings via
    ``with_cut``), a ``{robot_id: cut_layer}`` map covering the robots that
    keep an edge prefix, and the full-arch ``CutAssignment``.  Robots the
    planner sends cloud-only are absent from the map.
    """

    from repro.partition.executor import PartitionExecutor
    from repro.partition.planner import NETWORK_PROFILES, assign_cuts

    from repro.partition.graph import build_graph

    channel = NETWORK_PROFILES[network]
    full_cfg = get_config(arch)
    graph = build_graph(full_cfg)
    # the split executor cannot run a pure edge-only deployment (the LM
    # head always lives cloud-side), so cap the assignment at the deepest
    # EXECUTABLE cut — fully-redundant robots get every layer on the edge
    # but keep the head ping-pong priced honestly
    assignment = assign_cuts(
        telemetry, k_max=k_max, cfg=full_cfg, graph=graph, channel=channel,
        max_cut=len(graph.nodes) - 1,
    )
    if verbose:
        print(f"cut assignment [{network}]:", assignment.summary())
    if model.cfg.encoder_decoder:
        if verbose:
            print("encoder-decoder split execution not supported: "
                  "serving unpartitioned")
        return None, {}, assignment
    # map full-arch edge layer counts onto this model's stack; nudge apart
    # full cuts that would collapse onto the same (smoke) layer so the fleet
    # stays genuinely heterogeneous whenever the stack has room
    n_layers = model.cfg.num_layers
    full_layers = max(full_cfg.num_layers, 1)
    smoke_of: Dict[int, int] = {}
    prev = -1
    for cl in sorted({c for c in assignment.cut_layers if c >= 0}):
        s = min(max(int(round(cl / full_layers * n_layers)), prev + 1), n_layers)
        smoke_of[cl] = s
        prev = s
    robot_cuts = {
        r: smoke_of[cl]
        for r, cl in enumerate(assignment.cut_layers) if cl >= 0
    }
    if not robot_cuts:
        if verbose:
            print("assignment is all-cloud: serving unpartitioned")
        return None, {}, assignment
    base_cut = min(set(robot_cuts.values()))
    executor = PartitionExecutor(model, params, base_cut, channel=channel)
    if verbose:
        lanes = {c: sum(1 for v in robot_cuts.values() if v == c)
                 for c in sorted(set(robot_cuts.values()))}
        lane_str = " ".join(
            f"{n}x{c}-layer-edge" for c, n in lanes.items()
        )
        print(f"heterogeneous fleet: {lane_str} "
              f"(of {n_layers} layers; "
              f"{len(assignment.cuts) - len(robot_cuts)} cloud-only)")
    return executor, robot_cuts, assignment


def replan_from_telemetry(arch: str, telemetry, network: str = "wan",
                          pipelined: bool = False, verbose: bool = True):
    """Close the planner loop with the fleet's realized offload fraction.

    Replaces the global trigger-sim constant with ``telemetry``'s realized
    fleet offload fraction (a ``FleetTelemetry`` or a float), then compares
    three prices at that fraction: the re-planned cut, the global-fraction
    cut re-priced, and returns ``(plan, global_plan, repriced_global)``.
    The re-planned cut is never worse than the re-priced global cut —
    the planner minimizes over all cuts at the realized fraction.
    """

    from repro.partition.planner import (
        NETWORK_PROFILES, evaluate_cut, plan_partition,
    )

    frac = (
        telemetry if isinstance(telemetry, float)
        else telemetry.fleet_offload_fraction()
    )
    # floor: a fleet that never offloaded still needs the occasional refresh
    # priced in, and f=0 would degenerate interior cuts to prefix-only cost
    frac = min(max(frac, 0.02), 1.0)
    cfg = get_config(arch)
    channel = NETWORK_PROFILES[network]
    plan = plan_partition(
        cfg, channel=channel, offload_fraction=frac, pipelined=pipelined
    )
    global_plan = plan_partition(cfg, channel=channel, pipelined=pipelined)
    repriced = evaluate_cut(
        cfg, global_plan.cut, channel=channel,
        offload_fraction=frac, pipelined=pipelined,
    )
    if verbose:
        print(f"replan @ realized f_off={frac:.3f}:", plan.summary())
        print(
            f"  global-fraction cut {global_plan.cut} re-priced at realized "
            f"fraction: {repriced.total_ms:.1f}ms "
            f"(re-planned: {plan.total_ms:.1f}ms)"
        )
    return plan, global_plan, repriced


def fleet_executor(model: Model, params, arch: str, partition: str,
                   network: str = "wan", plan_2d: bool = False):
    """The split executor a mixed fleet's split robots serve through.

    ``partition`` is ``"auto"`` (``plan_fleet_partition``: ``None`` where
    the planner keeps the whole model on one side) or an integer edge
    layer count, which serves split lanes at that cut whatever the planner
    would pick.
    """

    if partition == "auto":
        return plan_fleet_partition(
            model, params, arch, network, plan_2d=plan_2d
        )[0]
    from repro.partition.executor import PartitionExecutor
    from repro.partition.planner import NETWORK_PROFILES

    return PartitionExecutor(
        model, params, int(partition), channel=NETWORK_PROFILES[network]
    )


def build_policy(model: Model, params, tok: EpisodeTokenizer, arch: str,
                 partition: str = "none", network: str = "wan",
                 paged: bool = False, plan_2d: bool = False,
                 verbose: bool = True):
    """Build the serving policy, optionally split per the partition planner.

    ``partition``: ``"none"`` (single-device CloudPolicy), ``"auto"`` (plan
    the compatibility-optimal cut for the FULL ``arch`` config and map its
    layer fraction onto this — possibly smoke-scale — model), or an integer
    edge layer count for an explicit split.  ``network`` picks the channel
    regime the planner prices (``lan`` / ``wan`` / ``congested``).
    ``paged`` routes the unpartitioned policy's decode through the paged KV
    substrate instead of dense per-slot slabs (identical greedy chunks).
    ``plan_2d`` (with ``"auto"``) plans over (cut layer x placement) and
    realizes the best executable 2-D plan — see ``plan_fleet_partition``.
    """

    if partition == "none":
        return CloudPolicy(model, params, tok, paged=paged), None

    from repro.partition.executor import PartitionExecutor, PartitionedPolicy
    from repro.partition.planner import NETWORK_PROFILES, plan_partition

    if partition == "auto":
        executor, plan = plan_fleet_partition(
            model, params, arch, network, verbose=verbose, plan_2d=plan_2d
        )
        if executor is None:
            return CloudPolicy(model, params, tok, paged=paged), plan
        return PartitionedPolicy(executor, tok), plan

    channel = NETWORK_PROFILES[network]
    plan = plan_partition(get_config(arch), channel=channel)
    if verbose:
        print(f"partition plan [{network}]:", plan.summary())
    cut = int(partition)
    executor = PartitionExecutor(model, params, cut, channel=channel)
    if verbose:
        print(f"split execution: {cut}/{model.cfg.num_layers} layers on the edge")
    return PartitionedPolicy(executor, tok), plan


def serving_config(arch: str, full: bool = False, layers: Optional[int] = None):
    """The config a serving entry point builds for ``arch``.

    The smoke preset by default; ``full`` gives the published widths
    (``get_config``).  ``layers`` cuts the depth and keeps every width.
    """

    cfg = get_config(arch) if full else get_smoke_config(arch)
    if layers is not None:
        if not 0 < layers <= cfg.num_layers:
            raise ValueError(
                f"layers must be in [1, {cfg.num_layers}] for {cfg.name}"
            )
        cfg = cfg.replace(num_layers=layers)
    return cfg


def add_model_args(p: argparse.ArgumentParser) -> None:
    """The model-selection options shared by the serving CLIs."""

    p.add_argument("--arch", default="openvla-7b")
    p.add_argument("--full", action="store_true",
                   help="serve --arch at its published widths instead of "
                        "the smoke preset")
    p.add_argument("--layers", type=int, default=None,
                   help="cut the served model's depth to N layers")


def main(argv=None):
    p = argparse.ArgumentParser()
    add_model_args(p)
    p.add_argument("--task", default="pick_place")
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
    p.add_argument("--trigger", default="always", choices=["always", "rapid"],
                   help="fleet dispatch policy: always-offload or the "
                        "closed-loop redundancy-aware RAPID trigger")
    p.add_argument("--assign-cuts", action="store_true",
                   help="two-episode closed loop: episode 1 gathers realized "
                        "per-robot offload fractions, then each robot is "
                        "re-assigned its own cut and episode 2 serves the "
                        "heterogeneous fleet")
    p.add_argument("--k-max", type=int, default=3,
                   help="max distinct concurrently-active cuts")
    p.add_argument("--scan-rounds", type=int, default=1,
                   help="decode rounds per jitted scan window (device-"
                        "resident decode; 1 = per-round stepping)")
    p.add_argument("--sharded", action="store_true",
                   help="shard the cloud engine (page pools, decode rows, "
                        "params) over every host device's data axis; test "
                        "multi-device on CPU with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N")
    p.add_argument("--disaggregate-prefill", action="store_true",
                   help="run prompt prefill on its own device group, "
                        "handing off via the paged cache at window "
                        "boundaries (prefill/decode disaggregation)")
    p.add_argument("--defer-hot", type=float, default=None,
                   help="cancellation-aware admission: preempt-rate "
                        "threshold above which a preempting robot's "
                        "admission is held one round")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome-trace/Perfetto JSON of the fleet "
                        "run's request lifecycles (load in ui.perfetto.dev)")
    p.add_argument("--metrics-json", default=None, metavar="PATH",
                   help="dump the run's metrics registry as flat JSON")
    p.add_argument("--metrics-prom", default=None, metavar="PATH",
                   help="dump the metrics in Prometheus text exposition")
    args = p.parse_args(argv)

    cfg = serving_config(args.arch, args.full, args.layers)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    if args.fleet:
        want_obs = bool(args.trace_out or args.metrics_json or args.metrics_prom)
        mk_obs = (
            (lambda: Observability(trace=args.trace_out is not None))
            if want_obs else (lambda: None)
        )
        executor = None
        split = []
        robot_cuts = None
        if args.partition != "none":
            # mixed fleet: every second robot serves through the planned
            # edge-cloud split; they share decode rounds with the rest
            executor = fleet_executor(
                model, params, args.arch, args.partition, args.network,
                plan_2d=args.plan_2d,
            )
            if executor is not None:
                split = list(range(1, args.fleet, 2))
            if args.plan_2d and executor is not None and split:
                # 2-D serving demo on MoE archs: alternate the split robots
                # between the planned cut lane and the 2-D space's best
                # expert-offload point, so layer-cut and gather/scatter
                # lanes genuinely share decode rounds
                lane = plan_expert_lane(
                    model, params, args.arch, args.network, base=executor
                )
                if lane is not None and lane.lane_key != executor.lane_key:
                    robot_cuts = {
                        r: (executor.lane_key if i % 2 == 0 else lane.lane_key)
                        for i, r in enumerate(split)
                    }
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
        out = serve_fleet(
            model, params, tok, n_robots=args.fleet, max_steps=args.steps,
            partition_executor=executor, split_robots=split,
            robot_cuts=robot_cuts,
            trigger=args.trigger, defer_hot_admission=args.defer_hot,
            scan_rounds=args.scan_rounds, obs=mk_obs(),
            mesh=mesh, prefill_group=prefill_group,
        )
        if args.assign_cuts:
            # close the loop: re-assign per-robot cuts from episode 1's
            # realized fractions and serve the next episode heterogeneously
            executor2, robot_cuts, _ = assign_fleet_cuts(
                model, params, args.arch, out["telemetry"], args.network,
                k_max=args.k_max,
            )
            if robot_cuts:
                # fresh Observability per episode: the exported trace and
                # SLO report describe the heterogeneous episode alone
                out = serve_fleet(
                    model, params, tok, n_robots=args.fleet,
                    max_steps=args.steps, partition_executor=executor2,
                    robot_cuts=robot_cuts, trigger=args.trigger,
                    defer_hot_admission=args.defer_hot,
                    scan_rounds=args.scan_rounds, obs=mk_obs(),
                )
        elif args.trigger == "rapid" and args.partition != "none":
            replan_from_telemetry(args.arch, out["telemetry"], args.network)
        obs = out.get("obs")
        if obs is not None:
            if args.trace_out:
                obs.trace.write(args.trace_out)
                print(f"trace: {obs.trace.n_events} events -> {args.trace_out}")
            if args.metrics_json:
                with open(args.metrics_json, "w") as f:
                    json.dump(obs.metrics.to_json(), f, indent=1)
                print(f"metrics: -> {args.metrics_json}")
            if args.metrics_prom:
                with open(args.metrics_prom, "w") as f:
                    f.write(obs.metrics.to_prometheus())
                print(f"metrics: -> {args.metrics_prom}")
        return out
    policy, _ = build_policy(
        model, params, tok, args.arch, args.partition, args.network,
        paged=args.paged,
    )
    return serve_episode(policy, task=args.task, max_steps=args.steps)


if __name__ == "__main__":
    main()
