"""Fleet-scale serving tests: served chunks against the isolated policies,
batch scheduler entry points, trace-driven arrivals, and episode churn."""

import functools

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.launch.serve import serve_fleet
from repro.models.model import Model
from repro.runtime.fleet import (
    FleetTrace,
    bursty_trace,
    make_trace,
    poisson_trace,
    serve_trace,
)
from repro.runtime.scheduler import ContinuousBatchingScheduler

PAGES_PER_REQ = -(-(14 + 56) // 16)


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke_config("openvla-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return cfg, model, params, tok


@functools.lru_cache(maxsize=None)
def _f32_stack(arch):
    cfg = get_smoke_config(arch).replace(dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return model, params, tok


# ---------------------------------------------------------------------------
# every served chunk == the isolated policy on the robot's observation
# ---------------------------------------------------------------------------

# case -> (arch, serve_fleet kwargs, robot_cuts)
CHUNK_CASES = {
    "always": (
        "openvla-7b",
        dict(n_robots=6, max_steps=80, max_slots=4, seed=3, scan_rounds=2),
        None,
    ),
    "rapid": (
        "openvla-7b",
        dict(n_robots=6, max_steps=80, max_slots=4, seed=3, scan_rounds=2,
             trigger="rapid", defer_hot_admission=0.2),
        None,
    ),
    "mixed_cuts": (
        "openvla-7b",
        dict(n_robots=4, max_steps=60, max_slots=2, scan_rounds=4),
        {1: 1, 2: 2, 3: 1},
    ),
    "expert_lane": (
        "qwen3-moe-235b-a22b",
        dict(n_robots=4, max_steps=24),
        {1: 1, 3: (2, (0,))},
    ),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_serve_fleet_chunks_match_isolated_policy(case):
    """Every chunk the fleet harvests equals the isolated batch-1 policy
    (``CloudPolicy``, or ``PartitionedPolicy`` at the robot's lane key) on
    that robot's episode observation at the tick it was submitted (f32)."""

    from repro.launch.serve import CloudPolicy
    from repro.partition.executor import PartitionExecutor, PartitionedPolicy
    from repro.robotics.episodes import generate_episode

    arch, kw, robot_cuts = CHUNK_CASES[case]
    model, params, tok = _f32_stack(arch)
    ex = None
    if robot_cuts:
        ex = PartitionExecutor(model, params, cut_layer=1)
        kw = dict(kw, partition_executor=ex, robot_cuts=robot_cuts)
    out = serve_fleet(model, params, tok, verbose=False, **kw)

    # the episodes serve_fleet builds, robot by robot
    tasks = ["pick_place", "drawer_open", "peg_insertion"]
    seed = kw.get("seed", 0)
    eps = [
        generate_episode(tasks[r % len(tasks)], seed=seed + r)
        for r in range(kw["n_robots"])
    ]
    policies = {None: CloudPolicy(model, params, tok)}
    for key in set((robot_cuts or {}).values()):
        cut, off = key if isinstance(key, tuple) else (key, ())
        policies[key] = PartitionedPolicy(
            ex.with_cut(cut, expert_offload=off), tok
        )
    assert out["chunks"], "no chunk was served"
    split_served = set()
    for r, t, tokens in out["chunks"]:
        key = (robot_cuts or {}).get(r)
        split_served.add(key)
        want = policies[key](eps[r].qd[t][None], eps[r].tau[t][None])[0]
        got = tok.decode_action(tokens).reshape(8, 7)
        np.testing.assert_array_equal(
            want, got, err_msg=f"robot {r} tick {t} lane {key}"
        )
    assert split_served == set(policies), "a lane served no chunk"


# ---------------------------------------------------------------------------
# batched scheduler entry points
# ---------------------------------------------------------------------------


def test_submit_batch_matches_serial_submits(stack):
    """submit_batch leaves the scheduler in the same state as N serial
    submits: same FIFO order stamps, same lanes, same deferral, and the
    drained chunks are identical."""

    _, model, params, tok = stack
    rng = np.random.default_rng(11)
    qd = rng.normal(0, 0.5, (4, 7)).astype(np.float32)
    tau = rng.normal(0, 0.5, (4, 7)).astype(np.float32)

    serial = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    for r in range(4):
        serial.submit(r, qd[r][None], tau[r][None], defer_rounds=1 if r == 2 else 0)
    batched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    batched.submit_batch(
        np.arange(4), qd, tau, defer_rounds=np.array([0, 0, 1, 0])
    )

    for qa, qb in zip(serial._queue, batched._queue):
        assert qa.robot_id == qb.robot_id
        assert qa.order == qb.order
        assert qa.earliest_round == qb.earliest_round
        np.testing.assert_array_equal(qa.obs, qb.obs)
    assert serial.deferred == batched.deferred == 1

    a = {r.robot_id: r.tokens for r in serial.drain()}
    b = {r.robot_id: r.tokens for r in batched.drain()}
    assert a.keys() == b.keys()
    for r in a:
        np.testing.assert_array_equal(a[r], b[r])


def test_cancel_batch_reports_per_robot_hits(stack):
    _, model, params, tok = stack
    rng = np.random.default_rng(12)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    qd = rng.normal(0, 0.5, (2, 7)).astype(np.float32)
    tau = rng.normal(0, 0.5, (2, 7)).astype(np.float32)
    sched.submit_batch(np.array([0, 1]), qd, tau)
    hits = sched.cancel_batch(np.array([1, 7]))
    assert hits.tolist() == [True, False]
    assert sched.cancelled == 1
    assert sched.n_pending == 1


# ---------------------------------------------------------------------------
# batched channel jitter
# ---------------------------------------------------------------------------


def test_sample_latency_ms_batch_bit_identical_to_serial():
    """One vmapped draw per (robot, ordinal) reproduces the serial
    fold_in-keyed stream bit for bit (threefry is deterministic per lane)."""

    from repro.runtime.channel import (
        ChannelConfig,
        sample_latency_ms,
        sample_latency_ms_batch,
    )

    cfg = ChannelConfig()
    key = jax.random.PRNGKey(3 + 7919)
    robots = np.array([0, 5, 0, 1023], np.int64)
    ords = np.array([0, 2, 1, 7], np.int64)
    got = sample_latency_ms_batch(cfg, 8, key, robots, ords)
    want = [
        sample_latency_ms(
            cfg, 8, jax.random.fold_in(jax.random.fold_in(key, int(r)), int(o))
        )
        for r, o in zip(robots, ords)
    ]
    assert got == want
    assert sample_latency_ms_batch(cfg, 8, key, [], []) == []


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------


def test_poisson_trace_invariants():
    tr = poisson_trace(128, 200, mean_dwell=80, seed=1)
    assert tr.n_robots == 128
    assert (tr.join_tick >= 0).all() and (tr.join_tick < 200).all()
    assert (tr.leave_tick > tr.join_tick).all()
    assert (tr.leave_tick <= 200).all()
    assert tr.active_at(0).sum() <= 128
    # churn means someone actually leaves before the horizon
    assert (tr.leave_tick < 200).any()
    # reproducible
    tr2 = poisson_trace(128, 200, mean_dwell=80, seed=1)
    np.testing.assert_array_equal(tr.join_tick, tr2.join_tick)


def test_bursty_trace_clusters_arrivals():
    tr = bursty_trace(64, 200, burst_every=50, burst_size=16, seed=2)
    # arrivals concentrate in burst windows: every join within 2 ticks of
    # a burst start
    rel = tr.join_tick % 50
    assert (rel <= 2).all()
    assert len(np.unique(tr.join_tick // 50)) >= 3


def test_make_trace_dispatch():
    assert isinstance(make_trace(8, 50, arrivals="poisson"), FleetTrace)
    assert isinstance(make_trace(8, 50, arrivals="bursty"), FleetTrace)
    with pytest.raises(ValueError, match="arrivals"):
        make_trace(8, 50, arrivals="uniform")


# ---------------------------------------------------------------------------
# trace-driven serving + episode churn
# ---------------------------------------------------------------------------


def test_serve_trace_poisson_slo_and_churn(stack):
    """The harness drives the real scheduler through arrivals and churn
    and reports through the SLO layer."""

    from repro.obs import Observability

    _, model, params, tok = stack
    tr = make_trace(24, 120, arrivals="poisson", mean_dwell=60, seed=4)
    obs = Observability(trace=False)
    out = serve_trace(
        model, params, tok, tr, horizon=120, max_slots=4, scan_rounds=2,
        trigger="rapid", obs=obs, verbose=False,
    )
    assert out["joined"] == 24
    assert out["left"] > 0
    assert out["completions"] > 0
    assert out["slo"] is not None
    assert out["slo"]["completions"] == out["completions"]
    assert out["slo"]["chunk_latency_ms"]["count"] == out["completions"]
    assert out["ticks_per_s"] > 0
    m = obs.metrics
    assert m.counter("fleet.joins").value == 24
    assert m.counter("fleet.leaves").value == out["left"]


def test_churn_reclaims_pages_without_reset(stack):
    """Robots leaving mid-serve hand their pages back through
    cancel_batch: once everyone is gone the pool reads in_use == 0 with
    no engine reset in between."""

    _, model, params, tok = stack
    n = 12
    rng = np.random.default_rng(5)
    # everyone joins early and leaves well before the horizon
    tr = FleetTrace(
        join_tick=rng.integers(0, 8, n).astype(np.int64),
        leave_tick=rng.integers(40, 70, n).astype(np.int64),
        episode=rng.integers(0, 3, n).astype(np.int64),
        offset=rng.integers(0, 512, n).astype(np.int64),
    )
    out = serve_trace(
        model, params, tok, tr, horizon=100, max_slots=4, scan_rounds=2,
        trigger="rapid", verbose=False,
    )
    assert out["left"] == n
    assert out["in_flight"] == 0
    assert out["pending"] == 0
    assert out["pool"].pages_in_use == 0
    assert out["pool"].high_water > 0, "fleet never used the pool"
    assert out["completions"] + out["cancels"] > 0


def test_churn_releases_split_lane_rows(stack):
    """A partitioned fleet that fully churns out leaves its lane empty:
    row state dropped (lazily re-allocated on next admission) and every
    page returned — reset-free reclamation across the split path too."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    n = 6
    tr = FleetTrace(
        join_tick=np.zeros(n, np.int64),
        leave_tick=np.full(n, 50, np.int64),
        episode=np.arange(n, dtype=np.int64) % 3,
        offset=np.zeros(n, np.int64),
    )
    out = serve_trace(
        model, params, tok, tr, horizon=80, max_slots=3, scan_rounds=2,
        trigger="rapid", partition_executor=ex,
        robot_cuts={r: 1 for r in range(n)}, verbose=False,
    )
    assert out["left"] == n
    assert out["pool"].pages_in_use == 0
    assert int(out["telemetry"].fires.sum()) > 0
    assert out["in_flight"] == 0 and out["pending"] == 0
    lane = out["sched"]._lanes[1]
    assert not lane.seqs and not lane.queue
    # the emptied lane dropped its row arrays (edge caches, page tables):
    # an idle cut pins no memory until its next admission
    assert lane._state is None and lane._edge is None


def test_serve_trace_always_mode_backlog(stack):
    """always-offload under a tiny pool builds a backlog but never leaks:
    at the horizon, resident pages == in-flight requests' pages."""

    _, model, params, tok = stack
    tr = make_trace(16, 60, arrivals="bursty", burst_every=16, seed=6)
    out = serve_trace(
        model, params, tok, tr, horizon=60, max_slots=2, trigger="always",
        verbose=False,
    )
    assert out["completions"] > 0
    assert out["pool"].pages_in_use % PAGES_PER_REQ == 0
