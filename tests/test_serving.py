"""Serving-engine tests: fused chunk decode, ragged decode, scheduler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.launch.serve import CloudPolicy, serve_fleet
from repro.models.model import Model
from repro.runtime.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke_config("openvla-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return cfg, model, params, tok


@pytest.fixture(scope="module")
def f32_stack():
    # exact parity with the isolated policies is pinned on f32: in bf16 the
    # batched rows, the fused scan and the materialized cut activation may
    # round differently from a batch-1 per-token decode
    cfg = get_smoke_config("openvla-7b").replace(
        dtype="float32", param_dtype="float32"
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return cfg, model, params, tok


def _obs(rng, b=1):
    qd = rng.normal(0, 0.5, (b, 7)).astype(np.float32)
    tau = rng.normal(0, 0.5, (b, 7)).astype(np.float32)
    return qd, tau


# ---------------------------------------------------------------------------
# fused on-device chunk decode
# ---------------------------------------------------------------------------


def test_fused_chunk_matches_step_logits_greedy(f32_stack):
    """The lax.scan chunk decoder emits exactly the tokens that feeding
    each greedy token through single-token ``decode_step`` calls yields
    (``CloudPolicy.step_logits``; f32)."""

    _, model, params, tok = f32_stack
    policy = CloudPolicy(model, params, tok)
    rng = np.random.default_rng(3)
    for b in (1, 3):
        qd, tau = _obs(rng, b)
        chunk = policy(qd, tau)
        assert chunk.shape == (b, 8, 7)
        _, fed = policy.step_logits(qd, tau, 56)
        want = tok.decode_action(fed).reshape(b, 8, 7)
        np.testing.assert_array_equal(chunk, want)


def test_paged_policy_matches_dense(stack):
    """CloudPolicy(paged=True) must emit the dense policy's exact chunks."""

    _, model, params, tok = stack
    dense = CloudPolicy(model, params, tok)
    paged = CloudPolicy(model, params, tok, paged=True)
    rng = np.random.default_rng(17)
    for b in (1, 3):
        qd, tau = _obs(rng, b)
        np.testing.assert_array_equal(dense(qd, tau), paged(qd, tau))


def test_fused_chunk_tokens_in_action_range(stack):
    _, model, params, tok = stack
    policy = CloudPolicy(model, params, tok)
    rng = np.random.default_rng(5)
    qd, tau = _obs(rng)
    acts = policy(qd, tau)
    assert np.all(np.abs(acts) <= tok.action_clip + 1e-6)


# ---------------------------------------------------------------------------
# ragged decode step (vector cache lengths)
# ---------------------------------------------------------------------------


def test_ragged_decode_step_matches_per_sequence(stack):
    """A batch at mixed depths must equal each sequence decoded alone."""

    _, model, params, tok = stack
    rng = np.random.default_rng(11)
    prompt = 14
    extra = 8
    prefill = jax.jit(lambda p, b: model.prefill(p, b, extra=extra))
    decode = jax.jit(model.decode_step)

    obs = rng.integers(tok.state_base, tok.action_base, (3, prompt))
    logits, cache = prefill(params, {"tokens": jnp.asarray(obs)})

    # advance sequence 0 by two tokens, sequence 1 by one, sequence 2 by none
    per_seq_logits = []
    for i, depth in enumerate((2, 1, 0)):
        li, ci = prefill(params, {"tokens": jnp.asarray(obs[i : i + 1])})
        tok_i = jnp.argmax(li[:, -1], -1)[:, None]
        for _ in range(depth):
            li, ci = decode(params, tok_i, ci)
            tok_i = jnp.argmax(li[:, -1], -1)[:, None]
        per_seq_logits.append((np.asarray(li[:, -1]), ci, tok_i))

    # build the ragged batch state by replaying the same tokens jointly
    lens = jnp.asarray([prompt, prompt, prompt], jnp.int32)
    cache = dict(cache)
    cache["len"] = lens
    toks = jnp.argmax(logits[:, -1], -1)[:, None]
    # step the whole batch twice; freeze rows once they hit their depth by
    # re-feeding their own last token (rows are independent, so rows past
    # their depth only matter through their final logits, checked below)
    logits_rows = logits
    for step in range(2):
        logits_rows, cache = decode(params, toks, cache)
        toks = jnp.argmax(logits_rows[:, -1], -1)[:, None]

    # row 0 advanced 2 steps jointly == sequence 0 advanced 2 steps alone
    np.testing.assert_allclose(
        np.asarray(logits_rows[0, -1]), per_seq_logits[0][0][0], atol=1e-5, rtol=1e-5
    )
    assert int(cache["len"][0]) == prompt + 2


def test_ragged_vector_lens_write_slots(stack):
    """Vector cache lengths place each sequence's token at its own slot."""

    from repro.models import attention as attn

    cfg, model, params, _ = stack
    b, s_cache = 3, 32
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    p0 = jax.tree.map(lambda a: a[0], params["unit"][0])["attn"]
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (b, 1, cfg.d_model)),
                    model.dtype)
    ck = jnp.zeros((b, s_cache, nkv, hd), model.dtype)
    cv = jnp.zeros_like(ck)
    lens = jnp.asarray([0, 5, 17], jnp.int32)
    _, nk, _ = attn.attention_decode_step(x, p0, cfg, ck, cv, lens, 0)
    nk = np.asarray(nk, np.float32)
    for i, l in enumerate((0, 5, 17)):
        assert np.any(nk[i, l] != 0), f"row {i} missing write at slot {l}"
        untouched = [j for j in range(s_cache) if j != l]
        assert not np.any(nk[i, untouched] != 0), f"row {i} wrote outside slot {l}"


# ---------------------------------------------------------------------------
# continuous-batching scheduler
# ---------------------------------------------------------------------------


def test_scheduler_matches_cloud_policy_staggered(f32_stack):
    """Chunks from ragged in-flight batches == isolated CloudPolicy calls."""

    _, model, params, tok = f32_stack
    policy = CloudPolicy(model, params, tok)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    rng = np.random.default_rng(0)
    reqs = [(r, *_obs(rng)) for r in range(6)]

    results = {}
    for r, qd, tau in reqs[:3]:
        sched.submit(r, qd, tau)
    nxt = 3
    while len(results) < len(reqs):
        for res in sched.step():
            results[res.robot_id] = res
        if nxt < len(reqs) and sched.round % 2 == 0:
            sched.submit(*reqs[nxt])  # joins while others are mid-decode
            nxt += 1

    assert sched.peak_active > 1, "requests never overlapped"
    for r, qd, tau in reqs:
        want = policy(qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got)


def test_scheduler_defers_when_pool_exhausted(stack):
    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=4,
        num_pages=2 * -(-(14 + 56) // 16),  # room for exactly two requests
    )
    rng = np.random.default_rng(1)
    for r in range(4):
        sched.submit(r, *_obs(rng))
    sched.step()
    assert sched.n_active == 2 and sched.n_pending == 2
    results = sched.drain()
    assert {res.robot_id for res in results} == {0, 1, 2, 3}
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_scheduler_releases_pages(stack):
    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=2)
    rng = np.random.default_rng(2)
    sched.submit(0, *_obs(rng))
    results = sched.drain()
    assert len(results) == 1
    assert results[0].tokens.shape == (56,)
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_serve_fleet_end_to_end(stack):
    _, model, params, tok = stack
    out = serve_fleet(
        model, params, tok, n_robots=2, max_steps=60, max_slots=2, verbose=False
    )
    assert out["actions"].shape == (60, 2, 7)
    assert out["offloads"].sum() > 0
    assert len(out["service_rounds"]) > 0
    # satellite: offload latency is sampled per chunk, not deterministic
    assert len(out["offload_ms"]) == len(out["service_rounds"])
    if len(out["offload_ms"]) > 1:
        assert np.std(out["offload_ms"]) > 0.0


# ---------------------------------------------------------------------------
# page-bounded admission (the paged substrate replaces fixed slots)
# ---------------------------------------------------------------------------


def test_scheduler_admits_beyond_initial_rows(f32_stack):
    """Residency is bounded by free pages, not by the old slot count."""

    _, model, params, tok = f32_stack
    pages_per_req = -(-(14 + 56) // 16)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=2, num_pages=5 * pages_per_req
    )
    policy = CloudPolicy(model, params, tok)
    rng = np.random.default_rng(8)
    reqs = [(r, *_obs(rng)) for r in range(5)]
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau)
    sched.step()
    assert sched.n_active == 5 > 2, "admission stopped at the old slot bound"
    assert sched.rows >= 5, "row arrays failed to grow"
    results = {res.robot_id: res for res in sched.drain()}
    for r, qd, tau in reqs:
        want = policy(qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got)


def test_chunk_result_reports_pool_utilization(stack):
    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=2)
    rng = np.random.default_rng(12)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng))
    results = sched.drain()
    assert len(results) == 2
    for res in results:
        assert res.pool is not None
        total = res.pool.pages_in_use + res.pool.pages_free
        assert total == sched.allocator.num_pages
        assert res.pool.high_water >= res.pool.pages_in_use
    # both admitted together: high-water saw both requests resident
    assert results[0].pool.high_water == 2 * sched.pages_per_req
    assert sched.pool_stats().pages_in_use == 0


# ---------------------------------------------------------------------------
# mixed fleet: partitioned + cloud-only robots share decode rounds
# ---------------------------------------------------------------------------


def test_mixed_kinds_share_rounds_and_match_isolated(f32_stack):
    """Cloud-only and split suffixes decode in the same scheduler rounds,
    each reproducing its isolated-path chunk exactly."""

    from repro.partition.executor import PartitionExecutor, PartitionedPolicy

    _, model, params, tok = f32_stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    sched.attach_partition(ex)
    rng = np.random.default_rng(21)
    reqs = [(r, *_obs(rng)) for r in range(4)]
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau, partitioned=(r % 2 == 1))
    results = {res.robot_id: res for res in sched.drain()}

    assert sched.mixed_rounds > 0, "kinds never decoded in the same round"
    assert {results[r].kind for r, _, _ in reqs} == {"cloud", "split"}

    cloud = CloudPolicy(model, params, tok)
    split = PartitionedPolicy(ex, tok)
    for r, qd, tau in reqs:
        want = (cloud if r % 2 == 0 else split)(qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got, err_msg=f"robot {r}")


def test_split_lane_shares_page_pool(f32_stack):
    """Split suffixes draw from the same allocator as cloud sequences."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = f32_stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    # pool holds exactly two requests: one cloud + one split fill it
    pages_per_req = -(-(14 + 56) // 16)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=4, num_pages=2 * pages_per_req
    )
    sched.attach_partition(ex)
    rng = np.random.default_rng(22)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng), partitioned=True)
    sched.submit(2, *_obs(rng))
    sched.submit(3, *_obs(rng), partitioned=True)
    sched.step()
    assert sched.n_active == 2 and sched.n_pending == 2
    assert sched.allocator.num_free == 0
    results = sched.drain()
    assert {res.robot_id for res in results} == {0, 1, 2, 3}
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_split_lane_admits_while_a_sequence_decodes(f32_stack):
    """Continuous arrivals into a pipelined split lane: a robot arrives at
    every window boundary while earlier ones still decode in the lane, so
    each admission's flush writes into the logits the last harvest read
    back.  Every chunk matches its isolated split path (f32)."""

    from repro.partition.executor import PartitionExecutor, PartitionedPolicy

    _, model, params, tok = f32_stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=2, scan_rounds=2
    )
    sched.attach_partition(ex)
    rng = np.random.default_rng(23)
    reqs = [(r, *_obs(rng)) for r in range(3)]
    results = {}
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau, partitioned=True)
        for _ in range(sched.scan_rounds):  # one window: admit ... harvest
            results.update((res.robot_id, res) for res in sched.step())
        assert sched._window is None and sched._lanes[1].seqs
    results.update((res.robot_id, res) for res in sched.drain())

    split = PartitionedPolicy(ex, tok)
    for r, qd, tau in reqs:
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(split(qd, tau)[0], got, err_msg=f"robot {r}")


def test_hetero_cuts_share_rounds_and_match_isolated(f32_stack):
    """Acceptance: a mixed fleet with >= 2 distinct active cuts shares one
    page allocator and decode rounds, and every robot's chunk matches its
    isolated single-cut path exactly (f32)."""

    from repro.partition.executor import PartitionExecutor, PartitionedPolicy

    _, model, params, tok = f32_stack
    ex1 = PartitionExecutor(model, params, cut_layer=1)
    ex2 = ex1.with_cut(2)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=6)
    sched.attach_partition(ex1)
    sched.attach_partition(ex2)
    rng = np.random.default_rng(41)
    cuts = {0: None, 1: 1, 2: 2, 3: 1, 4: 2, 5: None}
    reqs = [(r, *_obs(rng)) for r in cuts]
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau, partitioned=cuts[r] is not None, cut=cuts[r])
    results = {res.robot_id: res for res in sched.drain()}

    assert sched.hetero_rounds > 0, "distinct cuts never decoded together"
    assert sched.mixed_rounds > 0
    assert {results[r].cut for r in cuts} == {None, 1, 2}
    assert sched.allocator.num_free == sched.allocator.num_pages

    policies = {
        None: CloudPolicy(model, params, tok),
        1: PartitionedPolicy(ex1, tok),
        2: PartitionedPolicy(ex2, tok),
    }
    for r, qd, tau in reqs:
        want = policies[cuts[r]](qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got, err_msg=f"robot {r} cut {cuts[r]}")


def test_hetero_lanes_no_leak_and_release_row_arrays(f32_stack):
    """Satellite: cancelling a lane's last member releases the lane's row
    arrays, not just its rows — and across >= 2 concurrent lanes the shared
    pool drains to PoolStats.in_use == 0."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = f32_stack
    ex1 = PartitionExecutor(model, params, cut_layer=1)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=6)
    sched.attach_partition(ex1)
    sched.attach_partition(ex1.with_cut(2))
    rng = np.random.default_rng(42)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng), partitioned=True, cut=1)
    sched.submit(2, *_obs(rng), partitioned=True, cut=2)
    sched.step()  # all admitted, all lanes mid-decode
    assert sched.active_cuts == [1, 2]
    assert all(lane.has_buffers for lane in sched._lanes.values())
    # robot 2 was its lane's ONLY member: the cancel must drop the lane's
    # device row arrays (suffix pools + row state), not just zero its row
    assert sched.cancel(2)
    assert not sched._lanes[2].has_buffers, "emptied lane kept row arrays"
    assert sched._lanes[1].has_buffers, "lane with members must keep state"
    assert sched.allocator.num_in_use == 2 * sched.pages_per_req
    results = {res.robot_id for res in sched.drain()}
    assert results == {0, 1}
    assert sched.pool_stats().pages_in_use == 0, "leak across lanes"
    assert sched.allocator.num_free == sched.allocator.num_pages
    # completion also empties a lane -> its arrays are released too
    assert not any(lane.has_buffers for lane in sched._lanes.values())


def test_deferred_admission_holds_one_round(stack):
    """A defer_rounds=1 submission keeps its FIFO slot but is not admitted
    (no pages, no prefill) until the next round — and a cancel landing in
    that window removes a queued request, never a paid prefill."""

    _, model, params, tok = stack
    rng = np.random.default_rng(43)

    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=2)
    sched.submit(0, *_obs(rng), defer_rounds=1)
    sched.step()
    assert sched.n_active == 0 and sched.n_pending == 1
    assert sched.allocator.num_in_use == 0, "deferred request took pages"
    sched.step()
    assert sched.n_active == 1, "deferral must last exactly one round"
    assert sched.deferred == 1
    results = sched.drain()
    assert len(results) == 1 and results[0].tokens.shape == (56,)

    # cancel inside the deferral window: pure queue removal
    sched.submit(1, *_obs(rng), defer_rounds=1)
    sched.step()
    assert sched.cancel(1)
    assert sched.n_pending == 0 and sched.allocator.num_in_use == 0
    assert sched.drain() == []


def test_serve_fleet_mixed_end_to_end(stack):
    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    out = serve_fleet(
        model, params, tok, n_robots=3, max_steps=60, max_slots=2,
        partition_executor=ex, split_robots=[1], verbose=False,
    )
    assert out["actions"].shape == (60, 3, 7)
    assert out["mixed_rounds"] > 0
    assert out["split_robots"] == [1]
    assert out["pool"].high_water > 0


def test_serve_fleet_heterogeneous_cuts_end_to_end(stack):
    """serve_fleet(robot_cuts=...) runs >= 2 distinct cuts in one fleet:
    lanes are derived from the base executor via with_cut, decode rounds
    are shared, and the pool drains clean."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    out = serve_fleet(
        model, params, tok, n_robots=4, max_steps=60, max_slots=2,
        partition_executor=ex, robot_cuts={1: 1, 2: 2, 3: 1}, verbose=False,
    )
    assert out["actions"].shape == (60, 4, 7)
    assert out["robot_cuts"] == {1: 1, 2: 2, 3: 1}
    assert out["active_cuts"] == [1, 2]
    assert out["split_robots"] == [1, 2, 3]
    assert out["hetero_rounds"] > 0, "distinct cuts never decoded together"
    assert out["mixed_rounds"] > 0
    assert out["pool"].high_water > 0
    # whatever is still resident at episode end is in-flight work, a whole
    # number of requests' pages — nothing leaked from completed chunks
    assert out["pool"].pages_in_use % (-(-(14 + 56) // 16)) == 0


def test_serve_fleet_hetero_matches_offline_decision_core(stack):
    """Satellite: the heterogeneous fleet's recorded decision streams equal
    the offline rollout bit-for-bit for every robot, whatever cut it was
    assigned — cuts change WHERE a chunk is computed, never the decisions."""

    from repro.core.kinematics import KinematicFrame
    from repro.core.trigger import TriggerConfig
    from repro.partition.executor import PartitionExecutor
    from repro.robotics.episodes import generate_episode
    from repro.runtime.policy import PolicyConfig, rollout

    _, model, params, tok = stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    n_robots, max_steps, seed = 3, 200, 0
    out = serve_fleet(
        model, params, tok, n_robots=n_robots, max_steps=max_steps,
        max_slots=2, seed=seed, trigger="rapid", record_streams=True,
        partition_executor=ex, robot_cuts={0: 1, 2: 2}, verbose=False,
    )
    streams = out["telemetry"].streams()

    tasks = ["pick_place", "drawer_open", "peg_insertion"]
    eps = [
        generate_episode(tasks[i % len(tasks)], seed=seed + i)
        for i in range(n_robots)
    ]
    t_len = out["steps"]
    frames = KinematicFrame(
        q=jnp.asarray(np.stack([ep.q[:t_len] for ep in eps], 1)),
        qd=jnp.asarray(np.stack([ep.qd[:t_len] for ep in eps], 1)),
        tau=jnp.asarray(np.stack([ep.tau[:t_len] for ep in eps], 1)),
    )
    pcfg = PolicyConfig(
        trigger=TriggerConfig(cooldown_steps=7), chunk_len=8, on_empty="reuse"
    )
    _, dec = jax.jit(lambda f: rollout(pcfg, f))(frames)
    np.testing.assert_array_equal(streams["offload"], np.asarray(dec.offload))
    np.testing.assert_array_equal(streams["replayed"], np.asarray(dec.replayed))
    np.testing.assert_array_equal(streams["slot"], np.asarray(dec.slot))


def test_serve_fleet_defer_hot_admission(stack):
    """Cancellation-aware admission: with a hot trigger (cooldown shorter
    than service time) and a zero threshold, preempting robots' admissions
    are deferred — and the loop still completes chunks with exact page
    accounting."""

    from repro.core.trigger import TriggerConfig

    _, model, params, tok = stack
    kw = dict(
        n_robots=2, max_steps=300, max_slots=2, trigger="rapid",
        trigger_cfg=TriggerConfig(cooldown_steps=3), verbose=False,
    )
    out = serve_fleet(model, params, tok, defer_hot_admission=0.0, **kw)
    tel = out["telemetry"]
    assert out["deferred"] > 0, "hot preempts must defer admissions"
    assert tel.cancels.sum() > 0
    assert tel.completions.sum() > 0
    pages_per_req = -(-(14 + 56) // 16)
    in_flight = int(tel.fires.sum() - tel.completions.sum() - tel.cancels.sum())
    assert out["pool"].pages_in_use <= in_flight * pages_per_req
    # the decision core is untouched: same fires/replays with and without
    base = serve_fleet(model, params, tok, **kw)
    np.testing.assert_array_equal(tel.fires, base["telemetry"].fires)
    np.testing.assert_array_equal(tel.replays, base["telemetry"].replays)


# ---------------------------------------------------------------------------
# mid-flight cancellation (contact-phase preemption support)
# ---------------------------------------------------------------------------


def test_cancel_frees_pages_mid_flight(f32_stack):
    """Cancelling an in-flight sequence releases its pages and row; the
    survivor still decodes its exact isolated-path chunk."""

    _, model, params, tok = f32_stack
    policy = CloudPolicy(model, params, tok)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    rng = np.random.default_rng(31)
    reqs = [(r, *_obs(rng)) for r in range(2)]
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau)
    sched.step()  # both admitted, mid-decode
    assert sched.cancel(0)
    assert sched.allocator.num_in_use == sched.pages_per_req, "pages not freed"
    results = {res.robot_id: res for res in sched.drain()}
    assert set(results) == {1}, "cancelled sequence must not complete"
    want = policy(reqs[1][1], reqs[1][2])[0]
    got = tok.decode_action(results[1].tokens).reshape(8, 7)
    np.testing.assert_array_equal(want, got)
    assert sched.pool_stats().pages_in_use == 0
    assert sched.cancelled == 1


def test_cancel_queued_request_before_admission(stack):
    _, model, params, tok = stack
    pages_per_req = -(-(14 + 56) // 16)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=4, num_pages=pages_per_req
    )
    rng = np.random.default_rng(32)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng))
    sched.step()  # only robot 0 fits; robot 1 still queued
    assert sched.n_pending == 1
    assert sched.cancel(1)
    assert sched.n_pending == 0
    results = sched.drain()
    assert {res.robot_id for res in results} == {0}
    assert sched.pool_stats().pages_in_use == 0


def test_cancel_racing_final_decode_step_no_double_free(stack):
    """A preemption arriving on the chunk's last step: cancelling right
    before the finishing round frees once; cancelling right after the chunk
    completed is a no-op — never a double free."""

    _, model, params, tok = stack
    rng = np.random.default_rng(33)

    # cancel right BEFORE the finishing round (one token remaining)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=2)
    sched.submit(0, *_obs(rng))
    sched.step()  # admit + first decode block
    while next(iter(sched._seqs.values())).remaining > sched.decode_block:
        sched.step()
    assert sched.n_active == 1, "one block from completion"
    assert sched.cancel(0)
    assert sched.drain() == []
    assert sched.pool_stats().pages_in_use == 0
    assert sched.allocator.num_free == sched.allocator.num_pages

    # cancel right AFTER completion: nothing in flight, nothing double-freed
    sched.submit(0, *_obs(rng))
    results = sched.drain()
    assert len(results) == 1
    assert not sched.cancel(0), "completed sequence must not cancel"
    assert sched.pool_stats().pages_in_use == 0
    # the pool stays consistent: a fresh request is served fine
    sched.submit(0, *_obs(rng))
    assert len(sched.drain()) == 1
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_cancel_split_lane_frees_shared_pool(f32_stack):
    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = f32_stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    sched.attach_partition(ex)
    rng = np.random.default_rng(34)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng), partitioned=True)
    sched.step()
    assert sched.allocator.num_in_use == 2 * sched.pages_per_req
    assert sched.cancel(1), "split-lane sequence must be cancellable"
    assert sched.allocator.num_in_use == sched.pages_per_req
    results = {res.robot_id for res in sched.drain()}
    assert results == {0}
    assert sched.pool_stats().pages_in_use == 0


# ---------------------------------------------------------------------------
# closed-loop redundancy-aware fleet serving
# ---------------------------------------------------------------------------


def test_serve_fleet_rapid_replays_and_cancels(stack):
    """The rapid fleet replays cached chunks on redundant depletions, only
    fires offload, cancels stale in-flight work — and leaks no pages."""

    _, model, params, tok = stack
    out = serve_fleet(
        model, params, tok, n_robots=2, max_steps=300, max_slots=2,
        trigger="rapid", verbose=False,
    )
    tel = out["telemetry"]
    assert tel.replays.sum() > 0, "redundant depletions must replay the cache"
    assert tel.fires.sum() > 0, "contact phases must offload"
    assert 0.0 < out["offload_fraction"] < 1.0
    # replays never touched the scheduler: requests == fires - suppressed
    assert int(out["offloads"].sum()) == int(tel.fires.sum())
    # every page still held belongs to a request in flight at episode end —
    # cancels and completions freed everything else (no leaks)
    pages_per_req = -(-(14 + 56) // 16)
    in_flight = int(tel.fires.sum() - tel.completions.sum() - tel.cancels.sum())
    assert out["pool"].pages_in_use == in_flight * pages_per_req
    assert out["decode_rounds"] <= out["steps"]


def test_serve_fleet_rapid_cancels_in_flight_on_hot_trigger(stack):
    """With a cooldown shorter than the chunk service time, contact-phase
    fires land while the previous request is still decoding — the loop must
    cancel the stale sequence (pages freed, exactly one in flight per
    robot) and resubmit the fresh observation."""

    from repro.core.trigger import TriggerConfig

    _, model, params, tok = stack
    out = serve_fleet(
        model, params, tok, n_robots=2, max_steps=300, max_slots=2,
        trigger="rapid", trigger_cfg=TriggerConfig(cooldown_steps=3),
        verbose=False,
    )
    tel = out["telemetry"]
    assert tel.cancels.sum() > 0, "hot trigger must cancel in-flight work"
    assert out["cancelled"] == int(tel.cancels.sum())
    # accounting stays exact through cancel/resubmit churn: whatever is
    # still resident at episode end is exactly the uncancelled in-flight set
    pages_per_req = -(-(14 + 56) // 16)
    in_flight = int(tel.fires.sum() - tel.completions.sum() - tel.cancels.sum())
    assert out["pool"].pages_in_use == in_flight * pages_per_req


def test_serve_fleet_rapid_fewer_decode_rounds_than_always(stack):
    _, model, params, tok = stack
    kw = dict(n_robots=2, max_steps=300, max_slots=2, verbose=False)
    always = serve_fleet(model, params, tok, trigger="always", **kw)
    rapid = serve_fleet(model, params, tok, trigger="rapid", **kw)
    assert rapid["decode_rounds"] < always["decode_rounds"]
    assert rapid["offloads"].sum() < always["offloads"].sum()
    assert always["offload_fraction"] == 1.0


def test_serve_fleet_rejects_unknown_trigger(stack):
    _, model, params, tok = stack
    with pytest.raises(ValueError):
        serve_fleet(model, params, tok, n_robots=1, trigger="sometimes")


def test_fleet_offload_jitter_keyed_per_robot(stack):
    """Offload latency draws are keyed by (robot, ordinal): reproducible
    across runs and independent of cross-robot completion order."""

    import jax as _jax

    from repro.runtime.channel import ChannelConfig, sample_latency_ms

    _, model, params, tok = stack
    kw = dict(n_robots=2, max_steps=60, max_slots=2, seed=3, verbose=False)
    a = serve_fleet(model, params, tok, **kw)
    b = serve_fleet(model, params, tok, **kw)
    assert a["offload_ms_by_robot"] == b["offload_ms_by_robot"]
    assert any(a["offload_ms_by_robot"]), "fleet must have offloaded"
    # the first draw for robot 0 is exactly the (robot, ordinal)-keyed sample
    key = _jax.random.fold_in(_jax.random.fold_in(_jax.random.PRNGKey(3 + 7919), 0), 0)
    want = sample_latency_ms(ChannelConfig(), 8, key)
    assert a["offload_ms_by_robot"][0][0] == pytest.approx(want)


# ---------------------------------------------------------------------------
# adaptive decode blocks
# ---------------------------------------------------------------------------


def test_adaptive_block_monotone_in_queue_depth(stack):
    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=4, adaptive_block=True
    )
    blocks = [sched._block_for_depth(d) for d in range(0, 64)]
    assert blocks[0] == sched.decode_block
    assert all(a <= b for a, b in zip(blocks, blocks[1:])), "must be monotone"
    assert max(blocks) > sched.decode_block, "deep queues must grow the block"
    assert max(blocks) <= sched.max_block


def test_fixed_block_default_unchanged(stack):
    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=4)
    assert not sched.adaptive_block
    assert all(
        sched._block_for_depth(d) == sched.decode_block for d in range(0, 64)
    )


def test_adaptive_scheduler_matches_fixed_tokens(stack):
    """Bigger decode blocks change round pacing, never the greedy chunks."""

    _, model, params, tok = stack
    rng = np.random.default_rng(4)
    reqs = [(r, *_obs(rng)) for r in range(3)]

    def run(adaptive):
        sched = ContinuousBatchingScheduler(
            model, params, tok, max_slots=4, adaptive_block=adaptive
        )
        for r, qd, tau in reqs:
            sched.submit(r, qd, tau)
        return {res.robot_id: res.tokens for res in sched.drain()}

    fixed, adaptive = run(False), run(True)
    assert fixed.keys() == adaptive.keys()
    for r in fixed:
        np.testing.assert_array_equal(fixed[r], adaptive[r])


# ---------------------------------------------------------------------------
# engine cooldown vectorization
# ---------------------------------------------------------------------------


def test_cooldown_mask_matches_reference_loop():
    from repro.runtime.engine import _cooldown_mask

    rng = np.random.default_rng(9)
    for dens, cooldown in ((0.5, 4), (0.9, 1), (0.05, 16), (1.0, 3)):
        trig = rng.random(400) < dens
        want = np.zeros_like(trig)
        c = 0
        for t in range(trig.shape[0]):
            if trig[t] and c == 0:
                want[t] = True
                c = cooldown
            else:
                c = max(c - 1, 0)
        got = np.asarray(_cooldown_mask(jnp.asarray(trig), jnp.int32(cooldown)))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# device-resident decode: multi-round scan windows
# ---------------------------------------------------------------------------


def test_scan_window_bit_identical_cloud(f32_stack):
    """scan_rounds=R must emit the exact per-round-path chunks (pinned via
    the isolated CloudPolicy, which the R=1 path matches bit-for-bit)."""

    _, model, params, tok = f32_stack
    policy = CloudPolicy(model, params, tok)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=4, scan_rounds=4
    )
    rng = np.random.default_rng(71)
    reqs = [(r, *_obs(rng)) for r in range(6)]
    results = {}
    for r, qd, tau in reqs[:3]:
        sched.submit(r, qd, tau)
    nxt = 3
    while len(results) < len(reqs):
        for res in sched.step():
            results[res.robot_id] = res
        if nxt < len(reqs) and sched.round % 2 == 0:
            sched.submit(*reqs[nxt])  # lands mid-window, admitted at boundary
            nxt += 1
    assert sched.windows > 0 and sched.decode_rounds >= 4 * sched.windows - 3
    for r, qd, tau in reqs:
        want = policy(qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got, err_msg=f"robot {r}")
    assert sched.allocator.num_free == sched.allocator.num_pages


@pytest.mark.parametrize("scan_rounds", (1, 3))
def test_scan_window_bit_identical_hetero_fleet(f32_stack, scan_rounds):
    """Acceptance: the scheduler's split lanes and cloud rows, one round
    or several per scan window, are bit-identical (f32) to the isolated
    per-robot paths for a mixed-cut fleet."""

    from repro.partition.executor import PartitionExecutor, PartitionedPolicy

    _, model, params, tok = f32_stack
    ex1 = PartitionExecutor(model, params, cut_layer=1)
    ex2 = ex1.with_cut(2)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=6, scan_rounds=scan_rounds
    )
    sched.attach_partition(ex1)
    sched.attach_partition(ex2)
    rng = np.random.default_rng(72)
    cuts = {0: None, 1: 1, 2: 2, 3: 1, 4: 2, 5: None}
    reqs = [(r, *_obs(rng)) for r in cuts]
    for r, qd, tau in reqs:
        sched.submit(r, qd, tau, partitioned=cuts[r] is not None, cut=cuts[r])
    results = {res.robot_id: res for res in sched.drain()}

    assert sched.hetero_rounds > 0 and sched.mixed_rounds > 0
    policies = {
        None: CloudPolicy(model, params, tok),
        1: PartitionedPolicy(ex1, tok),
        2: PartitionedPolicy(ex2, tok),
    }
    for r, qd, tau in reqs:
        want = policies[cuts[r]](qd, tau)[0]
        got = tok.decode_action(results[r].tokens).reshape(8, 7)
        np.testing.assert_array_equal(want, got, err_msg=f"robot {r} cut {cuts[r]}")
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_cancel_mid_scan_window_defers_page_release(stack):
    """Satellite: a cancel landing between scan boundaries marks the row
    dead; its pages stay allocated until the boundary (the donated in-flight
    buffers still reference them) and the pool drains to in_use == 0."""

    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=2, scan_rounds=4
    )
    rng = np.random.default_rng(73)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng))
    out = sched.step()  # dispatches the 4-round window
    assert out == [] and sched._window is not None
    assert sched.allocator.num_in_use == 2 * sched.pages_per_req
    assert sched.cancel(0)
    # mid-window: the row is dead but its pages are still referenced by the
    # donated in-flight scan — they must NOT be reusable yet
    assert sched.allocator.num_in_use == 2 * sched.pages_per_req
    assert sched.cancelled == 1
    results = sched.drain()
    assert {res.robot_id for res in results} == {1}
    assert sched.pool_stats().pages_in_use == 0
    assert sched.allocator.num_free == sched.allocator.num_pages


def test_cancel_mid_scan_split_lane_drains_clean(f32_stack):
    """Mid-window cancel of a partitioned robot: dead at the boundary, lane
    row arrays released when it was the last member, pool drains clean."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = f32_stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=2, scan_rounds=4
    )
    sched.attach_partition(ex)
    rng = np.random.default_rng(74)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng), partitioned=True)
    sched.step()
    assert sched._window is not None
    assert sched.cancel(1)
    assert sched.allocator.num_in_use == 2 * sched.pages_per_req
    results = sched.drain()
    assert {res.robot_id for res in results} == {0}
    assert sched.pool_stats().pages_in_use == 0
    assert not sched._lanes[1].has_buffers


def test_round_boundary_admission_cancels_queued_not_prefilled(stack):
    """Satellite: with admission every R rounds, a deferred submission that
    is cancelled before its boundary is a pure queue removal — no pages, no
    paid prefill — while in-flight work is untouched."""

    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(
        model, params, tok, max_slots=2, scan_rounds=3
    )
    rng = np.random.default_rng(75)
    sched.submit(0, *_obs(rng))
    sched.step()  # robot 0 admitted, window dispatched
    pages = sched.allocator.num_in_use
    assert pages == sched.pages_per_req
    # staggered arrival mid-window with a deferral (PR 5's defer-hot window)
    sched.submit(1, *_obs(rng), defer_rounds=1)
    assert sched.n_pending == 1 and sched.deferred == 1
    sched.step()  # mid-window: no admission happens between boundaries
    assert sched.allocator.num_in_use == pages, "queued request took pages"
    assert sched.cancel(1), "cancel must hit the queued request"
    assert sched.n_pending == 0
    assert sched.allocator.num_in_use == pages
    results = sched.drain()
    assert {res.robot_id for res in results} == {0}
    assert sched.allocator.num_free == sched.allocator.num_pages


# ---------------------------------------------------------------------------
# observability acceptance: tracing is transparent, spans nest, SLO is pinned
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obs_fleet(stack):
    """One mixed-cut fleet (cuts {1, 2} + cloud-only robots, scan_rounds=4)
    served twice under identical kwargs — obs off, then obs on with
    tracing — shared by the observability acceptance tests."""

    from repro.obs import Observability
    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = stack
    ex = PartitionExecutor(model, params, cut_layer=1)
    kw = dict(n_robots=4, max_steps=60, max_slots=2, partition_executor=ex,
              robot_cuts={1: 1, 2: 2, 3: 1}, scan_rounds=4, verbose=False)
    off = serve_fleet(model, params, tok, **kw)
    obs = Observability(trace=True)
    on = serve_fleet(model, params, tok, obs=obs, **kw)
    return off, on, obs


def test_obs_is_transparent_to_serving(obs_fleet):
    """Instrumentation must not change what gets served: byte-identical
    actions and the same window count with obs on vs off (no syncs added
    inside scan windows, no extra boundaries)."""

    off, on, _ = obs_fleet
    np.testing.assert_array_equal(off["actions"], on["actions"])
    assert off["scan_windows"] == on["scan_windows"] > 0
    assert off["decode_rounds"] == on["decode_rounds"]
    assert off["hetero_rounds"] == on["hetero_rounds"] > 0
    assert off["slo"] is None and on["slo"] is not None


def _lifecycle_spans(trace):
    """Ordered (track, name, ts_us, end_us, args) X-spans from a trace."""

    obj = trace.to_chrome()
    tracks = {ev["tid"]: ev["args"]["name"] for ev in obj["traceEvents"]
              if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    return [
        (tracks[ev["tid"]], ev["name"], ev["ts"], ev["ts"] + ev["dur"],
         ev.get("args", {}))
        for ev in obj["traceEvents"] if ev.get("ph") == "X"
    ]


def test_trace_spans_nest_and_align_to_window_closes(obs_fleet):
    """Every completed request's trace triple nests (queue ⊂ chunk
    lifetime, decode tail-aligned), and each decode span ends exactly at
    a window-close timestamp on the lane that served it (<1us)."""

    from repro.obs import validate_chrome_trace

    _, _, obs = obs_fleet
    n, errors = validate_chrome_trace(obs.trace.to_chrome())
    assert errors == [] and n > 0
    spans = _lifecycle_spans(obs.trace)
    window_close = {}  # lane track -> list of window-end timestamps (us)
    for track, name, _, end, _ in spans:
        if track.startswith("lane "):
            window_close.setdefault(track, []).append(end)
    # all three lane kinds decoded: the shared cloud batch + both cuts
    assert set(window_close) >= {"lane cloud", "lane cut=1", "lane cut=2"}

    triples = [
        spans[i:i + 3] for i, s in enumerate(spans) if s[1] == "chunk"
    ]
    assert triples, "no request lifecycles recorded"
    for chunk, queue, decode in triples:
        track = chunk[0]
        assert queue[1] == "queue" and decode[1] == "decode"
        assert queue[0] == track and decode[0] == track
        # nesting: queue starts the lifetime, decode closes it
        assert queue[2] == chunk[2]                  # both start at submit
        assert chunk[2] <= queue[3] <= chunk[3]      # queue inside lifetime
        assert abs(decode[2] - queue[3]) < 1.0       # decode starts at admit
        assert abs(decode[3] - chunk[3]) < 1.0       # decode ends the chunk
        # the decode end is a window close on the request's own lane
        cut = chunk[4].get("cut")
        lane = "lane cloud" if cut is None else f"lane cut={cut}"
        assert min(abs(decode[3] - w) for w in window_close[lane]) < 1.0, (
            f"{track} decode end not a window boundary on {lane}"
        )


def test_slo_percentiles_pinned_by_trace_timestamps(obs_fleet):
    """The SLO report's p50/p99 chunk latency must sit in the same log2
    bucket as the exact nearest-rank percentile recomputed from the raw
    per-request trace spans — the histogram never drifts off the trace."""

    import math as _math

    from repro.obs.histogram import bucket_index

    _, on, obs = obs_fleet
    durs = sorted(
        (end - ts) / 1e3  # us -> ms
        for _, name, ts, end, _ in _lifecycle_spans(obs.trace)
        if name == "chunk"
    )
    hist = obs.metrics.get("serve.chunk_latency_ms")
    assert hist.count == len(durs) > 0  # one span per completion, no drops
    slo = on["slo"]["chunk_latency_ms"]
    assert slo["count"] == len(durs)
    for q, key in ((0.50, "p50"), (0.99, "p99")):
        exact = durs[max(1, _math.ceil(q * len(durs))) - 1]
        est = hist.quantile(q)
        assert bucket_index(est) == bucket_index(exact), (key, est, exact)
        assert slo[key] == pytest.approx(est, abs=1e-4)  # json is rounded
    # the exact moments agree with the raw spans too
    assert hist.mean == pytest.approx(sum(durs) / len(durs), rel=1e-6)
    assert hist.vmax == pytest.approx(durs[-1], rel=1e-6)
    # registry saw the decision core and the pool through the same handle
    assert on["slo"]["completions"] == len(durs)
    assert on["slo"]["pool_high_water"] > 0
    assert obs.metrics.get("fleet.ticks").value > 0


def test_scheduler_reset_gives_per_episode_high_water(stack):
    """scheduler.reset() (the --assign-cuts episode boundary) reclaims the
    pool and re-arms high_water so episode 2 reports its own KV pressure;
    lifetime alloc/free counters keep counting across the boundary."""

    _, model, params, tok = stack
    sched = ContinuousBatchingScheduler(model, params, tok, max_slots=2)
    rng = np.random.default_rng(21)
    sched.submit(0, *_obs(rng))
    sched.submit(1, *_obs(rng))
    sched.drain()
    alloc = sched.allocator
    hw1, allocs1 = alloc.high_water, alloc.total_allocs
    assert hw1 > 0 and allocs1 > 0 and alloc.total_frees == allocs1
    sched.reset()
    assert alloc.high_water == 0 and alloc.num_in_use == 0
    assert alloc.total_allocs == allocs1  # lifetime counters not reset
    sched.submit(2, *_obs(rng))
    sched.drain()
    assert 0 < alloc.high_water <= hw1  # episode-2's own pressure
    assert alloc.total_allocs > allocs1
