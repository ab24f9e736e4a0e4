"""2-D partitioning tests: (cut layer x placement) planning, expert
gather/scatter execution parity, mixed plain + expert-offload serving lanes,
and the per-leg channel-byte accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.models.model import Model
from repro.partition.executor import PartitionExecutor, PartitionedPolicy
from repro.partition.graph import BYTES_PER_PARAM, build_graph
from repro.partition.planner import (
    NETWORK_PROFILES,
    enumerate_cuts,
    enumerate_cuts_2d,
    plan_partition,
)
from repro.runtime.channel import ChannelConfig, roundtrip_ms, ship_ms
from repro.runtime.latency import arch_hardware_model

MOE_ARCHS = (
    "qwen3-moe-235b-a22b",
    "phi3.5-moe-42b-a6.6b",
    "jamba-1.5-large-398b",
)
ENCODER_ARCHS = ("openvla-7b", "phi-3-vision-4.2b", "seamless-m4t-medium")


def _f32_stack(arch):
    cfg = get_smoke_config(arch).replace(dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _batch_for(cfg, key, b=2, s=16):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.modality != "text" and not cfg.encoder_decoder:
        batch["frontend"] = (
            jax.random.normal(key, (b, cfg.num_modality_tokens, cfg.d_model)) * 0.02
        )
    return batch


def _moe_layers(cfg):
    return [i for i in range(cfg.num_layers) if cfg.is_moe_layer(i)]


# ---------------------------------------------------------------------------
# graph lowering: expert sub-blocks and encoder stage (hand-computed oracles)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_graph_expert_bytes_oracle(arch):
    """MoE nodes expose the separable expert sub-block: all-experts resident
    vs top-k executed, at exactly (3 if gated else 2) * d * d_ff per expert
    — the quantities expert offload moves across the budget."""

    cfg = get_config(arch)
    g = build_graph(cfg)
    per_exp = (3 if cfg.gated_mlp else 2) * cfg.d_model * cfg.d_ff
    moe_nodes = [n for n in g.nodes if n.is_moe]
    assert len(moe_nodes) == len(_moe_layers(cfg))
    for n in moe_nodes:
        assert n.expert_param_bytes == cfg.moe.num_experts * per_exp * BYTES_PER_PARAM
        assert n.expert_exec_bytes == (
            cfg.moe.num_experts_per_tok * per_exp * BYTES_PER_PARAM
        )
        assert n.moe_top_k == cfg.moe.num_experts_per_tok
        # experts are a strict sub-block: attention + router + norms stay
        assert n.expert_param_bytes < n.param_bytes
        assert n.expert_exec_bytes < n.exec_bytes
    for n in g.nodes:
        if not n.is_moe:
            assert n.expert_param_bytes == 0.0
            assert n.expert_exec_bytes == 0.0
            assert n.moe_top_k == 0


@pytest.mark.parametrize("arch", ENCODER_ARCHS)
def test_graph_encoder_stage_bytes_oracle(arch):
    """The placeable encoder stage: vision configs expose the d*d projector,
    enc-dec configs the whole encoder stack; the stage output is the encoded
    token rows that replace the raw observation on the uplink."""

    cfg = get_config(arch)
    g = build_graph(cfg)
    d = cfg.d_model
    if cfg.encoder_decoder:
        want_param = cfg.encoder_param_counts() * BYTES_PER_PARAM
        want_out = g.prompt_len * d * BYTES_PER_PARAM
    else:
        want_param = d * d * BYTES_PER_PARAM
        want_out = cfg.num_modality_tokens * d * BYTES_PER_PARAM
    assert g.encoder_param_bytes == want_param
    assert g.encoder_exec_bytes == want_param
    assert g.encoder_out_bytes == want_out
    # the stage is carved out of (so bounded by) the stem node's totals
    assert g.encoder_param_bytes <= g.nodes[0].param_bytes


def test_graph_text_only_has_no_encoder_stage():
    g = build_graph(get_config("gemma2-9b"))
    assert g.encoder_param_bytes == 0.0
    assert g.encoder_exec_bytes == 0.0
    assert g.encoder_out_bytes == 0.0


# ---------------------------------------------------------------------------
# asymmetric channel legs
# ---------------------------------------------------------------------------


def test_roundtrip_ms_prices_directions_separately():
    ch = ChannelConfig(rtt_ms=10.0, uplink_mbps=20.0, downlink_mbps=50.0)
    up_heavy = roundtrip_ms(ch, 1_000_000, 0)
    down_heavy = roundtrip_ms(ch, 0, 1_000_000)
    assert up_heavy == pytest.approx(10.0 + ship_ms(1_000_000, 20.0))
    assert down_heavy == pytest.approx(10.0 + ship_ms(1_000_000, 50.0))
    assert up_heavy > down_heavy  # the slower uplink costs more
    # equal-bandwidth channels price both directions identically
    sym = ChannelConfig(rtt_ms=10.0, uplink_mbps=40.0, downlink_mbps=40.0)
    assert roundtrip_ms(sym, 7, 0) == pytest.approx(roundtrip_ms(sym, 0, 7))


def test_network_profiles_are_asymmetric():
    for name, ch in NETWORK_PROFILES.items():
        assert ch.uplink_mbps <= ch.downlink_mbps, name
    assert NETWORK_PROFILES["wan"].uplink_mbps < NETWORK_PROFILES["wan"].downlink_mbps
    assert (
        NETWORK_PROFILES["congested"].uplink_mbps
        < NETWORK_PROFILES["congested"].downlink_mbps
    )


# ---------------------------------------------------------------------------
# 2-D planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_2d_space_contains_1d_evals_bit_identical(arch):
    """The 2-D option set starts with the plain 1-D evals, unmodified —
    the construction that makes never-worse a theorem, not a tuning."""

    cfg = get_config(arch)
    g = build_graph(cfg)
    hw = arch_hardware_model(int(g.total_param_bytes))
    for profile, channel in NETWORK_PROFILES.items():
        e1 = enumerate_cuts(g, hw, channel)
        e2 = enumerate_cuts_2d(g, hw, channel)
        assert len(e2) > len(e1), (arch, profile)
        assert e2[: len(e1)] == e1, (arch, profile)
        assert all(not e.placement for e in e2[: len(e1)])
        assert all(e.placement for e in e2[len(e1):])


def test_2d_plan_never_worse_than_1d_all_cells():
    """Acceptance: every architecture x profile, the 2-D plan (and its
    executable restriction) is never worse than the 1-D plan."""

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        g = build_graph(cfg)
        for profile, channel in NETWORK_PROFILES.items():
            p1 = plan_partition(cfg, channel=channel, graph=g)
            p2 = plan_partition(cfg, channel=channel, graph=g, plan_2d=True)
            px = plan_partition(
                cfg, channel=channel, graph=g, plan_2d=True,
                executable_only=True,
            )
            assert p2.plan_2d and px.plan_2d
            assert p2.total_ms <= p1.total_ms + 1e-9, (arch, profile)
            assert px.total_ms <= p1.total_ms + 1e-9, (arch, profile)
            # the executable subspace is itself a subset of the full 2-D one
            assert p2.total_ms <= px.total_ms + 1e-9, (arch, profile)
            assert px.placement in ("", "experts_cloud"), (arch, profile)


def test_2d_moves_moe_arch_off_cloud_only():
    """Acceptance: >= 1 MoE arch leaves cloud_only for a strictly faster
    2-D plan on wan AND congested (phi3.5-moe via the monitor placement)."""

    cfg = get_config("phi3.5-moe-42b-a6.6b")
    g = build_graph(cfg)
    for profile in ("wan", "congested"):
        channel = NETWORK_PROFILES[profile]
        p1 = plan_partition(cfg, channel=channel, graph=g)
        p2 = plan_partition(cfg, channel=channel, graph=g, plan_2d=True)
        assert p1.mode == "cloud_only", profile
        assert p2.mode != "cloud_only", profile
        assert p2.placement, profile
        assert p2.total_ms < p1.total_ms - 1e-9, profile


def test_experts_cloud_unlocks_infeasible_cuts():
    """Expert offload is a memory axis: on jamba (19 GB of experts per MoE
    block vs the 8 GB edge) cuts whose plain prefix busts the edge budget
    become feasible once the experts move cloudward."""

    cfg = get_config("jamba-1.5-large-398b")
    g = build_graph(cfg)
    hw = arch_hardware_model(int(g.total_param_bytes))
    ev = enumerate_cuts_2d(g, hw, NETWORK_PROFILES["wan"])
    base = {e.cut: e for e in ev if not e.placement}
    unlocked = [
        e for e in ev
        if e.placement == "experts_cloud" and e.feasible
        and not base[e.cut].feasible
    ]
    assert unlocked, "expert offload never unlocked a cut"
    for e in unlocked:
        assert e.edge_gb < base[e.cut].edge_gb
        assert e.cloud_gb > base[e.cut].cloud_gb
        assert e.net_expert_ms > 0.0


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_experts_cloud_leg_pricing_oracle(arch):
    """Every experts_cloud eval's gather/scatter milliseconds equal the
    hand-computed per-block legs: prompt's worth at prefill plus one
    top-k-up / mixture-down round trip per decode token, per block."""

    cfg = get_config(arch)
    g = build_graph(cfg)
    hw = arch_hardware_model(int(g.total_param_bytes))
    channel = NETWORK_PROFILES["wan"]
    act = g.d_model * BYTES_PER_PARAM
    evs = [
        e for e in enumerate_cuts_2d(g, hw, channel)
        if e.placement == "experts_cloud"
    ]
    assert evs, arch
    for e in evs:
        want = 0.0
        for layer in e.expert_offload:
            node = g.nodes[layer + 1]
            assert node.is_moe and node.layer == layer
            k = node.moe_top_k
            want += roundtrip_ms(
                channel, g.prompt_len * k * act, g.prompt_len * act
            )
            want += g.chunk_tokens * roundtrip_ms(channel, k * act, act)
        assert e.net_expert_ms == pytest.approx(want), (arch, e.cut)
        # offloaded blocks are the TRAILING edge MoE blocks, ascending
        assert list(e.expert_offload) == sorted(e.expert_offload)


def test_plan_2d_json_roundtrip():
    from repro.partition.planner import PartitionPlan

    for arch in ("phi3.5-moe-42b-a6.6b", "jamba-1.5-large-398b"):
        for profile in ("wan", "lan"):
            plan = plan_partition(
                get_config(arch), channel=NETWORK_PROFILES[profile],
                plan_2d=True,
            )
            again = PartitionPlan.from_json(plan.to_json())
            assert again.plan_2d and again == plan
            assert isinstance(again.expert_offload, tuple)


def test_executable_only_rejects_priced_only_placements():
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    g = build_graph(cfg)
    hw = arch_hardware_model(int(g.total_param_bytes))
    ev = enumerate_cuts_2d(
        g, hw, NETWORK_PROFILES["wan"], executable_only=True
    )
    assert all(e.placement in ("", "experts_cloud") for e in ev)


# ---------------------------------------------------------------------------
# gather/scatter expert execution (acceptance: bit-identical f32 chunks)
# ---------------------------------------------------------------------------


def _offload_cases(cfg):
    """(cut, offload) pairs: all MoE layers under a full-depth edge, and a
    single offloaded block under an interior cut."""

    moe = _moe_layers(cfg)
    cases = [(cfg.num_layers, tuple(moe))]
    interior = [l for l in moe if l < cfg.num_layers - 1]
    if interior:
        cut = interior[0] + 1
        cases.append((cut, (interior[0],)))
    return cases


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_expert_offload_forward_matches_unpartitioned(arch):
    cfg, model, params = _f32_stack(arch)
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    want, _, _ = model.forward(params, batch)
    for cut, off in _offload_cases(cfg):
        ex = PartitionExecutor(model, params, cut, expert_offload=off)
        got = ex.forward(batch)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err <= 1e-5, (arch, cut, off, err)


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_expert_offload_decode_bit_identical(arch):
    """Gather/scatter split serving must emit the EXACT greedy action chunk
    of the fused single-device policy (f32): the seam recomposes the fused
    MoE block op-for-op."""

    from repro.launch.serve import CloudPolicy

    cfg, model, params = _f32_stack(arch)
    tok = EpisodeTokenizer(cfg.vocab_size)
    ref = CloudPolicy(model, params, tok)
    rng = np.random.default_rng(7)
    qd = rng.normal(0, 0.5, (1, 7)).astype(np.float32)
    tau = rng.normal(0, 0.5, (1, 7)).astype(np.float32)
    want = ref(qd, tau)
    for cut, off in _offload_cases(cfg):
        ex = PartitionExecutor(model, params, cut, expert_offload=off)
        policy = PartitionedPolicy(ex, tok)
        np.testing.assert_array_equal(want, policy(qd, tau))
        assert policy.net_ms_log and policy.net_ms_log[0] > 0


def test_expert_offload_validation_and_lane_keys():
    cfg, model, params = _f32_stack("jamba-1.5-large-398b")
    moe = _moe_layers(cfg)
    non_moe = next(i for i in range(cfg.num_layers) if i not in moe)
    with pytest.raises(ValueError):
        # only MoE layers have a separable expert sub-block
        PartitionExecutor(
            model, params, cfg.num_layers, expert_offload=(non_moe,)
        )
    with pytest.raises(ValueError):
        # offloaded experts must sit edge-side of the cut
        PartitionExecutor(model, params, moe[0], expert_offload=(moe[0],))
    plain = PartitionExecutor(model, params, moe[0] + 1)
    assert plain.lane_key == moe[0] + 1
    off = PartitionExecutor(
        model, params, moe[0] + 1, expert_offload=(moe[0],)
    )
    assert off.lane_key == (moe[0] + 1, (moe[0],))
    # with_cut siblings are fresh lanes: the offload does not inherit
    sib = off.with_cut(moe[0] + 1)
    assert sib.lane_key == moe[0] + 1
    assert off.with_cut(moe[0] + 1, expert_offload=(moe[0],)) is off


def test_expert_offload_modeled_net_has_gather_scatter_legs():
    cfg, model, params = _f32_stack("qwen3-moe-235b-a22b")
    plain = PartitionExecutor(model, params, 2)
    off = PartitionExecutor(model, params, 2, expert_offload=(0, 1))
    base = plain.modeled_net_ms(14, 56)
    legs = off.modeled_net_ms(14, 56)
    assert "expert_ms" not in base or base.get("expert_ms", 0.0) == 0.0
    assert legs["expert_ms"] > 0.0
    assert legs["total_ms"] > base["total_ms"]


# ---------------------------------------------------------------------------
# mixed plain + expert-offload serving lanes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_rounds", (1, 4))
def test_fleet_mixed_expert_and_plain_lanes(scan_rounds):
    """A fleet with a plain-cut lane AND a gather/scatter expert lane shares
    decode rounds: bit-identical actions vs the unpartitioned fleet, both
    lanes active, and the page pool fully drained."""

    from repro.launch.serve import serve_fleet

    cfg, model, params = _f32_stack("qwen3-moe-235b-a22b")
    tok = EpisodeTokenizer(cfg.vocab_size)
    # 32 steps = a whole number of 8-round service periods: the final
    # chunks complete inside the horizon, so the pool must read empty
    base = serve_fleet(
        model, params, tok, n_robots=4, max_steps=32,
        scan_rounds=scan_rounds, verbose=False,
    )
    ex = PartitionExecutor(model, params, 1)
    out = serve_fleet(
        model, params, tok, n_robots=4, max_steps=32,
        partition_executor=ex,
        robot_cuts={1: 1, 3: (2, (0,))},
        scan_rounds=scan_rounds, verbose=False,
    )
    np.testing.assert_array_equal(base["actions"], out["actions"])
    assert out["mixed_rounds"] > 0
    assert out["hetero_rounds"] > 0
    assert out["active_cuts"] == [1, (2, (0,))]
    assert out["pool"].pages_in_use == 0


def test_plan_expert_lane_builds_offload_sibling():
    from repro.launch.serve import plan_expert_lane, plan_fleet_partition

    cfg, model, params = _f32_stack("phi3.5-moe-42b-a6.6b")
    base, plan = plan_fleet_partition(
        model, params, "phi3.5-moe-42b-a6.6b", network="lan",
        verbose=False, plan_2d=True,
    )
    assert base is not None and plan.plan_2d
    lane = plan_expert_lane(
        model, params, "phi3.5-moe-42b-a6.6b", network="lan", base=base,
        verbose=False,
    )
    assert lane is not None
    assert isinstance(lane.lane_key, tuple)
    assert lane.expert_offload and all(
        cfg.is_moe_layer(l) and l < lane.cut_layer for l in lane.expert_offload
    )
    # a dense arch has no experts to offload
    cfg_d, model_d, params_d = _f32_stack("gemma2-9b")
    assert plan_expert_lane(
        model_d, params_d, "gemma2-9b", network="lan", verbose=False
    ) is None


# ---------------------------------------------------------------------------
# per-leg channel-byte accounting -> SLO report
# ---------------------------------------------------------------------------


def test_record_chunk_bytes_oracle_and_slo_report():
    from repro.obs import Observability, build_slo_report

    cfg, model, params = _f32_stack("qwen3-moe-235b-a22b")
    obs = Observability()
    ex = PartitionExecutor(model, params, 2, expert_offload=(0, 1))
    ex.obs = obs
    ex.record_chunk_bytes(prompt_len=14, n_decode=56)
    act = cfg.d_model * 2.0
    tokens = 14 + 56
    k = cfg.moe.num_experts_per_tok
    rep = build_slo_report(obs.metrics)
    assert rep.channel_bytes_up == {
        "cut-activation": int(tokens * act),
        "expert-gather": int(2 * tokens * k * act),
    }
    assert rep.channel_bytes_down == {
        "cut-activation": int(56 * 4.0),
        "expert-scatter": int(2 * tokens * act),
    }
    js = rep.to_json()
    assert js["channel_bytes_up"] == rep.channel_bytes_up
    assert js["channel_bytes_down"] == rep.channel_bytes_down
    assert any("channel bytes" in line for line in rep.lines())
    # the counters export under their leg labels
    flat = obs.metrics.to_json()
    assert flat['channel.bytes_up{leg="expert-gather"}'] == int(
        2 * tokens * k * act
    )


def test_fleet_obs_exports_per_leg_bytes():
    from repro.launch.serve import serve_fleet
    from repro.obs import Observability

    cfg, model, params = _f32_stack("qwen3-moe-235b-a22b")
    tok = EpisodeTokenizer(cfg.vocab_size)
    ex = PartitionExecutor(model, params, 1)
    out = serve_fleet(
        model, params, tok, n_robots=4, max_steps=24,
        partition_executor=ex, robot_cuts={1: 1, 3: (2, (0,))},
        obs=Observability(), verbose=False,
    )
    slo = out["slo"]
    assert slo is not None
    assert slo["channel_bytes_up"]["cut-activation"] > 0
    assert slo["channel_bytes_up"]["expert-gather"] > 0
    assert slo["channel_bytes_down"]["expert-scatter"] > 0
    # gather ships top-k rows per token, scatter one mixture row back
    k = cfg.moe.num_experts_per_tok
    assert slo["channel_bytes_up"]["expert-gather"] == (
        k * slo["channel_bytes_down"]["expert-scatter"]
    )
