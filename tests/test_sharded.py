"""Multi-device sharded-decode parity suite (forced host devices).

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — every test
here skips on fewer than 8 devices.  Parity is pinned bit-exact on f32: the
sharded engine's only pool writes are unique-slot ``.at[].set`` and decode
attention is per-row math, so GSPMD placement must not change a single bit
(bf16 would differ at ulp level from batch-split gemm shapes, which is why
the smoke configs are overridden here).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.launch.mesh import make_host_mesh, make_test_mesh
from repro.models.model import Model
from repro.runtime.policy import FleetTelemetry
from repro.runtime.scheduler import ContinuousBatchingScheduler

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

# identical pool/row geometry for sharded and single-device schedulers so
# every jit bucket traces the same shapes: 63 pages = 8 * 8 - 1 (the +1
# trash page makes the pool dim split evenly over 8 data shards)
ENGINE_KW = dict(max_slots=8, num_pages=63, scan_rounds=2)


@pytest.fixture(scope="module")
def f32_stack():
    cfg = get_smoke_config("openvla-7b").replace(
        dtype="float32", param_dtype="float32"
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = EpisodeTokenizer(cfg.vocab_size)
    return cfg, model, params, tok


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh(data=8, devices=jax.devices()[:8])


def _obs(rng, b=1):
    qd = rng.normal(0, 0.5, (b, 7)).astype(np.float32)
    tau = rng.normal(0, 0.5, (b, 7)).astype(np.float32)
    return qd, tau


def _drain_tokens(sched, n_robots=6, seed=0):
    rng = np.random.default_rng(seed)
    for r in range(n_robots):
        sched.submit(r, *_obs(rng))
    return {res.robot_id: res.tokens for res in sched.drain()}


def test_make_host_mesh_shrinks_on_real_devices():
    # 3 does not divide 8: the model axis shrinks to 2 -> (4, 2)
    mesh = make_host_mesh(model=3)
    assert mesh.shape["model"] in (1, 2)
    assert mesh.shape["data"] * mesh.shape["model"] == len(jax.devices())


def test_sharded_cloud_parity_bit_exact(f32_stack, mesh):
    """Acceptance: cloud-only decode over an 8-way data mesh emits byte-for-
    byte the single-device tokens, and the pool drains on every shard."""

    _, model, params, tok = f32_stack
    base = ContinuousBatchingScheduler(model, params, tok, **ENGINE_KW)
    shd = ContinuousBatchingScheduler(model, params, tok, mesh=mesh, **ENGINE_KW)
    want = _drain_tokens(base)
    got = _drain_tokens(shd)
    assert want.keys() == got.keys()
    for r in want:
        np.testing.assert_array_equal(want[r], got[r], err_msg=f"robot {r}")

    st = shd.pool_stats()
    assert st.pages_in_use == 0
    assert st.shard_in_use == (0,) * 8
    # least-loaded steering spread six requests over several shards
    assert sum(1 for h in st.shard_high_water if h > 0) >= 2
    assert sum(st.shard_high_water) == st.high_water


def test_sharded_mixed_cut_parity_bit_exact(f32_stack, mesh):
    """Acceptance: a mixed fleet (cloud rows + split-suffix lanes sharing the
    global page pool) stays bit-identical under the mesh."""

    from repro.partition.executor import PartitionExecutor

    _, model, params, tok = f32_stack

    def run(mesh_):
        ex = PartitionExecutor(model, params, cut_layer=1)
        sched = ContinuousBatchingScheduler(
            model, params, tok, mesh=mesh_, **ENGINE_KW
        )
        sched.attach_partition(ex)
        rng = np.random.default_rng(21)
        reqs = [(r, *_obs(rng)) for r in range(6)]
        for r, qd, tau in reqs:
            sched.submit(r, qd, tau, partitioned=(r % 2 == 1))
        results = {res.robot_id: res for res in sched.drain()}
        assert sched.mixed_rounds > 0, "kinds never decoded together"
        return results, sched

    want, _ = run(None)
    got, shd = run(mesh)
    assert {got[r].kind for r in got} == {"cloud", "split"}
    for r in want:
        np.testing.assert_array_equal(
            want[r].tokens, got[r].tokens, err_msg=f"robot {r}"
        )
    st = shd.pool_stats()
    assert st.pages_in_use == 0
    assert st.shard_in_use == (0,) * 8


def test_paged_decode_attention_sharded_matches(mesh):
    # the sharded wrapper runs the same kernel over each shard's rows (7
    # rows: one empty pad row keeps the 8-way row split even)
    from repro.kernels.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_sharded,
    )

    rng = np.random.default_rng(7)
    b, h, kv, d, page, pool, maxp = 7, 8, 2, 64, 16, 24, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(pool, page, kv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool, page, kv, d)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, pool, (b, maxp)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, maxp * page, (b,)), jnp.int32)

    want = paged_decode_attention(q, kp, vp, pt, lens, interpret=True)
    got = paged_decode_attention_sharded(
        q, kp, vp, pt, lens, mesh=mesh, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_disaggregated_prefill_token_parity(f32_stack):
    """Pipelined prefill on its own device emits the same chunks (one window
    later) and releases every page at drain."""

    _, model, params, tok = f32_stack
    base = ContinuousBatchingScheduler(model, params, tok, **ENGINE_KW)
    dis = ContinuousBatchingScheduler(
        model, params, tok, prefill_group=[jax.devices()[-1]], **ENGINE_KW
    )
    want = _drain_tokens(base, seed=5)
    got = _drain_tokens(dis, seed=5)
    assert want.keys() == got.keys()
    for r in want:
        np.testing.assert_array_equal(want[r], got[r], err_msg=f"robot {r}")
    assert dis.pool_stats().pages_in_use == 0


def test_disaggregated_sharded_combo_parity(f32_stack):
    """Prefill on the tail device + decode sharded over the remaining 7."""

    _, model, params, tok = f32_stack
    mesh7 = make_test_mesh(data=7, devices=jax.devices()[:7])
    base = ContinuousBatchingScheduler(model, params, tok, **ENGINE_KW)
    combo = ContinuousBatchingScheduler(
        model, params, tok, mesh=mesh7,
        prefill_group=[jax.devices()[-1]], **ENGINE_KW
    )
    want = _drain_tokens(base, seed=9)
    got = _drain_tokens(combo, seed=9)
    for r in want:
        np.testing.assert_array_equal(want[r], got[r], err_msg=f"robot {r}")
    st = combo.pool_stats()
    assert st.pages_in_use == 0
    assert st.shard_in_use == (0,) * 7


class _SlowPrefillModel(Model):
    """Prompt prefill carrying ~8 GFLOP of ballast device compute, standing
    in for a long multimodal prompt encode.  The ballast must be *device*
    compute: the CPU backend executes callback-bearing jits synchronously at
    dispatch, so a host sleep can never overlap and would prove nothing."""

    def prefill(self, params, batch, extra=0):
        logits, cache = super().prefill(params, batch, extra=extra)

        def body(_, a):
            return jnp.tanh(a @ a)

        ballast = jax.lax.fori_loop(
            0, 20, body, jnp.eye(512, dtype=logits.dtype) * 0.5
        )
        # f32 x + 0.0 is bitwise x, so token parity between the serving
        # modes is untouched while the data dependence keeps the ballast in
        # every prefill execution
        return logits + (ballast[0, 0] * 0.0).astype(logits.dtype), cache


def _staggered_gaps(sched, n_windows):
    """Submit two fresh robots at every window boundary, so each dispatched
    window decodes the previous admission's rows while a new prompt prefill
    is outstanding.  Per-window host gaps feed the same FleetTelemetry
    boundary accounting ``serve_fleet`` uses (scan_windows / host_gap_ms)."""

    tel = FleetTelemetry(n_robots=64)
    rng = np.random.default_rng(3)
    next_id = 0
    last_sub = -1
    cur = 0.0
    while sched.window_closes < n_windows:
        w = sched.window_closes
        if w != last_sub:
            for _ in range(2):
                sched.submit(next_id, *_obs(rng))
                next_id += 1
            last_sub = w
        t0 = time.perf_counter()
        sched.step()
        cur += (time.perf_counter() - t0) * 1e3
        if sched.window_closes > w:
            tel.note_boundary(cur)
            cur = 0.0
    sched.drain()
    return tel


@pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="prefill/decode overlap needs a second core — on one core the "
    "prefill device's compute timeshares with decode and the host-gap "
    "comparison only measures contention",
)
def test_disaggregation_overlaps_prefill_with_decode(f32_stack):
    """Acceptance: under staggered load with a slow prompt prefill, the
    in-flight decode window's host gap no longer includes admission — the
    prefill runs on its own device while other sequences decode (pinned via
    the scan_windows / host_gap_ms boundary telemetry)."""

    cfg, _, _, tok = f32_stack
    model = _SlowPrefillModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_windows = 12
    # scan_rounds=4 keeps a chunk at 2 windows, so 2 submissions/window hold
    # steady-state residency under the initial 8 rows — no mid-run row
    # growth, hence no recompiles past the warmup windows
    kw = dict(max_slots=8, num_pages=63, scan_rounds=4)

    base = ContinuousBatchingScheduler(model, params, tok, **kw)
    tel_base = _staggered_gaps(base, n_windows)
    dis = ContinuousBatchingScheduler(
        model, params, tok, prefill_group=[jax.devices()[-1]], **kw
    )
    tel_dis = _staggered_gaps(dis, n_windows)

    assert tel_base.scan_windows == tel_dis.scan_windows == n_windows
    # skip the warmup windows (jit compilation lands there in both modes)
    gap_base = float(np.mean(tel_base.boundary_ms[3:]))
    gap_dis = float(np.mean(tel_dis.boundary_ms[3:]))
    assert gap_dis < 0.8 * gap_base, (gap_dis, gap_base)
