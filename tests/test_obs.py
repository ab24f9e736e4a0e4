"""Observability layer tests: histogram, registry, trace, SLO, pool counters."""

import json
import math

import numpy as np
import pytest

from repro.obs import (
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
    Observability,
    TraceRecorder,
    build_slo_report,
    clock,
    validate_chrome_trace,
)
from repro.obs.histogram import LO_MS, N_BUCKETS, bucket_bounds, bucket_index


# ---------------------------------------------------------------------------
# log2 latency histogram
# ---------------------------------------------------------------------------


def test_bucket_index_matches_bounds():
    """Every value lands in a bucket whose [lo, hi) bounds contain it."""

    for v in (0.0, 1e-6, LO_MS / 2, LO_MS, 0.0015, 0.3, 1.0, 7.7, 168.2,
              1e4, 1e9):
        i = bucket_index(v)
        lo, hi = bucket_bounds(i)
        assert lo <= v < hi or i == N_BUCKETS - 1, (v, i, lo, hi)
    assert bucket_index(-3.0) == 0  # negatives clamp
    # buckets tile: each hi is the next lo
    for i in range(N_BUCKETS - 1):
        assert bucket_bounds(i)[1] == bucket_bounds(i + 1)[0]


def test_histogram_quantile_bucket_contains_true_sample():
    """quantile(q)'s bucket must contain the exact nearest-rank sample —
    the guarantee the SLO acceptance test pins against trace timestamps."""

    rng = np.random.default_rng(11)
    samples = np.concatenate([
        rng.lognormal(3.0, 1.5, 400),   # spread across many buckets
        rng.uniform(100.0, 110.0, 50),  # a dense cluster in one bucket
    ])
    h = LatencyHistogram()
    for v in samples:
        h.observe(float(v))
    srt = np.sort(samples)
    for q in (0.01, 0.5, 0.9, 0.99, 1.0):
        exact = float(srt[max(1, math.ceil(q * len(srt))) - 1])
        est = h.quantile(q)
        assert bucket_index(est) == bucket_index(exact), (q, est, exact)
        assert h.vmin <= est <= h.vmax
    # exact moments ride along
    assert h.count == len(samples)
    assert h.mean == pytest.approx(float(samples.mean()))
    assert h.vmax == float(srt[-1]) and h.vmin == float(srt[0])


def test_histogram_empty_and_single():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    assert h.percentiles() == {"count": 0, "mean": 0.0, "p50": 0.0,
                               "p90": 0.0, "p99": 0.0, "max": 0.0}
    h.observe(42.0)
    # single sample: every quantile collapses to it (clamped to min/max)
    assert h.quantile(0.5) == pytest.approx(42.0, rel=0.5)
    lo, hi = h.bucket_of(42.0)
    assert lo <= h.quantile(0.99) <= hi


def test_histogram_merge_is_lossless_on_buckets():
    rng = np.random.default_rng(7)
    a_vals, b_vals = rng.exponential(50, 300), rng.exponential(5, 200)
    a, b, both = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for v in a_vals:
        a.observe(float(v)), both.observe(float(v))
    for v in b_vals:
        b.observe(float(v)), both.observe(float(v))
    a.merge(b)
    assert a.counts == both.counts
    assert a.count == both.count
    assert a.total == pytest.approx(both.total)
    assert a.vmin == both.vmin and a.vmax == both.vmax


def test_histogram_json_roundtrip():
    h = LatencyHistogram()
    for v in (0.05, 1.2, 1.3, 88.0, 2500.0):
        h.observe(v)
    d = json.loads(json.dumps(h.to_json()))  # through real JSON
    h2 = LatencyHistogram.from_json(d)
    assert h2.counts == h.counts
    assert h2.count == h.count and h2.total == pytest.approx(h.total)
    assert h2.vmin == h.vmin and h2.vmax == h.vmax
    assert h2.quantile(0.5) == h.quantile(0.5)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_kind_mismatch():
    m = MetricsRegistry()
    m.counter("sched.completions").inc(3)
    m.counter("sched.completions").inc()  # same object
    assert m.get("sched.completions").value == 4
    g = m.gauge("pool.pages_in_use")
    g.set(9.0), g.set(4.0)
    assert g.value == 4.0 and g.high == 9.0  # gauge keeps its high-water
    m.histogram("serve.chunk_latency_ms").observe(10.0)
    assert m.get("missing.metric") is None  # peek never creates
    with pytest.raises(TypeError):
        m.gauge("sched.completions")  # already a Counter
    with pytest.raises(TypeError):
        m.counter("serve.chunk_latency_ms")


def test_registry_labels_fold_into_key():
    m = MetricsRegistry()
    m.histogram("lane.edge_ms", cut=1, op="step").observe(1.0)
    m.histogram("lane.edge_ms", cut=2, op="step").observe(2.0)
    assert m.get("lane.edge_ms", op="step", cut=1).count == 1  # order-free
    assert m.get("lane.edge_ms") is None  # unlabeled is a distinct metric
    keys = [k for k, _ in m.items()]
    assert 'lane.edge_ms{cut="1",op="step"}' in keys


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(2)
    b.counter("c").inc(5)
    b.counter("only_b").inc(1)
    a.gauge("g").set(10.0)
    b.gauge("g").set(3.0)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(100.0)
    a.merge(b)
    assert a.get("c").value == 7
    assert a.get("only_b").value == 1
    assert a.get("g").value == 3.0 and a.get("g").high == 10.0
    assert a.get("h").count == 2 and a.get("h").vmax == 100.0


def test_prometheus_export_format():
    m = MetricsRegistry()
    m.counter("sched.completions").inc(12)
    m.gauge("pool.high_water").set(7)
    h = m.histogram("serve.chunk_latency_ms", kind="cloud")
    for v in (1.0, 2.0, 150.0):
        h.observe(v)
    text = m.to_prometheus()
    lines = text.strip().splitlines()
    assert "# TYPE sched_completions counter" in lines  # dots sanitized
    assert "sched_completions 12" in lines
    assert "# TYPE pool_high_water gauge" in lines
    assert "# TYPE serve_chunk_latency_ms histogram" in lines
    # cumulative le-buckets, monotone, closed by +Inf == count
    buckets = [l for l in lines if l.startswith("serve_chunk_latency_ms_bucket")]
    cums = [int(l.rsplit(" ", 1)[1]) for l in buckets]
    assert cums == sorted(cums) and cums[-1] == 3
    assert buckets[-1].startswith('serve_chunk_latency_ms_bucket{kind="cloud",le="+Inf"}')
    assert 'serve_chunk_latency_ms_count{kind="cloud"} 3' in lines
    sum_line = [l for l in lines if l.startswith("serve_chunk_latency_ms_sum")]
    assert float(sum_line[0].rsplit(" ", 1)[1]) == pytest.approx(153.0)


def test_registry_json_is_json_serializable():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.gauge("b").set(1.5)
    m.histogram("c").observe(3.0)
    d = json.loads(json.dumps(m.to_json()))
    assert d["a"] == 1
    assert d["b"] == {"value": 1.5, "high": 1.5}
    assert d["c"]["count"] == 1


# ---------------------------------------------------------------------------
# trace recorder + validator
# ---------------------------------------------------------------------------


def test_trace_chrome_export_validates(tmp_path):
    tr = TraceRecorder()
    t0 = tr.t0
    tr.complete("robot 0", "chunk", t0 + 0.001, t0 + 0.005, {"robot": 0})
    tr.complete("robot 0", "queue", t0 + 0.001, t0 + 0.002)
    tr.complete("lane cloud", "window 1", t0 + 0.002, t0 + 0.005)
    tr.instant("robot 1", "cancelled", t0 + 0.004, {"queued": True})
    assert tr.n_events == 4
    obj = tr.to_chrome()
    n, errors = validate_chrome_trace(obj)
    assert errors == [] and n == 4
    # one thread_name metadata record per track, names preserved
    names = {ev["args"]["name"] for ev in obj["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    assert names == {"robot 0", "robot 1", "lane cloud"}
    # write() emits loadable JSON
    path = tmp_path / "trace.json"
    tr.write(str(path))
    with open(path) as f:
        n2, errors2 = validate_chrome_trace(json.load(f))
    assert n2 == 4 and errors2 == []


def test_trace_validator_rejects_corruption():
    assert validate_chrome_trace({}) == (0, ["traceEvents missing or not a list"])
    _, errs = validate_chrome_trace({"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0}]})
    assert any("no events" in e for e in errs)
    _, errs = validate_chrome_trace({"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": -1.0}]})
    assert any("bad dur" in e for e in errs)
    _, errs = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 9.0, "dur": 1.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 2.0, "dur": 1.0}]})
    assert any("not monotone" in e for e in errs)
    # distinct tracks are independently monotone — no false positive
    _, errs = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 9.0, "dur": 1.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 2, "ts": 2.0, "dur": 1.0}]})
    assert errs == []


def test_clock_is_monotonic_and_shared():
    a = clock()
    b = clock()
    assert b >= a
    assert Observability.clock is clock  # one timebase for every producer


# ---------------------------------------------------------------------------
# page-pool lifetime counters (satellite: per-episode high-water)
# ---------------------------------------------------------------------------


def test_page_allocator_lifetime_counters_and_high_water_reset():
    from repro.runtime.kv_cache import PageAllocator

    alloc = PageAllocator(8)
    p1 = alloc.alloc(3)
    p2 = alloc.alloc(2)
    assert alloc.high_water == 5 and alloc.total_allocs == 5
    alloc.free(p2)
    assert alloc.num_in_use == 3 and alloc.total_frees == 2
    assert alloc.high_water == 5  # high-water survives frees...
    alloc.reset_high_water()
    assert alloc.high_water == 3  # ...until an episode boundary resets it
    alloc.alloc(1)
    assert alloc.high_water == 4  # and re-arms from live occupancy
    # reclaim_all: next episode starts from a clean pool, lifetime
    # alloc/free counters keep counting across episodes
    alloc.reclaim_all()
    assert alloc.num_in_use == 0 and alloc.high_water == 0
    assert alloc.total_allocs == 6 and alloc.total_frees == 6
    assert sorted(alloc.alloc(8)) == list(range(8))  # all pages back
    _ = p1


# ---------------------------------------------------------------------------
# SLO report
# ---------------------------------------------------------------------------


def test_slo_report_build_and_lines():
    m = MetricsRegistry()
    m.counter("sched.completions").inc(10)
    m.counter("sched.submissions").inc(12)
    m.counter("sched.cancels").inc(2)
    m.counter("fleet.fires").inc(8)
    m.counter("fleet.replays").inc(2)
    m.gauge("serve.wall_s").set(5.0)
    m.gauge("pool.high_water").set(9)
    m.gauge("pool.high_water").set(7)  # high-water mark wins
    m.gauge("pool.page_allocs_total").set(30)
    m.gauge("pool.page_frees_total").set(28)
    for v in (100.0, 110.0, 120.0, 130.0):
        m.histogram("serve.chunk_latency_ms").observe(v)
    m.histogram("serve.queue_wait_ms").observe(0.2)

    r = build_slo_report(m)
    assert r.completions == 10 and r.submissions == 12
    assert r.goodput_chunks_s == pytest.approx(2.0)
    assert r.cancel_rate == pytest.approx(2 / 12)
    assert r.replay_fraction == pytest.approx(2 / 10)
    assert r.pool_high_water == 9
    assert r.pool_page_allocs == 30 and r.pool_page_frees == 28
    assert r.chunk_latency_ms["count"] == 4
    assert r.chunk_latency_ms["mean"] == pytest.approx(115.0)

    d = json.loads(json.dumps(r.to_json()))
    assert d["goodput_chunks_s"] == 2.0
    assert d["chunk_latency_ms"]["count"] == 4
    lines = r.lines()
    assert all(l.startswith("SLO ") for l in lines)
    assert any("goodput" in l for l in lines)


def test_slo_report_empty_registry():
    r = build_slo_report(MetricsRegistry())
    assert r.goodput_chunks_s == 0.0 and r.cancel_rate == 0.0
    assert r.chunk_latency_ms["p99"] == 0.0
    assert r.lines()  # renders without dividing by zero


def test_observability_handle():
    obs = Observability()
    assert obs.trace is not None
    obs.metrics.counter("sched.completions").inc(4)
    obs.metrics.gauge("serve.wall_s").set(2.0)
    assert obs.slo_report().goodput_chunks_s == pytest.approx(2.0)
    assert Observability(trace=False).trace is None


# ---------------------------------------------------------------------------
# host spans and live-row counters in the scheduler
# ---------------------------------------------------------------------------

BOUNDARY = ("sched.admit", "sched.dispatch", "sched.sync", "sched.harvest")


@pytest.fixture(scope="module")
def smoke_stack():
    import jax

    from repro.configs import get_smoke_config
    from repro.data.pipeline import EpisodeTokenizer
    from repro.models.model import Model

    cfg = get_smoke_config("openvla-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, EpisodeTokenizer(cfg.vocab_size)


def _scheduler(smoke_stack, **kw):
    from repro.runtime.scheduler import ContinuousBatchingScheduler

    model, params, tok = smoke_stack
    return ContinuousBatchingScheduler(model, params, tok, **kw)


def _submit(sched, robots, seed):
    rng = np.random.default_rng(seed)
    for r in robots:
        sched.submit(r, rng.normal(0, 0.5, (1, 7)).astype(np.float32),
                     rng.normal(0, 0.5, (1, 7)).astype(np.float32))


def _row_token_counts(obs):
    from repro.runtime.scheduler import ROW_STATES

    return {s: obs.metrics.get("sched.row_tokens", state=s).value
            for s in ROW_STATES}


def test_span_off_is_the_shared_null_and_records_nothing(smoke_stack):
    from repro.obs import NULL_SPAN

    sched = _scheduler(smoke_stack, max_slots=2, scan_rounds=2)
    assert sched._span("sched.admit") is NULL_SPAN
    with sched._span("sched.dispatch") as span:
        assert not span
        span.set(rows=2)  # a no-op on the null span
    _submit(sched, range(2), 0)
    assert len(sched.drain()) == 2
    assert sched.obs is None
    obs = Observability()
    sched.obs = obs
    assert sched._span("sched.admit") is not NULL_SPAN
    assert obs.metrics.get("span_ms", span="sched.admit") is None  # not entered


def test_span_on_times_into_registry_and_host_track():
    obs = Observability()
    with obs.span("sched.admit", admitted=2) as span:
        assert span
        span.set(padded=4)
    h = obs.metrics.get("span_ms", span="sched.admit")
    assert h.count == 1 and h.total >= 0.0
    ev = [e for e in obs.trace.to_chrome()["traceEvents"] if e.get("ph") == "X"]
    assert [(e["name"], e["args"]) for e in ev] == [
        ("sched.admit", {"admitted": 2, "padded": 4})]
    assert validate_chrome_trace(obs.trace.to_chrome())[1] == []
    # without a recorder the registry alone is fed
    quiet = Observability(trace=False)
    with quiet.span("sched.sync"):
        pass
    assert quiet.metrics.get("span_ms", span="sched.sync").count == 1


def test_boundary_spans_land_in_the_profiler_host_plane(smoke_stack, tmp_path):
    """Under ``jax.profiler.trace`` the four boundary spans appear in the
    xplane host plane in boundary order, as often as ``span_ms`` counts
    them and as the recorder's ``host`` track lists them."""

    import glob

    import jax
    from jax.profiler import ProfileData

    obs = Observability()
    sched = _scheduler(smoke_stack, max_slots=2, scan_rounds=2, obs=obs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _submit(sched, range(3), 1)
        assert len(sched.drain()) == 3
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    host = sorted(
        (e.start_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("sched.")
    )
    names = [n for _, n in host]
    assert sched.windows > 1
    assert names == list(BOUNDARY) * sched.windows
    for name in BOUNDARY:
        assert obs.metrics.get("span_ms", span=name).count == sched.windows
    track = [e["name"] for e in obs.trace.to_chrome()["traceEvents"]
             if e.get("ph") == "X" and e["name"].startswith("sched.")]
    assert track == names


def test_row_tokens_cover_every_row_of_every_window(smoke_stack):
    obs = Observability(trace=False)
    sched = _scheduler(smoke_stack, max_slots=4, scan_rounds=2, obs=obs)
    _submit(sched, range(3), 2)
    results = sched.drain()
    t = _row_token_counts(obs)
    steps = sched.scan_rounds * sched.decode_block
    assert sum(t.values()) == sched.rows * steps * sched.windows
    # each delivered token was owed by a live chunk exactly once
    assert t["live"] == sum(r.tokens.size for r in results) == 3 * sched.total_tokens
    assert t["idle"] == (sched.rows - 3) * steps * sched.windows
    assert t["cancelled"] == 0


def test_mid_window_cancel_moves_the_row_to_cancelled(smoke_stack):
    obs = Observability(trace=False)
    sched = _scheduler(smoke_stack, max_slots=2, scan_rounds=4, obs=obs)
    _submit(sched, range(2), 3)
    sched.step()  # both admitted, the window dispatched
    assert sched._window is not None
    assert sched.cancel(1)  # marked dead: the window still decodes its row
    results = sched.drain()
    assert [r.robot_id for r in results] == [0]
    t = _row_token_counts(obs)
    steps = sched.scan_rounds * sched.decode_block
    assert t["cancelled"] == steps  # the dead row's one window
    assert t["live"] == sched.total_tokens
    assert sum(t.values()) == sched.rows * steps * sched.windows
