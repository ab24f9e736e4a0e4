"""Batched row release: every row released at a window boundary has its
capacity zeroed by one ``zero_caps`` program at the next admission,
before any program can read it."""

import jax
import numpy as np
import pytest

import repro.runtime.scheduler as scheduler_mod
from repro.configs import get_smoke_config
from repro.data.pipeline import EpisodeTokenizer
from repro.models.model import Model
from repro.obs import Observability
from repro.runtime.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke_config("openvla-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, EpisodeTokenizer(cfg.vocab_size)


def _sched(stack, **kw):
    model, params, tok = stack
    return ContinuousBatchingScheduler(model, params, tok, **kw)


def _state(robot):
    rng = np.random.default_rng(100 + robot)
    return (rng.normal(0, 0.5, (1, 7)).astype(np.float32),
            rng.normal(0, 0.5, (1, 7)).astype(np.float32))


def _submit(sched, robots):
    for r in robots:
        sched.submit(r, *_state(r))


def _spy_windows(sched):
    """Every row's capacity as each decode window is dispatched."""

    caps = []
    real = sched._decode_for

    def decode_for(n_steps, rounds):
        fn = real(n_steps, rounds)

        def call(params, logits, pcache):
            caps.append(np.asarray(pcache["cap"]))
            return fn(params, logits, pcache)

        return call

    sched._decode_for = decode_for
    return caps


@pytest.fixture
def flushes(monkeypatch):
    """The ``zero_caps`` programs dispatched, by their row masks."""

    calls = []
    real = scheduler_mod._zero_caps

    def zero_caps(cap, mask):
        calls.append(np.array(mask))
        return real(cap, mask)

    monkeypatch.setattr(scheduler_mod, "_zero_caps", zero_caps)
    return calls


def _count(obs, name):
    c = obs.metrics.get(name)
    return 0 if c is None else c.value


def _row_of(sched, robot):
    return next(s.row for s in sched._seqs.values() if s.robot_id == robot)


@pytest.mark.parametrize("k", [2, 3])
def test_rows_finished_at_one_boundary_zero_in_one_flush(stack, flushes, k):
    obs = Observability(trace=False)
    sched = _sched(stack, max_slots=4, scan_rounds=2, obs=obs)
    caps = _spy_windows(sched)
    _submit(sched, range(k))
    rows = list(range(k))
    assert len(sched.drain()) == k  # all k finish at the last boundary
    assert _count(obs, "sched.release_flushes") == 0 and not flushes
    _submit(sched, [k])
    sched.step()  # the next admission flushes, then the window dispatches
    assert _count(obs, "sched.release_flushes") == 1
    assert _count(obs, "sched.released_rows") == k
    assert len(flushes) == 1 and sorted(np.flatnonzero(flushes[0])) == rows
    new_row = _row_of(sched, k)
    assert new_row not in rows
    assert all(caps[-1][r] == 0 for r in rows)
    assert caps[-1][new_row] == sched.cap_tokens


def test_row_released_and_readmitted_at_one_boundary_keeps_its_capacity(
    stack, flushes
):
    obs = Observability(trace=False)
    # one row and one request's pages: robot 1 waits for robot 0's row
    sched = _sched(stack, max_slots=1, obs=obs)
    caps = _spy_windows(sched)
    _submit(sched, [0])
    sched.step()
    _submit(sched, [1])
    first = len(caps)
    out = {r.robot_id: r.tokens for r in sched.drain()}
    assert sorted(out) == [0, 1]
    # the boundary that released row 0 re-admitted it to robot 1
    assert len(flushes) == 1 and list(np.flatnonzero(flushes[0])) == [0]
    assert _count(obs, "sched.released_rows") == 1
    assert all(c[0] == sched.cap_tokens for c in caps[first:])
    fresh = _sched(stack, max_slots=1)
    _submit(fresh, [1])
    (alone,) = fresh.drain()
    np.testing.assert_array_equal(out[1], alone.tokens)


def test_cancel_outside_a_window_zeroes_before_the_next_dispatch(stack, flushes):
    obs = Observability(trace=False)
    sched = _sched(stack, max_slots=2, obs=obs)
    caps = _spy_windows(sched)
    _submit(sched, [0, 1])
    sched.step()  # scan_rounds=1: dispatched and harvested in one call
    assert sched._window is None
    row0, row1 = _row_of(sched, 0), _row_of(sched, 1)
    assert sched.cancel(0)
    assert caps[-1][row0] == sched.cap_tokens  # nothing has run since
    sched.step()
    assert caps[-1][row0] == 0 and caps[-1][row1] == sched.cap_tokens
    assert _count(obs, "sched.release_flushes") == 1 == len(flushes)
    assert _count(obs, "sched.released_rows") == 1


def test_boundary_without_a_release_dispatches_no_flush(stack, flushes):
    obs = Observability(trace=False)
    sched = _sched(stack, max_slots=2, scan_rounds=2, obs=obs)
    _submit(sched, [0, 1])
    assert len(sched.drain()) == 2
    assert sched.windows > 1  # admissions at boundaries that released nothing
    assert not flushes and _count(obs, "sched.release_flushes") == 0
    # a reset drops the pending releases with every other row's state
    sched.reset()
    _submit(sched, [2])
    sched.step()
    assert not flushes and _count(obs, "sched.released_rows") == 0


def test_rows_grown_with_a_release_pending_still_zero_it(stack, flushes):
    sched = _sched(stack, max_slots=1, num_pages=64)
    caps = _spy_windows(sched)
    _submit(sched, [0])
    sched.step()
    assert sched.cancel(0)
    sched._grow_rows()
    _submit(sched, [1, 2])
    sched.step()
    assert caps[-1].shape == (sched.rows,)
    assert caps[-1][0] == sched.cap_tokens  # re-admitted after its flush
    assert len(flushes) == 1 and list(np.flatnonzero(flushes[0])) == [0]
    assert flushes[0].shape == (2,)
