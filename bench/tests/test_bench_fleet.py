"""The traffic: the same fires for every seed, pinned for seed 0."""

import json
from pathlib import Path

import numpy as np

from fleet import build_schedule

BENCH = Path(__file__).resolve().parents[1]

TRAFFIC = json.loads((BENCH / "traffic" / "danube3-cloud-steady.json").read_text())


def test_fire_schedule_pinned_for_seed_0():
    s = build_schedule(TRAFFIC, 200, 100, seed=0)
    # RAPID at its serving defaults over 200 robots and 100 control ticks;
    # a change to the trigger, the episodes or the population moves this
    assert len(s) == 633
    assert s.robot[:5].tolist() == [149, 40, 136, 114, 162]
    assert np.all(np.diff(s.due_s) >= 0)


def test_every_seed_offers_the_same_fires_in_another_order():
    a = build_schedule(TRAFFIC, 200, 100, seed=0)
    b = build_schedule(TRAFFIC, 200, 100, seed=2**31 + 12345)
    assert sorted(a.tick.tolist()) == sorted(b.tick.tolist())
    assert not np.array_equal(a.robot, b.robot)
    # a robot fires at most once per tick, inside its own control period
    period = 1.0 / TRAFFIC["control_hz"]
    assert np.all((a.due_s >= a.tick * period) & (a.due_s < (a.tick + 1) * period))
