"""The robot fleet: who fires when, and with which observation.

The robots are the load generator.  A traffic file fixes the population
(episode and phase of every robot, from its own ``population_seed``), and
RAPID's decision core at its serving defaults (``trigger="rapid"``,
cooldown ``chunk_len - 1``, replay on an empty queue) turns each robot's
kinematic stream into fire ticks.  The population and its schedule are the
same for every ``--seed``: the seed only relabels the robots and draws
each robot's phase inside the control period, so every seed offers the
same set of fires, in another order.

Episode and phase assignment are copied from ``runtime/fleet.py``
(``_dwell_and_pool`` and ``serve_trace``'s episode pool) so that a change
there cannot move the traffic.  The decision core is the program's own
``runtime.policy.rollout``, run on the host's CPU device (on board, in the
deployment modelled here); the total fire count for seed 0 is pinned by a
test, so a change to the trigger cannot silently change the traffic.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Schedule:
    """Every fire of the run, sorted by due time.

    ``due_s`` is seconds after the start of the traffic (warm-up included);
    ``robot``/``tick`` name the robot and its control tick; ``qd``/``tau``
    are the kinematic observation the fire sends.
    """

    due_s: np.ndarray    # [F] float64
    robot: np.ndarray    # [F] int64
    tick: np.ndarray     # [F] int64
    qd: np.ndarray       # [F, n_joints] float32
    tau: np.ndarray      # [F, n_joints] float32

    def __len__(self) -> int:
        return int(self.due_s.shape[0])


def assign_population(n_robots: int, n_episodes: int, population_seed: int):
    """(episode index, phase offset) per robot, as ``runtime/fleet.py``
    draws them for a fleet that joins at tick 0 and never leaves."""

    rng = np.random.default_rng(population_seed)
    episode = rng.integers(0, n_episodes, n_robots).astype(np.int64)
    offset = rng.integers(0, 4096, n_robots).astype(np.int64)
    return episode, offset


def episode_pool(tasks: List[str], n_episodes: int, population_seed: int):
    """Pre-stacked kinematic streams ``(q, qd, tau)``, each [T, E, N]."""

    import jax

    from repro.robotics.episodes import generate_episode

    with jax.default_device(jax.devices("cpu")[0]):
        eps = [
            generate_episode(tasks[e % len(tasks)], seed=population_seed + e)
            for e in range(n_episodes)
        ]
    t_pool = min(ep.q.shape[0] for ep in eps)
    return tuple(
        np.stack([np.asarray(getattr(ep, k)[:t_pool], np.float32) for ep in eps], 1)
        for k in ("q", "qd", "tau")
    )


def fire_ticks(q, qd, tau, chunk_len: int, n_joints: int):
    """[T, R] bool: the ticks at which RAPID asks the cloud for a chunk."""

    import jax
    import jax.numpy as jnp

    from repro.core.kinematics import KinematicFrame
    from repro.core.trigger import TriggerConfig
    from repro.runtime.policy import PolicyConfig, rollout

    pcfg = PolicyConfig(
        trigger=TriggerConfig(n_joints=n_joints, cooldown_steps=max(chunk_len - 1, 1)),
        chunk_len=chunk_len,
        on_empty="reuse",
    )
    with jax.default_device(jax.devices("cpu")[0]):
        frames = KinematicFrame(jnp.asarray(q), jnp.asarray(qd), jnp.asarray(tau))
        _, dec = jax.jit(lambda f: rollout(pcfg, f))(frames)
        return np.asarray(dec.offload)


def build_schedule(traffic: dict, n_robots: int, ticks: int, seed: int) -> Schedule:
    """The fires of ``ticks`` control ticks of ``n_robots`` robots.

    The population and its fire ticks come from the traffic file alone;
    ``seed`` permutes robot ids and draws sub-period phases.
    """

    chunk_len, n_joints = traffic["chunk_len"], traffic["n_joints"]
    n_eps = traffic["episodes"]
    pop_seed = traffic["population_seed"]
    pre = traffic["pre_roll_ticks"]
    period = 1.0 / traffic["control_hz"]

    q_pool, qd_pool, tau_pool = episode_pool(traffic["tasks"], n_eps, pop_seed)
    t_pool = q_pool.shape[0]
    episode, offset = assign_population(n_robots, n_eps, pop_seed)
    t = np.arange(pre + ticks)[:, None]
    idx = (t + offset[None, :]) % t_pool                      # [T, R]
    frames = [p[idx, episode[None, :]] for p in (q_pool, qd_pool, tau_pool)]
    fires = fire_ticks(*frames, chunk_len, n_joints)[pre:]    # [ticks, R]

    rng = np.random.default_rng(seed)
    relabel = rng.permutation(n_robots)          # population index -> robot id
    phase = rng.random(n_robots)                 # by robot id, in periods

    tk, pop = np.nonzero(fires)
    robot = relabel[pop]
    due = (tk + phase[robot]) * period
    order = np.lexsort((robot, due))
    tk, pop, robot, due = tk[order], pop[order], robot[order], due[order]
    return Schedule(
        due_s=due,
        robot=robot.astype(np.int64),
        tick=tk.astype(np.int64),
        qd=frames[1][pre + tk, pop],
        tau=frames[2][pre + tk, pop],
    )
