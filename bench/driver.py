"""The open-loop robot fleet against the cloud engine, on the wall clock.

Each fire of the schedule is due at a fixed time.  The driver submits the
fires that are due (a robot whose previous chunk is still outstanding
first cancels it, as RAPID does), calls ``step()`` between due times, and
stamps every chunk at the ``step()`` call that returns it.  A chunk's
latency runs from its fire's due time to that stamp, so a stall in the
driver or the engine counts against every fire it delays.

A fire fails when its chunk never reaches the robot: cancelled by the
robot's next fire, or still outstanding one deadline after the window.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import numpy as np

PENDING, DELIVERED, CANCELLED, EXPIRED = 0, 1, 2, 3


@dataclass
class Outcome:
    """What became of every fire of the schedule."""

    status: np.ndarray                 # [F] int8
    latency_s: np.ndarray              # [F] float64 (nan unless delivered)
    lag_s: np.ndarray                  # [F] float64 submit time - due time
    tokens: Dict[int, np.ndarray] = field(default_factory=dict)  # fire -> tokens
    rounds: Dict[int, tuple] = field(default_factory=dict)       # fire -> (admitted, completed)
    lost_cancels: int = 0              # cancels the engine found nothing for


def _span(tracer, name: str):
    return tracer(name) if tracer is not None else contextlib.nullcontext()


class OpenLoop:
    """One robot fleet's fires served against ``sched`` on the wall clock.

    ``origin`` is the ``time.perf_counter`` instant of schedule time 0.
    Fires due before ``counted_from`` are load only (the warm-up traffic);
    the others make the ``Outcome``.  ``serve`` may be called again to go
    on where the last call stopped.
    """

    def __init__(self, sched, sch, counted_from: float, origin: float):
        self.sched, self.sch = sched, sch
        self.origin = origin
        self.first = int(np.searchsorted(sch.due_s, counted_from, "left"))
        f = len(sch)
        self.out = Outcome(
            status=np.zeros(f, np.int8), latency_s=np.full(f, np.nan),
            lag_s=np.full(f, np.nan),
        )
        self.outstanding: Dict[int, int] = {}    # robot -> fire index
        self.next = 0

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def serve(self, t_end: float, grace_s: float = 0.0,
              on_boundary: Optional[Callable[[float], None]] = None,
              tracer=None) -> None:
        """Submit the fires due before ``t_end`` and step the engine; return
        at ``t_end``, or once every counted fire is resolved when
        ``grace_s`` is given, at the latest ``grace_s`` after ``t_end``."""

        sched, due, out = self.sched, self.sch.due_s, self.out
        n_sub = int(np.searchsorted(due, t_end, "left"))
        while True:
            now = self.now()
            hi = min(int(np.searchsorted(due, now, "right")), n_sub)
            if hi > self.next:
                with _span(tracer, "bench.submit"):
                    self._submit(range(self.next, hi), now)
                self.next = hi
            with _span(tracer, "bench.step"):
                results = sched.step()
            if results:
                t = self.now()
                for r in results:
                    i = self.outstanding.pop(r.robot_id)
                    out.status[i] = DELIVERED
                    out.latency_s[i] = t - due[i]
                    if i >= self.first:
                        out.tokens[i] = r.tokens
                        out.rounds[i] = (r.admitted_round, r.completed_round)
            idle = sched.windows == sched.window_closes
            if idle and on_boundary is not None:
                on_boundary(self.now())
            now = self.now()
            if not grace_s and now >= t_end:
                return
            counted_open = any(i >= self.first for i in self.outstanding.values())
            if self.next >= n_sub and (not counted_open or now >= t_end + grace_s):
                break
            if idle and not sched.n_pending and not sched.n_active:
                wake = due[self.next] if self.next < n_sub else t_end + grace_s
                if wake > now:
                    with _span(tracer, "bench.sleep"):
                        time.sleep(wake - now)
        for i in self.outstanding.values():
            if i >= self.first:
                out.status[i] = EXPIRED
        out.status[:self.first] = PENDING   # load only: not part of the outcome

    def _submit(self, fires, now) -> None:
        """Cancel-then-submit for every fire in ``fires``, in one batch."""

        sched, sch, out, outstanding = self.sched, self.sch, self.out, self.outstanding
        latest: Dict[int, int] = {}
        for i in fires:
            r = int(sch.robot[i])
            if r in latest:                       # fired twice before a boundary
                out.status[latest[r]] = CANCELLED
            latest[r] = i
            out.lag_s[i] = now - sch.due_s[i]
        robots = np.fromiter(latest, np.int64, len(latest))
        stale = [r for r in robots if r in outstanding]
        if stale:
            hits = sched.cancel_batch(np.asarray(stale, np.int64))
            out.lost_cancels += int((~hits).sum())
            for r in stale:
                out.status[outstanding.pop(int(r))] = CANCELLED
        idx = np.fromiter(latest.values(), np.int64, len(latest))
        sched.submit_batch(robots, sch.qd[idx], sch.tau[idx])
        for r, i in latest.items():
            outstanding[r] = i
