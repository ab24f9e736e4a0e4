"""The admission metric: device milliseconds of the admission programs over
the scheduler's admitted-request counter."""

from types import SimpleNamespace

import pytest

from repro.obs import MetricsRegistry
from spec import reader

MS = 1_000_000  # ns

# two admission programs and the decode windows around them
ADMIT_TRACE = [("jit_window", 0, 300 * MS), ("jit_admit", 301 * MS, 12 * MS),
               ("jit_window", 314 * MS, 300 * MS), ("jit_admit", 615 * MS, 8 * MS)]


def _run(registry, modules=()):
    return SimpleNamespace(obs=registry, trace={"modules": list(modules)})


def test_admit_ms_per_request_over_admitted_requests():
    m = MetricsRegistry()
    m.counter("sched.admitted_requests").inc(7)
    m.counter("sched.admitted_requests").inc(3)
    m.counter("sched.admissions").inc(50)   # split lanes too: not read
    assert reader("admit_ms_per_request")(_run(m, ADMIT_TRACE)) == pytest.approx(20.0 / 10)


@pytest.mark.parametrize("admitted", [None, 0])
def test_admit_ms_per_request_reads_nothing_without_admitted_requests(admitted):
    m = MetricsRegistry()
    if admitted is not None:
        m.counter("sched.admitted_requests").inc(admitted)
    assert reader("admit_ms_per_request")(_run(m, ADMIT_TRACE)) is None
