"""Share of the traced window in which no operation ran on the device."""

from tracing import busy_ns


def read(run):
    window = run.t1 - run.t0
    if window <= 0 or not run.trace["ops"]:
        return None
    return 100.0 * (1.0 - busy_ns(run.trace["ops"]) / max(run.trace["chips"], 1) / window)
