"""Device milliseconds of the decode programs per scan window: their
device time in the traced window over the windows the scheduler
dispatched there."""

from tracing import durations


def read(run):
    d = durations(run.trace["modules"], run.programs["decode_window"])
    if not d or run.windows <= 0:
        return None
    return sum(d) / run.windows * 1e-6
