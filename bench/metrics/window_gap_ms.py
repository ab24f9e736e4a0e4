"""Mean idle time of the device between one scan window's decode programs
and the next window's: the host's work at each window boundary."""

from tracing import gaps_between, window_groups


def read(run):
    gaps = gaps_between(window_groups(run.trace["modules"], run.programs["decode_window"]))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
