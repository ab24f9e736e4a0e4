"""Share of the decode rows' tokens that a live chunk still owed: the
scheduler's ``sched.row_tokens`` counters, ``live`` over all four states
(``live``, ``past``, ``idle``, ``cancelled``).  Nothing to read where the
program has no such counters."""

STATES = ("live", "past", "idle", "cancelled")


def read(run):
    counts = {s: run.obs.get("sched.row_tokens", state=s) for s in STATES}
    if counts["live"] is None:
        return None
    total = sum(c.value for c in counts.values() if c is not None)
    return 100.0 * counts["live"].value / total if total else None
