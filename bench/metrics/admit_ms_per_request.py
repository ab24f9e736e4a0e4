"""Device milliseconds of admission per admitted request: the admission
programs' device time in the traced window (``jit_admit``, the name the
device trace gives the scheduler's ``_admit_for`` program: batched prompt
prefill and merge into the page pool) over the scheduler's
``sched.admitted_requests`` counter.  Nothing to read where the program
has no such counter, or admitted nothing."""

from tracing import durations

ADMIT_PROGRAM = "jit_admit"


def read(run):
    n = run.obs.get("sched.admitted_requests")
    d = durations(run.trace["modules"], [ADMIT_PROGRAM])
    if n is None or n.value <= 0 or not d:
        return None
    return sum(d) / n.value * 1e-6
