"""Host milliseconds per window boundary in the scheduler: the
``sched.admit``, ``sched.dispatch`` and ``sched.harvest`` spans' total
time in the scheduler's ``span_ms`` histograms, over the windows
dispatched (the ``sched.dispatch`` count).  ``sched.sync``, the host
waiting on the device, is left out.  Nothing to read where the program
has no such spans."""

SPANS = ("sched.admit", "sched.dispatch", "sched.harvest")


def read(run):
    hists = [run.obs.get("span_ms", span=s) for s in SPANS]
    if any(h is None for h in hists) or hists[1].count == 0:
        return None
    return sum(h.total for h in hists) / hists[1].count
