"""Median queue wait: submission to admission, from the scheduler's own
``serve.queue_wait_ms`` histogram over the traced window."""


def read(run):
    h = run.obs.get("serve.queue_wait_ms")
    if h is None or h.count == 0:
        return None
    return h.quantile(0.5)
