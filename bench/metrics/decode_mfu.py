"""Share of the chip's bf16 peak that the decode programs spend on useful
work: the model operations of every decode step that served a delivered
chunk (``costs.token_flops`` at its context length) over the decode
programs' device time times the peak.  Idle rows, rows decoding past
their chunk and cancelled requests count as time and not as work."""

from costs import token_flops
from tracing import durations


def read(run):
    d = durations(run.trace["modules"], run.programs["decode_window"])
    steps = run.useful_ctx.tolist()
    if not d or not steps:
        return None
    flops = sum(token_flops(run.cfg, c) for c in steps)
    return 100.0 * flops / (sum(d) * 1e-9 * run.peak["bf16_flops"])
