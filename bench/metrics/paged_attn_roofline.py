"""The paged decode attention kernel's share of its roofline: the least
time the chip needs for the kernel's useful operations and bytes (the
delivered chunks' decode steps at their context lengths, on every layer)
over the kernel's device time."""

from costs import paged_attn_cost, roofline_s
from tracing import leaves, matching


def read(run):
    ops = matching(leaves(run.trace["ops"]), run.programs["paged_kernel"])
    if not ops or not run.useful_ctx.size:
        return None
    flops, nbytes = paged_attn_cost(run.cfg, run.useful_ctx.tolist())
    n = run.cfg["num_layers"]
    kernel_s = sum(d for _, _, d in ops) * 1e-9
    return 100.0 * roofline_s(n * flops, n * nbytes, run.peak) / kernel_s
