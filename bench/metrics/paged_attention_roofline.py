"""paged_attention_roofline (kernels): the least time of the window's
decode attention over the device time of the ``paged_attention`` Pallas
kernel, in percent. Each output token after a request's first is one
active row of one decode step, reading the K and V words of its context
once in every layer (the architecture module's ``paged_attention``);
the least time is the larger of FLOPs over the bf16 peak and bytes over
HBM bandwidth (memory, at a GQA group of 4 or 12 query heads per KV
head)."""

import costs
from devtrace import kernel_time


def read(run):
    t_kernel = kernel_time(run.trace, "paged_attention")
    if not t_kernel:
        return None
    ctx = [r["plen"] + i for r in run.recs
           for i, t in enumerate(r["tokens"]) if i and run.in_window(t)]
    least, _ = costs.roofline_time(*run.arch.paged_attention(run.model, ctx),
                                   costs.peaks(run.device_kind))
    return 100.0 * least / t_kernel
