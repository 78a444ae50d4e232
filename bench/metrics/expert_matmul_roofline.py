"""expert_matmul_roofline (kernels): the least time of the decode steps'
grouped expert matmuls over the device time of the ``expert_matmul``
Pallas kernel inside the decode-step executable runs of the window, in
percent.

The kernel is found by its custom call's name in the reduced trace
(``expert_matmul_kernel_call.<n>``). Its work comes from the pool's
counters across the window (``PageStats.moe_rows`` / ``moe_calls``): each
call reads its expert's three matrices of takum8 words once and its rows'
bf16 activations, writing f32 (the architecture module's
``expert_matmul``), the least time being the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth (memory, at a few rows per
expert). The counters cover the decode steps whose tokens came in the
window (counted from the token stamps); the least time is scaled from
those steps to the decode-step executable runs the trace holds. Reads
nothing without the counters, a trace or the kernel."""

import bisect

import costs
from devtrace import module_calls, op_name

KERNEL = "expert_matmul_kernel_call"


def kernel_time(red, part: str = "step_paged") -> float:
    """Device seconds of the kernel on the first device, inside the runs
    of the executables whose name holds ``part`` (those wholly inside
    the window)."""
    if red is None or not red["devices"]:
        return 0.0
    dev = red["devices"][0]
    runs = sorted((s, s + d) for name, s, d in red["modules"][dev]
                  if part in name)
    starts = [a for a, _ in runs]
    total = 0.0
    for name, s, d in red["ops"][dev]:
        if op_name(name).split(".")[0] != KERNEL:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= runs[i][1]:
            total += d
    return total / 1e9


def read(run):
    t_kernel = kernel_time(run.trace)
    n_runs = len(module_calls(run.trace, "step_paged"))
    if not t_kernel or not n_runs or run.pool0 is None or run.pool1 is None:
        return None
    edges = [getattr(p, k, None) for p in (run.pool0, run.pool1)
             for k in ("moe_rows", "moe_calls")]
    if None in edges:
        return None
    rows = edges[2] - edges[0]
    calls = edges[3] - edges[1]
    steps = len({t for r in run.recs for t in r["tokens"][1:]
                 if run.in_window(t)})
    if calls <= 0 or not steps:
        return None
    flops, nbytes = run.arch.expert_matmul(run.model,
                                           [rows / calls] * calls)
    least, _ = costs.roofline_time(flops, nbytes,
                                   costs.peaks(run.device_kind))
    return 100.0 * least * (n_runs / steps) / t_kernel
