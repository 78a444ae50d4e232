"""expert_rows_per_call (experts): routed (token, held expert) rows per
expert call in the window's decode steps, a call being one held expert
with rows in one MoE layer of one step: Delta ``PageStats.moe_rows`` over
Delta ``PageStats.moe_calls`` across the window (the pool's snapshots at
its edges; the scheduler adds each step's counts, read with the step's
tokens). The rows the grouped expert matmul multiplies per read of an
expert's words. A pool without the counters, or a window with no expert
call, reads nothing."""


def read(run):
    if run.pool0 is None or run.pool1 is None:
        return None
    edges = [getattr(p, k, None) for p in (run.pool0, run.pool1)
             for k in ("moe_rows", "moe_calls")]
    if None in edges:
        return None
    rows0, calls0, rows1, calls1 = edges
    if calls1 <= calls0:
        return None
    return (rows1 - rows0) / (calls1 - calls0)
