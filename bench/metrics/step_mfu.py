"""step_mfu (step programs): model FLOPs of the tokens the prefill and
decode executables processed in the window (the architecture module's
``model_flops``), over their device time times the chip's bf16 peak
(``peaks.json``), in percent.

Decode tokens are the window's output tokens after each request's first
(each one row of a decode step, attending over its position's keys).
Prefill tokens are the prompt tokens of the requests admitted in the
window less the prefix-cache hits counted there, each request's
computed positions taken as the tail of its prompt."""

import costs
from devtrace import module_calls


def read(run):
    times = module_calls(run.trace, "prefill_chunk") + \
        module_calls(run.trace, "step_paged")
    if not times:
        return None
    ctx = []
    for r in run.recs:
        for i, t in enumerate(r["tokens"]):
            if i and run.in_window(t):
                ctx.append(r["plen"] + i)
    admitted = [r for r in run.recs
                if "t_admit" in r and run.in_window(r["t_admit"])]
    plen = sum(r["plen"] for r in admitted)
    hits = run.pool1.prefix_hit_tokens - run.pool0.prefix_hit_tokens
    keep = 1.0 - hits / plen if plen else 0.0
    for r in admitted:
        first = r["plen"] - int(round(keep * r["plen"]))
        ctx += list(range(first + 1, r["plen"] + 1))
    flops = run.arch.model_flops(run.model, ctx)
    peak = costs.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (sum(times) * peak)
