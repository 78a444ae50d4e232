"""tbt_p95_ms: 95th percentile of every gap between consecutive output
tokens of a request, over all requests, for gaps that end in the
window."""

from harness import percentile


def read(run):
    gaps = run.window_gaps_ms()
    return percentile(gaps, 95) if gaps else None
