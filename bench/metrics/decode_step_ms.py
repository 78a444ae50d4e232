"""decode_step_ms (step programs): device time per call of the paged
decode-step executable (``jit(_step_paged)``), from the profiler trace
of the window."""

from devtrace import module_calls


def read(run):
    calls = module_calls(run.trace, "step_paged")
    return 1e3 * sum(calls) / len(calls) if calls else None
