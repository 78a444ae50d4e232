"""prefill_chunk_ms (step programs): device time per call of the
chunked-prefill executables (``jit(_prefill_chunk)``, every width),
from the profiler trace of the window."""

from devtrace import module_calls


def read(run):
    calls = module_calls(run.trace, "prefill_chunk")
    return 1e3 * sum(calls) / len(calls) if calls else None
