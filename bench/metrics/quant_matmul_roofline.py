"""quant_matmul_roofline (kernels): the least time of the window's
weight matmuls over the device time of the ``quant_matmul`` Pallas
kernel, in percent. The least time of each call is the larger of its
FLOPs over the bf16 peak and its bytes (bf16 activations in, takum8
weight words, f32 out) over HBM bandwidth; the calls are those of the
decode steps (M = decode batch) and prefill chunks (M = page size) that
ran in the window, one per weight matmul per layer (the architecture
module's ``projection_dims``). At phi3-medium's shapes the decode
step's calls (M = 32) are bound by memory and the prefill chunk's
(M = 256) by the bf16 peak."""

import costs
from devtrace import kernel_time, module_calls


def read(run):
    t_kernel = kernel_time(run.trace, "quant_matmul")
    if not t_kernel:
        return None
    peak = costs.peaks(run.device_kind)
    least = 0.0
    for exe, rows in (("step_paged", run.engine["decode_batch"]),
                      ("prefill_chunk", run.engine["page_size"])):
        n = len(module_calls(run.trace, exe))
        for layers, dims in run.arch.projection_dims(run.model):
            per_call = sum(
                costs.roofline_time(*costs.qmm(rows, k, n_), peak)[0]
                for k, n_ in dims)
            least += n * per_call * layers
    return 100.0 * least / t_kernel
