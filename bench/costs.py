"""Operations and bytes that the served model's work needs, from shapes:
what no architecture changes. The counts that depend on a model's
layers are its architecture module's (``arch/<arch>.py``:
``projection_dims``, ``paged_attention``, ``model_flops``).

These are the least work and traffic of each computation, not what a
kernel happens to do: a roofline share divides the least time they allow
by the measured time, so padding, re-reads and idle batch rows show up
as lost share. Peaks come from ``peaks.json`` alone, keyed by the
device kind that JAX reports; an unknown kind is an error.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
WIRE_BYTES = 1          # takum8 word
ACT_BYTES = 2           # bf16 activations into the matmul
OUT_BYTES = 4           # f32 matmul output


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def qmm(m: int, k: int, n: int):
    """(flops, bytes) of ``x[m, k] @ decode(words[k, n])``."""
    return 2.0 * m * k * n, \
        m * k * ACT_BYTES + k * n * WIRE_BYTES + m * n * OUT_BYTES


def roofline_time(flops: float, nbytes: float, peak: dict):
    """(least seconds, "compute" | "memory")."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
