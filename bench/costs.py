"""Operations and bytes that the served model's work needs, from shapes.

These are the least work and traffic of each computation, not what a
kernel happens to do: a roofline share divides the least time they allow
by the measured time, so padding, re-reads and idle batch rows show up
as lost share. Peaks come from ``peaks.json`` alone, keyed by the
device kind that JAX reports; an unknown kind is an error.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

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


def projection_dims(cfg: dict):
    """``[(d_in, d_out)]`` of one layer's weight matmuls."""
    d, h, hkv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                         cfg["head_dim"], cfg["d_ff"])
    dims = [(d, h * hd), (d, hkv * hd), (d, hkv * hd), (h * hd, d)]
    dims += [(d, ff)] * (2 if cfg["activation"] == "swiglu" else 1)
    dims += [(ff, d)]
    return dims


def qmm(m: int, k: int, n: int):
    """(flops, bytes) of ``x[m, k] @ decode(words[k, n])``."""
    return 2.0 * m * k * n, \
        m * k * ACT_BYTES + k * n * WIRE_BYTES + m * n * OUT_BYTES


def paged_attention(cfg: dict, contexts: Iterable[int]):
    """(flops, bytes) of decode attention over takum8 pages: each entry
    of ``contexts`` is one active row of one step attending over that
    many keys, in every layer (K and V words read once, q and the f32
    output once per row)."""
    h, hkv, hd, L = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                     cfg["n_layers"])
    contexts = list(contexts)
    ctx = float(sum(contexts))
    n_rows = len(contexts)
    flops = 4.0 * h * hd * ctx * L
    kv = 2.0 * hkv * hd * WIRE_BYTES * ctx * L
    qo = 2.0 * n_rows * h * hd * OUT_BYTES * L
    return flops, kv + qo


def model_flops(cfg: dict, contexts: Iterable[int]) -> float:
    """Forward FLOPs of one token per entry of ``contexts`` (the number
    of keys it attends over): weight matmuls, attention and the
    unembedding."""
    per_tok = sum(2.0 * k * n for k, n in projection_dims(cfg)) \
        * cfg["n_layers"] + 2.0 * cfg["d_model"] * cfg["vocab"]
    att = 4.0 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    total = 0.0
    for c in contexts:
        total += per_tok + att * c
    return total


def roofline_time(flops: float, nbytes: float, peak: dict):
    """(least seconds, "compute" | "memory")."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
