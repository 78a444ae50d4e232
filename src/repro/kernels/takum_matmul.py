"""Weight-only-quantised matmul Pallas kernel: takum decode feeding the MXU.

This is the paper's codec in its natural habitat — the input stage of an
arithmetic unit. Weights are stored in HBM as wire words — takum8/takum16
or the posit8/posit16 baseline, anything whose ``FormatSpec`` decodes
straight to float (the LNS formats take the ℓ̄ datapath of
``lns_matmul.py`` instead) — at 2-4x less HBM traffic than f32/bf16;
each (bk, bn) weight tile is decoded to f32 *in VMEM* via
``spec.decode_tile`` and immediately consumed by the MXU matmul.

Weight-stationary schedule
--------------------------
Grid: ``(N/bn, K/bk, M/bm)`` with **M innermost** — the transpose of the
classic M-outer schedule. For each ``(j, kk)`` the weight tile is decoded
**exactly once**, into a VMEM scratch buffer, under
``pl.when(pl.program_id(2) == 0)``; all M steps then reuse the decoded
tile straight from VMEM. The old M-outer grid re-ran the decode ``M/bm``
times per tile, paying the VPU cost (and defeating the codec's fixed
12-bit-window advantage) on every revisit. For takum the decode is the
integer-only reconstruction of ``core/takum.py`` — shifts + one bitcast,
no ldexp/divide — so the VPU work that remains overlaps the MXU under
Mosaic pipelining (``dimension_semantics``: N parallel, K/M arbitrary).

Accumulation: the output block is the full ``(M, bn)`` stripe of the
current ``j`` (``index_map = (0, j)``), so its block index is constant
across every ``(kk, i)`` step of a ``j`` — all revisits are consecutive,
which is exactly the residency Pallas TPU guarantees, and the stripe is
DMA'd to HBM once per ``j`` (no per-step output write amplification;
with per-``(i, j)`` output blocks the M-innermost order would flush a
block on every inner step, ~+50% HBM traffic at serving shapes). Each
step accumulates its ``bm``-row slice in place. The stripe costs
``M * bn * 4`` bytes of VMEM; calls whose stripe would exceed
``acc_budget_bytes`` (default 4 MiB, i.e. M > ~8k rows at bn = 128)
fall back to the classic M-outer/K-innermost schedule, where consecutive
K steps accumulate directly in a ``(bm, bn)`` output block (one decode
per ``(i, j, kk)`` — correct, just not decode-once).

Tile size and strip-wise decode
-------------------------------
A grid step has a fixed cost (DMA issue and wait, semaphores, the scalar
loop): ~0.08 us on a v5e, against ~0.53 us for the decode and dot of a
128x128 takum8 tile at M = 32. So a blockless ``ops.quant_matmul`` call
derives ``(bm, bn, bk)`` from its shape (``ops.qmm_blocks``): one M-step
for up to 256 rows, and the largest tile of 128-multiples dividing K and
N that fits the VMEM budget — a few dozen grid steps per projection
instead of thousands (~12% less time for a 5120x17920 projection). A
large tile is decoded into the scratch strip by strip (``DEFAULT_STRIP``
pieces, a ``fori_loop``), so each trip keeps a few vregs live; Mosaic
unrolls a whole-tile decode, which spills and takes minutes to compile
at 1280x896. Block sizes stay caller-tunable through
``ops.quant_matmul`` (``block=``) and the autotune table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import formats

__all__ = ["qmatmul_kernel_call", "DEFAULT_ACC_BUDGET"]

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128
DEFAULT_ACC_BUDGET = 4 * 1024 * 1024  # VMEM bytes for the (M, bn) stripe
# (rows, cols) of the weight tile decoded per loop trip, and the largest
# tile decoded whole: on a v5e a (64, 256) strip beat (8|32, 128|256|512)
# by 0-4% at 1024x1024 and 1280x896 tiles, and the loop cost a 128x128
# tile 6% against decoding it whole
DEFAULT_STRIP = (64, 256)
WHOLE_TILE_WORDS = 128 * 128
# decoded takum16/posit16 weights are not bf16-exact: keep the MXU at f32
# (multi-pass), matching the f32 oracle ``kernels/ref.qmatmul_ref``
F32_DOT = jax.lax.Precision.HIGHEST


def _decode_into(w_ref, dst_ref, spec: formats.FormatSpec):
    """Decode the (bk, bn) word block into the f32 ``dst_ref`` one
    ``DEFAULT_STRIP`` piece per loop trip, so a large tile keeps a few
    vregs live per intermediate instead of spilling the whole tile's;
    every piece goes through the same ``spec.decode_tile``, so the
    result is bit-identical to a whole-tile decode. A strip width that
    does not divide the tile is halved down to 128 columns (896 and 640
    wide tiles); a tile of at most ``WHOLE_TILE_WORDS``, or that no
    strip divides, decodes whole (Mosaic unrolls a whole-tile decode:
    minutes to compile at 1280x896)."""
    bk, bn = w_ref.shape
    sr, sc = min(DEFAULT_STRIP[0], bk), min(DEFAULT_STRIP[1], bn)
    while bn % sc and sc > 128:
        sc //= 2
    if bk * bn <= WHOLE_TILE_WORDS or bk % sr or bn % sc:
        dst_ref[...] = spec.decode_tile(w_ref[...], dtype=jnp.float32)
        return
    ncol = bn // sc

    def body(s, carry):
        rows = pl.ds(pl.multiple_of((s // ncol) * sr, sr), sr)
        cols = pl.ds(pl.multiple_of((s % ncol) * sc, sc), sc)
        dst_ref[rows, cols] = spec.decode_tile(w_ref[rows, cols],
                                               dtype=jnp.float32)
        return carry

    jax.lax.fori_loop(0, (bk // sr) * ncol, body, 0)


def _qmm_ws_tile(x_ref, w_ref, o_ref, wdec_ref, *,
                 spec: formats.FormatSpec, bm: int):
    """One (j, kk, i) step: decode-once weight tile, stripe accumulate."""
    kk = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _decode():  # once per (j, kk): all M steps reuse wdec_ref
        _decode_into(w_ref, wdec_ref, spec)

    part = jnp.dot(
        x_ref[...].astype(jnp.float32), wdec_ref[...], precision=F32_DOT,
        preferred_element_type=jnp.float32,
    )
    # o_ref is the whole (M, bn) stripe of column j: constant block index
    # across all (kk, i) of a j, so the buffer stays resident and is
    # written back once per j
    rows = pl.ds(pl.multiple_of(i * bm, bm), bm)

    @pl.when(kk == 0)
    def _set():
        o_ref[rows, :] = part

    @pl.when(kk != 0)
    def _acc():
        o_ref[rows, :] += part


def _qmm_tile_moutermost(x_ref, w_ref, o_ref, *,
                         spec: formats.FormatSpec):
    """Classic (i, j, kk) K-innermost schedule: consecutive-visit output
    accumulation, one decode per grid step (big-M fallback)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = spec.decode_tile(w_ref[...], dtype=jnp.float32)
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, precision=F32_DOT,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "bn", "bk", "interpret",
                                    "acc_budget_bytes"))
def qmatmul_kernel_call(x, w_words, spec: formats.FormatSpec, *,
                        bm=DEFAULT_BM, bn=DEFAULT_BN,
                        bk=DEFAULT_BK, interpret: bool = False,
                        acc_budget_bytes: int = DEFAULT_ACC_BUDGET):
    """x [M, K] float  @  spec.decode(w_words [K, N])  -> f32 [M, N].

    M % bm == K % bk == N % bn == 0 (ops.py pads; zero words decode to 0.0,
    so K/N padding is exact).
    """
    m, k = x.shape
    k2, nn = w_words.shape
    assert k == k2
    kwargs = {}
    if m * bn * 4 <= acc_budget_bytes:
        grid = (nn // bn, k // bk, m // bm)  # (j, kk, i): M innermost
        if not interpret:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"))
        return pl.pallas_call(
            functools.partial(_qmm_ws_tile, spec=spec, bm=bm),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
                pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda j, kk, i: (0, j)),
            out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
            interpret=interpret,
            **kwargs,
        )(x, w_words)

    grid = (m // bm, nn // bn, k // bk)  # fallback: (i, j, kk), K innermost
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_qmm_tile_moutermost, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
        interpret=interpret,
    )(x, w_words)
