"""Fused Pallas matmul on the logarithmic-takum ℓ̄ datapath.

Completes the LNS half of the paper's codec story at kernel speed:
weights live in HBM as takum-LNS words (§III representation (10)),
activations are quantised to the same grid on the way in, and each
weight tile is decoded **in VMEM** to the tile-friendly ``(ell, flags)``
int32 lanes of the format's ``lns_parts`` hook (``FormatSpec`` specs
with ``has_lns_parts``; see ``takum.decode_lns_parts``) — after which a
*multiply* is one exact int32 add of un-barred ``ell`` lanes and one XOR
of sign bits. No float multiplier touches the product path, which is the
whole argument of arXiv:2404.18603 for LNS takums in multiply-heavy
inference.

Schedules (mirroring ``takum_matmul.py``)
-----------------------------------------
* **Weight-stationary** (default): grid ``(N/bn, K/bk, M/bm)``, M
  innermost. The weight tile is decoded exactly once per ``(j, kk)``
  under ``pl.when(i == 0)`` into two int32 VMEM scratch tiles; all M
  steps reuse it. The output is the full ``(M, bn)`` stripe of column
  block ``j`` (constant block index across a ``j`` — one HBM write per
  stripe).
* **M-outer fallback**: classic ``(M/bm, N/bn, K/bk)`` K-innermost grid
  when the stripe state would blow the VMEM budget (one decode per grid
  step — correct, just not decode-once).

Accumulators (``accum=`` — selected per call)
---------------------------------------------
* ``"linear"`` (default): each rank-1 product slab is converted
  ``ell -> e^(ell/2)`` in f32 and accumulated linearly — the standard
  LNS-DNN design point, and exactly what ``core.lns.lns_matmul``
  computes (the products themselves carry **no** f32 multiply rounding;
  only the conversion and the adds round).
* ``"gauss"``: accumulation stays in the logarithmic domain. The running
  sum is an ``(S, ell, zero)`` state folded product-by-product with the
  fixed-point Gauss-log addition of ``core.lns.gauss_add_parts``, whose
  φ tables (``core.lns.gauss_tables``) ride along as a ``(2, 1024)``
  int32 input resident in VMEM. State lives in int32 scratch: the
  ``(M, bn)`` stripe on the weight-stationary grid (12 B/element budget
  instead of 4), a ``(bm, bn)`` tile on the fallback grid. The f32
  conversion happens once, at the last K step. This is the bit-faithful
  software stand-in for a hardware Gauss-log LUT unit; it trades the MXU
  for a sequential VPU fold over K, so on today's TPUs it is a numerics
  vehicle, not a throughput path. Caveat: each fold does a dynamic
  vector gather (``jnp.take``) into the VMEM-resident table — verified
  in interpret mode (this repo's CI surface); Mosaic lowering of that
  gather on real TPUs is untested here, so smoke-test ``accum="gauss"``
  with ``interpret=False`` before relying on it on hardware.

Numerics contract (pinned by tests/test_lns_kernel.py): ``"linear"``
matches ``core.lns.lns_matmul`` bit-exactly for accumulation-free calls
(K = 1 — products are exact in ℓ̄) and to f32 summation-order tolerance
otherwise; ``"gauss"`` adds one ``2^-(wf+1)`` re-quantisation per fold
(see ``gauss_add_parts``). NaR words — weight or activation — convert
to NaN (per-slab for ``"linear"``, via a sticky flag for ``"gauss"``),
matching the XLA fallback's decode-to-NaN semantics; the demo-scale
``core.lns.lns_matmul`` reference ignores NaR. Word widths: n <= 27
(int32 ℓ̄ lanes) for ``"linear"``, n <= 23 for ``"gauss"`` (the LUT
interpolation bound of ``gauss_add_parts``) — in practice the wire
formats lns-takum8/16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import formats
from repro.core import lns, takum

__all__ = ["lns_matmul_kernel_call", "DEFAULT_ACC_BUDGET"]

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128
DEFAULT_ACC_BUDGET = 4 * 1024 * 1024  # VMEM bytes for the stripe state


def _prod_slab(xell, xflg, well, wflg, k):
    """(s, ell, zero, nar) of the rank-1 product slab ``x[:, k] ⊗ w[k, :]``.

    The LNS multiply: ell lanes add (exact int32), sign bits XOR,
    zero/NaR flags OR. Broadcasts (bm, 1) against (1, bn). Activation
    lanes arrive pre-decoded (once per call, in the dispatcher below) —
    only weights are decoded inside the grid, where the decode-once
    scratch pays off across the M steps. ``k`` is a static index: Mosaic
    has no dynamic slice along the lane dimension."""
    xe = xell[:, k:k + 1]
    xf = xflg[:, k:k + 1]
    we = well[k:k + 1, :]
    wg = wflg[k:k + 1, :]
    ell = xe + we
    s = (xf & 1) ^ (wg & 1)
    zero = ((xf >> 1) | (wg >> 1)) & 1
    nar = ((xf >> 2) | (wg >> 2)) & 1
    return s, ell, zero, nar


def _lns_to_f32(s, ell, zero, nar, wf: int):
    """sqrt(e)^ell with sign/zero/NaR applied — the one float conversion."""
    mag = jnp.exp(ell.astype(jnp.float32) * jnp.float32(0.5 / (1 << wf)))
    val = jnp.where(zero == 1, 0.0, jnp.where(s == 1, -mag, mag))
    return jnp.where(nar == 1, jnp.float32(jnp.nan), val)


def _linear_fold(xell, xflg, well, wflg, *, wf: int):
    """Sum of all bk product slabs, converted to f32 per slab (linear
    accumulation). Products are exact in ℓ̄; only the conversion rounds.
    NaR operands become NaN at conversion and propagate through the sum
    (matching the XLA fallback's decode-to-NaN semantics)."""
    bm, bk = xell.shape
    acc = jnp.zeros((bm, well.shape[1]), jnp.float32)
    for k in range(bk):  # unrolled: the slab index must be static
        acc = acc + _lns_to_f32(*_prod_slab(xell, xflg, well, wflg, k), wf)
    return acc


def _gauss_fold(xell, xflg, well, wflg, lut, state, *, wf: int):
    """Fold all bk product slabs into the logarithmic-domain state with
    the fixed-point Gauss-log addition (LUT + interpolation). NaR rides
    along as a sticky flag, ORed outside the Gauss add."""
    a_s, a_ell, a_zero, a_nar = state
    for k in range(xell.shape[1]):  # unrolled: the slab index is static
        p_s, p_ell, p_zero, p_nar = _prod_slab(xell, xflg, well, wflg, k)
        a_s, a_ell, a_zero = lns.gauss_add_parts(
            a_s, a_ell, a_zero, p_s, p_ell, p_zero, lut, wf=wf)
        a_nar = a_nar | p_nar
    return a_s, a_ell, a_zero, a_nar


# ---------------------------------------------------------------------------
# Weight-stationary (N, K, M-innermost) decode-once kernels
# ---------------------------------------------------------------------------


def _lns_ws_linear_tile(xell_ref, xflg_ref, w_ref, o_ref, wdec_ell,
                        wdec_flg, *, spec: formats.FormatSpec, bm: int,
                        wf: int):
    kk = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _decode():  # once per (j, kk): all M steps reuse the scratch tiles
        ell, flg = spec.lns_parts(w_ref[...])
        wdec_ell[...] = ell
        wdec_flg[...] = flg

    part = _linear_fold(xell_ref[...], xflg_ref[...],
                        wdec_ell[...], wdec_flg[...], wf=wf)
    rows = pl.ds(pl.multiple_of(i * bm, bm), bm)

    @pl.when(kk == 0)
    def _set():
        o_ref[rows, :] = part

    @pl.when(kk != 0)
    def _acc():
        o_ref[rows, :] += part


def _lns_ws_gauss_tile(xell_ref, xflg_ref, w_ref, lut_ref, o_ref,
                       wdec_ell, wdec_flg, acc_ell, acc_flg, *,
                       spec: formats.FormatSpec, bm: int, wf: int):
    kk = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _decode():
        ell, flg = spec.lns_parts(w_ref[...])
        wdec_ell[...] = ell
        wdec_flg[...] = flg

    rows = pl.ds(pl.multiple_of(i * bm, bm), bm)

    @pl.when(kk == 0)
    def _init():  # empty sum: the zero flag (bit 1) set, ell/sign clear
        acc_ell[rows, :] = jnp.zeros_like(acc_ell[rows, :])
        acc_flg[rows, :] = jnp.full_like(acc_flg[rows, :], 2)

    flg = acc_flg[rows, :]
    state = (flg & 1, acc_ell[rows, :], (flg >> 1) & 1, (flg >> 2) & 1)
    s, ell, zero, nar = _gauss_fold(xell_ref[...], xflg_ref[...],
                                    wdec_ell[...], wdec_flg[...],
                                    lut_ref[...], state, wf=wf)
    acc_ell[rows, :] = ell
    acc_flg[rows, :] = s | (zero << 1) | (nar << 2)

    @pl.when(kk == pl.num_programs(1) - 1)
    def _final():  # leave the log domain exactly once per output element
        o_ref[rows, :] = _lns_to_f32(s, ell, zero, nar, wf)


# ---------------------------------------------------------------------------
# Classic M-outer / K-innermost fallback kernels (big-M stripes)
# ---------------------------------------------------------------------------


def _lns_mo_linear_tile(xell_ref, xflg_ref, w_ref, o_ref, *,
                        spec: formats.FormatSpec, wf: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    well, wflg = spec.lns_parts(w_ref[...])
    o_ref[...] += _linear_fold(xell_ref[...], xflg_ref[...], well, wflg,
                               wf=wf)


def _lns_mo_gauss_tile(xell_ref, xflg_ref, w_ref, lut_ref, o_ref,
                       acc_ell, acc_flg, *, spec: formats.FormatSpec,
                       wf: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ell[...] = jnp.zeros_like(acc_ell[...])
        acc_flg[...] = jnp.full_like(acc_flg[...], 2)

    well, wflg = spec.lns_parts(w_ref[...])
    flg = acc_flg[...]
    state = (flg & 1, acc_ell[...], (flg >> 1) & 1, (flg >> 2) & 1)
    s, ell, zero, nar = _gauss_fold(xell_ref[...], xflg_ref[...], well,
                                    wflg, lut_ref[...], state, wf=wf)
    acc_ell[...] = ell
    acc_flg[...] = s | (zero << 1) | (nar << 2)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _final():
        o_ref[...] = _lns_to_f32(s, ell, zero, nar, wf)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("spec", "accum", "bm", "bn", "bk",
                                    "interpret", "acc_budget_bytes"))
def lns_matmul_kernel_call(x_words, w_words, spec: formats.FormatSpec, *,
                           accum: str = "linear",
                           bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                           interpret: bool = False,
                           acc_budget_bytes: int = DEFAULT_ACC_BUDGET):
    """decode(x_words [M, K]) ⊗ decode(w_words [K, N]) -> f32 [M, N].

    Both operands are takum-LNS words (M % bm == K % bk == N % bn == 0;
    ops.py pads — zero words decode to is_zero and contribute nothing, so
    padding is exact in both accumulation modes). Activations are decoded
    to their ``(ell, flags)`` int32 lanes **once per call**, outside the
    grid (the grid revisits each x tile N/bn times — re-decoding there
    would pay the VPU cost on every revisit for the operand that has no
    decode-once scratch); weights decode in-kernel, once per ``(j, kk)``.
    ``accum`` selects the linear-domain or Gauss-log accumulator; the
    weight-stationary grid is used while the stripe state fits
    ``acc_budget_bytes`` (4 B/element linear, 12 B/element gauss), else
    the M-outer fallback.
    """
    if accum not in ("linear", "gauss"):
        raise ValueError(f"unknown accum {accum!r}")
    m, k = x_words.shape
    k2, nn = w_words.shape
    assert k == k2
    wf = takum.frac_width(spec.n)
    xell, xflg = spec.lns_parts(x_words)
    lut = lns.gauss_tables(wf) if accum == "gauss" else None
    lut_spec = None if lut is None else pl.BlockSpec(
        lut.shape, lambda *_: (0,) * lut.ndim)
    bytes_per = 12 if accum == "gauss" else 4
    ws = m * bn * bytes_per <= acc_budget_bytes
    kwargs = {}
    if not interpret:
        # WS grid: only j (N) is parallel — kk/i share the stripe state.
        # M-outer grid: each (i, j) owns a disjoint output/state block,
        # so both are parallel (as in takum_matmul's fallback).
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
            if ws else ("parallel", "parallel", "arbitrary"))

    if ws:
        grid = (nn // bn, k // bk, m // bm)  # (j, kk, i): M innermost
        x_spec = pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk))
        w_spec = pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j))
        o_spec = pl.BlockSpec((m, bn), lambda j, kk, i: (0, j))
        wdec = [pltpu.VMEM((bk, bn), jnp.int32),
                pltpu.VMEM((bk, bn), jnp.int32)]
        if accum == "linear":
            return pl.pallas_call(
                functools.partial(_lns_ws_linear_tile, spec=spec, bm=bm, wf=wf),
                grid=grid,
                in_specs=[x_spec, x_spec, w_spec],
                out_specs=o_spec,
                out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
                scratch_shapes=wdec,
                interpret=interpret,
                **kwargs,
            )(xell, xflg, w_words)
        return pl.pallas_call(
            functools.partial(_lns_ws_gauss_tile, spec=spec, bm=bm, wf=wf),
            grid=grid,
            in_specs=[x_spec, x_spec, w_spec, lut_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
            scratch_shapes=wdec + [pltpu.VMEM((m, bn), jnp.int32),
                                   pltpu.VMEM((m, bn), jnp.int32)],
            interpret=interpret,
            **kwargs,
        )(xell, xflg, w_words, lut)

    grid = (m // bm, nn // bn, k // bk)  # fallback: (i, j, kk), K innermost
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    if accum == "linear":
        return pl.pallas_call(
            functools.partial(_lns_mo_linear_tile, spec=spec, wf=wf),
            grid=grid,
            in_specs=[x_spec, x_spec, w_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
            interpret=interpret,
            **kwargs,
        )(xell, xflg, w_words)
    return pl.pallas_call(
        functools.partial(_lns_mo_gauss_tile, spec=spec, wf=wf),
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec, lut_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(xell, xflg, w_words, lut)
