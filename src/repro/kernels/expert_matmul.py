"""Grouped expert matmul Pallas kernel: rows grouped by expert, each
expert's weight words decoded in VMEM on the way into the MXU.

The mixture-of-experts layer (``models/moe.py``) sorts its (token,
held expert) rows by expert and pads each expert's group to whole tiles
of ``bm`` rows, so every row tile belongs to one expert. Two
scalar-prefetched operands describe that layout: ``tile_expert[t]``, the
expert of row tile ``t``, and ``n_used``, the tiles that hold rows
(unused tiles at the end repeat the last used tile's expert).

Grid ``(N/bn, K/bk, tiles)``, tiles innermost, as the weight-stationary
``quant_matmul`` (``takum_matmul.py``): for each ``(j, kk)`` the weight
tile of an expert is decoded once, through the same
``takum_matmul._decode_into`` and ``FormatSpec.decode_tile``, when the
tile's expert differs from the previous tile's; the expert's further
row tiles reuse the decoded tile. The weight block's index is
``(tile_expert[t], kk, j)``: it changes only where the expert does, so
Pallas issues one weight DMA per ``(expert, kk, j)`` with rows, an
expert with no rows has no tile and its words are never read, and the
unused tiles at the end (same block index as the last used one) issue
none. The output block is the whole ``(M, bn)`` stripe of column ``j``,
resident across ``(kk, t)`` and written back once per ``j``; unused
tiles write zeros.

``kernels/ref.expert_matmul_ref`` computes the same tiles in the same
order with ``jax.numpy`` and is bit-identical in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import formats
from repro.kernels.takum_matmul import F32_DOT, _decode_into

__all__ = ["expert_matmul_kernel_call"]


def _expert_tile(te_ref, nu_ref, x_ref, w_ref, o_ref, wdec_ref, *,
                 spec: formats.FormatSpec, bm: int):
    """One ``(j, kk, t)`` step: decode the expert's weight tile when the
    expert changes, accumulate row tile ``t``'s part into the stripe."""
    kk = pl.program_id(1)
    t = pl.program_id(2)
    used = t < nu_ref[0]
    fresh = (t == 0) | (te_ref[t] != te_ref[jnp.maximum(t - 1, 0)])
    rows = pl.ds(pl.multiple_of(t * bm, bm), bm)

    @pl.when(used & fresh)
    def _decode():
        _decode_into(w_ref, wdec_ref, spec)

    @pl.when(used)
    def _accumulate():
        part = jnp.dot(x_ref[...].astype(jnp.float32), wdec_ref[...],
                       precision=F32_DOT,
                       preferred_element_type=jnp.float32)

        @pl.when(kk == 0)
        def _set():
            o_ref[rows, :] = part

        @pl.when(kk != 0)
        def _add():
            o_ref[rows, :] += part

    @pl.when(jnp.logical_not(used) & (kk == 0))
    def _zero():
        o_ref[rows, :] = jnp.zeros((bm, o_ref.shape[1]), jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bm", "bn", "bk", "interpret"))
def expert_matmul_kernel_call(x, w_words, tile_expert, n_used, *,
                              spec: formats.FormatSpec, bm: int, bn: int,
                              bk: int, interpret: bool = False):
    """x [M, K] float rows grouped by expert in ``bm``-row tiles,
    ``w_words [E, K, N]`` wire words, ``tile_expert [M / bm]`` and
    ``n_used [1]`` int32 -> f32 [M, N]; row tile ``t`` is multiplied by
    expert ``tile_expert[t]``'s decoded weights, tiles from ``n_used``
    on are zero. M % bm == K % bk == N % bn == 0 (``ops.expert_matmul``
    pads)."""
    m, k = x.shape
    n_exp, k2, nn = w_words.shape
    assert k == k2 and m % bm == 0, (x.shape, w_words.shape, bm)
    n_tiles = m // bm
    assert tile_expert.shape == (n_tiles,), (tile_expert.shape, n_tiles)

    def x_index(j, kk, t, te, nu):
        return (jnp.minimum(t, jnp.maximum(nu[0] - 1, 0)), kk)

    def w_index(j, kk, t, te, nu):
        return (te[t], kk, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nn // bn, k // bk, n_tiles),
        in_specs=[pl.BlockSpec((bm, bk), x_index),
                  pl.BlockSpec((None, bk, bn), w_index)],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk, t, te, nu: (0, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_expert_tile, spec=spec, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nn), jnp.float32),
        interpret=interpret,
        name="expert_matmul_kernel_call",
        **kwargs,
    )(tile_expert, n_used, x, w_words)
