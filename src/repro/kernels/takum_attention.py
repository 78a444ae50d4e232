"""Fused takum-decode flash attention over a wire-format KV cache.

The serving counterpart of ``takum_matmul.py``: the KV cache — the other
big HBM-resident tensor class besides the weights — lives in HBM as raw
takum words (``cfg.kv_quant``), and this kernel DMAs those words
directly into VMEM and decodes them **tile by tile inside the
online-softmax (flash) loop**. Full-precision K/V are never materialised
in HBM: a decode step reads ``n/32`` of the f32 cache bytes, which is
the paper's codec-at-the-datapath-input design applied to attention (the
decoder feeding the MXU's ``q @ k^T`` instead of a weight matmul).

Schedule
--------
Queries are pre-arranged to ``[B, Hkv, rows, hd]`` with
``rows = G * tq`` (GQA head group x query positions, row ``r`` holding
group ``r // tq``, query position ``pos + r % tq``) so that every query
row of a KV head shares the same K/V tiles. Grid: ``(B, Hkv, Tpad/bk)``
with the KV-block dimension innermost:

* **K tile decode** — ``(bk, hd)`` words -> f32 in VMEM through the
  cache format's ``FormatSpec.decode_tile`` (integer-only IEEE
  reconstruction for linear takum; decode + one exp for LNS takum; the
  2C posit decode for the posit baseline; a plain cast for the
  identity codec, which makes the uncompressed cache ride the same
  kernel);
* ``q @ k^T`` on the MXU, f32 accumulate, then causal / ``start`` /
  sliding-``window`` masking at ``_MASKED`` (finite, matching the jnp
  oracle — all-masked rows stay finite instead of NaN);
* running max/sum rescale (the online-softmax state ``m``/``l`` lives
  in lane-replicated ``(rows, 128)`` VMEM scratch, the weighted-V
  accumulator in ``(rows, hd)``);
* **V tile decode** and ``p @ v`` accumulate;
* at the last KV block, one normalisation and a single ``(rows, hd)``
  output write per ``(b, h)``.

``pos`` and the per-sequence ``start`` vector ride in as scalar-prefetch
operands (``PrefetchScalarGridSpec``): KV blocks entirely past the
causal band (``kk * bk > pos + tq - 1``) or entirely before the sliding
window are skipped with ``pl.when`` — and their *DMAs* are elided too,
because the KV index map clamps the block index to the last in-band
block, so Pallas sees a repeated block index and issues no new fetch.
A decode step therefore reads ~``pos`` wire words, not ``Tmax``.

VMEM per (b, h) step: ``bk * hd`` words x2 (K/V tiles, n/8 bytes each),
``rows * hd`` f32 x2 (q + accumulator), ``rows * 128`` f32 x2 (m/l),
plus the decoded tile in registers — comfortably inside the budget at
the default ``bk = 256``, ``hd = 128``, ``rows <= 1024``.

NaR words decode to NaN; an unmasked NaR poisons exactly the query rows
that attend to it (max/exp propagate NaN through ``m``/``p``), matching
the decode-then-attend oracle's containment semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import formats

__all__ = ["attention_kernel_call", "paged_attention_kernel_call",
           "DEFAULT_BK", "MASKED"]

DEFAULT_BK = 256     # KV-sequence tile (keys per decode-and-accumulate step)
MASKED = -1e30       # finite mask value (matches the jnp serving oracle)
# f32 operands stay f32 on the MXU (multi-pass), as in the f32 oracle:
# softmax weights are not bf16-exact
F32_DOT = jax.lax.Precision.HIGHEST


def kv_words_to_f32(words, spec: formats.FormatSpec):
    """Decode one KV tile to f32: the codec as the attention input stage.

    One call into the registered format's ``decode_tile`` hook — the
    integer-only IEEE reconstruction for linear takum, decode + the
    single ``sqrt(e)^ell`` exp for LNS takum (the only transcendental on
    the path, the same dataflow as the LNS matmul kernel), the 2C posit
    decode for the baseline, a cast for the identity codec (the cache
    already holds floats)."""
    return spec.decode_tile(words, dtype=jnp.float32)


def _attn_tile(pos_ref, start_ref, q_ref, kw_ref, vw_ref, o_ref,
               m_ref, l_ref, acc_ref, *, spec: formats.FormatSpec,
               bk: int, tq: int, window: int, scale: float):
    """One (b, h, kk) step of the online-softmax loop."""
    b = pl.program_id(0)
    kk = pl.program_id(2)
    pos = pos_ref[0]
    qmax = pos + tq - 1          # newest query position (causal band top)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    in_band = kk * bk <= qmax
    if window:
        # block entirely below every row's window iff its last key
        # position <= oldest query position - window
        in_band = in_band & ((kk + 1) * bk - 1 > pos - window)

    @pl.when(in_band)
    def _slab():
        q = q_ref[0, 0].astype(jnp.float32)              # (rows, hd)
        k = kv_words_to_f32(kw_ref[0, 0], spec)      # (bk, hd) f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=F32_DOT,
            preferred_element_type=jnp.float32) * scale  # (rows, bk)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qpos = pos + rows % tq
        kpos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = kpos <= qpos
        if window:
            msk = msk & (kpos > qpos - window)
        msk = msk & (kpos >= start_ref[b])
        s = jnp.where(msk, s, MASKED)

        m_prev = m_ref[...]                              # (rows, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])                    # (rows, bk)
        corr = jnp.exp(m_prev - m_new)                   # (rows, 128)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = kv_words_to_f32(vw_ref[0, 0], spec)      # (bk, hd) f32
        pv = jnp.dot(p, v, precision=F32_DOT,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finalise():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


def _q_index(b, h, kk, pos_ref, start_ref):
    return (b, h, 0, 0)


def _kv_index(b, h, kk, pos_ref, start_ref, *, bk: int, tq: int,
              window: int):
    # clamp to the in-band block range: out-of-band steps repeat a
    # boundary block index, so Pallas elides their DMAs — a decode step
    # reads ~pos wire words (or ~window with a sliding window), not Tpad
    last = (pos_ref[0] + tq - 1) // bk
    idx = jnp.minimum(kk, last)
    if window:
        # first block whose last key (kk+1)*bk - 1 exceeds the oldest
        # query's window floor pos - window (strict, matching the mask)
        first = jnp.maximum((pos_ref[0] - window + 1) // bk, 0)
        idx = jnp.maximum(idx, jnp.minimum(first, last))
    return (b, h, idx, 0)


@functools.partial(jax.jit,
                   static_argnames=("spec", "bk", "tq", "window",
                                    "interpret"))
def attention_kernel_call(q4, kw, vw, pos, start, *,
                          spec: formats.FormatSpec,
                          bk: int = DEFAULT_BK, tq: int, window: int = 0,
                          interpret: bool = False):
    """q4 [B, Hkv, rows, hd] float, kw/vw [B, Hkv, Tpad, hd] wire words
    (or floats for the identity codec) -> [B, Hkv, rows, hd] f32. The
    cache is head-major so that a KV block's last two dimensions are
    ``(bk, hd)``, the layout Mosaic's tiling requires.

    ``rows = G * tq`` with row ``r`` = (group ``r // tq``, query position
    ``pos + r % tq``); padding rows alias valid positions and are
    stripped by the caller. ``Tpad % bk == 0`` (ops.py pads with zero
    words — beyond-``pos`` positions are causally masked, so padding is
    exact). ``pos`` is a ``(1,)`` int32 array, ``start`` a ``(B,)`` int32
    array (zeros when no left-padding).
    """
    b, hkv, rows, hd = q4.shape
    tpad = kw.shape[2]
    assert tpad % bk == 0, (tpad, bk)
    assert kw.shape == vw.shape == (b, hkv, tpad, hd)
    nkb = tpad // bk
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           functools.partial(_kv_index, bk=bk, tq=tq,
                                             window=window))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), _q_index),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd), _q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),   # running max m
            pltpu.VMEM((rows, 128), jnp.float32),   # running sum l
            pltpu.VMEM((rows, hd), jnp.float32),    # weighted-V accum
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_attn_tile, spec=spec, bk=bk, tq=tq,
                          window=window, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(pos, start, q4, kw, vw)


# ---------------------------------------------------------------------------
# Paged variant: the KV cache is a pool of pages, gathered via block tables
# ---------------------------------------------------------------------------
#
# The serving scheduler (repro.serve) stores the wire-format cache as one
# [num_pages, Hkv, page_size, hd] pool per layer; each sequence owns a row
# of a [B, NP] *block table* mapping its kk-th KV block to a pool page.
# The table rides in as a third scalar-prefetch operand and the KV index
# map resolves (seq, kk) -> page id, so the grid gathers pages instead of
# slicing a contiguous cache. Because continuous batching packs sequences
# of different lengths into one decode batch, ``pos`` (and ``start``) are
# per-sequence [B] vectors here, not the contiguous kernel's shared
# scalar. Decode steps only (tq = 1): every query row of a KV head is one
# GQA group member at position ``pos[b]``.
#
# The clamped-index DMA elision carries over: out-of-band steps repeat
# the last in-band *page id* (same block index => no new fetch), so a
# step still reads ~``pos[b]`` wire words per sequence. The block-table
# read itself is clamped to the table width, which makes stale ``pos``
# drift on inactive scheduler slots harmless (they attend over the
# reserved scratch page their table points at).


def _paged_attn_tile(pos_ref, start_ref, table_ref, q_ref, kw_ref, vw_ref,
                     o_ref, m_ref, l_ref, acc_ref, *,
                     spec: formats.FormatSpec, ps: int, window: int,
                     scale: float):
    """One (b, h, kk) step over sequence b's kk-th KV page."""
    b = pl.program_id(0)
    kk = pl.program_id(2)
    pos = pos_ref[b]             # this sequence's newest (query) position

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    in_band = kk * ps <= pos
    if window:
        in_band = in_band & ((kk + 1) * ps - 1 > pos - window)

    @pl.when(in_band)
    def _slab():
        q = q_ref[0, 0].astype(jnp.float32)              # (rows, hd)
        k = kv_words_to_f32(kw_ref[0, 0], spec)      # (ps, hd) f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=F32_DOT,
            preferred_element_type=jnp.float32) * scale  # (rows, ps)
        kpos = kk * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = kpos <= pos
        if window:
            msk = msk & (kpos > pos - window)
        msk = msk & (kpos >= start_ref[b])
        s = jnp.where(msk, s, MASKED)

        m_prev = m_ref[...]                              # (rows, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])                    # (rows, ps)
        corr = jnp.exp(m_prev - m_new)                   # (rows, 128)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = kv_words_to_f32(vw_ref[0, 0], spec)      # (ps, hd) f32
        pv = jnp.dot(p, v, precision=F32_DOT,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finalise():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


def _paged_q_index(b, h, kk, pos_ref, start_ref, table_ref):
    return (b, h, 0, 0)


def _paged_kv_index(b, h, kk, pos_ref, start_ref, table_ref, *, ps: int,
                    npg: int, window: int):
    # clamp kk into sequence b's in-band block range, then translate to a
    # pool page through its block table: repeated page ids on out-of-band
    # steps elide the DMA exactly as in the contiguous kernel. ``last``
    # is additionally clamped to the table width so a stale ``pos`` on an
    # idle scheduler slot can never index past the table.
    last = jnp.minimum(pos_ref[b] // ps, npg - 1)
    idx = jnp.minimum(kk, last)
    if window:
        first = jnp.maximum((pos_ref[b] - window + 1) // ps, 0)
        idx = jnp.maximum(idx, jnp.minimum(first, last))
    return (table_ref[b, idx], h, 0, 0)


@functools.partial(jax.jit,
                   static_argnames=("spec", "ps", "window", "interpret"))
def paged_attention_kernel_call(q4, kw, vw, pos, start, table, *,
                                spec: formats.FormatSpec, ps: int,
                                window: int = 0, interpret: bool = False):
    """q4 [B, Hkv, rows, hd] float, kw/vw [P, Hkv, ps, hd] pooled wire
    words (or floats for the identity codec), table [B, NP] int32 page
    ids -> [B, Hkv, rows, hd] f32. Pages are head-major, so a KV block's
    last two dimensions are ``(ps, hd)``.

    Decode-step shape: ``rows`` is the (padded) GQA group width — every
    row of (b, h) is the same query position ``pos[b]``; padding rows
    alias row 0 and are stripped by the caller. ``pos`` and ``start``
    are per-sequence ``(B,)`` int32 vectors (continuous batching packs
    unequal-length sequences into one batch). Pages past a sequence's
    ``pos`` hold stale words from previous page owners — the causal
    mask (not zero-padding) is what excludes them.

    Tensor-parallel serving (serve/shard.py) slices the KV head dim:
    each shard calls this kernel with its *local* ``Hkv/tp`` heads and
    its ``1/tp`` slice of the pool, and the grid below iterates those
    local heads only — the block table (and the ``pos``/``start``
    vectors) are the same host-global arrays on every shard, so no
    per-shard kernel variant is needed; the grid's ``hkv`` extent is
    simply the shard's. Everything here derives from operand shapes,
    never from a model config, which is what makes that slicing safe.
    """
    b, hkv, rows, hd = q4.shape
    assert hkv >= 1 and q4.shape[1] == kw.shape[1], \
        (q4.shape, kw.shape)  # local (possibly sharded) head counts agree
    num_pages = kw.shape[0]
    assert kw.shape == vw.shape == (num_pages, hkv, ps, hd), \
        (kw.shape, vw.shape)
    npg = table.shape[1]
    assert table.shape == (b, npg)
    kv_spec = pl.BlockSpec((1, 1, ps, hd),
                           functools.partial(_paged_kv_index, ps=ps,
                                             npg=npg, window=window))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, npg),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), _paged_q_index),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd), _paged_q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),   # running max m
            pltpu.VMEM((rows, 128), jnp.float32),   # running sum l
            pltpu.VMEM((rows, hd), jnp.float32),    # weighted-V accum
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_paged_attn_tile, spec=spec, ps=ps,
                          window=window, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(pos, start, table, q4, kw, vw)
