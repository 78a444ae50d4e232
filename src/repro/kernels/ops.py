"""Public jit'd wrappers around the Pallas kernels.

Handle shape normalisation (flatten/pad to tile multiples), backend
dispatch (interpret=True off-TPU so the kernels validate on CPU), and the
custom VJP for the quantised matmul (STE on x; weights are frozen wire
words). The pure-jnp fallback path (``use_kernel=False``) lowers to plain
XLA ops — used by the dry-run so that full-scale compilation does not
depend on Mosaic availability for the host platform.

Format dispatch lives in the codec registry (``repro.formats``): every
entry point resolves its format argument **once here at the boundary** —
callers may pass a ``FormatSpec``, a registry name (``"takum8"``,
``"posit16"``, ``"lns-takum8"``, ``"none"``), a legacy kind string
(``"linear"`` / ``"lns"`` / ``"posit"``) next to a width, or — the
original API — a bare int width meaning linear takum. Below the boundary
everything dispatches on spec attributes; no format string survives into
the kernel layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import formats
from repro.kernels import autotune
from repro.kernels import ref as kref
from repro.kernels import lns_matmul as klns
from repro.kernels import takum_attention as kattn
from repro.kernels import expert_matmul as kexp
from repro.kernels import takum_codec, takum_matmul, quantize as kquant
from repro.obs.metrics import GLOBAL

__all__ = ["takum_decode", "takum_encode", "fake_quant_fused", "quant_matmul",
           "lns_matmul", "takum_attention", "paged_attention",
           "interpret_default", "WireMatrix", "default_qmm_blocks",
           "qmm_blocks", "qmm_vmem_bytes", "default_attention_bk",
           "resolved_blocks", "ExpertWire", "expert_matmul", "EXPERT_BM"]


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad2d_for(x, block):
    """Flatten to 2D [R, C] padded to block multiples; return unpad info."""
    flat = x.reshape(-1)
    c = block[1]
    rows = -(-flat.size // c)
    rows_pad = -(-rows // block[0]) * block[0]
    total = rows_pad * c
    flat = jnp.pad(flat, (0, total - flat.size))
    return flat.reshape(rows_pad, c), x.shape, x.size


def _unpad2d(y, shape, size):
    return y.reshape(-1)[:size].reshape(shape)


def takum_decode(words, fmt, *, use_kernel: bool = True,
                 block=takum_codec.DEFAULT_BLOCK, dtype=jnp.float32,
                 interpret: bool | None = None):
    """Decode wire words to float, any input shape.

    ``fmt`` is anything ``formats.resolve`` accepts — an int width
    (linear takum, the original API), a registry name (``"posit16"``),
    or a ``FormatSpec``. ``words`` must be an unsigned array holding the
    format's n-bit words (the ``word_dtype(n)`` convention; zero word ->
    0.0, NaR -> NaN). The input is flattened, padded to ``block``
    multiples for the Pallas grid, and the padding is stripped from the
    result, so arbitrary shapes round-trip exactly. ``dtype`` is the
    decode target (f32 default; f64 needs x64; other float dtypes
    compute in f32 and cast).

    ``use_kernel=False`` bypasses Pallas entirely and lowers the same
    reconstruction through plain XLA (bit-identical by construction —
    used by dry-runs that must not depend on Mosaic).
    ``interpret=None`` auto-selects: real Mosaic lowering on TPU,
    Pallas interpreter elsewhere; pass ``True``/``False`` to force.
    """
    spec = formats.resolve(fmt)
    if not use_kernel:
        return kref.decode_ref(words, spec, dtype=dtype)
    interpret = interpret_default() if interpret is None else interpret
    w2, shape, size = _pad2d_for(words, block)
    y = takum_codec.decode_kernel_call(w2, spec, block=block,
                                       interpret=interpret, dtype=dtype)
    return _unpad2d(y, shape, size)


def takum_encode(x, fmt, *, use_kernel: bool = True,
                 block=takum_codec.DEFAULT_BLOCK,
                 interpret: bool | None = None):
    """Encode floats to wire words (RNE, saturating), any input shape.

    Input is cast to f32 first (the codec's dtype contract), flattened
    and padded to ``block`` multiples, and returned in the format's
    ``word_dtype`` with the original shape. Finite nonzero values never
    round to the 0/NaR words (§V-A saturation); NaN -> NaR, ±inf ->
    largest magnitude. ``fmt``/``use_kernel``/``interpret`` as in
    :func:`takum_decode`.
    """
    spec = formats.resolve_wire(fmt)
    if not use_kernel:
        return kref.encode_ref(x, spec)
    interpret = interpret_default() if interpret is None else interpret
    x2, shape, size = _pad2d_for(jnp.asarray(x, jnp.float32), block)
    y = takum_codec.encode_kernel_call(x2, spec, block=block,
                                       interpret=interpret)
    return _unpad2d(y, shape, size)


def fake_quant_fused(x, n=None, *, use_kernel: bool = True,
                     block=kquant.DEFAULT_BLOCK, dtype=jnp.float32,
                     interpret: bool | None = None, fmt: str = "linear"):
    """Fused quantise-dequantise through a wire format's grid without
    materialising the word tensor in HBM (one read + one write per tile).

    ``(fmt, n)`` resolve through the registry: ``fmt="linear"`` rounds
    through the linear takum grid (pure-integer tile body, bit-identical
    to ``encode`` + ``decode``); ``fmt="lns"`` through the *logarithmic*
    grid — RNE in ell_bar space, that format's native rounding domain;
    ``fmt="posit"`` through the posit baseline grid. ``fmt`` may also be
    a registry name or ``FormatSpec`` on its own (``n`` then unused).
    Input is cast to f32; output is ``dtype`` with the input's shape
    (padding stripped as in :func:`takum_decode`). No scaling is applied
    — scaling lives a level up in ``core.quant``.
    ``use_kernel``/``interpret`` as in :func:`takum_decode`.
    """
    spec = formats.resolve_wire(fmt, n)
    if not use_kernel:
        return kref.fake_quant_ref(x, spec, dtype=dtype)
    interpret = interpret_default() if interpret is None else interpret
    x2, shape, size = _pad2d_for(jnp.asarray(x, jnp.float32), block)
    y = kquant.fake_quant_kernel_call(x2, spec, block=block,
                                      interpret=interpret, dtype=dtype)
    return _unpad2d(y, shape, size)


def _pad_to(x, m0, m1):
    p0 = -x.shape[0] % m0
    p1 = -x.shape[1] % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def quant_matmul(x, w_words, fmt, use_kernel: bool = True,
                 interpret: bool | None = None,
                 block: tuple | None = None):
    """x [..., K] @ decode(w_words [K, N]) -> [..., N] f32.

    The weight-only-quantised matmul: ``w_words`` are wire words of any
    float-decoding format — linear takum (``fmt`` an int width, the
    original API, or ``"takum<n>"``) or the posit baseline
    (``"posit<n>"``); the LNS formats take :func:`lns_matmul`'s ℓ̄
    datapath instead and are rejected here. Words are decoded
    tile-by-tile in VMEM on the way into the MXU; ``x`` is any float
    dtype (computed in f32) with arbitrary leading dims, flattened to
    rows. Rows/cols are padded to the block grid and unpadded on return
    — zero words decode to 0.0, so K/N padding is exact. Differentiable
    in x (weights are wire-format constants; the VJP decodes once and
    uses a plain matmul — serving never needs it, QAT examples do).

    ``use_kernel=False`` lowers to a fused XLA decode+dot instead of
    Pallas (used off-TPU and by dry-runs). ``interpret=None``
    auto-selects Mosaic on TPU / the Pallas interpreter elsewhere.
    ``block = (bm, bn, bk)`` overrides the weight-stationary kernel's
    tile sizes; ``None`` consults the autotune table
    (``kernels/autotune.py`` — per format, shape bucket and backend,
    ``REPRO_AUTOTUNE`` gates it) and on a miss derives the tile from the
    shape (:func:`qmm_blocks`: one M-step up to 256 rows, the largest
    VMEM-fitting tile of 128-multiples dividing K and N). Each kernel
    call adds its grid-step count to the ``qmatmul.grid_steps`` counter
    of ``repro.obs.metrics.GLOBAL`` at trace time.
    """
    return _quant_matmul_fwd_impl(x, w_words, fmt, use_kernel, interpret,
                                  block)


def default_qmm_blocks(m0: int) -> tuple:
    """The fixed MXU-shaped tile ``(bm, 128, 128)``, with ``bm`` clamped
    to the padded M so small serving batches don't round up to a full
    128-row tile. The blockless ``lns_matmul`` fallback, and the first
    candidate of every autotune sweep; a blockless ``quant_matmul``
    derives its tile from the shape instead (:func:`qmm_blocks`)."""
    bm = min(takum_matmul.DEFAULT_BM, max(8, -(-m0 // 8) * 8))
    return (bm, takum_matmul.DEFAULT_BN, takum_matmul.DEFAULT_BK)


QMM_ONE_STEP_M = 256  # padded rows up to this run as one M-step
# under the v5e's 16 MiB scoped VMEM default: at M = 256 Mosaic refused
# tiles whose qmm_vmem_bytes estimate reached ~14.9 MB
QMM_VMEM_BUDGET = 12 * 1024 * 1024
# weight words per grid step: twice as many gained under 1% on a v5e
QMM_MAX_TILE_WORDS = 1280 * 896


def qmm_vmem_bytes(m: int, bm: int, bk: int, bn: int,
                   word_bytes: int) -> int:
    """VMEM the weight-stationary kernel holds at blocks ``(bm, bn, bk)``
    for ``m`` padded rows: the double-buffered word blocks, the f32
    decoded tile, the double-buffered x blocks and their f32 cast, the
    double-buffered (M, bn) f32 output stripe and one step's product."""
    return (2 * word_bytes * bk * bn + 4 * bk * bn + 3 * 4 * bm * bk
            + 2 * 4 * m * bn + 4 * bm * bn)


def _tiles_of(dim: int) -> list:
    """Multiples of 128 that divide ``dim`` rounded up to 128."""
    units = -(-dim // 128)
    return [128 * d for d in range(1, units + 1) if units % d == 0]


def qmm_blocks(m0: int, k0: int, n0: int, word_bytes: int = 1) -> tuple:
    """The tile a blockless ``quant_matmul`` call derives from its shape.

    ``bm`` covers the padded M in one M-step up to ``QMM_ONE_STEP_M``
    rows (the decode step and the prefill chunk), else 128. ``bk`` and
    ``bn`` are the multiples of 128 that divide K and N rounded up to
    128 — so no call pads more than the 128 tile did — with the most
    weight words per grid step (at most ``QMM_MAX_TILE_WORDS``, ties to
    the wider ``bn``: fewer re-reads of x) whose :func:`qmm_vmem_bytes`
    stays within ``QMM_VMEM_BUDGET``. A grid step has a fixed cost;
    a 5120x17920 projection at M = 32 takes 80 steps instead of 5600.
    Calls whose
    (M, 128) stripe already exceeds the kernel's accumulator budget run
    its M-outer schedule at the 128 tile, as before."""
    mp = max(8, -(-m0 // 8) * 8)
    bm = mp if mp <= QMM_ONE_STEP_M else takum_matmul.DEFAULT_BM
    mp = -(-mp // bm) * bm
    if mp * takum_matmul.DEFAULT_BN * 4 > takum_matmul.DEFAULT_ACC_BUDGET:
        return (bm, takum_matmul.DEFAULT_BN, takum_matmul.DEFAULT_BK)
    return (bm,) + _widest_tile(mp, bm, k0, n0, word_bytes)


def _widest_tile(mp: int, bm: int, k0: int, n0: int,
                 word_bytes: int) -> tuple:
    """``(bn, bk)``: the multiples of 128 dividing N and K rounded up to
    128 with the most weight words (ties to the wider ``bn``), at most
    ``QMM_MAX_TILE_WORDS``, whose ``(mp, bn)`` f32 stripe fits the
    accumulator budget and whose :func:`qmm_vmem_bytes` fits
    ``QMM_VMEM_BUDGET``; the 128 tile when none does."""
    best = (takum_matmul.DEFAULT_BN, takum_matmul.DEFAULT_BK)
    for bk in _tiles_of(k0):
        for bn in _tiles_of(n0):
            if (bk * bn > QMM_MAX_TILE_WORDS
                    or mp * bn * 4 > takum_matmul.DEFAULT_ACC_BUDGET
                    or qmm_vmem_bytes(mp, bm, bk, bn, word_bytes)
                    > QMM_VMEM_BUDGET):
                continue
            if (bk * bn, bn) > (best[1] * best[0], best[0]):
                best = (bn, bk)
    return best


def default_attention_bk() -> int:
    """The hand-picked KV tile default for flash decode attention."""
    return kattn.DEFAULT_BK


def _qmm_blocks(spec, m0: int, k0: int, n0: int, block: tuple | None,
                op: str) -> tuple:
    """Tile sizes for a matmul call: explicit ``block`` wins; otherwise
    consult the autotune table for (op, format, shape bucket, backend)
    and fall back to the hand-picked default on a miss (or with
    ``REPRO_AUTOTUNE=0``)."""
    if block is not None:
        return block
    tuned = autotune.lookup(op, spec.name,
                            autotune.matmul_bucket(m0, k0, n0))
    if tuned is not None:
        return tuned
    if op == "qmatmul":
        return qmm_blocks(m0, k0, n0, spec.bytes_per_elem())
    return default_qmm_blocks(m0)


def resolved_blocks(op: str, spec_name, shape) -> tuple:
    """The blocks a blockless call would actually use — what BENCH rows
    record per row. ``shape`` is ``(m, k, n)`` for the matmul ops or the
    context length for ``"attention"``."""
    spec = formats.resolve(spec_name)
    if op == "attention":
        tmax = int(shape if isinstance(shape, int) else shape[0])
        tuned = autotune.lookup(op, spec.name,
                                autotune.attention_bucket(tmax))
        bk = tuned[0] if tuned is not None else default_attention_bk()
        return (min(bk, -(-tmax // 8) * 8),)
    m0, k0, n0 = shape
    return _qmm_blocks(spec, m0, k0, n0, None, op)


def _matmul_fwd_common(x, w_words, spec, use_kernel, interpret, block, *,
                       op, ref_fn, prep_fn, kernel_fn):
    """Shared shape plumbing for the quantised-matmul wrappers: flatten
    leading dims, pad to the block grid (zero words decode to 0.0 /
    is_zero, so padding is exact), dispatch kernel vs XLA fallback,
    unpad and restore the leading dims."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n0 = w_words.shape[-1]
    if not use_kernel:
        return ref_fn(x2, w_words, spec).reshape(*lead, n0)
    interpret_ = interpret_default() if interpret is None else interpret
    m0 = x2.shape[0]
    bm, bn, bk = _qmm_blocks(spec, m0, x2.shape[1], n0, block, op)
    xp = _pad_to(prep_fn(x2), bm, bk)
    wp = _pad_to(w_words, bk, bn)
    # grid steps this call compiles to, counted at trace time (a served
    # executable adds its steps once, at its compile)
    GLOBAL.counter(f"{op}.grid_steps").inc(
        (xp.shape[0] // bm) * (wp.shape[0] // bk) * (wp.shape[1] // bn))
    out = kernel_fn(xp, wp, bm, bn, bk, interpret_)
    return out[:m0, :n0].reshape(*lead, n0)


def _matmul_bwd_common(spec, res, g):
    """Shared VJP: weights are wire-format constants, so the only
    cotangent is ``g @ decode(w)^T`` (STE through any input rounding)."""
    x, w_words = res
    w = spec.decode_tile(w_words)
    gx = jnp.einsum("...n,kn->...k", g, w).astype(x.dtype)
    return gx, None


def _dense_wire_spec(fmt):
    """Resolve + guard for the float-decoding matmul: LNS words carry
    the ℓ̄ datapath and must go through :func:`lns_matmul`."""
    spec = formats.resolve_wire(fmt)
    if spec.has_lns_parts:
        raise ValueError(
            f"format {spec.name!r} is on the LNS ℓ̄ datapath; use "
            "ops.lns_matmul for it")
    return spec


def _quant_matmul_fwd_impl(x, w_words, fmt, use_kernel, interpret, block):
    spec = _dense_wire_spec(fmt)
    return _matmul_fwd_common(
        x, w_words, spec, use_kernel, interpret, block,
        op="qmatmul",
        ref_fn=kref.qmatmul_ref,
        prep_fn=lambda x2: x2,
        kernel_fn=lambda xp, wp, bm, bn, bk, itp:
            takum_matmul.qmatmul_kernel_call(xp, wp, spec, bm=bm, bn=bn,
                                             bk=bk, interpret=itp))


def _qmm_fwd(x, w_words, fmt, use_kernel, interpret, block):
    return _quant_matmul_fwd_impl(x, w_words, fmt, use_kernel, interpret,
                                  block), (x, w_words)


def _qmm_bwd(fmt, use_kernel, interpret, block, res, g):
    return _matmul_bwd_common(_dense_wire_spec(fmt), res, g)


quant_matmul.defvjp(_qmm_fwd, _qmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def lns_matmul(x, w_words, fmt, accum: str = "linear",
               use_kernel: bool = True, interpret: bool | None = None,
               block: tuple | None = None):
    """x [..., K] ⊗ decode(w_words [K, N]) -> [..., N] f32 on the LNS
    datapath.

    ``w_words`` are *logarithmic* takum wire words (``fmt`` an int width
    — resolving to ``lns-takum<n>`` — a registry name, or a
    ``FormatSpec`` with ``has_lns_parts``); ``x`` is float and is
    quantised to the same LNS grid on the way in (the LNS-DNN design
    point: both operands live in ell_bar space so every product is one
    exact int32 add — see ``kernels/lns_matmul.py``). ``accum="linear"``
    converts each product to f32 and accumulates linearly, matching the
    ``core.lns.lns_matmul`` reference bit-exactly for K = 1 and to f32
    summation-order tolerance otherwise; ``accum="gauss"`` folds
    products in the log domain through the Gauss-log LUT and leaves it
    once per output element (adds one ``2^-(wf+1)`` re-quantisation per
    fold). Padding, ``use_kernel``, ``interpret`` and ``block`` behave
    as in :func:`quant_matmul` (``use_kernel=False`` is the fused XLA
    decode+dot fallback, one extra f32 rounding per product — it is
    inherently linear-accumulating, so ``accum="gauss"`` with
    ``use_kernel=False`` raises rather than silently returning the wrong
    accumulator; the kernel path runs on any backend via the
    interpreter). Differentiable in x with a straight-through estimate
    through the activation quantisation: the VJP is ``g @ decode(w)^T``.
    """
    return _lns_matmul_fwd_impl(x, w_words, fmt, accum, use_kernel,
                                interpret, block)


def _lns_wire_spec(fmt):
    spec = formats.resolve_lns(fmt)
    if not spec.has_lns_parts:
        raise ValueError(
            f"format {spec.name!r} has no LNS ℓ̄ datapath; use "
            "ops.quant_matmul for float-decoding wire formats")
    return spec


def _lns_matmul_fwd_impl(x, w_words, fmt, accum, use_kernel, interpret,
                         block):
    # guard here, not in the public wrapper: custom_vjp routes grad calls
    # straight to the fwd rule, which must refuse just the same
    if accum == "gauss" and not use_kernel:
        raise ValueError(
            "accum='gauss' needs the kernel path: the XLA fallback is a "
            "fused decode+dot and cannot Gauss-accumulate; pass "
            "use_kernel=True (interpret mode runs on any backend)")
    spec = _lns_wire_spec(fmt)
    return _matmul_fwd_common(
        x, w_words, spec, use_kernel, interpret, block,
        op="lns_qmatmul",
        ref_fn=kref.lns_qmatmul_ref,
        # activations join the weights on the LNS grid before tiling
        prep_fn=lambda x2: spec.encode_tile(x2),
        kernel_fn=lambda xp, wp, bm, bn, bk, itp:
            klns.lns_matmul_kernel_call(xp, wp, spec, accum=accum, bm=bm,
                                        bn=bn, bk=bk, interpret=itp))


def _lmm_fwd(x, w_words, fmt, accum, use_kernel, interpret, block):
    return _lns_matmul_fwd_impl(x, w_words, fmt, accum, use_kernel,
                                interpret, block), (x, w_words)


def _lmm_bwd(fmt, accum, use_kernel, interpret, block, res, g):
    return _matmul_bwd_common(_lns_wire_spec(fmt), res, g)


lns_matmul.defvjp(_lmm_fwd, _lmm_bwd)


MAX_ATTN_Q_ROWS = 1024  # G*tq rows above this fall back to the oracle


def takum_attention(q, k_cache, v_cache, n=0, fmt="none", *,
                    pos, start=None, window: int = 0,
                    use_kernel: bool | None = None,
                    interpret: bool | None = None,
                    block: int | None = None,
                    max_q_rows: int = MAX_ATTN_Q_ROWS):
    """Attention over a wire-format KV cache, decoded inside the kernel.

    ``q [B, tq, H, hd]`` (any float dtype) attends over the head-major
    ``k_cache``/``v_cache [B, Hkv, Tmax, hd]`` — raw wire words of any
    registered format (``(fmt, n)`` resolve through the registry:
    ``("linear", 8)``, ``"takum16"``, ``"posit8"``, a ``FormatSpec`` …)
    or plain floats under the identity codec (``fmt="none"``: the
    uncompressed cache rides the same fused kernel). Returns
    ``[B, tq, H, hd]`` f32. GQA (``H = G * Hkv``) is handled by grouping
    the ``G`` query heads of each KV head into one row block so every
    K/V tile is read once per KV head.

    Masking: causal from ``pos`` (the position of ``q[:, 0]``; python
    int or traced scalar), per-sequence ``start`` (``[B]`` first valid
    key — left-padded prompts), sliding ``window`` (0 = full). Query
    rows with ``qpos < start`` (padding queries) are garbage on every
    path; they stay finite but the kernel and oracle average over
    different key sets, so only rows with ``qpos >= start`` are
    contract-comparable.

    ``use_kernel``: ``True`` = the fused Pallas flash kernel (KV words
    decoded tile-by-tile in VMEM; full-precision K/V never materialised
    in HBM); ``False`` = the jnp oracle — exactly the decode-then-attend
    path (whole cache decoded to f32, dense masked softmax), which is
    what XLA fuses best off-TPU; ``None`` = kernel on TPU, oracle
    elsewhere (the serving auto mode, mirroring ``WireMatrix``).
    ``interpret`` as in :func:`takum_decode`. ``block`` is the KV
    sequence tile ``bk`` (``None`` consults the autotune table, falling
    back to 256; either way clamped/aligned to ``Tmax``;
    ``Tmax`` is zero-word padded to a tile multiple — beyond-``pos``
    keys are causally masked, so padding is exact). Calls with
    ``G * tq > max_q_rows`` (prefill-shaped) fall back to the oracle:
    the kernel's query block is VMEM-resident per (b, h) step.
    """
    spec = formats.resolve(fmt, n)
    b, tq, h, hd = q.shape
    hkv, tmax = k_cache.shape[1], k_cache.shape[2]
    if h % hkv:
        raise ValueError(f"n_heads {h} not a multiple of n_kv_heads {hkv}")
    g = h // hkv
    if use_kernel is None:
        use_kernel = not interpret_default()
    if not use_kernel or g * tq > max_q_rows:
        return kref.attention_ref(q, k_cache, v_cache, 0, spec, pos=pos,
                                  start=start, window=window)
    interpret = interpret_default() if interpret is None else interpret
    rows = g * tq
    bq = -(-rows // 8) * 8
    # row r = (group r // tq, query position pos + r % tq)
    q4 = q.reshape(b, tq, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    q4 = q4.reshape(b, hkv, rows, hd).astype(jnp.float32)
    if bq != rows:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, bq - rows), (0, 0)))
    if block is None:  # no explicit tile: consult the autotune table
        tuned = autotune.lookup("attention", spec.name,
                                autotune.attention_bucket(tmax))
        block = tuned[0] if tuned is not None else kattn.DEFAULT_BK
    bk = min(block, -(-tmax // 8) * 8)
    pad_t = -tmax % bk
    kw, vw = k_cache, v_cache
    if pad_t:  # zero words decode to 0.0 / is_zero and are causally masked
        kw = jnp.pad(kw, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        vw = jnp.pad(vw, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    start_arr = (jnp.zeros((b,), jnp.int32) if start is None
                 else jnp.asarray(start, jnp.int32).reshape(b))
    out4 = kattn.attention_kernel_call(q4, kw, vw, pos_arr, start_arr,
                                       spec=spec, bk=bk, tq=tq,
                                       window=window, interpret=interpret)
    out = out4[:, :, :rows].reshape(b, hkv, g, tq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd)


def paged_attention(q, k_pool, v_pool, table, fmt="none", *, pos,
                    start=None, window: int = 0,
                    use_kernel: bool | None = None,
                    interpret: bool | None = None):
    """Decode-step attention over a *paged* wire-format KV cache.

    The serving scheduler's counterpart of :func:`takum_attention`:
    instead of one contiguous ``[B, Hkv, Tmax, hd]`` cache, K/V live in
    a shared ``[num_pages, Hkv, page_size, hd]`` pool (wire words of any
    registered format, or floats under the identity codec) and
    ``table [B, NP]`` maps each sequence's kk-th KV block to a pool
    page. ``q [B, 1, H, hd]`` is one decode step for a continuous batch:
    ``pos`` and ``start`` are per-sequence ``(B,)`` vectors (unequal
    sequence lengths in one packed batch). Returns ``[B, 1, H, hd]``
    f32.

    ``use_kernel=True`` runs the paged Pallas flash kernel — the block
    table rides in as a scalar-prefetch operand and the KV index map
    gathers pages, decoding words tile-by-tile in VMEM; ``False`` is the
    gather-then-``attention_ref`` oracle (each sequence's pages gathered
    contiguous, then the standard decode-then-attend reference);
    ``None`` = kernel on TPU, oracle elsewhere, mirroring
    :func:`takum_attention`. Pages past a sequence's ``pos`` hold stale
    words from previous page owners — containment comes from the causal
    mask, so parity holds for any pool contents beyond ``pos``.
    """
    spec = formats.resolve(fmt)
    b, tq, h, hd = q.shape
    if tq != 1:
        raise ValueError(
            f"paged_attention is decode-only (tq == 1), got tq={tq}; "
            "prefill runs on the contiguous cache and is scattered into "
            "pages by the scheduler")
    hkv, ps = k_pool.shape[1], k_pool.shape[2]
    if h % hkv:
        raise ValueError(f"n_heads {h} not a multiple of n_kv_heads {hkv}")
    g = h // hkv
    if use_kernel is None:
        use_kernel = not interpret_default()
    if not use_kernel:
        return kref.paged_attention_ref(q, k_pool, v_pool, table, spec,
                                        pos=pos, start=start, window=window)
    interpret = interpret_default() if interpret is None else interpret
    rows = g
    bq = -(-rows // 8) * 8
    q4 = q.reshape(b, 1, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    q4 = q4.reshape(b, hkv, rows, hd).astype(jnp.float32)
    if bq != rows:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, bq - rows), (0, 0)))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    start_arr = (jnp.zeros((b,), jnp.int32) if start is None
                 else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,)))
    table_arr = jnp.asarray(table, jnp.int32)
    out4 = kattn.paged_attention_kernel_call(
        q4, k_pool, v_pool, pos_arr, start_arr, table_arr, spec=spec,
        ps=ps, window=window, interpret=interpret)
    out = out4[:, :, :rows].reshape(b, hkv, g, 1, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h, hd)


@functools.partial(jax.jit, static_argnums=0)
def _encode_words(spec, w):
    # one fused program: run eagerly, the codec would hold several
    # lane-wide temporaries of the whole weight at once
    return spec.encode_tile(w.astype(jnp.float32))


@jax.tree_util.register_pytree_node_class
class WireMatrix:
    """A 2D weight in wire format, decoded on use.

    Drop-in for a float ``[K, N]`` matrix at ``x @ w`` sites: jax defers
    the matmul to :meth:`__rmatmul__`, which routes through
    :func:`quant_matmul` for float-decoding formats (linear takum and
    the posit baseline — the weight-stationary decode-once kernel on
    TPU, the fused XLA decode+dot elsewhere) or :func:`lns_matmul` for
    ``has_lns_parts`` formats (the ℓ̄-datapath kernel — the wire words
    are logarithmic takums and activations are quantised to the same
    grid per call). The route is chosen from the spec's *attributes*,
    so registering a new format needs no change here. This is how
    ``serve.engine.quantize_weights(..., mode="wire")`` swaps a served
    model onto n/32-size HBM weights without touching the model code.
    """

    def __init__(self, words, n=None, *, block: tuple | None = None,
                 fmt="linear"):
        self.spec = formats.resolve_wire(fmt, n)
        self.words = words
        self.block = block

    @classmethod
    def encode(cls, w, n=None, *, block: tuple | None = None,
               fmt="linear"):
        spec = formats.resolve_wire(fmt, n)
        return cls(_encode_words(spec, jnp.asarray(w)), block=block,
                   fmt=spec)

    # back-compat accessors (the spec carries the identity)
    @property
    def n(self) -> int:
        return self.spec.n

    @property
    def fmt(self) -> str:
        return self.spec.kind

    # pytree: words are the leaf; the spec and block are static
    def tree_flatten(self):
        return (self.words,), (self.spec, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], block=aux[1], fmt=aux[0])

    @property
    def shape(self):
        return self.words.shape

    @property
    def ndim(self):
        return self.words.ndim

    @property
    def dtype(self):  # decode target dtype, for callers probing params
        return jnp.float32

    def decode(self, dtype=jnp.float32):
        return self.spec.decode_tile(self.words, dtype=dtype)

    def __rmatmul__(self, x):
        if self.spec.has_lns_parts:
            out = lns_matmul(x, self.words, self.spec, "linear",
                             not interpret_default(), None, self.block)
        else:
            out = quant_matmul(x, self.words, self.spec,
                               not interpret_default(), None, self.block)
        return out.astype(x.dtype)

    def __repr__(self):
        return (f"WireMatrix(shape={tuple(self.words.shape)}, "
                f"spec={self.spec.name!r})")


EXPERT_BM = 32  # rows of one expert's row tile in the grouped matmul


def expert_matmul(x, w_words, tile_expert, n_used, fmt, *,
                  bm: int = EXPERT_BM, interpret: bool | None = None):
    """Grouped expert matmul: ``x [M, K]`` rows grouped by expert in
    ``bm``-row tiles times the decoded wire words ``w_words [E, K, N]``
    -> ``[M, N]`` f32. Row tile ``t`` belongs to expert
    ``tile_expert[t]``, and tiles from ``n_used[0]`` on hold no rows
    (they give zeros).

    Runs ``expert_matmul_kernel_call``: each expert's weight tiles are
    decoded in VMEM through ``FormatSpec.decode_tile``, and an expert
    without rows is never read; its oracle is ``kref.expert_matmul_ref``
    (``interpret=None`` = interpret mode off the TPU). The kernel's tile
    ``(bn, bk)`` follows ``quant_matmul``'s shape rule
    (:func:`_widest_tile`) for the whole ``(M, bn)`` output stripe; K
    and N are zero-padded to it (zero words decode to 0.0)."""
    spec = _dense_wire_spec(fmt)
    m, k0 = x.shape
    n0 = w_words.shape[-1]
    if m % bm or m * takum_matmul.DEFAULT_BN * 4 > \
            takum_matmul.DEFAULT_ACC_BUDGET:
        raise ValueError(f"expert_matmul: {m} rows must be whole {bm}-row "
                         "tiles whose output stripe fits the accumulator "
                         "budget; route fewer tokens per call")
    _, bn, bk = expert_blocks(m, k0, n0, spec.bytes_per_elem())
    kp, np_ = -(-k0 // bk) * bk, -(-n0 // bn) * bn
    xp = jnp.pad(x, ((0, 0), (0, kp - k0))) if kp != k0 else x
    wp = w_words
    if (kp, np_) != (k0, n0):
        wp = jnp.pad(w_words, ((0, 0), (0, kp - k0), (0, np_ - n0)))
    interpret = interpret_default() if interpret is None else interpret
    out = kexp.expert_matmul_kernel_call(
        xp, wp, tile_expert, n_used, spec=spec, bm=bm, bn=bn, bk=bk,
        interpret=interpret)
    return out[:, :n0]


def expert_blocks(m: int, k0: int, n0: int, word_bytes: int = 1) -> tuple:
    """``(bm, bn, bk)`` of an ``expert_matmul`` kernel call on ``m``
    grouped rows (what the kernel and its oracle tile by)."""
    return (EXPERT_BM,) + _widest_tile(m, EXPERT_BM, k0, n0, word_bytes)


@jax.tree_util.register_pytree_node_class
class ExpertWire:
    """A stack of expert weights ``[..., E, K, N]`` in wire format.

    The words are the pytree leaf, so ``lax.scan`` over a layer-stacked
    ``[L, E, K, N]`` stack hands each layer an ``ExpertWire`` of its own
    ``[E, K, N]`` words, as it does a ``WireMatrix``. No float copy of
    the experts exists: the mixture-of-experts layer passes the words to
    :func:`expert_matmul`, which decodes each expert's tiles in the
    kernel."""

    def __init__(self, words, fmt="takum8"):
        self.spec = formats.resolve_wire(fmt)
        self.words = words

    @classmethod
    def encode(cls, w, fmt="takum8"):
        spec = formats.resolve_wire(fmt)
        return cls(_encode_words(spec, jnp.asarray(w)), fmt=spec)

    def tree_flatten(self):
        return (self.words,), self.spec

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], fmt=aux)

    @property
    def shape(self):
        return self.words.shape

    @property
    def ndim(self):
        return self.words.ndim

    @property
    def dtype(self):  # decode target dtype, for callers probing params
        return jnp.float32

    def decode(self, dtype=jnp.float32):
        return self.spec.decode_tile(self.words, dtype=dtype)

    def __repr__(self):
        return (f"ExpertWire(shape={tuple(self.words.shape)}, "
                f"spec={self.spec.name!r})")
