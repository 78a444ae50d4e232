"""Shared bit-manipulation helpers for the takum / posit codecs.

All helpers operate on unsigned integer JAX arrays. Word widths up to 32 bits
are handled in ``uint32`` lanes; 64-bit words require ``jax_enable_x64``.

The helpers are deliberately branch-free (``where``/arithmetic only) so that
they vectorise cleanly on the TPU VPU and stay trivially differentiable-free
(integer domain).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "word_dtype",
    "compute_dtype",
    "mask",
    "safe_shl",
    "safe_shr",
    "ashr",
    "floor_log2_u8",
    "floor_log2",
    "clz",
    "popcount",
    "bit",
    "clamp",
    "x64_enabled",
]


def x64_enabled() -> bool:
    return jax.config.jax_enable_x64


def word_dtype(n: int):
    """Narrowest unsigned storage dtype for an ``n``-bit word."""
    if n <= 8:
        return jnp.uint8
    if n <= 16:
        return jnp.uint16
    if n <= 32:
        return jnp.uint32
    if n <= 64:
        if not x64_enabled():
            raise ValueError(
                f"{n}-bit words need jax_enable_x64 (uint64 lanes); enable it "
                "with jax.config.update('jax_enable_x64', True)"
            )
        return jnp.uint64
    raise ValueError(f"unsupported word width n={n}")


def compute_dtype(n: int):
    """Unsigned dtype used for internal codec computation (>= 32 bits)."""
    if n <= 32:
        return jnp.uint32
    return word_dtype(n)  # uint64, gated on x64


def signed_dtype(n: int):
    return jnp.int32 if n <= 32 else jnp.int64


def clamp(x, lo, hi=None):
    """``jnp.clip(x, lo, hi)`` (``hi=None``: ``jnp.maximum(x, lo)``) as
    compare + select.

    Bit-identical to the clip for every integer dtype, but it lowers on
    the TPU: Mosaic has no unsigned min/max (``arith.minui``/``maxui``),
    and the codec clamps unsigned lanes.
    """
    x = jnp.asarray(x)
    x = jnp.where(x < lo, jnp.asarray(lo, x.dtype), x)
    if hi is not None:
        x = jnp.where(x > hi, jnp.asarray(hi, x.dtype), x)
    return x


def mask(nbits, dtype=jnp.uint32):
    """All-ones mask of ``nbits`` (array or python int). nbits in [0, width]."""
    if isinstance(nbits, int):
        width = jnp.iinfo(dtype).bits
        if nbits <= 0:
            return jnp.asarray(0, dtype)
        if nbits >= width:
            return jnp.asarray(jnp.iinfo(dtype).max, dtype)
        return jnp.asarray((1 << nbits) - 1, dtype)
    nbits = jnp.asarray(nbits)
    width = jnp.iinfo(dtype).bits
    one = jnp.asarray(1, dtype)
    full = jnp.asarray(jnp.iinfo(dtype).max, dtype)
    n = clamp(nbits, 0, width)
    # (1 << n) - 1, avoiding the n == width overflow lane-wise.
    shifted = safe_shl(one, n.astype(dtype))
    return jnp.where(n >= width, full, shifted - one)


def _amount(x, s):
    """Coerce a shift amount to x's dtype, clamped into [0, width-1]."""
    width = jnp.iinfo(jnp.asarray(x).dtype).bits
    s = jnp.asarray(s)
    return clamp(s, 0, width - 1).astype(jnp.asarray(x).dtype)


def safe_shl(x, s):
    """``x << s`` that yields 0 for s >= width instead of UB."""
    x = jnp.asarray(x)
    width = jnp.iinfo(x.dtype).bits
    s = jnp.asarray(s)
    out = x << _amount(x, s)
    return jnp.where(s >= width, jnp.zeros_like(x), out)


def safe_shr(x, s):
    """Logical ``x >> s`` that yields 0 for s >= width instead of UB."""
    x = jnp.asarray(x)
    width = jnp.iinfo(x.dtype).bits
    s = jnp.asarray(s)
    out = x >> _amount(x, s)
    return jnp.where(s >= width, jnp.zeros_like(x), out)


def ashr(x, s, width: int):
    """Arithmetic right shift of a ``width``-bit two's-complement value.

    ``x`` holds the value in the low ``width`` bits of an unsigned lane.
    Returns the shifted value, again masked to ``width`` bits.

    Implementation: place the value at the top of the signed lane, use the
    hardware arithmetic shift, then shift back down. This is exactly the
    trick used for the paper's Table-I "bias via arithmetic right shift".
    """
    x = jnp.asarray(x)
    lane = jnp.iinfo(x.dtype).bits
    sx = x.astype(signed_dtype(lane))
    up = lane - width
    shifted = (sx << jnp.asarray(up, sx.dtype)) >> _amount(sx, jnp.asarray(s) + up)
    return (shifted.astype(x.dtype)) & mask(width, x.dtype)


def floor_log2_u8(x):
    """floor(log2(x)) for x in [1, 255] via a monotone compare-chain.

    Software analogue of the paper's 8-bit leading-one detector (§V-C): the
    position of the MSB.  Seven compares + adds, constant depth, no lookup
    table needed on a vector unit.
    """
    x = jnp.asarray(x)
    r = jnp.zeros(x.shape, jnp.int32)
    for k in range(1, 8):
        r = r + (x >= (1 << k)).astype(jnp.int32)
    return r


def lod8_lut(x):
    """Hardware-faithful 8-bit LOD after Ebrahimi et al. [17] (§V-C).

    Splits the byte into two nibbles, applies a 4-bit LUT to each, selects
    the high result (+4) if any high bit is set. Used only to validate the
    compare-chain against the paper's exact structure.
    """
    x = jnp.asarray(x, jnp.uint32)
    lo = x & 0xF
    hi = (x >> 4) & 0xF

    def lut4(v):
        # priority encoder for 4 bits: offset of MSB (0 for v in {0,1})
        return (
            jnp.where(v >= 8, 3, 0)
            + jnp.where((v >= 4) & (v < 8), 2, 0)
            + jnp.where((v >= 2) & (v < 4), 1, 0)
        ).astype(jnp.int32)

    return jnp.where(hi != 0, lut4(hi) + 4, lut4(lo))


def popcount(x):
    return jax.lax.population_count(jnp.asarray(x))


def _smear(x):
    """Propagate the MSB down: after smearing, x has all bits <= MSB set."""
    x = jnp.asarray(x)
    width = jnp.iinfo(x.dtype).bits
    s = 1
    while s < width:
        x = x | (x >> jnp.asarray(s, x.dtype))
        s *= 2
    return x


def floor_log2(x):
    """floor(log2(x)) for x >= 1, arbitrary lane width (smear + popcount).

    Note the O(log width) cost: this is what a *posit* decoder must pay over
    the full word, while the takum decoder only ever needs the 8-bit variant.
    """
    x = jnp.asarray(x)
    return (popcount(_smear(x)) - 1).astype(jnp.int32)


def clz(x, width: int):
    """Count leading zeros of the low ``width`` bits of x (x < 2**width)."""
    x = jnp.asarray(x)
    return jnp.where(
        x == 0, jnp.asarray(width, jnp.int32), width - 1 - floor_log2(clamp(x, 1))
    ).astype(jnp.int32)


def bit(x, i):
    """Extract bit i (0 = LSB) as the same dtype as x."""
    x = jnp.asarray(x)
    return safe_shr(x, i) & jnp.asarray(1, x.dtype)
