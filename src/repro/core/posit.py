"""Posit<n, es=2> baseline codec (Posit(TM) Standard 2022).

The paper evaluates its takum codec against two state-of-the-art posit
codecs, which we reproduce as software baselines:

* **FloPoCo-SM** — sign-magnitude dataflow: negate the word (full-width
  two's complement) when S = 1, then decode the magnitude into the classic
  internal representation (7): ``(S, e~, f~) -> (-1)^S (1 + f~) 2^e~``.
* **FloPoCo-2C** — two's-complement dataflow (Murillo et al. 2022): decode
  the raw word directly into representation (8):
  ``(S, e, f) -> ((1 - 3S) + f) 2^e``, avoiding the full-width negation.
  The regime rule flips with S, the exponent bits are XOR-ed with S
  (including ghost bits), and the fraction is used as-is (monotonic).

Both variants still require a **full-width** leading-run count and
**full-width** variable shifts — the structural cost the paper contrasts
with takum's fixed 12-bit header window. That contrast is what the Fig. 1-4
analog benchmarks measure.

Unlike the FloPoCo-2C encoder (which expects pre-computed rounding
information from the caller — see §VI-B), our posit encoder implements
full RNE rounding with posit saturation semantics, making the codec
comparison *harder* on takum than the paper's own (noted in the bench).

Float reconstruction is **integer-only**, matching the takum datapath
standard: ``posit_to_float`` assembles the IEEE word directly — sign |
biased exponent | fraction packed into an unsigned lane and bitcast —
with explicit RNE; no ldexp, float divide or transcendental on the hot
path. The pre-existing ldexp dataflow is retained as
``posit_to_float_ref`` and pinned bit-identical by
tests/test_posit_int_reconstruct.py, so the takum-vs-posit benchmark
rows compare *format* cost, not implementation quality.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitops
from repro.core.bitops import (
    bit,
    compute_dtype,
    mask,
    safe_shl,
    safe_shr,
    word_dtype,
)
# the IEEE assembly machinery is shared with the takum codec so both
# integer paths stay one implementation (and one audit surface)
from repro.core.takum import _IEEE, _rne_shr

__all__ = ["PositDecoded", "decode_sm", "decode_2c", "encode",
           "posit_to_float", "posit_to_float_ref", "float_to_posit",
           "frac_width"]


def frac_width(n: int) -> int:
    """Fraction field output width (left-aligned): max frac bits = n - 5."""
    return n - 5


class PositDecoded(NamedTuple):
    s: jnp.ndarray      # sign, int32 0/1
    e: jnp.ndarray      # exponent: rep (7) e~ for SM, rep (8) e for 2C
    frac: jnp.ndarray   # fraction field, width frac_width(n), left-aligned
    is_zero: jnp.ndarray
    is_nar: jnp.ndarray


def _validate_n(n: int) -> None:
    if not (6 <= n <= 64):
        raise ValueError(f"posit codec supports 6 <= n <= 64, got {n}")
    if n > 32 and not bitops.x64_enabled():
        raise ValueError("n > 32 requires jax_enable_x64")


def _leading_run(body_aligned, n: int, cdt):
    """Length of the leading run of identical bits in the top n-1 bits of
    ``body_aligned`` (left-aligned at the lane MSB). This is the full-width
    leading-run detector posits cannot avoid."""
    lane = jnp.iinfo(cdt).bits
    top = bit(body_aligned, lane - 1)
    u = jnp.where(top == 1, ~body_aligned, body_aligned)
    u = u & safe_shl(mask(n - 1, cdt), lane - (n - 1))  # keep n-1 top bits
    m = bitops.clz(u, lane)
    return jnp.minimum(m, n - 1).astype(jnp.int32), top.astype(jnp.int32)


def _extract_after_regime(P, m, n: int, cdt):
    """exp (2 bits, ghost-padded) and left-aligned fraction after a regime
    of length m (+1 terminator). Data-dependent full-width shifts."""
    remaining = n - 2 - m  # bits after sign+regime+terminator; may be < 0
    rem = jnp.maximum(remaining, 0)
    field = P & mask(rem, cdt)
    e2 = jnp.where(
        remaining >= 2,
        safe_shr(field, rem - 2) & jnp.asarray(3, cdt),
        jnp.where(remaining == 1, (field & jnp.asarray(1, cdt)) << jnp.asarray(1, cdt),
                  jnp.asarray(0, cdt)),
    ).astype(jnp.int32)
    wf = frac_width(n)
    fr_bits = jnp.maximum(remaining - 2, 0)
    frac = safe_shl(field & mask(fr_bits, cdt), wf - fr_bits)
    return e2, frac


def decode_sm(words, n: int, es: int = 2) -> PositDecoded:
    """FloPoCo-SM: negate-first decode to internal representation (7)."""
    _validate_n(n)
    assert es == 2
    cdt = compute_dtype(n)
    lane = jnp.iinfo(cdt).bits
    P = jnp.asarray(words).astype(cdt) & mask(n, cdt)
    s = bit(P, n - 1).astype(jnp.int32)
    is_zero = P == 0
    is_nar = P == safe_shl(jnp.asarray(1, cdt), n - 1)

    # full-width two's complement negation when negative
    X = jnp.where(s == 1, (~P + jnp.asarray(1, cdt)) & mask(n, cdt), P)
    body = safe_shl(X & mask(n - 1, cdt), lane - (n - 1))
    m, first = _leading_run(body, n, cdt)
    k = jnp.where(first == 1, m - 1, -m)
    e2, frac = _extract_after_regime(X, m, n, cdt)
    e = 4 * k + e2
    return PositDecoded(s=s, e=e.astype(jnp.int32), frac=frac,
                        is_zero=is_zero, is_nar=is_nar)


def decode_2c(words, n: int, es: int = 2) -> PositDecoded:
    """FloPoCo-2C: direct decode of the raw word to representation (8).

    No full-width negation: the regime rule flips with S, exponent bits
    (incl. ghost bits) are XOR-ed with S, the fraction is monotone as-is.
    """
    _validate_n(n)
    assert es == 2
    cdt = compute_dtype(n)
    lane = jnp.iinfo(cdt).bits
    P = jnp.asarray(words).astype(cdt) & mask(n, cdt)
    s = bit(P, n - 1).astype(jnp.int32)
    is_zero = P == 0
    is_nar = P == safe_shl(jnp.asarray(1, cdt), n - 1)

    body = safe_shl(P & mask(n - 1, cdt), lane - (n - 1))
    m, first = _leading_run(body, n, cdt)
    # k = m-1 when the leading bit differs from S, else -m
    k = jnp.where((first ^ s) == 1, m - 1, -m)
    e2, frac = _extract_after_regime(P, m, n, cdt)
    e2 = e2 ^ (3 * s)  # exponent bits inverted for negatives (ghosts too)
    e = 4 * k + e2
    return PositDecoded(s=s, e=e.astype(jnp.int32), frac=frac,
                        is_zero=is_zero, is_nar=is_nar)


# ---------------------------------------------------------------------------
# Encoder: from representation (8), full RNE + posit saturation
# ---------------------------------------------------------------------------


def encode(s, e, frac, n: int, *, wm: int, sticky=None,
           is_zero=None, is_nar=None, es: int = 2):
    """Encode (S, e, f) of representation (8) into rounded n-bit posits.

    The magnitude is assembled with full-width data-dependent shifts (the
    regime length is unbounded — the posit cost the paper contrasts with
    takum's <= 7-bit shifter), rounded RNE-to-even-word, saturated so that
    finite nonzero values never become 0 or NaR, then negated when S = 1.
    """
    _validate_n(n)
    assert es == 2
    cdt = compute_dtype(n)
    lane = jnp.iinfo(cdt).bits
    if wm < 1 or wm > lane - 4:
        raise ValueError(f"wm={wm} out of range")
    s = jnp.asarray(s).astype(jnp.int32)
    e = jnp.asarray(e).astype(jnp.int32)
    frac = jnp.asarray(frac).astype(cdt)
    sticky = (jnp.zeros(jnp.shape(e), bool) if sticky is None
              else jnp.asarray(sticky).astype(bool))

    # magnitude form: |v| = (1 + mf) 2^me
    f_nz = frac != 0
    mf = jnp.where((s == 1) & f_nz,
                   (safe_shl(jnp.asarray(1, cdt), wm) - frac) & mask(wm, cdt),
                   frac)
    me = e + ((s == 1) & ~f_nz)

    k = me >> 2
    e2 = (me & 3).astype(cdt)
    # clamp the regime so the run fits the lane; saturation flags keep RNE honest
    k_hi = k > n - 2
    k_lo = k < -(n - 2)
    k = jnp.clip(k, -(n - 2), n - 2)
    e2 = jnp.where(k_hi, jnp.asarray(3, cdt), jnp.where(k_lo, jnp.asarray(0, cdt), e2))
    mf = jnp.where(k_hi, mask(wm, cdt), jnp.where(k_lo, jnp.asarray(0, cdt), mf))
    sticky = sticky | k_hi | k_lo

    # regime field: k >= 0: (k+1) ones + '0'  (length k+2, value 2^(k+2)-2)
    #               k <  0: |k| zeros + '1'   (length |k|+1, value 1)
    rl = jnp.where(k >= 0, k + 2, 1 - k)
    regime_val = jnp.where(
        k >= 0,
        safe_shl(jnp.asarray(1, cdt), k + 2) - jnp.asarray(2, cdt),
        jnp.asarray(1, cdt),
    )

    low = safe_shl(e2, wm) | mf          # width 2 + wm
    cut = rl + 2 + wm - (n - 1)          # bits to drop (>= 0 given wm >= n-5)
    # case A: cut inside `low` (regime fully kept)
    body_a = safe_shl(regime_val, 2 + wm - cut) | safe_shr(low, cut)
    g_a = jnp.where(cut >= 1, bit(low, cut - 1), jnp.asarray(0, cdt))
    rest_a_nz = (cut >= 2) & ((low & mask(cut - 1, cdt)) != 0)
    # case B: cut inside the regime
    c2 = cut - (2 + wm)
    body_b = safe_shr(regime_val, c2)
    g_b = jnp.where(c2 >= 1, bit(regime_val, c2 - 1), jnp.asarray(0, cdt))
    rest_b_nz = ((regime_val & mask(c2 - 1, cdt)) != 0) | (low != 0)
    in_a = cut <= 2 + wm
    body = jnp.where(in_a, body_a, body_b)
    g = jnp.where(in_a, g_a, g_b)
    # boolean select spelled as and/or: Mosaic cannot lower a select
    # whose operands are booleans (it narrows i8 back to i1)
    rest_nz = (in_a & rest_a_nz) | (~in_a & rest_b_nz) | sticky

    rd = body & mask(n - 1, cdt)         # positive-magnitude word
    ru = rd + jnp.asarray(1, cdt)
    underflow_down = rd == 0
    overflow_up = ru > mask(n - 1, cdt)  # would become the NaR pattern
    tie = (g == 1) & ~rest_nz
    round_up = underflow_down | (
        ~overflow_up & (g == 1)
        & (rest_nz | (tie & ((rd & jnp.asarray(1, cdt)) == 1)))
    )
    word = jnp.where(round_up, ru, rd)
    word = jnp.where(s == 1, (~word + jnp.asarray(1, cdt)) & mask(n, cdt), word)
    if is_zero is not None:
        word = jnp.where(jnp.asarray(is_zero), jnp.asarray(0, cdt), word)
    if is_nar is not None:
        word = jnp.where(jnp.asarray(is_nar),
                         safe_shl(jnp.asarray(1, cdt), n - 1), word)
    return word.astype(word_dtype(n))


# ---------------------------------------------------------------------------
# float <-> posit
# ---------------------------------------------------------------------------


def _unbar(dec: PositDecoded, n: int):
    """(mf, me): magnitude fields of a 2C decode, S=1 un-barred.

    magnitude = (1 + mf/2^wf) * 2^me — the inverse of the representation
    (8) fraction negation (two's complement + exponent borrow), identical
    in shape to ``takum._unbar``."""
    wf = frac_width(n)
    s, e, f = dec.s, dec.e, dec.frac
    f_nz = f != 0
    mf = jnp.where((s == 1) & f_nz,
                   safe_shl(jnp.asarray(1, f.dtype), wf) - f, f)
    me = e + ((s == 1) & ~f_nz)
    return mf, me


def posit_to_float(words, n: int, dtype=jnp.float32, *, variant: str = "2c"):
    """Decode n-bit posits to float — **integer-only hot path**.

    The IEEE-754 word is assembled directly: sign | biased exponent |
    fraction packed into a uint32/uint64 lane and bitcast, with explicit
    RNE mantissa narrowing, gradual underflow and overflow-to-inf (posits
    with n <= 32 are all f32 normals — |e| <= 4(n-2)+3 — but the general
    machinery is kept so n > 32 under x64 behaves like the takum path).
    ``variant`` selects the decode dataflow ("2c" FloPoCo-2C, "sm"
    FloPoCo-SM); both produce bit-identical floats, pinned against the
    retained :func:`posit_to_float_ref` ldexp oracle. For ``wf`` wider
    than the target significand the oracle's two-step rounding
    (int->float conversion, then the ``1 + f`` add) is reproduced
    exactly. Other float dtypes compute in f32 and cast.
    """
    _validate_n(n)
    dt = jnp.dtype(dtype)
    if dt not in _IEEE:
        return posit_to_float(words, n, dtype=jnp.float32,
                              variant=variant).astype(dtype)
    if dt == jnp.dtype(jnp.float64) and not bitops.x64_enabled():
        # jax silently degrades f64 arrays to f32 without x64: match that.
        return posit_to_float(words, n, dtype=jnp.float32, variant=variant)
    fb, ebias, ew, nan_bits = _IEEE[dt]

    if variant == "2c":
        dec = decode_2c(words, n)
        mf, me = _unbar(dec, n)
    else:
        dec = decode_sm(words, n)
        mf, me = dec.frac, dec.e  # rep (7) is already magnitude form
    wf = frac_width(n)
    adt = jnp.uint64 if (fb == 52 or n > 32) else jnp.uint32
    mf = mf.astype(adt)

    # --- significand: mf (wf fraction bits) -> fb fraction bits, RNE ------
    sb = fb + 1
    if wf > sb:
        # emulate the oracle's int->float conversion: values wider than the
        # significand are rounded to sb significant bits first
        t = bitops.floor_log2(bitops.clamp(mf, 1))
        sh1 = jnp.maximum(t - fb, 0)
        mf = jnp.where(sh1 > 0, safe_shl(_rne_shr(mf, sh1), sh1), mf)
    if wf > fb:
        frac = _rne_shr(mf, jnp.asarray(wf - fb, jnp.int32))
    else:
        frac = safe_shl(mf, fb - wf)
    carry = (frac >> jnp.asarray(fb, adt)).astype(jnp.int32)  # 1 + f == 2.0
    frac = frac & mask(fb, adt)

    # --- exponent / assembly ---------------------------------------------
    be = me + (ebias + carry)             # biased exponent, int32
    sign = safe_shl(jnp.asarray(dec.s, adt), fb + ew)
    emax = 2 * ebias + 1                  # all-ones exponent field
    normal = sign | safe_shl(jnp.clip(be, 0, emax).astype(adt), fb) | frac
    inf = sign | safe_shl(jnp.asarray(emax, adt), fb)
    # gradual underflow: shift the full significand onto the subnormal grid
    sig = safe_shl(jnp.asarray(1, adt), fb) | frac
    sub = sign | _rne_shr(sig, (1 - be).astype(jnp.int32))
    word = jnp.where(be >= emax, inf, jnp.where(be <= 0, sub, normal))
    word = jnp.where(dec.is_zero, jnp.asarray(0, adt), word)
    word = jnp.where(dec.is_nar, jnp.asarray(nan_bits, adt), word)
    if fb == 23 and word.dtype != jnp.uint32:
        word = word.astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(word, dt)


def posit_to_float_ref(words, n: int, dtype=jnp.float32, *,
                       variant: str = "2c"):
    """Reference ldexp/divide reconstruction — the pre-integer-path
    implementation, retained as the oracle for the bit-exactness tests
    (tests/test_posit_int_reconstruct.py)."""
    dec = decode_2c(words, n) if variant == "2c" else decode_sm(words, n)
    wf = frac_width(n)
    if variant == "2c":
        mf, me = _unbar(dec, n)
    else:
        mf, me = dec.frac, dec.e
    mant = 1.0 + mf.astype(dtype) / jnp.asarray(1 << wf, dtype)
    out = jnp.where(dec.s == 1, -jnp.ldexp(mant, me), jnp.ldexp(mant, me))
    out = jnp.where(dec.is_zero, jnp.asarray(0, dtype), out)
    out = jnp.where(dec.is_nar, jnp.asarray(jnp.nan, dtype), out)
    return out.astype(dtype)


def float_to_posit(x, n: int):
    """Round float32 to n-bit posits (RNE, saturating; NaN -> NaR)."""
    x = jnp.asarray(x, jnp.float32)
    bits = x.view(jnp.uint32)
    s = (bits >> 31).astype(jnp.int32)
    exp_f = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    fr = bits & jnp.uint32(0x7FFFFF)
    is_zero = (exp_f == 0) & (fr == 0)
    is_nan = (exp_f == 255) & (fr != 0)
    is_inf = (exp_f == 255) & (fr == 0)
    b = bitops.floor_log2(bitops.clamp(fr, 1))
    sub = exp_f == 0
    E = jnp.where(sub, b - 149, exp_f - 127)
    mant23 = jnp.where(sub, safe_shl(fr, 23 - b) & jnp.uint32(0x7FFFFF), fr)
    # to representation (8)
    neg_borrow = (s == 1) & (mant23 == 0)
    e = jnp.where(neg_borrow, E - 1, E)
    f_field = jnp.where((s == 1) & (mant23 != 0),
                        (jnp.uint32(1 << 23) - mant23) & jnp.uint32(0x7FFFFF),
                        mant23)
    e = jnp.where(is_inf, jnp.int32(100_000), e)
    e = jnp.where(is_nan | is_zero, jnp.int32(0), e)
    return encode(s, e, f_field.astype(compute_dtype(n)), n, wm=23,
                  is_zero=is_zero, is_nar=is_nan)
