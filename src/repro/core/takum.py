"""Vectorized takum codec in JAX — the paper's core contribution.

Two decode/encode dataflows are provided:

* the **direct path** (default): computes the characteristic / precursor
  arithmetically. This is the production path — on a vector unit (TPU VPU)
  the compare-chain + integer arithmetic form is the natural lowering of
  the paper's gate-level tricks.
* the **hardware-faithful path** (``hw_path=True``): reproduces the VHDL
  dataflow bit for bit — conditional characteristic negation (Cor. 1),
  bias application via ``10``-prepend + arithmetic right shift (Table I),
  increment-only normalisation, the 8-bit nibble-LUT LOD (§V-C), the
  (n+7)-bit extended takum (§V-D) and the §V-A pattern-based
  under-/overflow predictor. It exists to *validate* the paper's
  algorithms; tests assert exact equivalence with the direct path.

Conventions
-----------
* An n-bit takum word travels in the narrowest unsigned dtype that holds
  it (``word_dtype(n)``); internal computation uses >= 32-bit lanes.
* Decoded mantissa/fraction fields are returned **left-aligned at width
  ``wf = max(n, 12) - 5``** (the paper's ``2^(n-5) * m`` fixed-point
  convention, Section III), i.e. ``mant = uint(M) << r``.
* The encoder takes the *barred* (monotonic) mantissa — internal
  representations (8) and (10) — so no two's-complement negation is ever
  needed around the codec. That monotonicity is the paper's Section III
  contribution.
* Rounding is round-to-nearest, ties to even **word**, saturating:
  a finite nonzero input never rounds to the 0 or NaR words (§V-A).

Supported widths: ``6 <= n <= 32`` everywhere; ``n <= 64`` with
``jax_enable_x64``. (Definition 1 covers n >= 2; widths below 6 are only
of theoretical interest and are exercised via the golden model.)

Float conversion is **integer-only in both directions**:
``float_to_takum`` disassembles the IEEE word with shifts/masks, and
``takum_to_float`` assembles one — sign | biased exponent | fraction
packed into an unsigned lane and bitcast, with explicit RNE gradual
underflow and overflow-to-inf. No ldexp, float divide or transcendental
anywhere on the hot path; the pre-existing ldexp dataflow is retained as
``takum_to_float_ref`` and pinned bit-identical by
tests/test_int_reconstruct.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitops
from repro.core.bitops import (
    ashr,
    bit,
    compute_dtype,
    floor_log2_u8,
    lod8_lut,
    mask,
    safe_shl,
    safe_shr,
    signed_dtype,
    word_dtype,
)

__all__ = [
    "TakumDecoded",
    "decode",
    "encode",
    "decode_linear",
    "encode_linear",
    "decode_lns",
    "decode_lns_parts",
    "encode_lns",
    "takum_to_float",
    "takum_to_float_ref",
    "float_to_takum",
    "lns_takum_to_float",
    "float_to_lns_takum",
    "frac_width",
    "NAR",
]


def frac_width(n: int) -> int:
    """Width of the decoded mantissa/fraction field (= max(n,12) - 5)."""
    return max(n, 12) - 5


def NAR(n: int):
    """The NaR word for width n."""
    return word_dtype(n)(1 << (n - 1))


class TakumDecoded(NamedTuple):
    """Decoder output: common foundation (S, c|e, m) of both internal reps.

    ``val`` is the characteristic ``c`` (or the exponent ``e`` when the
    decoder was specialised with ``output_exponent=True``). ``mant`` is the
    left-aligned mantissa field of width ``frac_width(n)``.
    """

    s: jnp.ndarray        # sign bit, int32 0/1
    val: jnp.ndarray      # characteristic c or exponent e, int32
    mant: jnp.ndarray     # mantissa field, width frac_width(n), compute dtype
    is_zero: jnp.ndarray  # bool
    is_nar: jnp.ndarray   # bool


def _validate_n(n: int) -> None:
    if not (6 <= n <= 64):
        raise ValueError(f"vectorized codec supports 6 <= n <= 64, got {n}")
    if n > 32 and not bitops.x64_enabled():
        raise ValueError("n > 32 requires jax_enable_x64")


# ---------------------------------------------------------------------------
# Decoder (Section IV)
# ---------------------------------------------------------------------------


def decode(words, n: int, *, output_exponent: bool = False,
           hw_path: bool = False) -> TakumDecoded:
    """Decode n-bit takum words to (S, c|e, mant) + special flags.

    ``output_exponent`` mirrors the paper's synthesis-time parameter
    (§IV-A): it folds the exponent negation ``e = (-1)^S (c + S)`` into the
    conditional negation the decoder performs anyway, at zero extra cost
    (the negation condition becomes ``D xor S`` instead of ``D``).
    """
    _validate_n(n)
    cdt = compute_dtype(n)
    w = jnp.asarray(words).astype(cdt)
    n12 = max(n, 12)
    wf = n12 - 5
    # ghost-bit expansion (Definition 1): right-pad to >= 12 bits
    t = safe_shl(w, n12 - n) if n < 12 else w

    s = bit(t, n12 - 1).astype(jnp.int32)
    d = bit(t, n12 - 2).astype(jnp.int32)
    rbits = (safe_shr(t, n12 - 5) & jnp.asarray(7, cdt)).astype(jnp.int32)
    r = jnp.where(d == 0, 7 - rbits, rbits)

    body = t & mask(n12 - 1, cdt)
    is_special = body == 0
    is_zero = is_special & (s == 0)
    is_nar = is_special & (s == 1)

    p12 = n12 - 5 - r  # mantissa bit count at the expanded width
    uint_c = (safe_shr(t, p12) & mask(r, cdt)).astype(jnp.int32)
    mant = safe_shl(t & mask(p12, cdt), r)  # left-aligned: uint(M) << r

    if hw_path:
        c_or_e = _characteristic_hw(t, n12, s, d, r, output_exponent)
    else:
        # Definition 1 equation (2), evaluated directly.
        c = jnp.where(
            d == 0,
            -(safe_shl(jnp.int32(1), r + 1).astype(jnp.int32)) + 1 + uint_c,
            safe_shl(jnp.int32(1), r).astype(jnp.int32) - 1 + uint_c,
        )
        if output_exponent:
            c_or_e = jnp.where(s == 1, -(c + 1), c)  # e = (-1)^S (c + S)
        else:
            c_or_e = c

    return TakumDecoded(s=s, val=c_or_e.astype(jnp.int32), mant=mant,
                        is_zero=is_zero, is_nar=is_nar)


def _characteristic_hw(t, n12: int, s, d, r, output_exponent: bool):
    """Hardware-faithful characteristic/exponent determinator (§IV-A).

    Mirrors rtl/decoder/predecoder.vhd: conditional negation of the raw
    characteristic bits (Cor. 1), bias application by prepending ``10``
    and arithmetic right shift by the antiregime (Table I), increment of
    the low 8 bits, prepend ``1``, final conditional negation.
    """
    cdt = t.dtype
    # top 12 bits hold the header: S D RRR + 7 raw characteristic bits
    h12 = (safe_shr(t, n12 - 12) & mask(12, cdt)).astype(jnp.uint32)
    craw = h12 & jnp.uint32(0x7F)
    # conditional negation of the 7 raw characteristic bits when D = 1
    craw = jnp.where(d == 1, craw ^ jnp.uint32(0x7F), craw)
    # prepend '10' -> 9-bit value, arithmetic right shift by antiregime
    val9 = jnp.uint32(0b10_0000000) | craw
    antiregime = 7 - r
    v = ashr(val9, antiregime, width=9)
    # increment the low 8 bits (never overflows: see paper §IV-A), prepend 1
    inc8 = (v + jnp.uint32(1)) & jnp.uint32(0xFF)
    c_tilde = jnp.uint32(0x100) | inc8
    # final conditional negation; condition is D, or D xor S when the
    # decoder is specialised to emit the exponent (output_exponent).
    cond = (d ^ s) if output_exponent else d
    c9 = jnp.where(cond == 1, c_tilde ^ jnp.uint32(0x1FF), c_tilde)
    # sign-extend 9-bit two's complement to int32
    return (c9.astype(jnp.int32) << 23) >> 23


# ---------------------------------------------------------------------------
# Encoder (Section V)
# ---------------------------------------------------------------------------


def encode(s, c, mant, n: int, *, wm: int, sticky=None,
           is_zero=None, is_nar=None, hw_path: bool = False,
           rounding: str = "rne", rng_bits=None):
    """Encode (S, c, mant[, sticky]) into rounded n-bit takum words.

    Parameters
    ----------
    s : 0/1 sign
    c : int32 characteristic. Out-of-range characteristics saturate to the
        largest/smallest-magnitude takum (never to 0/NaR), implementing the
        sticky-arithmetic semantics of §V-A.
    mant : the *barred* mantissa/fraction field (monotonic form of the
        internal representations (8)/(10)), width ``wm`` bits, unsigned.
    wm : static mantissa input width. Bits below the final cut position
        participate in round-to-nearest-even; ``sticky`` ORs in anything
        discarded even earlier by the caller.
    hw_path : use the §V-B..E dataflow (characteristic precursor via
        Prop. 2 with the nibble-LUT LOD, the (n+7)-bit extended takum,
        pattern-based under/overflow prediction). Requires ``wm == n - 5``
        and ``n >= 12``; semantically identical to the direct path.
    rounding : 'rne' (paper §V-E) or 'sr' (stochastic rounding — a
        beyond-paper extension used by gradient compression; rounds up
        with probability discarded/ulp, still saturating). 'sr' requires
        ``rng_bits`` (uniform random uint lanes) and n >= 12.
    """
    if rounding not in ("rne", "sr"):
        raise ValueError(f"unknown rounding {rounding!r}")
    if rounding == "sr":
        if hw_path:
            raise ValueError("sr rounding is only on the direct path")
        if n < 12:
            raise ValueError("sr rounding requires n >= 12")
        if rng_bits is None:
            raise ValueError("sr rounding requires rng_bits")
    _validate_n(n)
    cdt = compute_dtype(n)
    lane = jnp.iinfo(cdt).bits
    if wm < 1 or wm > lane - 5:
        raise ValueError(f"wm={wm} out of range for lane width {lane}")
    s = jnp.asarray(s).astype(jnp.int32)
    c = jnp.asarray(c).astype(jnp.int32)
    mant = jnp.asarray(mant).astype(cdt)
    sticky = (jnp.zeros(jnp.shape(c), bool) if sticky is None
              else jnp.asarray(sticky).astype(bool))

    # --- saturate out-of-range characteristics through the rounder -------
    over = c > 254
    under = c < -255
    c = jnp.clip(c, -255, 254)
    mant = jnp.where(over, mask(wm, cdt), jnp.where(under, jnp.asarray(0, cdt), mant))
    sticky = sticky | over | under

    # --- direction bit and characteristic precursor (Prop. 2) ------------
    d = (c >= 0).astype(jnp.int32)
    # (D==0 ? not c : c) + 1  ==  2^r + (C bits, inverted iff D==0)
    cp = (jnp.where(d == 1, c, ~c) + 1).astype(jnp.uint32)  # in [1, 255]
    if hw_path:
        r = lod8_lut(cp)
    else:
        r = floor_log2_u8(cp)
    r3 = jnp.where(d == 1, r, 7 - r)
    cbits = (jnp.where(d == 1, cp, ~cp).astype(cdt)) & mask(r, cdt)

    p = n - 5 - r  # mantissa bits that fit (may be negative for n < 12)

    if hw_path:
        if wm != n - 5 or n < 12:
            raise ValueError("hw_path encoder requires wm == n-5 and n >= 12")
        return _encode_hw(s, d, r, r3, cbits, mant, sticky, n, cdt,
                          is_zero=is_zero, is_nar=is_nar)

    # --- direct path: build round-down candidate + rounding bits ---------
    header = (
        safe_shl(s.astype(cdt), n - 1)
        | safe_shl(d.astype(cdt), n - 2)
        | safe_shl(r3.astype(cdt), n - 5)
    )
    cut = wm - p  # lane-varying; in [wm - (n-5), wm + 7 - ... ]
    # case A: cut <= wm (cut inside / below the mantissa; p >= 0)
    m_top_a = jnp.where(cut >= 0, safe_shr(mant, cut), safe_shl(mant, -cut))
    body_a = safe_shl(cbits, p) | m_top_a
    g_a = jnp.where(cut >= 1, bit(mant, cut - 1), jnp.asarray(0, cdt))
    rest_a = jnp.where(cut >= 2, mant & mask(cut - 1, cdt), jnp.asarray(0, cdt))
    # case B: p < 0 (n < 12): the cut lands inside the characteristic bits
    cut_c = -p
    body_b = safe_shr(cbits, cut_c)
    g_b = jnp.where(cut_c >= 1, bit(cbits, cut_c - 1), jnp.asarray(0, cdt))
    rest_b_nz = (cbits & mask(cut_c - 1, cdt)) != 0
    in_a = p >= 0
    body = jnp.where(in_a, body_a, body_b)
    g = jnp.where(in_a, g_a, g_b)
    # boolean select spelled as and/or: Mosaic cannot lower a select
    # whose operands are booleans (it narrows i8 back to i1)
    rest_nz = ((in_a & (rest_a != 0))
               | (~in_a & (rest_b_nz | (mant != 0))) | sticky)

    rd = header | body
    ru = (rd + jnp.asarray(1, cdt)) & mask(n, cdt)

    if rounding == "sr":
        # stochastic: round up with probability discarded/2^cut, via the
        # carry-out of (discarded + uniform). n >= 12 => always case A.
        discarded = jnp.where(cut >= 1, mant & mask(cut, cdt),
                              jnp.asarray(0, cdt))
        u = jnp.asarray(rng_bits).astype(cdt) & mask(cut, cdt)
        carry = safe_shr(discarded + u, cut) != 0
        carry = carry & (cut >= 1)
        low = mask(n - 1, cdt)
        underflow_down = (rd & low) == 0
        overflow_up = (ru & low) == 0
        round_up = underflow_down | (~overflow_up & carry)
        word = jnp.where(round_up, ru, rd)
        if is_zero is not None:
            word = jnp.where(jnp.asarray(is_zero), jnp.asarray(0, cdt), word)
        if is_nar is not None:
            word = jnp.where(jnp.asarray(is_nar),
                             safe_shl(jnp.asarray(1, cdt), n - 1), word)
        return word.astype(word_dtype(n))

    word = _round_and_specialise(rd, ru, g, rest_nz, s, n, cdt,
                                 is_zero=is_zero, is_nar=is_nar)
    return word.astype(word_dtype(n))


def _round_and_specialise(rd, ru, g, rest_nz, s, n, cdt, *, is_zero, is_nar):
    """§V-E rounder + §V-A saturation + special-case injection."""
    low = mask(n - 1, cdt)
    underflow_down = (rd & low) == 0   # RD would be the 0/NaR pattern
    overflow_up = (ru & low) == 0      # RU would wrap onto the 0/NaR pattern
    tie = (g == 1) & ~rest_nz
    round_up = underflow_down | (
        ~overflow_up
        & (g == 1)
        & (rest_nz | (tie & ((rd & jnp.asarray(1, cdt)) == 1)))
    )
    word = jnp.where(round_up, ru, rd)
    if is_zero is not None:
        word = jnp.where(jnp.asarray(is_zero), jnp.asarray(0, cdt), word)
    if is_nar is not None:
        word = jnp.where(jnp.asarray(is_nar),
                         safe_shl(jnp.asarray(1, cdt), n - 1), word)
    return word


def _encode_hw(s, d, r, r3, cbits, mant, sticky, n, cdt, *, is_zero, is_nar):
    """Hardware-faithful §V-D/E: (n+7)-bit extended takum, then round.

    The extended takum fully accommodates the (n-5)-bit mantissa even when
    all 7 characteristic bits are present; the shifter is bounded by a
    maximum offset of 7 — the paper's key contrast with posit encoders.
    """
    # extended takum: [S D RRR | C(r) M(n-5) 0(7-r)] -- built as
    # header << (n+2) | (C << (n+2-r)) | (M << (7-r))
    if n + 7 > jnp.iinfo(cdt).bits:
        if bitops.x64_enabled():
            cdt = jnp.uint64  # widen the lane so the (n+7)-bit ET fits
            cbits = cbits.astype(cdt)
            mant = mant.astype(cdt)
        else:
            raise ValueError("hw_path extended takum exceeds lane width; "
                             "enable x64 for n > 25")
    header = (
        safe_shl(s.astype(cdt), 4)
        | safe_shl(d.astype(cdt), 3)
        | r3.astype(cdt)
    )
    et = (
        safe_shl(header, n + 2)
        | safe_shl(cbits, (n + 2) - r)
        | safe_shl(mant, 7 - r)
    )
    rd = safe_shr(et, 7)
    ru = (rd + jnp.asarray(1, cdt)) & mask(n, cdt)
    g = bit(et, 6)
    rest_nz = ((et & mask(6, cdt)) != 0) | sticky

    # §V-A pattern predictor (n >= 12 form): under/overflow iff the 11 bits
    # after the sign (D, R, C -- regime necessarily 7) and the kept mantissa
    # bits are all zeros / all ones. Equivalent to the direct RD/RU special
    # checks; asserted equal in tests.
    eleven = (safe_shr(et, n - 5) & mask(11, cdt))
    kept_m = (safe_shr(et, 7) & mask(n - 12, cdt))
    under_pred = (eleven == 0) & (kept_m == 0)
    over_pred = (eleven == mask(11, cdt)) & (kept_m == mask(n - 12, cdt))

    tie = (g == 1) & ~rest_nz
    round_up = under_pred | (
        ~over_pred
        & (g == 1)
        & (rest_nz | (tie & ((rd & jnp.asarray(1, cdt)) == 1)))
    )
    word = jnp.where(round_up, ru, rd) & mask(n, cdt)
    if is_zero is not None:
        word = jnp.where(jnp.asarray(is_zero), jnp.asarray(0, cdt), word)
    if is_nar is not None:
        word = jnp.where(jnp.asarray(is_nar),
                         safe_shl(jnp.asarray(1, cdt), n - 1), word)
    return word.astype(word_dtype(n))


# ---------------------------------------------------------------------------
# Linear internal representation (S, e, f) -- equation (8)
# ---------------------------------------------------------------------------


def decode_linear(words, n: int, *, hw_path: bool = False) -> TakumDecoded:
    """Decode to the linear internal representation (S, e, f).

    ``val`` is the exponent e; ``mant`` is the monotonic fraction field of
    width ``frac_width(n)``. This is rtl/decoder/decoder_linear.vhd: the
    predecoder with output_exponent = 1.
    """
    return decode(words, n, output_exponent=True, hw_path=hw_path)


def encode_linear(s, e, frac, n: int, *, wm: int, sticky=None,
                  is_zero=None, is_nar=None, hw_path: bool = False,
                  rounding: str = "rne", rng_bits=None):
    """Encode from (S, e, f): c is e conditionally negated on S (§V-F)."""
    e = jnp.asarray(e).astype(jnp.int32)
    s = jnp.asarray(s).astype(jnp.int32)
    c = jnp.where(s == 1, ~e, e)
    return encode(s, c, frac, n, wm=wm, sticky=sticky,
                  is_zero=is_zero, is_nar=is_nar, hw_path=hw_path,
                  rounding=rounding, rng_bits=rng_bits)


# ---------------------------------------------------------------------------
# Logarithmic internal representation (S, ell_bar) -- equation (10)
# ---------------------------------------------------------------------------


class LnsDecoded(NamedTuple):
    s: jnp.ndarray         # sign, int32 0/1
    ell_bar: jnp.ndarray   # fixed point, signed, frac_width(n) fraction bits
    is_zero: jnp.ndarray
    is_nar: jnp.ndarray


def decode_lns(words, n: int, *, hw_path: bool = False) -> LnsDecoded:
    """Decode to (S, ell_bar): the novel barred-LNS representation.

    ell_bar = c + m is materialised by concatenating the 9-bit signed
    characteristic with the (n-5)-bit mantissa field (Section III) — a
    fixed-point number with ``frac_width(n)`` fractional bits, returned in
    a signed lane.
    """
    dec = decode(words, n, output_exponent=False, hw_path=hw_path)
    wf = frac_width(n)
    sdt = signed_dtype(jnp.iinfo(dec.mant.dtype).bits)
    ell = (dec.val.astype(sdt) << jnp.asarray(wf, sdt)) | dec.mant.astype(sdt)
    return LnsDecoded(s=dec.s, ell_bar=ell, is_zero=dec.is_zero,
                      is_nar=dec.is_nar)


def decode_lns_parts(words, n: int, *, hw_path: bool = False):
    """Tile-friendly integer LNS decode: two int32 lanes per element.

    Returns ``(ell, flags)`` where ``ell`` is the **un-barred** logarithmic
    value ``ell = (-1)^S ell_bar`` as a signed int32 fixed-point number with
    ``frac_width(n)`` fraction bits, and ``flags`` packs the special cases:
    bit 0 = S, bit 1 = is_zero, bit 2 = is_nar.

    This is the form the Pallas LNS matmul kernels keep in VMEM scratch:
    the product of two takum-LNS values is one int32 add of their ``ell``
    lanes (exact — the Section III story at tile granularity) and the sign
    is one XOR of the flag lanes. Requires ``n <= 27`` so that the 9-bit
    characteristic plus ``frac_width(n)`` fraction bits (plus one carry
    bit for a product) fit an int32 lane.
    """
    if n > 27:
        raise ValueError("decode_lns_parts needs ell + carry in int32 "
                         f"lanes: n <= 27, got {n}")
    dec = decode_lns(words, n, hw_path=hw_path)
    ell = jnp.where(dec.s == 1, -dec.ell_bar, dec.ell_bar).astype(jnp.int32)
    flags = (dec.s.astype(jnp.int32)
             | (dec.is_zero.astype(jnp.int32) << 1)
             | (dec.is_nar.astype(jnp.int32) << 2))
    return ell, flags


def encode_lns(s, ell_bar, n: int, *, wf: int, sticky=None,
               is_zero=None, is_nar=None, hw_path: bool = False):
    """Encode (S, ell_bar) where ell_bar has ``wf`` fraction bits (signed).

    The characteristic is the floor (arithmetic shift) and the mantissa the
    fractional remainder — both monotone in ell_bar, so no negation is
    needed (the Section III advantage).
    """
    ell = jnp.asarray(ell_bar)
    sdt = ell.dtype
    c = (ell >> jnp.asarray(wf, sdt)).astype(jnp.int32)
    cdt = compute_dtype(n)
    mant = (ell.astype(cdt)) & mask(wf, cdt)
    return encode(s, c, mant, n, wm=wf, sticky=sticky,
                  is_zero=is_zero, is_nar=is_nar, hw_path=hw_path)


# ---------------------------------------------------------------------------
# float <-> linear takum conversion (exact integer bit manipulation)
# ---------------------------------------------------------------------------


def float_to_takum(x, n: int, *, rounding: str = "rne", rng_bits=None):
    """Round float32 values to n-bit linear takum words (RNE, saturating).

    Pure integer manipulation of the IEEE encoding: no log/exp, and the
    fraction negation for negative inputs is the two's-complement-with-
    exponent-borrow dance that representation (8) makes monotonic.
    NaN -> NaR; +-inf saturates to the largest-magnitude takum.
    """
    _validate_n(n)
    x = jnp.asarray(x, jnp.float32)
    bits = x.view(jnp.uint32)
    s = (bits >> 31).astype(jnp.int32)
    exp_f = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    frac = bits & jnp.uint32(0x7FFFFF)

    is_zero = (exp_f == 0) & (frac == 0)
    is_nan = (exp_f == 255) & (frac != 0)
    is_inf = (exp_f == 255) & (frac == 0)

    # normalise subnormals: value = frac * 2^-149 = (1 + f') * 2^(b - 149)
    b = bitops.floor_log2(bitops.clamp(frac, 1))
    sub = exp_f == 0
    E = jnp.where(sub, b - 149, exp_f - 127)
    mant23 = jnp.where(sub, safe_shl(frac, 23 - b) & jnp.uint32(0x7FFFFF), frac)

    # negative values: (1+f)*2^E == -((f'-2)*2^e) with f' = 1-f
    # => fraction field two's-complemented, exponent borrows when f == 0
    neg_borrow = (s == 1) & (mant23 == 0)
    e = jnp.where(neg_borrow, E - 1, E)
    f_field = jnp.where(
        (s == 1) & (mant23 != 0),
        (jnp.uint32(1 << 23) - mant23) & jnp.uint32(0x7FFFFF),
        mant23,
    )
    # infinities: drive the saturation path with an out-of-range c
    e = jnp.where(is_inf, jnp.int32(10_000), e)
    e = jnp.where(is_nan | is_zero, jnp.int32(0), e)

    return encode_linear(
        s, e, f_field.astype(compute_dtype(n)), n, wm=23,
        is_zero=is_zero, is_nar=is_nan,
        rounding=rounding, rng_bits=rng_bits,
    )


def _unbar(dec: TakumDecoded, n: int):
    """(mf, me): magnitude fields of the linear decode, S=1 un-barred.

    magnitude = (1 + mf/2^wf) * 2^me, with mf the *monotonic* fraction
    field negated back for S=1 (two's complement + exponent borrow — the
    inverse of the float_to_takum dance below).
    """
    wf = frac_width(n)
    s, e, f = dec.s, dec.val, dec.mant
    f_nz = f != 0
    mf = jnp.where((s == 1) & f_nz,
                   safe_shl(jnp.asarray(1, f.dtype), wf) - f, f)
    me = e + ((s == 1) & ~f_nz)
    return mf, me


def _rne_shr(v, sh):
    """RNE(v / 2^sh) for unsigned lanes; ``sh`` lane-varying, >= 1 (any
    magnitude — shifts past the lane width collapse to sticky-only)."""
    kept = safe_shr(v, sh)
    g = bit(v, sh - 1)
    rest_nz = (v & mask(sh - 1, v.dtype)) != 0
    up = (g == jnp.asarray(1, v.dtype)) & (rest_nz | ((kept & jnp.asarray(1, v.dtype)) != 0))
    return kept + up.astype(v.dtype)


_IEEE = {  # fraction bits, exponent bias, exponent field width, NaN payload
    jnp.dtype(jnp.float32): (23, 127, 8, 0x7FC0_0000),
    jnp.dtype(jnp.float64): (52, 1023, 11, 0x7FF8_0000_0000_0000),
}


def takum_to_float(words, n: int, dtype=jnp.float32):
    """Decode n-bit linear takum words to float (value-exact where the
    target dtype permits; out-of-range magnitudes become inf/0 — float64
    under x64 covers the full takum range exactly for p <= 52).

    **Integer-only hot path**: the IEEE-754 word is assembled directly —
    sign | biased exponent | fraction packed into a uint32/uint64 lane and
    bitcast — with explicit RNE gradual underflow into the subnormal range
    and overflow saturation to inf. No ldexp, no float divide, no
    transcendental: shifts, adds, compares and one bitcast, so the decode
    kernels inherit the paper's pure-integer dataflow end to end. For
    ``wf > fraction bits`` the two-step rounding of the retained
    :func:`takum_to_float_ref` oracle (int->float conversion, then the
    ``1 + f`` add) is reproduced exactly, so both paths stay bit-identical.
    Other float dtypes (e.g. bfloat16) are computed in f32 and cast.
    """
    _validate_n(n)
    dt = jnp.dtype(dtype)
    if dt not in _IEEE:
        return takum_to_float(words, n, dtype=jnp.float32).astype(dtype)
    if dt == jnp.dtype(jnp.float64) and not bitops.x64_enabled():
        # jax silently degrades f64 arrays to f32 without x64: match that.
        return takum_to_float(words, n, dtype=jnp.float32)
    fb, ebias, ew, nan_bits = _IEEE[dt]

    dec = decode_linear(words, n)
    wf = frac_width(n)
    mf, me = _unbar(dec, n)
    # assembly lane: wide enough for both the IEEE word and the wf-bit
    # mantissa field (n > 32 decodes in uint64 lanes even for f32 output)
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


def takum8_to_float(words, dtype=jnp.float32):
    """Closed-form decode of 8-bit linear takum words to float32/bfloat16.

    Bit-identical to ``takum_to_float(words, 8, dtype)``, in a few dozen
    integer lane ops instead of the generic width's ~180: at n = 8 the
    fraction (wf = 7 bits) always fits f32's 23, so none of the generic
    RNE or int->float emulation can fire. A negative word is the two's
    complement of its magnitude, so the decode sign-extends, takes the
    absolute value ``a = D RRR TTT`` and reads it as positive. With
    ``r = R if D else 7 - R``, the r characteristic bits and 3 - r
    mantissa bits of the tail T land in the f32 word as
    ``T << (20 + r)``, above ghost zeros, and the characteristic's bias
    adds ``2^r + 126`` (D = 1) or ``128 - 2^(r+1)`` (D = 0) to the
    exponent field; so the magnitude's top 12 bits are
    ``1008 + ((T + 8) << r)`` or ``1024 + ((T - 16) << r)``. That field
    overflows to inf past 2^127; it reaches 0 or below only for a <= 8,
    whose values are 2^-127 (a = 8), 2^-143 (a = 7) and flushed to zero
    (a < 7, zero itself at a = 0). NaR (a = 128) gives f32's NaN.
    Integer-only: shifts, adds, compares and one bitcast.
    """
    dt = jnp.dtype(dtype)
    if dt != jnp.dtype(jnp.float32):
        return takum8_to_float(words, jnp.float32).astype(dtype)
    hi = jnp.asarray(words).astype(jnp.int32) << 24   # word's sign at bit 31
    a = jnp.abs(hi >> 24)                     # magnitude word, 0..128
    d0 = a < 64                               # D = 0
    reg = (a >> 3) & 7
    r = jnp.where(d0, 7 - reg, reg)
    base = (a & 7) + jnp.where(d0, -16, 8)
    top = (base << r) + jnp.where(d0, 1024, 1008)
    mag = jnp.minimum(top, 0x7F8) << 20       # exponent | fraction, inf cap
    tiny = jnp.where(a == 8, 1 << 22, jnp.where(a == 7, 1 << 6, 0))
    mag = jnp.where(a <= 8, tiny, mag)
    bits = mag | (hi & jnp.int32(-1 << 31))
    bits = jnp.where(a == 128, 0x7FC0_0000, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def takum_to_float_ref(words, n: int, dtype=jnp.float32):
    """Reference ldexp/divide reconstruction — the pre-integer-path
    implementation, retained as the oracle for the bit-exactness tests.

    The single ``ldexp`` of the original is split in two so subnormal
    magnitudes scale through a *normal* intermediate (one exact multiply,
    then one correctly-rounded one); on backends that keep gradual
    underflow this makes the oracle value-correct over the whole takum
    range. Note XLA:CPU flushes subnormal *runtime multiply results* to
    zero, so in the subnormal band the bit-level ground truth for tests is
    this same dataflow evaluated in numpy (see tests/test_int_reconstruct).
    """
    _validate_n(n)
    dec = decode_linear(words, n)
    wf = frac_width(n)
    mf, me = _unbar(dec, n)
    mant = 1.0 + mf.astype(dtype) / jnp.asarray(1 << wf, dtype)
    fi = jnp.finfo(dtype)
    e1 = jnp.clip(me, fi.minexp, fi.maxexp)
    mag = jnp.ldexp(jnp.ldexp(mant, e1), me - e1)
    out = jnp.where(dec.s == 1, -mag, mag)
    out = jnp.where(dec.is_zero, jnp.asarray(0, dtype), out)
    out = jnp.where(dec.is_nar, jnp.asarray(jnp.nan, dtype), out)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# float <-> logarithmic takum conversion (transcendental; for LNS apps)
# ---------------------------------------------------------------------------


def lns_takum_to_float(words, n: int, dtype=jnp.float32):
    """tau(T) = (-1)^S * sqrt(e)^((-1)^S * ell_bar) (Definition 1 + (10))."""
    dec = decode_lns(words, n)
    wf = frac_width(n)
    ell_bar = dec.ell_bar.astype(dtype) / jnp.asarray(1 << wf, dtype)
    ell = jnp.where(dec.s == 1, -ell_bar, ell_bar)
    mag = jnp.exp(ell * jnp.asarray(0.5, dtype))
    out = jnp.where(dec.s == 1, -mag, mag)
    out = jnp.where(dec.is_zero, jnp.asarray(0, dtype), out)
    out = jnp.where(dec.is_nar, jnp.asarray(jnp.nan, dtype), out)
    return out.astype(dtype)


def float_to_lns_takum(x, n: int, *, wf_fixed: int = 22):
    """Encode floats as logarithmic takums: ell = 2 ln|x|, RNE in ell_bar
    space (the format's native rounding domain).

    ``wf_fixed`` <= 22 keeps |ell_bar| * 2^wf within int32 (|ell_bar| < 256).
    """
    if wf_fixed > 22:
        raise ValueError("wf_fixed > 22 overflows the int32 ell_bar lane")
    x = jnp.asarray(x, jnp.float32)
    s = (x < 0).astype(jnp.int32)
    is_zero = x == 0
    is_nan = jnp.isnan(x)
    ell = 2.0 * jnp.log(jnp.abs(jnp.where(is_zero | is_nan, 1.0, x)))
    ell_bar = jnp.clip(jnp.where(s == 1, -ell, ell), -256.0, 256.0)
    ell_fixed = jnp.round(ell_bar * (1 << wf_fixed)).astype(signed_dtype(32))
    return encode_lns(s, ell_fixed, n, wf=wf_fixed,
                      is_zero=is_zero, is_nar=is_nan)
