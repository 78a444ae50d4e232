"""The takum8 word format, from its definition, for the weights and the
reference (nothing here comes from the program under test)."""

from __future__ import annotations

import math


def value(w: int) -> float:
    """Linear takum, 8 bits: sign, direction D, regime R (3 bits),
    r = R if D else 7 - R characteristic bits C, then 3 - r mantissa
    bits M; c = 2^r - 1 + C (D = 1) or -2^(r+1) + 1 + C (D = 0); value
    (1 + M / 2^p) * 2^c; negative words are the two's complement of
    their magnitude; 0 is zero and 0x80 is NaR."""
    w &= 0xFF
    if w == 0:
        return 0.0
    if w == 0x80:
        return math.nan
    if w & 0x80:
        return -value(-w)
    d = (w >> 6) & 1
    reg = (w >> 3) & 7
    r = reg if d else 7 - reg
    tail = w & 7
    if r <= 3:
        p = 3 - r
        c_bits, m = tail >> p, tail & ((1 << p) - 1)
    else:
        p, c_bits, m = 0, tail << (r - 3), 0
    c = (2 ** r - 1 + c_bits) if d else (-2 ** (r + 1) + 1 + c_bits)
    return (1 + m / 2 ** p) * 2.0 ** c
