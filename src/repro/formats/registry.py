"""The codec registry: one ``FormatSpec`` datapath for every wire format.

The paper's central observation is that takums and posits *share* their
internal representations — the codec is the differentiating layer. This
module is that observation in software: a wire format is a value
(:class:`FormatSpec`) bundling its identity (``name``, ``n``, ``kind``),
its tile-level ``decode_tile``/``encode_tile`` (pure jnp → traceable
inside Pallas kernel bodies), its LNS-parts decode where the ℓ̄ datapath
applies, its NaR/zero semantics and its wire bytes-per-element. Every
kernel, op, serving and config consumer resolves a spec **once at its
boundary** (``resolve`` accepts specs, registry names like ``"takum8"``
/ ``"posit16"``, and the legacy ``(kind, n)`` string pairs) and then
dispatches on spec *attributes* — no ``if fmt == "lns"`` branches
anywhere outside this module.

Registered formats
------------------
* ``takum8`` / ``takum16`` — linear takum (eq. (8)): integer-only IEEE
  reconstruction on decode, pure bit-disassembly on encode.
* ``lns-takum8`` / ``lns-takum16`` — logarithmic takum (eq. (10)):
  decode pays one ``exp``; ``lns_parts`` exposes the ``(ell, flags)``
  int32 lanes the ℓ̄-datapath matmul kernels consume.
* ``posit8`` / ``posit16`` — the paper's comparison baseline,
  Posit™ Standard 2022 ``es = 2``, FloPoCo-2C dataflow (direct
  two's-complement decode, representation (8) of ``core/posit.py``).
* ``none`` — the **identity codec**: a float cache/tensor riding the
  same kernels with a cast for decode and a pass-through encode.
  Bytes-per-element is that of the stored dtype, which makes it the one
  source of truth for cache-memory math (``docs/serving.md``).

Other widths (``"takum12"``, ``"posit32"``, ``"lns-takum24"`` …)
resolve on demand through the same constructor and are interned, so
``resolve`` always returns the same object for the same format — specs
are hashable and usable as jit static arguments and pytree aux data.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitops

__all__ = ["FormatSpec", "register", "get", "resolve", "resolve_wire",
           "resolve_lns", "all_formats", "wire_formats", "names",
           "wire_names", "IDENTITY", "lut_enabled"]


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    """A wire number format: identity + codec behaviour, as a value.

    The callables are module-level functions of ``core`` (hashable by
    identity), taking the width ``n`` explicitly, so a spec is a frozen,
    hashable bundle — safe as a jit static argument, a ``custom_vjp``
    non-diff argument, and pytree aux data (``WireMatrix``).

    ``decode_tile``/``encode_tile`` are the tile-granularity codec: pure
    jnp integer dataflow (one ``exp`` for the LNS kind), traceable
    inside Pallas kernel bodies. They are *also* the float oracle — the
    jnp fallback paths in ``kernels/ref.py`` call the same functions, so
    kernel and oracle stay bit-identical by construction.
    """

    name: str                 # registry key, e.g. "takum16", "posit8"
    kind: str                 # "linear" | "lns" | "posit" | "none"
    n: int                    # wire word width in bits (0 = identity)
    _decode: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    _encode: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    _lns_parts: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    _fake_quant: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    _lut: Optional[Callable] = dataclasses.field(
        default=None, repr=False)

    # -- identity ----------------------------------------------------------

    @property
    def is_identity(self) -> bool:
        """True for the ``none`` codec (float tensors, cast-only)."""
        return self.kind == "none"

    @property
    def word_dtype(self):
        """Wire storage dtype (``None`` for the identity codec, whose
        storage dtype is whatever float dtype the caller keeps)."""
        return None if self.is_identity else bitops.word_dtype(self.n)

    def bytes_per_elem(self, dtype=jnp.float32) -> int:
        """Stored bytes per element — the identity codec stores ``dtype``,
        wire codecs their ``word_dtype`` (= n/8 for the byte-multiple
        widths; non-byte widths like takum12 pad to the word dtype, and
        this reports what a cache actually allocates)."""
        if self.is_identity:
            return jnp.dtype(dtype).itemsize
        return jnp.dtype(self.word_dtype).itemsize

    @property
    def nar_word(self) -> Optional[int]:
        """The NaR bit pattern (``None`` for the identity codec: floats
        carry NaN natively)."""
        return None if self.is_identity else 1 << (self.n - 1)

    @property
    def zero_word(self) -> int:
        """The zero word — also the padding word the kernel layer relies
        on, because it decodes to exactly 0.0 in every format."""
        return 0

    # -- codec -------------------------------------------------------------

    @property
    def has_lut(self) -> bool:
        """Whether a table-lookup decode exists for this format (only the
        8-bit formats can — 256 entries fit a VMEM tile)."""
        return self._lut is not None

    @property
    def lut_decode(self) -> bool:
        """Whether :meth:`decode_tile` will take the LUT path *right now*
        — the hook exists and the environment enables it (see
        :func:`lut_enabled`). The registry, not the kernels, decides:
        every tile body reaches the table through the same
        ``decode_tile`` indirection with zero per-kernel branching."""
        return self._lut is not None and lut_enabled()

    def decode_tile(self, words, dtype=jnp.float32):
        """Wire words -> float, traceable inside a Pallas tile body.

        NaR decodes to NaN, the zero word to 0.0. For the identity codec
        this is a cast (so the uncompressed cache rides the same fused
        kernels). Formats with an enabled LUT hook (``lut_decode``)
        decode by table lookup instead of the computed dataflow —
        bit-identical by construction (the table is built by the
        computed decode at trace time)."""
        if self.is_identity:
            return jnp.asarray(words).astype(dtype)
        if self._lut is not None and lut_enabled():
            return self._lut(words, self.n, dtype=dtype)
        return self._decode(words, self.n, dtype=dtype)

    def encode_tile(self, x):
        """float32 -> wire words (RNE, saturating: finite nonzero values
        never round onto the 0/NaR patterns). NaN -> NaR. The identity
        codec passes the input through unchanged."""
        if self.is_identity:
            return jnp.asarray(x)
        return self._encode(jnp.asarray(x, jnp.float32), self.n)

    # note: decode_tile/encode_tile double as the float oracle — the jnp
    # fallback paths (kernels/ref.py) call the same functions the
    # kernels trace, which is what keeps kernel and oracle bit-identical
    # by construction.

    @property
    def has_lns_parts(self) -> bool:
        """Whether the format exposes the ℓ̄-datapath ``(ell, flags)``
        lanes (the LNS matmul kernels require it)."""
        return self._lns_parts is not None

    def lns_parts(self, words):
        """LNS decode to ``(ell, flags)`` int32 lanes (see
        ``takum.decode_lns_parts``); only for ``has_lns_parts`` specs."""
        if self._lns_parts is None:
            raise ValueError(
                f"format {self.name!r} has no LNS ℓ̄ datapath "
                "(only the lns-takum formats do)")
        return self._lns_parts(words, self.n)

    def fake_quant(self, x, dtype=jnp.float32):
        """Quantise-dequantise through this format's grid.

        Linear takum applies the power-of-two centring scale of
        ``core.quant`` (precision peaks at |x| ~ 1); the other wire
        formats round-trip unscaled — their dynamic range needs no scale
        side-channel. Identity is, well, the identity."""
        if self.is_identity:
            return jnp.asarray(x).astype(dtype)
        if self._fake_quant is not None:
            return self._fake_quant(x, self.n, dtype)
        return self.decode_tile(self.encode_tile(x), dtype=dtype)


# ---------------------------------------------------------------------------
# LUT decode gating
# ---------------------------------------------------------------------------


def lut_enabled() -> bool:
    """Whether LUT decode hooks are active for this process.

    Off unless ``REPRO_LUT_DECODE=1``. XLA:CPU lowers the gather
    serially and loses to the computed integer decode (~20x at 2M
    elements); on a TPU v5e posit8's table beats its computed decode
    1.9x in the codec-only kernel (docs/formats.md), which no serving
    workload has judged yet. Read per call (trace-time only), so tests
    can flip the env var without cache invalidation games.
    """
    return os.environ.get("REPRO_LUT_DECODE", "") == "1"


# ---------------------------------------------------------------------------
# Builtin codec hooks (module-level so specs hash/compare by identity)
# ---------------------------------------------------------------------------


# targets of takum8's closed-form decode (bit-identical to the generic
# one; a bfloat16 result is the float32 one cast, as there)
_TAKUM8_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _takum_decode(words, n, dtype=jnp.float32):
    from repro.core import takum
    if n == 8 and jnp.dtype(dtype) in _TAKUM8_DTYPES:
        return takum.takum8_to_float(words, dtype=dtype)
    return takum.takum_to_float(words, n, dtype=dtype)


def _takum_encode(x, n):
    from repro.core import takum
    return takum.float_to_takum(x, n)


def _takum_scaled_fake_quant(x, n, dtype):
    # the serving fake-quant path for linear takum: per-tensor
    # power-of-two centring (exact ldexp scales) around |x| ~ 1
    from repro.core import quant as q
    spec = q.QuantSpec(fmt="takum", n=n, scale="per_tensor")
    return q.dequantize(q.quantize(x, spec)).astype(dtype)


def _lns_decode(words, n, dtype=jnp.float32):
    from repro.core import takum
    return takum.lns_takum_to_float(words, n, dtype=dtype)


def _lns_encode(x, n):
    from repro.core import takum
    return takum.float_to_lns_takum(x, n)


def _lns_parts(words, n):
    from repro.core import takum
    return takum.decode_lns_parts(words, n)


def _posit_decode(words, n, dtype=jnp.float32):
    from repro.core import posit
    return posit.posit_to_float(words, n, dtype=dtype, variant="2c")


def _posit_encode(x, n):
    from repro.core import posit
    return posit.float_to_posit(x, n)


def _posit8_lut_decode(words, n, dtype=jnp.float32):
    """256-entry table decode for posit8.

    Pallas kernel bodies cannot capture array constants, so the table is
    built *inside* the traced body from a 2D iota (TPU requires >= 2D
    iotas) and the computed integer decode — at trace time this folds to
    a constant ``(2, 128)`` tile. Mosaic gathers only along one axis of
    a 2D array of the index's shape, so each word looks up its low seven
    bits in both 128-lane halves of the table (``take_along_axis`` over
    ``[R, 128]`` rows) and its top bit selects the half. Bit-identical to
    the computed path by construction.
    """
    assert n == 8
    from repro.core import posit
    idx = (jax.lax.broadcasted_iota(jnp.int32, (2, 128), 0) * 128
           + jax.lax.broadcasted_iota(jnp.int32, (2, 128), 1))
    tab = posit.posit_to_float(idx.astype(jnp.uint8), 8, dtype=dtype)
    w = jnp.asarray(words).astype(jnp.int32)
    shape, size = w.shape, w.size
    if w.ndim != 2 or shape[1] != 128:  # kernel tiles are already [R, 128]
        w = jnp.pad(w.reshape(-1), (0, -size % 128)).reshape(-1, 128)
    rows = w.shape[0]
    low = w & 127

    def half(h):
        return jnp.take_along_axis(
            jnp.broadcast_to(tab[h:h + 1], (rows, 128)), low, axis=1,
            mode="promise_in_bounds")

    out = jnp.where(w >= 128, half(1), half(0))
    if out.shape != shape:
        out = out.reshape(-1)[:size].reshape(shape)
    return out


_KIND_HOOKS = {
    "linear": dict(_decode=_takum_decode, _encode=_takum_encode,
                   _fake_quant=_takum_scaled_fake_quant),
    "lns": dict(_decode=_lns_decode, _encode=_lns_encode,
                _lns_parts=_lns_parts),
    "posit": dict(_decode=_posit_decode, _encode=_posit_encode),
}

_KIND_NAME = {"linear": "takum{n}", "lns": "lns-takum{n}",
              "posit": "posit{n}"}


@functools.lru_cache(maxsize=None)
def _make(kind: str, n: int) -> FormatSpec:
    """Intern constructor: the same (kind, n) always yields the same
    object, so jit caches and pytree treedefs compare cheaply."""
    if kind == "none":
        return FormatSpec(name="none", kind="none", n=0)
    if kind not in _KIND_HOOKS:
        raise ValueError(f"unknown format kind {kind!r} "
                         f"(known: {sorted(_KIND_HOOKS)} + 'none')")
    if not isinstance(n, int) or n < 2:
        raise ValueError(f"format kind {kind!r} needs a word width n, "
                         f"got {n!r}")
    hooks = dict(_KIND_HOOKS[kind])
    # LUT tile codec: only posit8 carries one. takum8 needs no table: on
    # one TPU v5e its closed-form decode runs the codec-only kernel at
    # 32.1 G words/s, against 16.1 for the generic-width decode and, for
    # posit8, 15.1 computed and 28.6 from the table (docs/formats.md).
    # The table stays behind REPRO_LUT_DECODE until a posit8 workload
    # judges it end to end.
    if kind == "posit" and n == 8:
        hooks["_lut"] = _posit8_lut_decode
    return FormatSpec(name=_KIND_NAME[kind].format(n=n), kind=kind, n=n,
                      **hooks)


# ---------------------------------------------------------------------------
# Registry + resolution
# ---------------------------------------------------------------------------


_REGISTRY: Dict[str, FormatSpec] = {}


def register(spec: FormatSpec) -> FormatSpec:
    """Register a spec under its name (idempotent for equal specs)."""
    prev = _REGISTRY.get(spec.name)
    if prev is not None and prev != spec:
        raise ValueError(f"format {spec.name!r} already registered "
                         "with different behaviour")
    _REGISTRY[spec.name] = spec
    return spec


IDENTITY = register(_make("none", 0))
for _n in (8, 16):
    register(_make("linear", _n))
    register(_make("lns", _n))
    register(_make("posit", _n))
del _n


def names() -> Tuple[str, ...]:
    """All registered format names (identity first, then by name)."""
    wire = sorted(k for k in _REGISTRY if k != "none")
    return ("none", *wire)


def wire_names() -> Tuple[str, ...]:
    """Registered non-identity (wire) format names."""
    return tuple(k for k in names() if k != "none")


def get(name: str) -> FormatSpec:
    if name not in _REGISTRY:
        raise ValueError(f"unknown format {name!r} "
                         f"(registered: {', '.join(names())})")
    return _REGISTRY[name]


def all_formats() -> Tuple[FormatSpec, ...]:
    """Every registered spec, the identity codec included."""
    return tuple(_REGISTRY[k] for k in names())


def wire_formats() -> Tuple[FormatSpec, ...]:
    """Every registered wire (non-identity) spec — what the
    registry-parametrised property tests sweep."""
    return tuple(_REGISTRY[k] for k in wire_names())


_NAME_RE = re.compile(r"(lns-)?takum(\d+)$|posit(\d+)$")


def resolve(fmt, n: Optional[int] = None) -> FormatSpec:
    """Resolve anything format-shaped to its ``FormatSpec``.

    Accepts, in order of preference:

    * a ``FormatSpec`` (returned as-is — the already-resolved case);
    * a registry / constructor name: ``"none"``, ``"takum8"``,
      ``"lns-takum16"``, ``"posit8"``, … (unregistered widths are
      constructed and interned on demand);
    * a legacy ``(kind, n)`` pair: ``resolve("linear", 8)``,
      ``resolve("lns", 16)``, ``resolve("posit", 8)`` — the string
      dispatch the kernel layer used to hard-code;
    * a bare int width (linear takum — the original ``n``-only API).

    When ``fmt`` carries its own width (a spec or a name) *and* a
    nonzero ``n`` is passed alongside, the two must agree — a mismatch
    would silently decode words at the wrong width, so it raises.
    """
    spec = _resolve_fmt(fmt, n)
    if n and spec.n and int(n) != spec.n:
        raise ValueError(
            f"width mismatch: resolved format {spec.name!r} (n={spec.n}) "
            f"but n={n} was passed alongside")
    return spec


def _resolve_fmt(fmt, n) -> FormatSpec:
    if isinstance(fmt, FormatSpec):
        return fmt
    if isinstance(fmt, int) and not isinstance(fmt, bool):
        return _make("linear", fmt)
    if not isinstance(fmt, str):
        raise ValueError(f"cannot resolve a format from {fmt!r}")
    if fmt == "none":
        return IDENTITY
    if fmt in _REGISTRY:
        return _REGISTRY[fmt]
    if fmt in _KIND_HOOKS:  # legacy (kind, n) pair
        if not n:
            raise ValueError(f"format kind {fmt!r} needs a word width n")
        return _make(fmt, int(n))
    m = _NAME_RE.fullmatch(fmt)
    if m is not None:  # constructor name at an unregistered width
        if m.group(3) is not None:
            return _make("posit", int(m.group(3)))
        return _make("lns" if m.group(1) else "linear", int(m.group(2)))
    raise ValueError(f"unknown format {fmt!r} "
                     f"(registered: {', '.join(names())})")


def resolve_lns(fmt, n: Optional[int] = None) -> FormatSpec:
    """Like :func:`resolve`, but a bare int width means the *LNS* takum
    of that width — the default the ℓ̄-datapath entry points
    (``ops.lns_matmul``, ``ref.lns_qmatmul_ref``) inherited from their
    original ``n``-only API. Keeps that policy in the registry instead
    of copy-pasted at every LNS boundary."""
    if isinstance(fmt, int) and not isinstance(fmt, bool):
        return _make("lns", fmt)
    return resolve(fmt, n)


def resolve_wire(fmt, n: Optional[int] = None) -> FormatSpec:
    """Like :func:`resolve`, but rejects the identity codec — for
    consumers that need actual wire words (weight quantisation)."""
    spec = resolve(fmt, n)
    if spec.is_identity:
        raise ValueError(
            f"format {fmt!r} is the identity codec; expected a wire "
            f"format ({', '.join(wire_names())})")
    return spec
