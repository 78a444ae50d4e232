"""Registry-parametrised property suite: every wire format, one sweep.

One ``pytest.mark.parametrize`` over the codec registry replaces the
per-format copy-pasted cases: for **every registered format** we pin
wire round-trip idempotence, NaR -> NaN containment, zero handling, and
kernel-vs-oracle parity for decode, matmul and attention. Registering a
new ``FormatSpec`` automatically subjects it to the whole suite — which
is the point of the registry: the posit baseline earns its kernels by
its registry entry alone, and these tests prove those kernels correct.

Also pins the acceptance property of the codec-registry refactor:
``kv_quant="posit8"`` serves a decode step through the fused attention
kernel with parity against the jnp oracle.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import formats
from repro.configs.base import parse_kv_quant
from repro.kernels import ops, ref

WIRE = formats.wire_formats()
ALL = formats.all_formats()
_ids = lambda s: s.name  # noqa: E731


def _rand_words(spec, shape, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 1 << spec.n, size=shape, dtype=np.int64)
    return jnp.asarray(w).astype(spec.word_dtype)


def _rand_floats(shape, seed):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape) *
            np.exp(rng.normal(size=shape) * 3)).astype(np.float32)


# ---------------------------------------------------------------------------
# Codec properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_wire_roundtrip_idempotent(spec):
    """decode -> encode -> decode is a fixed point: every word decodes
    onto its own grid, so re-encoding moves nothing (value idempotence;
    NaR round-trips as NaN == NaN under assert_array_equal)."""
    words = _rand_words(spec, (4096,), seed=1)
    x1 = np.asarray(spec.decode_tile(words))
    x2 = np.asarray(spec.decode_tile(spec.encode_tile(x1)))
    np.testing.assert_array_equal(x1, x2)


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_nar_to_nan_containment(spec):
    """NaR decodes to NaN, NaN encodes to NaR — and only NaR produces
    NaN: every other word decodes finite."""
    nar = spec.word_dtype(spec.nar_word)
    assert np.isnan(float(spec.decode_tile(nar)))
    assert int(spec.encode_tile(np.float32("nan"))) == spec.nar_word
    words = _rand_words(spec, (4096,), seed=2)
    dec = np.asarray(spec.decode_tile(words))
    assert (np.isnan(dec) == (np.asarray(words) == nar)).all()


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_zero_and_saturation_semantics(spec):
    """The zero word decodes to exactly 0.0 (the padding contract of the
    kernel layer), 0.0 encodes to the zero word, and finite nonzero
    values never round onto the 0/NaR patterns (saturating RNE)."""
    assert float(spec.decode_tile(spec.word_dtype(spec.zero_word))) == 0.0
    assert int(spec.encode_tile(np.float32(0.0))) == spec.zero_word
    x = np.concatenate([_rand_floats((2048,), seed=3),
                        np.float32([1e30, -1e30, 1e-30, -1e-30])])
    w = np.asarray(spec.encode_tile(x))
    assert (w != spec.zero_word).all() and (w != spec.nar_word).all()


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_bytes_per_elem_and_word_dtype(spec):
    assert spec.bytes_per_elem() == spec.n // 8
    assert jnp.iinfo(spec.word_dtype).bits >= spec.n


def test_identity_codec_is_registered():
    """The float cache is a first-class registered codec, not a special
    case: cast decode, pass-through encode, stored-dtype wire bytes."""
    spec = formats.get("none")
    assert spec.is_identity and spec in ALL
    assert spec.bytes_per_elem(jnp.float32) == 4
    assert spec.bytes_per_elem(jnp.bfloat16) == 2
    x = jnp.asarray(_rand_floats((8,), seed=4))
    np.testing.assert_array_equal(np.asarray(spec.encode_tile(x)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(spec.decode_tile(x)),
                                  np.asarray(x))


def _decode_tile_ops(spec, shape=(64, 256)):
    """Tile-shaped equations in the flattened jaxpr of ``decode_tile`` on
    a ``DEFAULT_STRIP`` of words: the VPU work per word. Broadcasts of
    scalar constants are not counted."""
    jaxpr = jax.make_jaxpr(spec.decode_tile)(
        jax.ShapeDtypeStruct(shape, spec.word_dtype)).jaxpr

    def count(jx):
        total = 0
        for eqn in jx.eqns:
            subs = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                    if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
            if subs:
                total += sum(count(sub) for sub in subs)
            elif (eqn.primitive.name != "broadcast_in_dim"
                  and any(o.aval.shape == shape for o in eqn.outvars)):
                total += 1
        return total

    return count(jaxpr)


def test_takum8_decode_takes_the_closed_form():
    """takum8's ``decode_tile`` is the closed-form n = 8 decode, not the
    generic width's (181 tile ops a word)."""
    assert _decode_tile_ops(formats.resolve("takum8")) <= 64


@pytest.mark.parametrize("name,ops_per_word", [("takum16", 179),
                                               ("posit8", 190)])
def test_other_decodes_keep_their_dataflow(name, ops_per_word, monkeypatch):
    """Every other format keeps the decode it had: its op count is pinned
    at the one before takum8 took the closed form."""
    monkeypatch.delenv("REPRO_LUT_DECODE", raising=False)
    assert _decode_tile_ops(formats.resolve(name)) == ops_per_word


# ---------------------------------------------------------------------------
# Kernel-vs-oracle parity, per registered format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_codec_kernels_match_oracle(spec):
    words = _rand_words(spec, (300, 40), seed=5)
    dec = ops.takum_decode(words, spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(dec),
                                  np.asarray(ref.decode_ref(words, spec)))
    x = _rand_floats((300, 40), seed=6)
    enc = ops.takum_encode(x, spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(enc),
                                  np.asarray(ref.encode_ref(x, spec)))
    fq = ops.fake_quant_fused(x, fmt=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(fq),
                                  np.asarray(ref.fake_quant_ref(x, spec)))


@pytest.mark.parametrize("spec", WIRE, ids=_ids)
def test_matmul_kernel_matches_oracle(spec):
    """Every wire format reaches a matmul kernel: the ℓ̄ datapath for
    ``has_lns_parts`` specs, the decode-once weight-stationary kernel
    for the float-decoding ones (linear takum *and* posit)."""
    x = jnp.asarray(_rand_floats((12, 32), seed=7) / 8)
    w_words = spec.encode_tile(_rand_floats((32, 16), seed=8) / 8)
    if spec.has_lns_parts:
        got = ops.lns_matmul(x, w_words, spec, "linear", True, True,
                             (8, 8, 8))
        want = ref.lns_qmatmul_ref(x, w_words, spec)
    else:
        got = ops.quant_matmul(x, w_words, spec, True, True, (8, 8, 8))
        want = ref.qmatmul_ref(x, w_words, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spec", ALL, ids=_ids)
def test_attention_kernel_matches_oracle(spec):
    """The fused flash decode kernel vs the decode-then-attend oracle,
    for every registered format — the identity codec included."""
    b, t, hkv, g, hd = 2, 96, 2, 2, 16
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(b, 1, g * hkv, hd)), jnp.float32)
    # head-major caches [B, Hkv, T, hd]
    kf = rng.normal(size=(b, t, hkv, hd)).astype(np.float32).swapaxes(1, 2)
    vf = rng.normal(size=(b, t, hkv, hd)).astype(np.float32).swapaxes(1, 2)
    kw, vw = spec.encode_tile(kf), spec.encode_tile(vf)
    got = ops.takum_attention(q, kw, vw, spec.n, spec, pos=t - 1,
                              use_kernel=True, interpret=True, block=32)
    want = ops.takum_attention(q, kw, vw, spec.n, spec, pos=t - 1,
                               use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Resolution / boundary behaviour
# ---------------------------------------------------------------------------


def test_resolve_accepts_every_spelling():
    s = formats.get("takum8")
    assert formats.resolve(s) is s
    assert formats.resolve(8) is s               # bare width = linear takum
    assert formats.resolve("takum8") is s
    assert formats.resolve("linear", 8) is s     # legacy (kind, n) pair
    assert formats.resolve("lns", 16) is formats.get("lns-takum16")
    assert formats.resolve("posit", 16) is formats.get("posit16")
    assert formats.resolve("none") is formats.IDENTITY
    # unregistered widths intern through the same constructor
    assert formats.resolve("takum12") is formats.resolve("linear", 12)


def test_resolve_errors_enumerate_registry():
    with pytest.raises(ValueError, match="takum8.*posit"):
        formats.resolve("takun8")
    with pytest.raises(ValueError, match="identity"):
        formats.resolve_wire("none")
    with pytest.raises(ValueError, match="width"):
        formats.resolve("linear")  # kind without n
    # a width passed alongside a width-carrying format must agree —
    # a silent mismatch would decode words at the wrong width
    with pytest.raises(ValueError, match="mismatch"):
        formats.resolve("takum8", 16)
    with pytest.raises(ValueError, match="mismatch"):
        formats.resolve(formats.get("posit16"), 8)
    assert formats.resolve("takum8", 8) is formats.get("takum8")


def test_parse_kv_quant_routes_through_registry():
    assert parse_kv_quant("none") == ("none", 0)
    assert parse_kv_quant("takum8") == ("linear", 8)
    assert parse_kv_quant("lns-takum16") == ("lns", 16)
    assert parse_kv_quant("posit8") == ("posit", 8)
    with pytest.raises(ValueError, match="kv_quant"):
        parse_kv_quant("takun8")


def test_matmul_route_guards():
    x = jnp.ones((4, 8), jnp.float32)
    w_lns = formats.get("lns-takum8").encode_tile(np.ones((8, 4), np.float32))
    with pytest.raises(ValueError, match="lns_matmul"):
        ops.quant_matmul(x, w_lns, "lns-takum8", True, True)
    w_lin = formats.get("takum8").encode_tile(np.ones((8, 4), np.float32))
    with pytest.raises(ValueError, match="quant_matmul"):
        ops.lns_matmul(x, w_lin, "takum8", "linear", True, True)


def test_quantize_weights_error_enumerates_registry():
    from repro.serve.engine import quantize_weights
    with pytest.raises(ValueError) as ei:
        quantize_weights({"wq": jnp.ones((4, 4))}, "takun8", verbose=False)
    msg = str(ei.value)
    for name in formats.wire_names():
        assert name in msg


# ---------------------------------------------------------------------------
# Posit proves the abstraction: wire weights + KV cache + fake-quant
# ---------------------------------------------------------------------------


def test_posit_wire_matrix_routes_decode_once_matmul():
    """WireMatrix posit words ride the same decode-once weight-stationary
    matmul as linear takum — no posit-specific kernel code."""
    rng = np.random.default_rng(11)
    w = rng.normal(size=(32, 16)).astype(np.float32) / 8
    x = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    wm = ops.WireMatrix.encode(w, fmt="posit16")
    assert wm.spec is formats.get("posit16")
    assert wm.words.dtype == jnp.uint16
    out = x @ wm
    want = ref.qmatmul_ref(x, wm.words, wm.spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_quantize_weights_posit_wire_and_fake(capsys):
    from repro.configs import get_arch
    from repro.models import model
    from repro.serve.engine import quantize_weights
    cfg = get_arch("phi3-medium-14b").reduced
    params = model.init(jax.random.PRNGKey(0), cfg)
    wired = quantize_weights(params, "posit8", mode="wire")
    out = capsys.readouterr().out
    assert "quantize_weights[posit8/wire]" in out
    leaves = jax.tree_util.tree_leaves(
        wired, is_leaf=lambda p: isinstance(p, ops.WireMatrix))
    wire_leaves = [l for l in leaves if isinstance(l, ops.WireMatrix)]
    assert wire_leaves and all(l.spec.kind == "posit" for l in wire_leaves)
    faked = quantize_weights(params, "posit16", mode="fake", verbose=False)
    l0 = jax.tree_util.tree_leaves(faked)[0]
    assert jnp.issubdtype(l0.dtype, jnp.floating)


def test_kv_quant_posit8_decode_step_kernel_parity(monkeypatch):
    """Acceptance pin: ``kv_quant="posit8"`` serves a decode step through
    the fused attention kernel, with parity against the jnp oracle."""
    from repro.configs import get_arch
    from repro.core.bitops import word_dtype
    from repro.models import layers as L

    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="posit8", kv_block=16)
    assert parse_kv_quant(cfg.kv_quant) == ("posit", 8)
    params = L.attn_init(jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads,
                         cfg.n_kv_heads, cfg.hd)
    spec = formats.get("posit8")
    rng = np.random.default_rng(12)
    b, tmax, pos = 2, 48, 33
    words = spec.encode_tile(
        rng.normal(size=(b, tmax, cfg.n_kv_heads, cfg.hd))
        .astype(np.float32).swapaxes(1, 2))
    cache = {"k": words, "v": words[:, :, ::-1],
             "pos": jnp.asarray(pos, jnp.int32),
             "start": jnp.asarray([0, 4], jnp.int32)}
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    positions = pos + jnp.zeros((b, 1), jnp.int32)

    outs = {}
    for use in (True, False):
        monkeypatch.setattr(L, "KV_ATTN_KERNEL", use)
        out, newc = L.attention(params, x, cfg, positions, cache=cache)
        outs[use] = np.asarray(out)
        assert int(newc["pos"]) == pos + 1
        assert newc["k"].dtype == word_dtype(8)
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5,
                               atol=2e-5)


def test_engine_generates_with_posit8_kv_cache():
    from repro.configs import get_arch
    from repro.models import model
    from repro.serve.engine import ServeEngine
    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="posit8")
    params = model.init(jax.random.PRNGKey(0), cfg)
    out = ServeEngine(params, cfg, max_len=24, kv_block=16).generate(
        [[3, 1, 4]], max_new=2)
    assert len(out[0]) == 5
