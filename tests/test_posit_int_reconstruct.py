"""Posit integer-only reconstruction + LUT tile codec + autotuner.

Mirrors tests/test_int_reconstruct.py for the posit datapath:

* bit-exactness of the integer ``posit_to_float`` against the *same
  ldexp dataflow evaluated in numpy* (IEEE RNE semantics, no XLA:CPU
  subnormal flush — moot for posits: every posit with n <= 32 decodes
  to an f32 normal, |e| <= 4(n-2)+3), exhaustive at small n and
  sampled at wide n, for BOTH decode variants (FloPoCo-SM and -2C);
* bitwise agreement with the retained jax oracle
  (``posit_to_float_ref``) over the full word space;
* the AST audit that the hot path contains no ldexp / float divide /
  transcendental, plus a jaxpr audit that no float64 (or any float
  intermediate beyond the final bitcast) appears — the "no silent
  promotion" guard. (The encoder's ``PositDecoded`` NamedTuple is
  trace-time-only under jit — XLA sees the unpacked lanes — so there
  is no runtime round-trip cost to measure; this audit is the
  meaningful check.);
* LUT-vs-computed tile parity through the registry: ``decode_tile``
  must produce bit-identical floats whichever path
  ``REPRO_LUT_DECODE`` selects;
* autotuner determinism: ``force`` sweeps and records, a cache hit
  under mode ``1`` returns identical blocks without re-timing,
  ``force`` re-sweeps to the same answer, and blockless ``ops`` calls
  consult the cache.
"""

import ast
import inspect
import json
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import formats
from repro.core import posit
from repro.core.bitops import word_dtype
from repro.core.posit import frac_width
from repro.kernels import autotune, ops

EXHAUSTIVE_N = [6, 8, 10, 12, 14, 16]
SAMPLED_N = [17, 20, 24, 28, 29, 30, 31, 32]
VARIANTS = ["2c", "sm"]


def _words(n, count=120_000, seed=0):
    """Random words + saturation edges + specials for width n."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 1 << n, count, dtype=np.int64)
    top = (1 << n) - 1 - np.arange(min(4096, 1 << (n - 1)), dtype=np.int64)
    bot = np.arange(min(4096, 1 << (n - 1)), dtype=np.int64)
    nar = 1 << (n - 1)
    edges = np.array([0, nar, nar - 1, nar + 1, 1, (1 << n) - 1],
                     dtype=np.int64)
    return np.concatenate([w, top, bot, edges])


def _np_ldexp_oracle(words, n, ftype=np.float32, variant="2c"):
    """The posit ldexp/divide dataflow in numpy: IEEE RNE semantics."""
    jw = jnp.asarray(words).astype(word_dtype(n))
    dec = (posit.decode_2c if variant == "2c" else posit.decode_sm)(jw, n)
    wf = frac_width(n)
    s = np.asarray(dec.s)
    f = np.asarray(dec.frac, np.uint64)
    if variant == "2c":
        f_nz = f != 0
        mf = np.where((s == 1) & f_nz,
                      (np.uint64(1) << np.uint64(wf)) - f, f)
        me = np.asarray(dec.e) + ((s == 1) & ~f_nz)
    else:  # rep (7) is already magnitude form
        mf, me = f, np.asarray(dec.e)
    with np.errstate(over="ignore"):
        mant = ftype(1.0) + mf.astype(ftype) / ftype(2.0 ** wf)
        mag = np.ldexp(mant, me)
    out = np.where(s == 1, -mag, mag).astype(ftype)
    out = np.where(np.asarray(dec.is_zero), ftype(0), out)
    out = np.where(np.asarray(dec.is_nar), ftype(np.nan), out)
    return out


def _assert_bits_equal(got, want, words, n):
    u = np.uint64 if got.dtype == np.float64 else np.uint32
    gb, wb = got.view(u), want.view(u)
    bad = gb != wb
    assert not bad.any(), \
        (n, [(hex(int(words[i])), got[i], want[i])
             for i in np.nonzero(bad)[0][:5]])


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("n", EXHAUSTIVE_N)
def test_integer_path_matches_ldexp_oracle_exhaustive(n, variant):
    words = np.arange(1 << n, dtype=np.int64)
    got = np.asarray(posit.posit_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n, variant=variant))
    _assert_bits_equal(got, _np_ldexp_oracle(words, n, variant=variant),
                       words, n)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("n", SAMPLED_N)
def test_integer_path_matches_ldexp_oracle_sampled(n, variant):
    words = _words(n, seed=n)
    got = np.asarray(posit.posit_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n, variant=variant))
    _assert_bits_equal(got, _np_ldexp_oracle(words, n, variant=variant),
                       words, n)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("n", EXHAUSTIVE_N + SAMPLED_N)
def test_integer_path_matches_jax_ref_everywhere(n, variant):
    """Unlike takum, posits at n <= 32 have no subnormal/overflow band in
    f32 (|e| <= 4(n-2)+3 = 123 at n = 32), so the retained jax oracle
    must agree bitwise over the ENTIRE word space — no exclusions."""
    words = (np.arange(1 << n, dtype=np.int64) if n <= 16
             else _words(n, seed=n))
    jw = jnp.asarray(words).astype(word_dtype(n))
    got = np.asarray(posit.posit_to_float(jw, n, variant=variant))
    want = np.asarray(posit.posit_to_float_ref(jw, n, variant=variant))
    _assert_bits_equal(got, want, words, n)


@pytest.mark.parametrize("n", [8, 16])
def test_variants_agree(n):
    """SM and 2C are two dataflows for one value function."""
    words = np.arange(1 << n, dtype=np.int64)
    jw = jnp.asarray(words).astype(word_dtype(n))
    a = np.asarray(posit.posit_to_float(jw, n, variant="2c"))
    b = np.asarray(posit.posit_to_float(jw, n, variant="sm"))
    _assert_bits_equal(a, b, words, n)


@pytest.mark.parametrize("n", [8, 16])
def test_encode_roundtrip_survives_integer_decode(n):
    """decode(encode(x)) must still be the identity on decoded values
    after the decode rewrite (the codec pair the fused kernels rely on)."""
    words = np.arange(1 << n, dtype=np.int64)
    jw = jnp.asarray(words).astype(word_dtype(n))
    x = posit.posit_to_float(jw, n)
    back = np.asarray(posit.float_to_posit(x, n))
    # NaR encodes to NaR; everything else is exactly representable
    nar = 1 << (n - 1)
    want = np.asarray(jw)
    assert (back == want).all(), \
        [(hex(int(w)), hex(int(b))) for w, b in zip(want, back)
         if w != b][:5] + [hex(nar)]


# ---------------------------------------------------------------------------
# Hot-path audits: integer ops + one bitcast only, no float64 anywhere
# ---------------------------------------------------------------------------


def _ast_audit(fn):
    """No ldexp / exp / log / pow calls and no float division in fn."""
    src = textwrap.dedent(inspect.getsource(fn))
    tree = ast.parse(src)
    banned_names = {"ldexp", "exp", "exp2", "log", "log2", "power", "pow"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            target = node.func
            name = (target.attr if isinstance(target, ast.Attribute)
                    else getattr(target, "id", ""))
            assert name not in banned_names, \
                f"{fn.__name__} calls {name} on the hot path"
        if isinstance(node, ast.BinOp):
            assert not isinstance(node.op, (ast.Div, ast.Pow)), \
                f"{fn.__name__} uses float divide/pow on the hot path"


def test_hot_paths_are_integer_only():
    _ast_audit(posit.posit_to_float)
    _ast_audit(posit.float_to_posit)
    _ast_audit(posit.encode)
    _ast_audit(posit._unbar)


def test_ref_oracle_still_uses_ldexp():
    """Guard the other direction: the retained oracle must keep the
    ldexp dataflow (otherwise the parity tests test nothing)."""
    assert "ldexp" in inspect.getsource(posit.posit_to_float_ref)


@pytest.mark.parametrize("n", [8, 16, 32])
def test_no_float_intermediates_in_decode_jaxpr(n):
    """The decode hot path must be integer lanes end to end: the only
    float aval in the jaxpr is the final bitcast output. In particular
    no float64 promotion can hide anywhere (the guard the takum path
    got in the original integer-reconstruction PR)."""
    jw = jnp.zeros(4, word_dtype(n))
    jaxpr = jax.make_jaxpr(
        lambda w: posit.posit_to_float(w, n))(jw).jaxpr
    float_avals = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                float_avals.append((eqn.primitive.name, str(dt)))
    assert float_avals == [("bitcast_convert_type", "float32")], float_avals


def test_encode_path_no_float64():
    """float_to_posit works on f32 bit patterns: no f64 promotion."""
    jaxpr = jax.make_jaxpr(
        lambda x: posit.float_to_posit(x, 16))(jnp.zeros(4, jnp.float32))
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            assert dt is None or dt != jnp.float64, eqn


# ---------------------------------------------------------------------------
# LUT tile codec through the registry
# ---------------------------------------------------------------------------


def test_posit8_lut_matches_computed_decode(monkeypatch):
    """256-entry table decode must be bit-identical to the computed
    integer dataflow, reached through the SAME decode_tile indirection
    the fused kernels use."""
    spec = formats.resolve("posit8")
    assert spec.has_lut
    words = jnp.arange(256, dtype=jnp.uint8)
    monkeypatch.setenv("REPRO_LUT_DECODE", "1")
    assert spec.lut_decode
    via_lut = np.asarray(spec.decode_tile(words))
    monkeypatch.setenv("REPRO_LUT_DECODE", "0")
    assert not spec.lut_decode
    computed = np.asarray(spec.decode_tile(words))
    _assert_bits_equal(via_lut, computed, np.arange(256), 8)


def test_lut_hook_registry_wiring(monkeypatch):
    """Only posit8 carries a LUT hook; the env var turns it on, and it
    is off by default on every backend."""
    assert formats.resolve("posit8").has_lut
    for name in ("takum8", "takum16", "posit16", "none"):
        assert not formats.resolve(name).has_lut, name
    monkeypatch.setenv("REPRO_LUT_DECODE", "0")
    assert not formats.lut_enabled()
    monkeypatch.setenv("REPRO_LUT_DECODE", "1")
    assert formats.lut_enabled()
    monkeypatch.delenv("REPRO_LUT_DECODE")
    assert not formats.lut_enabled()


def test_lut_path_used_in_fake_quant(monkeypatch):
    """fake_quant routes through decode_tile, so forcing the LUT on must
    not change a single bit of the quantised values."""
    spec = formats.resolve("posit8")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64,)).astype(np.float32)
    monkeypatch.setenv("REPRO_LUT_DECODE", "0")
    a = np.asarray(spec.fake_quant(x))
    monkeypatch.setenv("REPRO_LUT_DECODE", "1")
    b = np.asarray(spec.fake_quant(x))
    _assert_bits_equal(a, b, np.arange(x.size), 8)


# ---------------------------------------------------------------------------
# Autotuner: determinism + cache plumbing
# ---------------------------------------------------------------------------


def _fake_runner(calls, best=(32, 128, 128), slow_us=2000, fast_us=200):
    """run(blocks) -> zero-arg callable; `best` sleeps 10x less."""
    import time as _t

    def run(blocks):
        def go():
            calls.append(tuple(blocks))
            _t.sleep((fast_us if tuple(blocks) == best else slow_us) / 1e6)
        return go
    return run


def test_autotune_force_then_cache_hit_then_resweep(tmp_path, monkeypatch):
    cache = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    space = ((64, 128, 128), (32, 128, 128), (128, 128, 128))
    calls = []

    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    blocks, us, swept = autotune.cached_or_sweep(
        "qmatmul", "posit8", "m8k64n64", space, _fake_runner(calls),
        reps=1)
    assert swept and blocks == (32, 128, 128) and us is not None
    assert set(calls) == set(space)  # every candidate timed
    doc = json.loads(cache.read_text())
    key = f"qmatmul|posit8|m8k64n64|{jax.default_backend()}"
    assert doc["entries"][key]["blocks"] == [32, 128, 128]

    # mode 1: cache hit returns identical blocks with NO timing calls
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    calls.clear()
    blocks2, _, swept2 = autotune.cached_or_sweep(
        "qmatmul", "posit8", "m8k64n64", space, _fake_runner(calls),
        reps=1)
    assert blocks2 == blocks and not swept2 and calls == []
    assert autotune.lookup("qmatmul", "posit8", "m8k64n64") == blocks

    # force again: re-sweeps and lands on the same answer
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    calls.clear()
    blocks3, _, swept3 = autotune.cached_or_sweep(
        "qmatmul", "posit8", "m8k64n64", space, _fake_runner(calls),
        reps=1)
    assert swept3 and blocks3 == blocks and set(calls) == set(space)


def test_autotune_mode_semantics(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "empty.json"))
    space = ((8, 128, 128), (64, 128, 128))
    calls = []
    # mode 0: off — no lookup, no sweep, fallback untimed
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    blocks, us, swept = autotune.cached_or_sweep(
        "qmatmul", "takum8", "m8k8n8", space, _fake_runner(calls))
    assert blocks == (8, 128, 128) and not swept and calls == []
    assert autotune.lookup("qmatmul", "takum8", "m64k2048n2048") is None
    # mode 1 miss: fallback, never sweeps outside force
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    blocks, us, swept = autotune.cached_or_sweep(
        "qmatmul", "takum8", "m8k8n8", space, _fake_runner(calls))
    assert blocks == (8, 128, 128) and not swept and calls == []
    # invalid mode is an error, not a silent default
    monkeypatch.setenv("REPRO_AUTOTUNE", "2")
    with pytest.raises(ValueError):
        autotune.mode()


def test_autotune_sweep_skips_failing_candidates(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")

    def run(blocks):
        if blocks == (512, 512, 512):
            raise MemoryError("tile too large")
        return lambda: None
    blocks, _, swept = autotune.cached_or_sweep(
        "qmatmul", "posit16", "m8k8n8",
        ((8, 128, 128), (512, 512, 512)), run, reps=1)
    assert swept and blocks == (8, 128, 128)


def test_blockless_ops_consult_cache(tmp_path, monkeypatch):
    """A blockless quant_matmul/attention call resolves its tiles from
    the cache — the ISSUE's acceptance criterion, checked at the
    resolved_blocks seam the BENCH rows record."""
    cache = tmp_path / "tune.json"
    be = jax.default_backend()
    cache.write_text(json.dumps({"schema": 1, "entries": {
        f"qmatmul|takum16|m64k128n128|{be}": {"blocks": [16, 32, 32]},
        f"attention|takum8|t128|{be}": {"blocks": [64]},
    }}))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    assert ops.resolved_blocks("qmatmul", "takum16", (40, 96, 128)) == \
        (16, 32, 32)
    assert ops.resolved_blocks("attention", "takum8", 100) == (64,)
    # and the tuned blocks actually feed a real call with block=None
    from repro.core import takum
    from repro.kernels import ref as kref
    rng = np.random.default_rng(1)
    x = rng.normal(size=(40, 96)).astype(np.float32)
    w_words = takum.float_to_takum(
        rng.normal(size=(96, 128)).astype(np.float32), 16)
    out = ops.quant_matmul(x, w_words, 16, True, True, None)
    want = kref.qmatmul_ref(x, w_words, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # off: the same call must fall back to the shape-derived default
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert ops.resolved_blocks("qmatmul", "takum16", (40, 96, 128)) == \
        ops.qmm_blocks(40, 96, 128, 2)


def test_autotune_defaults_table_is_valid():
    """The checked-in defaults parse and every entry is well-formed."""
    with open(autotune.DEFAULTS_PATH) as f:
        doc = json.load(f)
    assert doc.get("entries"), "defaults table is empty"
    for key, ent in doc["entries"].items():
        op, fmt, bucket, backend = key.split("|")
        assert op in autotune.OPS, key
        assert isinstance(ent["blocks"], list) and ent["blocks"], key
        assert all(isinstance(b, int) and b > 0 for b in ent["blocks"]), key
        assert len(ent["blocks"]) == (1 if op == "attention" else 3), key
