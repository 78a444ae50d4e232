"""Integer-only float reconstruction: bit-exactness and hot-path audit.

Ground truth, per band:

* **normal f32/f64 range**: the retained jax ldexp oracle
  (``takum.takum_to_float_ref``) — bit-identical.
* **full range incl. subnormals/overflow**: the *same ldexp dataflow
  evaluated in numpy* (XLA:CPU flushes subnormal runtime multiply results
  to zero, numpy keeps IEEE gradual underflow — the paper-correct
  semantics the integer path implements).
* **n <= 28** (``wf <= 23``: no mantissa rounding): the exact golden
  model value, RNE'd to f32 — single rounding, so this is the strongest
  statement: the integer path IS correctly-rounded decode.

Plus: an AST audit that the integer hot path contains no ldexp, float
division or transcendental, and weight-stationary matmul parity sweeps.
"""

import ast
import inspect
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import golden, takum
from repro.core.bitops import word_dtype
from repro.core.takum import frac_width
from repro.kernels import ops, ref as kref

EXHAUSTIVE_N = [6, 8, 10, 12, 14, 16]
SAMPLED_N = [17, 20, 24, 28, 29, 30, 31, 32]


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


def _np_ldexp_oracle(words, n, ftype=np.float32):
    """The ldexp/divide dataflow in numpy: IEEE RNE + gradual underflow."""
    dec = takum.decode_linear(jnp.asarray(words).astype(word_dtype(n)), n)
    wf = frac_width(n)
    s = np.asarray(dec.s)
    f = np.asarray(dec.mant, np.uint64)
    f_nz = f != 0
    mf = np.where((s == 1) & f_nz, (np.uint64(1) << np.uint64(wf)) - f, f)
    me = np.asarray(dec.val) + ((s == 1) & ~f_nz)
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


@pytest.mark.parametrize("n", EXHAUSTIVE_N)
def test_integer_path_matches_ldexp_oracle_exhaustive(n):
    words = np.arange(1 << n, dtype=np.int64)
    got = np.asarray(takum.takum_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n))
    _assert_bits_equal(got, _np_ldexp_oracle(words, n), words, n)


@pytest.mark.parametrize("n", SAMPLED_N)
def test_integer_path_matches_ldexp_oracle_sampled(n):
    words = _words(n, seed=n)
    got = np.asarray(takum.takum_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n))
    _assert_bits_equal(got, _np_ldexp_oracle(words, n), words, n)


@pytest.mark.parametrize("n", EXHAUSTIVE_N + SAMPLED_N)
def test_integer_path_matches_jax_ref_in_normal_range(n):
    """The retained jax oracle agrees bitwise wherever XLA:CPU's subnormal
    flush cannot bite (|x| normal or exactly 0/NaR)."""
    words = (np.arange(1 << n, dtype=np.int64) if n <= 16
             else _words(n, seed=n))
    jw = jnp.asarray(words).astype(word_dtype(n))
    got = np.asarray(takum.takum_to_float(jw, n))
    want = np.asarray(takum.takum_to_float_ref(jw, n))
    normal = ~np.isfinite(got) | (got == 0) | (np.abs(got) >= 2.0 ** -126)
    # the integer path may resolve a subnormal where the flushing oracle
    # returned 0: restrict to the well-defined band
    _assert_bits_equal(got[normal], want[normal], words[normal], n)


@pytest.mark.parametrize("n", [6, 8, 10, 12])
def test_integer_path_exhaustive_vs_golden_exact(n):
    """Single-rounding ground truth: RNE(golden value) == integer path,
    over every word — covers NaR, zero, the full subnormal band and
    overflow-to-inf (f32's range is finite, takum6+'s is wider)."""
    words = np.arange(1 << n, dtype=np.int64)
    got = np.asarray(takum.takum_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n))
    for T in words:
        v = golden.takum_linear_value(int(T), n)
        if v is None:
            assert np.isnan(got[T]), T
            continue
        # float(Fraction) is exact here (<= 24 sig bits, |e| <= 255), and
        # np.float32 applies single IEEE RNE incl. gradual underflow
        with np.errstate(over="ignore"):
            want = np.float32(float(v))
        assert got[T].view(np.uint32) == want.view(np.uint32), \
            (T, got[T], want)


@pytest.mark.parametrize("n", [16, 20, 24, 28])
def test_integer_path_sampled_vs_golden_exact(n):
    words = np.unique(_words(n, count=2000, seed=n + 1))
    got = np.asarray(takum.takum_to_float(
        jnp.asarray(words).astype(word_dtype(n)), n))
    for i, T in enumerate(words):
        v = golden.takum_linear_value(int(T), n)
        if v is None:
            assert np.isnan(got[i]), T
            continue
        with np.errstate(over="ignore"):
            want = np.float32(float(v))
        assert got[i].view(np.uint32) == want.view(np.uint32), \
            (hex(int(T)), got[i], want)


# ---------------------------------------------------------------------------
# takum8's closed-form decode: the generic n = 8 decode, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(256,), (2, 128), (64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_takum8_closed_form_matches_generic_decode(shape, dtype):
    """Every word, in every layout a caller hands it: a flat vector, a
    kernel's (R, 128) rows and a ``DEFAULT_STRIP`` (64, 256) strip."""
    words = np.arange(int(np.prod(shape)), dtype=np.int64) % 256
    jw = jnp.asarray(words.reshape(shape)).astype(jnp.uint8)
    got = np.asarray(takum.takum8_to_float(jw, dtype))
    want = np.asarray(takum.takum_to_float(jw, 8, dtype))
    assert got.dtype == want.dtype == np.dtype(dtype)
    u = np.uint16 if got.dtype.itemsize == 2 else np.uint32
    np.testing.assert_array_equal(got.view(u), want.view(u))


def test_takum8_closed_form_matches_ldexp_oracle_and_pins_specials():
    words = np.arange(256, dtype=np.int64)
    got = np.asarray(takum.takum8_to_float(jnp.asarray(words)
                                           .astype(jnp.uint8)))
    _assert_bits_equal(got, _np_ldexp_oracle(words, 8), words, 8)
    bits = got.view(np.uint32)
    assert bits[0x80] == 0x7FC0_0000                      # NaR -> NaN
    assert bits[0] == 0                                   # +0.0
    inf = np.nonzero(np.isinf(got))[0]                    # past 2^127
    assert sorted(inf) == [*range(0x79, 0x80), *range(0x81, 0x88)]
    assert (got[0x79:0x80] > 0).all() and (got[0x81:0x88] < 0).all()
    assert got[0x78] == 2.0 ** 127 and got[0x88] == -(2.0 ** 127)
    sub = {7: 2.0 ** -143, 8: 2.0 ** -127, 0xF9: -2.0 ** -143,
           0xF8: -2.0 ** -127}                            # f32 subnormals
    for w, v in sub.items():
        assert bits[w] == np.float32(v).view(np.uint32), hex(w)
    assert (bits[1:7] == 0).all()                         # flushed: +0.0
    assert (bits[0xFA:] == 0x8000_0000).all()             # flushed: -0.0
    tiny = np.abs(got) < np.finfo(np.float32).tiny
    assert sorted(np.nonzero(tiny & (got != 0))[0]) == sorted(sub)


def test_takum64_integer_path_subprocess():
    """x64 lanes: f64 output bit-identical to the numpy ldexp oracle at
    n = 64 (and f32 output from uint64 lanes at n = 48)."""
    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp
        from repro.core.bitops import word_dtype
        # the same oracle the n <= 32 tests pin against (PYTHONPATH=src:tests)
        from test_int_reconstruct import _np_ldexp_oracle as oracle
        from repro.core import takum

        rng = np.random.default_rng(7)
        for n, ftype, jdt, u in [(64, np.float64, jnp.float64, np.uint64),
                                 (48, np.float32, jnp.float32, np.uint32),
                                 (64, np.float32, jnp.float32, np.uint32)]:
            words = rng.integers(0, 1 << 63, 100000,
                                 dtype=np.int64).astype(np.uint64)
            words |= rng.integers(0, 2, 100000,
                                  dtype=np.int64).astype(np.uint64) << \\
                np.uint64(63)
            if n < 64:
                words >>= np.uint64(64 - n)
            got = np.asarray(takum.takum_to_float(
                jnp.asarray(words).astype(word_dtype(n)), n, dtype=jdt))
            want = oracle(words, n, ftype)
            assert (got.view(u) == want.view(u)).all(), (n, ftype)
        print("INT64 RECON OK")
    """)
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:tests"
    out = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "INT64 RECON OK" in out.stdout


# ---------------------------------------------------------------------------
# Hot-path audit: integer ops + one bitcast only
# ---------------------------------------------------------------------------


def _ast_audit(fn, *, allow_div_in: tuple = ()):
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
    _ast_audit(takum.takum_to_float)
    _ast_audit(takum.takum8_to_float)
    _ast_audit(takum.float_to_takum)
    _ast_audit(takum._unbar)
    _ast_audit(takum._rne_shr)


def test_ref_oracle_still_uses_ldexp():
    """Guard the other direction: the retained oracle must keep the
    ldexp dataflow (otherwise the parity tests test nothing)."""
    src = inspect.getsource(takum.takum_to_float_ref)
    assert "ldexp" in src


# ---------------------------------------------------------------------------
# Weight-stationary matmul: parity across block configurations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("block", [
    (8, 64, 32),     # M/bm = 5: scratch reused across many M steps
    (16, 32, 32),    # all three grid dims > 1
    (40, 64, 64),    # M/bm = 1 after padding: serving decode shape
])
def test_weight_stationary_matmul_matches_ref_blocks(n, block):
    m, k, nn = 40, 96, 128
    rng = np.random.default_rng(n)
    x = rng.normal(size=(m, k)).astype(np.float32)
    # bounded weights (raw random words span ±2^254: the dot overflows and
    # inf-accumulation order would dominate the comparison)
    w_words = takum.float_to_takum(
        rng.normal(size=(k, nn)).astype(np.float32), n)
    out = ops.quant_matmul(x, w_words, n, True, True, block)
    want = kref.qmatmul_ref(x, w_words, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_weight_stationary_scratch_refreshes_per_weight_tile():
    """A grid with several (j, kk) tiles AND several M steps: if the
    scratch decode under ``program_id(m) == 0`` failed to refresh on a new
    (j, kk) — or refreshed on the wrong axis — parity with the oracle
    would break. Distinct per-tile weight words make staleness visible."""
    n = 16
    m, k, nn = 64, 128, 128
    rng = np.random.default_rng(3)
    x = rng.normal(size=(m, k)).astype(np.float32)
    w_words = takum.float_to_takum(
        rng.normal(size=(k, nn)).astype(np.float32), n)
    out = ops.quant_matmul(x, w_words, n, True, True, (16, 64, 64))
    want = kref.qmatmul_ref(x, w_words, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_wire_matrix_routes_through_qmatmul():
    rng = np.random.default_rng(4)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)).astype(np.float32))
    wm = ops.WireMatrix.encode(w, 16)
    out = x @ wm
    want = kref.qmatmul_ref(np.asarray(x).reshape(-1, 64), wm.words,
                            16).reshape(3, 5, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # pytree roundtrip preserves the wire format
    import jax
    leaves, treedef = jax.tree_util.tree_flatten({"w": wm})
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back["w"], ops.WireMatrix) and back["w"].n == 16


def test_qmatmul_big_m_fallback_matches_ref():
    """Force the VMEM-budget fallback (classic K-innermost schedule) and
    check it agrees with both the oracle and the weight-stationary path."""
    from repro.kernels import takum_matmul
    n = 16
    m, k, nn = 64, 128, 64
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w_words = takum.float_to_takum(
        rng.normal(size=(k, nn)).astype(np.float32), n)
    from repro import formats
    spec = formats.resolve("linear", n)
    ws = takum_matmul.qmatmul_kernel_call(
        x, w_words, spec, bm=16, bn=32, bk=32, interpret=True)
    fb = takum_matmul.qmatmul_kernel_call(
        x, w_words, spec, bm=16, bn=32, bk=32, interpret=True,
        acc_budget_bytes=0)
    want = kref.qmatmul_ref(x, w_words, n)
    np.testing.assert_allclose(np.asarray(fb), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ws), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
