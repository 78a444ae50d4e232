"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and word widths."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

WIDTHS = [8, 16, 32]
SHAPES = [(256, 128), (512, 256), (300, 200), (17,), (1024,)]


def _rand_words(shape, n, seed):
    rng = np.random.default_rng(seed)
    from repro.core.bitops import word_dtype
    w = rng.integers(0, 1 << n, size=shape, dtype=np.int64)
    return jnp.asarray(w).astype(word_dtype(n))


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("shape", SHAPES)
def test_decode_kernel_matches_ref(n, shape):
    words = _rand_words(shape, n, seed=hash((n, shape)) % 2**31)
    out = ops.takum_decode(words, n, interpret=True)
    want = ref.decode_ref(words, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("shape", [(256, 128), (300, 200), (1000,)])
def test_encode_kernel_matches_ref(n, shape):
    rng = np.random.default_rng(3)
    x = (rng.normal(size=shape) * np.exp(rng.normal(size=shape) * 4)
         ).astype(np.float32)
    out = ops.takum_encode(x, n, interpret=True)
    want = ref.encode_ref(x, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n", [8, 16])
def test_fake_quant_kernel_matches_ref(n):
    rng = np.random.default_rng(4)
    x = rng.normal(size=(300, 129)).astype(np.float32)
    out = ops.fake_quant_fused(x, n, interpret=True)
    want = ref.fake_quant_ref(x, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 384, 128),
                                 (100, 130, 60)])
def test_qmatmul_kernel_matches_ref(n, mkn):
    m, k, nn = mkn
    rng = np.random.default_rng(5)
    x = rng.normal(size=(m, k)).astype(np.float32)
    w_words = _rand_words((k, nn), n, seed=6)
    out = ops.quant_matmul(x, w_words, n, True, True)
    want = ref.qmatmul_ref(x, w_words, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_qmatmul_batched_and_grad():
    n = 16
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 5, 64)).astype(np.float32))
    from repro.core import takum as takum_mod
    w = rng.normal(size=(64, 32)).astype(np.float32)
    w_words = takum_mod.float_to_takum(w, n)
    out = ops.quant_matmul(x, w_words, n, False, None)
    assert out.shape == (2, 5, 32)

    def loss(x):
        return jnp.sum(ops.quant_matmul(x, w_words, n, False, None) ** 2)

    g = jax.grad(loss)(x)
    w_dec = np.asarray(ref.decode_ref(w_words, n))
    want_g = 2 * np.asarray(out) @ w_dec.T
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-4, atol=1e-4)


def test_kernel_vs_nokernel_paths_agree():
    n = 16
    rng = np.random.default_rng(8)
    x = rng.normal(size=(64, 96)).astype(np.float32)
    w_words = _rand_words((96, 48), n, seed=9)
    a = ops.quant_matmul(x, w_words, n, True, True)
    b = ops.quant_matmul(x, w_words, n, False, None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-4)


def _gauss_words(spec, k, n, seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, n)).astype(np.float32) / np.sqrt(k)
    return ops.WireMatrix.encode(w, fmt=spec).words


# multi-tile grids at the shape-derived tile sizes: 2+ steps along N or
# K, one M-step, and the strip-wise decode of large tiles
@pytest.mark.parametrize("fmt", ["takum8", "takum16", "posit8"])
@pytest.mark.parametrize("m,k,n,bk,bn", [
    (32, 512, 1280, 256, 640),
    (256, 1792, 896, 896, 896),
    (32, 1280, 1792, 640, 896),
    (256, 2560, 512, 1280, 256),
    (32, 1280, 2560, 640, 1280),
])
def test_qmatmul_large_tiles_match_ref(fmt, m, k, n, bk, bn):
    from repro import formats
    from repro.kernels import takum_matmul
    spec = formats.get(fmt)
    x = np.random.default_rng(10).normal(size=(m, k)).astype(np.float32)
    w_words = _gauss_words(spec, k, n, seed=11)
    out = takum_matmul.qmatmul_kernel_call(
        jnp.asarray(x), w_words, spec, bm=m, bn=bn, bk=bk, interpret=True)
    want = ref.qmatmul_ref(x, w_words, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# blockless calls take the shape-derived tile: K and N multiples of 128
# but not of the largest candidate tile, and ones that pad up to 128
@pytest.mark.parametrize("fmt", ["takum8", "takum16", "posit8"])
@pytest.mark.parametrize("mkn", [(32, 384, 1152), (256, 1408, 640),
                                 (40, 300, 200), (300, 256, 384)])
def test_qmatmul_blockless_matches_ref(fmt, mkn):
    from repro import formats
    spec = formats.get(fmt)
    m, k, n = mkn
    x = np.random.default_rng(12).normal(size=(m, k)).astype(np.float32)
    w_words = _gauss_words(spec, k, n, seed=13)
    out = ops.quant_matmul(x, w_words, spec, True, True)
    want = ref.qmatmul_ref(x, w_words, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


RULE_SHAPES = [(32, 5120, 5120), (32, 5120, 1280), (32, 5120, 17920),
               (32, 17920, 5120), (256, 5120, 5120), (256, 5120, 1280),
               (256, 5120, 17920), (256, 17920, 5120), (1, 64, 32),
               (40, 96, 128), (300, 1408, 640), (2048, 4096, 4096),
               (32, 5120, 4480), (100_000, 512, 512)]


@pytest.mark.parametrize("word_bytes", [1, 2])
@pytest.mark.parametrize("mkn", RULE_SHAPES)
def test_qmm_blocks_rule(mkn, word_bytes):
    """The shape-derived tile: divides the 128-padded K and N, one M-step
    up to 256 rows, VMEM estimate within the budget."""
    m, k, n = mkn
    bm, bn, bk = ops.qmm_blocks(m, k, n, word_bytes)
    kp, np_ = -(-k // 128) * 128, -(-n // 128) * 128
    assert bk % 128 == 0 and bn % 128 == 0
    assert kp % bk == 0 and np_ % bn == 0
    mp = max(8, -(-m // 8) * 8)
    if mp <= ops.QMM_ONE_STEP_M:
        assert bm == mp
    else:
        assert bm == 128
    assert bk * bn <= ops.QMM_MAX_TILE_WORDS
    mp = -(-mp // bm) * bm
    if (bk, bn) != (128, 128):
        assert ops.qmm_vmem_bytes(mp, bm, bk, bn, word_bytes) \
            <= ops.QMM_VMEM_BUDGET


def test_qmm_blocks_served_grid_steps():
    """One decode step (M = 32) of the 20-layer phi3-medium stage: the
    seven projections of a layer take a few hundred grid steps, not
    20,800 (128x128 tiles)."""
    d, ff, kv = 5120, 17920, 1280
    proj = [(d, d), (d, kv), (d, kv), (d, d), (d, ff), (d, ff), (ff, d)]

    def steps(blocks_of):
        total = 0
        for k, n in proj:
            bm, bn, bk = blocks_of(k, n)
            total += (k // bk) * (n // bn) * (32 // bm)
        return total * 20

    assert steps(lambda k, n: ops.default_qmm_blocks(32)) == 416_000
    assert steps(lambda k, n: ops.qmm_blocks(32, k, n)) < 20_000


def test_qmm_block_choice_and_grid_step_counter(monkeypatch):
    """An explicit block wins over the rule; ``lns_matmul`` keeps the
    fixed tile; each kernel call adds its grid steps to the counter."""
    from repro import formats
    from repro.obs.metrics import GLOBAL
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert ops.resolved_blocks("lns_qmatmul", "lns-takum8",
                               (32, 5120, 17920)) == \
        ops.default_qmm_blocks(32)
    assert ops.resolved_blocks("qmatmul", "takum8", (32, 5120, 17920)) \
        == ops.qmm_blocks(32, 5120, 17920)
    spec = formats.get("takum8")
    x = np.ones((24, 512), np.float32)
    w_words = _gauss_words(spec, 512, 384, seed=14)
    ctr = GLOBAL.counter("qmatmul.grid_steps")
    before = ctr.get()
    ops.quant_matmul(x, w_words, spec, True, True, (8, 128, 128))
    assert ctr.get() - before == 3 * 4 * 3
    before = ctr.get()
    ops.quant_matmul(x, w_words, spec, True, True)
    bm, bn, bk = ops.qmm_blocks(24, 512, 384)
    assert (bm, bn, bk) == (24, 384, 512)
    assert ctr.get() - before == 1
