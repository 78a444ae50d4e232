"""Fused takum-decode flash attention vs the decode-then-attend oracle.

Everything runs the Pallas interpreter, so tier-1 covers the kernel on
CPU. Parity is only contractual for *valid* query rows
(``qpos >= start``): all-masked padding rows stay finite on both paths
but average over different key sets (the kernel skips out-of-band KV
blocks entirely; the oracle softmaxes the whole -1e30 row).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import formats
from repro.core import takum
from repro.core.bitops import word_dtype
from repro.kernels import ops, ref

B, T, HKV, G, HD = 2, 96, 2, 2, 16
H = G * HKV


def _cache(rng, n, fmt, t=T):
    spec = formats.resolve(fmt, n)
    # head-major caches [B, Hkv, T, hd]
    kf = rng.normal(size=(B, t, HKV, HD)).astype(np.float32).swapaxes(1, 2)
    vf = rng.normal(size=(B, t, HKV, HD)).astype(np.float32).swapaxes(1, 2)
    if spec.is_identity:
        return jnp.asarray(kf), jnp.asarray(vf)
    return spec.encode_tile(kf), spec.encode_tile(vf)


def _q(rng, tq=1):
    return jnp.asarray(rng.normal(size=(B, tq, H, HD)), jnp.float32)


def _parity(q, kw, vw, n, fmt, *, pos, start=None, window=0, block=32,
            atol=2e-5):
    got = ops.takum_attention(q, kw, vw, n, fmt, pos=pos, start=start,
                              window=window, use_kernel=True,
                              interpret=True, block=block)
    want = ref.attention_ref(q, kw, vw, n, fmt, pos=pos, start=start,
                             window=window)
    tq = q.shape[1]
    valid = np.ones((B, tq), bool)
    if start is not None:
        valid = (pos + np.arange(tq))[None, :] >= np.asarray(start)[:, None]
    gv, wv = np.asarray(got)[valid], np.asarray(want)[valid]
    assert np.isfinite(gv).all() and np.isfinite(wv).all()
    err = np.abs(gv - wv)
    assert np.max(err) <= atol, float(np.max(err))
    return got, want


@pytest.mark.parametrize("spec", formats.all_formats(),
                         ids=lambda s: s.name)
def test_decode_step_parity(spec):
    # registry-parametrised: every registered codec (posit included)
    # sweeps through the fused kernel, replacing the old hand-written
    # (fmt, n) pair list
    rng = np.random.default_rng(0)
    kw, vw = _cache(rng, spec.n, spec)
    _parity(_q(rng), kw, vw, spec.n, spec, pos=T - 1)


@pytest.mark.parametrize("fmt,n", [("linear", 16), ("lns", 16)])
def test_mid_cache_pos_skips_tail(fmt, n):
    # pos in the middle: the clamped KV index map + pl.when band skip
    # must still match the oracle exactly on the valid prefix
    rng = np.random.default_rng(1)
    kw, vw = _cache(rng, n, fmt)
    _parity(_q(rng), kw, vw, n, fmt, pos=37)


def test_gqa_groups_match_per_head_reference():
    # G=2 query heads share each KV head; the row-block layout must not
    # mix groups: compare against the oracle which indexes heads directly
    rng = np.random.default_rng(2)
    kw, vw = _cache(rng, 16, "linear")
    got, want = _parity(_q(rng), kw, vw, 16, "linear", pos=T - 1)
    assert got.shape == (B, 1, H, HD)


def test_prefill_shaped_tq_with_start_and_window():
    rng = np.random.default_rng(3)
    kw, vw = _cache(rng, 16, "linear")
    q = _q(rng, tq=7)
    start = jnp.asarray([3, 41], jnp.int32)
    for window in (0, 20):
        _parity(q, kw, vw, 16, "linear", pos=37, start=start, window=window)


def test_window_with_low_side_block_clamp():
    # pos deep enough that whole KV blocks sit below the window: the
    # index-map low clamp (DMA elision) must not change results
    rng = np.random.default_rng(10)
    kw, vw = _cache(rng, 16, "linear")
    _parity(_q(rng), kw, vw, 16, "linear", pos=T - 1, window=20, block=16)
    _parity(_q(rng, tq=3), kw, vw, 16, "linear", pos=80, window=33,
            block=16)


def test_left_padded_decode_start_masking():
    rng = np.random.default_rng(4)
    kw, vw = _cache(rng, 8, "linear")
    start = jnp.asarray([0, 30], jnp.int32)
    _parity(_q(rng), kw, vw, 8, "linear", pos=T - 1, start=start)


def test_unaligned_cache_length_is_padded():
    # Tmax=T(96) not a multiple of block=40: ops pads with zero words
    rng = np.random.default_rng(5)
    kw, vw = _cache(rng, 16, "linear")
    _parity(_q(rng), kw, vw, 16, "linear", pos=T - 1, block=40)


def test_nar_words_poison_only_attending_rows():
    rng = np.random.default_rng(6)
    kw, vw = _cache(rng, 16, "linear")
    nar = word_dtype(16)(takum.NAR(16))
    # K NaR at a *valid* position of kv head 0, batch 0
    kw = kw.at[0, 0, 10, 3].set(nar)
    pos = T - 1
    got = ops.takum_attention(_q(rng), kw, vw, 16, "linear", pos=pos,
                              use_kernel=True, interpret=True, block=32)
    g = np.asarray(got)  # [B, 1, H, HD]; heads 0..G-1 belong to kv head 0
    assert np.isnan(g[0, 0, :G]).all(), "NaR must reach its query group"
    assert np.isfinite(g[0, 0, G:]).all(), "other kv heads must stay clean"
    assert np.isfinite(g[1]).all(), "other sequences must stay clean"
    # a V NaR poisons exactly its head-dim component (one column of
    # p @ v), for every query row attending to its kv head
    kw2, vw2 = _cache(rng, 16, "linear")
    vw2 = vw2.at[1, 1, 5, 0].set(nar)
    got2 = np.asarray(ops.takum_attention(
        _q(rng), kw2, vw2, 16, "linear", pos=pos, use_kernel=True,
        interpret=True, block=32))
    assert np.isnan(got2[1, 0, G:, 0]).all()
    assert np.isfinite(got2[1, 0, G:, 1:]).all()
    assert np.isfinite(got2[0]).all() and np.isfinite(got2[1, 0, :G]).all()


def test_nar_behind_start_mask_is_contained():
    rng = np.random.default_rng(7)
    kw, vw = _cache(rng, 16, "linear")
    kw = kw.at[0, 0, 2, 0].set(word_dtype(16)(takum.NAR(16)))
    start = jnp.asarray([5, 0], jnp.int32)  # NaR sits in masked padding
    got, _ = _parity(_q(rng), kw, vw, 16, "linear", pos=T - 1, start=start)
    assert np.isfinite(np.asarray(got)).all()


def _iter_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            av = getattr(v, "aval", None)
            if av is not None and hasattr(av, "shape"):
                yield av
        for val in eqn.params.values():
            yield from _iter_param_avals(val)


def _iter_param_avals(val):
    if hasattr(val, "eqns"):            # Jaxpr
        yield from _iter_avals(val)
    elif hasattr(val, "jaxpr"):         # ClosedJaxpr
        yield from _iter_avals(val.jaxpr)
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_param_avals(v)


def test_kernel_path_never_materialises_full_precision_kv():
    """The acceptance property: on the fused path, no float array the
    size of the decoded [B, Hkv, Tmax, hd] cache exists anywhere in the
    jaxpr — including inside the pallas_call body, whose decodes are
    (bk, hd) tiles."""
    rng = np.random.default_rng(8)
    kw, vw = _cache(rng, 8, "linear")
    q = _q(rng)

    def fn(q, kw, vw):
        return ops.takum_attention(q, kw, vw, 8, "linear", pos=T - 1,
                                   use_kernel=True, interpret=True,
                                   block=32)

    closed = jax.make_jaxpr(fn)(q, kw, vw)
    full = T * HKV * HD  # per-sequence decoded cache element count
    offenders = [
        av for av in _iter_avals(closed.jaxpr)
        if jnp.issubdtype(av.dtype, jnp.floating)
        and int(np.prod(av.shape)) >= full
    ]
    assert not offenders, offenders
    # and the oracle path *does* materialise it (the contrast the fused
    # kernel exists for)
    closed_ref = jax.make_jaxpr(
        lambda q, kw, vw: ops.takum_attention(
            q, kw, vw, 8, "linear", pos=T - 1, use_kernel=False))(q, kw, vw)
    assert any(
        jnp.issubdtype(av.dtype, jnp.floating)
        and int(np.prod(av.shape)) >= full
        for av in _iter_avals(closed_ref.jaxpr))


def test_layers_decode_routes_through_fused_op(monkeypatch):
    """models/layers.py plumbing: the decode-cache branch through the
    Pallas kernel matches the oracle route bit-for-tolerance, including
    start masking and the cache append."""
    from repro.configs import get_arch
    from repro.models import layers as L

    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="takum16", kv_block=16)
    params = L.attn_init(jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads,
                         cfg.n_kv_heads, cfg.hd)
    rng = np.random.default_rng(9)
    b, tmax, pos = 2, 48, 33
    words = takum.float_to_takum(
        rng.normal(size=(b, tmax, cfg.n_kv_heads, cfg.hd))
        .astype(np.float32).swapaxes(1, 2), 16)
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
        assert newc["k"].dtype == word_dtype(16)
        assert "start" in newc
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5,
                               atol=2e-5)
