"""Paged decode attention: fused kernel vs gather-then-attend oracle.

Everything runs the Pallas interpreter so tier-1 covers the paged kernel
on CPU. The contract mirrors the contiguous kernel's
(``test_takum_attention.py``) with the paged twists: per-sequence
``pos``/``start`` vectors, block-table gathers, stale words on recycled
pages contained by the causal mask, and the table clamp for drifted idle
slots.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import formats
from repro.core import takum
from repro.core.bitops import word_dtype
from repro.kernels import ops, ref

P, PS, HKV, G, HD, NP = 11, 16, 2, 2, 16, 4
H = G * HKV
B = 4


def _pool_and_table(rng, spec, *, garbage=False):
    # head-major pages [P, Hkv, ps, hd]
    kf = rng.normal(size=(P, PS, HKV, HD)).astype(np.float32).swapaxes(1, 2)
    vf = rng.normal(size=(P, PS, HKV, HD)).astype(np.float32).swapaxes(1, 2)
    if spec.is_identity:
        kw, vw = jnp.asarray(kf), jnp.asarray(vf)
    else:
        kw, vw = spec.encode_tile(kf), spec.encode_tile(vf)
    # distinct non-scratch pages per sequence, rows padded with page 0
    perm = rng.permutation(np.arange(1, P))
    table = np.zeros((B, NP), np.int32)
    table[0] = perm[:NP]
    table[1] = perm[NP:2 * NP]
    table[2, :2] = perm[8:10]
    # seq 3 idles on the scratch page (all-zero row)
    return kw, vw, jnp.asarray(table)


def _q(rng):
    return jnp.asarray(rng.normal(size=(B, 1, H, HD)), jnp.float32)


def _parity(q, kw, vw, table, spec, *, pos, start=None, window=0,
            atol=2e-5):
    got = ops.paged_attention(q, kw, vw, table, spec, pos=pos, start=start,
                              window=window, use_kernel=True,
                              interpret=True)
    want = ops.paged_attention(q, kw, vw, table, spec, pos=pos, start=start,
                               window=window, use_kernel=False)
    gv, wv = np.asarray(got), np.asarray(want)
    assert np.isfinite(gv).all() and np.isfinite(wv).all()
    assert np.max(np.abs(gv - wv)) <= atol, float(np.max(np.abs(gv - wv)))
    return got, want


@pytest.mark.parametrize("spec", formats.all_formats(),
                         ids=lambda s: s.name)
def test_paged_parity_every_registered_format(spec):
    rng = np.random.default_rng(0)
    kw, vw, table = _pool_and_table(rng, spec)
    pos = jnp.asarray([NP * PS - 1, 37, 20, 0], jnp.int32)
    start = jnp.asarray([0, 5, 3, 0], jnp.int32)
    _parity(_q(rng), kw, vw, table, spec, pos=pos, start=start)


def test_paged_window_parity():
    rng = np.random.default_rng(1)
    spec = formats.get("takum16")
    kw, vw, table = _pool_and_table(rng, spec)
    pos = jnp.asarray([60, 37, 20, 0], jnp.int32)
    for window in (7, 24):
        _parity(_q(rng), kw, vw, table, spec, pos=pos, window=window)


def test_paged_matches_contiguous_reference():
    """A paged cache whose table is laid out in page order must agree
    with the plain contiguous oracle on the same words — the gather is
    a layout change only."""
    rng = np.random.default_rng(2)
    spec = formats.get("takum8")
    kw, vw, _ = _pool_and_table(rng, spec)
    table = jnp.asarray(np.tile(np.arange(1, NP + 1, dtype=np.int32),
                                (B, 1)))
    pos = jnp.asarray([55, 31, 16, 8], jnp.int32)
    start = jnp.asarray([0, 2, 0, 1], jnp.int32)
    q = _q(rng)
    got, _ = _parity(q, kw, vw, table, spec, pos=pos, start=start)
    # contiguous reference: the same pages glued in order
    kc = kw[1:NP + 1].swapaxes(0, 1).reshape(HKV, NP * PS, HD)[None]
    vc = vw[1:NP + 1].swapaxes(0, 1).reshape(HKV, NP * PS, HD)[None]
    for b in range(B):
        want = ref.attention_ref(q[b:b + 1], kc, vc, spec.n, spec,
                                 pos=int(pos[b]), start=start[b:b + 1])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0]),
                                   rtol=1e-5, atol=2e-5)


def test_stale_words_on_recycled_pages_are_masked():
    """Pages past a sequence's pos hold a previous owner's words; the
    causal mask must make the result independent of them."""
    rng = np.random.default_rng(3)
    spec = formats.get("takum8")
    kw, vw, table = _pool_and_table(rng, spec)
    pos = jnp.asarray([20, 37, 20, 0], jnp.int32)
    q = _q(rng)
    base = ops.paged_attention(q, kw, vw, table, spec, pos=pos,
                               use_kernel=True, interpret=True)
    # scribble over every position past pos on seq 0's pages (pos 20:
    # block 1 offsets 5.., blocks 2, 3) and over the whole scratch page
    tab0 = np.asarray(table)[0]
    kw2 = np.asarray(kw).copy()
    kw2[tab0[1], :, 5:] = 201
    kw2[tab0[2:]] = 77
    kw2[0] = 123
    got = ops.paged_attention(q, jnp.asarray(kw2), vw, table, spec, pos=pos,
                              use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(base[:3]), np.asarray(got[:3]))


def test_idle_slot_pos_drift_stays_in_table():
    """Idle scheduler slots keep stepping with a stale pos that can
    exceed the table span; the clamped table read must keep the kernel
    in bounds and finite."""
    rng = np.random.default_rng(4)
    spec = formats.get("takum8")
    kw, vw, table = _pool_and_table(rng, spec)
    pos = jnp.asarray([NP * PS - 1, 37, 20, 10 * NP * PS], jnp.int32)
    got, want = _parity(_q(rng), kw, vw, table, spec, pos=pos)
    assert np.isfinite(np.asarray(got)).all()


def test_nar_poisons_only_attending_sequence():
    rng = np.random.default_rng(5)
    spec = formats.get("takum16")
    kw, vw, table = _pool_and_table(rng, spec)
    nar = word_dtype(16)(takum.NAR(16))
    # NaR at seq 1's position 8 (its page table[1, 0], offset 8), kv head 0
    kw = kw.at[int(table[1, 0]), 0, 8, 0].set(nar)
    pos = jnp.asarray([NP * PS - 1, 37, 20, 0], jnp.int32)
    got = np.asarray(ops.paged_attention(_q(rng), kw, vw, table, spec,
                                         pos=pos, use_kernel=True,
                                         interpret=True))
    assert np.isnan(got[1, 0, :G]).all(), "NaR must reach its query group"
    assert np.isfinite(got[1, 0, G:]).all(), "other kv heads stay clean"
    assert np.isfinite(got[0]).all() and np.isfinite(got[2:]).all(), \
        "other sequences must stay clean (pages are not shared)"


def test_paged_rejects_prefill_shapes():
    rng = np.random.default_rng(6)
    spec = formats.get("takum8")
    kw, vw, table = _pool_and_table(rng, spec)
    q = jnp.asarray(rng.normal(size=(B, 2, H, HD)), jnp.float32)
    with pytest.raises(ValueError, match="decode-only"):
        ops.paged_attention(q, kw, vw, table, spec,
                            pos=jnp.zeros((B,), jnp.int32))


def test_layers_paged_branch_appends_and_routes(monkeypatch):
    """models/layers.py paged-cache plumbing: the append lands at
    (table[b, pos // ps], pos % ps) and kernel vs oracle dispatch agree,
    mirroring the contiguous-cache routing test."""
    import dataclasses
    import jax
    from repro.configs import get_arch
    from repro.models import layers as L

    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="takum16")
    params = L.attn_init(jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads,
                         cfg.n_kv_heads, cfg.hd)
    rng = np.random.default_rng(7)
    b, npages, ps = 2, 7, 16
    npg = 3
    words = takum.float_to_takum(
        rng.normal(size=(npages, ps, cfg.n_kv_heads, cfg.hd))
        .astype(np.float32).swapaxes(1, 2), 16)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([33, 17], jnp.int32)
    cache = {"k": words, "v": words[::-1], "table": table, "pos": pos,
             "start": jnp.asarray([0, 4], jnp.int32)}
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    positions = np.asarray(pos)[:, None]

    outs = {}
    for use in (True, False):
        monkeypatch.setattr(L, "KV_ATTN_KERNEL", use)
        out, newc = L.attention(params, x, cfg, jnp.asarray(positions),
                                cache=cache)
        outs[use] = np.asarray(out)
        np.testing.assert_array_equal(np.asarray(newc["pos"]),
                                      np.asarray(pos) + 1)
        assert newc["k"].dtype == word_dtype(16)
        assert newc["k"].shape == (npages, cfg.n_kv_heads, ps, cfg.hd)
        # the append hit exactly (table[b, pos // ps], pos % ps)
        for i in range(b):
            pg = int(table[i, int(pos[i]) // ps])
            off = int(pos[i]) % ps
            assert not np.array_equal(
                np.asarray(newc["k"][pg, :, off]),
                np.asarray(words[pg, :, off]))
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5,
                               atol=2e-5)


def test_layers_paged_branch_is_decode_only():
    import dataclasses
    import jax
    from repro.configs import get_arch
    from repro.models import layers as L

    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="takum8")
    params = L.attn_init(jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads,
                         cfg.n_kv_heads, cfg.hd)
    cache = {"k": jnp.zeros((3, cfg.n_kv_heads, 8, cfg.hd), jnp.uint8),
             "v": jnp.zeros((3, cfg.n_kv_heads, 8, cfg.hd), jnp.uint8),
             "table": jnp.zeros((1, 2), jnp.int32),
             "pos": jnp.zeros((1,), jnp.int32),
             "start": jnp.zeros((1,), jnp.int32)}
    x = jnp.zeros((1, 4, cfg.d_model), jnp.float32)
    with pytest.raises(ValueError, match="decode-only"):
        L.attention(params, x, cfg, jnp.zeros((1, 4), jnp.int32),
                    cache=cache)
