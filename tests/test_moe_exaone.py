"""The expert layer of a K-EXAONE-shaped model at a tiny size on the CPU:
per-layer types in the layer plan, the sigmoid router against a direct
transcription of its equations, dropless routing that makes a token's
result its own, the shares of an expert-parallel deployment adding up to
the whole layer, and the grouped expert matmul kernel against its
oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.models import model, moe, transformer

SLIDING, FULL = "sliding_attention", "full_attention"
LLLG = [SLIDING] * 3 + [FULL]


def tiny(**kw):
    """Layer 0 dense and sliding, then experts: MoE L, L, G and L, L, L,
    G; 32 routed experts (8 per token) of which 8 are held, window 8."""
    base = dict(name="tiny-exaone", family="moe", n_layers=8, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96, vocab=256,
                n_experts=32, top_k=8, experts_first=8, experts_held=8,
                d_expert=32, n_shared_experts=1, router_scoring="sigmoid",
                routed_scaling=2.5, layer_types=LLLG * 2,
                mlp_layer_types=["dense"] + ["sparse"] * 7, window=8,
                norm_placement="post", norm_eps=1e-5, qk_norm=True,
                rope_full=False, rope_base=1e6, dtype="f32",
                kv_quant="none")
    base.update(kw)
    return ModelConfig(**base)


def test_layer_plan_follows_published_types():
    assert transformer.layer_plan(tiny()) == [
        (("self:local",), 1),
        (("moe:local", "moe:local", "moe"), 1),
        (("moe:local", "moe:local", "moe:local", "moe"), 1)]
    # all 48 published layers: the LLLG periods scan
    full = tiny(n_layers=48, layer_types=LLLG * 12,
                mlp_layer_types=["dense"] + ["sparse"] * 47)
    assert transformer.layer_plan(full) == [
        (("self:local",), 1),
        (("moe:local", "moe:local", "moe"), 1),
        (("moe:local", "moe:local", "moe:local", "moe"), 11)]
    cfg = tiny()
    assert [transformer._window_for(cfg, k) for k in
            ("self:local", "moe:local", "moe")] == [8, 8, 0]
    with pytest.raises(ValueError, match="layer_types"):
        tiny(n_layers=4)


def _moe_params(cfg, key=0):
    return moe.moe_init(jax.random.PRNGKey(key), cfg.d_model, cfg.d_expert,
                        cfg.n_experts, held=cfg.experts_held,
                        n_shared=cfg.n_shared_experts, bias=True)


def test_router_matches_its_equations():
    """s = sigmoid(x W), choice by top-k of s + b (the bias picks, never
    weighs), gates s / sum(s) * 2.5 over the chosen."""
    cfg = tiny()
    p = _moe_params(cfg)
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(5),
                                         (cfg.n_experts,)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    idx, g, aux = moe.route(p, x, cfg)
    xs, w, b = (np.asarray(a, np.float64) for a in
                (x, p["router"], p["router_bias"]))
    s = 1.0 / (1.0 + np.exp(-(xs @ w)))
    for t in range(16):
        choice = np.argsort(-(s[t] + b), kind="stable")[:cfg.top_k]
        assert set(np.asarray(idx[t]).tolist()) == set(choice.tolist())
        want = s[t, np.asarray(idx[t])] / s[t, choice].sum() * 2.5
        np.testing.assert_allclose(np.asarray(g[t]), want, rtol=1e-5)
    assert float(aux) == 0.0
    # the bias changed the choice somewhere, so it is not a no-op
    plain = np.argsort(-s, axis=1)[:, :cfg.top_k]
    assert any(set(plain[t]) != set(np.asarray(idx[t])) for t in range(16))


def _wire(p):
    q = dict(p)
    for k in ("experts_wg", "experts_w1", "experts_w2"):
        q[k] = ops.ExpertWire.encode(p[k])
    return q


@pytest.mark.parametrize("wire", [False, True])
def test_a_tokens_result_is_its_own(wire):
    """No capacity, no drop: a token's output is bit-identical whatever
    shares its call, in decode-sized and prefill-sized batches."""
    cfg = tiny()
    p = _moe_params(cfg)
    if wire:
        p = _wire(p)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    f = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg)[0])
    whole = np.asarray(f(p, x))
    alone = np.concatenate([np.asarray(f(p, x[:, i:i + 1]))
                            for i in range(24)], axis=1)
    np.testing.assert_array_equal(whole, alone)
    # the same token beside batch-mates that all route alike
    mates = jnp.concatenate([x[:, :1], jnp.zeros_like(x[:, 1:])], axis=1)
    np.testing.assert_array_equal(np.asarray(f(p, mates))[:, 0], whole[:, 0])


def test_shares_add_up_to_the_whole_layer():
    """Four chips holding experts 0-7, 8-15, 16-23, 24-31: their routed
    parts plus the shared expert once give the layer that holds all 32."""
    cfg = tiny(experts_first=0, experts_held=0)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, cfg.d_model))
    whole, _, _ = moe.moe_apply(p, x, cfg)
    shared = moe.L.mlp(p["shared"], x, "swiglu")
    total = shared
    for first in range(0, 32, 8):
        part = dict(p)
        for k in ("experts_wg", "experts_w1", "experts_w2"):
            part[k] = p[k][first:first + 8]
        y, _, _ = moe.moe_apply(part, x, tiny(experts_first=first))
        total = total + (y - shared)
    # four partial sums in another order than one: f32 rounding
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_counters_count_routed_rows_and_calls():
    cfg = tiny()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 1, cfg.d_model))
    _, _, stats = moe.moe_apply(p, x, cfg)
    idx, _, _ = moe.route(p, x.reshape(4, -1), cfg)
    held = (np.asarray(idx) >= 8) & (np.asarray(idx) < 16)
    assert int(stats["moe_rows"]) == held.sum()
    assert int(stats["moe_calls"]) == len(set(np.asarray(idx)[held]))


def _layout_case(counts, held=4, bm=8, k=16, n=32):
    """Rows grouped as ``counts`` rows per expert, random x and words."""
    m = moe.rows_bound(n, 1, held, bm)
    group = np.array([-(-c // bm) * bm for c in counts], np.int32)
    ends = np.cumsum(group)
    n_used = int(ends[-1] // bm)
    te = np.searchsorted(ends, np.arange(m // bm) * bm, side="right")
    te = np.minimum(np.where(np.arange(m // bm) < n_used, te,
                             te[max(n_used - 1, 0)]), held - 1)
    rng = np.random.default_rng(sum(counts))
    x = np.zeros((m, k), np.float32)
    for e, c in enumerate(counts):
        x[ends[e] - group[e]:ends[e] - group[e] + c] = rng.normal(size=(c, k))
    from repro.core import takum
    w = takum.float_to_takum(jnp.asarray(
        rng.normal(size=(held, k, 24)).astype(np.float32) / 4), 8)
    return (jnp.asarray(x), w, jnp.asarray(group),
            jnp.asarray(te, jnp.int32), jnp.asarray([n_used], jnp.int32))


@pytest.mark.parametrize("counts", [[5, 0, 17, 3], [32, 0, 0, 0],
                                    [0, 0, 0, 32], [0, 0, 0, 0]])
def test_expert_matmul_kernel_matches_its_oracle(counts):
    """Interpret-mode kernel == the jnp oracle bit for bit, with an empty
    expert, one expert taking every row, and no rows at all; and the
    layer's path off the TPU (``lax.ragged_dot`` of the decoded words) to
    f32 rounding."""
    x, w, group, te, nu = _layout_case(counts)
    spec = "takum8"
    got = ops.expert_matmul(x, w, te, nu, spec, bm=8, interpret=True)
    _, bn, bk = ops.expert_blocks(x.shape[0], x.shape[1], w.shape[-1])
    xp = jnp.pad(x, ((0, 0), (0, bk - x.shape[1])))
    wp = jnp.pad(w, ((0, 0), (0, bk - w.shape[1]), (0, bn - w.shape[2])))
    want = kref.expert_matmul_ref(xp, wp, te, nu, spec, bm=8, bn=bn,
                                  bk=bk)[:, :w.shape[2]]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    fallback = moe._grouped(x, ops.ExpertWire(w, spec), group, te, nu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(fallback),
                               rtol=1e-5, atol=1e-5)
    if not sum(counts):
        assert not np.asarray(got).any()


def test_quantize_weights_wires_expert_stacks():
    from repro.serve.engine import quantize_weights
    cfg = tiny(kv_quant="takum8")
    params = model.init(jax.random.PRNGKey(0), cfg)
    qp = quantize_weights(params, "takum8", mode="wire", verbose=False)
    blk = qp["groups"][2]["b3"]["moe"]
    for k in ("experts_wg", "experts_w1", "experts_w2"):
        assert isinstance(blk[k], ops.ExpertWire)
        assert blk[k].words.dtype == jnp.uint8 and blk[k].ndim == 4
    # routing stays float32
    assert blk["router"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(blk["router"]),
        np.asarray(params["groups"][2]["b3"]["moe"]["router"]))


def test_decode_rows_do_not_change_with_batch_mates():
    """A paged decode step's logits for one row are bit-identical
    whatever the other rows hold."""
    from repro.serve.engine import quantize_weights
    cfg = tiny(kv_quant="takum8", dtype="bf16")
    params = quantize_weights(model.init(jax.random.PRNGKey(0), cfg),
                              "takum8", mode="wire", verbose=False)
    cache = model.init_paged_cache(cfg, 4, 8, 8, 2)
    for group in cache:
        for node in group.values():
            node["attn"]["table"] = jnp.broadcast_to(
                jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 0]], jnp.int32),
                node["attn"]["table"].shape)
    step = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, cfg, c,
                                                          pos=pos)[0])
    pos = jnp.asarray([[9], [3], [12], [0]], jnp.int32)
    a = step(params, jnp.asarray([[7], [1], [2], [3]]), cache, pos)
    b = step(params, jnp.asarray([[7], [200], [9], [77]]), cache, pos)
    np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])


def test_more_tokens_than_one_grouped_call_route_in_chunks():
    """Past ``TOKEN_CHUNK`` tokens the layer routes chunk by chunk (its
    padding tokens take no rows); each token's result is still its own,
    and the counters add up over the chunks."""
    cfg = tiny()
    p = _wire(_moe_params(cfg))
    n = moe.TOKEN_CHUNK + 88
    x = jax.random.normal(jax.random.PRNGKey(6), (1, n, cfg.d_model))
    f = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))
    whole, _, stats = f(p, x)
    half = n // 2
    (a, _, sa), (b, _, sb) = f(p, x[:, :half]), f(p, x[:, half:])
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.concatenate([a, b], axis=1))
    assert int(stats["moe_rows"]) == int(sa["moe_rows"]) + int(sb["moe_rows"])


def test_param_counts_follow_the_layer_types():
    """At the published widths, K-EXAONE-236B-A23B's 48 layers (dense
    layer 0, then 128 routed experts of 2048 and a shared one) count
    236.6 B parameters, 23.7 B of them active a token, as its name
    says; the experts held here do not change the model's count."""
    cfg = tiny(n_layers=48, d_model=6144, n_heads=64, n_kv_heads=8,
               head_dim=128, d_ff=18432, d_expert=2048, n_experts=128,
               experts_first=0, experts_held=16, vocab=153600,
               layer_types=LLLG * 12,
               mlp_layer_types=["dense"] + ["sparse"] * 47)
    assert 236.5e9 < cfg.param_count() < 236.7e9
    assert 23.6e9 < cfg.active_param_count() < 23.7e9
