"""The ``exaone_moe`` architecture module: found by the configuration's
``"arch"``, its configuration file against the catalog's keys, its
counts pinned at the published widths, its reference against the
program at a tiny size, a tiny harness run of ``kexaone-overload``, and
the readers of the cell's two metrics on synthetic counters and
traces."""

import copy
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import costs
import harness

CELL = "kexaone-overload"
# at these sizes sound runs read 0.33-0.40 in bfloat16, the cell's
# precision (0.0 in float32: every served token the reference's first
# choice), and the int4 control 1.31-3.04 (CPU, seeds 1-3): the limit
# sits between
TINY_LIMIT = 0.5


def _cell():
    spec = harness.load_spec(bench_tiny.ROOT)
    return harness.load_cell(spec, CELL, bench_tiny.ROOT)


def tiny_cfg(**model):
    """The cell's configuration at a tiny size of the same structure:
    dense sliding layer 0, MoE L, L, G and L, L, L, G; 32 routed experts
    (8 per token), 8 held (ids 8-15), window 8."""
    _, cfg, _, _ = _cell()
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                        d_ff=96, d_expert=32, n_experts=32, experts_first=8,
                        experts_held=8, window=8, vocab=512)
    cfg["model"].update(model)
    return cfg


def test_module_is_found_by_arch():
    cell, cfg, arch, tp = _cell()
    assert cfg["arch"] == "exaone_moe" and arch.__name__.endswith(
        "exaone_moe")
    m = arch.model(cfg)
    assert m["vocab"] == 19200 and m["n_layers"] == 8
    assert tp["arrival"]["process"] == "stratified_exponential"


def test_file_keeps_the_catalog_keys():
    """Top-level keys are the published config's, the reduced ones as the
    cut says; the program's ``model`` group agrees with them."""
    _, cfg, _, _ = _cell()
    m = cfg["model"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == \
        (m["n_layers"], m["experts_held"], m["vocab"], 0)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    pairs = [("hidden_size", "d_model"), ("num_attention_heads", "n_heads"),
             ("num_key_value_heads", "n_kv_heads"), ("head_dim", "head_dim"),
             ("intermediate_size", "d_ff"),
             ("moe_intermediate_size", "d_expert"),
             ("num_experts_per_tok", "top_k"),
             ("num_shared_experts", "n_shared_experts"),
             ("routed_scaling_factor", "routed_scaling"),
             ("rms_norm_eps", "norm_eps"), ("sliding_window", "window")]
    for top, field in pairs:
        assert cfg[top] == m[field], (top, field)
    assert m["n_experts"] == cfg["published"]["num_experts"]
    assert cfg["rope_parameters"]["rope_theta"] == m["rope_base"]
    assert m["layer_types"] == cfg["layer_types"][:m["n_layers"]]
    assert m["mlp_layer_types"] == cfg["mlp_layer_types"][:m["n_layers"]]
    assert cfg["scoring_func"] == m["router_scoring"] == "sigmoid"


def test_counts_at_published_widths():
    _, cfg, arch, _ = _cell()
    m = arch.model(cfg)
    d, f, ff = 6144, 2048, 18432
    attn = [(d, 8192), (d, 1024), (d, 1024), (8192, d)]
    assert arch.projection_dims(m) == [
        (8, attn), (1, [(d, ff), (d, ff), (ff, d)]),
        (7, [(d, f), (d, f), (f, d)])]
    # 6 sliding layers read at most 128 keys, 2 full layers all 1000
    flops, nbytes = arch.paged_attention(m, [1000, 64])
    keys = 6 * 128 + 2 * 1000 + 8 * 64
    assert flops == 4.0 * 64 * 128 * keys
    assert nbytes == 2.0 * 8 * 128 * keys + 2 * 2 * 64 * 128 * 4 * 8
    proj = 2.0 * (8 * sum(k * n for k, n in attn) + 3 * d * ff
                  + 7 * 3 * d * f)
    per_tok = proj + 7 * (2.0 * d * 128 + 8 * 16 / 128 * 6 * d * f) \
        + 2.0 * d * 19200
    assert arch.model_flops(m, [1000]) == pytest.approx(
        per_tok + 4.0 * 64 * 128 * (6 * 128 + 2 * 1000), rel=1e-12)
    fl, by = arch.expert_matmul(m, [8, 0, 24])
    assert fl == 2.0 * 32 * 3 * d * f
    assert by == 3 * 3 * d * f + 32 * (2 * d * 2 + 2 * f * 4 + f * 2
                                       + d * 4)


def test_reference_matches_the_paged_program():
    """Chunked prefill on a contiguous cache, then paged decode past the
    window, against the reference's full forward. Both hold K and V as
    takum8 words and compute in float32 (the program's float32 path, not
    the cell's bfloat16), so only the f32 summation order differs: measured
    <= 3e-6 on logits of magnitude ~3.5; 1e-4 leaves room for another
    order of the sums and none for a takum8 rounding step (~4%)."""
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    from repro.serve.paged import PagePool
    _, _, arch, _ = _cell()
    cfg = tiny_cfg(dtype="f32")
    m = arch.model(cfg)
    mc = ModelConfig(**cfg["model"])
    params = arch.build_params(3, m)
    seq = np.random.default_rng(0).integers(0, 512, size=30).tolist()
    n_prompt, ps = 20, 8
    chunk_fn = jax.jit(lambda p, t, c, pos, last: M.prefill_chunk(
        p, t, mc, c, pos=pos, last_idx=last))
    step_fn = jax.jit(lambda p, t, c, pos: M.decode_step(p, t, mc, c,
                                                         pos=pos))
    contig = M.init_cache(mc, batch=1,
                          max_len=(-(-n_prompt // ps) + 1) * ps)
    for c0 in range(0, n_prompt, ps):
        chunk = seq[c0:min(c0 + ps, n_prompt)]
        tok = np.zeros((1, ps), np.int32)
        tok[0, :len(chunk)] = chunk
        row, contig = chunk_fn(params, jnp.asarray(tok), contig,
                               jnp.asarray(c0), jnp.asarray(len(chunk) - 1))
    logits = [np.asarray(row)[0]]
    pool = PagePool(mc, batch=2, num_pages=16, page_size=ps, max_pages=8)
    pages = pool.alloc(-(-len(seq) // ps))
    pool.scatter_prefill(contig, pages[:-(-n_prompt // ps)])
    pool.assign(0, pages, pos=n_prompt)
    pool.push_tables()
    for t in range(n_prompt, len(seq) - 1):
        tok = np.zeros((2, 1), np.int32)
        tok[0, 0] = seq[t]
        lg, pool.cache = step_fn(params, jnp.asarray(tok), pool.cache,
                                 jnp.asarray(pool.pos[:, None].copy()))
        pool.advance([0])
        logits.append(np.asarray(lg)[0])
    rows, calls = pool.note_experts()
    assert rows > 0 and 0 < calls <= rows
    ref = arch.forward_logits(3, m, [seq[:-1]],
                              [np.arange(n_prompt - 1, len(seq) - 1)])[0]
    np.testing.assert_allclose(np.stack(logits), ref, rtol=0, atol=1e-4)


def test_shares_add_up_to_the_uncut_reference_layer():
    """Four chips' routed parts (experts 0-7, 8-15, 16-23, 24-31, from
    the program's expert layer on their words) plus the shared expert
    once equal the reference's layer holding all 32 experts (float32;
    only summation order differs)."""
    from repro.configs.base import ModelConfig
    from repro.kernels.ops import ExpertWire
    from repro.models import moe
    _, _, arch, _ = _cell()
    import reference as R
    whole = arch.model(tiny_cfg(experts_first=0, experts_held=32))
    key = jax.random.PRNGKey(7)
    words = arch.layer_words(key, whole, 1, True)
    w = {k: (v if k.startswith("experts_") else R.decode(v))
         for k, v in words.items()}
    w["router"], w["router_bias"] = arch.router(key, whole, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 64))
    want = arch.routed(x, w, whole) + arch._swiglu(
        x, w["shared_wg"], w["shared_w1"], w["shared_w2"])
    shared = {k: w[f"shared_{k}"] for k in ("wg", "w1", "w2")}
    total = moe.L.mlp(shared, x, "swiglu")
    for first in range(0, 32, 8):
        mc = ModelConfig(**dict(tiny_cfg(experts_first=first,
                                         experts_held=8, dtype="f32")
                                ["model"]))
        p = {"router": w["router"], "router_bias": w["router_bias"]}
        for k in ("experts_wg", "experts_w1", "experts_w2"):
            p[k] = ExpertWire(words[k][first:first + 8])
        y, _, _ = moe.moe_apply(p, x[None], mc)
        total = total + y[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _run_tiny(seed, control=False, trace=False):
    _, _, _, tp = _cell()
    cfg = tiny_cfg()
    cfg["engine"] = {"page_size": 8, "decode_batch": 4, "num_pages": 64,
                     "max_len": 128}
    cfg["check"].update(sample_tokens=16, max_logit_gap=TINY_LIMIT)
    tp = copy.deepcopy(tp)
    tp.update(preroll_s=0.3, postroll_s=10.0)
    tp["arrival"]["rate_per_s"] = 6.0
    tp["prefixes"].update(count=2, length={"dist": "fixed", "value": 16})
    tp["suffix"] = {"dist": "uniform", "min": 2, "max": 6}
    tp["output"] = {"dist": "uniform", "min": 3, "max": 8}
    return harness.run_cell(CELL, seed, 1.0, trace, time.perf_counter(),
                            root=bench_tiny.ROOT, chips_check=False,
                            cfg_override=cfg, traffic_override=tp,
                            control=control, compile_cache=False)


@pytest.mark.usefixtures("tiny_compile_cache")
def test_tiny_cell_is_correct_and_the_control_is_not():
    res = _run_tiny(2, control=True)
    run = res.pop("_run")
    assert res["correct"], res["checks"]
    assert not res["control_correct"], res["control_checks"]
    assert res["failed"] == 0 and run.window_tokens() > 0
    assert run.pool1.moe_calls > run.pool0.moe_calls
    assert set(res["metrics"]) == {"output_tok_per_s", "setup_s"}


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------


def _stats(rows, calls):
    return types.SimpleNamespace(moe_rows=rows, moe_calls=calls)


def test_expert_rows_per_call_reader():
    read = harness.reader("expert_rows_per_call")
    run = types.SimpleNamespace(pool0=_stats(100, 10),
                                pool1=_stats(1100, 135))
    assert read(run) == 1000 / 125
    run.pool1 = _stats(100, 10)
    assert read(run) is None
    # a pool without the counters (the parent's) reads nothing
    run.pool0 = run.pool1 = types.SimpleNamespace(fill_tokens=1)
    assert read(run) is None


def test_expert_matmul_roofline_reader():
    _, cfg, arch, _ = _cell()
    read = harness.reader("expert_matmul_roofline")
    op = "%expert_matmul_kernel_call.{} = f32[1536,2048] custom-call(...)"
    trace = {"window": [0, 10_000], "devices": ["d"],
             "modules": {"d": [["jit__step_paged(1)", 100, 1000],
                               ["jit__step_paged(1)", 2000, 1000],
                               ["jit__prefill_chunk(3)", 4000, 1000]]},
             "ops": {"d": [[op.format(3), 200, 300],
                           [op.format(4), 2100, 500],
                           [op.format(5), 4100, 700],     # prefill: out
                           ["%qmatmul_kernel_call.1 = ...", 300, 50]]},
             "host": []}
    recs = [{"tokens": [0.5, 1.0, 2.0, 3.0]}, {"tokens": [0.2, 1.0, 2.0]},
            {"tokens": [9.5, 9.9]}]
    run = types.SimpleNamespace(
        trace=trace, pool0=_stats(0, 0), pool1=_stats(3000, 300),
        recs=recs, in_window=lambda t: 0.0 <= t < 5.0, arch=arch,
        model=arch.model(cfg), device_kind="TPU v5 lite")
    flops, nbytes = arch.expert_matmul(run.model, [10.0] * 300)
    least, bound = costs.roofline_time(flops, nbytes,
                                       costs.peaks("TPU v5 lite"))
    assert bound == "memory"
    # 3 decode steps in the window, 2 step runs traced, 800 ns of kernel
    assert read(run) == pytest.approx(100.0 * least * 2 / 3 / 800e-9)
    run.trace = None
    assert read(run) is None
    run.trace, run.pool0 = trace, types.SimpleNamespace()
    assert read(run) is None
