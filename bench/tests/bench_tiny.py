"""Tiny stand-ins of the cells for CPU rehearsals: every phase of a run
(weights, warm-up waves, pre-roll, window, post-roll, check, readers)
at the family's toy widths, with short traffic."""

import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

# sound runs read at most 0.280 and the control at least 0.385 at these
# sizes (CPU, seeds 1-8), so the limit sits between; see
# test_bench_control.py
TINY_LIMIT = 0.33


def overrides(cell: str):
    spec = harness.load_spec(ROOT)
    _, cfg, _, tp = harness.load_cell(spec, cell, ROOT)
    cfg, tp = copy.deepcopy(cfg), copy.deepcopy(tp)
    gqa = cfg["model"]["n_heads"] // cfg["model"]["n_kv_heads"]
    cfg["model"].update(n_layers=2, d_model=64, n_kv_heads=1,
                        n_heads=gqa, head_dim=16, d_ff=128, vocab=512)
    cfg["engine"] = {"page_size": 8, "decode_batch": 4, "num_pages": 64,
                     "max_len": 128}
    cfg["check"].update(sample_tokens=16, max_logit_gap=TINY_LIMIT)
    tp.update(preroll_s=0.3, postroll_s=10.0)
    tp["arrival"]["rate_per_s"] = 6.0
    n_pre = tp["prefixes"]["count"]
    tp["prefixes"].update(count=min(n_pre, 2),
                          length={"dist": "fixed", "value": 16})
    tp["suffix"] = {"dist": "uniform", "min": 2, "max": 12}
    tp["output"] = {"dist": "uniform", "min": 3, "max": 8}
    return cfg, tp


def run(cell: str, seed: int, *, trace: bool = False, control: bool = False,
        seconds: float = 1.0, root: str = ROOT, spec=None):
    cfg, tp = overrides(cell)
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            root=root, chips_check=False, spec=spec,
                            cfg_override=cfg, traffic_override=tp,
                            control=control, compile_cache=False)


def compile_cache(directory):
    """Context: JAX's persistent cache in ``directory`` for the runs of
    one test module (every run builds a new engine, whose programs then
    load instead of compiling), switched off again afterwards."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    @contextlib.contextmanager
    def ctx():
        prior = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", str(directory))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_compilation_cache_dir", prior)
            cc.reset_cache()
    return ctx()
