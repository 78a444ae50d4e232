"""A later configuration, traffic mix, per-layer metric and
architecture join the benchmark as new files plus new ``BENCHMARK.json``
entries: the harness finds each by its name alone."""

import json
import os
import shutil

import pytest

import bench_tiny

pytestmark = pytest.mark.usefixtures("tiny_compile_cache")

# dense_gqa with its unembedding negated, which its own reference
# applies too: the program then serves what the dense reference ranks
# last, so only this module's reference can find the run correct
THROWAWAY_ARCH = '''
import os

import harness
import reference

DENSE = harness.load_arch("dense_gqa", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CALLS = []


def model(cfg):
    CALLS.append("model")
    return DENSE.model(cfg)


def build_params(seed, model):
    CALLS.append("build_params")
    params = DENSE.build_params(seed, model)
    params["unembed"] = -params["unembed"]
    return params


def served_gaps(seed, model, requests, control=False):
    CALLS.append("served_gaps")

    def forward(seqs, rows, control=False):
        return [-x for x in DENSE.forward_logits(seed, model, seqs, rows,
                                                 control)]
    return reference.served_gaps(forward, requests, control)


def model_flops(model, contexts):
    CALLS.append("model_flops")
    return 2.0 * DENSE.model_flops(model, contexts)


projection_dims = DENSE.projection_dims
paged_attention = DENSE.paged_attention
'''


def _copy(tmp_path):
    """A checkout with a copy of ``bench/`` and ``BENCHMARK.json``'s
    entries (the caller writes the file)."""
    root = tmp_path
    bench = root / "bench"
    shutil.copytree(bench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(bench_tiny.ROOT, "src"), root / "src")
    spec = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
    return root, bench, spec


def _add_cell(root, bench, spec, cfg, tp):
    """``cfg`` and ``tp`` as config ``throwaway`` and mix
    ``throwaway-mix``, under the cell ``throwaway-cell``."""
    cfg["model"]["name"] = "throwaway"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(tp))
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "bench/configs/throwaway.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway-cell",
                              "config": "throwaway",
                              "traffic": "throwaway-mix", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root, bench, spec = _copy(tmp_path)
    cfg, tp = bench_tiny.overrides("phi3m-overload")
    (bench / "metrics" / "throwaway_count.py").write_text(
        "def read(run):\n    return float(len(run.window_requests()))\n")
    spec["per_layer"].append({"name": "throwaway_count", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "service", "moves": "output_tok_per_s",
                              "workloads": ["throwaway-cell"]})
    _add_cell(root, bench, spec, cfg, tp)
    res = bench_tiny.harness.run_cell(
        "throwaway-cell", 4, 1.0, True, 0.0, root=str(root),
        chips_check=False, compile_cache=False)
    assert res["correct"]
    assert res["metrics"]["throwaway_count"]["value"] == res["attempted"]
    assert res["metrics"]["throwaway_count"]["unit"] == "count"


def test_new_arch_found_by_name(tmp_path, monkeypatch):
    """Weights, the check's reference and ``step_mfu``'s FLOPs all come
    from the module the configuration names."""
    import devtrace
    harness = bench_tiny.harness
    root, bench, spec = _copy(tmp_path)
    (bench / "arch" / "throwaway.py").write_text(THROWAWAY_ARCH)
    cfg, tp = bench_tiny.overrides("phi3m-overload")
    cfg["arch"] = "throwaway"
    _add_cell(root, bench, spec, cfg, tp)
    res = harness.run_cell("throwaway-cell", 4, 1.0, False, 0.0,
                           root=str(root), chips_check=False,
                           compile_cache=False)
    run = res["_run"]
    calls = run.arch.CALLS
    assert calls.count("build_params") == 1
    assert calls.count("served_gaps") == 1
    assert res["correct"], res["checks"]
    assert res["checks"]["sampled_tokens"]["value"] >= 16
    # step_mfu over one second of decode steps on a v5e
    monkeypatch.setattr(devtrace, "module_calls",
                        lambda trace, name: [1.0] * (name == "step_paged"))
    run.device_kind = "TPU v5 lite"
    got = harness.reader("step_mfu", str(bench))(run)
    assert calls[-1] == "model_flops"
    run.arch = harness.load_arch("dense_gqa", str(root))
    assert got == pytest.approx(2.0 * harness.reader("step_mfu",
                                                     str(bench))(run),
                                rel=1e-12)


@pytest.mark.parametrize("arch", [None, "nonesuch"])
def test_unknown_arch_lists_the_modules(tmp_path, arch):
    root, bench, spec = _copy(tmp_path)
    (bench / "arch" / "throwaway.py").write_text(THROWAWAY_ARCH)
    cfg, tp = bench_tiny.overrides("phi3m-overload")
    cfg.pop("arch")
    if arch:
        cfg["arch"] = arch
    _add_cell(root, bench, spec, cfg, tp)
    with pytest.raises(KeyError) as err:
        bench_tiny.harness.load_cell(spec, "throwaway-cell", str(root))
    msg = err.value.args[0]
    assert "['dense_gqa', 'throwaway']" in msg and repr(arch) in msg
