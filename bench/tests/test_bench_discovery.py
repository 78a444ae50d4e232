"""A later configuration, traffic mix and per-layer metric join the
benchmark as new files plus new ``BENCHMARK.json`` entries: the harness
finds each by its name alone."""

import json
import os
import shutil

import pytest

import bench_tiny

pytestmark = pytest.mark.usefixtures("tiny_compile_cache")


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root = tmp_path
    bench = root / "bench"
    shutil.copytree(bench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(bench_tiny.ROOT, "src"), root / "src")
    spec = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
    cfg, tp = bench_tiny.overrides("phi3m-overload")
    cfg["model"]["name"] = "throwaway"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(tp))
    (bench / "metrics" / "throwaway_count.py").write_text(
        "def read(run):\n    return float(len(run.window_requests()))\n")
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "bench/configs/throwaway.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway-cell",
                              "config": "throwaway",
                              "traffic": "throwaway-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "throwaway_count", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "service", "moves": "output_tok_per_s",
                              "workloads": ["throwaway-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = bench_tiny.harness.run_cell(
        "throwaway-cell", 4, 1.0, True, 0.0, root=str(root),
        chips_check=False, compile_cache=False)
    assert res["correct"]
    assert res["metrics"]["throwaway_count"]["value"] == res["attempted"]
    assert res["metrics"]["throwaway_count"]["unit"] == "count"
