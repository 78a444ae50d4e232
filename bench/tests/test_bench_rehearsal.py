"""A short CPU rehearsal of each cell's phases at the family's toy
widths, checking the result line's keys; and the refusal to run
without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny

pytestmark = pytest.mark.usefixtures("tiny_compile_cache")

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("cell,trace", [("phi3m-overload", False),
                                        ("phi3m-overload", True)])
def test_cell_phases_on_cpu(cell, trace):
    res = bench_tiny.run(cell, 3, trace=trace)
    run = res.pop("_run")
    res.pop("_log")
    assert KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    spec = bench_tiny.harness.load_spec(bench_tiny.ROOT)
    names = {m["name"] for m in bench_tiny.harness.metrics_for(spec, cell,
                                                               trace)}
    assert set(res["metrics"]) <= names
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # host-side readers report; device readers find no TPU plane
        assert "prefix_hit_share" in res["metrics"]
        assert "decode_step_ms" not in res["metrics"]
    else:
        assert set(res["metrics"]) == names
        assert res["metrics"]["setup_s"]["value"] > 0
    assert run.window_tokens() > 0
    json.dumps(res)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3m-overload",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _run_py(bench_tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
