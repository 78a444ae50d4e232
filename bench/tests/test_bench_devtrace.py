"""The reduction from a profiler trace to device events, and what the
readers take from it: busy union, idle share, device time per
executable and per kernel."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_clip_by_hand():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 33, 1]]
    assert devtrace.union_ns(ev) == 15 + 5
    assert devtrace.gaps(ev, 0, 40) == [(15, 15), (35, 5)]
    assert devtrace.clip(ev, 8, 31) == [["a", 8, 2], ["b", 8, 7],
                                        ["c", 30, 1]]
    assert devtrace.inside(ev, 5, 34) == [["b", 5, 10], ["d", 33, 1]]
    assert devtrace.host_at([["bench.run", 14, 20], ["bench.submit", 10, 2]],
                            15, 15) == "bench.run"


def test_reduce_a_recorded_cpu_trace(tmp_path):
    """Host spans and the window come through; the CPU has no TPU
    device plane, so no device events."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=devtrace.capture_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.run"):
                f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.after"):
        pass
    jax.profiler.stop_trace()
    red = devtrace.reduce_xplane(devtrace.find_xplane(str(tmp_path)))
    w0, w1 = red["window"]
    assert w1 > w0
    names = [h[0] for h in red["host"]]
    assert names.count("bench.run") == 3 and "bench.after" not in names
    assert all(w0 <= s and s + d <= w1 for _, s, d in red["host"])
    assert red["devices"] == []
    bd = devtrace.busy_and_breakdown(red)
    assert bd["busy_s"] == 0.0 and bd["window_s"] == (w1 - w0) / 1e9


@pytest.fixture(scope="module")
def tpu_slice():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def test_recorded_tpu_slice_busy_and_idle(tpu_slice):
    red = tpu_slice
    dev = red["devices"][0]
    w0, w1 = red["window"]
    ops = sorted(red["ops"][dev], key=lambda e: e[1])
    busy, cur_s, cur_e = 0, None, None       # by hand: sweep the sorted ops
    for _, s, d in ops:
        if cur_s is None or s > cur_e:
            busy += 0 if cur_s is None else cur_e - cur_s
            cur_s, cur_e = s, s + d
        else:
            cur_e = max(cur_e, s + d)
    busy += cur_e - cur_s
    bd = devtrace.busy_and_breakdown(red)
    assert bd["busy_s"] == pytest.approx(busy / 1e9)
    assert bd["window_s"] == pytest.approx((w1 - w0) / 1e9)
    idle = sum(g for _, g in devtrace.gaps(ops, w0, w1))
    assert idle + busy == pytest.approx(w1 - w0)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_recorded_tpu_slice_executables_and_kernels(tpu_slice):
    red = tpu_slice
    dev = red["devices"][0]
    runs = 0
    for part in ("prefill_chunk", "step_paged"):
        by_hand = [d / 1e9 for n, _, d in red["modules"][dev] if part in n]
        assert devtrace.module_calls(red, part) == by_hand
        runs += len(by_hand)
    assert runs
    found = 0
    mods = red["modules"][dev]
    for kernel, want in devtrace.KERNELS.items():
        by_hand = sum(d for n, s, d in red["ops"][dev]
                      if n.startswith(f"%{want}.") and any(
                          a <= s and s + d <= a + b for _, a, b in mods)) / 1e9
        assert devtrace.kernel_time(red, kernel) == pytest.approx(by_hand)
        found += by_hand > 0
    assert found


def test_leaf_ops_and_names():
    ev = [["%while.1 = (s32[]) while(...)", 0, 100],
          ["%qmatmul_kernel_call.3 = f32[8] custom-call(%x)", 10, 20],
          ["%fusion.2 = f32[8] fusion(%qmatmul_kernel_call.3)", 40, 5],
          ["%copy.9 = f32[8] copy(%y)", 120, 3]]
    assert [devtrace.op_name(e[0]) for e in devtrace.leaf_ops(ev)] == \
        ["qmatmul_kernel_call.3", "fusion.2", "copy.9"]
    late = ["%qmatmul_kernel_call.4 = f32[8] custom-call(%x)", 130, 7]
    red = {"window": [0, 200], "devices": ["d"],
           "modules": {"d": [["jit__step_paged(1)", 0, 110]]},
           "ops": {"d": ev + [late]}, "host": []}
    # the second call lies in no executable run wholly inside the window
    assert devtrace.kernel_time(red, "quant_matmul") == 20 / 1e9
