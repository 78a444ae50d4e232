"""The program's phase spans on the device trace's clock: capture around
a tiny serving run, idle gaps labelled by phase, the per-phase idle
seconds, the decode turnaround, and the readers that take them."""

import os
import sys
import time
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import harness  # noqa: E402
import progspans  # noqa: E402

PHASES = ("housekeeping", "admit", "prefill_chunk", "prefill_sync",
          "prefill_commit", "decode_dispatch", "decode_sync",
          "decode_commit")


# ---------------------------------------------------------------------------
# hand-built events
# ---------------------------------------------------------------------------


def _red(ops, host=(), program=None, window=(0, 100)):
    red = {"window": list(window), "devices": ["d"],
           "modules": {"d": []}, "ops": {"d": [list(e) for e in ops]},
           "host": [list(h) for h in host]}
    if program is not None:
        red["program"] = [list(p) for p in program]
    return red


def test_gap_labels_with_and_without_a_program_span():
    # device busy [0,10) [30,40) [45,100): gaps [10,30) [40,45)
    ops = [["a", 0, 10], ["b", 30, 10], ["c", 45, 55]]
    host = [["bench.run", 0, 100]]
    program = [["sched.decode_sync", 0, 12],
               ["sched.decode_commit", 12, 5],
               ["sched.admit", 17, 13]]
    red = _red(ops, host, program)
    # [10,30): decode_sync 2, decode_commit 5, admit 13 -> admit
    # [40,45): no program span
    assert progspans.idle_gaps(red) == [
        ["bench.run>sched.admit", 20 / 1e9], ["bench.run", 5 / 1e9]]
    # the harness's own labels are unchanged
    assert devtrace.busy_and_breakdown(red)["idle_gaps"] == [
        ["bench.run", 20 / 1e9], ["bench.run", 5 / 1e9]]
    # without the program key the label is the harness span alone
    del red["program"]
    assert progspans.idle_gaps(red)[0][0] == "bench.run"
    # two spans overlapping a gap equally: the shorter (inner) one
    assert progspans.program_at([["sched.outer", 0, 50],
                                 ["sched.inner", 10, 20]], 10, 20) == \
        "sched.inner"
    assert progspans.program_at([["sched.x", 0, 5]], 5, 5) is None


def test_idle_seconds_by_phase_by_hand():
    ops = [["a", 0, 10], ["b", 30, 10], ["c", 45, 55]]
    program = [["sched.decode_sync", 0, 12], ["sched.decode_commit", 12, 5],
               ["sched.admit", 17, 25]]
    ib = progspans.idle_by_phase(_red(ops, program=program))
    assert ib["idle_s"] == pytest.approx(25 / 1e9)
    # [10,30): sync 2 + commit 5 + admit 13; [40,45): admit 2
    assert ib["under_program_s"] == pytest.approx(22 / 1e9)
    assert ib["phases"] == pytest.approx({
        "sched.admit": 15 / 1e9, "sched.decode_commit": 5 / 1e9,
        "sched.decode_sync": 2 / 1e9})
    assert list(ib["phases"]) == ["sched.admit", "sched.decode_commit",
                                  "sched.decode_sync"]
    none = progspans.idle_by_phase(_red(ops))
    assert none["under_program_s"] == 0.0 and none["phases"] == {}


def test_decode_turnarounds_leave_out_cut_spans():
    D, S = progspans.DECODE_DISPATCH, progspans.DECODE_SYNC
    program = [[S, 0, 10],            # starts at the window's edge: cut
               [D, 20, 5], [S, 25, 30],
               ["sched.prefill_chunk", 60, 4],
               [D, 70, 6], [S, 76, 10],
               [D, 95, 10]]           # runs past the window's end: cut
    red = _red([], program=program, window=(0, 100))
    assert progspans.decode_turnarounds(red) == [(55, 76)]
    red["program"][0] = [S, 1, 9]     # now wholly inside
    assert progspans.decode_turnarounds(red) == [(10, 25), (55, 76)]
    assert progspans.decode_turnarounds(_red([])) == []


def _run(trace=None, pool0=None, pool1=None):
    run = harness.Run.__new__(harness.Run)
    run.trace, run.pool0, run.pool1 = trace, pool0, pool1
    return run


def _stats(tokens, capacity, **extra):
    return types.SimpleNamespace(fill_tokens=tokens, fill_capacity=capacity,
                                 **extra)


@pytest.mark.parametrize("pool0,pool1,want", [
    (_stats(100, 400), _stats(400, 1000), 50.0),
    (None, _stats(1, 2), None),
    (_stats(1, 2), None, None),
    # a pool without the counters (an older program) reads nothing
    (types.SimpleNamespace(prefix_hit_tokens=0),
     types.SimpleNamespace(prefix_hit_tokens=5), None),
    # no decode step in the window
    (_stats(7, 9), _stats(7, 9), None),
])
def test_kv_page_fill_reader(pool0, pool1, want):
    got = harness.reader("kv_page_fill")(_run(pool0=pool0, pool1=pool1))
    assert got == (pytest.approx(want) if want is not None else None)


def test_decode_turnaround_reader():
    read = harness.reader("decode_turnaround_ms")
    D, S = progspans.DECODE_DISPATCH, progspans.DECODE_SYNC
    program = [[S, 10, 10], [D, 30, 2e6], [S, 3e6, 1e6],
               [D, 5e6, 4e6], [S, 9.5e6, 1]]
    red = _red([], program=program, window=(0, 1e7))
    # (2e6 + 30 - 20) and (9e6 - 4e6): 3.000005 ms on average
    assert read(_run(trace=red)) == pytest.approx((2e6 + 10 + 5e6) / 2 / 1e6)
    assert read(_run()) is None
    del red["program"]
    assert read(_run(trace=red)) is None


# ---------------------------------------------------------------------------
# a profiler capture around a tiny serving run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax
    from repro.configs import get_arch
    from repro.models import model
    cfg = get_arch("phi3-medium-14b").reduced
    return cfg, model.init(jax.random.PRNGKey(0), cfg)


def _serve(eng, cfg, annotate):
    """Three requests through ``run()``, each stream event taken inside
    ``bench.run`` and followed by a ``bench.consumer`` span."""
    import numpy as np
    rng = np.random.default_rng(0)
    for n in (11, 5, 19):
        eng.submit(list(map(int, rng.integers(0, cfg.vocab, n))), 4)
    gen = eng.run()
    while True:
        with annotate("bench.run"):
            ev = next(gen, None)
        if ev is None:
            return
        with annotate("bench.consumer"):
            time.sleep(0.0005)


@pytest.mark.parametrize("obs_level", ["0", "1"])
def test_phases_reach_the_profiler_trace(tiny, tmp_path, monkeypatch,
                                         obs_level):
    """Every phase is on the host plane; every decode_sync lies inside the
    window; no phase overlaps the consumer's time (none is open across a
    yield); with ``REPRO_OBS=1`` the tracer holds the same phases in the
    same order (one helper feeds both sinks)."""
    import jax
    from repro.obs import SCHED_TRACK
    from repro.serve.engine import ServeEngine
    monkeypatch.setenv("REPRO_OBS", obs_level)
    cfg, params = tiny
    eng = ServeEngine(params, cfg, max_len=48, page_size=8, decode_batch=2)
    annotate = jax.profiler.TraceAnnotation
    _serve(eng, cfg, annotate)              # compiles outside the trace
    if eng.obs is not None:
        eng.obs.tracer.spans.clear()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=devtrace.capture_options())
    with annotate("bench.window"):
        _serve(eng, cfg, annotate)
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    red = progspans.with_program(devtrace.reduce_xplane(path), path)
    names = [n for n, _, _ in sorted(red["program"], key=lambda e: e[1])]
    assert {progspans.PROGRAM_PREFIX + p for p in PHASES} == set(names)
    everything = progspans.program_events(path, -float("inf"),
                                          float("inf"))
    w0, w1 = red["window"]
    syncs = [e for e in everything if e[0] == progspans.DECODE_SYNC]
    assert syncs and all(w0 <= s and s + d <= w1 for _, s, d in syncs)
    consumer = [h for h in red["host"] if h[0] == "bench.consumer"]
    assert consumer
    for _, cs, cd in consumer:
        assert not any(s < cs + cd and cs < s + d
                       for _, s, d in red["program"])
    assert progspans.decode_turnarounds(red)
    if obs_level == "1":
        spans = [s for s in eng.obs.tracer.track_spans(SCHED_TRACK)
                 if s.name != "tick"]
        assert [progspans.PROGRAM_PREFIX + s.name for s in spans] == names
        assert all(s.depth == 1 and s.t1 is not None for s in spans)
    else:
        assert eng.obs is None


@pytest.mark.usefixtures("tiny_compile_cache")
def test_phases_report_on_a_cpu_rehearsal(monkeypatch):
    """``phases.py``'s reduction through the harness at toy size: the
    program spans reach the readers, the report and the slice."""
    import bench_tiny
    import phases
    monkeypatch.setattr(devtrace, "reduce_xplane",
                        phases.keep_program(devtrace.reduce_xplane))
    res = bench_tiny.run("phi3m-overload", 3, trace=True)
    run = res["_run"]
    names = {n for n, _, _ in run.trace["program"]}
    assert progspans.DECODE_SYNC in names
    assert harness.reader("decode_turnaround_ms")(run) > 0
    assert 0 < res["metrics"]["kv_page_fill"]["value"] <= 100
    rep = phases.report(run.trace)
    assert rep["decode_turnaround_ms"]["n"] > 0
    assert set(rep["host_s_by_phase"]) <= {
        progspans.PROGRAM_PREFIX + p for p in PHASES}
    t0, t1 = phases.pick_slice(run.trace)
    cut = progspans.slice_trace(run.trace, t0, t1)
    assert cut["window"] == [t0, t1]
    kinds = [n for n, _, _ in sorted(cut["program"], key=lambda e: e[1])]
    assert kinds[0] == "sched.prefill_chunk"
    assert kinds.count(progspans.DECODE_SYNC) == 2


def test_phases_refuses_without_a_chip():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/phases.py", "--workload", "phi3m-overload",
         "--seed", "1", "--seconds", "1"], cwd=os.path.dirname(BENCH),
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------------------------
# a slice recorded on the chip: one prefill chunk, then two decode steps
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def decode_slice():
    import json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_decode.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_decode_slice_steps_and_phases(decode_slice):
    red = decode_slice
    assert len(devtrace.module_calls(red, "prefill_chunk")) == 1
    assert len(devtrace.module_calls(red, "step_paged")) == 2
    for kernel in devtrace.KERNELS:
        assert devtrace.kernel_time(red, kernel) > 0
    prog = sorted(red["program"], key=lambda e: e[1])
    assert {progspans.PROGRAM_PREFIX + p for p in PHASES} == \
        {n for n, _, _ in prog}
    assert prog[0][0] == "sched.prefill_chunk"
    # the phases run one after another on the scheduler's thread
    assert all(a[1] + a[2] <= b[1] for a, b in zip(prog, prog[1:]))
    syncs = [e for e in prog if e[0] == progspans.DECODE_SYNC]
    dispatches = [e for e in prog if e[0] == progspans.DECODE_DISPATCH]
    assert progspans.decode_turnarounds(red) == [
        (syncs[0][1] + syncs[0][2], dispatches[1][1] + dispatches[1][2])]


def test_recorded_decode_slice_idle_by_phase(decode_slice):
    red = decode_slice
    bd = devtrace.busy_and_breakdown(red)
    ib = progspans.idle_by_phase(red)
    assert ib["idle_s"] == pytest.approx(bd["window_s"] - bd["busy_s"])
    assert 0 < ib["under_program_s"] <= ib["idle_s"]
    assert sum(ib["phases"].values()) == \
        pytest.approx(ib["under_program_s"])
    gaps = progspans.idle_gaps(red)
    assert [d for _, d in gaps] == [d for _, d in bd["idle_gaps"]]
    for (label, _), (host, _) in zip(gaps, bd["idle_gaps"]):
        assert label.startswith(host + ">" + progspans.PROGRAM_PREFIX)
