"""Observability stack: tracer nesting, metrics, exports, scheduler
integration — and the token-neutrality pin.

The contracts under test (``docs/observability.md``):

  * **token-neutrality**: the same workload with ``REPRO_OBS=2`` and
    with it unset generates bit-identical tokens and stream payloads,
    under both attention dispatch paths;
  * **span completeness**: every terminal request's track holds a
    well-nested, fully closed span tree rooted at ``request`` —
    through chunked prefill, preemption/requeue, deadlines, cancel and
    NaR poisoning;
  * **metric honesty**: the pool gauges equal ``PagePool.stats()`` at
    every sampled tick (not just at the end), counters never decrease,
    and the prefix gauges equal ``PrefixCache.stats()``;
  * **exports**: JSONL round-trips; the Chrome ``trace_event`` doc is
    valid JSON with complete-span/instant/metadata events;
  * observation must never *change* fault injection: the injector's
    ledger is identical with and without an observer attached.
"""

import dataclasses
import io
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import model
from repro.obs import (ServeObs, export, level, obs_from_env, phase)
from repro.obs.metrics import CompileWatcher, MetricsRegistry
from repro.obs.trace import (SCHED_TRACK, RequestTiming, Tracer,
                             percentile)
from repro.serve.engine import ServeEngine
from repro.serve.faults import FaultInjector
from repro.serve.scheduler import TERMINAL

PS = 8


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


@pytest.fixture(scope="module")
def base_cfg():
    return get_arch("phi3-medium-14b").reduced


@pytest.fixture(scope="module")
def params(base_cfg):
    return model.init(jax.random.PRNGKey(0), base_cfg)


def _engine(params, cfg, **kw):
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", PS)
    kw.setdefault("decode_batch", 2)
    kw.setdefault("now_fn", FakeClock())
    return ServeEngine(params, cfg, **kw)


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, cfg.vocab, n))) for n in lens]


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_close_track():
    clk = FakeClock()
    tr = Tracer(clk)
    tr.begin(7, "request")
    tr.begin(7, "queued")
    tr.end(7, "queued")
    tr.begin(7, "prefill")
    tr.begin(7, "chunk")
    assert tr.open_depth(7) == 3
    # preemption idiom: close phases, keep the root
    tr.close_track(7, keep=1, preempted=True)
    assert tr.open_depth(7) == 1
    assert all(s.t1 is not None for s in tr.track_spans(7)[1:])
    assert tr.track_spans(7)[-1].args["preempted"] is True
    tr.begin(7, "queued", requeue=True)
    tr.close_track(7)                    # terminal: everything closes
    assert tr.open_depth(7) == 0
    depths = [s.depth for s in tr.track_spans(7)]
    assert depths == [0, 1, 1, 2, 1]     # well-nested by construction


def test_tracer_misnesting_raises():
    tr = Tracer(FakeClock())
    tr.begin(0, "a")
    tr.begin(0, "b")
    with pytest.raises(RuntimeError, match="mis-nesting"):
        tr.end(0, "a")
    with pytest.raises(RuntimeError, match="mis-nesting"):
        tr.end(1, "a")                   # nothing open on that track


def test_phase_helper_sinks():
    """Both sinks off: the shared no-op, nothing built. With a tracer:
    the span on SCHED_TRACK with its args, closed on exit — an
    exception included."""
    assert phase("decode_sync") is phase("admit", queued=3)
    tr = Tracer(FakeClock())
    with phase("decode_dispatch", tr, rows=4):
        assert tr.open_depth(SCHED_TRACK) == 1
    with pytest.raises(ValueError):
        with phase("admit", tr, queued=2):
            raise ValueError("boom")
    spans = tr.track_spans(SCHED_TRACK)
    assert [(s.name, s.args) for s in spans] == [
        ("decode_dispatch", {"rows": 4}), ("admit", {"queued": 2})]
    assert all(s.t1 > s.t0 for s in spans)
    assert tr.open_depth(SCHED_TRACK) == 0


def test_percentile_nearest_rank():
    xs = [10.0, 20.0, 30.0, 40.0]
    assert percentile(xs, 50) == 20.0
    assert percentile(xs, 99) == 40.0
    assert percentile(xs, 0) == 10.0
    assert percentile([], 50) == 0.0
    assert percentile([5.0], 99) == 5.0


def test_request_timing_from_stamps():
    tm = RequestTiming.from_stamps(
        3, "done", t_submit=1.0, t_admit=1.5, t_first=2.0,
        tok_times=[2.0, 2.1, 2.3], t_end=2.4)
    assert tm.queue_ms == pytest.approx(500.0)
    assert tm.ttft_ms == pytest.approx(1000.0)
    assert tm.total_ms == pytest.approx(1400.0)
    assert tm.n_tokens == 3
    assert tm.tbt_ms_p50 == pytest.approx(100.0)
    assert tm.tbt_ms_p99 == pytest.approx(200.0)
    # stamps a failed-in-queue request never gets stay 0.0, not None
    tq = RequestTiming.from_stamps(4, "timeout", t_submit=1.0,
                                   t_admit=None, t_first=None,
                                   tok_times=[], t_end=3.0)
    assert tq.queue_ms == tq.ttft_ms == 0.0 and tq.total_ms > 0


# ---------------------------------------------------------------------------
# metrics unit behaviour
# ---------------------------------------------------------------------------


def test_metrics_registry_kinds_and_rings():
    clk = FakeClock()
    m = MetricsRegistry(ring=4, now_fn=clk)
    m.counter("c").inc()
    m.counter("c").inc(2)
    assert m.counter("c").get() == 3
    with pytest.raises(ValueError, match="negative"):
        m.counter("c").inc(-1)
    with pytest.raises(TypeError, match="counter"):
        m.gauge("c")
    m.gauge("g").set(7)
    m.histogram("h").observe(3.0)
    m.histogram("h").observe(700.0)
    assert m.histogram("h").get() == 2
    assert m.histogram("h").mean == pytest.approx(351.5)
    for tick in range(6):
        m.sample(tick)
    assert len(m.series("c")) == 4       # ring bounded
    assert [v for _, _, v in m.series("c")] == [3.0] * 4
    snap = m.snapshot()
    assert snap == {"c": 3.0, "g": 7.0, "h": 2.0}
    dump = m.dump()
    assert "# TYPE c counter" in dump and 'h_bucket{le="+Inf"} 2' in dump


def test_obs_level_gate(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert level() == 0 and obs_from_env() is None
    monkeypatch.setenv("REPRO_OBS", "1")
    obs = obs_from_env(FakeClock())
    assert isinstance(obs, ServeObs) and not obs.numeric
    obs.close()
    monkeypatch.setenv("REPRO_OBS", "2")
    obs = obs_from_env(FakeClock())
    assert obs.numeric
    obs.close()
    monkeypatch.setenv("REPRO_OBS", "yes")
    with pytest.raises(ValueError, match="REPRO_OBS"):
        level()


def test_compile_watcher_counts_and_arms():
    reg = MetricsRegistry(now_fn=FakeClock())
    with CompileWatcher(registry=reg) as w:
        f = jax.jit(lambda x: x * 2 + 1)
        f(jnp.ones((3,)))                # compile
        before = w.compiles
        assert before >= 1
        f(jnp.ones((3,)))                # cache hit: nothing fires
        assert w.compiles == before
        w.arm()
        f(jnp.ones((3,)))                # still cached
        assert w.steady_state_recompiles == 0
        f(jnp.ones((4,)))                # new shape -> armed recompile
        assert w.steady_state_recompiles >= 1
        assert reg.counter("jax.recompiles_steady_state").get() >= 1
    # stopped: further compiles don't count
    n = w.compiles
    jax.jit(lambda x: x - 5)(jnp.ones((2,)))
    assert w.compiles == n


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------


def test_exports_roundtrip_and_chrome_shape(tmp_path):
    tr = Tracer(FakeClock())
    tr.begin(0, "request", prompt_tokens=4)
    tr.begin(0, "queued")
    tr.end(0, "queued")
    tr.instant(0, "first_token", token=9)
    tr.begin(SCHED_TRACK, "tick", tick=1)
    tr.end(SCHED_TRACK, "tick")
    tr.close_track(0)
    tm = RequestTiming.from_stamps(0, "done", t_submit=0.0, t_admit=0.1,
                                   t_first=0.2, tok_times=[0.2], t_end=0.3)
    recs = export.trace_records(tr, [tm], meta={"run": "unit"})
    assert recs[0] == {"kind": "meta", "run": "unit"}
    p = tmp_path / "t.jsonl"
    export.write_jsonl(p, recs)
    assert export.read_jsonl(p) == recs

    doc = export.chrome_trace(recs)
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} == {"X", "i", "M"}
    names = {e["args"]["name"] for e in events
             if e["name"] == "thread_name"}
    assert names == {"scheduler", "request 0"}
    assert all(e["ts"] >= 0 for e in events if e["ph"] != "M")
    buf = io.StringIO()
    export.write_chrome(buf, recs)
    assert json.loads(buf.getvalue()) == doc


# ---------------------------------------------------------------------------
# scheduler integration: parity, span completeness, metric honesty
# ---------------------------------------------------------------------------


def _serve_chaos(params, cfg, monkeypatch, obs_level):
    """One deterministic chaotic workload; returns (engine, rids,
    event payloads)."""
    if obs_level:
        monkeypatch.setenv("REPRO_OBS", str(obs_level))
    else:
        monkeypatch.delenv("REPRO_OBS", raising=False)
    eng = _engine(params, cfg)
    sched = eng.scheduler()
    sched.injector = FaultInjector(sched.pool, rate=0.3, seed=5,
                                   kind="nar", target="live",
                                   max_faults=2)
    prompts = _prompts(cfg, (3, 11, 19, PS, 5), seed=9)
    rids = [eng.submit(p, 4, priority=i % 3,
                       temperature=0.7 if i == 2 else 0.0, seed=i)
            for i, p in enumerate(prompts)]
    # a deadline far past the fake clock's horizon: exercises the
    # deadline bookkeeping without making the *schedule* depend on how
    # many clock reads happen per tick (obs reads the clock more often;
    # token-neutrality must hold anyway)
    rids.append(eng.submit(_prompts(cfg, (PS,), seed=1)[0], 4,
                           deadline_ms=60_000.0))
    victim = eng.submit(_prompts(cfg, (6,), seed=2)[0], 6)
    rids.append(victim)
    payloads = []
    for i, ev in enumerate(eng.run()):
        payloads.append((ev.rid, ev.token, ev.done, ev.status))
        if i == 3:
            eng.cancel(victim)
    return eng, rids, payloads


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["oracle", "kernel"])
def test_token_neutrality_and_span_completeness(base_cfg, params,
                                                use_kernel, monkeypatch):
    from repro.models import layers as L
    monkeypatch.setattr(L, "KV_ATTN_KERNEL", use_kernel)
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")
    eng_off, rids, pay_off = _serve_chaos(params, cfg, monkeypatch, 0)
    assert eng_off.obs is None
    eng_on, rids_on, pay_on = _serve_chaos(params, cfg, monkeypatch, 2)
    assert rids_on == rids
    # the pin: observability changes nothing observable in the stream
    assert pay_on == pay_off
    for r in rids:
        assert eng_on.status(r) == eng_off.status(r)

    # span completeness: every terminal request's track is a fully
    # closed tree rooted at "request"
    tr = eng_on.obs.tracer
    for r in rids:
        assert eng_on.status(r) in TERMINAL
        assert tr.open_depth(r) == 0
        spans = tr.track_spans(r)
        assert spans and spans[0].name == "request"
        assert all(s.t1 is not None for s in spans)
        assert all(s.t1 >= s.t0 for s in spans)
        # depth-0 root is unique; phase spans nest strictly under it
        assert [s.depth for s in spans].count(0) == 1
    # terminal instants: exactly one per request
    terminals = [i for i in tr.instants if i.name == "terminal"]
    assert sorted(i.track for i in terminals) == sorted(rids)
    # timing rides the done event and the accessor, obs on or off
    for eng in (eng_off, eng_on):
        for r in rids:
            tm = eng.timing(r)
            assert tm.status == eng.status(r)
            assert tm.total_ms > 0


def test_metric_gauges_match_pool_stats_every_tick(base_cfg, params,
                                                   monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "2")
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")
    eng = _engine(params, cfg)
    sched = eng.scheduler()
    m = sched.obs.metrics
    checked = {"n": 0}
    orig = sched._obs_sample

    def sampled():
        orig()
        st = sched.pool.stats()
        assert m.gauge("pool.free").get() == st.free
        assert m.gauge("pool.in_use").get() == st.in_use
        assert m.gauge("pool.shared_pages").get() == st.shared_pages
        assert m.gauge("pool.quarantined").get() == st.quarantined
        for key, val in sched.prefix.stats().items():
            assert m.gauge(f"prefix.{key}").get() == val
        checked["n"] += 1

    monkeypatch.setattr(sched, "_obs_sample", sampled)
    base = _prompts(cfg, (2 * PS,), seed=4)[0]
    r1 = eng.submit(base, 3)
    for ev in eng.run():
        pass
    r2 = eng.submit(base + _prompts(cfg, (3,), seed=5)[0], 3)
    for ev in eng.run():
        pass
    assert checked["n"] == sched._tick > 0
    assert eng.status(r1) == eng.status(r2) == "done"
    # the warm-tree resubmission was a real prefix hit, visible here
    assert m.gauge("prefix.hit_tokens").get() >= PS
    # counters sampled into rings are monotone
    for name in ("sched.requests_submitted", "sched.tokens"):
        vals = [v for _, _, v in m.series(name)]
        assert vals == sorted(vals) and vals[-1] > 0
    # numeric level sampled the NaR scan each tick; the pool is clean
    assert [v for _, _, v in m.series("pool.nar_words")][-1] == 0


def test_scan_nar_counts_injected_words(base_cfg, params, monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "2")
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")
    eng = _engine(params, cfg, prefix_cache=False)
    sched = eng.scheduler()
    rid = eng.submit(_prompts(cfg, (PS,), seed=7)[0], 6)
    stream = eng.run()
    next(stream)                         # prefill done: pages are live
    assert sched.pool.scan_nar() == 0
    inj = FaultInjector(sched.pool, rate=1.0, seed=0, kind="nar",
                        target="live", max_faults=1)
    (rec,) = inj.step(sched._tick)
    # the scan sees the corrupted word while the page is still owned
    assert sched.pool.scan_nar() >= 1
    assert sched.pool.scan_nar(pages=[rec.page]) >= 1
    for ev in stream:                    # NaR logits pin the corruption
        pass
    assert eng.status(rid) == "poisoned"
    assert sched.pool.stats().quarantined >= 1


def test_fault_observer_does_not_change_schedule(base_cfg, params):
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")

    def run(with_observer):
        eng = _engine(params, cfg, prefix_cache=False)
        sched = eng.scheduler()
        inj = FaultInjector(sched.pool, rate=0.5, seed=11, kind="nar",
                            target="live", max_faults=3)
        seen = []
        if with_observer:
            inj.observer = seen.append
        sched.injector = inj
        for p in _prompts(cfg, (PS, 11), seed=8):
            eng.submit(p, 4)
        for ev in eng.run():
            pass
        return inj.injected, seen

    ledger_plain, _ = run(False)
    ledger_obs, seen = run(True)
    assert ledger_obs == ledger_plain    # observation is not targeting
    assert seen == ledger_obs            # and the observer saw each one


PHASES = ("housekeeping", "admit", "prefill_chunk", "prefill_sync",
          "prefill_commit", "decode_dispatch", "decode_sync",
          "decode_commit")


def test_scheduler_phases_on_the_sched_track(base_cfg, params,
                                             monkeypatch):
    """With ``REPRO_OBS=1`` every phase lands on SCHED_TRACK inside its
    tick, closed, and none spans a yield: the consumer's clock reading
    between two stream events lies outside every phase."""
    monkeypatch.setenv("REPRO_OBS", "1")
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")
    clock = FakeClock()
    eng = _engine(params, cfg, now_fn=clock)
    for p in _prompts(cfg, (11, 5, 19)):
        eng.submit(p, 4)
    seen = [clock() for _ in eng.run()]
    spans = eng.obs.tracer.track_spans(SCHED_TRACK)
    phases = [s for s in spans if s.name != "tick"]
    assert {s.name for s in phases} == set(PHASES)
    assert all(s.depth == 1 and s.t1 is not None for s in phases)
    assert not any(s.t0 < t < s.t1 for s in phases for t in seen)
    assert "decode_step" not in {s.name for s in spans}


def test_trace_records_raises_when_off(base_cfg, params, monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    cfg = dataclasses.replace(base_cfg, kv_quant="takum8")
    eng = _engine(params, cfg)
    eng.scheduler()
    with pytest.raises(RuntimeError, match="REPRO_OBS"):
        eng.trace_records()


# ---------------------------------------------------------------------------
# numeric health helpers + config audit
# ---------------------------------------------------------------------------


def test_residual_norms_walks_cache_tree():
    from repro.dist.tp import residual_norms
    tree = {"layers": [{"tp_res_o": jnp.asarray([3.0, 4.0]),
                        "tp_res_m": jnp.zeros((2,)),
                        "attn": {"k": jnp.ones((2, 2))}}]}
    norms = residual_norms(tree)
    assert set(norms) == {"tp_res_o/0", "tp_res_m/0"}
    assert norms["tp_res_o/0"] == pytest.approx(5.0)
    assert norms["tp_res_m/0"] == 0.0


def test_quantize_weights_saturation_counters(monkeypatch):
    from repro.obs.metrics import GLOBAL
    from repro.serve.engine import quantize_weights, _format_max
    from repro import formats
    monkeypatch.setenv("REPRO_OBS", "1")
    fmax = _format_max(formats.resolve_wire("takum8"))
    assert 0 < fmax < float("inf")
    base = GLOBAL.counter("quant.saturated").get()
    params = {"blk": {"w1": jnp.asarray([[1.0, 2.0 * fmax],
                                         [-3.0 * fmax, 0.5]])}}
    quantize_weights(params, "takum8", verbose=False)
    assert GLOBAL.counter("quant.saturated").get() == base + 2


def test_env_knob_audit(monkeypatch):
    from repro.launch.env import KNOBS, audit_line, effective_knobs
    env = {"REPRO_OBS": "2", "REPRO_FAULT_RATE": "1.5"}
    knobs = effective_knobs(env)
    assert set(knobs) == set(KNOBS)
    assert knobs["REPRO_OBS"] == {"value": "2", "set": True}
    assert knobs["REPRO_AUTOTUNE"] == {"value": "1", "set": False}
    line = audit_line(env)
    assert line.startswith("# repro-config ")
    assert "REPRO_OBS=2!" in line        # explicit settings marked
    assert "REPRO_AUTOTUNE=1" in line and "REPRO_AUTOTUNE=1!" not in line
    assert "REPRO_SHARD_COMPRESS=(unset)" in line


def test_watchdog_transition_hook():
    from repro.ft.watchdog import Heartbeat, Watchdog
    clk = FakeClock()
    seen = []
    wd = Watchdog(2, dead_after=1.0, now_fn=clk,
                  on_transition=lambda h, s: seen.append((h, s)))
    for h in (0, 1):
        wd.beat(Heartbeat(host=h, step=0, t=clk(), step_time=0.0))
    assert wd.dead_hosts() == [] and seen == []
    clk.t += 5.0                         # host 1 goes silent
    wd.beat(Heartbeat(host=0, step=1, t=clk(), step_time=0.0))
    assert wd.dead_hosts() == [1]
    assert seen == [(1, "dead")]
    wd.beat(Heartbeat(host=1, step=1, t=clk(), step_time=0.0))
    assert wd.dead_hosts() == []
    assert seen == [(1, "dead"), (1, "alive")]
    assert wd.dead_hosts() == [] and len(seen) == 2   # no re-fire
