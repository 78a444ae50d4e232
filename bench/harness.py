"""The benchmark harness: one cell, one seed, one measured window.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs, from the checkout root:

1. set-up (``setup_s``): the device check, the compile cache, the
   weights made on the device from the seed, the engine, the warm-up
   waves that compile every shape the traffic can produce, and the
   pre-roll, open-loop traffic until the decode batch is in steady state;
2. the window: the cell's open-loop schedule through
   ``ServeEngine.submit`` / ``run`` for ``--seconds``; then a post-roll
   that keeps the load on until every request due in the window has its
   first token (at most ``postroll_s``);
3. the check: a seeded sample of the requests the window finished, the
   longest among them, against the float32 reference of the
   configuration's architecture module, after the engine is freed;
4. the result: one JSON line with the cell's end-to-end metrics
   (``--trace 0``) or its per-layer metrics read from a profiler trace of
   the window (``--trace 1``).

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``: ``configs/<file>``,
``traffic/<traffic>.json``, ``metrics/<metric>.py``; and a configuration
names its architecture, ``arch/<arch>.py``, which brings the weights,
the reference and the counts that depend on the model's shape
(``arch/__init__.py``).
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

clock = time.perf_counter


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The data files
# ---------------------------------------------------------------------------


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def load_cell(spec: dict, workload: str, root: str = ROOT):
    """(cell, configuration file, architecture module, traffic
    parameters)."""
    import traffic
    cell = find(spec["workloads"], workload, "workload")
    centry = find(spec["configs"], cell["config"], "config")
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    return cell, cfg, load_arch(cfg.get("arch"), root), \
        traffic.load(cell["traffic"], os.path.join(root, "bench"))


def _exec_file(mod_name: str, path: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(name: Optional[str], root: str = ROOT):
    """The architecture module ``bench/arch/<name>.py`` that a
    configuration names under ``"arch"``, loaded once per process (its
    jitted reference layers keep their compiled programs from run to
    run)."""
    folder = os.path.join(root, "bench", "arch")
    known = sorted(f[:-3] for f in os.listdir(folder)
                   if f.endswith(".py") and not f.startswith("_"))
    if name not in known:
        raise KeyError(f"no architecture module {name!r} (a configuration "
                       f"names one under \"arch\"); known under {folder}: "
                       f"{known}")
    path = os.path.join(folder, f"{name}.py")
    mod_name = "bench_arch_" + name.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(mod_name)
    if mod is None or mod.__file__ != path:
        mod = sys.modules[mod_name] = _exec_file(mod_name, path)
    return mod


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, root: str = BENCH):
    """The ``read(run)`` function of metric ``name``: ``metrics/<name>.py``,
    else the file of the name's part before its first dot (one reader
    for ``ttft_p90_ms.<cell>`` and its kin)."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(root, "metrics", f"{stem}.py")
        if os.path.exists(path):
            mod_name = "bench_metric_" + stem.replace(".", "_").replace("-", "_")
            return _exec_file(mod_name, path).read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{os.path.join(root, 'metrics')}")


# ---------------------------------------------------------------------------
# Device, compile cache, compile events
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    dev = device_info()
    if dev["platform"] != "tpu":
        raise NoChip(f"no TPU found (jax platform {dev['platform']!r})")
    if dev["count"] < n:
        raise NoChip(f"the cell needs {n} chips, jax sees {dev['count']}")
    return dev


def memory_peak() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``.jax_cache/`` of the checkout; every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Every compile-related ``jax.monitoring`` event with its time:
    backend compiles and persistent-cache loads."""

    def __init__(self):
        self.events: List[tuple] = []

    def start(self) -> "CompileLog":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._event)
        return self

    def _dur(self, event: str, duration: float, **_):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.events.append((clock(), event, duration))

    def _event(self, event: str, **_):
        if event.endswith("cache_hits") or event.endswith("cache_misses"):
            self.events.append((clock(), event, 0.0))

    def compiles(self, t0: float = 0.0, t1: float = float("inf")) -> int:
        return sum(1 for t, e, _ in self.events
                   if t0 <= t < t1 and "backend_compile" in e)

    def loads(self, t0: float = 0.0, t1: float = float("inf")) -> int:
        return sum(1 for t, e, _ in self.events
                   if t0 <= t < t1 and e.endswith("cache_hits"))

    def seconds(self, kind: str, t0: float = 0.0,
                t1: float = float("inf")) -> float:
        return sum(d for t, e, d in self.events if t0 <= t < t1 and kind in e)


# ---------------------------------------------------------------------------
# The open-loop load generator
# ---------------------------------------------------------------------------


class LoadGen:
    """Submits requests at their due times and advances ``run()`` one
    stream event at a time, stamping everything on ``clock``."""

    def __init__(self, eng, annotate):
        self.eng = eng
        self.annotate = annotate
        self.gen = None
        self.inflight = 0
        self.recs: List[dict] = []
        self.by_rid: Dict[int, dict] = {}

    def submit(self, req, due: float) -> dict:
        from repro.serve.paged import AdmissionError
        rec = {"due": due, "plen": len(req.prompt), "max_new": req.max_new,
               "group": req.group, "segment": req.segment, "tokens": [],
               "status": None, "rid": None, "prompt": req.prompt}
        with self.annotate("bench.submit"):
            rec["t_submit"] = clock()
            try:
                rec["rid"] = self.eng.submit(req.prompt, req.max_new)
            except (AdmissionError, ValueError) as e:
                rec["status"] = f"refused: {e}"
        self.recs.append(rec)
        if rec["rid"] is not None:
            self.by_rid[rec["rid"]] = rec
            self.inflight += 1
        return rec

    def step(self) -> bool:
        """Advance the serving loop by one stream event; False when it
        has nothing in flight."""
        if self.inflight == 0:
            return False
        if self.gen is None:
            self.gen = self.eng.run()
        with self.annotate("bench.run"):
            ev = next(self.gen, None)
        if ev is None:
            self.gen = None
            return self.inflight > 0
        rec = self.by_rid[ev.rid]
        if ev.status == "ok":
            rec["tokens"].append(ev.t)
        if ev.done:
            rec["status"] = "done" if ev.status == "ok" else ev.status
            rec["t_done"] = ev.t
            self.inflight -= 1
        return True

    def drain(self) -> None:
        while self.step():
            pass

    def admit_times(self) -> None:
        """Fill ``t_admit`` from the engine's per-request timing."""
        for rec in self.recs:
            if rec["rid"] is None or rec["status"] not in (None, "done"):
                continue
            try:
                tm = self.eng.timing(rec["rid"])
                st = self.eng.status(rec["rid"])
            except KeyError:
                continue
            if st != "queued":
                rec["t_admit"] = rec["t_submit"] + tm.queue_ms / 1e3


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


class Run:
    """What the metric readers see: the cell, its architecture module and
    sizes, every request's stamps, the window, the pool counters and the
    reduced trace (``None`` without ``--trace 1``)."""

    def __init__(self, cell, cfg, arch, seconds):
        self.cell = cell
        self.cfg = cfg
        self.arch = arch
        self.model = arch.model(cfg)
        self.engine = cfg["engine"]
        self.seconds = seconds
        self.t0 = self.t1 = 0.0
        self.recs: List[dict] = []
        self.setup_s = 0.0
        self.setup_split: Dict[str, float] = {}
        self.pool0 = self.pool1 = None
        self.trace: Optional[dict] = None
        self.peak = None
        self.device_kind = ""

    # -- views the readers share --------------------------------------------
    def window_requests(self) -> List[dict]:
        return [r for r in self.recs if r["segment"] == "window"]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def ttfts_ms(self) -> List[float]:
        """TTFT from the due time of every request due in the window;
        ``inf`` for one with no first token."""
        out = []
        for r in self.window_requests():
            out.append(1e3 * (r["tokens"][0] - r["due"]) if r["tokens"]
                       else float("inf"))
        return out

    def window_tokens(self) -> int:
        return sum(1 for r in self.recs for t in r["tokens"]
                   if self.in_window(t))

    def active_at(self, t: float) -> int:
        """Requests admitted and not yet finished at ``t``."""
        return sum(1 for r in self.recs if r.get("t_admit", t + 1) <= t
                   and r.get("t_done", float("inf")) > t)

    def window_gaps_ms(self) -> List[float]:
        out = []
        for r in self.recs:
            ts = r["tokens"]
            out += [1e3 * (b - a) for a, b in zip(ts, ts[1:])
                    if self.in_window(b)]
        return out


def percentile(vals: List[float], q: float) -> float:
    """Nearest-rank percentile: the value at rank ceil(q / 100 * n)."""
    if not vals:
        return float("nan")
    s = sorted(vals)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]


def build(run: Run, seed: int):
    """Weights and engine."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.serve.engine import ServeEngine
    t = clock()
    params = run.arch.build_params(seed, run.model)
    jax.block_until_ready(params)
    t_w = clock() - t
    cfg = run.cfg
    e = cfg["engine"]
    eng = ServeEngine(params, ModelConfig(**cfg["model"]),
                      max_len=e["max_len"], page_size=e["page_size"],
                      decode_batch=e["decode_batch"],
                      num_pages=e["num_pages"], seed=seed, now_fn=clock)
    eng.scheduler()               # the pool is made here
    return eng, t_w


def sample_for_check(run: Run, seed: int, target_tokens: int,
                     max_requests: int) -> List[dict]:
    """The longest finished request, then seeded draws of the others
    until ``target_tokens`` served tokens are in the sample."""
    import numpy as np
    done = [r for r in run.recs
            if r["status"] == "done" and r["segment"] != "warmup"
            and r.get("t_done", 0.0) >= run.t0]
    if not done:
        return []
    done.sort(key=lambda r: (-(r["plen"] + len(r["tokens"])), r["rid"]))
    pick = [done[0]]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    for i in rng.permutation(len(done) - 1) + 1:
        if sum(len(r["tokens"]) for r in pick) >= target_tokens \
                or len(pick) >= max_requests:
            break
        pick.append(done[int(i)])
    return pick


def serve_window(eng, run: Run, tobj, annotate, trace_dir,
                 setup_t0: float) -> None:
    """Warm-up waves, then pre-roll, window and post-roll on one
    open-loop load generator. With ``trace_dir`` the profiler runs from two
    seconds before the window to the end of the post-roll, and the
    window is marked by a ``bench.window`` span."""
    import jax
    load = LoadGen(eng, annotate)
    t = clock()
    for wave in tobj.warmup:
        for req in wave:
            load.submit(req, clock())
        load.drain()
    run.setup_split["warmup_s"] = clock() - t
    pool = eng.scheduler().pool
    arrivals = sorted(tobj.arrivals, key=lambda r: r.due)
    n_window = len(tobj.segment("window"))
    run.t0 = clock() + tobj.params["preroll_s"]
    run.t1 = run.t0 + run.seconds
    hard_end = run.t1 + tobj.params["postroll_s"]
    i, tracing, span, phase = 0, False, None, "pre"
    while True:
        now = clock()
        if trace_dir and not tracing and now >= run.t0 - 2.0:
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_mod().capture_options())
            tracing = True
        if phase == "pre" and now >= run.t0:
            phase, run.pool0 = "window", pool.stats()
            if trace_dir:
                span = annotate("bench.window")
                span.__enter__()
        if phase == "window" and now >= run.t1:
            phase, run.pool1 = "post", pool.stats()
            if span is not None:
                span.__exit__(None, None, None)
        if phase == "post":
            due = [r for r in load.recs if r["segment"] == "window"]
            if now >= hard_end or (len(due) == n_window and all(
                    r["tokens"] or r["status"] for r in due)):
                break
        while i < len(arrivals) and run.t0 + arrivals[i].due <= now:
            load.submit(arrivals[i], run.t0 + arrivals[i].due)
            i += 1
        if not load.step():
            nxt = run.t0 + arrivals[i].due if i < len(arrivals) else hard_end
            time.sleep(max(0.0, min(nxt - clock(), 0.005)))
    if tracing:
        jax.profiler.stop_trace()
    load.admit_times()
    run.recs = load.recs
    run.setup_s = run.t0 - setup_t0
    run.setup_split["preroll_s"] = tobj.params["preroll_s"]


def trace_mod():
    import devtrace
    return devtrace


def check(run: Run, cfg: dict, seed: int, sample: List[tuple],
          control: bool = False):
    """``(checks, control_checks)``: the compared numbers, each with its
    limit, for the tokens the program served, against the reference of
    ``run.arch``; with ``control=True`` the same numbers for the control's
    picks put in the program's place (the reference with int4 weights
    choosing its own first token at each served position), else
    ``None``. :func:`correct_from` judges both."""
    import numpy as np
    lim = cfg["check"]
    vocab = run.model["vocab"]
    if not sample:
        empty = {"sampled_tokens": {"value": 0, "limit": lim["sample_tokens"]}}
        return empty, (dict(empty) if control else None)
    served = sum(len(g) for _, g in sample)
    gaps, ctl_gaps = run.arch.served_gaps(seed, run.model, sample, control)

    def numbers(picks_in_vocab: bool, gap_list) -> dict:
        return {"max_logit_gap": {"value": float(max(float(np.max(g))
                                                     for g in gap_list)),
                                  "limit": lim["max_logit_gap"]},
                "tokens_out_of_vocab": {"value": 0 if picks_in_vocab else 1,
                                        "limit": 0},
                "sampled_tokens": {"value": served,
                                   "limit": lim["sample_tokens"]}}

    in_vocab = all(0 <= t < vocab for _, g in sample for t in g)
    return numbers(in_vocab, gaps), \
        (numbers(True, ctl_gaps) if control else None)


def correct_from(checks: dict) -> bool:
    ok = True
    for name, c in checks.items():
        if name == "sampled_tokens":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             setup_t0: float, root: str = ROOT, chips_check: bool = True,
             spec: Optional[dict] = None, cfg_override: Optional[dict] = None,
             traffic_override: Optional[dict] = None, control: bool = False,
             compile_cache: bool = True, check_outputs: bool = True) -> dict:
    """Everything but the printing; returns the result object with an
    extra ``"_log"`` list of earlier lines."""
    import jax
    import traffic as traffic_lib
    spec = spec or load_spec(root)
    cell, cfg, arch, tparams = load_cell(spec, workload, root)
    if cfg_override:
        cfg, arch = cfg_override, load_arch(cfg_override.get("arch"), root)
    tparams = traffic_override or tparams
    dev = require_chips(cell["chips"]) if chips_check else device_info()
    cache_dir = enable_compile_cache(root) if compile_cache else "off"
    os.environ.setdefault("REPRO_AUTOTUNE", "1")
    table = os.path.join(root, "src", "repro", "kernels",
                         "autotune_defaults.json")
    if os.path.exists(table):
        os.environ["REPRO_AUTOTUNE_CACHE"] = table
    complog = CompileLog().start()
    lines = [f"device {dev}; compile cache {cache_dir}"]
    run = Run(cell, cfg, arch, seconds)
    run.device_kind = dev["kind"]
    eng, run.setup_split["weights_s"] = build(run, seed)
    tobj = traffic_lib.generate(tparams, seed, run.model["vocab"], seconds,
                                cfg["engine"]["page_size"])
    trace_dir = None
    if trace:
        # a directory of this run's own: two runs from one checkout (the
        # tests' workers) must not read or delete each other's trace
        parent = os.path.join(root, "bench_out", "trace")
        os.makedirs(parent, exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix=f"{workload}.", dir=parent)
    serve_window(eng, run, tobj, jax.profiler.TraceAnnotation, trace_dir,
                 setup_t0)
    run.setup_split["compile_s"] = complog.seconds("backend_compile", 0, run.t0)
    run.setup_split["cache_load_s"] = complog.seconds("cache_retrieval", 0,
                                                      run.t0)
    run.setup_split["other_s"] = run.setup_s - sum(
        v for k, v in run.setup_split.items()
        if k in ("weights_s", "warmup_s", "preroll_s"))
    win = run.window_requests()
    lateness = sorted(r["t_submit"] - r["due"] for r in win)
    lines.append(
        "setup_s split: " + ", ".join(f"{k} {v:.3f}" for k, v in
                                      run.setup_split.items())
        + f"; backend compiles before the window {complog.compiles(0, run.t0)}"
        f", persistent-cache loads {complog.loads(0, run.t0)}")
    lines.append(
        f"window: {len(win)} requests due, {run.window_tokens()} tokens, "
        f"{len(run.window_gaps_ms())} gaps; active requests "
        f"{run.active_at(run.t0)} at the start, {run.active_at(run.t1)} at "
        f"the end; generator lateness p50 "
        f"{1e3 * percentile(lateness, 50):.3f} ms, max "
        f"{1e3 * (lateness[-1] if lateness else 0):.3f} ms")
    lines.append(f"compiles inside the window: "
                 f"{complog.compiles(run.t0, run.t1)} backend compiles, "
                 f"{complog.loads(run.t0, run.t1)} persistent-cache loads")
    if trace:
        red = trace_mod().reduce_xplane(trace_mod().find_xplane(trace_dir))
        run.trace = red
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.peak = memory_peak()
    sample = sample_for_check(run, seed, cfg["check"]["sample_tokens"],
                              cfg["check"]["max_requests"]) \
        if check_outputs else []
    pairs = []
    for r in sample:
        out = eng.result(r["rid"])
        pairs.append((out[:r["plen"]], out[r["plen"]:]))
    del eng
    gc.collect()
    t = clock()
    checks, control_checks = check(run, cfg, seed, pairs, control)
    lines.append(f"check: {len(pairs)} requests, "
                 f"{sum(len(g) for _, g in pairs)} served tokens, reference "
                 f"{clock() - t:.1f} s")
    metrics = {}
    for m in metrics_for(spec, workload, trace):
        val = reader(m["name"], os.path.join(root, "bench"))(run)
        if val is not None and not math.isfinite(val):
            lines.append(f"metric {m['name']} left out: {val} (a request "
                         f"due in the window had no first token)")
        elif val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": run.peak}
    result = {"correct": correct_from(checks), "attempted": len(win),
              "failed": sum(1 for r in win if r["status"] not in
                            (None, "done")),
              "metrics": metrics, "device": device}
    if trace:
        bd = trace_mod().busy_and_breakdown(run.trace)
        device["busy_s"] = bd["busy_s"]
        device["window_s"] = bd["window_s"]
        result["breakdown"] = {"device_ops": bd["device_ops"],
                               "idle_gaps": bd["idle_gaps"]}
    if control:
        result["control_correct"] = correct_from(control_checks)
        result["control_checks"] = control_checks
    result["checks"] = checks
    result["_log"] = lines
    result["_run"] = run
    return result


def main(argv=None, setup_t0: Optional[float] = None) -> int:
    setup_t0 = clock() if setup_t0 is None else setup_t0
    ap = argparse.ArgumentParser(description="one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"bench: the program's sources are not at {ROOT}/src")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), setup_t0)
    except NoChip as e:
        log(f"bench: {e}; refusing to run")
        return 3
    for line in res.pop("_log"):
        log(line)
    res.pop("_run")
    print(json.dumps(res), flush=True)
    for name, c in res["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return 0
