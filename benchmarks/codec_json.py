"""Machine-readable codec/qmatmul throughput -> BENCH_codec.json.

Tracks the perf trajectory of the hot paths this repo optimises, with
every format drawn from the codec registry (``repro.formats``):

* decode / encode / fused fake-quant throughput (elements/s and wire
  GB/s) for the linear takum formats **and the posit baseline**
  (``posit8``/``posit16``, es = 2, 2C dataflow) — the paper's
  takum-vs-posit codec comparison as measured software throughput: the
  takum decode is the fixed-12-bit-window integer reconstruction, the
  posit decode pays the full-width leading-run count and shifts;
* weight-only-quantised matmul at a serving decode shape (small M, big
  weights) for takum8/16 and posit8/16 through the same decode-once
  weight-stationary kernel, reported as effective weight GB/s (weight
  wire bytes / wall time — the roofline quantity serving cares about);
* the same serving shape on the LNS ℓ̄ datapath (``lns_qmatmul`` rows):
  logarithmic-takum wire weights through ``ops.lns_matmul`` with the
  linear-domain accumulator, activations quantised to the LNS grid per
  call (rel_err therefore includes activation quantisation, unlike the
  weight-only ``qmatmul`` rows);
* decode-step attention over the wire-format KV cache
  (``kv_attention`` rows): one-token flash decode at T in {1k,8k},
  takum8/16 and posit8 wire caches vs the f32 cache (the identity
  codec), reporting µs and the bytes-read ratio — the serving-bandwidth
  quantity the fused ``ops.takum_attention`` kernel exists to shrink;
* end-to-end serving (``serving`` rows, schema 4): staggered
  mixed-length requests through the real ``ServeEngine`` on the reduced
  arch — continuous batching over the paged takum-wire KV pool vs the
  lockstep static batch, takum8 vs f32 caches — reporting measured
  tokens/s plus the *analytic* concurrent-sequence capacity at a fixed
  HBM budget (pool page bytes from the codec registry, the
  ``docs/serving.md`` capacity math: takum8 pages fit 4x the sequences
  of f32 in the same budget).

On non-TPU hosts the matmul/attention numbers use the XLA fallback
paths (``use_kernel=False``) — the Pallas interpreter is a correctness
tool, not a performance proxy. Every row records which path ran in its
own ``path`` field (``pallas_mosaic`` / ``pallas_interpret`` /
``xla_fallback``), so BENCH trajectories stay comparable across
backends per row.

Schema 5 additions: every kernel row (qmatmul / lns_qmatmul /
kv_attention / kv_attention_paged) records the ``blocks`` configuration
the call actually used — the autotune table's answer when one exists
(``kernels/autotune.py``; the doc-level ``autotune_mode`` records the
``REPRO_AUTOTUNE`` mode in effect), the hand-picked default otherwise —
plus a paged-attention section and a ``roofline`` section of
per-kernel-row two-term points (``benchmarks/roofline.py``): arithmetic
intensity, the v5e compute/memory bounds and the dominant term, so each
BENCH row carries the bound its tuned blocks are chasing.

Schema 6 additions: shared-prefix serving rows
(``serving["prefix/<fmt>/{on,off}"]``) — a batch of requests sharing a
system-prompt prefix through the scheduler with the radix-tree prefix
cache enabled vs disabled (the PR-5 FIFO baseline), measuring
time-to-first-token per request (``ttft_us_mean``/``ttft_us_max``),
throughput, and ``prefix_hit_rate`` (prompt tokens served from shared
wire pages / prompt tokens submitted; the ``on`` row's rate is the gate
— it must be > 0 on a warm tree) plus the peak ``shared_pages`` count
(pages with more than one owner — the dedup the capacity math credits).

Schema 7 additions: failure-model serving rows (``serving_faults``) —
goodput under page-pressure overload with preemption enabled vs
disabled (``overload/preempt_{on,off}``: wall time, goodput from
completed requests only, TTFT p50/p99, the preemption count the gate
pins ``>= 1`` on vs ``== 0`` off), and containment under seeded NaR
wire-page injection (``inject/nar``: faults injected, owners poisoned,
``token_parity`` — survivors bit-identical to a fault-free run — and
the quarantined page count).

Schema 8 additions: sharded serving rows
(``serving_sharded["tp{1,2,4,8}/{on,off}"]``) — the packed decode step
over a forced-host-device tensor-parallel mesh at tp in {1, 2, 4, 8}
with compressed collectives on (takum16 wire) and off, run in a
subprocess (the XLA host-device count must be set before jax imports).
Each row carries wall and device-normalized throughput (``wall * tp``;
the forced devices time-slice one CPU core, so normalization is what
the gate reads — the ``normalization`` field says so), the analytic
ring-interconnect byte census per step (compression scales it by
``wire_bits/32``), and the per-device pool shard bytes. Gates
(``tools/check_bench_schema.py``): compress-on rows move strictly
fewer interconnect bytes than their f32 twins, and tp=8 normalized
throughput >= tp=1.

Schema 9 additions: observability overhead rows
(``serving_obs["obs/takum8/{off,on}"]``) — the same continuous-batching
workload with ``REPRO_OBS`` unset and at level 1 (tracing + metrics).
The ``on`` row records ``overhead_pct`` (best-round wall time vs the
off row), ``token_parity`` (the on-run's tokens are bit-identical —
observability is token-neutral by contract) and
``recompiles_steady_state`` from the compile watcher, armed after the
warmup round. Gates: overhead <= 5%, recompiles == 0, parity true.

``--smoke`` (also ``run(smoke=True)``) shrinks every shape to
CI-on-CPU size and writes ``BENCH_codec.smoke.json`` instead — a schema
and dataflow gate (every row still exercises its real code path), not a
measurement; CI runs it so the bench cannot silently break.
"""

from __future__ import annotations

import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import formats
from repro.kernels import ops
from benchmarks.common import csv_line, time_fn

OUT_PATH = "BENCH_codec.json"
SMOKE_OUT_PATH = "BENCH_codec.smoke.json"
N_ELEMS = 1 << 21
QMM_M, QMM_K, QMM_N = 64, 2048, 2048
CODEC_FORMATS = ("takum8", "takum16", "posit8", "posit16")
QMM_FORMATS = ("takum8", "takum16", "posit8", "posit16")
LNS_FORMATS = ("lns-takum8", "lns-takum16")
KV_T = (1024, 8192)                    # decode-step context lengths
KV_FORMATS = ("none", "takum8", "takum16", "posit8")
KV_B, KV_HKV, KV_G, KV_HD = 1, 8, 4, 128
SERVE_FORMATS = ("none", "takum8")     # cache formats for the serving rows
SERVE_HBM_BUDGET = 1 << 30             # capacity-math budget (1 GiB)


def _path(use_kernel: bool) -> str:
    if not use_kernel:
        return "xla_fallback"
    return ("pallas_mosaic" if jax.default_backend() == "tpu"
            else "pallas_interpret")


def _codec_section(rng, n_elems: int) -> dict:
    out: dict = {}
    x = jnp.asarray(rng.normal(size=n_elems).astype(np.float32) *
                    np.exp(rng.normal(size=n_elems) * 4).astype(np.float32))
    for spec in map(formats.get, CODEC_FORMATS):
        words = jnp.asarray(
            rng.integers(0, 1 << spec.n, n_elems, dtype=np.int64)
        ).astype(spec.word_dtype)
        dec = jax.jit(lambda w, s=spec: s.decode_tile(w))
        enc = jax.jit(lambda v, s=spec: s.encode_tile(v))
        fq = jax.jit(lambda v, s=spec: s.decode_tile(s.encode_tile(v)))
        t_dec = time_fn(dec, words)
        t_enc = time_fn(enc, x)
        t_fq = time_fn(fq, x)
        for name, t in [("decode", t_dec), ("encode", t_enc),
                        ("fake_quant", t_fq)]:
            out.setdefault(name, {})[spec.name] = {
                "elems": n_elems,
                "us": round(t * 1e6, 2),
                "gelems_per_s": round(n_elems / t / 1e9, 4),
                "wire_gb_per_s": round(n_elems * spec.n / 8 / t / 1e9, 4),
            }
    return out


def _qmatmul_rows(rng, specs, *, op, matmul_fn, shape,
                  extra_fields: dict) -> dict:
    """Shared serving-shape matmul bench: one row per registry spec,
    keyed by ``spec.name``, timing weight-GB/s and rel_err vs f32. The
    timed call passes no ``block=``, so it uses exactly the blocks the
    autotune table resolves — recorded per row via
    ``ops.resolved_blocks``."""
    out: dict = {}
    m, k, nn = shape
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = (rng.normal(size=(k, nn)).astype(np.float32) / np.sqrt(k))
    refo = np.asarray(x) @ w
    for spec in specs:
        w_words = spec.encode_tile(w)
        qmm = jax.jit(lambda a, ww, s=spec: matmul_fn(a, ww, s))
        t = time_fn(qmm, x, w_words)
        got = np.asarray(qmm(x, w_words))
        rel = float(np.linalg.norm(got - refo) / np.linalg.norm(refo))
        wire_bytes = k * nn * spec.bytes_per_elem()
        out[spec.name] = {
            "m": m, "k": k, "n": nn,
            **extra_fields,
            "blocks": list(ops.resolved_blocks(op, spec, (m, k, nn))),
            "us": round(t * 1e6, 2),
            "weight_gb_per_s": round(wire_bytes / t / 1e9, 4),
            "hbm_ratio_vs_f32": round(32 / spec.n, 2),
            "rel_err": rel,
        }
    return out


def _qmatmul_section(rng, use_kernel: bool, shape) -> dict:
    return _qmatmul_rows(
        rng, map(formats.get, QMM_FORMATS), op="qmatmul",
        matmul_fn=lambda a, ww, s: ops.quant_matmul(a, ww, s, use_kernel,
                                                    None),
        shape=shape, extra_fields={"path": _path(use_kernel)})


def _lns_qmatmul_section(rng, use_kernel: bool, shape) -> dict:
    return _qmatmul_rows(
        rng, map(formats.get, LNS_FORMATS), op="lns_qmatmul",
        matmul_fn=lambda a, ww, s: ops.lns_matmul(a, ww, s, "linear",
                                                  use_kernel, None),
        shape=shape,
        extra_fields={"accum": "linear", "path": _path(use_kernel)})


def _kv_attention_section(rng, use_kernel: bool, kv_t) -> dict:
    """Decode-step (tq = 1) attention over the KV cache at serving
    contexts: wire-format caches through ``ops.takum_attention`` vs the
    f32 cache (the identity codec — same op, same kernel).
    ``bytes_read`` counts both K and V over the full context; the ratio
    vs f32 is the HBM-bandwidth win the fused kernel realises."""
    out: dict = {}
    h = KV_HKV * KV_G
    for t in kv_t:
        q = jnp.asarray(
            rng.normal(size=(KV_B, 1, h, KV_HD)).astype(np.float32))
        kf = rng.normal(size=(KV_B, KV_HKV, t, KV_HD)).astype(np.float32)
        vf = rng.normal(size=(KV_B, KV_HKV, t, KV_HD)).astype(np.float32)
        ref_row = None
        for spec in map(formats.get, KV_FORMATS):
            if spec.is_identity:
                kw, vw = jnp.asarray(kf), jnp.asarray(vf)
            else:
                kw, vw = spec.encode_tile(kf), spec.encode_tile(vf)
            bytes_per = spec.bytes_per_elem(jnp.float32)
            attn = jax.jit(lambda a, kk, vv, s=spec, t=t:
                           ops.takum_attention(a, kk, vv, s.n, s, pos=t - 1,
                                               use_kernel=use_kernel))
            tt = time_fn(attn, q, kw, vw)
            got = np.asarray(attn(q, kw, vw))
            if ref_row is None:
                ref_row = got
            rel = float(np.linalg.norm(got - ref_row)
                        / np.linalg.norm(ref_row))
            kv_bytes = 2 * KV_B * t * KV_HKV * KV_HD * bytes_per
            name = "f32" if spec.is_identity else spec.name
            out[f"t{t}/{name}"] = {
                "b": KV_B, "t": t, "h": h, "h_kv": KV_HKV, "hd": KV_HD,
                "blocks": list(ops.resolved_blocks("attention", spec, t)),
                "us": round(tt * 1e6, 2),
                "kv_bytes_read": kv_bytes,
                "bytes_read_ratio_vs_f32": round(bytes_per / 4, 4),
                "kv_gb_per_s": round(kv_bytes / tt / 1e9, 4),
                "rel_err": rel,
                "path": _path(use_kernel),
            }
    return out


PAGED_FORMATS = ("none", "takum8", "posit8")


def _paged_attention_section(rng, use_kernel: bool, kv_t, ps: int) -> dict:
    """Decode-step attention over the *paged* pool — the serving
    scheduler's kernel (``ops.paged_attention``). The KV tile is fixed
    by the pool page size, so ``blocks`` records ``[ps]`` — the
    configuration actually used (there is no free tile knob to sweep;
    the page size is a pool-level choice, docs/serving.md)."""
    out: dict = {}
    h = KV_HKV * KV_G
    for t in kv_t:
        npages = -(-t // ps)
        q = jnp.asarray(
            rng.normal(size=(KV_B, 1, h, KV_HD)).astype(np.float32))
        # head-major pages [P, Hkv, ps, hd]
        kf = rng.normal(size=(KV_B * npages, KV_HKV, ps,
                              KV_HD)).astype(np.float32)
        vf = rng.normal(size=(KV_B * npages, KV_HKV, ps,
                              KV_HD)).astype(np.float32)
        table = jnp.arange(KV_B * npages, dtype=jnp.int32).reshape(
            KV_B, npages)
        ref_row = None
        for spec in map(formats.resolve, PAGED_FORMATS):
            if spec.is_identity:
                kp, vp = jnp.asarray(kf), jnp.asarray(vf)
            else:
                kp, vp = spec.encode_tile(kf), spec.encode_tile(vf)
            bytes_per = spec.bytes_per_elem(jnp.float32)
            attn = jax.jit(lambda a, kk, vv, tb, s=spec, t=t:
                           ops.paged_attention(a, kk, vv, tb, s, pos=t - 1,
                                               use_kernel=use_kernel))
            tt = time_fn(attn, q, kp, vp, table)
            got = np.asarray(attn(q, kp, vp, table))
            if ref_row is None:
                ref_row = got
            rel = float(np.linalg.norm(got - ref_row)
                        / np.linalg.norm(ref_row))
            kv_bytes = 2 * KV_B * t * KV_HKV * KV_HD * bytes_per
            name = "f32" if spec.is_identity else spec.name
            out[f"t{t}/{name}"] = {
                "b": KV_B, "t": t, "h": h, "h_kv": KV_HKV, "hd": KV_HD,
                "page_size": ps, "num_pages": npages,
                "blocks": [ps],
                "us": round(tt * 1e6, 2),
                "kv_bytes_read": kv_bytes,
                "bytes_read_ratio_vs_f32": round(bytes_per / 4, 4),
                "kv_gb_per_s": round(kv_bytes / tt / 1e9, 4),
                "rel_err": rel,
                "path": _path(use_kernel),
            }
    return out


def _serving_section(smoke: bool) -> dict:
    """End-to-end serving rows: continuous batching (paged pool) vs
    lockstep, takum8 vs f32 cache, on the reduced arch. Tokens/s is a
    wall-clock measurement of the *schedule* (CPU numbers gate the
    dataflow, TPU numbers the trajectory); capacity is analytic from
    the registry's bytes-per-element at a fixed HBM budget."""
    import dataclasses

    import jax as _jax

    from repro.configs import get_arch
    from repro.models import model as _model
    from repro.serve.engine import CACHE_SLACK, ServeEngine
    from repro.serve.paged import PagePool, pages_for

    base = get_arch("phi3-medium-14b").reduced
    if smoke:
        lens, max_new, ps, db = (16, 9, 4, 13), 4, 8, 2
    else:
        lens = (512, 73, 260, 41, 480, 150, 300, 210)
        max_new, ps, db = 64, 64, 4
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, base.vocab, n)) for n in lens]
    total_ctx = max(lens) + max_new
    params = _model.init(_jax.random.PRNGKey(0), base)
    out: dict = {}
    for fmt in SERVE_FORMATS:
        cfg = dataclasses.replace(base, kv_quant=fmt)
        spec = formats.resolve(fmt)
        eng = ServeEngine(params, cfg, max_len=total_ctx, page_size=ps,
                          decode_batch=db)
        # analytic capacity at the budget (registry bytes-per-element):
        # lockstep pads every sequence to max(prompt) + max_new + slack;
        # the paged pool pays each request's own bucket + growth pages,
        # so mixed prompt lengths buy extra concurrent sequences even
        # before early EOS
        pool = PagePool(cfg, batch=db, num_pages=2, page_size=ps,
                        max_pages=pages_for(total_ctx, ps),
                        alloc_device=False)
        token_bytes = pool.page_hbm_bytes() // ps
        seq_bytes = pool.page_hbm_bytes() * round(
            sum(pages_for(-(-n // ps) * ps + max_new - 1, ps)
                for n in lens) / len(lens))
        contig_bytes = (total_ctx + CACHE_SLACK) * token_bytes
        name = "f32" if spec.is_identity else spec.name
        for mode in ("lockstep", "continuous"):
            gen = (eng.generate_lockstep if mode == "lockstep"
                   else eng.generate)
            gen(prompts, max_new)                      # compile warmup
            t0 = time.perf_counter()
            outs = gen(prompts, max_new)
            dt = time.perf_counter() - t0
            new_toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
            row = {
                "n_requests": len(prompts),
                "max_new": max_new,
                "page_size": ps,
                "decode_batch": db,
                "us": round(dt * 1e6, 2),
                "tokens_per_s": round(new_toks / dt, 2),
                "hbm_budget": SERVE_HBM_BUDGET,
                "capacity_at_budget": SERVE_HBM_BUDGET // (
                    seq_bytes if mode == "continuous" else contig_bytes),
                "seq_kv_bytes": (seq_bytes if mode == "continuous"
                                 else contig_bytes),
                "hbm_ratio_vs_f32": round(
                    spec.bytes_per_elem(jnp.float32) / 4, 4),
                "path": "scheduler" if mode == "continuous" else "lockstep",
            }
            if mode == "continuous":
                pstats = eng.scheduler().pool.stats()
                row["peak_pages"] = pstats.peak_in_use
            out[f"{mode}/{name}"] = row
    return out


def _prefix_serving_rows(smoke: bool) -> dict:
    """Shared-prefix serving: every request starts with the same system
    prompt. With the prefix cache on, the warm tree serves those pages
    as shared wire words (one physical copy, refcounted), so prefill
    skips straight to each request's private tail — lower TTFT and a
    nonzero prefix hit rate vs the cache-off (PR-5 FIFO) baseline. The
    timed pass runs on a warm tree (an untimed round populates it and
    absorbs compilation); parity tests pin that warm-tree outputs stay
    token-identical, so this row is purely a latency/dedup measurement."""
    import dataclasses

    import jax as _jax

    from repro.configs import get_arch
    from repro.models import model as _model
    from repro.serve.engine import ServeEngine

    base = get_arch("phi3-medium-14b").reduced
    if smoke:
        sys_len, tails, max_new, ps, db = 16, (4, 7, 2, 5, 6, 3), 4, 8, 2
    else:
        sys_len = 256
        tails = (73, 41, 150, 210, 30, 90, 120, 55)
        max_new, ps, db = 64, 64, 4
    rng = np.random.default_rng(1)
    sys_prompt = list(rng.integers(0, base.vocab, sys_len))
    prompts = [sys_prompt + list(rng.integers(0, base.vocab, n))
               for n in tails]
    max_len = sys_len + max(tails) + max_new
    cfg = dataclasses.replace(base, kv_quant="takum8")
    params = _model.init(_jax.random.PRNGKey(0), base)
    import statistics

    n_prompt_toks = sum(len(p) for p in prompts)
    out: dict = {}
    for on in (True, False):
        eng = ServeEngine(params, cfg, max_len=max_len, page_size=ps,
                          decode_batch=db, prefix_cache=on)
        # round 0 warms (compilation + tree population); the medians of
        # 3 timed warm-tree rounds resist scheduler-noise on CPU hosts
        ttft_means, ttft_maxs, totals, tps, hit_rounds = [], [], [], [], []
        shared_peak = 0
        for rnd in range(4):
            t0 = time.perf_counter()
            rids = [eng.submit(p, max_new) for p in prompts]
            pool = eng.scheduler().pool
            hits0 = pool.stats().prefix_hit_tokens
            first: dict = {}
            for ev in eng.run():
                if ev.rid not in first:
                    first[ev.rid] = time.perf_counter() - t0
                shared_peak = max(shared_peak, pool.shared_pages())
            dt = time.perf_counter() - t0
            if rnd == 0:
                continue
            ttfts = [first[r] for r in rids]
            new_toks = sum(len(eng.result(r))
                           for r in rids) - n_prompt_toks
            ttft_means.append(sum(ttfts) / len(ttfts))
            ttft_maxs.append(max(ttfts))
            totals.append(dt)
            tps.append(new_toks / dt)
            hit_rounds.append(pool.stats().prefix_hit_tokens - hits0)
        hits = hit_rounds[-1]
        out[f"prefix/takum8/{'on' if on else 'off'}"] = {
            "n_requests": len(prompts),
            "shared_prefix_tokens": sys_len,
            "max_new": max_new,
            "page_size": ps,
            "decode_batch": db,
            "timed_rounds": len(totals),
            "us": round(statistics.median(totals) * 1e6, 2),
            "ttft_us_mean": round(statistics.median(ttft_means) * 1e6, 2),
            "ttft_us_max": round(statistics.median(ttft_maxs) * 1e6, 2),
            "tokens_per_s": round(statistics.median(tps), 2),
            "prefix_hit_tokens": hits,
            "prefix_hit_rate": round(hits / n_prompt_toks, 4),
            "shared_pages_peak": shared_peak,
            "path": "scheduler",
        }
    return out


def _faults_serving_rows(smoke: bool) -> dict:
    """Failure-model serving rows (schema 7). Overload: a pool sized for
    one worst-case request takes low-priority traffic plus a late
    high-priority arrival — with ``preempt=True`` the VIP evicts a
    victim (which resumes bit-identically; the parity suites pin that)
    and its TTFT drops; with ``preempt=False`` it waits head-of-line.
    Goodput counts completed requests' tokens only. Injection: a seeded
    ``FaultInjector`` writes one NaR word into a live wire page; the row
    records the blast radius (owners poisoned, pages quarantined) and
    ``token_parity`` — every surviving request bit-identical to a
    fault-free lockstep run, the containment the chaos suite gates."""
    import dataclasses
    import statistics

    import jax as _jax

    from repro.configs import get_arch
    from repro.models import model as _model
    from repro.serve.engine import ServeEngine
    from repro.serve.faults import FaultInjector
    from repro.serve.paged import pages_for

    base = get_arch("phi3-medium-14b").reduced
    if smoke:
        plen, max_new, ps, db = 8, 6, 8, 2
    else:
        plen, max_new, ps, db = 64, 32, 64, 4
    rng = np.random.default_rng(2)
    cfg = dataclasses.replace(base, kv_quant="takum8")
    params = _model.init(_jax.random.PRNGKey(0), base)
    prompts = [list(rng.integers(0, base.vocab, plen)) for _ in range(3)]
    ppr = pages_for(plen + max_new - 1, ps)      # pages per request
    out: dict = {}

    def overload_round(preempt: bool):
        eng = ServeEngine(params, cfg, max_len=plen + max_new,
                          page_size=ps, decode_batch=db,
                          num_pages=2 * ppr, preempt=preempt)
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new, priority=0) for p in prompts[:2]]
        first: dict = {}
        vip = None
        for n, ev in enumerate(eng.run(), 1):
            if ev.rid not in first:
                first[ev.rid] = time.perf_counter() - t0
            if vip is None and n >= 2:           # VIP lands mid-stream
                vip = eng.submit(prompts[2], max_new, priority=5)
                rids.append(vip)
        dt = time.perf_counter() - t0
        done = [r for r in rids if eng.status(r) == "done"]
        good = sum(len(eng.result(r)) - plen for r in done)
        return eng, rids, first, dt, done, good

    for preempt in (True, False):
        overload_round(preempt)                  # compile + warmup
        eng, rids, first, dt, done, good = overload_round(preempt)
        ttfts = sorted(first.values())
        out[f"overload/preempt_{'on' if preempt else 'off'}"] = {
            "n_requests": len(rids),
            "max_new": max_new,
            "page_size": ps,
            "num_pages": 2 * ppr,
            "us": round(dt * 1e6, 2),
            "goodput_tokens_per_s": round(good / dt, 2),
            "ttft_us_p50": round(statistics.median(ttfts) * 1e6, 2),
            "ttft_us_p99": round(ttfts[-1] * 1e6, 2),
            "preemptions": eng.scheduler().preemptions,
            "completed": len(done),
            "path": "scheduler",
        }

    eng = ServeEngine(params, cfg, max_len=plen + max_new, page_size=ps,
                      decode_batch=db, num_pages=4 * ppr + 1,
                      prefix_cache=False)
    eng.generate([prompts[0]], max_new)          # compile warmup
    rate, seed = 1.0, 0
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new) for p in prompts]
    sched = eng.scheduler()
    sched.injector = FaultInjector(sched.pool, rate=rate, seed=seed,
                                   kind="nar", target="live", max_faults=1)
    for _ in eng.run():
        pass
    dt = time.perf_counter() - t0
    done = [r for r in rids if eng.status(r) == "done"]
    poisoned = [r for r in rids if eng.status(r) == "poisoned"]
    parity = all(
        eng.result(r) == eng.generate_lockstep([prompts[i]], max_new)[0]
        for i, r in enumerate(rids) if r in done)
    out["inject/nar"] = {
        "n_requests": len(rids),
        "max_new": max_new,
        "page_size": ps,
        "fault_rate": rate,
        "fault_seed": seed,
        "kind": "nar",
        "us": round(dt * 1e6, 2),
        "injected": len(sched.injector.injected),
        "poisoned": len(poisoned),
        "unaffected": len(done),
        "token_parity": parity,
        "quarantined_pages": sched.pool.pages_quarantined(),
        "path": "scheduler",
    }
    return out


def _sharded_serving_rows(smoke: bool) -> dict:
    """Sharded serving rows (schema 8), measured by
    ``benchmarks/serve_sharded.py``. On a TPU it runs in this process
    over the real devices: a child could not reach a chip this process
    holds. On the CPU it runs in a subprocess pinned to the CPU, because
    forcing the XLA host-platform device count only works before the
    backend starts, and this process started it long ago. The child
    prints its row dict as the last stdout line; ``#``-prefixed progress
    lines above it surface in our output on failure."""
    import os
    import subprocess
    import sys

    from repro.launch.env import host_env

    if jax.default_backend() == "tpu":
        from benchmarks import serve_sharded
        return serve_sharded.rows(smoke)

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "serve_sharded.py")
    root = os.path.dirname(os.path.dirname(script))
    env = host_env(8)
    env["REPRO_HOST_DEVICES"] = "8"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.join(root, "src"))
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(
            f"serve_sharded.py failed ({out.returncode}):\n"
            f"{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _obs_serving_rows(smoke: bool) -> dict:
    """Observability overhead rows (schema 9): the same continuous-
    batching workload with ``REPRO_OBS`` unset and at level 1 (tracing
    + metrics; level 2's per-tick device sync is a diagnostic mode, not
    a production default, so it is not priced here). The ``on`` row
    carries ``overhead_pct`` (from the best round each — the low-noise
    estimator; medians are reported too), ``token_parity`` (the on-run
    generates bit-identical tokens — the contract the serve-gate suites
    pin) and ``recompiles_steady_state`` (the compile watcher is armed
    after the warmup round; any retrace after that is a defect). The
    off/on rounds are *interleaved* on two live engines, so monotone
    machine-load drift hits both sides equally instead of being billed
    to whichever side ran second. The schema gate holds overhead at
    <= 5% and recompiles at exactly 0."""
    import dataclasses
    import os
    import statistics

    import jax as _jax

    from repro.configs import get_arch
    from repro.models import model as _model
    from repro.serve.engine import ServeEngine

    base = get_arch("phi3-medium-14b").reduced
    if smoke:
        plens, max_new, ps, db, rounds = (4, 7, 11, 6, 9, 13), 4, 8, 2, 5
    else:
        plens = (73, 41, 150, 210, 30, 90, 120, 55)
        max_new, ps, db, rounds = 64, 64, 4, 3
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, base.vocab, n)) for n in plens]
    cfg = dataclasses.replace(base, kv_quant="takum8")
    params = _model.init(_jax.random.PRNGKey(0), base)

    out: dict = {}
    results: dict = {}
    prior = os.environ.get("REPRO_OBS")

    def _set_env(obs_on):
        if obs_on:
            os.environ["REPRO_OBS"] = "1"
        else:
            os.environ.pop("REPRO_OBS", None)

    def _round(eng):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new) for p in prompts]
        n_tokens = 0
        for ev in eng.run():
            n_tokens += ev.token >= 0
        return time.perf_counter() - t0, n_tokens, rids

    try:
        # prefix cache off: every round redoes the same work, so round
        # times are comparable and the delta is pure obs cost
        engines, totals, tps = {}, {}, {}
        for obs_on in (False, True):
            _set_env(obs_on)
            engines[obs_on] = ServeEngine(
                params, cfg, max_len=max(plens) + max_new,
                page_size=ps, decode_batch=db, prefix_cache=False)
            _round(engines[obs_on])   # warmup: compiles + first traces
            if engines[obs_on].obs is not None:
                engines[obs_on].obs.arm_steady()
            totals[obs_on], tps[obs_on] = [], []
        for _ in range(rounds):
            for obs_on in (False, True):
                _set_env(obs_on)
                dt, n_tokens, rids = _round(engines[obs_on])
                totals[obs_on].append(dt)
                tps[obs_on].append(n_tokens / dt)
                results[obs_on] = [engines[obs_on].result(r)
                                   for r in rids]
        for obs_on in (False, True):
            eng = engines[obs_on]
            key = f"obs/takum8/{'on' if obs_on else 'off'}"
            out[key] = {
                "repro_obs": "1" if obs_on else "(unset)",
                "n_requests": len(prompts),
                "max_new": max_new,
                "page_size": ps,
                "decode_batch": db,
                "timed_rounds": rounds,
                "us": round(statistics.median(totals[obs_on]) * 1e6, 2),
                "us_best": round(min(totals[obs_on]) * 1e6, 2),
                "tokens_per_s": round(statistics.median(tps[obs_on]), 2),
                "path": "scheduler",
            }
            if eng.obs is not None:
                w = eng.obs.compile_watcher
                out[key]["recompiles_steady_state"] = \
                    w.steady_state_recompiles
                out[key]["compiles_total"] = w.compiles
                out[key]["trace_spans"] = len(eng.obs.tracer.spans)
                eng.obs.close()
    finally:
        if prior is None:
            os.environ.pop("REPRO_OBS", None)
        else:
            os.environ["REPRO_OBS"] = prior
    on, off = out["obs/takum8/on"], out["obs/takum8/off"]
    on["token_parity"] = results[True] == results[False]
    on["overhead_pct"] = round(
        100.0 * (on["us_best"] - off["us_best"]) / off["us_best"], 2)
    return out


def run(print_fn=print, out_path: str | None = None,
        smoke: bool = False) -> dict:
    from benchmarks import roofline
    from repro.kernels import autotune

    rng = np.random.default_rng(0)
    use_kernel = jax.default_backend() == "tpu"
    if smoke:  # CI-on-CPU shapes: a schema/dataflow gate, not a measurement
        n_elems, qmm_shape, kv_t, paged_ps = 1 << 12, (8, 128, 128), (128,), 16
    else:
        n_elems, qmm_shape, kv_t, paged_ps = (
            N_ELEMS, (QMM_M, QMM_K, QMM_N), KV_T, 64)
    if out_path is None:
        out_path = SMOKE_OUT_PATH if smoke else OUT_PATH
    doc = {
        "schema": 9,
        "smoke": smoke,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "host": platform.machine(),
        "autotune_mode": autotune.mode(),
        **_codec_section(rng, n_elems),
        "qmatmul": _qmatmul_section(rng, use_kernel, qmm_shape),
        "lns_qmatmul": _lns_qmatmul_section(rng, use_kernel, qmm_shape),
        "kv_attention": _kv_attention_section(rng, use_kernel, kv_t),
        "kv_attention_paged": _paged_attention_section(rng, use_kernel,
                                                       kv_t, paged_ps),
        "serving": {**_serving_section(smoke),
                    **_prefix_serving_rows(smoke)},
        "serving_faults": _faults_serving_rows(smoke),
        "serving_sharded": _sharded_serving_rows(smoke),
        "serving_obs": _obs_serving_rows(smoke),
    }
    doc["roofline"] = roofline.kernel_points_from_bench(doc)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    for name in ("decode", "encode", "fake_quant"):
        for fmt, row in doc[name].items():
            print_fn(csv_line(f"codec_json/{name}/{fmt}", row["us"],
                              f"wire_gb_per_s={row['wire_gb_per_s']}"))
    for fmt, row in doc["qmatmul"].items():
        print_fn(csv_line(f"codec_json/qmatmul/{fmt}", row["us"],
                          f"weight_gb_per_s={row['weight_gb_per_s']}"))
    for fmt, row in doc["lns_qmatmul"].items():
        print_fn(csv_line(f"codec_json/lns_qmatmul/{fmt}", row["us"],
                          f"weight_gb_per_s={row['weight_gb_per_s']}"))
    for fmt, row in doc["kv_attention"].items():
        print_fn(csv_line(
            f"codec_json/kv_attention/{fmt}", row["us"],
            f"bytes_read_ratio_vs_f32={row['bytes_read_ratio_vs_f32']}"))
    for fmt, row in doc["kv_attention_paged"].items():
        print_fn(csv_line(
            f"codec_json/kv_attention_paged/{fmt}", row["us"],
            f"bytes_read_ratio_vs_f32={row['bytes_read_ratio_vs_f32']}"))
    for key, row in doc["serving"].items():
        if "prefix_hit_rate" in row:
            extra = (f"ttft_us_mean={row['ttft_us_mean']} "
                     f"prefix_hit_rate={row['prefix_hit_rate']}")
        else:
            extra = (f"tokens_per_s={row['tokens_per_s']} "
                     f"capacity_at_budget={row['capacity_at_budget']}")
        print_fn(csv_line(f"codec_json/serving/{key}", row["us"], extra))
    for key, row in doc["serving_faults"].items():
        if key.startswith("overload/"):
            extra = (f"goodput_tokens_per_s={row['goodput_tokens_per_s']} "
                     f"preemptions={row['preemptions']}")
        else:
            extra = (f"poisoned={row['poisoned']} "
                     f"token_parity={row['token_parity']}")
        print_fn(csv_line(f"codec_json/serving_faults/{key}", row["us"],
                          extra))
    for key, row in doc["serving_sharded"].items():
        print_fn(csv_line(
            f"codec_json/serving_sharded/{key}", row["us"],
            f"tokens_per_s={row['tokens_per_s']} "
            f"interconnect_bytes_per_step="
            f"{row['interconnect_bytes_per_step']}"))
    for key, row in doc["serving_obs"].items():
        extra = f"tokens_per_s={row['tokens_per_s']}"
        if "overhead_pct" in row:
            extra += (f" overhead_pct={row['overhead_pct']} "
                      f"recompiles_steady_state="
                      f"{row['recompiles_steady_state']} "
                      f"token_parity={row['token_parity']}")
        print_fn(csv_line(f"codec_json/serving_obs/{key}", row["us"],
                          extra))
    print_fn(f"# wrote {out_path}")
    return doc


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes; write BENCH_codec.smoke.json")
    ap.add_argument("--out", default=None, help="override output path")
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)
