"""Sharded serving throughput rows (BENCH schema 8, ``serving_sharded``).

On a TPU ``benchmarks/codec_json.py`` calls :func:`rows` in its own
process, over the real devices. On the CPU, forcing host devices
requires setting XLA flags before the backend starts, so it runs this
script in a fresh subprocess pinned to the CPU
(``REPRO_HOST_DEVICES=8``) and parses the JSON line it prints last.
Direct use:

    REPRO_HOST_DEVICES=8 PYTHONPATH=src python benchmarks/serve_sharded.py --smoke

Measures the *packed decode step* (the scheduler's ``_step_paged``
executable) at ``tp`` in {1, 2, 4, 8} with compressed collectives on
(takum16 wire) and off: a chained run of ``STEPS`` steps with one
device sync at the end, so the row times the steady-state decode loop,
not per-step host round-trips.

Throughput accounting — read before comparing rows: the forced CPU
"devices" time-slice ONE physical core, so wall-clock cannot improve
with tp there (every shard's FLOPs land on the same core, plus ring-hop
overhead). ``tokens_per_s_wall`` is that raw wall number; on the CPU
``tokens_per_s`` is device-normalized (``wall * tp``) — the throughput
the same step graph delivers when each shard owns a real device,
because each shard executes ``1/tp`` of the model per step. On real
devices the shards run in parallel and ``tokens_per_s`` is the wall
number. The
tp-scaling acceptance gate (``tools/check_bench_schema.py``) reads the
device-normalized number; interconnect bytes are the analytic ring
census from ``ShardPlan.step_interconnect_bytes`` (hop counts x wire
bytes-per-element), where compression is an exact ``n/32`` scaling.
"""

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import model as _model
from repro.serve.engine import ServeEngine
from repro.serve.shard import ShardPlan

WIRE = "takum16"
DECODE_BATCH = 4
MAX_LEN = 64
PAGE_SIZE = 8


def bench_cfg(smoke: bool):
    """Wide enough that per-step matmul work dominates the per-step
    dispatch overhead of an 8-device host mesh — otherwise the
    device-normalized throughput would measure the dispatcher, not the
    model. Heads stay 16/8 so tp=8 still owns one KV head per rank."""
    d = 512 if smoke else 1024
    return dataclasses.replace(
        get_arch("phi3-medium-14b").reduced,
        d_model=d, d_ff=4 * d, head_dim=d // 16,
        n_heads=16, n_kv_heads=8, kv_quant="takum8")


def time_steps(eng, prompts, steps: int):
    """Serve once to warm compile + populate the pool, then time a
    chained run of the packed decode step (single end sync)."""
    eng.generate(prompts, 2)
    sched = eng.scheduler()
    pool = sched.pool
    w = eng.decode_batch
    tok = jnp.zeros((w, 1), jnp.int32)
    pos = jnp.asarray(pool.pos[:, None].copy())
    keys = jnp.zeros((w, 2), jnp.uint32)
    temps = jnp.zeros((w,), jnp.float32)
    top_ps = jnp.ones((w,), jnp.float32)
    cache = pool.cache

    def run(n, cache, t, k):
        for _ in range(n):
            t, cache, k, _bad = eng._step_paged(
                eng.params, t, cache, pos, k, temps, top_ps)
        jax.block_until_ready(t)
        return cache, t, k

    # Warm the exact chained signatures, then keep threading the same
    # (token, key, cache) arrays into the timed run: resetting the token
    # to a fresh host array here would change one input sharding and
    # sneak a recompile (~1.5 s) into the timed region.
    cache, t, k = run(2, cache, tok, keys)
    t0 = time.perf_counter()
    run(steps, cache, t, k)
    return time.perf_counter() - t0


def rows(smoke: bool) -> dict:
    """The ``serving_sharded`` rows, over this process's devices."""
    steps = 8 if smoke else 32
    forced = jax.default_backend() == "cpu"
    cfg = bench_cfg(smoke)
    params = _model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, cfg.vocab - 1, n)))
               for n in (12, 5, 9, 17)]

    out = {}
    tps = [t for t in (1, 2, 4, 8) if t <= jax.device_count()]
    for tp in tps:
        for compress in (WIRE, None):
            plan = ShardPlan(tp=tp, compress=compress)
            eng = ServeEngine(params, cfg, max_len=MAX_LEN,
                              page_size=PAGE_SIZE,
                              decode_batch=DECODE_BATCH,
                              shard=plan if tp > 1 else None)
            dt = time_steps(eng, prompts, steps)
            pool = eng.scheduler().pool
            wall = DECODE_BATCH * steps / dt
            key = f"tp{tp}/{'on' if compress else 'off'}"
            out[key] = {
                "tp": tp,
                "compress": compress,
                "steps": steps,
                "decode_batch": DECODE_BATCH,
                "us": round(dt * 1e6, 2),
                "tokens_per_s_wall": round(wall, 2),
                "tokens_per_s": round(wall * tp if forced else wall, 2),
                "normalization": ("device (wall * tp; forced host "
                                  "devices time-slice one CPU core)"
                                  if forced else "wall (one device per "
                                  "shard)"),
                "interconnect_bytes_per_step":
                    plan.step_interconnect_bytes(cfg, DECODE_BATCH),
                "pool_shard_bytes": plan.shard_pool_bytes(pool),
                "path": "sharded_step" if tp > 1 else "single_device",
            }
            print(f"# {key}: {dt * 1e3:.1f} ms / {steps} steps, "
                  f"wall {wall:.1f} tok/s, "
                  f"{out[key]['tokens_per_s']} tok/s "
                  f"({out[key]['normalization']}), "
                  f"{out[key]['interconnect_bytes_per_step']} "
                  "interconnect B/step")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print(json.dumps(rows(args.smoke)))
    return 0


if __name__ == "__main__":
    # the forced host-device count must be in XLA_FLAGS before the CPU
    # backend starts, i.e. before the first device query
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{os.environ.get('REPRO_HOST_DEVICES', '8')} "
        + os.environ.get("XLA_FLAGS", ""))
    raise SystemExit(main())
