"""Chip smoke: serve phi3-medium-14b at its published widths on one TPU.

    python chip_smoke.py             # one chip: serve, executables, kernels
    python chip_smoke.py --chips 4   # four chips: ShardPlan(dp=2, tp=2)

Drives the normal serving path once: ``get_arch("phi3-medium-14b")`` ->
``model.init`` -> ``quantize_weights(..., "takum8", mode="wire")`` ->
``ServeEngine`` with a takum8 paged KV pool -> ``submit``/``run``. That
is the scheduler, the paged wire pool, the fused attention kernels and
the ``WireMatrix`` decode-once matmuls. Every width is the published one
(d_model 5120, 40 heads, 10 KV heads, head_dim 128, d_ff 17920, vocab
100352); the one cut is depth, 4 of the 40 layers (:data:`LAYERS`).
Weights are random, made from ``--seed``.

Checks, each printed on its own line before the result:

* every request ends ``done`` (none ``poisoned``), tokens are in the
  vocabulary, the prefix cache served the shared prompt, and the pool
  drains;
* the compiled prefill-chunk and decode-step executables contain
  ``tpu_custom_call`` (the Pallas kernels, not their oracles), and no
  prefill chunk exceeds ``ops.MAX_ATTN_Q_ROWS``;
* each fused kernel (codec decode/encode, ``quant_matmul``,
  ``lns_matmul``, ``takum_attention``, ``paged_attention``) matches its
  ``use_kernel=False`` oracle on the same device, at the smoke's shapes,
  within the tolerances of the CPU parity tests.

With ``--chips 4`` only the tensor-parallel path runs: the same model
under ``ShardPlan(dp=2, tp=2)``, in gather mode and with takum8-
compressed collectives, against the one-chip engine in the same process
(``PageStats``, greedy tokens and first-chunk logits; see
:func:`sharded_parity`).

The script refuses to run without a TPU and exits non-zero on any failed
check. Its last stdout line is ``{"ok": true, "device": {...}}``. Times
it prints are set-up and compile times of a smoke run, not benchmark
numbers. Phases are importable functions; ``tests/test_chip_smoke.py``
rehearses them on the CPU at the ``reduced`` preset.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "phi3-medium-14b"
LAYERS = 4        # depth cut: 4 of phi3-medium's 40 layers
KV_FORMAT = "takum8"
WEIGHT_FORMAT = "takum8"
# gather-mode first-chunk logits vs one chip, as a share of the largest
# one-chip logit. Three times the 0.016 a TPU v5e showed, where a
# bf16/takum8 rounding flip, not a mapping error, separates the two.
GATHER_LOGIT_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A check of the smoke found a wrong result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Presets: the chip run and its CPU rehearsal
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Preset:
    """Model and traffic of one smoke run.

    Wave 1 sends ``lens`` plus one request of ``prefix + tails[0]``
    tokens. Wave 2, after wave 1 drained, sends ``prefix + tails[1]``
    (a partial prefix-cache hit) and the bare ``prefix`` (a full hit,
    whose last page is recomputed through copy-on-write). Prompt lengths
    are chosen so the contiguous prefill widths (prompt pages plus one
    slack page) fall into few buckets: each bucket is one compile.
    """
    cfg: object
    page_size: int
    max_new: int
    prefix: int
    lens: tuple
    tails: tuple
    decode_batch: int
    sharded_lens: tuple   # the --chips 4 traffic: one width bucket

    @property
    def max_len(self) -> int:
        longest = max(self.lens + (self.prefix + max(self.tails),))
        return -(-(longest + self.max_new) // self.page_size) \
            * self.page_size


def preset(name: str = "full") -> Preset:
    """``"full"``: the chip run (published widths, :data:`LAYERS`
    layers). ``"reduced"``: the family's reduced config at toy sizes, for
    rehearsals on the CPU."""
    from repro.configs import get_arch
    spec = get_arch(ARCH)
    if name == "full":
        cfg = dataclasses.replace(spec.config, n_layers=LAYERS,
                                  kv_quant=KV_FORMAT)
        # widths (pages + 1) * 64: 320, 768, 1088 -> three prefill compiles
        return Preset(cfg=cfg, page_size=64, max_new=32, prefix=256,
                      lens=(200, 250, 700, 1000, 1024), tails=(394, 444),
                      decode_batch=8, sharded_lens=(200, 230, 250, 256))
    if name == "reduced":
        cfg = dataclasses.replace(spec.reduced, kv_quant=KV_FORMAT)
        return Preset(cfg=cfg, page_size=8, max_new=4, prefix=16,
                      lens=(9, 20, 33), tails=(7, 12), decode_batch=4,
                      sharded_lens=(9, 12, 14, 16))
    raise ValueError(f"unknown preset {name!r}")


def traffic(p: Preset, seed: int):
    """Seeded greedy prompts: ``(wave1, wave2)`` lists of token lists."""
    import numpy as np
    rng = np.random.default_rng(seed)
    vocab = p.cfg.vocab

    def toks(n):
        return [int(t) for t in rng.integers(1, vocab - 1, n)]

    shared = toks(p.prefix)
    wave1 = [toks(n) for n in p.lens] + [shared + toks(p.tails[0])]
    wave2 = [shared + toks(p.tails[1]), list(shared)]
    return wave1, wave2


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def hbm_peak() -> str:
    import jax
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return f"{stats['peak_bytes_in_use']} B"


def build_params(p: Preset, seed: int):
    """Seeded random weights, projections wired to takum8 words."""
    import jax
    from repro.configs import get_arch
    from repro.models import model
    from repro.serve.engine import quantize_weights
    t0 = time.perf_counter()
    cfg = p.cfg
    log(f"model {cfg.name}: {cfg.n_layers} layers (published: "
        f"{get_arch(ARCH).config.n_layers}), "
        f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
        f"head_dim {cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{cfg.param_count()} parameters")
    params = quantize_weights(model.init(jax.random.PRNGKey(seed), cfg),
                              WEIGHT_FORMAT, mode="wire")
    jax.block_until_ready(params)
    log(f"params built in {time.perf_counter() - t0:.1f} s; "
        f"device peak so far {hbm_peak()}")
    return params


def make_engine(params, p: Preset, seed: int, shard=None):
    from repro.serve.engine import ServeEngine
    return ServeEngine(params, p.cfg, max_len=p.max_len,
                       page_size=p.page_size, decode_batch=p.decode_batch,
                       seed=seed, shard=shard)


def _drain(eng, prompts, max_new):
    rids = [eng.submit(pr, max_new) for pr in prompts]
    for _ in eng.run():
        pass
    return rids


def serve(eng, p: Preset, seed: int) -> dict:
    """The traffic of :func:`traffic` through ``submit``/``run``."""
    t0 = time.perf_counter()
    wave1, wave2 = traffic(p, seed)
    prompts = wave1 + wave2
    rids = _drain(eng, wave1, p.max_new) + _drain(eng, wave2, p.max_new)
    log(f"served {len(rids)} requests, prompt lengths "
        f"{[len(x) for x in prompts]}, max_new {p.max_new}, in "
        f"{time.perf_counter() - t0:.1f} s including compiles "
        "(chip smoke, not a benchmark)")
    status = [eng.status(r) for r in rids]
    check(all(s == "done" for s in status), f"every request done: {status}")
    outs = [eng.result(r) for r in rids]
    gen = [o[len(pr):] for o, pr in zip(outs, prompts)]
    check(all(len(g) == p.max_new for g in gen),
          f"each request generated {p.max_new} tokens")
    check(all(0 <= t < p.cfg.vocab for g in gen for t in g),
          "generated tokens are in the vocabulary")
    sched = eng.scheduler()
    pool = sched.pool
    stats = pool.stats()
    check(stats.prefix_hit_tokens >= 2 * p.prefix - p.page_size,
          f"prefix cache served the shared prompt: "
          f"{stats.prefix_hit_tokens} prompt tokens from shared pages")
    held = sched.prefix.pages_held() if sched.prefix is not None else 0
    check(pool.pages_in_use() == held,
          f"only prefix-cache pages stay allocated: {pool.pages_in_use()} "
          f"in use, {held} held by the prefix cache")
    if sched.prefix is not None:
        sched.prefix.clear()
    check(pool.pages_in_use() == 0, "the pool drains to empty")
    return {"tokens": gen, "stats": stats}


def _prefill_width(p: Preset, plen: int) -> int:
    """The scheduler's contiguous prefill width for a ``plen`` prompt:
    its pages plus one slack page."""
    return (-(-plen // p.page_size) + 1) * p.page_size


def step_executables(eng, p: Preset) -> dict:
    """HLO text of the compiled prefill-chunk and decode-step programs,
    lowered with the arguments the scheduler passed them while serving
    (so both come from the executable cache)."""
    import jax.numpy as jnp
    from repro.models import model
    sched = eng.scheduler()
    w = sched.decode_batch
    contig = model.init_cache(eng.cfg, batch=1,
                              max_len=_prefill_width(p, p.lens[0]))
    prefill = eng._prefill_chunk.lower(
        eng.params, jnp.zeros((1, p.page_size), jnp.int32), contig,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(p.page_size - 1, jnp.int32)).compile()
    step = eng._step_paged.lower(
        eng.params, jnp.zeros((w, 1), jnp.int32), sched.pool.cache,
        jnp.zeros((w, 1), jnp.int32), jnp.zeros((w, 2), jnp.uint32),
        jnp.zeros((w,), jnp.float32), jnp.ones((w,), jnp.float32)).compile()
    return {"prefill_chunk": prefill.as_text(),
            "decode_step": step.as_text()}


def check_executables(eng, p: Preset, *, expect_kernels: bool) -> None:
    from repro.kernels import ops
    rows = p.cfg.n_heads // p.cfg.n_kv_heads * p.page_size
    check(rows <= ops.MAX_ATTN_Q_ROWS,
          f"a {p.page_size}-token prefill chunk has {rows} attention rows "
          f"per KV head, within the kernel's {ops.MAX_ATTN_Q_ROWS}")
    t0 = time.perf_counter()
    texts = step_executables(eng, p)
    log(f"step executables lowered in {time.perf_counter() - t0:.1f} s")
    for name, txt in texts.items():
        n = txt.count("tpu_custom_call")
        if expect_kernels:
            check(n > 0, f"{name} executable holds {n} tpu_custom_call")
        else:
            log(f"{name} executable holds {n} tpu_custom_call "
                "(interpret mode: none expected)")


def check_close(got, want, rtol, atol, what: str) -> None:
    """Kernel vs oracle with a CPU parity test's ``rtol``/``atol``,
    applied normwise: ``max|got - want| <= atol + rtol * max|want|``.

    The per-element form of the same tolerance is printed beside it. It
    is not the criterion: at K = 5120 an output can cancel towards zero,
    and there the chip's kernel and its XLA oracle round their
    transcendentals and sums differently by more than ``rtol`` of that
    output."""
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.abs(got - want)
    scale = float(jnp.max(jnp.abs(want)))
    max_err = float(jnp.max(err))
    outside = int(jnp.sum(err > atol + rtol * jnp.abs(want)))
    check(bool(jnp.all(jnp.isfinite(got))) and
          max_err <= atol + rtol * scale,
          f"{what} vs oracle: max abs err {max_err:.3g}, max |oracle| "
          f"{scale:.3g} (rtol {rtol:g}, atol {atol:g}); {outside} of "
          f"{err.size} elements outside the per-element bound")


def _bits_equal(a, b) -> bool:
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jax.lax.bitcast_convert_type(a, jnp.uint32)
        b = jax.lax.bitcast_convert_type(b, jnp.uint32)
    return a.shape == b.shape and bool(jnp.array_equal(a, b))


def kernel_parity(p: Preset, seed: int) -> None:
    """Each fused kernel against its ``use_kernel=False`` oracle, both on
    this process's device, at the smoke's shapes. Oracles run at f32
    matmul precision, as the kernels do. The codec must be bit-exact;
    the matmuls and attention take the tolerances of the CPU parity tests
    (tests/test_kernels.py, test_lns_kernel.py, test_takum_attention.py,
    test_paged_attention.py) through :func:`check_close`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import formats
    from repro.kernels import ops, takum_codec
    cfg = p.cfg
    d, ff, h, hkv, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, \
        cfg.n_kv_heads, cfg.hd
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def normal(shape, scale=1.0, dtype=jnp.float32):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def run(fn, *args):
        # one compiled program per call: eager full-size codec ops would
        # hold several lane-wide temporaries of the whole operand at once
        return jax.jit(fn)(*args)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return run(fn, *args)

    t0 = time.perf_counter()
    w = normal((d, ff), d ** -0.5)
    # codec: bit-exact on both sides, for every format on the path
    for name in ("takum8", "takum16", "posit8"):
        spec = formats.get(name)
        enc = run(lambda a: ops.takum_encode(a, spec, use_kernel=True), w)
        enc_o = run(lambda a: ops.takum_encode(a, spec, use_kernel=False), w)
        check(_bits_equal(enc, enc_o),
              f"codec encode {name} [{d}, {ff}] bit-exact vs oracle")
        dec = run(lambda a: ops.takum_decode(a, spec, use_kernel=True), enc_o)
        dec_o = run(lambda a: ops.takum_decode(a, spec, use_kernel=False),
                    enc_o)
        check(_bits_equal(dec, dec_o),
              f"codec decode {name} [{d}, {ff}] bit-exact vs oracle")
    # the posit8 table decode (off by default): a fresh trace with it on
    posit8 = formats.get("posit8")
    enc = run(lambda a: ops.takum_encode(a, posit8, use_kernel=False), w)
    prior = os.environ.get("REPRO_LUT_DECODE")
    os.environ["REPRO_LUT_DECODE"] = "1"
    takum_codec.decode_kernel_call.clear_cache()
    try:
        dec = run(lambda a: ops.takum_decode(a, posit8, use_kernel=True),
                  enc)
    finally:
        if prior is None:
            os.environ.pop("REPRO_LUT_DECODE")
        else:
            os.environ["REPRO_LUT_DECODE"] = prior
        takum_codec.decode_kernel_call.clear_cache()
    dec_o = run(lambda a: ops.takum_decode(a, posit8, use_kernel=False), enc)
    check(_bits_equal(dec, dec_o),
          "codec decode posit8 through the table bit-exact vs oracle")

    # weight matmuls at the decode-step and prefill-chunk row counts
    wspec = formats.get(WEIGHT_FORMAT)
    words = ops.WireMatrix.encode(w, fmt=wspec).words
    for m in (p.decode_batch, p.page_size):
        x = normal((m, d), dtype=jnp.bfloat16)
        got = run(lambda a, b: ops.quant_matmul(a, b, wspec, True), x, words)
        want = oracle(lambda a, b: ops.quant_matmul(a, b, wspec, False),
                      x, words)
        check_close(got, want, 1e-5, 1e-4,
                    f"quant_matmul {WEIGHT_FORMAT} [{m}, {d}] @ [{d}, {ff}]")
    lspec = formats.get("lns-takum8")
    lwords = ops.WireMatrix.encode(w, fmt=lspec).words
    x = normal((p.decode_batch, d))
    got = run(lambda a, b: ops.lns_matmul(a, b, lspec, "linear", True),
              x, lwords)
    want = oracle(lambda a, b: ops.lns_matmul(a, b, lspec, "linear", False),
                  x, lwords)
    check_close(got, want, 2e-5, 1e-6,
                f"lns_matmul lns-takum8 [{p.decode_batch}, {d}] @ [{d}, {ff}]")

    # attention over takum8 words: the contiguous prefill cache (decode
    # row and a page-sized chunk) and the paged pool (a decode step)
    kv = formats.get(KV_FORMAT)
    t = p.max_len
    kc, vc = (kv.encode_tile(normal((1, hkv, t, hd))) for _ in range(2))
    for tq in (1, p.page_size):
        q = normal((1, tq, h, hd), dtype=jnp.bfloat16)
        pos = t - p.max_new - tq
        got = run(lambda a, b, c: ops.takum_attention(
            a, b, c, 0, kv, pos=pos, use_kernel=True), q, kc, vc)
        want = oracle(lambda a, b, c: ops.takum_attention(
            a, b, c, 0, kv, pos=pos, use_kernel=False), q, kc, vc)
        check_close(got, want, 1e-5, 2e-5,
                    f"takum_attention {KV_FORMAT} tq={tq} over {t} positions")
    npg = t // p.page_size
    num_pages = p.decode_batch * npg + 1
    kp, vp = (kv.encode_tile(normal((num_pages, hkv, p.page_size, hd)))
              for _ in range(2))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(num_pages - 1)
                        .reshape(p.decode_batch, npg).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, t - 1, p.decode_batch)
                      .astype(np.int32))
    q = normal((p.decode_batch, 1, h, hd), dtype=jnp.bfloat16)
    got = run(lambda *a: ops.paged_attention(*a, kv, pos=pos,
                                             use_kernel=True),
              q, kp, vp, table)
    want = oracle(lambda *a: ops.paged_attention(*a, kv, pos=pos,
                                                 use_kernel=False),
                  q, kp, vp, table)
    check_close(got, want, 1e-5, 2e-5,
                f"paged_attention {KV_FORMAT} page {p.page_size}, "
                f"{num_pages} pages, batch {p.decode_batch}")
    log(f"kernel parity in {time.perf_counter() - t0:.1f} s")


def _first_chunk(eng, p: Preset, prompt):
    """The first prefill chunk of ``prompt`` through the engine's own
    prefill executable: its last row of logits, and the K/V words it
    wrote, one ``[layers, Hkv, n, hd]`` array per scan group and key."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model
    n = min(len(prompt), p.page_size)
    tokens = np.zeros((1, p.page_size), np.int32)
    tokens[0, :n] = prompt[:n]
    contig = model.init_cache(eng.cfg, batch=1,
                              max_len=_prefill_width(p, len(prompt)))
    row, cache = eng._prefill_chunk(eng.params, jnp.asarray(tokens), contig,
                                    jnp.asarray(0, jnp.int32),
                                    jnp.asarray(n - 1, jnp.int32))
    words = [np.asarray(node[key])[:, 0, :, :n]
             for node in eng.scheduler().pool._attn_nodes(cache)
             for key in ("k", "v")]
    return np.asarray(row, np.float32), words


def _words_agreement(words, base) -> str:
    """Per layer, the share of first-chunk K and V words equal to the
    one-chip engine's: where a divergence starts."""
    import numpy as np
    shares = []
    for k, v, bk, bv in zip(words[0::2], words[1::2], base[0::2],
                            base[1::2]):
        shares += [f"K {np.mean(k[i] == bk[i]):.4f} "
                   f"V {np.mean(v[i] == bv[i]):.4f}"
                   for i in range(k.shape[0])]
    return ", ".join(f"layer {i}: {s}" for i, s in enumerate(shares))


def _leaf_devices(leaves) -> set:
    return {frozenset(d.id for d in leaf.sharding.device_set)
            for leaf in leaves}


def sharded_parity(p: Preset, seed: int, n_chips: int = 4) -> None:
    """``ShardPlan(dp=2, tp=2)`` over ``n_chips`` devices, in gather mode
    and with takum8-compressed collectives, against the one-chip engine
    on the same prompts in this process.

    Both modes must finish every request with the one-chip engine's
    ``PageStats``, over a mesh of ``n_chips`` distinct devices that
    every weight and KV pool leaf spans. Gather mode does the same
    arithmetic as one device; where its greedy tokens are not bit-exact
    the script says so and prints where the first-chunk K/V words start
    to differ, and its first-chunk logits must still lie within
    :data:`GATHER_LOGIT_TOL` of the largest one-chip logit (a wrong head
    or column mapping moves them by the size of the logits). Compressed
    collectives are lossy by design: their differences are printed, not
    judged (docs/serving.md)."""
    import jax
    import numpy as np
    from repro.serve.shard import ShardPlan
    devs = jax.devices()
    check(len(devs) >= n_chips, f"{len(devs)} devices for a "
          f"{n_chips}-chip plan")
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, p.cfg.vocab - 1, n)]
               for n in p.sharded_lens]
    params = build_params(p, seed)

    def serve_all(eng):
        t0 = time.perf_counter()
        rids = _drain(eng, prompts, p.max_new)
        status = [eng.status(r) for r in rids]
        outs = [eng.result(r)[len(pr):] for r, pr in zip(rids, prompts)]
        return status, outs, eng.scheduler().pool.stats(), \
            time.perf_counter() - t0

    base = make_engine(params, p, seed)
    b_status, b_toks, b_stats, dt = serve_all(base)
    log(f"one chip: {len(prompts)} requests in {dt:.1f} s including "
        "compiles (chip smoke, not a benchmark)")
    check(all(s == "done" for s in b_status), f"one chip: {b_status}")
    b_first = [_first_chunk(base, p, pr) for pr in prompts]
    scale = max(float(np.max(np.abs(lg))) for lg, _ in b_first)
    log(f"one chip: first-chunk max |logit| {scale:.6g}")
    del base      # its weights and pool live on the first device only
    gc.collect()
    placed = None
    for compress in (None, "takum8"):
        mode = "gather" if compress is None else f"gather+{compress}"
        plan = ShardPlan(dp=2, tp=2, compress=compress)
        eng = make_engine(placed if placed is not None else params, p,
                          seed, shard=plan)
        placed = eng.params
        mesh_ids = [d.id for d in eng._sharded_steps.mesh.devices.flat]
        check(len(set(mesh_ids)) == n_chips,
              f"{mode}: mesh over devices {mesh_ids}")
        status, toks, stats, dt = serve_all(eng)
        log(f"{mode}: {len(prompts)} requests in {dt:.1f} s including "
            "compiles (chip smoke, not a benchmark)")
        check(all(s == "done" for s in status), f"{mode}: {status}")
        pool = eng.scheduler().pool
        kv = [node[key] for node in pool._attn_nodes(pool.cache)
              for key in ("k", "v")]
        spread = _leaf_devices(jax.tree_util.tree_leaves(eng.params) + kv)
        check(spread == {frozenset(mesh_ids)},
              f"{mode}: every weight and KV pool leaf spans all "
              f"{n_chips} devices")
        check(stats == b_stats, f"{mode}: PageStats equal the one-chip "
              f"engine ({stats})")
        first = [_first_chunk(eng, p, pr) for pr in prompts]
        diffs = [float(np.max(np.abs(lg - b_lg)))
                 for (lg, _), (b_lg, _) in zip(first, b_first)]
        same = sum(a == b for x, y in zip(toks, b_toks) for a, b in zip(x, y))
        total = sum(len(x) for x in b_toks)
        log(f"{mode}: {same}/{total} greedy tokens equal the one-chip "
            f"engine; first-chunk max logit diff per request "
            f"{[float(f'{d:.6g}') for d in diffs]}")
        agree = _words_agreement(first[0][1], b_first[0][1])
        log(f"{mode}: first-chunk K/V words equal to the one-chip "
            f"engine's, request 0, {agree}")
        check(all(np.isfinite(lg).all() for lg, _ in first),
              f"{mode}: first-chunk logits are finite")
        if compress is not None:
            continue
        if toks == b_toks:
            log("gather: greedy tokens bit-exact vs the one-chip engine")
        else:
            log(f"gather: NOT bit-exact on this chip: {total - same} of "
                f"{total} greedy tokens differ from the one-chip engine")
        check(max(diffs) <= GATHER_LOGIT_TOL * scale,
              f"gather: first-chunk logits within {GATHER_LOGIT_TOL:g} x "
              f"max |logit| ({GATHER_LOGIT_TOL * scale:.6g}) of the "
              f"one-chip engine's: max diff {max(diffs):.6g}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel ShardPlan phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (jax platform "
              f"{dev['platform']!r}); refusing to run on it",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.kernels import autotune
        from repro.launch.env import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro sources are not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"device {dev}; compile cache {enable_compile_cache()}")
    # blocks come from the tracked table only: an untracked local sweep
    # cache must not change what the smoke compiles
    os.environ["REPRO_AUTOTUNE_CACHE"] = autotune.DEFAULTS_PATH
    t0 = time.perf_counter()
    p = preset("full")
    try:
        if args.chips == 4:
            sharded_parity(p, args.seed)
        else:
            eng = make_engine(build_params(p, args.seed), p, args.seed)
            serve(eng, p, args.seed)
            check_executables(eng, p, expect_kernels=True)
            # free the engine's weights and pool (the scheduler and the
            # engine refer to each other) before the full-size kernels
            del eng
            gc.collect()
            kernel_parity(p, args.seed)
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        log(f"chip smoke FAILED after {time.perf_counter() - t0:.1f} s")
        return 1
    log(f"device peak memory {hbm_peak()}; smoke took "
        f"{time.perf_counter() - t0:.1f} s (chip smoke, not a benchmark)")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
