"""Block autotuner for the fused kernels: best-of-swept, cached.

Every Pallas kernel in this repo exposes its tile sizes as a ``block=``
argument (``(bm, bn, bk)`` for the matmuls, the KV tile ``bk`` for flash
attention). Until this module existed those were hand-picked constants;
now a BENCH row reports the *best known* configuration instead of one
guess, and any caller that passes no explicit blocks gets the tuned ones
for free.

Key space
---------
Entries are keyed ``"{op}|{format}|{bucket}|{backend}"``:

* ``op`` — ``"qmatmul"`` | ``"lns_qmatmul"`` | ``"attention"``;
* ``format`` — the registry spec name (``"takum8"``, ``"posit16"``,
  ``"none"`` …) — decode cost differs per format, so the best tile does
  too;
* ``bucket`` — a shape bucket, not the exact shape: matmul shapes round
  each dim up to a power of two (``m64k2048n2048``), attention buckets
  the context length (``t8192``). Buckets keep the table small while
  distinguishing the regimes that matter (decode-step M=1..64 vs
  prefill, short vs long context);
* ``backend`` — ``jax.default_backend()``: a tile that wins on TPU
  means nothing on CPU.

Storage
-------
Two JSON tables, local overriding checked-in:

* ``autotune_defaults.json`` (next to this module, checked in) — the
  portable defaults; regenerate with ``make autotune`` on the target
  backend and commit;
* a gitignored local cache (``.repro_autotune.json`` in the working
  directory, or ``$REPRO_AUTOTUNE_CACHE``) — what a local sweep writes.

``REPRO_AUTOTUNE`` picks the mode:

* ``0`` — off: lookups return nothing, callers use their blockless
  fallbacks (``ops.qmm_blocks``' shape-derived tile for ``qmatmul``,
  the fixed tiles elsewhere);
* ``1`` (default) — lookup only: consult the tables, never sweep.
  This is the CI mode — ``make bench-smoke`` runs with it so CI
  validates the table without paying for a sweep;
* ``force`` — re-sweep even on a cache hit and write the local cache
  (what ``make autotune`` / ``python -m repro.kernels.autotune`` use).

Sweeps are honest by construction: every sweep space starts with the
blockless fallback and selection is strict-improvement, so the tuned
result beats or matches the old default on every row — on backends where
the blocks cannot matter (the XLA fallback path ignores them) the
fallback simply wins its ties.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

__all__ = ["matmul_bucket", "attention_bucket", "lookup", "qmm_space",
           "attn_space", "cached_or_sweep", "mode", "local_cache_path",
           "DEFAULTS_PATH"]

DEFAULTS_PATH = os.path.join(os.path.dirname(__file__),
                             "autotune_defaults.json")

OPS = ("qmatmul", "lns_qmatmul", "attention")

# matmul candidates beyond the hand-picked fallback: MXU-shaped variants
# trading M-parallelism against K-reuse of the decoded weight tile
_QMM_CANDIDATES = (
    (128, 128, 128),
    (64, 128, 128),
    (32, 128, 128),
    (128, 128, 256),
    (128, 256, 128),
    (64, 128, 256),
    (256, 128, 128),
)

# KV sequence tile for flash decode attention
_ATTN_CANDIDATES = ((256,), (128,), (512,), (1024,))


def mode() -> str:
    """Current autotune mode: '0' | '1' | 'force' (default '1')."""
    m = os.environ.get("REPRO_AUTOTUNE", "1")
    if m not in ("0", "1", "force"):
        raise ValueError(f"REPRO_AUTOTUNE={m!r}: expected 0, 1 or force")
    return m


def local_cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE",
                          os.path.join(os.getcwd(), ".repro_autotune.json"))


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def _p2(x: int) -> int:
    """Round up to a power of two (min 8)."""
    x = max(int(x), 8)
    return 1 << (x - 1).bit_length()


def matmul_bucket(m: int, k: int, n: int) -> str:
    """Bucket a [M, K] @ [K, N] problem: each dim to its power of two."""
    return f"m{_p2(m)}k{_p2(k)}n{_p2(n)}"


def attention_bucket(tmax: int) -> str:
    """Bucket a decode-attention problem by context length."""
    return f"t{_p2(tmax)}"


def _key(op: str, fmt: str, bucket: str, backend: Optional[str]) -> str:
    if op not in OPS:
        raise ValueError(f"unknown autotune op {op!r} (known: {OPS})")
    backend = backend or jax.default_backend()
    return f"{op}|{fmt}|{bucket}|{backend}"


# ---------------------------------------------------------------------------
# Table I/O (defaults + local cache, local wins)
# ---------------------------------------------------------------------------


_loaded: Dict[str, dict] = {}  # path -> {"entries": {...}} (mtime-validated)
_mtimes: Dict[str, float] = {}


def _load(path: str) -> dict:
    try:
        mt = os.path.getmtime(path)
    except OSError:
        return {"schema": 1, "entries": {}}
    if path not in _loaded or _mtimes.get(path) != mt:
        with open(path) as f:
            _loaded[path] = json.load(f)
        _mtimes[path] = mt
    return _loaded[path]


def _entry(op, fmt, bucket, backend) -> Optional[dict]:
    key = _key(op, fmt, bucket, backend)
    for path in (local_cache_path(), DEFAULTS_PATH):  # local wins
        ent = _load(path).get("entries", {}).get(key)
        if ent is not None:
            return ent
    return None


def lookup(op: str, fmt: str, bucket: str,
           backend: Optional[str] = None) -> Optional[Tuple[int, ...]]:
    """The tuned blocks for a key, or None (miss, or REPRO_AUTOTUNE=0).

    This is what the ``ops`` wrappers consult whenever the caller passes
    no explicit ``block=``; a miss falls back to the blockless default
    at the call site.
    """
    if mode() == "0":
        return None
    ent = _entry(op, fmt, bucket, backend)
    # process-wide table traffic counters: a serving run whose misses
    # keep climbing is running hand-picked fallback tiles — visible in
    # the obs snapshot as autotune.hit/autotune.miss (lookup happens at
    # trace time, so steady state adds nothing after the first compile)
    from repro.obs.metrics import GLOBAL
    GLOBAL.counter("autotune.hit" if ent is not None
                   else "autotune.miss").inc()
    return None if ent is None else tuple(ent["blocks"])


def _record(op, fmt, bucket, backend, blocks, us) -> None:
    path = local_cache_path()
    doc = _load(path)
    doc.setdefault("schema", 1)
    doc.setdefault("entries", {})[_key(op, fmt, bucket, backend)] = {
        "blocks": list(blocks),
        "us": round(us * 1e6, 2),
        "backend": backend or jax.default_backend(),
        "swept": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    _loaded[path] = doc
    _mtimes[path] = os.path.getmtime(path)


# ---------------------------------------------------------------------------
# Sweeping
# ---------------------------------------------------------------------------


def qmm_space(fallback: Tuple[int, int, int]) -> Tuple[tuple, ...]:
    """Matmul sweep space; the hand-picked fallback is always first (so
    strict-improvement selection can never do worse than it)."""
    out = [tuple(fallback)]
    out += [c for c in _QMM_CANDIDATES if c != tuple(fallback)]
    return tuple(out)


def attn_space(fallback_bk: int) -> Tuple[tuple, ...]:
    out = [(int(fallback_bk),)]
    out += [c for c in _ATTN_CANDIDATES if c != (int(fallback_bk),)]
    return tuple(out)


def _time(run: Callable[[], object], reps: int = 5) -> float:
    run()  # compile / warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = run()
        if hasattr(r, "block_until_ready"):
            r.block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def cached_or_sweep(op: str, fmt: str, bucket: str,
                    space: Sequence[tuple],
                    run: Callable[[tuple], Callable[[], object]],
                    backend: Optional[str] = None,
                    reps: int = 5,
                    log: Optional[Callable[[str], None]] = None):
    """Return ``(blocks, us, swept)`` for a key.

    Cache hit (mode '1'): the cached blocks, no timing — deterministic,
    identical on every call. Mode 'force': sweep the space (the fallback
    candidate first, strict improvement to replace it) and write the
    local cache. Mode '0' or a mode-'1' miss: the first space entry (the
    fallback) untimed.

    ``run(blocks)`` returns a zero-arg callable executing the kernel at
    those blocks; candidates that fail to compile (e.g. a tile too large
    for VMEM) are skipped.
    """
    m = mode()
    fallback = tuple(space[0])
    if m == "0":
        return fallback, None, False
    cached = lookup(op, fmt, bucket, backend)
    if cached is not None and m != "force":
        return cached, (_entry(op, fmt, bucket, backend) or {}).get("us"), \
            False
    if m != "force":  # mode '1' miss: never sweep outside force
        return fallback, None, False
    best, best_t = fallback, None
    for cand in space:
        try:
            t = _time(run(tuple(cand)), reps=reps)
        except Exception as e:  # tile doesn't fit / invalid grid: skip
            if log:
                log(f"#   {cand}: skipped ({type(e).__name__})")
            continue
        if log:
            log(f"#   {cand}: {t * 1e6:.1f} us")
        if best_t is None or t < best_t:  # strict: first (fallback) wins ties
            best, best_t = tuple(cand), t
    _record(op, fmt, bucket, backend, best, best_t or 0.0)
    return best, (best_t or 0.0) * 1e6, True


# ---------------------------------------------------------------------------
# CLI: sweep the standard BENCH problems and write the local cache
# ---------------------------------------------------------------------------


def _sweep_all(log=print, write_defaults: bool = False) -> dict:
    """Sweep every (op, format) pair at the BENCH shapes on this backend.

    Run via ``make autotune``. Uses the backend's production path
    (Pallas on TPU, the XLA fallback elsewhere — where blocks are
    recorded but cannot matter, so the fallback default wins its ties
    and the table stays honest).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro import formats
    from repro.kernels import ops

    os.environ["REPRO_AUTOTUNE"] = "force"
    backend = jax.default_backend()
    use_kernel = backend == "tpu"
    rng = np.random.default_rng(0)
    results = {}

    from benchmarks import codec_json as cj  # the BENCH problem shapes

    m, k, nn = cj.QMM_M, cj.QMM_K, cj.QMM_N
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = rng.normal(size=(k, nn)).astype(np.float32) / np.sqrt(k)
    for op, fmts, mm in (
            ("qmatmul", cj.QMM_FORMATS,
             lambda a, ww, s, b: ops.quant_matmul(a, ww, s, use_kernel,
                                                  None, b)),
            ("lns_qmatmul", cj.LNS_FORMATS,
             lambda a, ww, s, b: ops.lns_matmul(a, ww, s, "linear",
                                                use_kernel, None, b))):
        for name in fmts:
            spec = formats.get(name)
            ww = spec.encode_tile(w)
            bucket = matmul_bucket(m, k, nn)
            # the first candidate is what a blockless call takes on a miss
            fb = (ops.qmm_blocks(m, k, nn, spec.bytes_per_elem())
                  if op == "qmatmul" else ops.default_qmm_blocks(m))
            log(f"# sweep {op}/{name} {bucket} [{backend}]")
            blocks, us, _ = cached_or_sweep(
                op, name, bucket, qmm_space(fb),
                lambda b: (lambda: jax.jit(
                    lambda a, w_, s=spec, b=b: mm(a, w_, s, b)
                )(x, ww)), log=log)
            results[f"{op}|{name}|{bucket}"] = blocks
            log(f"#   -> {blocks} ({us and round(us, 1)} us)")

    h = cj.KV_HKV * cj.KV_G
    for t in cj.KV_T:
        q = jnp.asarray(rng.normal(
            size=(cj.KV_B, 1, h, cj.KV_HD)).astype(np.float32))
        kf = rng.normal(size=(cj.KV_B, cj.KV_HKV, t,
                              cj.KV_HD)).astype(np.float32)
        for name in cj.KV_FORMATS:
            spec = formats.resolve(name)
            if spec.is_identity:
                kw = vw = jnp.asarray(kf)
            else:
                kw = vw = spec.encode_tile(kf)
            bucket = attention_bucket(t)
            log(f"# sweep attention/{spec.name} {bucket} [{backend}]")
            blocks, us, _ = cached_or_sweep(
                "attention", spec.name, bucket,
                attn_space(ops.default_attention_bk()),
                lambda b: (lambda: jax.jit(
                    lambda qq, kk, vv, s=spec, t=t, b=b:
                    ops.takum_attention(qq, kk, vv, s.n, s, pos=t - 1,
                                        use_kernel=use_kernel, block=b[0])
                )(q, kw, vw)), log=log)
            results[f"attention|{spec.name}|{bucket}"] = blocks
            log(f"#   -> {blocks} ({us and round(us, 1)} us)")

    if write_defaults:
        local = _load(local_cache_path())
        doc = _load(DEFAULTS_PATH)
        doc.setdefault("schema", 1)
        doc.setdefault("entries", {}).update(local.get("entries", {}))
        with open(DEFAULTS_PATH, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        _loaded[DEFAULTS_PATH] = doc
        _mtimes[DEFAULTS_PATH] = os.path.getmtime(DEFAULTS_PATH)
        log(f"# merged local cache into {DEFAULTS_PATH}")
    return results


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="Sweep kernel block spaces at the BENCH shapes and "
                    "write the local autotune cache.")
    ap.add_argument("--write-defaults", action="store_true",
                    help="also merge the result into the checked-in "
                         "autotune_defaults.json")
    args = ap.parse_args(argv)
    _sweep_all(write_defaults=args.write_defaults)


if __name__ == "__main__":
    main()
