"""Host process environment for serving and benchmark launches.

One launch path for ``ServeEngine`` runs and ``benchmarks/run.py`` (the
shell half is ``launch/run.sh``, which sources the same policy):

* **tcmalloc** — XLA's host-side allocator traffic (pinned staging
  buffers, per-step temporaries) is malloc-bound under glibc; every
  serving rig we reference LD_PRELOADs tcmalloc when present. This
  module *detects* (a preload must happen before process start — too
  late from Python) and the shell script *applies*;
* **XLA_FLAGS host-device-count knob** — ``REPRO_HOST_DEVICES=N``
  maps to ``--xla_force_host_platform_device_count=N`` for CPU-mesh
  experiments, mirroring ``launch/dryrun.py``'s hard-coded 512;
* log hygiene (``TF_CPP_MIN_LOG_LEVEL``) and the large-alloc report
  threshold so numpy staging buffers don't spam the console;
* the persistent compilation cache (:func:`enable_compile_cache`),
  turned on by the entry points, never at import.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

__all__ = ["TCMALLOC_PATHS", "find_tcmalloc", "tcmalloc_active",
           "host_env", "warn_if_no_tcmalloc", "KNOBS", "effective_knobs",
           "audit_line", "log_config", "enable_compile_cache",
           "COMPILE_CACHE_DIR"]

TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)

LARGE_ALLOC_THRESHOLD = 60_000_000_000  # quiet numpy staging buffers

# fixed cache path at the checkout root: the path is part of the cache
# key, so a temporary or per-run directory would never hit
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def find_tcmalloc() -> Optional[str]:
    """First present tcmalloc shared object, or None."""
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def tcmalloc_active() -> bool:
    """Whether this process was started with tcmalloc preloaded."""
    return "tcmalloc" in os.environ.get("LD_PRELOAD", "")


def host_env(host_device_count: Optional[int] = None,
             base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The launch environment as a dict (for subprocess launches).

    ``LD_PRELOAD`` is included when a tcmalloc is found — effective only
    for *new* processes, which is why benchmarks and serving go through
    ``launch/run.sh`` (or this dict + ``subprocess``) rather than
    setting it mid-process. ``host_device_count`` adds the XLA
    host-platform device knob (``REPRO_HOST_DEVICES`` in run.sh).
    """
    env = dict(os.environ if base is None else base)
    so = find_tcmalloc()
    if so and "tcmalloc" not in env.get("LD_PRELOAD", ""):
        env["LD_PRELOAD"] = (so + (" " + env["LD_PRELOAD"]
                                   if env.get("LD_PRELOAD") else ""))
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                   str(LARGE_ALLOC_THRESHOLD))
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    if host_device_count:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={host_device_count} "
            + env.get("XLA_FLAGS", ""))
    return env


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is: JAX reads it
    itself and nothing else is set here. Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR` (``.jax_cache/`` at the checkout root), so
    a second run from the same checkout reuses the first run's
    executables. Called by the launchers (``chip_smoke.py``,
    ``launch/serve.py``, ``benchmarks/run.py``); tests leave it off.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def warn_if_no_tcmalloc(print_fn: Callable[[str], None] = print) -> bool:
    """Warn (once per call) when benchmarking without tcmalloc.

    Returns True when tcmalloc is preloaded. Timing noise from glibc
    malloc arenas is real on the multi-GB staging buffers the codec
    benches allocate; the numbers stay valid but less stable.
    """
    if tcmalloc_active():
        return True
    so = find_tcmalloc()
    hint = (f"launch/run.sh will preload {so}" if so
            else "no tcmalloc .so found on this host")
    print_fn(f"# warning: tcmalloc not preloaded ({hint}); "
             "benchmark timings may be noisier")
    return False


# ---------------------------------------------------------------------------
# startup config audit: every REPRO_* knob the stack reads, with the
# default each reader applies when the variable is unset. A serving or
# bench launch logs ONE structured line up front so any run's effective
# configuration is reconstructable from its log — the knobs change
# dispatch (attention kernel, LUT decode), numerics (shard compression,
# fault injection) and measurement (autotune, observability), and a run
# whose knobs are unknown is a run whose numbers are unexplainable.

KNOBS: Dict[str, str] = {
    "REPRO_OBS": "0",                 # 0 off | 1 trace+metrics | 2 +numeric
    "REPRO_KV_ATTN_KERNEL": "auto",   # fused-attention dispatch (0/1/auto)
    "REPRO_AUTOTUNE": "1",            # block autotuner (0/1/force)
    "REPRO_AUTOTUNE_CACHE": "",       # sweep cache path ("" = ./.repro_autotune.json)
    "REPRO_LUT_DECODE": "",           # 1 = posit8 table decode (default off)
    "REPRO_CAUSAL_SKIP": "0",         # skip fully-masked KV tiles
    "REPRO_FAULT_RATE": "0",          # injected faults per scheduler tick
    "REPRO_FAULT_SEED": "0",          # fault injector PRNG seed
    "REPRO_FAULT_KIND": "nar",        # nar | flip
    "REPRO_SHARD_COMPRESS": "",       # TP collective compression override
    "REPRO_HOST_DEVICES": "",         # forced XLA host device count
}


def effective_knobs(env: Optional[Dict[str, str]] = None
                    ) -> Dict[str, Dict[str, object]]:
    """Each knob's effective value: ``{"value": str, "set": bool}``.

    ``set`` distinguishes an explicit setting from the reader's default
    — ``REPRO_AUTOTUNE=1`` and an unset variable behave identically but
    audit differently (one was a decision)."""
    env = os.environ if env is None else env
    out: Dict[str, Dict[str, object]] = {}
    for name, default in KNOBS.items():
        raw = env.get(name)
        out[name] = {"value": default if raw is None else raw,
                     "set": raw is not None}
    return out


def audit_line(env: Optional[Dict[str, str]] = None) -> str:
    """The one-line startup config audit: every knob as ``NAME=value``,
    explicit settings marked with ``!``, prefixed ``# repro-config``
    (greppable, comment-shaped so it is inert in piped JSONL logs)."""
    knobs = effective_knobs(env)
    parts = [f"{n}={k['value'] or '(unset)'}{'!' if k['set'] else ''}"
             for n, k in sorted(knobs.items())]
    return "# repro-config " + " ".join(parts)


def log_config(print_fn: Callable[[str], None] = print,
               env: Optional[Dict[str, str]] = None) -> str:
    """Emit (and return) the startup audit line."""
    line = audit_line(env)
    print_fn(line)
    return line
