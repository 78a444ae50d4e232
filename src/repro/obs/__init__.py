"""``repro.obs`` — zero-dependency tracing + metrics for the serving stack.

The serving stack (paged wire-format KV pool, continuous-batching
scheduler, tensor-parallel steps) has every *mechanism* a production
system needs; this package is how you *see* it running. Three layers,
all off by default and all token-neutral (observability must never
change what a request generates — the fuzz suite pins obs-on vs obs-off
bit-exactly):

* **Request-lifecycle tracing** (:mod:`repro.obs.trace`): an
  injectable-clock span recorder. The scheduler opens one root span per
  request (``request``) with well-nested phase children (``queued`` →
  ``prefill`` with per-chunk spans → ``decode``), and drops instant
  events for the interesting transitions (``prefix_hit``,
  ``first_token``, ``token``, ``preempt``, ``fault``, ``quarantine``,
  ``terminal``). Export as JSONL or Chrome ``trace_event`` JSON that
  loads directly in Perfetto (:mod:`repro.obs.export`), summarize from
  the command line (``python -m repro.obs.report``).
* **Metrics** (:mod:`repro.obs.metrics`): counters / gauges /
  histograms in a registry, sampled into ring buffers once per
  scheduler tick (pool occupancy and quarantine, prefix hit tokens,
  preemptions, batch occupancy, tokens, autotune cache hits), plus a
  **recompile detector** hooking JAX's compile events — a retrace
  inside steady-state decode (the hidden ~1.5 s recompile PR 9 found by
  hand inside a timed bench region) becomes a visible counter and a
  test assertion.
* **Numeric health** (``REPRO_OBS=2``): NaR-word pool scans
  (:meth:`repro.serve.paged.PagePool.scan_nar`), per-call-site TP
  error-feedback residual norms (:func:`repro.dist.tp.residual_norms`)
  and fake-quant saturation counters — the takum-vs-posit properties
  the paper's argument leans on, as live gauges. These read device
  arrays (a sync per tick), hence the separate level.

Env gate ``REPRO_OBS``: ``0``/unset — off, every hook is a ``None``
check; ``1`` — tracing + metrics; ``2`` — tracing + metrics + numeric
health. The scheduler builds its bundle via :func:`obs_from_env` at
construction, on its own injectable clock, so traces from tests on a
fake clock are deterministic.

The scheduler's phases (:func:`phase`) are the one exception to the
gate: at every level each phase is also a ``jax.profiler``
annotation ``sched.<name>``, which costs a check when no profiler
session records and puts the host's phases on the device trace's clock
when one does.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

from repro.obs import export, metrics, trace  # noqa: F401 (re-export)
from repro.obs.metrics import GLOBAL, CompileWatcher, MetricsRegistry
from repro.obs.trace import SCHED_TRACK, RequestTiming, Tracer, phase

__all__ = ["OBS_ENV", "level", "enabled", "numeric_enabled", "ServeObs",
           "obs_from_env", "Tracer", "MetricsRegistry", "CompileWatcher",
           "RequestTiming", "SCHED_TRACK", "GLOBAL", "phase"]

OBS_ENV = "REPRO_OBS"


def level() -> int:
    """Effective ``REPRO_OBS`` level: 0 (off), 1 (trace+metrics) or
    2 (+ numeric health). Anything else raises — a typo'd knob must not
    silently disable observability."""
    raw = os.environ.get(OBS_ENV, "0") or "0"
    if raw not in ("0", "1", "2"):
        raise ValueError(f"{OBS_ENV}={raw!r}: expected 0, 1 or 2")
    return int(raw)


def enabled() -> bool:
    return level() >= 1


def numeric_enabled() -> bool:
    return level() >= 2


class ServeObs:
    """One serving loop's observability bundle: a :class:`Tracer`, a
    :class:`MetricsRegistry` and a started :class:`CompileWatcher`, all
    on the same clock. The scheduler owns one (``Scheduler.obs``) when
    ``REPRO_OBS`` is on; everything it does is host-side bookkeeping —
    no device values are read below numeric level.
    """

    def __init__(self, now_fn: Optional[Callable[[], float]] = None, *,
                 numeric: bool = False, ring: int = 4096):
        self.tracer = Tracer(now_fn)
        self.metrics = MetricsRegistry(ring=ring, now_fn=now_fn)
        self.numeric = numeric
        self.compile_watcher = CompileWatcher(registry=self.metrics)
        self.compile_watcher.start()

    def arm_steady(self) -> None:
        """Declare steady state: from now on, *any* JAX compile counts
        into ``jax.recompiles_steady_state`` (call after warmup — a
        serving loop past its first full round should never retrace)."""
        self.compile_watcher.arm()

    @property
    def steady_state_recompiles(self) -> int:
        return self.compile_watcher.steady_state_recompiles

    def close(self) -> None:
        """Detach the compile listener (tests; idempotent)."""
        self.compile_watcher.stop()


def obs_from_env(now_fn: Optional[Callable[[], float]] = None
                 ) -> Optional[ServeObs]:
    """A :class:`ServeObs` at the ``REPRO_OBS`` level, or ``None`` when
    observability is off (the production default — callers guard every
    hook with ``if obs is not None``)."""
    lvl = level()
    if lvl == 0:
        return None
    return ServeObs(now_fn, numeric=lvl >= 2)
