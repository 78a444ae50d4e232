"""Profiler capture and the reduction from a device trace to events.

``capture(dir)`` starts JAX's profiler with the Python tracer off (host
spans come only from ``TraceAnnotation``); ``reduce_xplane(path)`` reads
the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` and keeps:

* ``window``: ``[start_ns, end_ns]`` of the ``bench.window`` host span,
  the measured window on the trace's clock;
* ``modules``: ``[name, start_ns, dur_ns]`` of each executable run on
  each device (the "XLA Modules" line) that lies wholly inside the
  window, so a per-call time never counts a call cut at the window's
  edge;
* ``ops``: ``[name, start_ns, dur_ns]`` of each device operation (the
  "XLA Ops" line), Pallas kernels among them under their kernel names;
* ``host``: ``[name, start_ns, dur_ns]`` of the harness's ``bench.*``
  spans.

Ops and host spans are clipped to the window. The reduced form is a plain dict,
which the metric readers take and the tests check against a small
recorded trace (``tests/data/trace_small.json``).
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."


def capture_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):] \
        .split()[0].isdigit()


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[list] = []
    modules: Dict[str, List[list]] = {}
    ops: Dict[str, List[list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
        elif _device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dst = modules.setdefault(plane.name, [])
                elif line.name == "XLA Ops":
                    dst = ops.setdefault(plane.name, [])
                else:
                    continue
                for ev in line.events:
                    dst.append([ev.name, ev.start_ns, ev.duration_ns])
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN} span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devices = sorted(set(modules) | set(ops))
    return {"window": [w0, w1],
            "devices": devices,
            "modules": {d: inside(modules.get(d, []), w0, w1)
                        for d in devices},
            "ops": {d: clip(ops.get(d, []), w0, w1) for d in devices},
            "host": clip([h for h in host if h[0] != WINDOW_SPAN], w0, w1)}


def clip(events: Sequence[list], t0: float, t1: float) -> List[list]:
    """Events cut to ``[t0, t1]``; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a or (d == 0 and t0 <= s <= t1):
            out.append([name, a, b - a])
    return out


def inside(events: Sequence[list], t0: float, t1: float) -> List[list]:
    """The events that lie wholly inside ``[t0, t1]``."""
    return [list(e) for e in events if e[1] >= t0 and e[1] + e[2] <= t1]


def union_ns(events: Sequence[list]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    start = None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if start is None or s > end:
            if start is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if start is not None:
        total += end - start
    return total


def gaps(events: Sequence[list], t0: float, t1: float) -> List[Tuple]:
    """Idle intervals ``(start, length)`` of ``[t0, t1]`` not covered by
    any event."""
    out, cur = [], t0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, s + d)
    if t1 > cur:
        out.append((cur, t1 - cur))
    return out


def host_at(host: Sequence[list], t: float, length: float) -> str:
    """The harness span that overlaps an idle interval most."""
    best, best_overlap = "host: no bench span", 0.0
    for name, s, d in host:
        ov = min(s + d, t + length) - max(s, t)
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def op_name(event_name: str) -> str:
    """The HLO instruction's own name (``%qmatmul_kernel_call.86 = f32[...]
    custom-call(...)`` -> ``qmatmul_kernel_call.86``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaf_ops(events: Sequence[list]) -> List[list]:
    """The events that hold no other: a ``while`` over the layers is an
    event on the same line as the operations inside it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def busy_and_breakdown(red: dict, top: int = 10) -> dict:
    """Device busy seconds (mean over devices), the window's seconds,
    the operations that took most time (leaf operations, by instruction
    name) and the longest idle gaps labelled by the harness span the
    host was in."""
    w0, w1 = red["window"]
    busy, totals, idle = [], {}, []
    for dev in red["devices"]:
        evs = red["ops"][dev] or red["modules"][dev]
        busy.append(union_ns(evs))
        for name, _, d in leaf_ops(red["ops"][dev]):
            key = op_name(name)
            totals[key] = totals.get(key, 0.0) + d
        idle += gaps(evs, w0, w1)
    idle.sort(key=lambda g: -g[1])
    return {
        "busy_s": (sum(busy) / len(busy) if busy else 0.0) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, d / 1e9] for n, d in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_at(red["host"], s, d), d / 1e9]
                      for s, d in idle[:top]],
    }


# Pallas kernels as the trace names them: the custom call of each
# kernel's wrapper in ``repro.kernels`` (instruction ``<name>.<n>``)
KERNELS = {
    "quant_matmul": "qmatmul_kernel_call",
    "paged_attention": "paged_attention_kernel_call",
}


def module_calls(red, part: str) -> List[float]:
    """Seconds of each run of the executables whose name holds ``part``
    on the first device (``[]`` without a trace)."""
    if red is None or not red["devices"]:
        return []
    dev = red["devices"][0]
    return [d / 1e9 for name, _, d in red["modules"][dev] if part in name]


def kernel_time(red, kernel: str) -> float:
    """Device seconds of a Pallas kernel on the first device, inside the
    executable runs of ``modules`` (those wholly inside the window), so
    it covers the same calls as :func:`module_calls`."""
    if red is None or not red["devices"]:
        return 0.0
    dev = red["devices"][0]
    want = KERNELS[kernel]
    runs = sorted((s, s + d) for _, s, d in red["modules"][dev])
    starts = [a for a, _ in runs]
    total = 0.0
    for name, s, d in red["ops"][dev]:
        if op_name(name).split(".")[0] != want:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= runs[i][1]:
            total += d
    return total / 1e9
