"""The program's own spans on the device trace's clock.

The scheduler marks each host phase of its serving loop with a profiler
annotation named ``sched.<phase>`` (``repro.obs.phase``): housekeeping,
admission, a prefill chunk's dispatch, the host reads of a finishing
prefill and its commit to the pool, and a decode step's dispatch, its
blocking read and its commit. They land on the host plane of the same
``.xplane.pb`` as the device's operations, so each idle gap of the device
can be put down to the phase the host was in.

``program_events(path, w0, w1)`` reads them, clipped to the window like
the harness's ``host`` spans; ``with_program(red, path)`` adds them to a
reduced trace (``devtrace.reduce_xplane``) under the key ``program``.
What the readers and the report take from them:

* :func:`idle_gaps`: the longest idle gaps, each labelled with the
  harness span (``devtrace.host_at``) and the program span that overlaps
  it most, as ``bench.run>sched.decode_commit``;
* :func:`idle_by_phase`: the device-idle seconds of the window, the part
  of them that lies under any program span, and the seconds per phase;
* :func:`decode_turnarounds`: for each pair of consecutive decode steps
  inside the window, the host time from the end of step n's
  ``sched.decode_sync`` to the end of step n+1's ``sched.decode_dispatch``
  (``metrics/decode_turnaround_ms.py``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import devtrace

PROGRAM_PREFIX = "sched."
DECODE_DISPATCH = "sched.decode_dispatch"
DECODE_SYNC = "sched.decode_sync"


def program_events(path: str, w0: float, w1: float) -> List[list]:
    """``[name, start_ns, dur_ns]`` of every host event named
    ``sched.*`` in the trace at ``path``, clipped to ``[w0, w1]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    # an annotation's arguments may ride in its name
                    # ("name#k=v#") where the converter leaves them there
                    events.append([ev.name.split("#", 1)[0], ev.start_ns,
                                   ev.duration_ns])
    return devtrace.clip(events, w0, w1)


def with_program(red: dict, path: str) -> dict:
    """``red`` with the program spans of the trace at ``path`` under
    ``program``."""
    red["program"] = program_events(path, *red["window"])
    return red


def program_at(program: Sequence[list], t: float,
               length: float) -> Optional[str]:
    """The program span that overlaps ``[t, t + length]`` most (the
    shorter, so the inner, of two that overlap it equally), or ``None``."""
    best, key = None, (0.0, 0.0)
    for name, s, d in program:
        ov = min(s + d, t + length) - max(s, t)
        if ov > 0 and (ov, -d) > key:
            best, key = name, (ov, -d)
    return best


def gap_label(red: dict, t: float, length: float) -> str:
    """The harness span the host was in during an idle gap, followed by
    ``>`` and the program span that overlaps the gap most; the harness
    span alone where no program span overlaps it."""
    label = devtrace.host_at(red["host"], t, length)
    inner = program_at(red.get("program") or [], t, length)
    return label if inner is None else f"{label}>{inner}"


def device_gaps(red: dict, dev: str) -> List[tuple]:
    """The idle gaps ``(start, length)`` of one device in the window, as
    ``devtrace.busy_and_breakdown`` finds them, in time order."""
    w0, w1 = red["window"]
    return devtrace.gaps(red["ops"][dev] or red["modules"][dev], w0, w1)


def idle_gaps(red: dict, top: int = 10) -> List[list]:
    """``[label, seconds]`` of the ``top`` longest idle gaps."""
    gaps = [g for dev in red["devices"] for g in device_gaps(red, dev)]
    gaps.sort(key=lambda g: -g[1])
    return [[gap_label(red, s, d), d / 1e9] for s, d in gaps[:top]]


def idle_by_phase(red: dict) -> Dict[str, object]:
    """Device-idle seconds of the window (mean over devices), the part
    of them under any program span, and that part by phase. The phases
    run one after another on the scheduler's thread, so no two overlap
    and the parts add up."""
    program = sorted(red.get("program") or [], key=lambda e: e[1])
    n_dev = max(len(red["devices"]), 1)
    idle = covered = 0.0
    phases: Dict[str, float] = {}
    for dev in red["devices"]:
        j = 0          # gaps and phases both in time order: one sweep
        for s, d in device_gaps(red, dev):
            idle += d
            while j < len(program) and program[j][1] + program[j][2] <= s:
                j += 1
            k = j
            while k < len(program) and program[k][1] < s + d:
                name, ps, pd = program[k]
                ov = min(ps + pd, s + d) - max(ps, s)
                if ov > 0:
                    covered += ov
                    phases[name] = phases.get(name, 0.0) + ov / 1e9 / n_dev
                k += 1
    return {"idle_s": idle / 1e9 / n_dev,
            "under_program_s": covered / 1e9 / n_dev,
            "phases": dict(sorted(phases.items(), key=lambda kv: -kv[1]))}


def decode_turnarounds(red: dict) -> List[tuple]:
    """``(start_ns, end_ns)`` from the end of each decode step's
    ``decode_sync`` to the end of the next step's ``decode_dispatch``,
    over the spans that lie wholly inside the window (a span cut by the
    window's edge is left out, and so is its pair)."""
    w0, w1 = red["window"]
    out, sync_end = [], None
    for name, s, d in sorted(red.get("program") or [], key=lambda e: e[1]):
        if name not in (DECODE_SYNC, DECODE_DISPATCH):
            continue
        whole = w0 < s and s + d < w1
        if name == DECODE_SYNC:
            sync_end = s + d if whole else None
        else:
            if sync_end is not None and whole:
                out.append((sync_end, s + d))
            sync_end = None
    return out


def slice_trace(red: dict, t0: float, t1: float) -> dict:
    """A reduced trace cut to ``[t0, t1]``, made the window: executable
    runs wholly inside it, operations, harness and program spans clipped,
    each operation named by its instruction alone (``%fusion.2``, what
    ``devtrace.op_name`` reads) and every time in whole nanoseconds: a
    recorded slice small enough to keep with the tests."""
    def ns(events):
        return [[n, int(s), int(d)] for n, s, d in events]
    cut = {"window": [int(t0), int(t1)], "devices": list(red["devices"]),
           "modules": {d: ns(devtrace.inside(red["modules"][d], t0, t1))
                       for d in red["devices"]},
           "ops": {d: ns([[n.split(" = ", 1)[0], s, dur] for n, s, dur in
                          devtrace.clip(red["ops"][d], t0, t1)])
                   for d in red["devices"]},
           "host": ns(devtrace.clip(red["host"], t0, t1))}
    if "program" in red:
        cut["program"] = ns(devtrace.clip(red["program"], t0, t1))
    return cut
