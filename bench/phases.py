"""A traced run of one cell that keeps the program's phase spans.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--slice <file.json>]

Runs the cell as ``run.py --trace 1`` does (``harness.run_cell``), with
one difference: the reduction of the trace also keeps the scheduler's
``sched.*`` host spans (``progspans.with_program``). The last line of
standard output is the result as ``run.py`` prints it, with
``decode_turnaround_ms`` among the metrics, the idle gaps labelled by
phase, and a ``program`` object: the window's device-idle seconds, the
part of them under a program span and that part by phase, the host
seconds each phase took, and the decode turnarounds. ``--slice`` writes
a recorded slice of the window: the first prefill chunk followed by two
decode steps with no other chunk between, with their spans.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402
import harness  # noqa: E402
import progspans  # noqa: E402

PREFILL_CHUNK = "sched.prefill_chunk"
PREFILL_SYNC = "sched.prefill_sync"


def keep_program(reduce):
    """``reduce_xplane`` that also keeps the program spans."""
    def reduce_xplane(path):
        return progspans.with_program(reduce(path), path)
    return reduce_xplane


def pick_slice(red: dict):
    """``(t0, t1)`` from the start of a ``prefill_chunk`` span through
    the span after the second ``decode_sync`` that follows it, with no
    other ``prefill_chunk`` between; ``None`` if the window has none."""
    prog = sorted(red["program"], key=lambda e: e[1])
    for i, (name, t0, _) in enumerate(prog):
        if name != PREFILL_CHUNK:
            continue
        syncs = 0
        for j in range(i + 1, len(prog) - 1):
            if prog[j][0] == PREFILL_CHUNK:
                break
            syncs += prog[j][0] == progspans.DECODE_SYNC
            if syncs == 2:
                return t0, prog[j + 1][1] + prog[j + 1][2]
    return None


def report(red: dict) -> dict:
    ib = progspans.idle_by_phase(red)
    host = {}
    for name, _, d in red["program"]:
        host[name] = host.get(name, 0.0) + d / 1e9
    turns = progspans.decode_turnarounds(red)
    syncs = [(s, s + d) for n, s, d in red["program"] if n == PREFILL_SYNC]
    plain = [b - a for a, b in turns
             if not any(a < e and s < b for s, e in syncs)]

    def ms(vals):
        vals = sorted(v / 1e6 for v in vals)
        return {"n": len(vals),
                "mean": sum(vals) / len(vals) if vals else None,
                "p50": harness.percentile(vals, 50) if vals else None,
                "p90": harness.percentile(vals, 90) if vals else None}

    return {"idle_s": ib["idle_s"], "idle_under_program_s":
            ib["under_program_s"], "idle_by_phase_s": ib["phases"],
            "host_s_by_phase": dict(sorted(host.items(),
                                           key=lambda kv: -kv[1])),
            "decode_turnaround_ms": ms([b - a for a, b in turns]),
            "decode_turnaround_ms_no_prefill_sync": ms(plain)}


def main(argv=None, setup_t0=None) -> int:
    setup_t0 = time.perf_counter() if setup_t0 is None else setup_t0
    ap = argparse.ArgumentParser(description="one traced cell, with the "
                                 "program's phase spans")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    # the harness's reduction keeps only its own bench.* host spans
    devtrace.reduce_xplane = keep_program(devtrace.reduce_xplane)
    try:
        res = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               setup_t0)
    except harness.NoChip as e:
        harness.log(f"bench: {e}; refusing to run")
        return 3
    run = res.pop("_run")
    for line in res.pop("_log"):
        harness.log(line)
    val = harness.reader("decode_turnaround_ms")(run)
    if val is not None:
        res["metrics"]["decode_turnaround_ms"] = {"value": val, "unit": "ms"}
    res["breakdown"]["idle_gaps"] = progspans.idle_gaps(run.trace)
    res["program"] = prog = report(run.trace)
    harness.log(f"device idle {prog['idle_s']:.6f} s of the window, "
                f"{prog['idle_under_program_s']:.6f} s of it under a "
                f"sched.* span")
    if args.slice:
        cut = pick_slice(run.trace)
        if cut is None:
            harness.log("no prefill chunk followed by two decode steps")
        else:
            with open(args.slice, "w") as f:
                json.dump(progspans.slice_trace(run.trace, *cut), f)
    print(json.dumps(res), flush=True)
    for name, c in res["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(setup_t0=T0))
