"""Find a cell's knee once: the same cell at several fixed arrival rates,
in one process, on the chip.

    python bench/sweep.py --workload <cell> --seconds <s> --seed <n> \\
        --rates 0.5 1.0 1.5

Each rate is one run of the cell with its traffic's ``rate_per_s``
replaced (no output check). One JSON line per rate: the end-to-end
metrics, the queue wait, and the backlog at the window's close (requests
due in the window not yet admitted). The knee is the highest rate whose
backlog stays near zero and whose tokens per second still follow the
offered load; a cell's rate is then written into its traffic file.
"""

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--preroll", type=float,
                    help="pre-roll seconds in place of the mix's own")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    spec = harness.load_spec()
    _, _, _, tp = harness.load_cell(spec, args.workload)
    for rate in args.rates:
        t = time.perf_counter()
        tp_r = copy.deepcopy(tp)
        tp_r["arrival"]["rate_per_s"] = rate
        if args.preroll is not None:
            tp_r["preroll_s"] = args.preroll
        try:
            res = harness.run_cell(args.workload, args.seed, args.seconds,
                                   False, t, traffic_override=tp_r,
                                   check_outputs=False)
        except harness.NoChip as e:
            harness.log(f"sweep: {e}")
            return 3
        run = res.pop("_run")
        for line in res.pop("_log"):
            harness.log(line)
        win = run.window_requests()
        backlog = sum(1 for r in win
                      if "t_admit" not in r or r["t_admit"] >= run.t1)
        waits = [1e3 * (r["t_admit"] - r["due"]) for r in win
                 if "t_admit" in r]
        print(json.dumps({
            "rate_per_s": rate, "due": len(win), "backlog_at_close": backlog,
            "queue_wait_p50_ms": harness.percentile(waits, 50),
            "queue_wait_p90_ms": harness.percentile(waits, 90),
            "ttft_p90_ms": harness.percentile(run.ttfts_ms(), 90),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
