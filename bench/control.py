"""Readings that set the check's limit, on the chip, in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell as ``run.py`` makes it (weights,
warm-up, pre-roll, window), then the check's sample compared twice: the
program's served tokens against the float32 reference (the lower
reading), and the control (the reference with int4 weights, the next
precision below the configuration's takum8) choosing its own token at
each served position, put in the program's place and judged by the same
``correct_from`` (the upper reading). One JSON line per seed, with
``correct`` for the program and ``control_correct`` for the control.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    t = T0                 # the first run's set-up counts from the start
    for seed in args.seeds:
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t, control=True)
        except harness.NoChip as e:
            harness.log(f"control: {e}")
            return 3
        for line in res.pop("_log"):
            harness.log(line)
        res.pop("_run")
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "checks": res["checks"],
                          "control_checks": res["control_checks"],
                          "metrics": res["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
