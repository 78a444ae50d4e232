"""Run one benchmark cell; see ``harness.py``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints earlier lines (set-up split, generator lateness, compiles inside
the window, the check) on standard error, the result as the last line of
standard output, and each compared number beside its limit as the last
lines of standard error. Exits non-zero, with no result line, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(setup_t0=T0))
