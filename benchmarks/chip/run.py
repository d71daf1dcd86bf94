"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics from a profiler trace of the window (``--trace 1``) as one JSON
object on the last line of standard output, and each number compared
with the reference beside its limit on the last lines of standard error.
Exits non-zero, printing no result, without an accelerator, with fewer
chips than the cell needs, or outside a checkout of the repository.
"""

import time

T_PROCESS = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import harness  # noqa: E402

    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
