#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on and needs a TPU: without one it
exits 2 and prints no result. The last line of stdout is one JSON object
(`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1`
also `breakdown`, and last `checks`: each number compared with the
reference beside its limit, also the last lines of stderr). See
`bench/harness.py` for how a cell is found and `PERF.md` for the cells.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
