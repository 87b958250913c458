"""Run one benchmark cell once and print its result as one JSON line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
