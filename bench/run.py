"""Run one benchmark cell: load, warm up, measure, check, print one line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last the ``checks`` compared, each with its limit).
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would otherwise keep its logs at a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
