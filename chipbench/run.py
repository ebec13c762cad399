#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload resnet20.paper_chunk8 \\
        --seed 12345 --seconds 10 --trace 0

Run from the root of a checkout.  Exits non-zero, printing no result,
where JAX finds no TPU, fewer chips than the cell asks for, or a chip
missing from ``chipbench/peaks.json``.  The last line of stdout is the
result as one JSON object; the last lines of stderr are the numbers of
the output check beside their limits.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's log files would otherwise go to a fixed path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
