#!/usr/bin/env python3
"""Run one cell of the benchmark that BENCHMARK.json describes.

    python3 bench/run.py --workload gcn-products.labor0 --seed 7 \
        --seconds 10 --trace 0

Exits non-zero, with no result line, where JAX finds no TPU or fewer
chips than the cell asks for. See ``bench/harness.py`` for what a run
does.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# the TPU runtime's logs stay inside the checkout, not in /tmp
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(_ROOT, "bench", ".cache", "tpu_logs"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
