#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 chipbench/run.py --workload qwen2.5-3b.chat --seed 1 \
        --seconds 40 --trace 0

See ``chipbench/bench/harness.py`` for what a run does and prints.
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
