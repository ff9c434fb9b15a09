#!/usr/bin/env python3
"""Readings behind a cell's limits and rate, on the chip, in one process.

Every reading is one run of the cell through ``harness.run``, the same
set-up, window and check that ``run.py`` makes; one line of JSON each.

    python3 chipbench/control.py --workload qwen2.5-3b.chat \
        --seeds 1,2,3,4 --controls 3 --seconds 10

reads the program's numbers on each seed and, on the first ``--controls``
seeds, puts the control in the program's place (the reference in the next
precision below the one the configuration states): those runs must come
out not correct.

    python3 chipbench/control.py --workload qwen2.5-3b.chat \
        --seeds 5 --seconds 30 --rates 4,5,6

sweeps an open-loop cell's offered rate instead (one run per rate) and
prints, per rate, its metrics and how long the backlog took to drain after
the window: the knee is the highest rate whose backlog does not grow.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.bench import harness  # noqa: E402


def reading(cell, seed, seconds, **kw):
    res = harness.run(cell, seed, seconds, False,
                      t_start=time.perf_counter(), **kw)
    line = {"seed": seed, **{k: v for k, v in kw.items() if v}}
    line.update((k, res[k]) for k in ("correct", "attempted", "failed",
                                      "metrics", "window", "compared"))
    print(json.dumps(harness._finite(line)), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--controls", type=int, default=0,
                    help="how many of the first seeds read the control")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        for rate in (float(r) for r in args.rates.split(",")):
            reading(cell, seeds[0], args.seconds, rate=rate)
    else:
        for i, seed in enumerate(seeds):
            reading(cell, seed, args.seconds, control=i < args.controls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
