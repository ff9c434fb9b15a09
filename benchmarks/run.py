"""Benchmark orchestrator: one bench per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only static|gemm|tinybio|dispatch|multiqueue|transfer|serve|sharded|overload|roofline]
                                            [--trace PATH]

``--trace PATH`` exports each traced serve bench's Chrome trace JSON
(ISSUE 7): with one traced bench selected the file lands at PATH verbatim;
with several, each gets a ``PATH`` suffixed by the bench name before the
extension (``trace.json`` -> ``trace.serve.json`` / ``trace.overload.json``).
"""

import argparse
import pathlib
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_dispatch, bench_gemm_overhead, bench_multiqueue,
               bench_overload, bench_roofline, bench_serve, bench_sharded,
               bench_static, bench_tinybio, bench_transfer)

BENCHES = {
    "static": bench_static.run,        # paper Fig 2
    "gemm": bench_gemm_overhead.run,   # paper Fig 3
    "tinybio": bench_tinybio.run,      # paper Fig 4
    "dispatch": bench_dispatch.run,    # §VIII-B measured analogue
    "multiqueue": bench_multiqueue.run,  # ISSUE-3 out-of-order critical path
    "transfer": bench_transfer.run,    # ISSUE-4 explicit-transfer DAG
    "serve": bench_serve.run,          # ISSUE-2 cached-graph serving path
    "sharded": bench_sharded.run,      # ISSUE-5 mesh-sharded serving lane
    "overload": bench_overload.run,    # ISSUE-6 open-loop goodput under faults
    "roofline": bench_roofline.run,    # EXPERIMENTS §Roofline table
}

#: benches that accept run(trace_path=...) and export a Chrome trace
TRACED_BENCHES = ("serve", "overload")


def _trace_path_for(base, name, n_traced):
    """PATH verbatim for a single traced bench, name-suffixed for many."""
    if n_traced == 1:
        return base
    p = pathlib.Path(base)
    return str(p.with_name(f"{p.stem}.{name}{p.suffix or '.json'}"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="export Chrome trace JSON from the traced serve "
                         "benches (serve, overload)")
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    n_traced = sum(1 for n in names if n in TRACED_BENCHES)
    t0 = time.time()
    for name in names:
        if args.trace is not None and name in TRACED_BENCHES:
            BENCHES[name](trace_path=_trace_path_for(args.trace, name,
                                                     n_traced))
        else:
            BENCHES[name]()
        print()
    print(f"[benchmarks] {len(names)} suites in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
