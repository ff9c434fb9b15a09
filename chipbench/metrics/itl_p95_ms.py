"""95th percentile of every gap between consecutive tokens of every
request of the window, as the client received them."""

import numpy as np

from chipbench.bench.traffic import quantile

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(run):
    reqs = getattr(run.bench, "requests", None)
    if not reqs:
        return None
    gaps = [np.diff(r.times) for r in reqs if len(r.times) > 1]
    if not gaps:
        return None
    return quantile(np.concatenate(gaps) * 1e3, 0.95)
