"""Selections of the traced calls that several metric readers share."""

from __future__ import annotations

from typing import List, Tuple

from .traffic import quantile


def traced(run) -> List[Tuple]:
    """(call, span) of the traced calls; none where the trace holds no
    device operation (no TPU plane), so device readers find nothing."""
    if run.trace is None or not run.trace.ops:
        return []
    return run.traced


def decode_only(run) -> List[Tuple]:
    """(call, span) of the engine steps that ran no prefill."""
    return [(c, s) for c, s in traced(run)
            if c.name == "advance" and c.step and not c.prefills]


def prefilling(run) -> List[Tuple]:
    """(call, span) of the calls in which the engine prefilled."""
    return [(c, s) for c, s in traced(run) if c.prefills]


def busy_ns(run, span) -> float:
    return run.trace.busy_ns(span.t0, span.t1)


def idle_share(run):
    """Percent of the traced window in which no operation ran on the
    device (mean over the cell's chips); the reader of every
    ``idle_share.<cells>`` metric, which differ only in what they move."""
    if run.trace is None or not run.trace.ops:
        return None
    w0, w1 = run.trace.window
    return 100.0 * (1.0 - run.trace.busy_ns(w0, w1) / (w1 - w0))


def ttft_p95(run):
    """95th percentile, over every request of the window, of the time from
    when it was due (open loop) or sent (closed loop) to its first token
    reaching the client, in ms; the reader of ``ttft_p95_ms`` and of the
    per-layer ``ttft_p95_ms.<cell>`` of a cell that does not judge it end
    to end."""
    reqs = getattr(run.bench, "requests", None)
    if not reqs:
        return None
    return quantile([(r.times[0] - r.due) * 1e3 for r in reqs if r.times],
                    0.95)
