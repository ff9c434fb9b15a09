"""The time to first token's 95th percentile (``layers.ttft_p95``) in the
chat cell, read per layer: there it is set by whether the densest burst's
queue for a slot tips over, which about one run in seven did on a TPU v5e
(a second mode half as high again), so no bound holds it end to end.  The
slot queue drains at the pace of the decode step that sets the gap
between tokens."""

from chipbench.bench.layers import ttft_p95 as read  # noqa: F401

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"
LAYER, MOVES = "engine and server", "itl_p95_ms"
