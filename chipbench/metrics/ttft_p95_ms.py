"""95th percentile, over every request of the window, of the time from
when the request was due (open loop) or sent (closed loop) to its first
token reaching the client (``layers.ttft_p95``)."""

from chipbench.bench.layers import ttft_p95 as read  # noqa: F401

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"
