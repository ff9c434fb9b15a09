"""Idle share of the device (``layers.idle_share``) in a cell judged by
requests per second."""

from chipbench.bench.layers import idle_share as read  # noqa: F401

UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
LAYER, MOVES = "device", "requests_per_s"
