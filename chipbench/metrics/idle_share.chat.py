"""Idle share of the device (``layers.idle_share``) in a cell whose decode
steps set the gap between tokens."""

from chipbench.bench.layers import idle_share as read  # noqa: F401

UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
LAYER, MOVES = "device", "itl_p95_ms"
