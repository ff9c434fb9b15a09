"""Host time of one decode step: the span of an ``advance`` call that ran
no prefill, less the device-busy time inside it (engine ``generate``,
server pump, stream bookkeeping)."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER, MOVES = "engine and server", "itl_p95_ms"


def read(run):
    steps = layers.decode_only(run)
    if not steps:
        return None
    host = sum((s.t1 - s.t0) - layers.busy_ns(run, s) for _, s in steps)
    return host / len(steps) / 1e6
