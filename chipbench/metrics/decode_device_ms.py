"""Device time of one engine decode step: device-busy time inside the
harness's ``advance`` calls that ran no prefill, over those calls."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    steps = layers.decode_only(run)
    if not steps:
        return None
    return sum(layers.busy_ns(run, s) for _, s in steps) / len(steps) / 1e6
