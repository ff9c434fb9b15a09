"""Share of the roofline reached by the engine's decode step: the least
time of each step (the larger of its flops over peak and its bytes over
peak bandwidth: every weight, the live slots' keys and values, one new
key and value per slot) over its device-busy time, summed over the
traced steps that ran no prefill."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    steps = layers.decode_only(run)
    busy = sum(layers.busy_ns(run, s) for _, s in steps) / 1e9
    if not steps or busy <= 0:
        return None
    cfg, fam = run.cell.config, run.family
    least = sum(max(fam.decode_flops(cfg, c.live) / run.peak_flops,
                    fam.decode_bytes(cfg, c.live) / run.peak_bytes)
                for c, _ in steps)
    return 100.0 * least / busy
