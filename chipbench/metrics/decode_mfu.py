"""Model flops of the live slots of each decode step over the step's host
wall time times the chip's bf16 peak, summed over the traced steps that
ran no prefill."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "host_clock"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    steps = layers.decode_only(run)
    wall = sum(c.t1 - c.t0 for c, _ in steps)
    if not steps or wall <= 0:
        return None
    flops = sum(run.family.decode_flops(run.cell.config, c.live)
                for c, _ in steps)
    return 100.0 * flops / (wall * run.peak_flops)
