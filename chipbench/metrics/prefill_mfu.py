"""Prefill flops (every matmul and the causal attention of each prompt)
over the host wall time of the calls that prefilled times the chip's
bf16 peak.  Those calls may also have run a decode step, which this does
not count, so the share is a lower bound."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "host_clock"
LAYER, MOVES = "model step", "requests_per_s"


def read(run):
    calls = layers.prefilling(run)
    wall = sum(c.t1 - c.t0 for c, _ in calls)
    if not calls or wall <= 0:
        return None
    flops = sum(run.family.prefill_flops(run.cell.config, s)
                for c, _ in calls for s in c.prefills)
    return 100.0 * flops / (wall * run.peak_flops)
