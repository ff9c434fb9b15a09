"""Share of the roofline reached by the Pallas flash-attention kernel:
the least time of each layer's causal attention over the prompt (flops
from the shapes, causal pairs only; bytes of q, k, v and the output) over
the kernel's device time, in the calls that prefilled."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "requests_per_s"


def read(run):
    cfg = run.cell.config
    pattern = cfg["kernels"]["flash_attention"]
    least = spent = 0.0
    for c, s in layers.prefilling(run):
        spent += run.trace.op_ns(s.t0, s.t1, pattern) / 1e9
        for n in c.prefills:
            flops, moved = run.family.flash_cost(cfg, n)
            least += cfg["num_hidden_layers"] * max(
                flops / run.peak_flops, moved / run.peak_bytes)
    if spent <= 0:
        return None
    return 100.0 * least / spent
