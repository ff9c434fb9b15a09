"""Share of the roofline reached by the Pallas grouped matmul of the held
experts: the least time of each of its calls (the larger of flops over
peak and bytes over peak bandwidth, from the rows the engine routed to
held experts in that launch and layer and the weights of the experts that
got rows, each read once) over the kernel's device time, in the traced
calls whose launches ran it."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "ttft_p95_ms"


def read(run):
    launches = getattr(run.bench, "moe_launches", None)
    if not launches:
        return None
    cfg = run.cell.config
    pattern = cfg["kernels"]["moe_gmm"]
    least = spent = 0.0
    for c, s in layers.traced(run):
        if c.id not in launches:
            continue
        spent += run.trace.op_ns(s.t0, s.t1, pattern) / 1e9
        for _, rows in launches[c.id]:
            least += sum(max(flops / run.peak_flops, moved / run.peak_bytes)
                         for flops, moved in run.family.gmm_costs(cfg, rows))
    if spent <= 0:
        return None
    return 100.0 * least / spent
