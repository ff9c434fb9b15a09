"""Share of the roofline reached by the engine's decode step in the long
document cell: the least time of each step (the larger of its flops over
peak and its bytes over peak bandwidth: the non-expert weights, the
weights of each MoE layer's held experts that the step routed rows to,
the live slots' latent cache) over its device-busy time, summed over the
traced steps that ran no prefill."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    launches = getattr(run.bench, "moe_launches", None)
    if not launches:
        return None
    steps = [(c, s) for c, s in layers.decode_only(run)
             if [k for k, _ in launches.get(c.id, ())] == ["generate"]]
    busy = sum(layers.busy_ns(run, s) for _, s in steps) / 1e9
    if not steps or busy <= 0:
        return None
    cfg, fam = run.cell.config, run.family
    least = 0.0
    for c, _ in steps:
        rows = launches[c.id][0][1]
        least += max(fam.decode_flops(cfg, c.live, rows) / run.peak_flops,
                     fam.decode_bytes(cfg, c.live, rows) / run.peak_bytes)
    return 100.0 * least / busy
