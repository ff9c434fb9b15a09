"""Share of the roofline reached by the Pallas selective scan: the least
time of each prefill's scans (one a Mamba layer; for each, the larger of
its flops over peak and its bytes over peak bandwidth: x, delta, B and C
read, y and the final state written) over the kernel's device time, in
the calls that prefilled.  A family without ``scan_cost`` has no scan, and
a trace without the kernel reads nothing."""

from chipbench.bench import layers

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "ttft_p95_ms"


def read(run):
    fam, cfg = run.family, run.cell.config
    pattern = cfg.get("kernels", {}).get("mamba_scan")
    if pattern is None or not hasattr(fam, "scan_cost"):
        return None
    least = spent = 0.0
    for c, s in layers.prefilling(run):
        spent += run.trace.op_ns(s.t0, s.t1, pattern) / 1e9
        for n in c.prefills:
            flops, moved = fam.scan_cost(cfg, n)
            least += fam.scans_per_prefill(cfg) * max(
                flops / run.peak_flops, moved / run.peak_bytes)
    if spent <= 0:
        return None
    return 100.0 * least / spent
