"""Share of the roofline reached by the TinyBio kernels (fir, delineate,
stockham_fft, svm): the least time of the recordings the lane launched in
the traced window (for each kernel the larger of its flops over the bf16
peak and its bytes over peak bandwidth, which every one of these float32
kernels is bound by; rows a partial batch pads with count nothing) over
the kernels' summed device time."""

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "requests_per_s"


def read(run):
    if run.trace is None or not run.trace.ops or run.rows_traced <= 0:
        return None
    cfg = run.cell.config
    w0, w1 = run.trace.window
    flops_s, bytes_s = (run.peaks["bf16_flops_per_s"],
                        run.peaks["hbm_bytes_per_s"])
    least = sum(max(flops / flops_s, moved / bytes_s)
                for flops, moved in run.family.kernel_costs(cfg).values())
    # device seconds summed over the chips (op_ns averages over them)
    spent = run.trace.n_devices * sum(
        run.trace.op_ns(w0, w1, pattern)
        for pattern in cfg["kernels"].values()) / 1e9
    if spent <= 0:
        return None
    return 100.0 * run.rows_traced * least / spent
