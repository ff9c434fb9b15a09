"""The whole pipeline's share of the chips' bf16 peak: the flops of every
recording answered in the traced window over the window times the peak."""

UNIT, BETTER, SOURCE = "%", "higher", "host_clock"
LAYER, MOVES = "model step", "requests_per_s"


def read(run):
    if run.trace is None or not run.trace.ops or run.answers_traced <= 0:
        return None
    window_s = run.trace.window_ns() / 1e9
    flops = run.answers_traced * run.family.pipeline_flops(run.cell.config)
    return 100.0 * flops / (window_s * run.peak_flops)
