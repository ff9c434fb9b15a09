"""Host time per launch: the traced window less its device-busy time,
over the launches the lane's ``QueueStats`` counted in it (runtime,
dispatch, batching and the client's own calls)."""

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER, MOVES = "runtime and dispatch", "requests_per_s"


def read(run):
    if run.trace is None or not run.trace.ops or run.launches_traced <= 0:
        return None
    w0, w1 = run.trace.window
    idle = (w1 - w0) - run.trace.busy_ns(w0, w1)
    return idle / run.launches_traced / 1e6
