"""Requests answered inside the window, over the window's length."""

UNIT, BETTER, SOURCE = "requests/s", "higher", "host_clock"


def read(run):
    end = run.t0 + run.seconds
    done = [t for t in run.bench.completions() if t <= end]
    return len(done) / run.seconds if done else None
