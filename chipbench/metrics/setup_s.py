"""Seconds from the start of the process to the window: loading, drawing
the weights, building the server and warming (compiling) every shape the
cell's traffic uses."""

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(run):
    return run.setup_s
