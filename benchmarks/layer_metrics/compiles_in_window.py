"""Compile: programs that went to the backend compiler inside the measured
window. It must be 0; a run in which it is not is not `correct`."""


def read(run, label=None):
    return run.window_meter["compiles"]
