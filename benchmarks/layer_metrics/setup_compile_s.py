"""Compile: seconds of set-up spent in the backend compiler, reading the
persistent cache included: long on the first run in a checkout, short
after."""


def read(run, label=None):
    return run.setup_meter["compile_s"]
