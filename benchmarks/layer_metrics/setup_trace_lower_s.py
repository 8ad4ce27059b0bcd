"""Compile: seconds of set-up spent tracing and lowering programs
(jax.monitoring), which no compilation cache saves."""


def read(run, label=None):
    return run.setup_meter["trace_lower_s"]
