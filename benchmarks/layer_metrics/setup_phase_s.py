"""Set-up by phase, in seconds; the label names the phase: `import`
(process start to the runner, Python and JAX), `weights`, `reference` (the
comparison, which in a serving cell is also the warm-up of the unified
program) and `warmup`."""


def read(run, label=None):
    return run.phases.get(label)
