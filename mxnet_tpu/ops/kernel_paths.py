"""Which implementation each traced kernel call took: (kernel, path) ->
calls, `path` being 'pallas' (the Mosaic kernel) or 'xla' (the dense or
einsum form). Counted while a program is traced, so the serving engine can
turn the difference over its program's first call into
serving_kernel_path_total (docs/OBSERVABILITY.md): a silent fall to the
dense path on the chip is then a number. Kept apart from the kernels'
modules so that reading the counts imports no Pallas."""

PATHS = {}


def note_path(kernel, path):
    PATHS[(kernel, path)] = PATHS.get((kernel, path), 0) + 1
