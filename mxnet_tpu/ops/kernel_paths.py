"""Which implementation each traced kernel call took: (kernel, path) ->
calls, `path` being 'pallas' (the Mosaic kernel) or 'xla' (the dense or
einsum form). Counted while a program is traced, so the serving engine can
turn the difference over its program's first call into
serving_kernel_path_total (docs/OBSERVABILITY.md): a silent fall to the
dense path on the chip is then a number. Kept apart from the kernels'
modules so that reading the counts imports no Pallas.

TILES holds, the same way, the block a Mosaic kernel was BUILT with where
it chooses one from the call's shapes: (kernel, tile) -> calls, `tile` the
chosen sizes as `name=value` pairs in the order the kernel gives them
('pages=4,keys=256,rows=64'). The engine turns it into
serving_kernel_tile_total."""

PATHS = {}
TILES = {}


def note_path(kernel, path):
    PATHS[(kernel, path)] = PATHS.get((kernel, path), 0) + 1


def note_tile(kernel, **sizes):
    key = (kernel, ",".join(f"{k}={v}" for k, v in sizes.items()))
    TILES[key] = TILES.get(key, 0) + 1
