#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, seeded weights made on the device, the comparison with the
float32 reference, warm-up of the cell's own programs), then a measured
window of `--seconds`, then ONE JSON object as the last line of the output:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics
with `--trace 0`, its per-layer metrics with `--trace 1`), `device`, and
with `--trace 1` a `breakdown`. Earlier lines are for people.

It needs the TPU and as many chips as the cell names; without them it
prints a reason on stderr and exits 3, and prints no result.

    --check          the cell's `tiny` sizes on the CPU (on as many virtual
                     devices as the cell has chips): the control flow, the
                     counts and `correct`, and no time, rate or share of a
                     device. For the tests.
    --keep-trace DIR keep the traced slice's .xplane.pb there, to be read
                     with `python -m benchmarks.trace.inspect`.
"""
import time
T_START = time.perf_counter()       # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the package is `benchmarks`; its directory must not shadow top-level
# modules (its `trace/` would hide the standard library's)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from benchmarks import cells
    from benchmarks.harness import Run, fail
    try:
        cell = cells.Cell(args.workload, tiny=args.check)
    except cells.CellError as e:
        fail(str(e), code=2)
    seconds = args.seconds if args.seconds is not None else cell.run_seconds

    if args.check:
        # before JAX starts: the CPU, with a virtual device per chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = " ".join(
            [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
            + [f"--xla_force_host_platform_device_count={cell.chips}"])
    try:
        import jax
        import mxnet_tpu
        devices = jax.devices()
    except (ImportError, RuntimeError) as e:
        fail(f"cannot start: {type(e).__name__}: {e}")
    if not os.path.abspath(mxnet_tpu.__file__).startswith(
            cells.CHECKOUT + os.sep):
        fail(f"the program was imported from {mxnet_tpu.__file__}, not from "
             f"this checkout ({cells.CHECKOUT}); nothing was run")
    want = "cpu" if args.check else "tpu"
    if devices[0].platform != want:
        fail(f"JAX found platform {devices[0].platform!r}, not {want!r}; "
             "nothing was run")
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, JAX found "
             f"{len(devices)}; nothing was run")
    if args.check:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        from mxnet_tpu.runtime import enable_compile_cache
        cache_dir = enable_compile_cache()
        # every program of the run goes to the cache, small ones too, and
        # none is evicted: a later run of the cell then compiles nothing at
        # all. (A size cap smaller than one run's programs, set from
        # outside, would evict the oldest entry first, which is the first
        # the next run asks for: a guess at why PR 22's four-chip runs got
        # 0 hits of 64, not verified.)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)

    run = Run(cell, args.seed, seconds, args.trace, devices[:cell.chips],
              T_START, keep_trace=args.keep_trace)
    if not args.check:
        run.say(f"compile cache: {cache_dir}")
    run.say(f"{cell.name}: config {cell.entry['config']}, traffic "
            f"{cell.entry['traffic']}, seed {run.seed}, window {seconds} s, "
            f"trace {args.trace}, {devices[0].device_kind} x {cell.chips}")
    cell.module("runners", cell.config["runner"]).run(run)

    from benchmarks import report
    line = report.result_line(run, check=args.check)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
