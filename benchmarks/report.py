"""The last line of the output: the contract's keys and nothing the
contract does not name. End-to-end metrics come from the runner and the
harness's own clock; each per-layer metric from its reader,
`layer_metrics/<name>.py`, where `<name>` is the metric's name up to its
first dot. What follows the dot is handed to the reader as `label`: it
tells apart the entries one reader serves (`setup_phase_s.weights`, or the
same reading in cells whose end-to-end metrics differ).

Under `--check` a reader that finds nothing to read returns None and its
metric is left out. On the chip every metric BENCHMARK.json lists for the
cell has to be there: one that is not is an error, not a shorter line."""
from . import meter
from .trace import reduce as _reduce


def _value(metric, value):
    return {"value": float(value), "unit": metric["unit"]}


def per_layer(run, check=False):
    """The cell's per-layer metrics, each from its reader."""
    metrics = {}
    for m in run.cell.per_layer:
        # a CPU says what the program counted, and nothing else
        if check and m["source"] != "program_counter":
            continue
        name, _, label = m["name"].partition(".")
        value = run.cell.module("layer_metrics", name).read(run, label or None)
        if value is not None:
            metrics[m["name"]] = _value(m, value)
        elif not check:
            raise RuntimeError(f"{run.cell.name}: layer_metrics/{name}.py "
                               f"found nothing to read for {m['name']}")
    return metrics


def end_to_end(run):
    values = dict(run.end_to_end, setup_s=run.setup_s)
    metrics = {}
    for m in run.cell.end_to_end:
        if values.get(m["name"]) is None:
            raise RuntimeError(f"{run.cell.name} reported no {m['name']}")
        metrics[m["name"]] = _value(m, values[m["name"]])
    return metrics


def result_line(run, check=False):
    red = run.tracer.reduction
    if run.trace:
        metrics = per_layer(run, check)
    else:
        metrics = {} if check else end_to_end(run)
    ok = run.result["correct"] and not run.result.get("compiled_in_window")
    line = {"correct": bool(ok), "attempted": int(run.result["attempted"]),
            "failed": int(run.result["failed"]), "metrics": metrics,
            "device": run.device()}
    if line["device"]["memory_peak_bytes"]:
        gb = lambda n: f"{n / 1e9:.3f}"
        run.say(f"memory, GB: {gb(line['device']['memory_peak_bytes'])} on "
                "the fullest chip while the window's program ran = "
                f"{gb(max(run.held_in_window))} held by the allocator at the "
                "window's close + "
                f"{gb(run.facts.get('program_temp_bytes', 0))} of the "
                "program's own temporaries (memory_analysis, not counted by "
                "the allocator); the allocator's own peak over the whole "
                f"process {gb(meter.allocator_peak(run.devices))}; "
                f"memory_stats of chip 0: {run.devices[0].memory_stats()}")
    if check:
        run.say(f"compiles in set-up: {run.setup_meter['compiles']}; in the "
                f"window: {run.window_meter['compiles']}")
    else:
        phases = ", ".join(f"{k} {v:.2f}" for k, v in run.phases.items())
        run.say(f"set-up {run.setup_s:.2f} s ({phases}); compiles in "
                f"set-up: {run.setup_meter}; in the window: "
                f"{run.window_meter}")
    if run.trace and red is not None and not check:
        line["device"]["busy_s"] = red["busy_s"]
        line["device"]["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": _reduce.top(red["by_op"]),
                             "idle_gaps": _reduce.top(red["idle_gaps"])}
        run.say(f"traced {red['window_s']:.3f} s on {red['chips']} chip(s): "
                f"busy {red['busy_s']:.3f} s; by kind of instruction "
                f"{_reduce.top(red['by_kind'], 8)}; host spans seen "
                f"{dict(red['spans'])}")
    return line
