"""A cell is one entry of `workloads` in BENCHMARK.json: a configuration
under a traffic mix. Everything else about it is found by those two names:
`configs/<config>.json` and `traffic/<traffic>.json`, each of which names
the code that reads it (`runner`, `generator`, `reference`)."""
import copy
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class CellError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise CellError(f"cannot read {path}: {e}") from e


def merge(base, override):
    """`override` laid over `base`, dict by dict; other values replace."""
    out = copy.deepcopy(base)
    for k, v in override.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


class Cell:
    """The files of one cell. With `tiny`, each file's own `tiny` block is
    laid over it: the sizes `--check` runs on a CPU."""

    def __init__(self, workload, tiny=False):
        bench = _load(os.path.join(CHECKOUT, "BENCHMARK.json"))
        self.run_seconds = bench["run_seconds"]
        entries = {w["name"]: w for w in bench["workloads"]}
        if workload not in entries:
            raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                            f"it has {sorted(entries)}")
        self.entry = entries[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.tiny = tiny
        cfg_entry = next((c for c in bench["configs"]
                          if c["name"] == self.entry["config"]), None)
        if cfg_entry is None:
            raise CellError(f"workload {workload!r} names config "
                            f"{self.entry['config']!r}, which BENCHMARK.json "
                            "does not list")
        self.config = _load(os.path.join(CHECKOUT, cfg_entry["file"]))
        self.traffic = _load(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        if tiny:
            self.config = merge(self.config, self.config.get("tiny", {}))
            self.traffic = merge(self.traffic, self.traffic.get("tiny", {}))
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    def _metrics(self, entries):
        return [m for m in entries
                if "workloads" not in m or self.name in m["workloads"]]

    def module(self, kind, name):
        """`benchmarks/<kind>/<name>.py`, found by a name in a data file."""
        try:
            return importlib.import_module(f"benchmarks.{kind}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"benchmarks.{kind}.{name}":
                raise
            raise CellError(f"{self.name}: no benchmarks/{kind}/{name}.py")\
                from e


def peaks(device_kind):
    """Published peaks of one chip, from peaks.json. A device that is not
    in the table is an error, not a default."""
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"benchmarks/peaks.json (it has {sorted(table)})")
    return table[device_kind]
