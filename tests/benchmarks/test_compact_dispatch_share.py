"""The reader of `compact_dispatch_share`, on runs made by hand: the
engine's `model_counters["live_rows"]` = (dispatches, dispatches that took
the compact feed-forward); and its entry in BENCHMARK.json, held by
name."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import compact_dispatch_share  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "falcon_h1_34b.chat_backlog"


def _run(**engine_stats):
    facts = {"engine_stats": engine_stats} if engine_stats else {}
    return types.SimpleNamespace(facts=facts)


@pytest.mark.parametrize("counted, want", [
    ([600, 597], 99.5), ([600, 0], 0.0), ([600, 600], 100.0),
    ([0, 0], 0.0)])
def test_the_share_is_compact_over_dispatches_in_per_cent(counted, want):
    run = _run(decode_dispatches=600,
               model_counters={"moe": [[600, 1, 1, 1, 1]],
                               "live_rows": counted})
    assert compact_dispatch_share.read(run) == pytest.approx(want)


def test_an_engine_without_the_counter_reads_zero():
    """Three counters and nothing else, as the run made by hand in
    test_bench_units.py has and as a parent's program gives; and a model
    that counts something else."""
    run = _run(decode_dispatches=5, prefill_tokens=100, tokens_emitted=3)
    assert compact_dispatch_share.read(run) == 0.0
    run = _run(decode_dispatches=5, model_counters={})
    assert compact_dispatch_share.read(run) == 0.0
    run = _run(decode_dispatches=5, model_counters={"moe": [[5, 1, 1, 1, 1]]})
    assert compact_dispatch_share.read(run) == 0.0


def test_without_engine_stats_or_dispatches_there_is_nothing_to_read():
    assert compact_dispatch_share.read(_run()) is None
    assert compact_dispatch_share.read(
        _run(decode_dispatches=0, model_counters={"live_rows": [0, 0]})) \
        is None


def test_benchmark_json_lists_it_by_name_for_the_falcon_cell_alone():
    entries = [m for m in BENCH["per_layer"]
               if m["name"] == "compact_dispatch_share"]
    assert len(entries) == 1
    assert entries[0] == {
        "name": "compact_dispatch_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "model",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # the layer is one BENCHMARK.json already names
    assert sum(m["layer"] == "model" for m in BENCH["per_layer"]) > 1
    # the cell reports the end-to-end metric it moves
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert CELL in moved["workloads"]


def test_the_reader_reads_what_the_engine_counts():
    """The name the reader looks up is the one the models declare."""
    from mxnet_tpu.models.hybrid import LIVE_ROWS_COUNTER
    assert list(LIVE_ROWS_COUNTER) == ["live_rows"]
    assert LIVE_ROWS_COUNTER["live_rows"][0] == (2,)
