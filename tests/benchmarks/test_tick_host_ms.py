"""The reader of the nine `tick_host_ms.*` metrics, on runs made by hand:
mean host milliseconds a dispatch by phase of the serving tick, from the
engine's `tick_phase_seconds` and the benchmark's clock around
`eng.step()`."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import tick_host_ms  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ENTRIES = [m for m in BENCH["per_layer"]
           if m["name"].startswith("tick_host_ms.")]
LABELS = ["admit", "sync_slot", "assemble", "launch", "wait", "fetch",
          "fanout", "finish", "rest"]

# four dispatches in five steps of 0.25 s: 312.5 ms of wall a dispatch
STEPS = [(i, i + 0.25, 3, 1, 0, 100) for i in range(5)]
BOOKED = {"step": 0.003, "admit": 0.004, "sync_slot": 0.008,
          "assemble": 0.002, "dispatch": 0.001, "dispatch.launch": 0.012,
          "dispatch.wait": 1.1, "dispatch.fetch": 0.016, "fanout": 0.006,
          "finish": 0.0004}
# by hand: 1e3 * seconds / 4
WANT = {"admit": 1.0, "sync_slot": 2.0, "assemble": 0.5, "launch": 3.0,
        "wait": 275.0, "fetch": 4.0, "fanout": 1.5, "finish": 0.1,
        "rest": 312.5 - 287.1}


def _run(**engine_stats):
    facts = {"steps": STEPS}
    if engine_stats:
        facts["engine_stats"] = engine_stats
    return types.SimpleNamespace(facts=facts)


@pytest.mark.parametrize("label", LABELS)
def test_each_label_reads_its_phase_per_dispatch(label):
    run = _run(decode_dispatches=4, tick_phase_seconds=BOOKED)
    assert tick_host_ms.read(run, label) == pytest.approx(WANT[label])


def test_the_nine_sum_to_the_steps_wall_per_dispatch():
    run = _run(decode_dispatches=4, tick_phase_seconds=BOOKED)
    assert sum(tick_host_ms.read(run, label) for label in LABELS) \
        == pytest.approx(1e3 * 5 * 0.25 / 4)


@pytest.mark.parametrize("label", LABELS)
def test_a_phase_the_engine_did_not_book_reads_zero_and_rest_the_tick(label):
    """Counters and no phases, as the run made by hand in
    test_bench_units.py has them and as a program without the spans
    gives: no reader is silent, and `rest` is the whole tick."""
    run = _run(decode_dispatches=4)
    want = 312.5 if label == "rest" else 0.0
    assert tick_host_ms.read(run, label) == pytest.approx(want)


@pytest.mark.parametrize("label", LABELS)
def test_without_engine_stats_or_dispatches_there_is_nothing_to_read(label):
    assert tick_host_ms.read(_run(), label) is None
    assert tick_host_ms.read(_run(decode_dispatches=0), label) is None


def test_benchmark_json_lists_the_nine_for_the_backlog_cell():
    assert [m["name"].partition(".")[2] for m in ENTRIES] == LABELS
    assert BENCH["per_layer"][-len(ENTRIES):] == ENTRIES     # appended
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) \
            == ("ms", "lower", "program_span", "serve_tokens_per_s")
        assert m["workloads"] == ["gpt2_774m.doc_backlog"]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_every_entry_names_a_phase_the_engine_books(entry):
    from mxnet_tpu.serving.engine import TICK_PHASES
    label = entry["name"].partition(".")[2]
    scheduler = label in ("admit", "sync_slot", "finish")
    assert entry["layer"] == ("scheduler" if scheduler else "dispatch")
    if label != "rest":
        assert tick_host_ms.PHASES[label] in TICK_PHASES
    else:
        # what `rest` holds beside the call: the two spans with children
        assert set(TICK_PHASES) - set(tick_host_ms.PHASES.values()) \
            == {"step", "dispatch"}
