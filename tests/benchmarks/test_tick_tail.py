"""The readers of the tick's tail, on runs made by hand: `tick_wall_ms.*`,
`tick_tail_excess_share`, `slowest_tick_ms.*`, `tick_gc_ms` and
`tick_host_off_cpu_ms`, from the engine's `tick_seconds`, `slowest_ticks`,
`tick_gc_seconds` and `tick_cpu_seconds`; and their eleven entries in
BENCHMARK.json, held by name."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import (  # noqa: E402
    slowest_tick_ms, tick_gc_ms, tick_host_off_cpu_ms,
    tick_tail_excess_share, tick_wall_ms)

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = ["gpt2_774m.doc_backlog", "falcon_h1_34b.chat_backlog"]
OTHER_CELLS = ["nemotron3_super_120b.doc_backlog",
               "kimi_linear_48b.longdoc_backlog", "bert_base_mlm.phase2_t512"]
# name -> (unit, layer, reader)
ELEVEN = {
    "tick_wall_ms.p50": ("ms", "dispatch", tick_wall_ms),
    "tick_wall_ms.p99": ("ms", "dispatch", tick_wall_ms),
    "tick_wall_ms.max": ("ms", "dispatch", tick_wall_ms),
    "tick_tail_excess_share": ("%", "dispatch", tick_tail_excess_share),
    "slowest_tick_ms.wait": ("ms", "dispatch", slowest_tick_ms),
    "slowest_tick_ms.launch": ("ms", "dispatch", slowest_tick_ms),
    "slowest_tick_ms.fetch": ("ms", "dispatch", slowest_tick_ms),
    "slowest_tick_ms.sync_slot": ("ms", "scheduler", slowest_tick_ms),
    "slowest_tick_ms.gc": ("ms", "dispatch", slowest_tick_ms),
    "tick_gc_ms": ("ms", "dispatch", tick_gc_ms),
    "tick_host_off_cpu_ms": ("ms", "dispatch", tick_host_off_cpu_ms),
}

# a tick of 2 s that waited 1.5 s for the device, spent 0.3 s in the copies
# and 0.1 s in the collector, and was on the CPU for 0.21 s of its 2
PHASES = {"step": 0.02, "admit": 0.0, "sync_slot": 0.04, "assemble": 0.01,
          "dispatch": 0.01, "dispatch.launch": 0.1, "dispatch.wait": 1.5,
          "dispatch.fetch": 0.3, "fanout": 0.02, "finish": 0.0}
STALL = {"tick": 17, "wall_s": 2.0, "cpu_s": 0.21, "gc_s": 0.1,
         "gc_collections": [3, 1, 1], "phases": PHASES,
         "spans": dict.fromkeys(PHASES, 1), "queued": 900, "active": 16}
# the other seven kept are plain ticks of 0.1 s
PLAIN = [dict(STALL, tick=20 + i, wall_s=0.1) for i in range(7)]
# by hand, ms: 1e3 x the phase
SLOWEST_WANT = {"wait": 1500.0, "launch": 100.0, "fetch": 300.0,
                "sync_slot": 40.0, "gc": 100.0}
# 500 ticks of 0.1 s and the one of 2 s, in 400 dispatches
STATS = {
    "decode_dispatches": 400,
    "tick_seconds": {"count": 501, "sum": 52.0, "max": 2.0, "p50": 0.0993,
                     "p99": 0.11},
    "slowest_ticks": [STALL] + PLAIN,
    "tick_gc_seconds": 0.8,
    "tick_phase_seconds": {ph: 100 * s for ph, s in PHASES.items()},
    "tick_cpu_seconds": 21.0,
}


def _run(trace=False, **engine_stats):
    said = []
    facts = {"engine_stats": engine_stats} if engine_stats else {}
    return types.SimpleNamespace(facts=facts, trace=trace, say=said.append,
                                 said=said)


def _read(name, run):
    reader = ELEVEN[name][2]
    return reader.read(run, name.partition(".")[2] or None)


# -- each reading, by hand ----------------------------------------------------

@pytest.mark.parametrize("label, want", [("p50", 99.3), ("p99", 110.0),
                                         ("max", 2000.0)])
def test_tick_wall_reads_the_engines_histogram_in_ms(label, want):
    assert tick_wall_ms.read(_run(**STATS), label) == pytest.approx(want)


@pytest.mark.parametrize("label", sorted(SLOWEST_WANT))
def test_each_label_reads_its_part_of_the_slowest_tick(label):
    assert slowest_tick_ms.read(_run(**STATS), label) \
        == pytest.approx(SLOWEST_WANT[label])


def test_the_excess_is_what_the_kept_ticks_cost_beyond_a_mean_tick_each():
    got = tick_tail_excess_share.read(_run(**STATS))
    assert got == pytest.approx(100 * (2.7 - 8 * 52 / 501) / 52)
    assert got == pytest.approx(3.60, abs=0.005)


@pytest.mark.parametrize("kept", [1, 3, 8])
def test_the_excess_is_zero_when_the_kept_ticks_equal_the_mean(kept):
    stats = dict(STATS, tick_seconds=dict(STATS["tick_seconds"], count=40,
                                          sum=4.0, max=0.1),
                 slowest_ticks=PLAIN[:1] * kept)
    assert tick_tail_excess_share.read(_run(**stats)) \
        == pytest.approx(0.0, abs=1e-12)


def test_the_collectors_tax_is_its_seconds_a_dispatch():
    assert tick_gc_ms.read(_run(**STATS)) == pytest.approx(1e3 * 0.8 / 400)


def test_off_cpu_is_the_ticks_wall_outside_the_wait_minus_their_cpu():
    # 100 x (2.0 - 1.5) s of host phases less 21 s on the CPU, over 400
    # dispatches; the 150 s of dispatch.wait left out
    assert tick_host_off_cpu_ms.read(_run(**STATS)) \
        == pytest.approx(1e3 * (50.0 - 21.0) / 400)


# -- nothing to read, and nothing booked --------------------------------------

@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_without_engine_stats_or_dispatches_there_is_nothing_to_read(name):
    assert _read(name, _run()) is None
    assert _read(name, _run(decode_dispatches=0, tokens_emitted=3)) is None


@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_an_engine_that_booked_no_such_key_reads_zero(name):
    """Three counters and nothing else, as the run made by hand in
    test_bench_units.py has and as a parent's program gives."""
    run = _run(trace=True, decode_dispatches=5, prefill_tokens=100,
               tokens_emitted=3)
    assert _read(name, run) == 0.0
    assert run.said == []


@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_an_engine_reset_and_never_stepped_reads_zero(name):
    empty = {"count": 0, "sum": 0.0, "max": 0.0, "p50": 0.0, "p99": 0.0}
    run = _run(decode_dispatches=5, tick_seconds=empty, slowest_ticks=[],
               tick_gc_seconds=0.0,
               tick_phase_seconds=dict.fromkeys(PHASES, 0.0),
               tick_cpu_seconds=0.0)
    assert _read(name, run) == 0.0


# -- the earlier line ---------------------------------------------------------

def test_the_reader_of_the_kept_records_says_them_whole_on_one_line():
    run = _run(trace=True, **STATS)
    for name in sorted(ELEVEN):
        _read(name, run)
    assert len(run.said) == 1 and "\n" not in run.said[0]
    head, _, tail = run.said[0].partition(": ")
    assert "slowest ticks" in head
    assert json.loads(tail) == STATS["slowest_ticks"]


# -- BENCHMARK.json -----------------------------------------------------------

ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}


@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_benchmark_json_lists_the_entry_by_name_for_the_two_cells(name):
    m = ENTRIES[name]
    unit, layer, _ = ELEVEN[name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == (unit, "lower", "program_span", layer, "serve_tokens_per_s")
    assert m["workloads"] == CELLS
    assert not set(m["workloads"]) & set(OTHER_CELLS)


def test_the_eleven_are_all_this_prs_readers_serve():
    files = {"tick_wall_ms", "tick_tail_excess_share", "slowest_tick_ms",
             "tick_gc_ms", "tick_host_off_cpu_ms"}
    served = [m["name"] for m in BENCH["per_layer"]
              if m["name"].partition(".")[0] in files]
    assert sorted(served) == sorted(ELEVEN)
    assert {n.partition(".")[0] for n in ELEVEN} == files


@pytest.mark.parametrize("name", sorted(n for n in ELEVEN
                                        if n.startswith("slowest_tick_ms.")))
def test_a_phase_label_names_a_phase_the_engine_books(name):
    from benchmarks.layer_metrics.tick_host_ms import PHASES as LABELS
    from mxnet_tpu.serving.engine import TICK_PHASES
    label = name.partition(".")[2]
    if label != "gc":
        assert LABELS[label] in TICK_PHASES
    assert set(PHASES) == set(TICK_PHASES)
