"""The serving tick's own tail: every tick's wall, CPU and collector's
seconds (`stats["tick_seconds"]`, `["tick_cpu_seconds"]`,
`["tick_gc_seconds"]`), the eight slowest ticks kept whole
(`stats["slowest_ticks"]`), and the garbage collector's hook they read.
Counts and inequalities only: a CPU's times are never held to a number,
apart from a sleep the test itself made.

The collector's hook is process-global, so everything that touches it is
in this one file (`--dist loadfile` keeps a file in one process)."""
import gc
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import Request, ServingEngine
from mxnet_tpu.serving.engine import SLOWEST_TICKS_KEPT, TICK_PHASES
from mxnet_tpu.telemetry import tracing

RECORD_KEYS = {"tick", "wall_s", "cpu_s", "gc_s", "gc_collections",
               "phases", "spans", "queued", "active"}
NEW_STATS = ("tick_seconds", "slowest_ticks", "tick_cpu_seconds",
             "tick_gc_seconds")
EMPTY = {"tick_seconds": {"count": 0, "sum": 0.0, "max": 0.0, "p50": 0.0,
                          "p99": 0.0},
         "slowest_ticks": [],
         "tick_cpu_seconds": 0.0,
         "tick_gc_seconds": 0.0}


def _engine(**kw):
    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(net, **kw)


def _requests(n=3, new=(2, 4, 3)):
    rng = np.random.default_rng(5)
    return [Request(rng.integers(1, 97, 3 + 4 * i).tolist(), new[i % len(new)],
                    request_id=f"t{i}", seed=i) for i in range(n)]


def _warm(eng):
    eng.serve([Request([1, 2, 3], 2)])          # compile outside the window
    eng.reset_stats()
    return eng


def _at_tick(n, what):
    """A step hook that calls what() in the n-th tick from now. It fires
    inside serving.step and under no child span."""
    seen = []

    def hook(engine):
        seen.append(engine._tick)
        if len(seen) == n:
            what()
    hook.seen = seen
    return hook


@pytest.fixture(scope="module")
def served():
    """Twenty-odd ticks of one engine, its second tick held up by a sleep
    of 50 ms: the stats at the end and the serving.step events."""
    eng = _warm(_engine())
    eng.dispatch_hook = _at_tick(2, lambda: time.sleep(0.05))
    steps = []
    on_span = lambda ev: steps.append(ev) if ev["name"] == "serving.step" \
        and ev.get("engine") == eng._eid else None
    telemetry.add_event_hook(on_span)
    try:
        done = eng.serve(_requests(8, new=(5, 7, 6)))
    finally:
        telemetry.remove_event_hook(on_span)
    assert [r.status for r in done] == ["finished"] * 8
    return eng, dict(eng.stats), steps


# -- the record ---------------------------------------------------------------

def test_at_most_eight_records_are_kept_slowest_first(served):
    _, st, steps = served
    assert len(steps) >= 20
    kept = st["slowest_ticks"]
    assert len(kept) == SLOWEST_TICKS_KEPT == 8
    walls = [r["wall_s"] for r in kept]
    assert walls == sorted(walls, reverse=True)
    # they ARE the eight slowest of the ticks that ran
    assert walls == sorted((ev["dur"] for ev in steps), reverse=True)[:8]
    assert len({r["tick"] for r in kept}) == 8


def test_every_kept_record_is_whole_and_its_phases_sum_to_its_wall(served):
    _, st, _ = served
    for rec in st["slowest_ticks"]:
        assert set(rec) == RECORD_KEYS
        for key in ("phases", "spans"):
            assert set(rec[key]) == set(TICK_PHASES)
        assert sum(rec["phases"].values()) \
            == pytest.approx(rec["wall_s"], abs=1e-9)
        assert 0 <= rec["cpu_s"] <= rec["wall_s"]
        assert 0 <= rec["gc_s"] <= rec["wall_s"]
        assert len(rec["gc_collections"]) == 3
        # every tick of a drain dispatches once
        assert rec["spans"]["step"] == rec["spans"]["dispatch.wait"] == 1
        assert rec["spans"]["sync_slot"] \
            == rec["spans"]["admit"] + rec["spans"]["finish"]
        assert rec["active"] <= 2 and rec["queued"] >= 0


def test_a_sleeping_step_hook_is_the_slowest_tick_in_step_and_off_the_cpu(
        served):
    eng, st, _ = served
    rec = st["slowest_ticks"][0]
    assert rec["tick"] == eng.dispatch_hook.seen[1]
    assert rec["wall_s"] >= 0.05
    assert rec["phases"]["step"] >= 0.05
    assert max(rec["phases"], key=rec["phases"].get) == "step"
    assert rec["wall_s"] - rec["cpu_s"] >= 0.04


def test_the_histogram_holds_every_tick_and_the_exact_maximum(served):
    eng, st, steps = served
    ts = st["tick_seconds"]
    assert set(ts) == {"count", "sum", "max", "p50", "p99"}
    assert ts["count"] == len(steps) == st["decode_dispatches"]
    assert ts["max"] == st["slowest_ticks"][0]["wall_s"] \
        == max(ev["dur"] for ev in steps)
    assert ts["sum"] == pytest.approx(sum(ev["dur"] for ev in steps))
    assert ts["sum"] == pytest.approx(sum(st["tick_phase_seconds"].values()))
    assert 0 < ts["p50"] <= ts["p99"] <= ts["max"]
    child = telemetry.get("serving_tick_seconds").labels(eng._eid)
    b = child.buckets
    assert b[0] == pytest.approx(1e-3) and b[-1] >= 16.0
    assert all(hi / lo <= 2 ** 0.25 * (1 + 1e-9) for lo, hi in zip(b, b[1:]))


def test_the_cpu_seconds_are_a_part_of_the_ticks_wall(served):
    eng, st, steps = served
    # the CPU clock is read inside the wall clock, at the two ends of
    # serving.step and nowhere else
    assert 0 < st["tick_cpu_seconds"] <= st["tick_seconds"]["sum"] - 0.04
    assert st["tick_cpu_seconds"] \
        == pytest.approx(sum(ev["cpu_s"] for ev in steps))
    fam = telemetry.get("serving_tick_cpu_seconds_total")
    assert fam.labels(eng._eid).value == eng.stats["tick_cpu_seconds"]


def test_the_threads_clock_is_read_twice_a_tick_and_no_more(monkeypatch):
    from mxnet_tpu.serving import engine as engine_module
    eng = _warm(_engine())
    calls, real = [], time.thread_time
    monkeypatch.setattr(engine_module.time, "thread_time",
                        lambda: calls.append(1) or real())
    eng.serve(_requests())
    assert len(calls) == 2 * eng.stats["tick_seconds"]["count"] > 0


def test_the_step_event_carries_cpu_and_collector_seconds(served):
    _, st, steps = served
    assert all({"cpu_s", "gc_s", "tick", "queued", "active"} <= set(ev)
               for ev in steps)
    by_tick = {ev["tick"]: ev for ev in steps}
    for rec in st["slowest_ticks"]:
        ev = by_tick[rec["tick"]]
        assert (ev["dur"], ev["cpu_s"], ev["gc_s"]) \
            == (rec["wall_s"], rec["cpu_s"], rec["gc_s"])
    # and only that event: the other tick spans keep the attributes they had
    other = [ev for ev in telemetry.events()
             if ev["name"].startswith("serving.")
             and ev["name"] != "serving.step"]
    assert other and not any("cpu_s" in ev or "gc_s" in ev for ev in other)


def test_a_plain_span_is_as_it_was():
    with telemetry.span("tickrecord.plain", k=1) as sp:
        pass
    ev = telemetry.events()[-1]
    assert set(ev) == {"name", "ts", "dur", "self", "depth", "parent",
                       "thread", "k"}
    assert not hasattr(sp, "cpu_s")


# -- the collector ------------------------------------------------------------

def test_a_collection_inside_a_tick_is_counted_in_that_tick():
    eng = _warm(_engine())
    eng.dispatch_hook = _at_tick(2, gc.collect)
    before = telemetry.gc_totals()
    eng.serve(_requests())
    st = eng.stats
    rec = {r["tick"]: r for r in st["slowest_ticks"]}[
        eng.dispatch_hook.seen[1]]
    assert rec["gc_s"] > 0 and rec["gc_collections"][2] >= 1
    assert rec["gc_s"] <= rec["wall_s"]
    assert st["tick_gc_seconds"] >= rec["gc_s"]
    assert st["tick_gc_seconds"] == pytest.approx(
        sum(r["gc_s"] for r in st["slowest_ticks"]))     # under eight ticks
    after = telemetry.gc_totals()
    assert after[0] >= before[0] + rec["gc_s"] and after[3] >= before[3] + 1
    fam = telemetry.get("serving_tick_gc_seconds_total")
    assert fam.labels(eng._eid).value == st["tick_gc_seconds"]


def test_the_hook_is_installed_once_however_many_engines_are_built():
    _engine(), _engine()
    telemetry.install_gc_hook()
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = telemetry.gc_totals()
    gc.collect()
    after = telemetry.gc_totals()
    assert after[0] > before[0] and after[3] == before[3] + 1
    assert after[1:3] == before[1:3]
    # the totals are the hook's own: zeroing the registry leaves them
    telemetry.reset()
    assert telemetry.gc_totals() == after


def test_a_fault_inside_the_hook_never_reaches_the_collectors_caller():
    telemetry.install_gc_hook()
    before = telemetry.gc_totals()
    # a "stop" that names no generation: the hook's own KeyError, which
    # the collector would print as unraisable if it came out
    tracing._on_gc("start", {})
    tracing._on_gc("stop", {})
    assert telemetry.gc_totals()[1:] == before[1:]
    # and it counts again afterwards
    assert isinstance(gc.collect(), int)
    assert telemetry.gc_totals()[3] == before[3] + 1


# -- reset, and two engines ---------------------------------------------------

def test_reset_stats_empties_all_four_keys_and_sets_the_slots_gauge():
    eng = _engine()
    assert {k: eng.stats[k] for k in NEW_STATS} == EMPTY
    eng.serve([Request([1, 2, 3], 2)])
    st = eng.stats
    assert st["tick_seconds"]["count"] == len(st["slowest_ticks"]) > 0
    assert st["tick_cpu_seconds"] > 0
    eng.reset_stats()
    assert {k: eng.stats[k] for k in NEW_STATS} == EMPTY
    assert telemetry.get("serving_slots").labels(eng._eid).value == 2
    # and it records again afterwards, from nothing
    eng.serve([Request([1, 2, 3], 2)])
    assert eng.stats["tick_seconds"]["count"] \
        == len(eng.stats["slowest_ticks"]) > 0


def test_two_engines_in_one_process_keep_apart():
    one, two = _warm(_engine()), _warm(_engine())
    one.dispatch_hook = _at_tick(1, lambda: time.sleep(0.05))
    one.serve(_requests())
    a, b = one.stats, two.stats
    assert a["tick_seconds"]["count"] == a["decode_dispatches"] > 0
    assert a["tick_seconds"]["max"] >= 0.05
    assert {k: b[k] for k in NEW_STATS} == EMPTY
    two.serve(_requests())
    b = two.stats
    assert b["tick_seconds"]["count"] == b["decode_dispatches"] > 0
    assert one.stats["tick_seconds"] == a["tick_seconds"]
    assert {r["tick"] for r in b["slowest_ticks"]} \
        <= set(range(1, two._tick + 1))


def test_what_stats_hands_out_is_a_copy():
    eng = _warm(_engine())
    eng.serve(_requests())
    got = eng.stats["slowest_ticks"]
    got[0]["phases"]["step"] = -1.0
    got.clear()
    assert eng.stats["slowest_ticks"][0]["phases"]["step"] > 0


def test_a_sync_outside_a_tick_is_in_no_ticks_record():
    """cancel() of a running request uploads its slot outside step(): the
    counter takes the span, the next tick's record does not."""
    eng = _warm(_engine())
    req = Request([5, 6, 7, 8], 6, request_id="gone")
    eng.submit(req)
    eng.step()
    before = eng.stats["tick_phase_seconds"]["sync_slot"]
    assert eng.cancel("gone")
    assert eng.stats["tick_phase_seconds"]["sync_slot"] > before
    eng.serve([Request([1, 2, 3], 2)])
    for rec in eng.stats["slowest_ticks"]:
        assert sum(rec["phases"].values()) \
            == pytest.approx(rec["wall_s"], abs=1e-9)
