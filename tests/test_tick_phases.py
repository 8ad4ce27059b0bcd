"""The serving tick's host phases: the spans inside ServingEngine.step(),
their nesting, the self-time counters they sum into
(`stats["tick_phase_seconds"]`), and what the profiler sees of them.
Counts and identities only: a CPU's times are never compared with a
number."""
import collections

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import Request, ServingEngine
from mxnet_tpu.serving.engine import TICK_PHASES

# span -> the spans it may be nested in, as the engine opens them
PARENTS = {
    "serving.step": {"serving.drain"},
    "serving.admit": {"serving.step"},
    "serving.sync_slot": {"serving.admit", "serving.finish"},
    "serving.assemble": {"serving.step"},
    "serving.dispatch": {"serving.step"},
    "serving.dispatch.launch": {"serving.dispatch"},
    "serving.dispatch.wait": {"serving.dispatch"},
    "serving.dispatch.fetch": {"serving.dispatch"},
    "serving.fanout": {"serving.step"},
    "serving.finish": {"serving.fanout"},
}
EVERY_TICK = ["serving.step", "serving.assemble", "serving.dispatch",
              "serving.dispatch.launch", "serving.dispatch.wait",
              "serving.dispatch.fetch", "serving.fanout"]
NEW = [n for n in PARENTS if n != "serving.dispatch"]


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


def _requests():
    rng = np.random.default_rng(5)
    return [Request(rng.integers(1, 97, n).tolist(), new,
                    request_id=f"t{i}", seed=i)
            for i, (n, new) in enumerate([(3, 2), (11, 4), (5, 3)])]


class _Capture:
    """The span events of one engine, as its event hook saw them."""

    def __init__(self, eng):
        self.eid, self.events = eng._eid, []

    def __call__(self, ev):
        if ev.get("engine") == self.eid:
            self.events.append(ev)

    def __enter__(self):
        telemetry.add_event_hook(self)
        return self

    def __exit__(self, *exc):
        telemetry.remove_event_hook(self)

    def named(self, name):
        return [ev for ev in self.events if ev["name"] == name]


@pytest.fixture(scope="module")
def served():
    """One engine, three requests over two slots, served to the end: the
    events of the window and the stats at its close, as the benchmark's
    runner takes them (reset at the start, copied at the end)."""
    eng = _engine()
    eng.serve([Request([1, 2, 3], 2)])          # compile outside the window
    eng.reset_stats()
    reqs = _requests()
    with _Capture(eng) as cap:
        done = eng.serve(reqs)
    assert [r.status for r in done] == ["finished"] * 3
    return eng, reqs, cap, dict(eng.stats)


# -- the spans ----------------------------------------------------------------

def test_the_tick_phases_are_the_ten_spans():
    assert {"serving." + ph for ph in TICK_PHASES} == set(PARENTS)


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_a_served_request_logs_each_span_under_its_parent(served, name):
    _, _, cap, _ = served
    evs = cap.named(name)
    assert evs, f"no {name} event"
    assert {ev["parent"] for ev in evs} <= PARENTS[name]
    assert all("status" not in ev and ev["self"] <= ev["dur"] for ev in evs)


def test_sync_slot_nests_under_admission_and_under_finish(served):
    _, reqs, cap, _ = served
    parents = collections.Counter(
        ev["parent"] for ev in cap.named("serving.sync_slot"))
    assert parents == {"serving.admit": len(reqs),
                       "serving.finish": len(reqs)}


@pytest.mark.parametrize("name", EVERY_TICK)
def test_every_tick_logs_the_spans_that_need_no_admission(served, name):
    eng, _, cap, st = served
    ticks = [ev["tick"] for ev in cap.named("serving.step")]
    assert ticks == list(range(ticks[0], ticks[0] + len(ticks)))
    assert ticks[-1] == eng._tick
    # every tick of a drain has work, so every tick dispatches
    assert len(cap.named(name)) == len(ticks) == st["decode_dispatches"]


@pytest.mark.parametrize("name", ["serving.admit", "serving.finish"])
def test_admit_and_finish_carry_the_request_and_its_slot(served, name):
    _, reqs, cap, _ = served
    evs = cap.named(name)
    assert sorted(ev["request"] for ev in evs) == sorted(r.id for r in reqs)
    assert all(0 <= ev["slot"] < 2 for ev in evs)
    if name == "serving.admit":
        assert {ev["request"]: ev["prompt_len"] for ev in evs} \
            == {r.id: r.prompt_len for r in reqs}


def test_dispatch_keeps_its_attributes(served):
    _, _, cap, st = served
    evs = cap.named("serving.dispatch")
    assert all({"engine", "active", "prefill_tokens", "drafted"} <= set(ev)
               for ev in evs)
    assert sum(ev["prefill_tokens"] for ev in evs) == st["prefill_tokens"]


# -- the counter is the spans' sum --------------------------------------------

def test_the_phases_sum_to_the_wall_of_the_steps(served):
    _, _, cap, st = served
    wall = sum(ev["dur"] for ev in cap.named("serving.step"))
    assert wall > 0
    assert sum(st["tick_phase_seconds"].values()) \
        == pytest.approx(wall, abs=1e-9)


@pytest.mark.parametrize("phase", TICK_PHASES)
def test_a_phase_is_the_self_time_of_its_spans(served, phase):
    _, _, cap, st = served
    evs = cap.named("serving." + phase)
    assert st["tick_phase_seconds"][phase] \
        == pytest.approx(sum(ev["self"] for ev in evs), abs=1e-9)
    assert st["tick_phase_seconds"][phase] > 0


def test_the_counter_family_holds_the_same_numbers(served):
    eng, _, _, _ = served
    fam = telemetry.get("serving_tick_phase_seconds_total")
    mine = {c["phase"]: c["value"] for c in fam.snapshot()["children"]
            if c["engine"] == eng._eid}
    assert mine == eng.stats["tick_phase_seconds"]
    assert set(mine) == set(TICK_PHASES)


def test_reset_stats_zeroes_every_phase():
    eng = _engine()
    eng.serve([Request([1, 2, 3], 2)])
    assert all(v > 0 for v in eng.stats["tick_phase_seconds"].values())
    eng.reset_stats()
    assert eng.stats["tick_phase_seconds"] == dict.fromkeys(TICK_PHASES, 0.0)


# -- span self time -----------------------------------------------------------

@pytest.mark.parametrize("child_raises", [False, True])
def test_self_time_is_duration_minus_the_children(child_raises):
    with telemetry.span("tickphase.outer") as outer:
        with telemetry.span("tickphase.first") as first:
            with telemetry.span("tickphase.inner") as inner:
                pass
        try:
            with telemetry.span("tickphase.second") as second:
                if child_raises:
                    raise KeyError("x")
        except KeyError:
            pass
    assert inner.self_s == inner.dur and second.self_s == second.dur
    assert first.self_s == first.dur - inner.dur
    assert outer.self_s == outer.dur - (first.dur + second.dur)
    assert 0 <= outer.self_s <= outer.dur
    evs = {ev["name"]: ev for ev in telemetry.events()[-4:]}
    assert evs["tickphase.outer"]["self"] == outer.self_s
    assert evs["tickphase.first"]["self"] == first.self_s
    assert ("status" in evs["tickphase.second"]) == child_raises
    # self times partition their root
    assert sum(ev["self"] for ev in evs.values()) \
        == pytest.approx(outer.dur, abs=1e-12)


def test_the_flight_ring_takes_an_event_with_a_key_named_self(tmp_path):
    rec = telemetry.flight.install(out_dir=str(tmp_path))
    try:
        with telemetry.span("tickphase.flight"):
            pass
        got = [ev for ev in rec.events() if ev.get("kind") == "span"
               and ev["name"] == "tickphase.flight"]
    finally:
        telemetry.flight.uninstall()
    assert len(got) == 1 and got[0]["self"] == got[0]["dur"]


# -- a dispatch that raises ---------------------------------------------------

@pytest.mark.parametrize("where", ["assemble", "launch"])
def test_a_raising_dispatch_closes_its_spans_and_is_requeued(where):
    eng = _engine(retry_backoff_s=0.0)
    eng.serve([Request([1, 2, 3], 2)])
    eng.reset_stats()
    boom = {"left": 1}

    def hook(engine, phase="step", requests=()):
        if phase == "decode" and boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("injected")

    real = eng._unified_fn

    def raising_program():
        if not boom["left"]:
            return real()
        boom["left"] -= 1

        def fn(*args):
            raise RuntimeError("injected")
        return fn

    if where == "assemble":
        eng.dispatch_hook = hook
    else:
        eng._unified_fn = raising_program
    req = Request([4, 5, 6, 7], 3, request_id="again")
    with _Capture(eng) as cap:
        done = eng.serve([req])
    assert [r.status for r in done] == ["finished"]
    assert len(req.output_tokens) == 3
    st = eng.stats
    assert st["dispatch_errors"] == 1 and st["dispatch_retries"] == 1
    failed = [ev["name"] for ev in cap.events if ev.get("status") == "error"]
    assert failed == {
        "assemble": ["serving.assemble"],
        "launch": ["serving.dispatch.launch", "serving.dispatch"],
    }[where]
    # the supervisor caught it inside the tick: the tick's own span is clean
    assert all("status" not in ev for ev in cap.named("serving.step"))
    # a closed span books its self time, raised through or not
    assert sum(st["tick_phase_seconds"].values()) == pytest.approx(
        sum(ev["dur"] for ev in cap.named("serving.step")), abs=1e-9)


# -- the profiler -------------------------------------------------------------

class _Annotation:
    made = []

    def __init__(self, name, **kw):
        self.name = name
        _Annotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotated(monkeypatch):
    """What jax.profiler.TraceAnnotation was asked for while an engine
    served three requests with the profiler's device trace marked as
    running, and the events of the same run."""
    import jax
    import mxnet_tpu.profiler as prof
    eng = _engine()
    eng.serve([Request([1, 2, 3], 2)])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(_Annotation, "made", [])
    monkeypatch.setitem(prof._state, "jax_trace", True)
    with _Capture(eng) as cap:
        eng.serve(_requests())
    monkeypatch.setitem(prof._state, "jax_trace", False)
    traced = collections.Counter(_Annotation.made)
    _Annotation.made.clear()
    eng.serve(_requests())
    return traced, cap, list(_Annotation.made)


@pytest.mark.parametrize("name", NEW)
def test_each_new_span_is_annotated_once_while_the_trace_runs(annotated,
                                                              name):
    traced, cap, _ = annotated
    assert traced[name] == len(cap.named(name)) > 0


def test_no_annotation_is_made_while_no_trace_runs(annotated):
    traced, _, untraced = annotated
    assert traced["serving.dispatch"] > 0
    assert untraced == []


# -- the repair that rides along ----------------------------------------------

def test_pool_free_pages_is_fresh_after_a_plain_admission():
    """No prefix cache, no adapter pool: admission leases the slot's pages
    and the gauge says so before any dispatch has run."""
    eng = _engine()
    free = eng.stats["pool_free_pages"]
    assert free == eng.page_pool.num_free
    eng.submit(Request([1, 2, 3, 4, 5], 2))
    admitted = []
    real = eng._dispatch

    def dispatch():
        admitted.append(eng.stats["pool_free_pages"])
        return real()

    eng._dispatch = dispatch
    eng.step()
    assert admitted == [eng.page_pool.num_free] and admitted[0] < free
