"""ISSUE 17: end-to-end request tracing + TTFT phase budget + SLO burn.

Covers: W3C traceparent parse/format round-trips (invalid headers
IGNORED per spec, never rejected), the HTTP edge adopting/echoing the
caller's trace id and threading it into the request timeline, the
injected-clock TTFT phase decomposition (the five `telemetry.PHASES`
telescope to EXACTLY the first-token latency on one engine clock),
the spilled-tier variant (host_pagein phase + kv_tier="spilled" at
first token), export/adopt migration stitching one trace across two
engines (same trace id, original t_begin, accumulated phase budget —
and tools/trace_report folds the Chrome export into ONE waterfall),
multi-window burn-rate arithmetic against a numpy sliding-window
oracle, the `/sloz` endpoint schema, the fast-burn flight-dump latch
firing exactly once per objective, and `SheddingPolicy(slo=...)`
counting a burning objective toward the overload level.
"""
import importlib
import json
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import (Request, ServingEngine, ServingFrontend,
                               SheddingPolicy)
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry.request_trace import PHASES
from mxnet_tpu.telemetry.slo import SLO, SLOEngine

_NET = {}


def _tiny():
    if "net" not in _NET:
        cfg = GPT2Config(vocab_size=97, units=32, num_layers=2,
                         num_heads=2, max_length=64, dropout=0.0,
                         attention_dropout=0.0)
        mx.rng.seed(3)
        net = GPT2ForCausalLM(cfg)
        net.initialize(mx.init.Normal(0.05))
        _NET["net"] = net
    return _NET["net"]


def _engine(**kw):
    # shapes mirror tests/test_kv_spill.py's engines (num_slots=2,
    # max_length=64, page_size=8, xla, prefix cache at 64 or the
    # 4-page spill config): in a full tier-1 run every dispatch here
    # is a jit-cache HIT, not a fresh compile
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("prefix_cache", True)
    kw.setdefault("prefix_cache_pages", 64)
    return ServingEngine(_tiny(), **kw)


class Tick:
    """Injectable engine/SLO clock — deterministic phase arithmetic."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


def _trace_for(rid, engine=None, status=None):
    """The most recent recorded timeline for one request id."""
    out = [t for t in telemetry.request_log.recent(500)
           if t["request_id"] == rid
           and (engine is None or t["engine"] == str(engine))
           and (status is None or t["status"] == status)]
    assert out, f"no timeline for {rid!r}"
    return out[-1]


def _first_token(trace):
    evs = [e for e in trace["events"] if e["event"] == "first_token"]
    assert evs, f"no first_token event in {trace['request_id']!r}"
    return evs[-1]


# ---------------------------------------------------------------------------
# W3C trace-context round trips
# ---------------------------------------------------------------------------

def test_traceparent_parse_format_roundtrip():
    tid, sid = telemetry.new_trace_id(), telemetry.new_span_id()
    assert len(tid) == 32 and tid != "0" * 32
    assert len(sid) == 16 and sid != "0" * 16
    hdr = telemetry.format_traceparent(tid, sid)
    assert telemetry.parse_traceparent(hdr) == (tid, sid)
    # a fresh span id is minted when none is supplied
    t2, s2 = telemetry.parse_traceparent(telemetry.format_traceparent(tid))
    assert t2 == tid and len(s2) == 16 and s2 != "0" * 16
    # unsampled flag still parses; case is normalized per spec
    assert telemetry.parse_traceparent(
        telemetry.format_traceparent(tid, sid, sampled=False)) == (tid, sid)
    assert telemetry.parse_traceparent(
        f"00-{tid.upper()}-{sid.upper()}-01") == (tid, sid)
    # future versions with extra fields are tolerated (spec: parse
    # the known prefix), version ff is forbidden
    assert telemetry.parse_traceparent(
        f"01-{tid}-{sid}-01-extrafield") == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-abc-def-01",
    "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",          # forbidden version
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",          # zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",          # zero span id
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",          # short trace id
    "00-" + "a" * 32 + "-" + "b" * 15 + "-01",          # short span id
    "00-" + "a" * 32 + "-" + "b" * 16 + "-1",           # short flags
    "00-" + "g" * 32 + "-" + "b" * 16 + "-01",          # non-hex
    "00-" + "a" * 32 + "-" + "b" * 16,                  # missing flags
])
def test_traceparent_invalid_headers_ignored(bad):
    assert telemetry.parse_traceparent(bad) is None


def test_http_edge_adopts_and_echoes_trace_context():
    telemetry.request_log.clear()
    tid = "ab" * 16
    want = telemetry.format_traceparent(tid, "cd" * 8)

    def post(body, headers=()):
        req = urllib.request.Request(
            f"http://{fe.host}:{fe.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **dict(headers)})
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, dict(r.headers), json.loads(r.read())

    # the frontend's backend mirrors tests/test_frontend.py's engine
    # shape (num_slots=2, max_length=32, no prefix
    # cache) so its programs are already compiled in a tier-1 run
    backend = ServingEngine(_tiny(), num_slots=2, max_length=32,
                            page_size=8, attn_impl="xla")
    with ServingFrontend(backend, keepalive_s=0.05,
                         step_idle_s=0.005) as fe:
        code, hdrs, body = post(
            {"prompt": [1, 2, 3, 4], "max_new_tokens": 3,
             "stream": False, "request_id": "tp0"},
            headers=[("traceparent", want)])
        assert code == 200 and body["status"] == "finished"
        # the response echoes the SAME trace id (fresh span)
        echoed = telemetry.parse_traceparent(hdrs.get("traceparent"))
        assert echoed is not None and echoed[0] == tid
        # a malformed header is ignored per spec: 200, FRESH trace
        code2, hdrs2, body2 = post(
            {"prompt": [1, 2, 3], "max_new_tokens": 2,
             "stream": False, "request_id": "tp1"},
            headers=[("traceparent", "zz-not-a-trace-00")])
        assert code2 == 200 and body2["status"] == "finished"
        fresh = telemetry.parse_traceparent(hdrs2.get("traceparent"))
        assert fresh is not None and fresh[0] != tid
    # the propagated id landed on the recorded timeline
    assert _trace_for("tp0")["trace_id"] == tid
    assert _trace_for("tp1")["trace_id"] == fresh[0]


# ---------------------------------------------------------------------------
# TTFT phase decomposition
# ---------------------------------------------------------------------------

def test_phase_budget_sums_to_ttft_injected_clock():
    """On one injected clock the five phases TELESCOPE: queue_wait +
    prefix_match + host_pagein + prefill_chunks + first_decode is
    exactly the recorded TTFT — no epsilon, same floats."""
    telemetry.request_log.clear()
    tick = Tick()
    eng = _engine(clock=tick)
    rng = np.random.default_rng(11)
    req = Request(rng.integers(1, 97, size=12).tolist(), 4,
                  request_id="ph0")
    eng.submit(req)
    tick.advance(0.25)              # the queue_wait the clock will see
    steps = 0
    while req.status != "finished":
        eng.step()
        tick.advance(0.5)
        steps += 1
        assert steps < 100
    tr = _trace_for("ph0", engine=eng._eid)
    ft = _first_token(tr)
    ph = tr["phases"]
    assert set(ph) <= set(PHASES)
    assert ph["queue_wait"] == 0.25
    assert ph["prefix_match"] == 0.0        # same frozen-step instant
    assert "host_pagein" not in ph          # no spill tier configured
    assert sum(ph.values()) == ft["ttft"]
    assert ft["kv_tier"] == "cold"
    # the per-event spans agree with the accumulated budget
    spans = {}
    for e in tr["events"]:
        if e["event"] == "phase":
            spans[e["phase"]] = spans.get(e["phase"], 0.0) + e["dur"]
    assert spans == ph


def test_phase_budget_real_clock_and_chrome_export():
    telemetry.request_log.clear()
    eng = _engine()
    rng = np.random.default_rng(13)
    done = eng.serve([Request(rng.integers(1, 97, size=9).tolist(), 3,
                              request_id=f"rc{i}", seed=50 + i)
                      for i in range(3)])
    assert all(r.status == "finished" for r in done)
    for i in range(3):
        tr = _trace_for(f"rc{i}")
        total = sum(tr["phases"].values())
        assert abs(total - _first_token(tr)["ttft"]) < 1e-6
    # the Chrome export renders each phase as a cat="phase" slice named
    # by the phase itself, on the request's own track
    ct = telemetry.chrome_trace()
    names = {e["name"] for e in ct["traceEvents"]
             if e.get("cat") == "phase"}
    assert names and names <= set(PHASES)


def test_phase_spilled_pagein_and_tier_label():
    """A radix hit on a SPILLED prefix pages the payload back in: the
    admitting request's budget grows a host_pagein phase and its first
    token is labeled kv_tier="spilled"."""
    telemetry.request_log.clear()
    rng = np.random.default_rng(17)
    shared = rng.integers(1, 97, size=24).tolist()
    churn = [rng.integers(1, 97, size=17).tolist() for _ in range(6)]
    eng = _engine(prefix_cache_pages=4, host_kv_bytes=1 << 22)
    eng.serve([Request(shared + [5, 6, 7], 3, request_id="warm")])
    for i, p in enumerate(churn):               # force the spill
        eng.serve([Request(p, 2, request_id=f"c{i}")])
    eng.serve([Request(shared + [8, 9], 3, request_id="hit")])
    assert eng.stats["kv_pagein_pages"] >= 1
    tr = _trace_for("hit")
    assert tr["phases"].get("host_pagein", 0.0) > 0.0
    assert _first_token(tr)["kv_tier"] == "spilled"
    assert abs(sum(tr["phases"].values())
               - _first_token(tr)["ttft"]) < 1e-6
    # the cold start got the cold label, and the TTFT-by-prompt
    # histogram grew children for both tiers
    assert _first_token(_trace_for("warm"))["kv_tier"] == "cold"
    tiers = {k[1] for k in eng._ttft_children}
    assert {"cold", "spilled"} <= tiers


def test_phase_names_are_a_closed_enum():
    with pytest.raises(ValueError, match="unknown phase"):
        telemetry.request_log.phase("x", "0", "warmup", 0.1)


# ---------------------------------------------------------------------------
# migration stitches ONE trace
# ---------------------------------------------------------------------------

def test_migrated_request_is_one_stitched_trace():
    """Export mid-PREFILL (before the first token), adopt on a second
    engine: the continuation reuses the origin's trace id and start,
    accumulates its phase budget on top, and records first_token — so
    the stitched trace decomposes the migrated request's TTFT too."""
    telemetry.request_log.clear()
    # num_slots=3 + chunk_tokens=4, no prefix cache: the exact shape
    # tests/test_chunked_prefill.py already compiled
    mk = dict(num_slots=3, chunk_tokens=4, prefix_cache=False)
    eng1, eng2 = _engine(**mk), _engine(**mk)
    tid = telemetry.new_trace_id()
    rng = np.random.default_rng(19)
    req = Request(rng.integers(1, 97, size=14).tolist(), 4,
                  request_id="mig", seed=4, do_sample=True,
                  temperature=0.9)
    req.trace = {"trace_id": tid}
    eng1.submit(req)
    eng1.step()                 # admit + first prompt chunk only
    assert req.status == "prefilling" and not req.output_tokens
    moved = eng1.export_requests()
    assert moved == [req] and req.status == "exported"
    eng2.adopt(req, migrated_from=eng1._eid)
    steps = 0
    while eng2.has_work:
        eng2.step()
        steps += 1
        assert steps < 300
    assert req.status == "finished"

    origin = _trace_for("mig", engine=eng1._eid, status="migrated")
    cont = _trace_for("mig", engine=eng2._eid, status="finished")
    # one trace: same id, original start, continuation marked resumed
    assert origin["trace_id"] == tid and cont["trace_id"] == tid
    assert cont["t_begin"] == origin["t_begin"]
    assert "resumed_at" in cont["events"][0]
    assert cont.get("migrated_from") == eng1._eid
    # the phase budget ACCUMULATED across the hop: every phase the
    # origin measured is present in the continuation with >= its time
    assert origin["phases"].get("queue_wait", 0.0) > 0.0
    for name, dur in origin["phases"].items():
        assert cont["phases"].get(name, 0.0) >= dur
    # first token landed on the ADOPTER; undercount-never-overcount:
    # the stitched budget stays within the first-token latency (the
    # export->adopt gap is unattributed, never invented)
    ft = _first_token(cont)
    assert sum(cont["phases"].values()) <= ft["ttft"] + 1e-6

    # tools/trace_report folds the two engines into ONE waterfall,
    # keyed by the request's stable "req <id>" track name
    trace_report = importlib.import_module("tools.trace_report")
    by_req, _, procs = trace_report.collect(
        telemetry.chrome_trace()["traceEvents"])
    evs = by_req["req mig"]
    engines = {procs[e["pid"]] for e in evs}
    assert engines == {f"engine {eng1._eid}", f"engine {eng2._eid}"}


# ---------------------------------------------------------------------------
# burn-rate arithmetic vs a numpy oracle
# ---------------------------------------------------------------------------

def test_burn_rate_matches_numpy_oracle():
    slo = SLO("oracle", ttft_p99_ms=100.0, target=0.98,
              fast_window_s=60.0, slow_window_s=600.0, min_events=10)
    tick = Tick()
    eng = SLOEngine([slo], clock=tick)
    rng = np.random.default_rng(23)
    ts = np.sort(rng.uniform(0.0, 600.0, size=400))
    good = rng.random(400) >= 0.3
    for t, g in zip(ts, good):
        tick.t = float(t)
        # good => under the 100 ms bound, bad => over it
        eng.observe_ttft(0.05 if g else 0.5)

    def oracle(t_now, window):
        m = ts >= t_now - window
        n = int(m.sum())
        if n < slo.min_events:
            return 0.0
        return float((~good[m]).sum() / n) / (1.0 - slo.target)

    for t_now in (600.0, 630.0, 660.0, 900.0, 1200.0):
        rows = eng.evaluate(t_now=t_now)
        assert len(rows) == 1
        r = rows[0]
        assert r["fast"]["burn_rate"] == pytest.approx(
            oracle(t_now, 60.0), abs=1e-12)
        assert r["slow"]["burn_rate"] == pytest.approx(
            oracle(t_now, 600.0), abs=1e-12)
        assert r["fast_burning"] == (
            r["fast"]["burn_rate"] >= slo.fast_burn)


def test_burn_rate_min_events_guard():
    slo = SLO("early", ttft_p99_ms=1.0, min_events=10)
    tick = Tick()
    eng = SLOEngine([slo], clock=tick)
    for i in range(9):                      # nine straight failures...
        tick.t = float(i)
        eng.observe_ttft(5.0)
    row = eng.evaluate(t_now=9.0)[0]
    assert row["fast"]["burn_rate"] == 0.0  # ...must not page early
    assert not row["fast_burning"]
    tick.t = 9.5
    eng.observe_ttft(5.0)                   # the tenth trips it
    row = eng.evaluate(t_now=9.5)[0]
    assert row["fast"]["burn_rate"] == pytest.approx(1.0 / 0.01)
    assert row["fast_burning"]


def test_slo_per_dimension_series_split():
    slo = SLO("split", ttft_p99_ms=100.0, per=("priority",),
              min_events=1)
    tick = Tick()
    eng = SLOEngine([slo], clock=tick)
    eng.observe_ttft(0.5, priority=0)       # bad for priority 0
    eng.observe_ttft(0.05, priority=1)      # good for priority 1
    rows = {tuple(sorted(r["labels"].items())): r
            for r in eng.evaluate(t_now=0.0)}
    assert rows[(("priority", "0"),)]["fast"]["bad"] == 1
    assert rows[(("priority", "1"),)]["fast"]["bad"] == 0


def test_slo_validation():
    with pytest.raises(ValueError):
        SLO("none-set")                     # needs a bound
    with pytest.raises(ValueError):
        SLO("bad-target", ttft_p99_ms=1.0, target=1.0)
    with pytest.raises(ValueError):
        SLO("bad-dim", ttft_p99_ms=1.0, per=("flavor",))


# ---------------------------------------------------------------------------
# /sloz endpoint
# ---------------------------------------------------------------------------

def test_sloz_snapshot_schema_and_endpoint():
    telemetry.slo.configure([
        SLO("interactive_ttft", ttft_p99_ms=500.0, target=0.99,
            per=("priority",), min_events=2),
        SLO("decode_goodput", goodput_min=20.0, target=0.95,
            min_events=2)])
    try:
        telemetry.slo.observe_ttft(0.1, priority=0)
        telemetry.slo.observe_ttft(0.4, priority=0)
        telemetry.slo.observe_goodput(35.0)
        srv = telemetry.IntrospectionServer(0)
        try:
            with urllib.request.urlopen(srv.url + "/sloz",
                                        timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"] == "application/json"
                snap = json.loads(r.read())
            with urllib.request.urlopen(srv.url + "/", timeout=10) as r:
                assert b"/sloz" in r.read()
        finally:
            srv.stop()
        assert set(snap) == {"objectives", "series", "fast_burning"}
        decls = {d["name"]: d for d in snap["objectives"]}
        assert decls["interactive_ttft"]["ttft_p99_ms"] == 500.0
        assert decls["interactive_ttft"]["per"] == ["priority"]
        assert decls["decode_goodput"]["goodput_min"] == 20.0
        for row in snap["series"]:
            assert set(row) >= {"objective", "labels", "fast", "slow",
                                "fast_burning", "slow_burning"}
            for w in ("fast", "slow"):
                assert set(row[w]) == {"window_s", "events", "bad",
                                       "burn_rate"}
        ttft_rows = [r for r in snap["series"]
                     if r["objective"] == "interactive_ttft"]
        assert ttft_rows and ttft_rows[0]["labels"] == {"priority": "0"}
        assert ttft_rows[0]["fast"]["events"] == 2
        assert snap["fast_burning"] == []
    finally:
        telemetry.slo.configure(())


# ---------------------------------------------------------------------------
# fast-burn flight latch + shedding integration
# ---------------------------------------------------------------------------

def test_fast_burn_latches_exactly_one_flight_dump(tmp_path):
    rec = flight.install(out_dir=str(tmp_path / "fd"),
                         stall_timeout=1e9,
                         queue_full_threshold=10 ** 6)
    tick = Tick()
    eng = SLOEngine([SLO("burny", ttft_p99_ms=1.0, min_events=5,
                         fast_window_s=60.0)], clock=tick)
    try:
        for i in range(8):
            tick.advance(0.1)
            eng.observe_ttft(5.0)           # all bad
        assert eng.fast_burning() == ["burny"]
        assert "slo_burn:burny" in rec.latched
        assert len(rec.dumps) == 1
        # a sustained burn stays latched: repeat evaluations dump NOTHING
        for _ in range(5):
            tick.advance(1.0)
            eng.evaluate()
        assert len(rec.dumps) == 1
        # burn recedes (fast window drains), then re-ignites: the
        # flight latch still holds until an operator rearms
        tick.advance(120.0)
        assert eng.fast_burning() == []
        for _ in range(8):
            tick.advance(0.1)
            eng.observe_ttft(5.0)
        assert eng.fast_burning() == ["burny"]
        assert len(rec.dumps) == 1
    finally:
        flight.uninstall()


class _StubGauge:
    def set(self, v):
        self.value = v


class _StubSched:
    num_queued = 0
    num_active = 0


class _StubEngine:
    """The slice of ServingEngine that SheddingPolicy.assess reads."""

    def __init__(self, clock):
        self.scheduler = _StubSched()
        self._clock = clock
        self._metrics = {"overload_level": _StubGauge()}

    def admission_capacity_estimate(self):
        return 100


def test_shedding_policy_counts_burning_objective():
    tick = Tick()
    slo_eng = SLOEngine([SLO("shed_ttft", ttft_p99_ms=1.0,
                             min_events=5, fast_window_s=60.0)],
                        clock=tick)
    pol = SheddingPolicy(queue_low=4, queue_high=8, slo=slo_eng,
                         slo_eval_interval_s=0.0)
    eng = _StubEngine(tick)
    assert pol.assess(eng) == 0             # calm: no events, no queue
    for _ in range(6):
        tick.advance(0.1)
        slo_eng.observe_ttft(5.0)           # torch the error budget
    assert pol.assess(eng) == 1             # burning alone: ELEVATED
    assert pol.snapshot()["slo_burning"] == ["shed_ttft"]
    eng.scheduler.num_queued = 4            # + backlog at the low mark
    assert pol.assess(eng) == 2             # burning + backlog: OVERLOAD
    assert eng._metrics["overload_level"].value == 2
    # slo=False switches the signal off entirely
    off = SheddingPolicy(queue_low=4, queue_high=8, slo=False)
    eng.scheduler.num_queued = 0
    assert off.assess(eng) == 0


def test_shedding_policy_burn_evaluation_is_throttled():
    tick = Tick()
    slo_eng = SLOEngine([SLO("cached", ttft_p99_ms=1.0, min_events=2,
                             fast_window_s=60.0)], clock=tick)
    pol = SheddingPolicy(queue_low=4, queue_high=8, slo=slo_eng,
                         slo_eval_interval_s=10.0)
    eng = _StubEngine(tick)
    for _ in range(4):
        tick.advance(0.1)
        slo_eng.observe_ttft(5.0)
    assert pol.assess(eng) == 1
    # the burn drains out of the fast window, but within the throttle
    # interval assess still reports the CACHED verdict...
    tick.advance(5.0)
    slo_eng.clear()
    assert pol.assess(eng) == 1
    # ...and re-evaluates once the interval has elapsed
    tick.advance(10.0)
    assert pol.assess(eng) == 0
