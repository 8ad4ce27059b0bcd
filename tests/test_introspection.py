"""Live-observability tests (tier-1, ISSUE 5).

Covers: per-request lifecycle timelines + Chrome/Perfetto export
schema, the stdlib HTTP introspection server (endpoint smoke +
concurrent-scrape-during-serving soak), the anomaly-triggered flight
recorder (stall / queue-full storm / trainer NaN, each dumping exactly
once), the span error-status satellite, empty-histogram percentile
semantics, and the metrics-catalog checker.
"""
import glob
import json
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import Histogram, flight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_engine(**kw):
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import ServingEngine

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(net, **kw), cfg


# ---------------------------------------------------------------------------
# satellites: span error status, empty-percentile semantics
# ---------------------------------------------------------------------------

def test_span_error_status_on_exception():
    """A raising block unwinds through span.__exit__, the exception
    propagates, and the recorded event carries status=error + type."""
    telemetry.clear_events()
    with pytest.raises(ValueError, match="boom"):
        with telemetry.span("erroring.phase", attempt=1):
            raise ValueError("boom")
    ev = [e for e in telemetry.events()
          if e["name"] == "erroring.phase"][-1]
    assert ev["status"] == "error"
    assert ev["error"] == "ValueError"
    assert ev["attempt"] == 1 and ev["dur"] >= 0
    # a clean span records no status key at all
    with telemetry.span("clean.phase"):
        pass
    ev = [e for e in telemetry.events() if e["name"] == "clean.phase"][-1]
    assert "status" not in ev and "error" not in ev


def test_empty_histogram_percentile_is_nan():
    """Documented semantics (docs/OBSERVABILITY.md): an empty histogram
    returns float('nan') from percentile(q) — never a forged 0.0 —
    and out-of-range q raises."""
    h = Histogram("h", buckets=(1.0, 2.0))
    for q in (0, 50, 99, 100):
        assert math.isnan(h.percentile(q))
    snap = h.snapshot()
    assert "p50" not in snap and snap["count"] == 0
    json.dumps(snap, allow_nan=False)   # snapshot stays JSON-clean
    with pytest.raises(MXNetError):
        h.percentile(-1)
    with pytest.raises(MXNetError):
        h.percentile(101)
    h.observe(1.5)
    assert not math.isnan(h.percentile(50))


# ---------------------------------------------------------------------------
# request lifecycle timelines
# ---------------------------------------------------------------------------

def test_request_lifecycle_timeline():
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine(prefix_cache=True)
    rng = np.random.default_rng(5)
    reqs = [Request(rng.integers(0, cfg.vocab_size, n).tolist(), 4,
                    seed=i, request_id=f"t{i}")
            for i, n in enumerate((3, 9, 17))]
    done = eng.serve(reqs)
    assert len(done) == 3
    recent = {t["request_id"]: t for t in telemetry.request_log.recent()}
    for r in reqs:
        tr = recent[r.id]
        names = [e["event"] for e in tr["events"]]
        assert names[0] == "enqueued"
        assert names[-1] == "finished"
        assert tr["status"] == "finished"
        assert "admitted" in names and "prefill" in names
        assert "prefix_match" in names          # cache enabled
        assert names.count("decode") >= 1
        # timestamps are monotonic along the timeline
        ts = [e["ts"] for e in tr["events"]]
        assert ts == sorted(ts)
        assert tr["t_end"] >= tr["t_begin"]
        assert tr["prompt_len"] == r.prompt_len
        fin = tr["events"][-1]
        assert fin["reason"] in ("eos", "budget")
        assert fin["tokens"] == len(r.output_tokens)
        # dispatch events carry durations and per-dispatch token counts
        decodes = [e for e in tr["events"] if e["event"] == "decode"]
        assert all(e["dur"] > 0 for e in decodes)
        assert sum(e["tokens"] for e in decodes) \
            == len(r.output_tokens) - 1         # first token is prefill's


def test_rejected_and_cancelled_requests_recorded():
    """Terminal `rejected` timelines for queue-full AND over-long
    prompts (the /requests view shows rejected traffic), `cancelled`
    for cancel()."""
    from mxnet_tpu.serving import QueueFullError, Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine(max_queue=1)
    with pytest.raises(MXNetError):
        eng.submit(Request(list(range(1, 40)), 2, request_id="long"))
    eng.submit(Request([1, 2, 3], 2, request_id="ok"))
    with pytest.raises(QueueFullError):
        eng.submit(Request([4, 5, 6], 2, request_id="overflow"))
    cancelled = eng.cancel("ok")
    assert cancelled is not None
    recent = {t["request_id"]: t for t in telemetry.request_log.recent()}
    assert recent["long"]["status"] == "rejected"
    assert recent["long"]["events"][-1]["event"] == "rejected"
    assert recent["long"]["reason"] == "prompt_too_long"
    assert recent["overflow"]["status"] == "rejected"
    assert recent["overflow"]["reason"] == "queue_full"
    assert recent["ok"]["status"] == "cancelled"
    assert eng.stats["requests_rejected"] == 2


def test_speculative_timeline_records_draft_counts():
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine(speculative=True, spec_tokens=3)
    pat = [5, 9, 13]
    done = eng.serve([Request(pat * 3 + pat[:1], 8, request_id="s0")])
    assert len(done) == 1
    tr = {t["request_id"]: t
          for t in telemetry.request_log.recent()}["s0"]
    verifies = [e for e in tr["events"] if e["event"] == "verify"]
    assert verifies, "speculative dispatches must record verify events"
    for ev in verifies:
        assert 0 <= ev["accepted"] <= ev["drafted"] <= 2
        assert ev["tokens"] >= 0 and ev["dur"] > 0
    assert eng.stats["spec_draft_tokens"] \
        == sum(e["drafted"] for e in verifies)


def test_disabled_request_log_records_nothing():
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    telemetry.request_log.enabled = False
    try:
        eng, cfg = _tiny_engine()
        eng.serve([Request([1, 2, 3], 2, request_id="quiet")])
    finally:
        telemetry.request_log.enabled = True
    assert telemetry.request_log.recent() == []


# ---------------------------------------------------------------------------
# Chrome/Perfetto trace export
# ---------------------------------------------------------------------------

def _check_chrome_trace(trace):
    """Schema check: the structure ui.perfetto.dev / chrome://tracing
    actually requires, plus internal ts/dur consistency."""
    assert set(trace) >= {"traceEvents", "displayTimeUnit"}
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert set(e) >= {"name", "ph", "pid", "tid"}, e
        assert e["ph"] in ("X", "i", "M"), e
        if e["ph"] != "M":
            assert isinstance(e["ts"], float) and e["ts"] > 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")
    # every request slice must CONTAIN its phase slices (monotonically
    # consistent ts/dur — what makes the perfetto nesting render)
    by_track = {}
    for e in evs:
        if e["ph"] == "X":
            by_track.setdefault((e["pid"], e["tid"]), []).append(e)
    n_requests = 0
    for track in by_track.values():
        roots = [e for e in track if e["name"] == "request"]
        if not roots:
            continue                      # host-span tracks
        n_requests += len(roots)
        for root in roots:
            lo, hi = root["ts"], root["ts"] + root["dur"]
            for e in track:
                if e is root or e["name"] == "request":
                    continue
                assert e["ts"] >= lo - 1.0, (e, root)       # 1 µs slack
                assert e["ts"] + e.get("dur", 0) <= hi + 1.0, (e, root)
    return n_requests


def test_chrome_trace_schema_and_nesting():
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    telemetry.clear_events()
    eng, cfg = _tiny_engine()
    rng = np.random.default_rng(2)
    eng.serve([Request(rng.integers(0, cfg.vocab_size, 5).tolist(), 4,
                       request_id=f"c{i}") for i in range(3)])
    trace = telemetry.chrome_trace()
    # must be pure JSON (round-trips), with every request on its track
    trace = json.loads(json.dumps(trace))
    assert _check_chrome_trace(trace) == 3
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"request", "queued", "prefill", "decode"} <= names
    # span events ride in pid 0
    assert any(e["pid"] == 0 and e["name"] == "serving.dispatch"
               for e in trace["traceEvents"] if e["ph"] == "X")
    # the last_ms window drops everything for a 0-width window
    assert telemetry.chrome_trace(last_ms=0.0)["traceEvents"] == [] \
        or all(e["ph"] == "M"
               for e in telemetry.chrome_trace(last_ms=0.0)["traceEvents"])


# ---------------------------------------------------------------------------
# HTTP introspection server
# ---------------------------------------------------------------------------

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_server_endpoint_smoke():
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine()
    eng.serve([Request([1, 2, 3, 4], 3, request_id="smoke0")])
    srv = telemetry.IntrospectionServer(0)
    try:
        code, ctype, body = _get(srv.url + "/healthz")
        assert code == 200 and body == b"ok\n"
        assert ctype.startswith("text/plain")

        code, ctype, body = _get(srv.url + "/metrics")
        assert code == 200
        assert ctype == "text/plain; version=0.0.4; charset=utf-8"
        text = body.decode()
        assert "# TYPE serving_prefill_total counter" in text
        assert f'engine="{eng._eid}"' in text

        code, ctype, body = _get(srv.url + "/statusz")
        assert code == 200 and ctype == "application/json"
        sz = json.loads(body)
        assert sz["uptime_seconds"] >= 0
        comp = sz["components"][f"engine/{eng._eid}"]
        assert comp["config"]["num_slots"] == eng.num_slots
        assert comp["scheduler"]["active"] == {}
        assert comp["stats"]["requests_finished"] == 1
        assert sz["jit_cache"]["retraces"] is not None

        code, _, body = _get(srv.url + "/requests?n=5")
        reqs = json.loads(body)["requests"]
        assert any(t["request_id"] == "smoke0" for t in reqs)

        code, _, body = _get(srv.url + "/trace")
        trace = json.loads(body)
        assert _check_chrome_trace(trace) >= 1
        code, _, body = _get(srv.url + "/trace?last_ms=60000")
        assert code == 200 and json.loads(body)["traceEvents"]

        code, _, body = _get(srv.url + "/")
        assert code == 200 and b"/metrics" in body

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_serve_singleton_semantics():
    telemetry.stop_server()
    try:
        a = telemetry.serve(0)
        assert telemetry.serve(0) is a
        assert telemetry.serve(a.port) is a
        assert telemetry.get_server() is a
        with pytest.raises(MXNetError):
            telemetry.serve(a.port + 1)
    finally:
        telemetry.stop_server()
    assert telemetry.get_server() is None


@pytest.mark.slow
def test_concurrent_scrape_during_serving_soak():
    """Scrapers hammer every endpoint while the engine serves: no
    exceptions, no non-200s, no torn JSON/exposition snapshots."""
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine(num_slots=2)
    srv = telemetry.IntrospectionServer(0)
    failures = []
    stop = threading.Event()

    def scraper(path, parse):
        while not stop.is_set():
            try:
                code, _, body = _get(srv.url + path, timeout=10)
                if code != 200:
                    failures.append((path, code))
                elif parse:
                    json.loads(body)
                elif b"# TYPE" not in body:
                    failures.append((path, "no exposition"))
            except Exception as e:                # pragma: no cover
                failures.append((path, repr(e)))
                return
            stop.wait(0.002)

    threads = [threading.Thread(target=scraper, args=(p, j), daemon=True)
               for p, j in (("/metrics", False), ("/statusz", True),
                            ("/requests?n=20", True), ("/trace", True))]
    try:
        for t in threads:
            t.start()
        rng = np.random.default_rng(11)
        reqs = [Request(rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(2, 12))).tolist(),
                        int(rng.integers(2, 6)), seed=i,
                        request_id=f"soak{i}") for i in range(12)]
        done = eng.serve(reqs)
        assert len(done) == len(reqs)
        time.sleep(0.1)                 # one more scrape of the idle state
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        srv.stop()
    assert failures == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _assert_complete_dump(path):
    assert os.path.isdir(path)
    files = sorted(os.listdir(path))
    assert files == ["events.jsonl", "metrics.json", "state.json"]
    events = [json.loads(l)
              for l in open(os.path.join(path, "events.jsonl"))]
    metrics = json.load(open(os.path.join(path, "metrics.json")))
    state = json.load(open(os.path.join(path, "state.json")))
    assert metrics["instruments"]
    assert state["reason"] and "components" in state and \
        "requests" in state
    # no half-written staging dirs left behind
    parent = os.path.dirname(path)
    assert not [d for d in os.listdir(parent) if d.endswith(".tmp")]
    return events, metrics, state


def test_flight_stall_trigger_dumps_once(tmp_path):
    """A blocked dispatch loop (busy engine, frozen progress) trips the
    watchdog exactly once and the dump is complete."""
    from mxnet_tpu.serving import Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine()
    # the watchdog sees this engine alone: one that an earlier test of
    # this worker left busy (a request in flight, never stepped again)
    # is a stalled engine too, and trips a second dump
    for name in list(flight._watches):
        if name != f"engine{eng._eid}":
            flight.unwatch(name)
    rec = flight.install(out_dir=str(tmp_path / "fd"),
                         stall_timeout=0.25, poll_interval=0.05)
    release = threading.Event()
    eng.dispatch_hook = lambda _eng: release.wait(20)
    try:
        eng.submit(Request([1, 2, 3], 3, request_id="stuck"))
        worker = threading.Thread(target=eng.step, daemon=True)
        worker.start()
        deadline = time.monotonic() + 10
        while not rec.dumps and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rec.dumps, "watchdog never fired on a stalled engine"
        time.sleep(0.5)                  # more watchdog ticks pass ...
        assert len(rec.dumps) == 1       # ... but the reason is latched
        events, metrics, state = _assert_complete_dump(rec.dumps[0])
        assert state["reason"] == f"stall:engine{eng._eid}"
        assert state["detail"]["stalled_for_s"] >= 0.25
        comp = state["components"][f"engine/{eng._eid}"]
        assert comp["scheduler"]["queued_ids"] == ["stuck"]
        assert any(e["kind"] == "request" and
                   e.get("request_id") == "stuck" for e in events)
        assert telemetry.get("flight_dumps_total").labels(
            state["reason"]).value == 1
    finally:
        release.set()
        worker.join(timeout=30)
        eng.dispatch_hook = None
        eng.serve()                      # drain the queued request
        flight.uninstall()


def test_flight_queue_full_storm_dumps_once(tmp_path):
    from mxnet_tpu.serving import QueueFullError, Request

    telemetry.request_log.clear()
    eng, cfg = _tiny_engine(max_queue=1)
    rec = flight.install(out_dir=str(tmp_path / "fd"),
                         queue_full_threshold=4, queue_full_window=30.0,
                         stall_timeout=1e9)
    try:
        eng.submit(Request([1, 2, 3], 2, request_id="seated"))
        for i in range(8):               # 8 rejections > threshold 4
            with pytest.raises(QueueFullError):
                eng.submit(Request([4, 5, 6], 2, request_id=f"r{i}"))
        assert len(rec.dumps) == 1       # latched after the storm trips
        events, metrics, state = _assert_complete_dump(rec.dumps[0])
        assert state["reason"] == f"queue_full:engine{eng._eid}"
        assert state["detail"]["rejections"] == 4
        assert [e for e in events if e["kind"] == "queue_full"]
        # the rejected traffic is visible in the dumped timelines too:
        # the dump freezes at the 4th rejection (the trigger point)
        rejected = [t for t in state["requests"]
                    if t["status"] == "rejected"]
        assert len(rejected) == 4
    finally:
        flight.uninstall()
        eng.serve()


def test_flight_trainer_nan_dumps_once(tmp_path):
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import Trainer, loss as gloss, nn

    rec = flight.install(out_dir=str(tmp_path / "fd"),
                         watch_trainer=True, stall_timeout=1e9)
    nonfinite_before = telemetry.get("trainer_nonfinite_steps_total").value
    try:
        net = nn.Dense(3, flatten=False, in_units=4)
        net.initialize(mx.init.Normal(0.1))
        trainer = Trainer(net.collect_params(), opt.SGD(learning_rate=0.1))
        lfn = gloss.L2Loss()
        y = mx.nd.array(np.zeros((2, 3), np.float32))

        def step(x):
            with mx.autograd.record():
                loss = lfn(net(x), y)
            loss.backward()
            trainer.step(batch_size=2)

        step(mx.nd.array(np.ones((2, 4), np.float32)))
        assert rec.dumps == []           # finite step: no dump
        bad = np.ones((2, 4), np.float32)
        bad[0, 0] = np.nan               # NaN loss -> NaN grads
        step(mx.nd.array(bad))
        assert len(rec.dumps) == 1
        events, metrics, state = _assert_complete_dump(rec.dumps[0])
        assert state["reason"] == "trainer_nonfinite"
        assert math.isnan(state["detail"]["grad_norm_sq"]) or \
            state["detail"]["grad_norm_sq"] in ("nan", "inf") or \
            not math.isfinite(float(state["detail"]["grad_norm_sq"]))
        step(mx.nd.array(bad))           # second NaN step: latched
        assert len(rec.dumps) == 1
        assert telemetry.get("trainer_nonfinite_steps_total").value \
            == nonfinite_before + 2      # counted even while latched
        rec.rearm("trainer_nonfinite")
        step(mx.nd.array(bad))
        assert len(rec.dumps) == 2       # re-armed: fires again
    finally:
        flight.uninstall()


def test_flight_sentinel_off_costs_nothing():
    """Without watch_trainer the sentinel never runs (no recorder, or
    recorder without the flag)."""
    assert flight.get() is None
    assert not flight.trainer_sentinel_enabled()
    assert flight.trigger("nothing_armed") is None   # safe no-op
    flight.note_queue_full("nobody")                 # safe no-op


# ---------------------------------------------------------------------------
# metrics catalog CI check
# ---------------------------------------------------------------------------

def test_metrics_catalog_is_complete():
    """tools/check_metrics_catalog.py walks the live registry and fails
    if any registered metric is missing from docs/OBSERVABILITY.md —
    run here so the catalog can never rot."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_metrics_catalog.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, \
        f"catalog check failed:\n{proc.stdout}\n{proc.stderr}"
    assert "OK:" in proc.stdout


# ---------------------------------------------------------------------------
# the documents name files that exist
# ---------------------------------------------------------------------------

# a `python[3] <path>.py` command, or a backticked path under one of the
# tree's directories (a trailing `:line` / `::name` is not part of it)
_DOC_PATH = re.compile(
    r"python3? ((?:[\w.-]+/)*[\w.-]+\.py)\b"
    r"|`((?:tools|docs|benchmarks|tests|mxnet_tpu)/[^`\s:]*)")


def test_documents_name_files_that_exist():
    """README.md, docs/*.md and the verify skill send a builder to
    commands and files; every one of them is in the tree."""
    docs = [os.path.join(REPO, "README.md"),
            os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    docs += sorted(glob.glob(os.path.join(REPO, "docs", "*.md")))
    missing = []
    for doc in docs:
        with open(doc) as f:
            text = f.read()
        for m in _DOC_PATH.finditer(text):
            path = m.group(1) or m.group(2)
            if not glob.glob(os.path.join(REPO, path)):
                line = text.count("\n", 0, m.start()) + 1
                missing.append(f"{os.path.relpath(doc, REPO)}:{line}: {path}")
    assert not missing, "documents name files that are gone:\n" + \
        "\n".join(missing)
