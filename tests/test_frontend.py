"""HTTP front-end tests (tier-1, ISSUE 12).

Covers: SSE streaming bit-identical to an offline engine run,
non-stream JSON bodies, invalid-request 400s, queue-full -> 429 and
draining -> 503 with Retry-After + the full structured rejection
body, frontend drain flipping /readyz and admission, client
disconnects cancelling (slots/pages released, counters reconciled),
seeded disconnect churn, the bounded-stream slow-client overflow
cancel, idempotent double-cancel through engine and router, a replica
kill mid-stream surviving bit-identically through export/adopt
migration, and the deterministic context-manager lifecycle of both
HTTP servers. The full open-loop chaos soak (tools/http_soak.py) runs
under @pytest.mark.slow, outside tier-1.
"""
import http.client
import json
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import (ReplicaFaultPlan, Request, ServingEngine,
                               ServingFrontend, ServingRouter,
                               TokenStream)

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
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(_tiny(), **kw)


def _frontend(backend, **kw):
    kw.setdefault("keepalive_s", 0.05)
    kw.setdefault("step_idle_s", 0.005)
    return ServingFrontend(backend, **kw)


def _post(fe, body, timeout=120):
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read().decode()
    finally:
        conn.close()


def _get(fe, path, timeout=30):
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read().decode()
    finally:
        conn.close()


def _sse(text):
    """[(event, payload)] from a close-delimited SSE body; keepalive
    comments are dropped."""
    out = []
    for block in text.split("\n\n"):
        block = block.strip()
        if not block or block.startswith(":"):
            continue
        ev, payload = None, None
        for line in block.splitlines():
            if line.startswith("event: "):
                ev = line[len("event: "):]
            elif line.startswith("data: "):
                payload = json.loads(line[len("data: "):])
        if ev is not None:
            out.append((ev, payload))
    return out


def _tokens(events):
    toks = []
    for ev, p in events:
        if ev == "tokens":
            assert p["index"] == len(toks)   # contiguous, in order
            toks.extend(p["tokens"])
    return toks


def _done(events):
    dones = [p for ev, p in events if ev == "done"]
    assert len(dones) == 1, f"expected exactly one done event: {events}"
    return dones[0]


def _reqs(n, max_new=6, prompt_seed=7, seed_base=100):
    rng = np.random.default_rng(prompt_seed)
    out = []
    for i in range(n):
        prompt = rng.integers(1, 97, size=int(rng.integers(3, 9)))
        out.append(Request(prompt, max_new, request_id=f"r{i}",
                           do_sample=True, temperature=0.9,
                           seed=seed_base + i))
    return out


def _raw_stream_socket(fe, body_dict, timeout=120):
    """Open a raw socket POST so the test can hang up mid-stream."""
    body = json.dumps(body_dict).encode()
    sock = socket.create_connection((fe.host, fe.port), timeout=timeout)
    sock.sendall(b"POST /v1/generate HTTP/1.0\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: " + str(len(body)).encode()
                 + b"\r\n\r\n" + body)
    return sock


def _quiesce(fe, backend, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if (not backend.has_work and fe.stats["active_streams"] == 0
                and fe._cmd_q.empty()):
            return
        time.sleep(0.02)
    raise AssertionError("frontend did not quiesce: "
                         f"{fe.stats}, has_work={backend.has_work}")


# ---------------------------------------------------------------------------
# streaming correctness
# ---------------------------------------------------------------------------

def test_stream_roundtrip_matches_offline():
    """SSE-streamed sampled outputs are bit-identical to the same
    requests served by a plain in-process engine."""
    ref = _engine()
    want = {r.id: list(r.output_tokens) for r in ref.serve(_reqs(3))
            if r.status == "finished"}
    assert len(want) == 3
    eng = _engine()
    with _frontend(eng) as fe:
        for r in _reqs(3):
            status, hdrs, body = _post(fe, {
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": r.max_new_tokens,
                "request_id": r.id, "do_sample": True,
                "temperature": 0.9, "seed": r.seed})
            assert status == 200
            assert hdrs["X-Request-Id"] == r.id
            evs = _sse(body)
            assert _done(evs)["status"] == "finished"
            assert _tokens(evs) == want[r.id]
        assert fe.stats["requests_by_code"]["200"] == 3
    assert eng.audit_pages() == [] and eng.audit_adapters() == []
    assert eng.scheduler.num_active == 0


def test_nonstream_json_body_and_usage():
    eng = _engine()
    with _frontend(eng) as fe:
        status, hdrs, body = _post(fe, {"prompt": [5, 6, 7],
                                        "max_new_tokens": 4,
                                        "stream": False})
        assert status == 200
        out = json.loads(body)
        assert out["status"] == "finished"
        assert out["request_id"] == hdrs["X-Request-Id"]
        assert len(out["output_tokens"]) == 4
        assert out["usage"] == {"prompt_tokens": 3,
                                "completion_tokens": 4}


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_compile_failure_stops_the_loop_and_fails_streams_loudly():
    """A unified program that cannot compile (the Mosaic kernel on a CPU)
    fails every step the same way: the serving loop must stop and say so
    — open streams close as failed, later requests get a 500 — instead
    of swallowing the error forever while clients wait."""
    with _frontend(_engine(attn_impl="pallas")) as fe:
        status, _, text = _post(fe, {"prompt": [1, 2, 3],
                                     "max_new_tokens": 4})
        assert status == 200                     # headers went out first
        assert _done(_sse(text))["status"] == "failed"
        assert _tokens(_sse(text)) == []
        fe._loop_thread.join(timeout=10)
        assert not fe._loop_thread.is_alive()
        status, _, text = _post(fe, {"prompt": [1, 2, 3],
                                     "max_new_tokens": 4})
        assert status == 500 and "failed to lower/compile" in text


def test_invalid_requests_answer_400():
    eng = _engine()
    with _frontend(eng) as fe:
        for body in ({}, {"prompt": []}, {"prompt": "abc"},
                     {"prompt": [1, 2], "max_new_tokens": "lots"}):
            status, _, data = _post(fe, body)
            assert status == 400
            assert json.loads(data)["error"]["reason"] \
                == "invalid_request"
        # engine-side validation rejections are the client's fault too
        status, _, data = _post(fe, {"prompt": list(range(1, 41)),
                                     "max_new_tokens": 2})
        assert status == 400          # prompt exceeds slot capacity 32
        status, _, data = _post(fe, {"prompt": [1, 2], "adapter_id": 9})
        assert status == 400          # unknown adapter
        # a non-JSON body
        conn = http.client.HTTPConnection(fe.host, fe.port, timeout=30)
        conn.request("POST", "/v1/generate", "not json at all")
        assert conn.getresponse().status == 400
        conn.close()
        assert fe.stats["requests_by_code"]["400"] == 7


# ---------------------------------------------------------------------------
# backpressure -> HTTP status codes
# ---------------------------------------------------------------------------

def test_queue_full_maps_to_429_with_retry_after():
    eng = _engine(num_slots=1, max_queue=1)
    with _frontend(eng) as fe:
        held = []

        def hold(rid):
            held.append(_post(fe, {"prompt": [3, 4, 5],
                                   "max_new_tokens": 24,
                                   "request_id": rid}))

        t1 = threading.Thread(target=hold, args=("a",))
        t1.start()
        deadline = time.time() + 120
        while eng.scheduler.num_active < 1 and time.time() < deadline:
            time.sleep(0.01)
        t2 = threading.Thread(target=hold, args=("b",))
        t2.start()
        while eng.scheduler.num_queued < 1 and time.time() < deadline:
            time.sleep(0.01)
        status, hdrs, data = _post(fe, {"prompt": [3, 4, 5],
                                        "max_new_tokens": 2,
                                        "request_id": "c"})
        t1.join(timeout=120)
        t2.join(timeout=120)
        assert status == 429
        assert int(hdrs["Retry-After"]) >= 1
        err = json.loads(data)["error"]
        assert err["type"] == "QueueFullError"
        assert err["reason"] == "queue_full"
        assert err["queue_depth"] == 1 and err["active_slots"] == 1
        assert [s for s, _, _ in held] == [200, 200]
    assert eng.audit_pages() == []


def test_draining_engine_maps_to_503():
    eng = _engine()
    with _frontend(eng) as fe:
        eng.drain()
        status, hdrs, data = _post(fe, {"prompt": [1, 2],
                                        "max_new_tokens": 2})
        assert status == 503
        assert "Retry-After" in hdrs
        err = json.loads(data)["error"]
        assert err["type"] == "ShedError"
        assert err["reason"] == "draining"
        eng.undrain()


def test_frontend_drain_flips_readyz_and_sheds_new_requests():
    eng = _engine()
    fe = _frontend(eng)
    try:
        name = fe._probe_name
        status, _, _ = _get(fe, f"/readyz?component={name}")
        assert status == 200
        fe.begin_drain()
        status, _, data = _get(fe, f"/readyz?component={name}")
        assert status == 503
        assert json.loads(data)["ready"] is False
        status, hdrs, data = _post(fe, {"prompt": [1],
                                        "max_new_tokens": 2})
        assert status == 503
        assert "Retry-After" in hdrs
        assert json.loads(data)["error"]["reason"] == "draining"
    finally:
        fe.close()


# ---------------------------------------------------------------------------
# disconnects cancel; churn reconciles
# ---------------------------------------------------------------------------

def test_disconnect_mid_stream_cancels_and_releases():
    eng = _engine(num_slots=1)
    with _frontend(eng) as fe:
        sock = _raw_stream_socket(fe, {"prompt": [9, 8, 7],
                                       "max_new_tokens": 28,
                                       "request_id": "gone"})
        buf = b""
        while b"event: tokens" not in buf:
            chunk = sock.recv(4096)
            assert chunk, "server closed before the first token"
            buf += chunk
        sock.close()                 # hang up mid-decode
        deadline = time.time() + 60
        while time.time() < deadline:
            if (eng.stats["requests_cancelled"] == 1
                    and eng.scheduler.num_active == 0
                    and fe.stats["active_streams"] == 0):
                break
            time.sleep(0.02)
        s = fe.stats
        assert eng.stats["requests_cancelled"] == 1
        assert s["disconnects"] == 1
        assert s["cancels_issued"] == 1 and s["cancels_noop"] == 0
        assert eng.scheduler.num_active == 0
        assert eng.scheduler.num_queued == 0
    assert eng.audit_pages() == [] and eng.audit_adapters() == []


def test_disconnect_churn_reconciles():
    """Threaded clients hanging up at seeded random points — during
    queue wait, mid-prefill, mid-decode, after eos — leave no leaked
    slot/page/adapter state, and serving_cancelled reconciles with
    http_disconnects (every disconnect issues exactly one idempotent
    cancel)."""
    eng = _engine(num_slots=2, max_queue=16)
    with _frontend(eng, stream_buffer=512) as fe:
        n = 10
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 97,
                                size=int(rng.integers(3, 8))).tolist()
                   for _ in range(n)]
        # bytes of response to read before hanging up; None = read all.
        # 0 hangs up during queue wait / prefill; small cutoffs land
        # mid-decode; large ones race natural finish.
        cutoffs = [None if i % 3 == 0 else int(rng.integers(0, 500))
                   for i in range(n)]
        results = {}

        def client(i):
            sock = _raw_stream_socket(
                fe, {"prompt": prompts[i], "max_new_tokens": 8,
                     "request_id": f"churn-{i}"})
            got, cut = b"", cutoffs[i]
            while cut is None or len(got) < cut:
                chunk = sock.recv(4096)
                if not chunk:
                    break
                got += chunk
            sock.close()
            results[i] = got

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        _quiesce(fe, eng)
        st, es = fe.stats, eng.stats
        # every request reached exactly one terminal state
        assert es["requests_finished"] + es["requests_cancelled"] == n
        # disconnect accounting: every detected disconnect issued one
        # cancel; those that found live work match the engine's count,
        # the rest were idempotent no-ops (natural-finish race)
        assert st["cancels_issued"] + st["cancels_noop"] \
            == st["disconnects"]
        assert es["requests_cancelled"] == st["cancels_issued"]
        # clients that read to the end saw a complete stream
        for i in range(n):
            if cutoffs[i] is None:
                text = results[i].decode(errors="replace")
                evs = _sse(text.split("\r\n\r\n", 1)[1])
                assert _done(evs)["status"] == "finished"
        assert eng.scheduler.num_active == 0
        assert eng.scheduler.num_queued == 0
    assert eng.audit_pages() == [] and eng.audit_adapters() == []


# ---------------------------------------------------------------------------
# slow-client overflow policy
# ---------------------------------------------------------------------------

def test_stream_overflow_cancels_request():
    """A subscriber whose bounded buffer fills is a slow client: the
    engine cancels the request (terminal cancelled/stream_overflow)
    instead of buffering unboundedly, and releases everything."""
    eng = _engine(num_slots=1)
    req = Request([1, 2, 3], 12, request_id="slowpoke")
    st = TokenStream(capacity=1)    # nobody ever take()s
    req.stream = st
    eng.submit(req)
    steps = 0
    while eng.has_work and steps < 200:
        eng.step()
        steps += 1
    assert req.status == "cancelled"
    assert st.overflowed is True
    assert st.closed == "cancelled"
    assert len(req.output_tokens) >= 1   # tokens before the overflow
    assert eng.stats["requests_cancelled"] == 1
    assert eng.scheduler.num_active == 0
    assert eng.audit_pages() == []


def test_slow_reader_overflow_error_event_over_http(monkeypatch):
    """A reader that lags the engine backs the bounded buffer up: the
    engine overflow-cancels and the client gets the structured `error`
    event then `done` cancelled over the live HTTP stream. The lag is
    injected at the exact production seam (the handler's take() loop
    — what a blocked socket write does to it); the client also
    advertises a 1-token flow-control window, so two tokens landing
    inside one lag window are already too many."""
    from mxnet_tpu.serving import frontend as fr
    orig = fr.TokenStream.take

    def laggy_take(self, timeout=None):
        time.sleep(0.3)
        return orig(self, timeout)

    eng = _engine(num_slots=1)
    with _frontend(eng) as fe:
        monkeypatch.setattr(fr.TokenStream, "take", laggy_take)
        status, _, body = _post(fe, {"prompt": [7, 8, 9],
                                     "max_new_tokens": 16,
                                     "stream_buffer": 1,
                                     "request_id": "laggard"})
        assert status == 200
        evs = _sse(body)
        errs = [p for ev, p in evs if ev == "error"]
        assert len(errs) == 1 and errs[0]["error"] == "overflow"
        assert _done(evs)["status"] == "cancelled"
        assert fe.stats["stream_overflows"] == 1
        assert eng.stats["requests_cancelled"] == 1
        monkeypatch.setattr(fr.TokenStream, "take", orig)
        # a malformed flow-control window is the client's fault
        status, _, data = _post(fe, {"prompt": [1, 2],
                                     "max_new_tokens": 2,
                                     "stream_buffer": "wide"})
        assert status == 400
    assert eng.scheduler.num_active == 0
    assert eng.audit_pages() == []


# ---------------------------------------------------------------------------
# idempotent cancellation
# ---------------------------------------------------------------------------

def test_double_cancel_via_router_is_idempotent():
    engines = [_engine() for _ in range(2)]
    router = ServingRouter(engines)
    req = Request([5, 5, 5], 6, request_id="dc")
    router.submit(req)
    assert router.cancel("dc") is req
    assert req.status == "cancelled"
    assert router.cancel("dc") is None       # owner map already clear
    assert all(e.cancel("dc") is False for e in engines)
    assert sum(e.stats["requests_cancelled"] for e in engines) == 1


# ---------------------------------------------------------------------------
# fleet integration: replica kill mid-stream
# ---------------------------------------------------------------------------

def test_replica_kill_mid_stream_survives_bit_identical():
    """Killing the replica that owns an in-flight streamed request
    migrates it (export/adopt) with the TokenStream attached — the
    client's stream runs to completion and the token sequence matches
    an unfaulted offline run exactly."""
    prompt = [11, 23, 42, 7, 56]
    ref = Request(prompt, 12, request_id="k0", do_sample=True,
                  temperature=0.9, seed=11)
    _engine(num_slots=2).serve([ref])
    want = list(ref.output_tokens)
    assert ref.status == "finished" and len(want) == 12

    engines = [_engine(num_slots=2) for _ in range(2)]
    router = ServingRouter(engines, hedge_after_s=1e9)
    plan = None
    with _frontend(router) as fe:
        out = {}

        def go():
            out["res"] = _post(fe, {"prompt": prompt,
                                    "max_new_tokens": 12,
                                    "request_id": "k0",
                                    "do_sample": True,
                                    "temperature": 0.9, "seed": 11},
                               timeout=300)

        t = threading.Thread(target=go)
        t.start()
        deadline = time.time() + 120
        owner = None
        while owner is None and time.time() < deadline:
            o = router._owner.get("k0")
            if o is not None and len(o[1].output_tokens) >= 3:
                owner = o[0]        # mid-decode on this replica
            time.sleep(0.005)
        assert owner is not None, "request never started decoding"
        plan = ReplicaFaultPlan(kill={1: owner}).install(router)
        t.join(timeout=300)
        plan.uninstall()
        assert plan.counts["kill"] == 1
        status, _, body = out["res"]
        assert status == 200
        evs = _sse(body)
        assert _done(evs)["status"] == "finished"
        assert _tokens(evs) == want
    for e in engines:
        assert e.audit_pages() == [] and e.audit_adapters() == []


# ---------------------------------------------------------------------------
# lifecycle: deterministic close, context managers, port release
# ---------------------------------------------------------------------------

def _assert_port_free(host, port):
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.bind((host, port))
    finally:
        s.close()


def test_lifecycle_context_managers_release_ports():
    eng = _engine()
    with ServingFrontend(eng) as fe:
        host, port = fe.host, fe.port
        assert _get(fe, "/healthz")[0] == 200
    _assert_port_free(host, port)
    fe.close()                       # idempotent
    assert not fe._loop_thread.is_alive()

    with telemetry.IntrospectionServer(0) as srv:
        tport = srv.port
    _assert_port_free(srv.host, tport)
    srv.close()                      # idempotent
    srv.stop()                       # alias stays supported


def test_shutdown_drains_open_streams_then_closes():
    eng = _engine(num_slots=1)
    fe = _frontend(eng)
    res = {}

    def go():
        res["r"] = _post(fe, {"prompt": [4, 5, 6],
                              "max_new_tokens": 10,
                              "request_id": "drainme"}, timeout=300)

    t = threading.Thread(target=go)
    t.start()
    deadline = time.time() + 120
    while eng.scheduler.num_active < 1 and time.time() < deadline:
        time.sleep(0.01)
    fe.shutdown(timeout=120)         # graceful: stream finishes first
    t.join(timeout=120)
    status, _, body = res["r"]
    assert status == 200
    evs = _sse(body)
    assert _done(evs)["status"] == "finished"
    assert len(_tokens(evs)) == 10
    assert not fe._loop_thread.is_alive()
    _assert_port_free(fe.host, fe.port)
    assert eng.audit_pages() == []


# ---------------------------------------------------------------------------
# the full chaos soak (out of tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_http_soak_end_to_end():
    import tools.http_soak as soak
    rc = soak.main(["--requests", "24", "--seed", "7",
                    "--kill-after", "4"])
    assert rc == 0
