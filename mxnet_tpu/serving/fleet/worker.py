"""Fleet worker: one ServingEngine behind a control-plane HTTP server.

`FleetWorker` subclasses `ServingFrontend` — it keeps the whole data
plane (`POST /v1/generate` SSE streaming, /healthz, /readyz, /metrics,
the serving-loop thread that owns the engine) and adds the fleet
control plane on the SAME port:

    GET  /fleet/stats     role, wire version, engine stats (including
                          chunk_tokens and steady_state_compiles — the
                          router reads both)
    GET  /fleet/requests  this engine's recent request timelines (the
                          soak verifies stitched traces here)
    POST /fleet/prefill   submit, run prefill to the first token, then
                          export WITH the KV page payload -> wire blob
    POST /fleet/adopt     decode a wire blob, adopt it (payload
                          scatter or replay restart), stream the
                          continuation as SSE
    POST /fleet/export    drain-style export of everything in flight
                          as replay blobs (no payloads)
    POST /fleet/cancel    cancel by request id
    POST /fleet/drain     stop admitting (engine + frontend); in-flight
                          work keeps serving
    POST /fleet/undrain   reopen admission

Threading discipline is inherited: handler threads never touch the
engine. The one extension is a generic `("call", (fn, box))` command —
control RPCs (export, adopt, drain) run `fn(engine)` ON the serving
loop between step() calls, exactly where @loop_only methods are legal.

Run as a process: `python -m mxnet_tpu.serving.fleet.worker --spec
SPEC.json [--role prefill|decode|mixed] [--port N]`. The spec fully
determines the model (config + init seed), so every worker in a fleet
builds bit-identical weights without shipping checkpoints; the worker
warms the steady-state programs (including one export->adopt handoff
round-trip, so disaggregation costs zero steady-state compiles) and
then prints one `FLEET_WORKER_READY {json}` line for the supervisor.
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from urllib.parse import parse_qs, urlparse

from ...base import MXNetError
from ...runtime import enable_compile_cache
from ... import telemetry
from ..frontend import (ServingFrontend, TokenStream, _FrontendServer,
                        _Handler, _drain_rejection, _invalid_body,
                        _rejection_body, _DISCONNECT_ERRORS)
from ..scheduler import (Request, RejectedError, QueueFullError,
                         TERMINAL_STATUSES)
from . import wire

__all__ = ["FleetWorker", "build_engine", "warm_engine", "main"]

ROLES = ("prefill", "decode", "mixed")


class _CallBox:
    """Result slot for a generic serving-loop call."""
    __slots__ = ("outcome", "error", "result", "event")

    def __init__(self):
        self.outcome = None
        self.error = None
        self.result = None
        self.event = threading.Event()


class _WorkerHandler(_Handler):
    server_version = "mx-fleet-worker/1.0"

    @property
    def fw(self):
        return self.server.owner.frontend

    def do_GET(self):               # noqa: N802 (stdlib handler name)
        path = urlparse(self.path).path
        try:
            if path == "/fleet/stats":
                self._reply(self.fw.fleet_stats())
                return
            if path == "/fleet/requests":
                q = parse_qs(urlparse(self.path).query)
                try:
                    n = max(1, int(q["n"][0])) if "n" in q else 100
                except ValueError:
                    n = 100
                self._reply(self.fw.recent_requests(n))
                return
            if path == "/fleet/sloz":
                self._reply(self.fw.fleet_sloz())
                return
            if path == "/fleet/flightz":
                self._reply(self.fw.fleet_flightz())
                return
        except _DISCONNECT_ERRORS:
            return
        except Exception as e:      # noqa: BLE001 — must answer
            self._reply({"error": f"{type(e).__name__}: {e}"}, code=500)
            return
        super().do_GET()

    def do_POST(self):              # noqa: N802 (stdlib handler name)
        path = urlparse(self.path).path
        route = {
            "/fleet/prefill": self._fleet_prefill,
            "/fleet/adopt": self._fleet_adopt,
            "/fleet/export": self._fleet_export,
            "/fleet/cancel": self._fleet_cancel,
            "/fleet/drain": self._fleet_drain,
            "/fleet/undrain": self._fleet_undrain,
        }.get(path)
        if route is None:
            super().do_POST()
            return
        try:
            route()
        except _DISCONNECT_ERRORS:
            pass
        except Exception as e:      # noqa: BLE001 — must answer
            self._counted_reply(
                {"error": {"type": type(e).__name__,
                           "reason": "internal",
                           "message": str(e)}}, 500)

    # -- plumbing ----------------------------------------------------------
    def _read_body(self):
        return self.rfile.read(
            int(self.headers.get("Content-Length") or 0))

    def _read_json(self):
        body = json.loads(self._read_body() or b"{}")
        if not isinstance(body, dict):
            raise ValueError("request body must be a JSON object")
        return body

    # -- control plane -----------------------------------------------------
    def _fleet_cancel(self):
        try:
            body = self._read_json()
            rid = str(body["request_id"])
        except Exception as e:      # noqa: BLE001 — malformed request
            self._counted_reply(_invalid_body(e), 400)
            return
        self.fw.cancel(rid)
        self._reply({"ok": True, "request_id": rid})

    def _fleet_drain(self):
        self.fw.begin_drain()
        self.fw.call_on_loop(lambda eng: eng.drain())
        self._reply({"ok": True, "draining": True})

    def _fleet_undrain(self):
        self.fw.call_on_loop(lambda eng: eng.undrain())
        self.fw.end_drain()
        self._reply({"ok": True, "draining": False})

    def _fleet_export(self):
        blobs = self.fw.call_on_loop(
            lambda eng: [wire.encode_request(r)
                         for r in self.fw.close_streams(
                             eng.export_requests())])
        self._reply({"requests": blobs, "wire_version": wire.WIRE_VERSION})

    # -- disaggregation data plane -----------------------------------------
    def _fleet_prefill(self):
        """Admit, run prefill to the first emitted token, export the
        request WITH its KV payload, answer the wire blob. A request
        that goes terminal during prefill (1-token budget, instant
        EOS, deadline) comes back as a `final` blob — nothing left to
        hand off."""
        fw = self.fw
        try:
            body = self._read_json()
        except Exception as e:      # noqa: BLE001 — malformed request
            self._counted_reply(_invalid_body(e), 400)
            return
        if fw.draining:
            self._reject_reply(_drain_rejection(fw), 503)
            return
        try:
            req = fw._build_request(body)
        except (MXNetError, TypeError, ValueError, KeyError) as e:
            self._counted_reply(_invalid_body(e), 400)
            return
        tp = telemetry.parse_traceparent(self.headers.get("traceparent"))
        req.trace = {"trace_id": tp[0], "parent_span": tp[1]} \
            if tp is not None else {"trace_id": telemetry.new_trace_id()}
        outcome, err = fw._submit_via_loop(req)
        if outcome == "rejected":
            code = 429 if isinstance(err, QueueFullError) else 503
            self._reject_reply(_rejection_body(err), code)
            return
        if outcome == "invalid":
            self._counted_reply(_invalid_body(err), 400)
            return
        if outcome != "ok":
            self._counted_reply(
                {"error": {"type": "Internal", "reason": "internal",
                           "message": str(err)}}, 500)
            return
        deadline = time.monotonic() + fw.prefill_timeout_s
        while time.monotonic() < deadline:
            if req.output_tokens or req.status in TERMINAL_STATUSES:
                break
            time.sleep(0.002)
        exported = None
        if req.status not in TERMINAL_STATUSES:
            exported = fw.call_on_loop(
                lambda eng: eng.export_handoff(req.id))
        if exported is None:
            if req.status in TERMINAL_STATUSES:
                blob = wire.encode_request(req)
                blob["final"] = True
                fw._note_handoff(final=True)
                self._counted_reply(blob, 200)
                return
            # still mid-prefill at the timeout: give the slot back
            fw.cancel(req.id)
            self._counted_reply(
                {"error": {"type": "Timeout",
                           "reason": "prefill_timeout",
                           "message": "prefill did not reach its "
                                      "first token in "
                                      f"{fw.prefill_timeout_s}s"}}, 500)
            return
        if not fw.ship_payload:
            # replay fallback mode: the blob carries kv_history only,
            # the decode worker re-prefills (bit-identical, just
            # slower)
            exported.kv_payload = None
        blob = wire.encode_request(exported)
        blob["final"] = False
        fw._note_handoff(final=False)
        self._counted_reply(blob, 200)

    def _fleet_adopt(self):
        """Decode a wire blob, adopt it on the serving loop, and
        stream the continuation. Version mismatch -> 409 with the
        structured reason (never a guess-and-adopt)."""
        fw = self.fw
        try:
            blob = wire.loads(self._read_body())
        except wire.WireVersionError as e:
            fw._note_version_reject()
            self._counted_reply(
                {"error": {"type": "WireVersionError",
                           "reason": "wire_version_mismatch",
                           "message": str(e),
                           "got": e.got, "want": e.want}}, 409)
            return
        except MXNetError as e:
            self._counted_reply(_invalid_body(e), 400)
            return
        try:
            req = wire.decode_request(blob)
        except (MXNetError, KeyError, TypeError, ValueError) as e:
            self._counted_reply(_invalid_body(e), 400)
            return
        if fw.draining:
            self._reject_reply(_drain_rejection(fw), 503)
            return
        stream = TokenStream(
            capacity=max(fw.stream_buffer, req.max_new_tokens + 8))
        req.stream = stream
        base = len(req.output_tokens)
        try:
            fw.call_on_loop(
                lambda eng: eng.adopt(req, migrated_from="wire"))
        except RejectedError as e:
            code = 429 if isinstance(e, QueueFullError) else 503
            self._reject_reply(_rejection_body(e), code)
            return
        except MXNetError as e:
            self._counted_reply(_invalid_body(e), 400)
            return
        fw._register(req, stream)
        try:
            self._adopt_stream(fw, req, stream, base)
        finally:
            fw._unregister(req)

    def _adopt_stream(self, fw, req, stream, base):
        """SSE continuation of an adopted request. The `adopted` event
        acks the handoff (the router withholds client tokens until it
        lands, so client TTFT includes the handoff); `tokens` indices
        are LOCAL — index 0 is global token `base` — and the router
        re-bases them."""
        try:
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/event-stream; charset=utf-8")
            self.send_header("Cache-Control", "no-store")
            self.send_header("X-Request-Id", req.id)
            if req.trace:
                self.send_header(
                    "traceparent",
                    telemetry.format_traceparent(req.trace["trace_id"]))
            self.send_header("Connection", "close")
            self.end_headers()
            self._send_event("adopted", {
                "request_id": req.id, "base": base,
                "worker": fw.worker_id})
        except _DISCONNECT_ERRORS:
            fw._on_disconnect(req)
            return
        fw._code_inc(200)
        sent = 0
        while True:
            toks, closed = stream.take(timeout=fw.keepalive_s)
            try:
                if toks:
                    self._send_event("tokens",
                                     {"tokens": toks, "index": sent})
                    sent += len(toks)
                if closed is not None:
                    status = req.status \
                        if req.status in TERMINAL_STATUSES else closed
                    if stream.overflowed:
                        fw._note_overflow()
                        self._send_event("error", {
                            "error": "overflow", "sent": sent,
                            "message": "client fell behind on the "
                                       "adopted stream; request "
                                       "cancelled"})
                    else:
                        tail = [int(t) for t
                                in req.output_tokens[base + sent:]]
                        if tail:
                            self._send_event(
                                "tokens",
                                {"tokens": tail, "index": sent})
                            sent += len(tail)
                    self._send_event("done", {
                        "request_id": req.id, "status": status,
                        "emitted": len(req.output_tokens),
                        "sent": sent,
                        # the full stitched phase budget (handoff
                        # included) — the router reads the TTFT
                        # decomposition from here
                        "phases": {k: float(v) for k, v
                                   in (req.phases or {}).items()}})
                    return
                if not toks:
                    self.wfile.write(b": keepalive\n\n")
                    self.wfile.flush()
            except _DISCONNECT_ERRORS:
                fw._on_disconnect(req)
                return


class _WorkerServer(_FrontendServer):
    handler_class = _WorkerHandler
    name_prefix = "mx-fleet-worker-http"


class FleetWorker(ServingFrontend):
    """ServingFrontend + the fleet control plane (one port, one
    engine, one serving loop). `role` is a declaration the router
    honors — "prefill" workers take new prompts and export at first
    token, "decode" workers adopt and stream, "mixed" does both; the
    worker itself never refuses a data-plane call based on role, so a
    degraded fleet can still route around losses."""

    server_class = _WorkerServer

    def __init__(self, engine, role="mixed", worker_id=None,
                 ship_payload=True, prefill_timeout_s=120.0, **kw):
        if role not in ROLES:
            raise MXNetError(f"role must be one of {ROLES}, got {role!r}")
        self.role = role
        self.worker_id = str(worker_id) if worker_id is not None \
            else f"w{os.getpid()}"
        self.ship_payload = bool(ship_payload)
        self.prefill_timeout_s = float(prefill_timeout_s)
        self._fleet_lock = threading.Lock()
        self._handoffs = 0
        self._handoffs_final = 0
        self._version_rejects = 0
        self._steady_compiles = 0
        # count compiles flagged steady (post-mark_warm shape churn)
        # that belong to THIS worker's engine — the disaggregation
        # acceptance bar is steady_state_compiles == 0 per worker
        prefix = f"engine{engine._eid}/"

        def _on_compile(ev, _prefix=prefix):
            if ev.get("steady") and str(ev.get("program", "")).startswith(
                    _prefix):
                with self._fleet_lock:
                    self._steady_compiles += 1

        self._compile_hook = _on_compile
        telemetry.cost.add_compile_hook(_on_compile)
        super().__init__(engine, **kw)

    @property
    def engine(self):
        return self._backend

    # -- serving-loop extension: generic calls -----------------------------
    def _drain_cmds(self, fail=False):
        """Full override of ServingFrontend._drain_cmds (the base
        treats every non-"submit" kind as a cancel payload): adds the
        ("call", (fn, box)) command that control RPCs use to run
        @loop_only engine methods on the owning thread."""
        while True:
            try:
                kind, payload = self._cmd_q.get_nowait()
            except queue.Empty:
                return
            if kind == "submit":
                req, box = payload
                if fail:
                    box.outcome = "error"
                    box.error = MXNetError("worker closed")
                    box.event.set()
                    continue
                self._do_submit(req, box)
            elif kind == "call":
                fn, box = payload
                if fail:
                    box.outcome = "error"
                    box.error = MXNetError("worker closed")
                    box.event.set()
                    continue
                try:
                    box.result = fn(self._backend)
                    box.outcome = "ok"
                except Exception as e:  # noqa: BLE001 — surfaced to caller
                    box.outcome, box.error = "error", e
                box.event.set()
            else:
                self._do_cancel(payload)

    def call_on_loop(self, fn, timeout=None):
        """Run `fn(engine)` on the serving loop and return its result
        (exceptions re-raise here). The only legal path from a handler
        thread to a @loop_only engine method."""
        box = _CallBox()
        self._cmd_q.put(("call", (fn, box)))
        self._wake.set()
        if not box.event.wait(timeout or self.submit_timeout_s):
            raise MXNetError("serving-loop call timed out")
        if box.outcome != "ok":
            raise box.error
        return box.result

    def close(self):
        telemetry.cost.remove_compile_hook(self._compile_hook)
        super().close()

    # -- control-plane helpers (handler threads) ---------------------------
    def end_drain(self):
        """Reopen frontend admission after /fleet/drain (the engine
        side is undrained separately, on the loop)."""
        self._draining = False
        telemetry.flight.record("frontend_undrained",
                                frontend=self._fid)

    def close_streams(self, reqs, status="exported"):
        """Close any attached client streams on exported requests —
        over the wire the blob carries the tokens, and the stream's
        reader learns the request moved via its `done` event."""
        for r in reqs:
            st = getattr(r, "stream", None)
            if st is not None:
                st.close(status)
                r.stream = None
        return reqs

    def _note_handoff(self, final):
        with self._fleet_lock:
            self._handoffs += 1
            if final:
                self._handoffs_final += 1

    def _note_version_reject(self):
        with self._fleet_lock:
            self._version_rejects += 1

    def fleet_stats(self):
        eng = self._backend
        return {
            "worker_id": self.worker_id,
            "role": self.role,
            "pid": os.getpid(),
            "url": self.url,
            # THIS process's wall-anchored request-trace clock, sampled
            # at answer time — the fleet collector brackets the RPC
            # with its own clock and derives a per-worker offset, so
            # cross-process trace assembly can align every worker's
            # timeline onto the collector's axis
            "now": telemetry.now(),
            "wire_version": wire.WIRE_VERSION,
            "ship_payload": self.ship_payload,
            "draining": self.draining,
            "handoffs": self._handoffs,
            "handoffs_final": self._handoffs_final,
            "wire_version_rejects": self._version_rejects,
            "engine": {
                "chunk_tokens": eng.chunk_tokens,
                "prefill_chunk_budget": eng.prefill_chunk_budget,
                "page_size": eng.page_size,
                "max_length": eng.max_length,
                "num_slots": eng.num_slots,
                "kv_dtype": eng.kv_dtype,
                "weight_dtype": eng.weight_dtype,
            },
            "stats": dict(eng.stats,
                          steady_state_compiles=self._steady_compiles),
            "frontend": self.stats,
        }

    def recent_requests(self, n=100):
        """This engine's recent request timelines only — two in-process
        workers share the process-global request log, so the engine id
        scopes the answer."""
        eid = str(self._backend._eid)
        return [t for t in telemetry.request_log.recent(max(n * 4, 64))
                if str(t.get("engine")) == eid][-n:]

    def fleet_sloz(self):
        """GET /fleet/sloz — this process's SLO engine snapshot plus
        the clock stamp the collector's alignment needs."""
        return {"worker_id": self.worker_id, "now": telemetry.now(),
                "slo": telemetry.slo.snapshot()}

    def fleet_flightz(self):
        """GET /fleet/flightz — this process's flight-recorder state:
        latched reasons (the collector mirrors any NEW latch into a
        correlated fleet dump), completed dump paths, and a bounded
        tail of the breadcrumb ring."""
        rec = telemetry.flight.get()
        out = {"worker_id": self.worker_id, "now": telemetry.now(),
               "armed": rec is not None,
               "latched": telemetry.flight.latched_reasons()}
        if rec is not None:
            out["dumps"] = [str(p) for p in rec.dumps]
            out["events_tail"] = rec.events()[-64:]
        return out


# -- spec-driven process entry ---------------------------------------------

def build_engine(spec):
    """Build (model, config, engine) from a JSON-safe spec:
    {"config": GPT2Config kwargs, "seed": int, "init_std": float,
    "engine": ServingEngine kwargs}. The seed pins initialization, so
    every process given the same spec holds bit-identical weights —
    the fleet's substitute for shipping checkpoints."""
    import mxnet_tpu as mx
    from ...models import GPT2Config, GPT2ForCausalLM
    from ..engine import ServingEngine

    cfg = GPT2Config(**spec.get("config", {}))
    mx.rng.seed(int(spec.get("seed", 3)))
    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(float(spec.get("init_std", 0.05))))
    eng = ServingEngine(net, **spec.get("engine", {}))
    return net, cfg, eng


def warm_engine(eng, cfg, spec=None):
    """Compile the full steady-state program set BEFORE declaring
    ready: greedy + sampled serving across EVERY prefill bucket a
    prompt (or a migrated re-prefill of prompt + emitted tokens) can
    land in, and one export_handoff -> adopt round-trip so the tier
    gather/scatter (and int8 zero-scale) programs are warm — a
    disaggregated fleet must run with steady_state_compiles == 0,
    handoffs included. Ends with mark_warm() + reset_stats()."""
    import numpy as np
    spec = spec or {}
    rng = np.random.default_rng(int(spec.get("warmup_seed", 17)))
    vocab = int(cfg.vocab_size)
    mk = lambda n, i, samp: Request(    # noqa: E731 — local shorthand
        rng.integers(0, vocab, n).tolist(), 4, seed=9900 + i,
        do_sample=samp, request_id=f"_warm{i}")
    page = int(eng.page_size)
    lens = [4, 5] + list(range(page, int(eng.max_length), page))
    # two passes, one per program variant: the engine picks greedy-only
    # vs mixed-sampling by whether ANY active slot samples, so a serve()
    # that interleaves both leaves whichever variant the scheduler never
    # isolated uncompiled — an all-greedy pass then an all-sampled pass
    # pins both, across every bucket
    i = 0
    for samp in (False, True):
        eng.serve([mk(n, (i := i + 1), samp) for n in lens])
    # the round-trip prompt spans two KV pages so multi-page handoffs
    # are compiled too
    r = mk(page + 3, i, True)
    eng.submit(r)
    for _ in range(64):
        eng.step()
        if r.output_tokens or r.status in TERMINAL_STATUSES:
            break
    e = eng.export_handoff(r.id)
    if e is not None:
        eng.adopt(e, migrated_from="warmup")
    while eng.has_work:
        eng.step()
    eng.mark_warm()
    eng.reset_stats()


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="run one fleet worker process")
    ap.add_argument("--spec", required=True,
                    help="model+engine spec: a JSON file path or an "
                         "inline JSON object")
    ap.add_argument("--role", default=None, choices=ROLES)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--worker-id", default=None)
    ap.add_argument("--no-ship-payload", action="store_true",
                    help="handoff blobs carry kv_history only (replay "
                         "restart on the decode side) — the ablation "
                         "arm")
    ap.add_argument("--no-warmup", action="store_true")
    args = ap.parse_args(argv)
    raw = args.spec
    if os.path.exists(raw):
        with open(raw, "r", encoding="utf-8") as f:
            raw = f.read()
    spec = json.loads(raw)
    enable_compile_cache()
    _net, cfg, eng = build_engine(spec)
    if not args.no_warmup:
        warm_engine(eng, cfg, spec)
    fw = FleetWorker(
        eng, role=args.role or spec.get("role", "mixed"),
        worker_id=args.worker_id, port=args.port, host=args.host,
        ship_payload=not args.no_ship_payload,
        **spec.get("frontend", {}))
    print("FLEET_WORKER_READY " + json.dumps(
        {"url": fw.url, "pid": os.getpid(), "role": fw.role,
         "worker_id": fw.worker_id}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        fw.close()


if __name__ == "__main__":
    main()
