#!/usr/bin/env python
"""Open-loop HTTP chaos soak for the serving frontend
(docs/SERVING.md "HTTP front-end").

A seeded Poisson stream of real-socket HTTP clients — well-behaved
readers, mid-stream hangups, and slow readers that stall mid-stream —
hits a ServingFrontend fronting a multi-replica ServingRouter while a
ReplicaFaultPlan kills one replica mid-run. Open-loop means arrivals
do NOT wait for completions, so backpressure is real: the admission
queue fills and the 429/503 mapping gets exercised alongside the
chaos.

Pass criteria (exit 0 only if ALL hold):
  * every admitted request reached exactly one terminal state — the
    engines' finished+cancelled+failed counters reconcile with the
    number of non-rejected submissions, and nothing is left queued,
    active, or registered anywhere (zero lost requests);
  * zero leaked resources: page audits, adapter audits, slot maps,
    router owner map, and the frontend's live-stream table all clean;
  * every fully-read greedy stream is bit-identical to the same
    request served by an offline single engine; partially-read
    streams (hangups, overflow) received a prefix of that reference;
  * every 429/503 rejection carried a Retry-After header and the full
    structured JSON body (type/reason/retry_after_s);
  * disconnect accounting reconciles (cancels_issued + cancels_noop
    == disconnects observed), and any overflow the frontend counted
    reached its client as a structured `error` event;
  * the scheduled replica kill fired and the fleet kept serving;
  * trace + span accounting: every 200 response echoes the client's
    W3C traceparent trace id and the engine timelines adopted it
    (including across a kill-migration); every first-token timeline's
    phase budget (queue_wait/prefix_match/host_pagein/prefill_chunks/
    first_decode) never exceeds the engine TTFT, sums to it within
    5 ms for undisturbed requests, and the client-observed TTFB is
    never below the engine TTFT for fully-read streams;
  * steady_state_compiles == 0 on every replica after warmup — the
    chaos (kills, migrations, cancels, overflows) must not retrace;
  * graceful drain works: after begin_drain() a probe request gets
    503 reason="draining" with Retry-After, then shutdown() drains
    and releases the port.

`--fleet` runs the same bar across REAL worker subprocesses
(docs/SERVING.md "Cross-process fleet & disaggregated prefill/decode"):
the backend becomes a FleetRouter over `spawn_fleet` workers, the same
ServingFrontend serves the ingress port, and the replica kill becomes
a seeded SIGKILL of one worker process mid-decode. The bar does not
soften — zero lost requests, bit-identical full reads against the
offline reference (the failover replays across the process boundary),
structured 429/503, and steady_state_compiles == 0 on every surviving
worker (read over its own /fleet/stats).

Usage:
    JAX_PLATFORMS=cpu python tools/http_soak.py
    python tools/http_soak.py --requests 96 --seed 3 --kill-after 8
    python tools/http_soak.py --replicas 3 --rate 40 --kill-after 0
    python tools/http_soak.py --hbm-budget-bytes 163840 \
        --host-budget-bytes 4194304   # tiered KV: spill + page-in
    python tools/http_soak.py --fleet                # real subprocesses
    python tools/http_soak.py --fleet --kv-dtype int8
"""
import argparse
import http.client
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _compiles(eid):
    """Total compiles attributed to one engine's programs."""
    from mxnet_tpu import telemetry
    rep = telemetry.cost.report()["programs"]
    return sum(s["compiles"] for p, s in rep.items()
               if p.startswith(f"engine{eid}/"))


def _sse_events(text):
    """[(event, payload)] from a close-delimited SSE body."""
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
                try:
                    payload = json.loads(line[len("data: "):])
                except ValueError:
                    payload = None
        if ev is not None:
            out.append((ev, payload))
    return out


def _sse_tokens(events):
    toks = []
    for ev, p in events:
        if ev == "tokens" and p:
            toks.extend(p["tokens"])
    return toks


class _Client:
    """One soak client: POSTs over a raw socket and reads according
    to its seeded behavior. Records everything for the verdict."""

    def __init__(self, idx, behavior, body, cutoff=None, stall_s=0.0,
                 traceparent=None):
        self.idx = idx
        self.behavior = behavior      # "read" | "hangup" | "slow"
        self.body = body
        self.cutoff = cutoff          # hangup: bytes to read first
        self.stall_s = stall_s        # slow: stall after first tokens
        self.traceparent = traceparent
        self.status = None
        self.headers = {}
        self.raw = b""
        self.error = None
        self.t_sent = None            # request bytes on the wire
        self.t_first = None           # first token event bytes seen

    def run(self, host, port):
        try:
            payload = json.dumps(self.body).encode()
            head = (b"POST /v1/generate HTTP/1.0\r\n"
                    b"Content-Type: application/json\r\n")
            if self.traceparent:
                head += (b"traceparent: "
                         + self.traceparent.encode() + b"\r\n")
            sock = socket.create_connection((host, port), timeout=300)
            try:
                sock.sendall(
                    head + b"Content-Length: "
                    + str(len(payload)).encode()
                    + b"\r\n\r\n" + payload)
                self.t_sent = time.perf_counter()
                stalled = False
                while True:
                    if self.behavior == "hangup" \
                            and len(self.raw) >= self.cutoff:
                        break         # hang up mid-stream, no goodbye
                    chunk = sock.recv(4096)
                    if not chunk:
                        break
                    self.raw += chunk
                    if self.t_first is None \
                            and b"event: tokens" in self.raw:
                        self.t_first = time.perf_counter()
                    if (self.behavior == "slow" and not stalled
                            and b"event: tokens" in self.raw):
                        # fall behind for real: the server keeps
                        # generating into the bounded buffer and must
                        # overflow-cancel rather than grow it
                        stalled = True
                        time.sleep(self.stall_s)
            finally:
                sock.close()
        except Exception as e:        # noqa: BLE001 — verdict data
            self.error = f"{type(e).__name__}: {e}"
            return
        head, _, rest = self.raw.partition(b"\r\n\r\n")
        lines = head.decode(errors="replace").splitlines()
        if lines and lines[0].startswith("HTTP/"):
            try:
                self.status = int(lines[0].split()[1])
            except (IndexError, ValueError):
                pass
            for ln in lines[1:]:
                k, _, v = ln.partition(":")
                self.headers[k.strip().lower()] = v.strip()
        self.raw = rest


def _main_fleet(args):
    """The --fleet soak: same seeded clients, same verdicts, but the
    backend is a fleet of REAL worker subprocesses and the chaos is a
    SIGKILL delivered to one of them mid-decode. Everything the
    verdict needs from a worker crosses its own HTTP surface
    (/fleet/stats) — this process never touches a worker's engine."""
    os.environ.setdefault("MX_ASSERT_OWNERSHIP", "1")
    from mxnet_tpu.analysis import set_assert_ownership
    set_assert_ownership(
        os.environ["MX_ASSERT_OWNERSHIP"] in ("1", "true", "yes"))

    import numpy as np

    from mxnet_tpu.serving import Request, ServingFrontend
    from mxnet_tpu.serving.fleet import (FleetRouter, WorkerClient,
                                         spawn_fleet)
    from mxnet_tpu.serving.fleet.worker import build_engine

    max_len, page, slots = 64, 8, 2
    kv = None if args.kv_dtype == "float32" else args.kv_dtype
    # ONE spec builds the workers AND the offline reference: the init
    # seed pins the weights, so bit-identity across the process
    # boundary is meaningful. int8 gets the same non-binding prefill
    # budget the in-process soak uses — the chunk grid is part of the
    # numerics (docs/SERVING.md "Quantized KV pages")
    spec = {
        "config": dict(vocab_size=97, units=32, num_layers=2,
                       num_heads=2, max_length=max_len, dropout=0.0,
                       attention_dropout=0.0),
        "seed": 3, "init_std": 0.05,
        "engine": dict(num_slots=slots, max_length=max_len,
                       page_size=page, attn_impl="xla",
                       max_queue=4, kv_dtype=kv,
                       prefill_chunk_budget=slots * page if kv
                       else None),
    }
    rng = np.random.default_rng(args.seed)
    behaviors = []
    for i in range(args.requests):
        u = rng.random()
        behaviors.append("read" if u < 0.5
                         else "hangup" if u < 0.8 else "slow")
    bodies, prompts = [], []
    for i in range(args.requests):
        prompt = rng.integers(1, spec["config"]["vocab_size"],
                              int(rng.integers(3, 13))).tolist()
        prompts.append(prompt)
        body = {"prompt": prompt,
                "max_new_tokens": int(rng.integers(6, 17)),
                "request_id": f"soak-{i}"}
        if behaviors[i] == "slow":
            body["stream_buffer"] = 2
        bodies.append(body)
    victim_idx = int(rng.integers(0, args.replicas))

    # offline reference: the same spec served by one local fault-free
    # engine — the bar every fleet stream is judged against. Admission
    # control stays on the workers; the reference queues everything.
    _net, _cfg, ref_eng = build_engine(
        dict(spec, engine=dict(spec["engine"], max_queue=None)))
    ref_reqs = [Request(p, b["max_new_tokens"], request_id=b["request_id"])
                for p, b in zip(prompts, bodies)]
    ref_eng.serve(ref_reqs)
    reference = {r.id: [int(t) for t in r.output_tokens]
                 for r in ref_reqs}
    assert all(r.status == "finished" for r in ref_reqs)

    if args.disagg:
        # disaggregated lane: one prefill worker, the rest decode —
        # every admitted request crosses a handoff, which is what the
        # stitched-trace verdict needs. The seeded SIGKILL is off here
        # (killing the only prefill worker leaves nothing to fail over
        # to); the mixed lane keeps owning the chaos story.
        if args.replicas < 2:
            raise SystemExit("--disagg needs --replicas >= 2")
        if args.kill_after > 0:
            print("# --disagg: disabling the seeded SIGKILL "
                  "(single prefill worker)", file=sys.stderr)
            args.kill_after = 0
        roles = ("prefill",) + ("decode",) * (args.replicas - 1)
    else:
        roles = ("mixed",) * args.replicas
    print(f"# --fleet: spawning {args.replicas} {'/'.join(roles)} "
          f"workers (kv_dtype={args.kv_dtype}) ...", file=sys.stderr)
    procs = spawn_fleet(spec, roles=roles)

    failures = []

    def check(ok, msg):
        if not ok:
            failures.append(msg)

    kill_note = {"fired": False, "tokens_emitted": None,
                 "active_slots": None}

    def killer():
        # mid-decode, for real: wait until the seeded victim process
        # has emitted >= kill-after tokens AND holds an active decode
        # slot, then SIGKILL it — no goodbye, no flushing
        c = WorkerClient(procs.workers[victim_idx].url)
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                st = c.stats()["stats"]
            except Exception:         # noqa: BLE001 — transient, retry
                time.sleep(0.01)
                continue
            if st["tokens_emitted"] >= args.kill_after \
                    and st["slot_occupancy"] > 0:
                kill_note.update(
                    fired=True, tokens_emitted=st["tokens_emitted"],
                    active_slots=st["slot_occupancy"])
                procs.workers[victim_idx].kill()
                return
            time.sleep(0.005)

    router = FleetRouter(procs.urls)
    # the fleet observability plane rides along the whole soak: the
    # collector scrapes/merges every worker over the control plane and
    # the verdict below judges its trace/staleness contracts
    coll = router.observe(interval_s=0.5, scrape_timeout_s=5.0)
    clients = []
    for i, (beh, body) in enumerate(zip(behaviors, bodies)):
        tp = f"00-{i + 1:032x}-{i + 1:016x}-01"
        if beh == "read":
            c = _Client(i, "read", body, traceparent=tp)
        elif beh == "hangup":
            c = _Client(i, "hangup", body,
                        cutoff=int(rng.integers(0, 600)), traceparent=tp)
        else:
            c = _Client(i, "slow", body,
                        stall_s=float(rng.uniform(1.0, 1.6)),
                        traceparent=tp)
        clients.append(c)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                         args.requests))

    # pre-soak free-page baseline per worker: the cross-process leak
    # bar — after quiesce every survivor must be back at it
    free_at_warm = {}
    for w in procs.workers:
        free_at_warm[w.url] = \
            WorkerClient(w.url).stats()["stats"]["pool_free_pages"]

    fe = ServingFrontend(router, stream_buffer=args.stream_buffer,
                         keepalive_s=0.05, step_idle_s=0.005)
    deaths = failovers = 0
    try:
        if args.kill_after > 0:
            threading.Thread(target=killer, daemon=True,
                             name="soak-fleet-killer").start()
        threads = []
        t0 = time.perf_counter()
        for arr, c in zip(arrivals, clients):
            lag = arr - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            t = threading.Thread(target=c.run, args=(fe.host, fe.port),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=600)
        check(not any(t.is_alive() for t in threads),
              "client threads still alive after 600s")

        deadline = time.time() + 180
        while time.time() < deadline:
            if (not router.has_work
                    and fe.stats["active_streams"] == 0
                    and fe._cmd_q.empty()):
                break
            time.sleep(0.02)
        soak_s = time.perf_counter() - t0

        # -- graceful drain, at the ingress ------------------------------
        fe.begin_drain()
        probe = _Client(-1, "read", {"prompt": [1, 2], "max_new_tokens": 2})
        probe.run(fe.host, fe.port)
        err = {}
        try:
            err = json.loads(probe.raw.decode())["error"]
        except Exception:             # noqa: BLE001 — verdict below
            pass
        check(probe.status == 503 and err.get("reason") == "draining"
              and int(probe.headers.get("retry-after", 0)) >= 1,
              f"drain probe: status={probe.status}, error={err}, "
              f"retry-after={probe.headers.get('retry-after')!r}")

        # -- verdict -----------------------------------------------------
        st = fe.stats
        by_code = dict(st["requests_by_code"])
        rejected = sum(int(v) for k, v in by_code.items()
                       if k in ("400", "429", "500", "503"))
        rejected -= 1                 # the drain probe's 503
        admitted = args.requests - rejected
        check(not router.has_work, "router still has work after quiesce")
        check(not router._live, f"live map leaked: {router._live}")
        check(st["active_streams"] == 0,
              f"live streams leaked: {st['active_streams']}")

        deaths = int(router._m["deaths"].value)
        failovers = int(router._m["failovers"].value)
        if args.kill_after > 0:
            check(kill_note["fired"],
                  "seeded SIGKILL never fired (victim never held an "
                  "active decode slot past the token threshold)")
            states = {w["url"]: w["state"]
                      for w in router.fleet_stats()["workers"]}
            check(states.get(procs.workers[victim_idx].url) == "down",
                  f"victim not marked down: {states}")
            check(deaths >= 1, f"worker deaths observed: {deaths}")
            check(failovers >= 1,
                  f"no mid-flight failover despite killing a worker "
                  f"with {kill_note['active_slots']} active slots")

        # survivors: compile-flat and leak-free, judged over their OWN
        # control plane — this process cannot reach their engines
        worker_rows = []
        for i, w in enumerate(procs.workers):
            if i == victim_idx and kill_note["fired"]:
                worker_rows.append({"url": w.url, "role": w.role,
                                    "state": "killed"})
                continue
            s = WorkerClient(w.url).stats()
            es = s["stats"]
            worker_rows.append({
                "url": w.url, "role": w.role, "state": "up",
                "tokens_emitted": es["tokens_emitted"],
                "requests_finished": es["requests_finished"],
                "steady_state_compiles": es["steady_state_compiles"]})
            check(es["steady_state_compiles"] == 0,
                  f"worker {w.url} steady_state_compiles = "
                  f"{es['steady_state_compiles']}")
            check(es["slot_occupancy"] == 0 and es["queue_depth"] == 0,
                  f"worker {w.url} not idle after quiesce: "
                  f"active={es['slot_occupancy']} "
                  f"queued={es['queue_depth']}")
            check(es["pool_free_pages"] == free_at_warm[w.url],
                  f"worker {w.url} leaked KV pages: "
                  f"{es['pool_free_pages']} free vs "
                  f"{free_at_warm[w.url]} at warm")
            check(s["frontend"]["active_streams"] == 0,
                  f"worker {w.url} leaked worker-side streams")

        # per-client verdicts against the offline reference
        identical = prefix_ok = overflows_seen = reject_ok = 0
        for c in clients:
            check(c.error is None, f"client {c.idx}: {c.error}")
            if c.error is not None or c.status is None:
                continue
            if c.status in (429, 503):
                try:
                    e = json.loads(c.raw.decode())["error"]
                    good = (e.get("type") and e.get("reason")
                            and "retry_after_s" in e)
                except Exception:     # noqa: BLE001 — verdict
                    good = False
                good = good and int(c.headers.get("retry-after", 0)) >= 1
                check(good, f"client {c.idx}: {c.status} rejection "
                            f"missing Retry-After or structured body")
                reject_ok += int(bool(good))
                continue
            if c.status != 200:
                check(False, f"client {c.idx}: unexpected {c.status}")
                continue
            want = c.traceparent.split("-")[1]
            got_tp = (c.headers.get("traceparent") or "").split("-")
            check(len(got_tp) == 4 and got_tp[1] == want,
                  f"client {c.idx}: traceparent not echoed "
                  f"({c.headers.get('traceparent')!r})")
            evs = _sse_events(c.raw.decode(errors="replace"))
            got = _sse_tokens(evs)
            ref = reference[f"soak-{c.idx}"]
            if c.behavior == "read":
                dones = [p for ev, p in evs if ev == "done"]
                check(len(dones) == 1
                      and dones[0]["status"] == "finished",
                      f"client {c.idx}: full read did not finish: "
                      f"{dones}")
                check(got == ref,
                      f"client {c.idx}: stream diverged from offline "
                      f"reference ({got} != {ref})")
                identical += int(got == ref)
            else:
                check(got == ref[:len(got)],
                      f"client {c.idx}: partial stream is not a prefix "
                      f"of the reference")
                prefix_ok += int(got == ref[:len(got)])
                overflows_seen += int(any(
                    ev == "error" and p and p.get("error") == "overflow"
                    for ev, p in evs))
        check(st["stream_overflows"] == overflows_seen,
              f"overflow accounting: counted {st['stream_overflows']}, "
              f"clients saw {overflows_seen} error events")
        check(identical > 0,
              "no fully-read stream survived to judge bit-identity")

        # -- trace/observe-plane verdict ---------------------------------
        # one final scrape over whatever is still alive, then judge the
        # collector's contracts: clean runs scrape error-free, SIGKILL
        # runs flag the victim stale (never fatal to the scrape loop),
        # and the assembled fleet trace is clock-aligned
        coll.scrape()
        fz = coll.fleetz()
        scrape_errors = {w["url"]: w["scrape_errors"]
                         for w in fz["workers"]}
        if kill_note["fired"]:
            vrow = [w for w in fz["workers"]
                    if w["url"] == procs.workers[victim_idx].url]
            check(vrow and (vrow[0]["state"] == "stale"
                            or vrow[0]["scrape_errors"] > 0),
                  f"killed worker not flagged stale in /fleetz: {vrow}")
        else:
            check(sum(scrape_errors.values()) == 0,
                  f"fleet_scrape_errors_total != 0 on a clean run: "
                  f"{scrape_errors}")
        tr = coll.fleet_chrome_trace()
        tracks, order_bad = {}, []
        for ev in tr["traceEvents"]:
            if ev.get("ph") == "X":
                tracks.setdefault((ev["pid"], ev["tid"]),
                                  []).append(ev["ts"])
        for k, tss in tracks.items():
            if tss != sorted(tss):
                order_bad.append(k)
        check(not order_bad,
              f"per-track timestamps not monotone after clock "
              f"alignment: {order_bad[:4]}")
        by_trace, finished, track_trace = {}, set(), {}
        for ev in tr["traceEvents"]:
            if ev.get("ph") != "X" or ev.get("cat") != "request":
                continue
            a = ev.get("args") or {}
            tid_ = a.get("trace_id")
            if not tid_:
                continue
            track_trace[(ev["pid"], ev["tid"])] = tid_
            if str(a.get("request_id", "")).startswith("soak-"):
                by_trace.setdefault(tid_, set()).add(ev["pid"])
                if a.get("status") == "finished":
                    finished.add(tid_)
        stitched = [t for t in finished if len(by_trace[t]) >= 2]
        if args.disagg:
            unstitched = sorted(finished - set(stitched))
            check(bool(finished) and not unstitched,
                  f"disagg stitched-trace bar: {len(unstitched)} of "
                  f"{len(finished)} finished soak traces do not span "
                  f">=2 worker processes")
            # alignment sanity per stitched request: the adopting
            # track's first phase span must not begin measurably
            # before the source track's last one ends (the gap between
            # them IS the handoff wire flight — negative beyond clock
            # slack means the aligned axes disagree)
            spans = {}
            for ev in tr["traceEvents"]:
                if ev.get("ph") != "X" or ev.get("cat") != "phase":
                    continue
                t = track_trace.get((ev["pid"], ev["tid"]))
                if t in finished and len(by_trace.get(t, ())) >= 2:
                    spans.setdefault(t, {}).setdefault(
                        ev["pid"], []).append(
                        (ev["ts"], ev["ts"] + ev["dur"]))
            for t, per_pid in spans.items():
                if len(per_pid) < 2:
                    continue
                pids = sorted(per_pid, key=lambda p: min(
                    a for a, _ in per_pid[p]))
                src_end = max(b for _, b in per_pid[pids[0]])
                dst_start = min(a for a, _ in per_pid[pids[-1]])
                check(dst_start - src_end > -100e3,
                      f"trace {t}: adopting track begins "
                      f"{(src_end - dst_start) / 1e3:.1f} ms before "
                      f"the source track ends (clock alignment)")
        observe_row = {
            "scrape_errors": scrape_errors,
            "workers_stale": fz["fleet"]["workers_stale"],
            "tracks": len(tracks),
            "finished_soak_traces": len(finished),
            "stitched_cross_worker": len(stitched),
            "fleet_dumps": fz["fleet_dumps"],
        }

        fe.shutdown(timeout=60)
        check(not fe._loop_thread.is_alive(), "serving loop still alive")
    finally:
        fe.close()
        router.close()
        procs.close()

    summary = {
        "mode": "fleet",
        "requests": args.requests,
        "replicas": args.replicas,
        "disagg": bool(args.disagg),
        "observe": observe_row,
        "kv_dtype": args.kv_dtype,
        "soak_seconds": round(soak_s, 3),
        "requests_by_code": by_code,
        "admitted": admitted,
        "rejected": rejected,
        "full_streams_bit_identical": identical,
        "partial_streams_prefix_ok": prefix_ok,
        "rejections_with_retry_after": reject_ok,
        "stream_overflows": st["stream_overflows"],
        "sigkill": {
            "victim": victim_idx,
            "fired": kill_note["fired"],
            "victim_tokens_emitted": kill_note["tokens_emitted"],
            "victim_active_slots": kill_note["active_slots"],
            "worker_deaths": deaths,
            "failovers": failovers,
        },
        "workers": worker_rows,
        "failures": failures,
        "ok": not failures,
    }
    print(json.dumps(summary, indent=1, sort_keys=True))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 0 if not failures else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=48,
                    help="number of open-loop clients")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds arrivals, prompts, and chaos behavior")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (req/s) — deliberately "
                         "above capacity so backpressure is real and "
                         "the 429 path fires")
    ap.add_argument("--kill-after", type=int, default=8, metavar="STEP",
                    help="router step at which one seeded replica is "
                         "killed (0 disables the kill)")
    ap.add_argument("--stream-buffer", type=int, default=16,
                    help="per-stream token buffer — small, so slow "
                         "readers genuinely overflow")
    ap.add_argument("--kv-dtype", default="float32",
                    choices=("float32", "int8"),
                    help="KV page storage: int8 runs the whole soak — "
                         "chaos, kill-migration, bit-identity bar — "
                         "through quantized pages with fused dequant")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per replica engine — "
                         "the offline reference stays tp=1, so the "
                         "bit-identity bar also proves the sharded "
                         "fleet matches an unsharded engine (on a CPU "
                         "host the virtual device count is forced "
                         "automatically)")
    ap.add_argument("--hbm-budget-bytes", type=int, default=None,
                    metavar="N",
                    help="byte-denominated KV page budget per replica "
                         "(PagePool.from_bytes sizing) — set it below "
                         "the working set so the prefix cache evicts "
                         "under the soak")
    ap.add_argument("--host-budget-bytes", type=int, default=None,
                    metavar="M",
                    help="host-RAM KV spill tier per replica (implies "
                         "prefix_cache): evicted pages spill instead "
                         "of vanishing and page back in on radix hits. "
                         "The offline reference stays spill-OFF, so "
                         "the bit-identity bar is exactly the tier's "
                         "exactness contract — 0 output mismatches vs "
                         "the spill-off reference, no page leaked "
                         "across tiers (cross-tier audit)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the soak across REAL worker subprocesses: "
                         "a FleetRouter over spawn_fleet workers behind "
                         "the same ingress frontend, with the seeded "
                         "kill delivered as a SIGKILL to one worker "
                         "process mid-decode (--kill-after then means: "
                         "kill once the victim has emitted that many "
                         "tokens with a decode in flight)")
    ap.add_argument("--disagg", action="store_true",
                    help="with --fleet: disaggregated roles (one "
                         "prefill worker, the rest decode) so every "
                         "admitted request crosses a prefill->decode "
                         "handoff — the verdict then asserts each "
                         "finished request's stitched trace spans >=2 "
                         "worker processes on the collector's clock-"
                         "aligned fleet trace (disables the seeded "
                         "SIGKILL: there is only one prefill worker)")
    ap.add_argument("--json", default=None,
                    help="also write the summary JSON to this path")
    args = ap.parse_args(argv)
    if args.disagg and not args.fleet:
        ap.error("--disagg requires --fleet")
    if args.fleet:
        if args.tp > 1 or args.hbm_budget_bytes is not None \
                or args.host_budget_bytes is not None:
            ap.error("--fleet does not compose with --tp / "
                     "--hbm-budget-bytes / --host-budget-bytes "
                     "(single-process engine knobs)")
        return _main_fleet(args)
    if (args.tp > 1 and "jax" not in sys.modules
            and "host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.tp}")

    # the soak is exactly the workload the ownership assertions exist
    # for: HTTP handler threads racing a serving loop under chaos.
    # Enable them unless the caller explicitly disabled them.
    os.environ.setdefault("MX_ASSERT_OWNERSHIP", "1")
    from mxnet_tpu.analysis import set_assert_ownership
    set_assert_ownership(
        os.environ["MX_ASSERT_OWNERSHIP"] in ("1", "true", "yes"))

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import (ReplicaFaultPlan, Request,
                                   ServingEngine, ServingFrontend,
                                   ServingRouter)

    # tp shards head-wise, so the toy model grows heads to match
    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2,
                     num_heads=max(2, args.tp),
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    mx.rng.seed(3)
    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.05))
    max_len, page, slots = 64, 8, 2
    rng = np.random.default_rng(args.seed)

    # seeded client behaviors: ~50% read everything, ~30% hang up at
    # a seeded byte offset (0 = before the first event), ~20% are slow
    # readers that stall mid-stream and advertise a tiny flow-control
    # window (the keepalive/pacing chaos; at toy token counts the
    # kernel socket buffers absorb the whole stream, so the overflow-
    # cancel policy itself is pinned by tests/test_frontend.py)
    behaviors = []
    for i in range(args.requests):
        u = rng.random()
        behaviors.append("read" if u < 0.5
                         else "hangup" if u < 0.8 else "slow")

    # the request set: greedy, so every replica/batching/migration
    # history must produce the SAME tokens as the offline reference.
    # Tiered runs draw prompts from shared multi-page prefix families
    # whose combined working set overflows the retention budget — the
    # soak then actually spills, pages in on radix revisits, and the
    # bit-identity bar covers the tier (random sub-page prompts never
    # would).
    tiered = args.host_budget_bytes is not None
    fams = [rng.integers(1, cfg.vocab_size, 3 * page).tolist()
            for _ in range(6)] if tiered else None
    bodies, prompts = [], []
    for i in range(args.requests):
        if tiered:
            prompt = (fams[int(rng.integers(0, len(fams)))]
                      + rng.integers(1, cfg.vocab_size,
                                     int(rng.integers(0, 6))).tolist())
        else:
            prompt = rng.integers(1, cfg.vocab_size,
                                  int(rng.integers(3, 13))).tolist()
        prompts.append(prompt)
        body = {"prompt": prompt,
                "max_new_tokens": int(rng.integers(6, 17)),
                "request_id": f"soak-{i}"}
        if behaviors[i] == "slow":
            body["stream_buffer"] = 2
        bodies.append(body)

    def new_engine(max_queue=None, tp=1, spill=False):
        kv = None if args.kv_dtype == "float32" else args.kv_dtype
        # int8 pages: the chunk grid is part of the numerics, so the
        # bit-identity bar needs a non-binding prefill budget — every
        # prompt then chunks on the same grid in the reference engine,
        # the replicas, and a migration replay (docs/SERVING.md
        # "Quantized KV pages")
        budget = slots * page if kv else None
        # the tiered replicas and the spill-off reference both run a
        # prefix cache, so the ONLY thing the bit-identity bar varies
        # is the host tier itself (docs/SERVING.md "Tiered KV cache")
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, attn_impl="xla",
                            max_queue=max_queue,
                            kv_dtype=kv, prefill_chunk_budget=budget,
                            prefix_cache=tiered,
                            hbm_budget_bytes=(args.hbm_budget_bytes
                                              if spill else None),
                            host_kv_bytes=(args.host_budget_bytes
                                           if spill else None),
                            tp=tp)
        # warm every prefill bucket a migrated request can land in
        # (re-prefill covers prompt + already-emitted tokens; tiered
        # prompts are longer — 3 shared pages + a 0-5 token tail)
        pmax = (3 * page + 5) if tiered else 12
        eng.serve([Request(list(range(1, b + 1)), 2,
                           request_id=f"warm{b}")
                   for b in range(page, min(pmax + 16 + page, max_len),
                                  page)])
        eng.mark_warm()
        eng.reset_stats()
        return eng

    # offline reference: ONE fault-free engine serves clones of every
    # request — the bit-identity bar for everything the soak streams
    ref_eng = new_engine()
    ref_reqs = [Request(p, b["max_new_tokens"], request_id=b["request_id"])
                for p, b in zip(prompts, bodies)]
    ref_eng.serve(ref_reqs)
    reference = {r.id: [int(t) for t in r.output_tokens]
                 for r in ref_reqs}
    assert all(r.status == "finished" for r in ref_reqs)

    engines = [new_engine(max_queue=4, tp=args.tp, spill=tiered)
               for _ in range(args.replicas)]
    compiles_at_warm = {e._eid: _compiles(e._eid) for e in engines}
    router = ServingRouter(engines, hedge_after_s=1e9)
    plan = None
    if args.kill_after > 0:
        victim = int(rng.integers(0, args.replicas))
        plan = ReplicaFaultPlan(
            kill={args.kill_after: victim}).install(router)

    clients = []
    for i, (beh, body) in enumerate(zip(behaviors, bodies)):
        # every client propagates a W3C trace context; the verdict
        # checks the response echoes the SAME trace id and that the
        # engine-side timeline adopted it (docs/OBSERVABILITY.md
        # "Trace propagation")
        tp = f"00-{i + 1:032x}-{i + 1:016x}-01"
        if beh == "read":
            c = _Client(i, "read", body, traceparent=tp)
        elif beh == "hangup":
            c = _Client(i, "hangup", body,
                        cutoff=int(rng.integers(0, 600)), traceparent=tp)
        else:
            c = _Client(i, "slow", body,
                        stall_s=float(rng.uniform(1.0, 1.6)),
                        traceparent=tp)
        clients.append(c)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                         args.requests))

    failures = []

    def check(ok, msg):
        if not ok:
            failures.append(msg)

    fe = ServingFrontend(router, stream_buffer=args.stream_buffer,
                         keepalive_s=0.05, step_idle_s=0.005)
    try:
        threads = []
        t0 = time.perf_counter()
        for arr, c in zip(arrivals, clients):
            lag = arr - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)       # open-loop: fire on schedule
            t = threading.Thread(target=c.run, args=(fe.host, fe.port),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=600)
        check(not any(t.is_alive() for t in threads),
              "client threads still alive after 600s")

        # quiesce: the serving loop finishes whatever the hangups left
        deadline = time.time() + 120
        while time.time() < deadline:
            if (not router.has_work
                    and fe.stats["active_streams"] == 0
                    and fe._cmd_q.empty()):
                break
            time.sleep(0.02)
        soak_s = time.perf_counter() - t0

        # -- graceful drain, while everything is still up ----------------
        fe.begin_drain()
        probe = _Client(-1, "read", {"prompt": [1, 2], "max_new_tokens": 2})
        probe.run(fe.host, fe.port)
        err = {}
        try:
            err = json.loads(probe.raw.decode())["error"]
        except Exception:             # noqa: BLE001 — verdict below
            pass
        check(probe.status == 503 and err.get("reason") == "draining"
              and int(probe.headers.get("retry-after", 0)) >= 1,
              f"drain probe: status={probe.status}, error={err}, "
              f"retry-after={probe.headers.get('retry-after')!r}")

        # -- verdict ------------------------------------------------------
        st = fe.stats
        by_code = dict(st["requests_by_code"])
        rejected = sum(int(v) for k, v in by_code.items()
                       if k in ("400", "429", "500", "503"))
        rejected -= 1                 # the drain probe's 503
        admitted = args.requests - rejected
        finished = sum(e.stats["requests_finished"] for e in engines)
        cancelled = sum(e.stats["requests_cancelled"] for e in engines)
        failed = sum(e.stats["requests_failed"] for e in engines)

        check(finished + cancelled + failed == admitted,
              f"terminal accounting: finished {finished} + cancelled "
              f"{cancelled} + failed {failed} != admitted {admitted} "
              f"(codes {by_code})")
        check(failed == 0, f"requests_failed = {failed}")
        check(not router.has_work, "router still has work after quiesce")
        check(not router._owner, f"owner map leaked: {router._owner}")
        check(st["active_streams"] == 0,
              f"live streams leaked: {st['active_streams']}")
        for e in engines:
            check(e.scheduler.num_active == 0 and e.scheduler.num_queued
                  == 0, f"engine{e._eid} slots/queue not empty")
            check(e.audit_pages() == [],
                  f"engine{e._eid} page audit: {e.audit_pages()}")
            check(e.audit_adapters() == [],
                  f"engine{e._eid} adapter audit: {e.audit_adapters()}")
            if e.host_pool is not None:
                # cross-tier leak bar: nothing pinned, no orphaned or
                # double-resident page between HBM and the host tier
                # (audit_pages above already checks residency overlap)
                check(e.host_pool.audit() == [],
                      f"engine{e._eid} host tier audit: "
                      f"{e.host_pool.audit()}")
            drift = _compiles(e._eid) - compiles_at_warm[e._eid]
            check(drift == 0,
                  f"engine{e._eid} steady_state_compiles = {drift}")
        check(st["cancels_issued"] + st["cancels_noop"]
              == st["disconnects"],
              f"cancel accounting: issued {st['cancels_issued']} + noop "
              f"{st['cancels_noop']} != disconnects {st['disconnects']}")
        if plan is not None:
            check(plan.counts["kill"] == 1,
                  f"scheduled kill never fired: {dict(plan.counts)}")
            check(router.stats["replica_down"].get("kill") == 1,
                  f"replica_down: {router.stats['replica_down']}")

        # per-client verdicts against the offline reference
        identical = prefix_ok = overflows_seen = reject_ok = 0
        for c in clients:
            check(c.error is None, f"client {c.idx}: {c.error}")
            if c.error is not None or c.status is None:
                continue
            if c.status in (429, 503):
                try:
                    e = json.loads(c.raw.decode())["error"]
                    good = (e.get("type") and e.get("reason")
                            and "retry_after_s" in e)
                except Exception:     # noqa: BLE001 — verdict
                    good = False
                good = good and int(c.headers.get("retry-after", 0)) >= 1
                check(good, f"client {c.idx}: {c.status} rejection "
                            f"missing Retry-After or structured body")
                reject_ok += int(bool(good))
                continue
            if c.status != 200:
                check(False, f"client {c.idx}: unexpected {c.status}")
                continue
            evs = _sse_events(c.raw.decode(errors="replace"))
            got = _sse_tokens(evs)
            ref = reference[f"soak-{c.idx}"]
            if c.behavior == "read":
                dones = [p for ev, p in evs if ev == "done"]
                check(len(dones) == 1
                      and dones[0]["status"] == "finished",
                      f"client {c.idx}: full read did not finish: "
                      f"{dones}")
                check(got == ref,
                      f"client {c.idx}: stream diverged from offline "
                      f"reference ({got} != {ref})")
                identical += int(got == ref)
            else:
                check(got == ref[:len(got)],
                      f"client {c.idx}: partial stream is not a prefix "
                      f"of the reference")
                prefix_ok += int(got == ref[:len(got)])
                overflows_seen += int(any(
                    ev == "error" and p and p.get("error") == "overflow"
                    for ev, p in evs))

        # every overflow the frontend counted reached its client as a
        # structured error event (only slow readers can overflow —
        # everyone else's budget fits the buffer)
        check(st["stream_overflows"] == overflows_seen,
              f"overflow accounting: counted {st['stream_overflows']}, "
              f"clients saw {overflows_seen} error events")

        # -- trace + span accounting --------------------------------------
        # the TTFT phase budget must reconcile with what both sides
        # measured: phases never overcount the engine TTFT, sum to it
        # exactly for undisturbed requests, and the engine can never
        # claim a first token later than the client saw bytes
        from mxnet_tpu import telemetry
        fleet = {str(e._eid) for e in engines}
        per_req = {}
        for tr in telemetry.request_log.recent(10**6):
            rid = str(tr["request_id"])
            if str(tr["engine"]) in fleet and rid.startswith("soak-"):
                per_req.setdefault(rid, []).append(tr)
        disturb = {"requeued", "preempted", "resumed", "resumed_swap",
                   "hedged", "swap_stale", "decode_discarded"}
        spans = strict = trace_prop = ttfb_ok = 0
        for c in clients:
            rid = f"soak-{c.idx}"
            trs = per_req.get(rid, [])
            if c.status == 200 and c.traceparent:
                want = c.traceparent.split("-")[1]
                got = (c.headers.get("traceparent") or "").split("-")
                check(len(got) == 4 and got[1] == want,
                      f"client {c.idx}: traceparent not echoed "
                      f"({c.headers.get('traceparent')!r})")
                check(all(tr["trace_id"] == want for tr in trs),
                      f"client {c.idx}: engine timeline dropped the "
                      f"propagated trace id "
                      f"({[tr['trace_id'] for tr in trs]})")
                trace_prop += 1
            fts = [(tr, ev) for tr in trs for ev in tr["events"]
                   if ev["event"] == "first_token"]
            if not fts:
                continue              # cancelled/killed pre-first-token
            tr, ev = fts[-1]
            ttft = float(ev["ttft"])
            ph = tr.get("phases") or {}
            total = sum(ph.values())
            # the budget may undercount (requeue/migration gaps are
            # nobody's phase) but must never overcount
            check(total <= ttft + 0.005,
                  f"{rid}: phase sum {total * 1e3:.1f} ms > TTFT "
                  f"{ttft * 1e3:.1f} ms (phases {ph})")
            spans += 1
            clean = (len(trs) == 1
                     and "resumed_at" not in tr["events"][0]
                     and not any(e["event"] in disturb
                                 for e in tr["events"]))
            if clean:
                check(abs(total - ttft) <= 0.005,
                      f"{rid}: clean request's phases sum to "
                      f"{total * 1e3:.1f} ms vs TTFT {ttft * 1e3:.1f} "
                      f"ms — the budget must account the whole TTFT")
                strict += 1
            if c.behavior == "read" and c.status == 200 \
                    and c.t_first is not None and c.t_sent is not None:
                ttfb = c.t_first - c.t_sent
                check(ttfb + 1e-3 >= ttft,
                      f"{rid}: client TTFB {ttfb * 1e3:.1f} ms < engine "
                      f"TTFT {ttft * 1e3:.1f} ms — the engine cannot "
                      f"emit before the client asked")
                ttfb_ok += 1
        check(spans > 0, "span accounting: no first_token timelines "
                         "recorded (request log disabled?)")

        fe.shutdown(timeout=60)
        check(not fe._loop_thread.is_alive(), "serving loop still alive")
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((fe.host, fe.port))
        except OSError:
            check(False, "port not released after shutdown")
        finally:
            s.close()
    finally:
        if plan is not None:
            plan.uninstall()
        fe.close()

    summary = {
        "requests": args.requests,
        "tp": args.tp,
        "soak_seconds": round(soak_s, 3),
        "requests_by_code": by_code,
        "admitted": admitted,
        "finished": finished,
        "cancelled": cancelled,
        "rejected": rejected,
        "disconnects": st["disconnects"],
        "stream_overflows": st["stream_overflows"],
        "overflow_error_events": overflows_seen,
        "full_streams_bit_identical": identical,
        "partial_streams_prefix_ok": prefix_ok,
        "rejections_with_retry_after": reject_ok,
        "migrated": router.stats["migrated"],
        "replica_down": router.stats["replica_down"],
        "steady_state_compiles": {
            f"engine{e._eid}": _compiles(e._eid) - compiles_at_warm[e._eid]
            for e in engines},
        "span_accounting": {
            "first_token_timelines": spans,
            "clean_exact": strict,
            "client_ttfb_vs_engine_ttft": ttfb_ok,
            "traceparent_round_trips": trace_prop,
        },
        "kv_tier": None if not tiered else {
            "kv_spill_pages": sum(e.stats["kv_spill_pages"]
                                  for e in engines),
            "kv_pagein_pages": sum(e.stats["kv_pagein_pages"]
                                   for e in engines),
            "kv_host_evictions": sum(e.stats["kv_host_evictions"]
                                     for e in engines),
            "kv_host_entries_left": sum(e.host_pool.num_entries
                                        for e in engines),
            "preempts": sum(e.stats["preempts"] for e in engines),
        },
        "failures": failures,
        "ok": not failures,
    }
    print(json.dumps(summary, indent=1, sort_keys=True))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
