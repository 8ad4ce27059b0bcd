#!/usr/bin/env python
"""Run a tiny serving and/or training loop and print the telemetry
snapshot — the smoke-test CLI for the observability subsystem
(docs/OBSERVABILITY.md).

Usage:
    JAX_PLATFORMS=cpu python tools/dump_telemetry.py            # both
    python tools/dump_telemetry.py --workload serving
    python tools/dump_telemetry.py --workload training
    python tools/dump_telemetry.py --format prometheus
    python tools/dump_telemetry.py --out telemetry.json
    python tools/dump_telemetry.py --spans spans.jsonl
    python tools/dump_telemetry.py --trace trace.json   # -> perfetto
    python tools/dump_telemetry.py --serve 9100 --linger 60
    python tools/dump_telemetry.py --cost     # MFU/roofline/compile
    python tools/dump_telemetry.py --shed     # load-shedding headline
    python tools/dump_telemetry.py --tenants  # multi-tenant headline
    python tools/dump_telemetry.py --router   # multi-replica headline
    python tools/dump_telemetry.py --http     # HTTP-ingress headline
    python tools/dump_telemetry.py --kv       # tiered-KV headline
    python tools/dump_telemetry.py --slo      # SLO burn-rate headline

--trace writes the run's request timelines + spans as Chrome
trace_event JSON (open in ui.perfetto.dev). --serve starts the live
introspection server (docs/OBSERVABILITY.md) and --linger keeps the
process alive that many seconds so you can curl /metrics, /statusz,
/requests, /trace, /compilez, /memz. --cost prints the device-cost
headline: per-program FLOPs / arithmetic intensity / roofline side /
MFU, compile attribution, and the HBM-ledger reconciliation against
live-array bytes.

Exit code 0 means the loops ran and the snapshot round-tripped.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_serving():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import Request, ServingEngine

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    # w8 weights on the demo engine so the --cost weight headline and
    # the serving_weight_bytes gauges carry real quantized values
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla", prefix_cache=True,
                        weight_dtype="int8")
    rng = np.random.default_rng(0)
    # half the prompts extend one shared prefix so the prefix-cache
    # instruments carry real values in the dump
    shared = rng.integers(0, cfg.vocab_size, 9).tolist()
    reqs = [Request(shared + rng.integers(0, cfg.vocab_size, 3).tolist()
                    if i % 2 else
                    rng.integers(0, cfg.vocab_size, n).tolist(), 5,
                    seed=i, do_sample=bool(i % 2), request_id=i)
            for i, n in enumerate((3, 7, 12, 5))]
    done = eng.serve(reqs)
    assert len(done) == len(reqs)
    # a second engine with speculative decoding over a repetitive
    # workload, so the spec_* instruments carry real values in the dump
    spec = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                         attn_impl="xla", speculative=True,
                         spec_tokens=4)
    pat = rng.integers(0, cfg.vocab_size, 3).tolist()
    sreqs = [Request(pat * (2 + i % 2) + pat[:1], 8, seed=i,
                     request_id=100 + i) for i in range(3)]
    assert len(spec.serve(sreqs)) == len(sreqs)
    return eng, spec


def run_shedding():
    """A deliberately overloaded engine: tight watermarks, a one-shot
    burst of mixed-priority deadline traffic — so the shed/overload/
    degradation instruments carry real values in the dump."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import (RejectedError, Request, ServingEngine,
                                   SheddingPolicy)

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    eng = ServingEngine(
        net, num_slots=1, max_length=32, page_size=8,
        attn_impl="xla",
        policy=SheddingPolicy(queue_low=1, queue_high=2,
                              degrade_after=2, recover_after=2))
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, 4).tolist(), 3,
                    seed=i, priority=i % 3, request_id=400 + i,
                    deadline_ms=None if i % 2 else 2000.0)
            for i in range(10)]
    shed = 0
    for r in reqs:
        try:
            eng.submit(r)
        except RejectedError:
            shed += 1
    while eng.has_work:
        eng.step()
    for _ in range(3):          # calm ticks so degradation recovers
        eng.step()
    return eng


def run_router():
    """A two-replica router with aggressive hedging and a seeded
    mid-run replica kill — so the router_* instruments (placement,
    migration, hedging, replica-down) carry real values in the dump."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import (ReplicaFaultPlan, Request,
                                   ServingEngine, ServingRouter)

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    engines = [ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                             attn_impl="xla")
               for _ in range(2)]
    router = ServingRouter(engines, hedge_after_s=0.0)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 8).tolist()
    reqs = [Request(shared + rng.integers(1, cfg.vocab_size, 3).tolist()
                    if i % 2 else
                    rng.integers(1, cfg.vocab_size, 6).tolist(), 5,
                    seed=i, request_id=500 + i) for i in range(10)]
    plan = ReplicaFaultPlan(kill={6: 0}).install(router)
    try:
        for r in reqs:
            router.submit(r)
        steps = 0
        while router.has_work and steps < 5000:
            router.step()
            steps += 1
    finally:
        plan.uninstall()
    return router


def run_http():
    """A live ServingFrontend over a tiny engine: two clients stream
    /v1/generate to completion and one hangs up mid-stream — so the
    http_* instruments (requests by code, disconnects, TTFB, active
    streams) carry real values in the dump."""
    import http.client
    import socket

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import ServingEngine, ServingFrontend

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla")
    fe = ServingFrontend(eng, keepalive_s=0.05, step_idle_s=0.005)
    try:
        for i in range(2):          # well-behaved streaming clients
            conn = http.client.HTTPConnection(fe.host, fe.port,
                                              timeout=120)
            conn.request("POST", "/v1/generate",
                         json.dumps({"prompt": [3 + i, 5, 7],
                                     "max_new_tokens": 5,
                                     "request_id": f"http-{i}"}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200, resp.status
            resp.read()
            conn.close()
        # one client that hangs up mid-stream -> disconnect + cancel
        body = json.dumps({"prompt": [9, 8, 7], "max_new_tokens": 24,
                           "request_id": "http-gone"}).encode()
        sock = socket.create_connection((fe.host, fe.port), timeout=120)
        sock.sendall(b"POST /v1/generate HTTP/1.0\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        buf = b""
        while b"event: tokens" not in buf:
            chunk = sock.recv(4096)
            if not chunk:
                break
            buf += chunk
        sock.close()
        import time
        deadline = time.time() + 60
        while time.time() < deadline:
            if not eng.has_work and fe.stats["active_streams"] == 0 \
                    and fe.stats["disconnects"] >= 1:
                break
            time.sleep(0.02)
    finally:
        fe.close()
    return fe


def run_fleet():
    """A REAL cross-process fleet: prefill + decode worker
    subprocesses behind the wire protocol, a few streamed requests
    (every one crossing a prefill->decode handoff) — so the router's
    fleet_* instruments carry real values in the dump. The workers'
    own telemetry lives in THEIR processes; the FleetCollector scrapes
    and merges it (counters summed, gauges per-worker, histograms
    bucket-wise) into one registry, exactly what a fleet scrape config
    would see. Returns (router, fleet view) with the /fleetz payload
    and the merged-family headline captured before the workers exit."""
    import numpy as np

    from mxnet_tpu.serving import Request, TokenStream
    from mxnet_tpu.serving.fleet import FleetRouter, spawn_fleet

    spec = {"config": {"vocab_size": 97, "units": 32, "num_layers": 2,
                       "num_heads": 2, "max_length": 64, "dropout": 0.0,
                       "attention_dropout": 0.0},
            "seed": 3, "init_std": 0.05,
            "engine": {"num_slots": 2, "max_length": 32, "page_size": 8,
                       "attn_impl": "xla"}}
    rng = np.random.default_rng(0)
    with spawn_fleet(spec, roles=("prefill", "decode")) as procs:
        router = FleetRouter(procs.urls)
        reqs = [Request(rng.integers(0, 97, n).tolist(), 5, seed=i,
                        do_sample=bool(i % 2), request_id=f"fleet-{i}")
                for i, n in enumerate((4, 9, 6))]
        for r in reqs:
            r.stream = TokenStream(capacity=64)
            router.submit(r)
        for r in reqs:
            router.result(r, timeout=120)
        assert all(r.status == "finished" for r in reqs)
        # one collector scrape over the live worker ports, then
        # snapshot everything the headline needs before they exit
        coll = router.observe(interval_s=0.5)
        merged = coll.scrape()
        tok = merged.get("serving_tokens_emitted_total")
        tokens = (sum(c._value for _, c in tok._samples())
                  if tok is not None else 0.0)
        view = {"fleetz": coll.fleetz(),
                "families": len(merged._instruments),
                "tokens": tokens}
        router.close()
    return router, view


def run_tenants():
    """A multi-tenant engine: more registered adapters than slab
    slots, three tenants with one pushed past its queue quota — so
    the serving_adapter_* / serving_tenant_* instruments carry real
    values in the dump."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import (AdapterPool, RejectedError, Request,
                                   ServingEngine, TenantQuota,
                                   random_lora)

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    pool = AdapterPool(cfg, slots=3, max_rank=2)   # 2 usable slots
    adapters = [f"ft{i}" for i in range(4)]        # > usable slots
    for i, name in enumerate(adapters):
        pool.register(name, random_lora(cfg, rank=2, seed=20 + i,
                                        scale=0.05))
    eng = ServingEngine(
        net, num_slots=2, max_length=32, page_size=8,
        attn_impl="xla", adapter_pool=pool,
        tenant_quotas={"hog": TenantQuota(max_active=1, max_queue=2),
                       "calm": TenantQuota(weight=2.0)})
    rng = np.random.default_rng(0)
    tenants = ["hog", "hog", "hog", "calm", "free"]
    shed = 0
    for i in range(12):
        r = Request(rng.integers(1, cfg.vocab_size, 5).tolist(), 4,
                    request_id=600 + i, tenant=tenants[i % len(tenants)],
                    adapter_id=adapters[i % len(adapters)])
        try:
            eng.submit(r)
        except RejectedError:
            shed += 1
    while eng.has_work:
        eng.step()
    return eng


def run_kv():
    """A spill-pressured tiered-KV engine: a page budget several times
    smaller than the working set plus a host-RAM tier, shared-prefix
    traffic evicting and re-hitting spilled nodes — so the
    serving_kv_spill*/serving_kv_pagein* instruments carry real values
    in the dump."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import Request, ServingEngine

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", kv_dtype="int8",
                        prefix_cache=True, prefix_cache_pages=4,
                        host_kv_bytes=1 << 22)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 24).tolist()
    eng.serve([Request(shared + rng.integers(1, 97, 4).tolist(), 4,
                       request_id=700)])
    for i in range(6):               # churn past the page budget
        eng.serve([Request(rng.integers(1, 97, 17).tolist(), 3,
                           request_id=701 + i)])
    eng.serve([Request(shared + rng.integers(1, 97, 4).tolist(), 4,
                       request_id=710)])   # radix hit pages back in
    return eng


def run_slo():
    """A tiny engine serving under two declared objectives — one
    generous (stays green) and one deliberately blown (its fast window
    burns budget immediately) — so the slo_* instruments, the /sloz
    burn table, and the per-request phase budgets carry real values in
    the dump."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import Request, ServingEngine

    telemetry.slo.configure([
        telemetry.SLO("ttft_generous", ttft_p99_ms=60_000.0,
                      min_events=2),
        telemetry.SLO("ttft_blown", ttft_p99_ms=0.01, min_events=2),
    ])
    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla")
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(1, cfg.vocab_size, 5).tolist(), 3,
                    seed=i, request_id=800 + i) for i in range(4)]
    done = eng.serve(reqs)
    assert len(done) == len(reqs)
    telemetry.slo.slo_engine.evaluate()
    return eng


def run_training():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import Trainer, loss as gloss, nn

    net = nn.Dense(4, flatten=False, in_units=8)
    net.initialize(mx.init.Normal(0.1))
    trainer = Trainer(net.collect_params(), opt.SGD(learning_rate=0.1))
    lfn = gloss.L2Loss()
    rng = np.random.default_rng(0)
    for _ in range(3):
        x = mx.nd.array(rng.standard_normal((4, 8)), dtype="float32")
        y = mx.nd.array(rng.standard_normal((4, 4)), dtype="float32")
        with mx.autograd.record():
            loss = lfn(net(x), y)
        loss.backward()
        trainer.step(batch_size=4)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", choices=("serving", "training", "both"),
                    default="both")
    ap.add_argument("--format", choices=("json", "prometheus"),
                    default="json")
    ap.add_argument("--out", default=None,
                    help="also dump the JSON snapshot to this path")
    ap.add_argument("--spans", default=None,
                    help="append span events to this JSONL file")
    ap.add_argument("--trace", default=None,
                    help="write Chrome trace_event JSON (perfetto) here")
    ap.add_argument("--cost", action="store_true",
                    help="print the MFU/roofline/compile headline and "
                         "the HBM-ledger reconciliation")
    ap.add_argument("--shed", action="store_true",
                    help="also run an overloaded engine (tight "
                         "watermarks, mixed-priority deadline burst) "
                         "and print the load-shedding headline")
    ap.add_argument("--tenants", action="store_true",
                    help="also run a multi-tenant LoRA engine (paged "
                         "adapter slab + tenant quotas) and print the "
                         "per-tenant headline")
    ap.add_argument("--slo", action="store_true",
                    help="also run an engine under declared SLO "
                         "objectives (one green, one deliberately "
                         "burning) and print the burn-rate headline")
    ap.add_argument("--kv", action="store_true",
                    help="also run a spill-pressured tiered-KV engine "
                         "(tiny page budget + host-RAM tier) and print "
                         "the spill/page-in headline")
    ap.add_argument("--router", action="store_true",
                    help="also run a two-replica router with hedging "
                         "and a seeded mid-run replica kill and print "
                         "the multi-replica headline")
    ap.add_argument("--http", action="store_true",
                    help="also serve a tiny engine over a live HTTP "
                         "frontend (streaming clients + one mid-stream "
                         "hangup) and print the ingress headline")
    ap.add_argument("--fleet", action="store_true",
                    help="also run a REAL prefill+decode worker-"
                         "subprocess fleet, scrape and aggregate "
                         "/metrics across the worker ports, and print "
                         "the fleet headline")
    ap.add_argument("--serve", type=int, default=None, metavar="PORT",
                    help="start the live introspection server (0 = any "
                         "free port)")
    ap.add_argument("--linger", type=float, default=0.0,
                    help="with --serve: keep the process alive this many "
                         "seconds after the workloads finish")
    args = ap.parse_args()

    from mxnet_tpu import telemetry

    srv = None
    if args.serve is not None:
        srv = telemetry.serve(args.serve)
        print(f"# introspection server: {srv.url} "
              "(/metrics /statusz /requests /trace /healthz)")
    if args.spans:
        telemetry.enable_jsonl(args.spans)
    eng = spec = shed_eng = router = tenant_eng = frontend = None
    kv_eng = slo_eng = fleet_router = fleet_agg = None
    with telemetry.span("dump_telemetry.workloads"):
        if args.workload in ("serving", "both"):
            eng, spec = run_serving()
        if args.shed:
            shed_eng = run_shedding()
        if args.slo:
            slo_eng = run_slo()
        if args.tenants:
            tenant_eng = run_tenants()
        if args.kv:
            kv_eng = run_kv()
        if args.router:
            router = run_router()
        if args.http:
            frontend = run_http()
        if args.fleet:
            fleet_router, fleet_agg = run_fleet()
        if args.workload in ("training", "both"):
            run_training()
    telemetry.memory.sample()

    if args.format == "prometheus":
        print(telemetry.render_prometheus())
    else:
        print(json.dumps(telemetry.snapshot(), indent=1, sort_keys=True))
    if eng is not None:
        # the prefix-cache headline, precomputed (the raw counters are
        # all in the snapshot above): hit-rate and page sharing
        s = eng.stats
        lookups = s["prefix_hits"] + s["prefix_misses"]
        rate = s["prefix_hits"] / lookups if lookups else 0.0
        print(f"# prefix-cache: hit-rate {rate:.2%} "
              f"({s['prefix_hits']}/{lookups}), "
              f"tokens saved {s['prefix_tokens_saved']}, "
              f"pages cached {s['prefix_cache_pages']}, "
              f"pages shared {s['prefix_pages_shared']}, "
              f"evicted {s['prefix_evicted_pages']}, "
              f"pool free {s['pool_free_pages']}")
    if spec is not None:
        # the speculative-decoding headline: acceptance rate is the
        # quantity that decides whether speculation pays
        s = spec.stats
        drafted = s["spec_draft_tokens"]
        rate = s["spec_accepted_tokens"] / drafted if drafted else 0.0
        per_disp = s["tokens_emitted"] / max(s["decode_dispatches"], 1)
        print(f"# speculative: acceptance {rate:.2%} "
              f"({s['spec_accepted_tokens']}/{drafted}), "
              f"rollbacks {s['spec_rollbacks']}, "
              f"{per_disp:.2f} tokens/dispatch")
    if shed_eng is not None:
        # the load-shedding headline: what /statusz "robustness" and
        # serving_shed_total{reason,priority} would show for the burst
        rb = shed_eng._statusz()["robustness"]
        s = shed_eng.stats
        by = ", ".join(f"{k}:{v}" for k, v in sorted(rb["shed"].items()))
        print(f"# shed: {s['shed']} total ({by or 'none'}), "
              f"rejected {s['requests_rejected']}, "
              f"finished {s['requests_finished']}, "
              f"overload level {rb['overload_level']}, "
              f"degraded {'yes' if rb['degraded'] else 'no'}, "
              f"downgrades {rb['policy']['downgrades']}")
    if slo_eng is not None:
        # the SLO headline: what /sloz would show — per-objective
        # fast/slow burn rates over their windows, and which
        # objectives are currently burning fast enough to page
        snap = telemetry.slo.snapshot()
        rows = ", ".join(
            f"{r['objective']}[fast {r['fast']['burn_rate']:.1f}x "
            f"({r['fast']['bad']}/{r['fast']['events']} bad), "
            f"slow {r['slow']['burn_rate']:.1f}x]"
            for r in snap["series"])
        burning = ", ".join(snap["fast_burning"]) or "none"
        print(f"# slo: {rows or 'no objectives'}; "
              f"fast-burning: {burning}")
    if tenant_eng is not None:
        # the multi-tenant headline: per-tenant fairness outcomes plus
        # how hard the adapter slab is paging
        s = tenant_eng.stats
        pool = tenant_eng.adapter_pool
        per = ", ".join(
            f"{t}[admitted {v.get('admitted', 0)}, "
            f"shed {sum(v.get('shed', {}).values())}, "
            f"active {v.get('active', 0)}]"
            for t, v in sorted(tenant_eng.tenant_stats().items()))
        page_rate = pool.page_ins / max(s["prefills"], 1)
        print(f"# tenants: {per or 'none'}")
        print(f"# adapters: resident {pool.num_resident}/"
              f"{pool.slots - 1} slots, registered "
              f"{pool.num_registered}, page-ins {pool.page_ins} "
              f"({page_rate:.2f}/prefill), evictions {pool.evictions}, "
              f"slab {pool.slab_bytes() / 1024:.1f} KiB")
    if kv_eng is not None:
        # the tiered-KV headline: how much re-prefill the host tier is
        # absorbing, and both tiers' occupancy right now
        s = kv_eng.stats
        hp = kv_eng.host_pool
        lookups = s["prefix_hits"] + s["prefix_misses"]
        rate = s["prefix_hits"] / lookups if lookups else 0.0
        print(f"# kv-tier: spilled {s['kv_spill_pages']} pages "
              f"({s['kv_spill_bytes'] / 1024:.1f} KiB), paged in "
              f"{s['kv_pagein_pages']} ({s['kv_pagein_bytes'] / 1024:.1f}"
              f" KiB), host {hp.num_entries} entries "
              f"{hp.bytes_used / 1024:.1f}/{hp.budget_bytes / 1024:.1f} "
              f"KiB (evictions {s['kv_host_evictions']}), resident "
              f"{s['prefix_resident_pages']} / spilled "
              f"{s['prefix_spilled_pages']} tree pages, hit-rate "
              f"{rate:.2%}, preempts {s['preempts']} "
              f"(resumed {s['preempt_resumed']}, restarted "
              f"{s['preempt_restarted']})")
    if router is not None:
        # the multi-replica headline: placement quality, failover and
        # hedging outcomes, and where each replica stands right now
        s = router.stats
        st = router._statusz()
        occ = ", ".join(
            f"engine{r['engine']}[{r['state']}"
            + (f":{r['down_reason']}" if r["down_reason"] else "")
            + f"] q{r['queued']}/a{r['active']}"
            for r in st["replicas"])
        downs = ", ".join(f"{k}:{v}" for k, v in
                          sorted(s["replica_down"].items()))
        print(f"# router: {s['requests']} routed "
              f"(affinity {s['affinity']}, spill {s['spill']}), "
              f"migrated {s['migrated']}, hedges {s['hedges']} "
              f"(won {s['hedges_won']}, wasted {s['hedges_wasted']}), "
              f"replica-down {{{downs or 'none'}}}, "
              f"ready {s['replicas_ready']}/{s['replicas']} — {occ}")
    if frontend is not None:
        # the HTTP-ingress headline: the status-code ledger plus the
        # robustness counters (disconnect->cancel, overflow-cancel)
        s = frontend.stats
        codes = ", ".join(f"{k}:{v}"
                          for k, v in sorted(s["requests_by_code"].items()))
        ttfb = telemetry.get("http_ttfb_seconds").labels(frontend._fid)
        tail = (f"ttfb p99 {ttfb.percentile(99) * 1e3:.1f} ms"
                if ttfb.count else "no TTFB samples")
        print(f"# http: {{{codes or 'none'}}} by code, "
              f"disconnects {s['disconnects']} "
              f"(cancels issued {s['cancels_issued']}, "
              f"noop {s['cancels_noop']}), "
              f"overflows {s['stream_overflows']}, {tail}")
    if fleet_agg is not None:
        # the fleet headline: per-worker rows from the collector's
        # /fleetz payload + the router's own placement/handoff
        # instruments (fleet_* in the snapshot above — worker-side
        # counters only exist in their processes, hence the collector
        # scrape/merge)
        fz = fleet_agg["fleetz"]
        for w in fz["workers"]:
            print(f"# fleet worker {w['worker_id']} ({w['role']}) "
                  f"{w['url']}: {w['state']}, "
                  f"handoffs {w['handoffs']}, "
                  f"steady compiles {w['steady_state_compiles']}")
        ho = telemetry.get("fleet_handoff_seconds")
        rid = fleet_router._rid
        hs = ho.labels(rid) if ho is not None else None
        tail = (f"handoff p50 {hs.percentile(50) * 1e3:.1f} ms"
                if hs is not None and hs.count else "no handoff samples")
        print(f"# fleet: {len(fz['workers'])} workers scraped "
              f"({fz['fleet']['workers_stale']} stale), "
              f"{fleet_agg['families']} metric families merged "
              f"(e.g. serving_tokens_emitted_total "
              f"{fleet_agg['tokens']:.0f} across the fleet), {tail}")
    if args.cost:
        # the /compilez + /memz headline, human-shaped: where every
        # dispatched program sits on the roofline and where HBM went
        rep = telemetry.cost.report()
        print(f"# device-cost: {rep['device_kind']} — peak "
              f"{rep['peak_flops'] / 1e12:.1f} TFLOP/s, "
              f"{rep['peak_bandwidth_bytes_per_sec'] / 1e9:.0f} GB/s, "
              f"ridge {rep['ridge_intensity']:.1f} flop/byte")
        for prog, s in rep["programs"].items():
            ai = s.get("arithmetic_intensity")
            mfu = s.get("mfu")
            avg = (s["dispatch_seconds"] / s["dispatches"] * 1e3
                   if s["dispatches"] else 0.0)
            # registered flops are whole-model; a tp=N program's
            # per-chip share is the number that sits on one chip's
            # roofline (the MFU figure already divides by shards)
            sh = s.get("shards") or 1
            print(f"#   {prog}: "
                  + (f"{s['flops'] / 1e6:.2f} MFLOP"
                     + (f" ({s['flops'] / sh / 1e6:.2f}/chip × {sh})"
                        if sh > 1 else "")
                     + ", " if s["flops"]
                     else "flops n/a, ")
                  + (f"AI {ai:.1f} ({s.get('bound', '?')}-bound), "
                     if ai else "")
                  + (f"MFU {mfu:.2%}, " if mfu is not None else "")
                  + f"compiles {s['compiles']} "
                  f"({s['compile_seconds']:.2f}s), "
                  f"dispatches {s['dispatches']} (avg {avg:.2f} ms)")
        if eng is not None:
            # the capacity headline quantized pages move: HBM per
            # generated token, next to the ledger that accounts it
            s = eng.stats
            print(f"# kv cost: {s['kv_bytes_per_token']:.1f} "
                  f"bytes/token "
                  f"({s['kv_page_bytes']} B/page, "
                  f"kv_dtype {'int8' if s['kv_quant_enabled'] else 'fp'}"
                  f", quant {'on' if s['kv_quant_enabled'] else 'off'})")
            if s.get("tp_shards", 1) > 1:
                tp = s["tp_shards"]
                print(f"# per-chip: {tp} tp shards — each chip holds "
                      f"{s['kv_page_bytes'] // tp} B/page and did 1/{tp} "
                      "of the FLOPs above; tokens/sec/chip divides "
                      "goodput by the shard count (docs/SERVING.md "
                      '"Tensor-parallel serving")')
            # the other capacity headline: the served weight slab (w8
            # moves it ~4x) and what each decode step reads per chip
            per_tok = (s["weight_bytes_per_chip"]
                       / max(eng.num_slots, 1))
            print(f"# weight cost: "
                  f"{s['weight_bytes_total'] / 1e6:.2f} MB served "
                  f"(int8 {s['weight_bytes_int8'] / 1e6:.2f} MB + "
                  f"fp32 {s['weight_bytes_float32'] / 1e6:.2f} MB), "
                  f"w8 {'on' if s['weight_quant_enabled'] else 'off'}, "
                  f"{s['weight_bytes_per_chip'] / 1e6:.2f} MB/chip "
                  f"weight reads per dispatch "
                  f"(~{per_tok / 1e3:.1f} KB/token at full batch)")
            if s["weight_quant_enabled"]:
                slab_fp = sum(int(q.codes.size) * 4
                              for q in eng._w8_plan)
                slab_w8 = sum(int(q.codes.size) + int(q.scale.size) * 4
                              for q in eng._w8_plan)
                print(f"#   w8 slab: {slab_w8 / 1e6:.2f} MB codes+scales"
                      f" vs {slab_fp / 1e6:.2f} MB fp32 "
                      f"({slab_fp / slab_w8:.1f}x smaller)")
        led = telemetry.ledger.snapshot()
        live = led.get("live_array_bytes")
        unattr = led.get("unattributed_bytes")
        print(f"# hbm ledger: accounted "
              f"{led['accounted_bytes'] / 1e6:.2f} MB"
              + (f" | live {live / 1e6:.2f} MB" if live is not None
                 else "")
              + (f" | unattributed {unattr / 1e6:.2f} MB "
                 f"({led.get('unattributed_fraction', 0):.1%})"
                 if unattr is not None else "")
              + (f" | headroom {led['headroom_bytes'] / 1e6:.0f} MB"
                 if led.get("headroom_bytes") is not None else ""))
        for name, cats in led["components"].items():
            parts = ", ".join(
                f"{c} {v['bytes'] / 1e6:.2f} MB"
                + (" (detail)" if v.get("detail") else "")
                for c, v in cats.items() if isinstance(v, dict)
                and "bytes" in v)
            print(f"#   {name}: {parts}")
    # request-timeline headline: what /requests would show for this run
    timelines = telemetry.request_log.recent(8)
    if timelines:
        print(f"# request timelines: {len(telemetry.request_log.recent(10**6))}"
              " recorded; most recent:")
        for tr in timelines[-4:]:
            evs = ",".join(e["event"] for e in tr["events"]
                           if e["event"] != "phase")
            ph = tr.get("phases") or {}
            extra = "" if not ph else " | " + " ".join(
                f"{k}={v * 1e3:.1f}ms" for k, v in ph.items())
            print(f"#   req {tr['request_id']} [{tr['status']}] "
                  f"{evs}{extra}")
    if args.trace:
        with open(args.trace, "w") as f:
            json.dump(telemetry.chrome_trace(), f)
        print(f"# chrome trace -> {args.trace} "
              "(open in ui.perfetto.dev)")
    if args.out:
        telemetry.dump(args.out)
    if args.spans:
        telemetry.disable_jsonl()
    if srv is not None and args.linger > 0:
        import time
        print(f"# lingering {args.linger}s — curl {srv.url}/statusz")
        time.sleep(args.linger)
    if srv is not None:
        telemetry.stop_server()
    return 0


if __name__ == "__main__":
    sys.exit(main())
