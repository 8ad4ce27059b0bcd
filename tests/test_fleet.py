"""Cross-process serving fleet: the versioned wire format, the
export_handoff -> adopt KV-payload contract, FleetWorker's control
plane over real HTTP, FleetRouter placement/failover, and
disaggregated prefill/decode (docs/SERVING.md "Cross-process fleet &
disaggregated prefill/decode").

The bar everywhere is the migration contract from the in-process
router: a request that moves — over the wire, across a SIGKILL, or
through a prefill->decode handoff — finishes with tokens bit-identical
to an uninterrupted run, as ONE stitched trace. Subprocess launchers
live in the slow lane; the fast lane covers the wire format and the
in-process HTTP fleet.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import Request, ServingEngine, TokenStream
from mxnet_tpu.serving.fleet import (
    FleetRouter, FleetWorker, WorkerClient, WorkerGone, WorkerRejected,
    spawn_fleet, spawn_worker, warm_engine, wire)

_CONFIG = dict(vocab_size=97, units=32, num_layers=2, num_heads=2,
               max_length=64, dropout=0.0, attention_dropout=0.0)
_ENGINE = dict(num_slots=2, max_length=32, page_size=8, attn_impl="xla")
_SPEC = {"config": _CONFIG, "seed": 3, "init_std": 0.05,
         "engine": _ENGINE}

_net_cache = {}


def _tiny():
    if "net" not in _net_cache:
        cfg = GPT2Config(**_CONFIG)
        mx.rng.seed(3)
        net = GPT2ForCausalLM(cfg)
        net.initialize(mx.init.Normal(0.05))
        _net_cache["net"] = (net, cfg)
    return _net_cache["net"]


def _engine(**kw):
    net, _ = _tiny()
    return ServingEngine(net, **dict(_ENGINE, **kw))


def _mk(prompt, n_new=6, **kw):
    kw.setdefault("request_id", "r")
    return Request(list(prompt), n_new, **kw)


# ---------------------------------------------------------------------------
# wire format: byte-for-byte round trip, every payload variant
# ---------------------------------------------------------------------------

def _variants():
    p = list(range(5, 14))
    yield "plain", _mk(p[:4], request_id="v0")
    r = _mk(p, 8, request_id="v1", do_sample=True, temperature=0.7,
            top_k=11, top_p=0.9, seed=42, eos_token_id=3, priority=0,
            deadline_ms=1500.0, adapter_id="ad1", tenant="t9")
    r.output_tokens = [7, 8, 9]
    r.kv_history = [8, 4]
    r.phases = {"queue_wait": 0.001, "prefill_chunks": 0.02}
    r.trace = {"trace_id": "ab" * 16, "t_begin": 12.5}
    yield "loaded", r


@pytest.mark.parametrize("name,req",
                         list(_variants()),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_wire_round_trip_byte_identical(name, req):
    d1 = wire.encode_request(req)
    b1 = wire.dumps(d1)
    req2 = wire.decode_request(wire.loads(b1))
    d2 = wire.encode_request(req2)
    assert d1 == d2
    assert wire.dumps(d2) == b1          # canonical bytes, not just ==
    assert [int(t) for t in req2.prompt] == [int(t) for t in req.prompt]
    assert req2.output_tokens == list(req.output_tokens)
    assert req2.kv_history == list(req.kv_history or [])
    assert req2.seed == req.seed and req2.do_sample == req.do_sample
    assert req2.token_times == []        # engine-local, re-created


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_wire_payload_round_trip_and_adopt_bit_identical(kv_dtype):
    """export_handoff blob -> bytes -> decode -> adopt on a second
    engine: ndarray pages byte-equal through base64, and the adopted
    request finishes bit-identical to an uninterrupted serve."""
    kw = dict(kv_dtype=kv_dtype) if kv_dtype else {}
    eng = _engine(**kw)
    r = _mk([5, 6, 7, 8, 9], request_id="w1", do_sample=True, seed=1)
    eng.submit(r)
    for _ in range(50):
        eng.step()
        if r.output_tokens:
            break
    e = eng.export_handoff(r.id)
    assert e is not None and e.kv_payload is not None
    d1 = wire.encode_request(e)
    b1 = wire.dumps(d1)
    req2 = wire.decode_request(wire.loads(b1))
    assert wire.dumps(wire.encode_request(req2)) == b1
    for pa, pb in zip(e.kv_payload["pages"], req2.kv_payload["pages"]):
        assert set(pa) == set(pb)
        for k in pa:
            assert np.asarray(pa[k]).tobytes() == pb[k].tobytes(), k
            assert np.asarray(pa[k]).dtype == pb[k].dtype, k

    ref_eng = _engine(**kw)
    ref = _mk([5, 6, 7, 8, 9], request_id="ref", do_sample=True, seed=1)
    ref_eng.serve([ref])
    B = _engine(**kw)
    B.adopt(req2, migrated_from="wire")
    while B.has_work:
        B.step()
    assert req2.status == "finished"
    assert req2.output_tokens == list(ref.output_tokens)


def test_wire_version_mismatch_rejects_structurally():
    d = wire.encode_request(_mk([1, 2, 3]))
    bad = dict(d, wire_version=99)
    with pytest.raises(wire.WireVersionError) as ei:
        wire.check_version(bad)
    assert ei.value.got == 99 and ei.value.want == wire.WIRE_VERSION
    with pytest.raises(wire.WireVersionError):
        wire.loads(wire.dumps(bad))
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        wire.loads(b"{not json")


# ---------------------------------------------------------------------------
# in-process HTTP fleet: mixed routing + disaggregated prefill/decode
# ---------------------------------------------------------------------------

def _reference(prompts, n_new, **kw):
    eng = _engine(**kw)
    reqs = [_mk(p, n_new, request_id=f"ref{i}", seed=i,
                do_sample=bool(i % 2)) for i, p in enumerate(prompts)]
    eng.serve(reqs)
    return {i: list(r.output_tokens) for i, r in enumerate(reqs)}


def _worker(role, warm=True, **kw):
    net, cfg = _tiny()
    eng = ServingEngine(net, **dict(_ENGINE, **kw))
    if warm:
        warm_engine(eng, cfg)
    return FleetWorker(eng, role=role, worker_id=f"{role}-t")


def _run(router, prompts, n_new, tag):
    reqs = [_mk(p, n_new, request_id=f"{tag}{i}", seed=i,
                do_sample=bool(i % 2)) for i, p in enumerate(prompts)]
    for r in reqs:
        r.stream = TokenStream(capacity=64)
        router.submit(r)
    for r in reqs:
        router.result(r, timeout=120)
    return reqs


def test_fleet_http_mixed_and_disagg_bit_identical():
    """The core fleet contract over real HTTP, fp32: a two-worker
    mixed fleet and a prefill+decode disaggregated fleet both finish
    every request bit-identical to a single uninterrupted engine; the
    disaggregated run records a "handoff" phase on every request and
    compiles nothing after warmup (int8 runs in the slow lane)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 11, 3)]
    ref = _reference(prompts, 8)

    w1, w2 = _worker("mixed"), _worker("mixed")
    router = FleetRouter([w1.url, w2.url])
    try:
        assert not router.disaggregated
        for i, r in enumerate(_run(router, prompts, 8, "m")):
            assert r.status == "finished", (r.id, r.status)
            assert list(r.output_tokens) == ref[i], r.id
            assert r.stream.emitted == len(ref[i])
    finally:
        router.close()
        w1.close(), w2.close()

    wp, wd = _worker("prefill"), _worker("decode")
    drouter = FleetRouter([wp.url, wd.url])
    try:
        assert drouter.disaggregated
        dreqs = _run(drouter, prompts, 8, "d")
        for i, r in enumerate(dreqs):
            assert r.status == "finished", (r.id, r.status, r.phases)
            assert list(r.output_tokens) == ref[i], r.id
            assert "handoff" in r.phases and r.phases["handoff"] >= 0
        sp = WorkerClient(wp.url).stats()
        sd = WorkerClient(wd.url).stats()
        assert sp["role"] == "prefill" and sd["role"] == "decode"
        assert sp["handoffs"] == len(dreqs)
        assert sp["stats"]["steady_state_compiles"] == 0
        assert sd["stats"]["steady_state_compiles"] == 0
        # a mismatched blob is refused structurally, not adopted
        blob = wire.encode_request(_mk(prompts[0], request_id="v"))
        blob["wire_version"] = 99
        with pytest.raises(WorkerRejected) as ei:
            WorkerClient(wd.url).adopt(blob)
        assert ei.value.code == 409
        assert ei.value.reason == "wire_version_mismatch"
        assert sd["wire_version_rejects"] == 0   # counted after this
    finally:
        drouter.close()
        wp.close(), wd.close()


def test_spawn_worker_refuses_a_platform_it_cannot_give_each_child():
    """A chip belongs to one process; the launcher hands out the CPU
    explicitly and says so instead of letting a TPU child hang."""
    with pytest.raises(mx.MXNetError, match="chip of its own"):
        spawn_worker(_SPEC, env={"JAX_PLATFORMS": "tpu"})


def test_fleet_worker_control_plane_drain_and_stats():
    """/fleet/drain flips admission off (503 with a structured body),
    /fleet/undrain restores it, and /fleet/stats reports the engine
    geometry the router validates at init."""
    w = _worker("mixed", warm=False)
    c = WorkerClient(w.url)
    try:
        s = c.stats()
        assert s["wire_version"] == wire.WIRE_VERSION
        assert s["engine"]["chunk_tokens"] >= 1
        assert s["engine"]["page_size"] == _ENGINE["page_size"]
        c.drain()
        assert c.stats()["draining"]
        with pytest.raises(WorkerRejected) as ei:
            list(c.generate({"prompt": [1, 2, 3],
                             "max_new_tokens": 2}))
        assert ei.value.code == 503
        c.undrain()
        assert not c.stats()["draining"]
        ev = list(c.generate({"prompt": [1, 2, 3], "max_new_tokens": 2,
                              "request_id": "ok"}))
        assert ev[-1][0] == "done"
    finally:
        w.close()


def test_fleet_router_rejects_mixed_wire_or_chunking():
    """FleetRouter refuses to build over workers whose prefill
    chunking disagrees — a synthesized replay plan from one worker
    would not be bit-identical on the other."""
    w1 = _worker("mixed", warm=False)
    w2 = _worker("mixed", warm=False, chunk_tokens=16)
    from mxnet_tpu.base import MXNetError
    try:
        assert w1.engine.chunk_tokens != w2.engine.chunk_tokens
        with pytest.raises(MXNetError):
            FleetRouter([w1.url, w2.url])
    finally:
        w1.close(), w2.close()


# ---------------------------------------------------------------------------
# subprocess fleet: SIGKILL failover (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_sigkill_mid_decode_bit_identical_int8():
    """Two REAL worker processes (int8 KV), one SIGKILLed mid-decode:
    every in-flight request finishes on the survivor bit-identical to
    an uninterrupted run — the router re-places with a synthesized
    natural-grid replay blob — and the survivor's timeline carries the
    ORIGINAL trace_id (one stitched trace, not two requests)."""
    spec = dict(_SPEC, engine=dict(_ENGINE, kv_dtype="int8"))
    ref = _reference([[3, 1, 4, 1, 5], list(range(11)), [9, 2, 6]],
                     10, kv_dtype="int8")
    prompts = [[3, 1, 4, 1, 5], list(range(11)), [9, 2, 6]]
    with spawn_fleet(spec, roles=("mixed", "mixed")) as procs:
        router = FleetRouter(procs.urls)
        reqs = [_mk(p, 10, request_id=f"k{i}", seed=i,
                    do_sample=bool(i % 2))
                for i, p in enumerate(prompts)]
        for r in reqs:
            r.stream = TokenStream(capacity=64)
            router.submit(r)
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(len(r.output_tokens) >= 2 for r in reqs):
                break
            time.sleep(0.02)
        assert all(len(r.output_tokens) >= 2 for r in reqs), \
            [(r.id, len(r.output_tokens)) for r in reqs]
        victim, survivor = procs.workers
        victim.kill()
        for r in reqs:
            router.result(r, timeout=120)
        for i, r in enumerate(reqs):
            assert r.status == "finished", (r.id, r.status)
            assert list(r.output_tokens) == ref[i], (
                r.id, r.output_tokens, ref[i])
        states = {w["url"]: w["state"]
                  for w in router.fleet_stats()["workers"]}
        assert states[victim.url] == "down"
        by_id = {e["request_id"]: e
                 for e in WorkerClient(survivor.url).requests()}
        stitched = [r.id for r in reqs if r.id in by_id
                    and by_id[r.id].get("trace_id")
                    == r.trace["trace_id"]]
        assert stitched, "no stitched trace on the survivor"
        router.close()


@pytest.mark.slow
def test_fleet_sigkill_mid_decode_bit_identical_w8():
    """The int8 SIGKILL story with int8 WEIGHTS on: w8 is pure
    construction-time data, so every worker process re-quantizes the
    same net to the same bytes — a migrated request finishes on the
    survivor bit-identical to an uninterrupted w8 run, and the worker
    advertises weight_dtype through its stats geometry."""
    spec = dict(_SPEC, engine=dict(_ENGINE, weight_dtype="int8"))
    prompts = [[3, 1, 4, 1, 5], list(range(11)), [9, 2, 6]]
    ref = _reference(prompts, 10, weight_dtype="int8")
    with spawn_fleet(spec, roles=("mixed", "mixed")) as procs:
        router = FleetRouter(procs.urls)
        assert all(WorkerClient(u).stats()["engine"]["weight_dtype"]
                   == "int8" for u in procs.urls)
        reqs = [_mk(p, 10, request_id=f"w{i}", seed=i,
                    do_sample=bool(i % 2))
                for i, p in enumerate(prompts)]
        for r in reqs:
            r.stream = TokenStream(capacity=64)
            router.submit(r)
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(len(r.output_tokens) >= 2 for r in reqs):
                break
            time.sleep(0.02)
        assert all(len(r.output_tokens) >= 2 for r in reqs), \
            [(r.id, len(r.output_tokens)) for r in reqs]
        victim, survivor = procs.workers
        victim.kill()
        for r in reqs:
            router.result(r, timeout=120)
        for i, r in enumerate(reqs):
            assert r.status == "finished", (r.id, r.status)
            assert list(r.output_tokens) == ref[i], (
                r.id, r.output_tokens, ref[i])
        states = {w["url"]: w["state"]
                  for w in router.fleet_stats()["workers"]}
        assert states[victim.url] == "down"
        router.close()


@pytest.mark.slow
def test_fleet_disagg_subprocess_with_and_without_payload():
    """Disaggregated prefill/decode across real processes: handoff
    WITH KV-page payload and the --no-ship-payload replay fallback
    both finish bit-identical to the mixed reference."""
    prompts = [[2, 7, 1, 8], list(range(9))]
    ref = _reference(prompts, 8)
    for ship in (True, False):
        with spawn_fleet(_SPEC, roles=("prefill", "decode"),
                         ship_payload=ship) as procs:
            router = FleetRouter(procs.urls)
            for i, r in enumerate(_run(router, prompts, 8, "d")):
                assert r.status == "finished", (ship, r.id, r.status)
                assert list(r.output_tokens) == ref[i], (ship, r.id)
                # the handoff TTFT phase exists only where a KV
                # payload was adopted — the replay fallback restarts
                # from kv_history and records no hop
                assert ("handoff" in r.phases) == ship, (ship, r.phases)
            crossed = sum(w["stats"]["handoffs"]
                          for w in router.fleet_stats()["workers"]
                          if w["role"] == "prefill")
            assert crossed == len(prompts), (ship, crossed)
            router.close()
