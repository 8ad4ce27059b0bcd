"""Multi-replica router tests (tier-1, ISSUE 9).

Covers: radix-prefix affinity-hash determinism + load-aware spill,
per-component readiness (/readyz — a draining replica is not ready but
the process stays healthy), the ServingEngine drain/undrain/adopt/
export seams, router drain/rejoin rolling restarts, hedged dispatch
(winner cancels loser, both directions), replica-kill mid-decode with
bit-identity of migrated outputs vs an unfaulted run, the aggregated
min retry-after with no router/replica shed double-count, and a
Poisson chaos soak (100+ requests, seeded replica kill + hang +
poison) losing zero accepted requests with clean page audits.
"""
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import (FaultPlan, QueueFullError, ReplicaFaultPlan,
                               Request, ServingEngine, ServingRouter,
                               ShedError)
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry import server as tserver

_NET = {}


def _tiny():
    # one shared tiny model: every replica (and the baseline engine)
    # must see identical weights for bit-identity assertions, and
    # reusing it keeps each test from recompiling
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


def _reqs(n=8, max_new=6, prompt_seed=7, seed_base=100):
    """Deterministic sampled workload: two calls yield equal
    (prompt, seed) pairs without sharing mutable Request objects."""
    rng = np.random.default_rng(prompt_seed)
    out = []
    for i in range(n):
        prompt = rng.integers(1, 97, size=int(rng.integers(3, 9)))
        out.append(Request(prompt, max_new, request_id=f"r{i}",
                           do_sample=True, temperature=0.9,
                           seed=seed_base + i))
    return out


def _outputs(done):
    return {r.id: list(r.output_tokens) for r in done
            if r.status == "finished"}


def _drive(router, steps=20000):
    n = 0
    done = []
    while router.has_work and n < steps:
        done.extend(router.step())
        n += 1
    assert n < steps, "router did not converge"
    return done


# ---------------------------------------------------------------------------
# placement: affinity determinism + load-aware spill
# ---------------------------------------------------------------------------

def test_affinity_hash_deterministic_and_spills_under_load():
    engines = [_engine() for _ in range(3)]
    router = ServingRouter(engines)
    cands = list(range(3))

    # same prompt prefix -> same replica, every time; the hash reads
    # only the first page of tokens
    page = list(np.random.default_rng(5).integers(1, 97, size=8))
    a = router._affinity_idx(Request(page + [3, 4], 4, request_id="a"),
                             cands)
    for tail in ([], [50], [60, 61, 62]):
        r = Request(page + tail, 4, request_id=f"t{len(tail)}")
        assert router._affinity_idx(r, cands) == a

    # distinct prefixes spread over the fleet
    rng = np.random.default_rng(11)
    targets = {router._affinity_idx(
        Request(rng.integers(1, 97, size=10), 4, request_id=f"p{i}"),
        cands) for i in range(32)}
    assert len(targets) >= 2

    # a replica leaving the candidate set only moves its own keys
    keep = [i for i in cands if i != (a + 1) % 3]
    assert router._affinity_idx(Request(page, 4, request_id="x"),
                                keep) == a

    # spill: pile the affinity replica's queue past its num_slots and
    # the next same-prefix submit lands elsewhere
    for i in range(2):
        router.submit(Request(page + [i], 6, request_id=f"q{i}"))
    assert all(router._owner[f"q{i}"][0] == a for i in range(2))
    spilled = router.submit(Request(page + [9], 6, request_id="spill"))
    sidx = router._owner["spill"][0]
    assert sidx != a
    assert router.stats["spill"] >= 1
    done = _drive(router)
    assert all(r.status == "finished" for r in done)
    assert spilled in done
    for eng in engines:
        assert eng.audit_pages() == []


# ---------------------------------------------------------------------------
# satellites: engine drain + per-component readiness
# ---------------------------------------------------------------------------

def test_engine_drain_rejects_finishes_clean_and_undrains():
    eng = _engine()
    reqs = _reqs(4)
    want = _outputs(_engine().serve(_reqs(4)))
    for r in reqs:
        eng.submit(r)
    for _ in range(2):
        eng.step()
    eng.drain()
    assert eng.draining and eng.stats["draining"]
    assert eng._statusz()["robustness"]["draining"]
    with pytest.raises(ShedError) as ei:
        eng.submit(Request([1, 2, 3], 4, request_id="late"))
    assert ei.value.reason == "draining"
    assert hasattr(ei.value, "retry_after_s")
    # queued + running work still completes, then the engine is empty
    done = list(reqs)
    n = 0
    while eng.has_work and n < 5000:
        eng.step()
        n += 1
    assert n < 5000
    assert eng.drained
    assert _outputs(done) == want
    assert eng.audit_pages() == []
    assert not eng.is_ready()
    eng.undrain()
    assert not eng.draining
    out = eng.serve([Request([1, 2, 3], 4, request_id="after")])
    assert out[0].status == "finished"


def test_readyz_per_component_draining_replica_stays_healthy():
    e0, e1 = _engine(), _engine()
    e0.serve(_reqs(2))          # compile before mark_warm
    e1.serve(_reqs(2))
    e0.mark_warm()
    e1.mark_warm()
    e1.drain()
    name0, name1 = f"engine{e0._eid}", f"engine{e1._eid}"
    assert tserver.component_ready(name0)
    assert not tserver.component_ready(name1)
    st = tserver.readiness()[name1]
    assert st["draining"] and st["warmed"] and not st["degraded"]

    srv = telemetry.IntrospectionServer(0)
    try:
        def get(path):
            try:
                with urllib.request.urlopen(srv.url + path,
                                            timeout=10) as r:
                    return r.status, r.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        # liveness unchanged: a draining replica is HEALTHY
        code, body = get("/healthz")
        assert code == 200 and body == b"ok\n"
        # fleet readiness: one ready replica keeps /readyz 200
        code, body = get("/readyz")
        assert code == 200 and b'"ready": true' in body
        code, body = get(f"/readyz?component={name1}")
        assert code == 503 and b'"ready": false' in body
        code, body = get(f"/readyz?component={name0}")
        assert code == 200
    finally:
        srv.stop()
    e1.undrain()
    assert tserver.component_ready(name1)


# ---------------------------------------------------------------------------
# router drain / rejoin (rolling restart)
# ---------------------------------------------------------------------------

def test_router_drain_routes_around_and_rejoin_restores():
    engines = [_engine() for _ in range(2)]
    router = ServingRouter(engines)
    router.drain(0)
    assert router.stats["drains"] == 1
    assert router._routable() == [1]
    reqs = _reqs(5)
    for r in reqs:
        router.submit(r)
    assert all(router._owner[r.id][0] == 1 for r in reqs)
    done = _drive(router)
    assert _outputs(done) == _outputs(_engine().serve(_reqs(5)))
    assert engines[0].audit_pages() == engines[1].audit_pages() == []
    router.rejoin(0)
    assert set(router._routable()) == {0, 1}
    # and with migrate=True a mid-flight drain re-homes the backlog
    router2 = ServingRouter([_engine(), _engine()])
    for r in _reqs(5, prompt_seed=19):
        router2.submit(r)
    busy = max(range(2), key=lambda i: router2._load(i))
    router2.drain(busy, migrate=True)
    assert router2.replicas[busy].engine.scheduler.has_work is False
    done2 = _drive(router2)
    assert _outputs(done2) == _outputs(
        _engine().serve(_reqs(5, prompt_seed=19)))
    assert router2.stats["migrated"] >= 1


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_hedge_winner_cancels_loser_both_directions():
    # direction 1: the primary replica wedges -> the hedge WINS
    engines = [_engine() for _ in range(2)]
    router = ServingRouter(engines, hedge_after_s=0.0,
                           watchdog_ticks=10 ** 6)
    req = _reqs(1)[0]
    want = _outputs(_engine().serve(_reqs(1)))
    router.submit(req)
    primary = router._owner[req.id][0]
    plan = ReplicaFaultPlan(hang={1: primary}, hang_ticks=None)
    plan.install(router)
    done = _drive(router)
    plan.uninstall()
    assert [r.id for r in done] == [req.id]
    assert req.status == "finished"
    assert _outputs(done) == want
    s = router.stats
    assert s["hedges"] == 1 and s["hedges_won"] == 1
    assert s["hedges_wasted"] == 0
    # the loser (primary copy) was cancelled on its wedged-but-alive
    # replica: its pages came back
    assert engines[primary].stats["requests_cancelled"] == 1
    assert engines[primary].audit_pages() == []
    assert engines[1 - primary].audit_pages() == []

    # direction 2: nothing is wrong -> the primary wins, the hedge is
    # the cancelled (wasted) copy
    engines2 = [_engine() for _ in range(2)]
    router2 = ServingRouter(engines2, hedge_after_s=0.0,
                            watchdog_ticks=10 ** 6)
    req2 = _reqs(1, prompt_seed=23)[0]
    router2.submit(req2)
    done2 = _drive(router2)
    assert [r.id for r in done2] == [req2.id]
    assert req2.status == "finished"
    assert _outputs(done2) == _outputs(
        _engine().serve(_reqs(1, prompt_seed=23)))
    s2 = router2.stats
    assert s2["hedges"] == 1 and s2["hedges_wasted"] == 1
    assert s2["hedges_won"] == 0
    assert engines2[0].audit_pages() == engines2[1].audit_pages() == []


# ---------------------------------------------------------------------------
# failover: replica kill mid-decode, bit-identical migration
# ---------------------------------------------------------------------------

def test_replica_kill_mid_decode_migrates_bit_identical(tmp_path):
    want = _outputs(_engine(num_slots=4).serve(_reqs(10)))
    engines = [_engine(), _engine()]
    router = ServingRouter(engines)
    rec = flight.install(out_dir=str(tmp_path / "fd"), stall_timeout=1e9,
                         queue_full_threshold=10 ** 6)
    plan = ReplicaFaultPlan(kill={4: 0}).install(router)
    try:
        for r in _reqs(10):
            router.submit(r)
        done = _drive(router)
    finally:
        plan.uninstall()
        flight.uninstall()
    assert plan.counts["kill"] == 1
    assert router.replicas[0].state == "down"
    assert router.replicas[0].down_reason == "kill"
    # zero lost: every accepted request finished, outputs bit-identical
    # to the unfaulted run
    assert {r.status for r in done} == {"finished"}
    assert _outputs(done) == want
    assert router.stats["migrated"] >= 1
    assert router.stats["replica_down"] == {"kill": 1}
    # the survivor's page accounting is clean; so is the corpse's —
    # export released every lease host-side
    assert engines[1].audit_pages() == []
    assert engines[0].audit_pages() == []
    # exactly ONE flight dump latched for the kill
    reason = f"replica_down:engine{engines[0]._eid}"
    assert reason in rec.latched
    assert len(rec.dumps) == 1
    # a dead replica reads not-ready (its admission was closed)
    assert not tserver.component_ready(f"engine{engines[0]._eid}")
    # request-trace continuity: the migrated request's old timeline
    # ended "migrated" and a new one carries migrated_from
    recent = telemetry.request_log.recent(200)
    migrated = [t for t in recent if t.get("migrated_from")]
    assert migrated
    assert any(t["status"] == "migrated" for t in recent)


# ---------------------------------------------------------------------------
# aggregated retry-after, no shed double-count
# ---------------------------------------------------------------------------

def test_router_aggregated_retry_after_min_no_double_count():
    engines = [_engine(max_queue=2), _engine(max_queue=2)]
    router = ServingRouter(engines)
    # establish service-rate history so wait estimates are real
    for r in _reqs(4):
        router.submit(r)
    _drive(router)
    shed_before = [e.stats["shed"] for e in engines]

    # fill every replica's queue without stepping
    reqs = _reqs(12, prompt_seed=31)
    accepted = []
    for r in reqs:
        try:
            router.submit(r)
            accepted.append(r)
        except QueueFullError:
            break
    # both replicas now at bound (2 slots active + 2 queued each)
    overflow = Request([5, 6, 7], 4, request_id="over")
    with pytest.raises(QueueFullError) as ei:
        router.submit(overflow)
    err = ei.value
    assert err.reason == "queue_full"
    waits = [e.estimated_queue_wait() for e in engines]
    waits = [w for w in waits if w is not None]
    assert waits, "no wait estimate despite service history"
    assert err.retry_after_s == pytest.approx(min(waits))
    # the router-level rejection counted ONLY router_shed_total:
    # pre-screening means no replica counted a shed for it
    assert [e.stats["shed"] for e in engines] == shed_before
    assert router.stats["shed"].get("queue_full", 0) >= 1
    done = _drive(router)
    assert all(r.status == "finished" for r in done)
    assert engines[0].audit_pages() == engines[1].audit_pages() == []

    # no routable replica at all -> structured shed, not a crash
    router.drain(0)
    router.drain(1)
    with pytest.raises(ShedError) as ei2:
        router.submit(Request([1, 2], 2, request_id="noone"))
    assert ei2.value.reason == "no_ready_replica"
    assert hasattr(ei2.value, "retry_after_s")


# ---------------------------------------------------------------------------
# chaos soak: Poisson arrivals, kill + hang + poison across the fleet
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_chaos_soak_kill_hang_poison_zero_loss(tmp_path):
    N = 104
    poison = {"c17": "both", "c61": "decode", "c88": "prefill"}

    def mk():
        rng = np.random.default_rng(11)
        reqs = []
        for i in range(N):
            prompt = rng.integers(1, 97, size=int(rng.integers(2, 10)))
            n_new = int(rng.integers(2, 7))
            if i == 61:
                # decode-phase poison still gains one token per
                # re-prefill cycle; a budget beyond max_retries makes
                # quarantine win over that slow progress
                n_new = 12
            reqs.append(Request(prompt, n_new, request_id=f"c{i}",
                                do_sample=True, temperature=0.8,
                                seed=1000 + i))
        return reqs

    want = _outputs(_engine(num_slots=4).serve(mk()))
    assert len(want) == N

    engines = [_engine(max_retries=6, retry_backoff_s=0.0)
               for _ in range(3)]
    # hedging off: a hedge clone's id is not in the poison map, so a
    # poisoned request could sneak out through its clone
    router = ServingRouter(engines, hedge_min_samples=10 ** 9,
                           watchdog_ticks=6)
    rec = flight.install(out_dir=str(tmp_path / "fd"), stall_timeout=1e9,
                         queue_full_threshold=10 ** 6)
    # replica-level chaos: kill replica 0 early, wedge replica 1 later
    # (the stall watchdog must detect and evacuate it); every replica
    # also poisons the same request ids wherever they land
    rplan = ReplicaFaultPlan(kill={20: 0}, hang={45: 1},
                             hang_ticks=None).install(router)
    eplans = [FaultPlan(poison=dict(poison)).install(e) for e in engines]
    arrivals = np.random.default_rng(13)
    pending = mk()[::-1]
    done, shed, steps = [], [], 0
    try:
        while (pending or router.has_work) and steps < 20000:
            for _ in range(int(arrivals.poisson(2.0))):
                if pending:
                    r = pending.pop()
                    try:
                        router.submit(r)
                    except (QueueFullError, ShedError):
                        shed.append(r)
            done.extend(router.step())
            steps += 1
    finally:
        rplan.uninstall()
        for p in eplans:
            p.uninstall()
        flight.uninstall()
    assert steps < 20000, "chaos soak did not converge"
    assert rplan.counts["kill"] == 1 and rplan.counts["hang"] >= 1
    assert router.stats["replica_down"] == {"kill": 1, "stall": 1}
    assert router.stats["migrated"] >= 1

    # ZERO accepted requests lost: everything not shed at submit and
    # not quarantined finished bit-identical to the fault-free run —
    # only poisoned ids may quarantine
    got = _outputs(done)
    shed_ids = {r.id for r in shed}
    for r in shed:    # structured sheds carry a retry hint
        assert r.status == "shed"
    failed_ids = {r.id for r in done if r.status == "failed"}
    assert failed_ids <= set(poison)
    expect = {k: v for k, v in want.items()
              if k not in failed_ids and k not in shed_ids}
    assert got == expect
    assert len(got) + len(shed_ids) + len(failed_ids) == N

    # every replica's page accounting is clean — survivors by
    # invariant, corpses because export released their leases
    for eng in engines:
        assert eng.audit_pages() == []
    # each replica failure latched exactly one flight dump (poison
    # dispatch errors latch their own reasons; filter to ours)
    down = [r for r in rec.latched if r.startswith("replica_down:")]
    assert sorted(down) == sorted(
        [f"replica_down:engine{engines[0]._eid}",
         f"replica_down:engine{engines[1]._eid}"])
