"""Overload-hardening tests (tier-1, ISSUE 8).

Covers: deadline enforcement (queued shed + running cancellation,
deterministic via an injectable clock), priority-class admission with
aging-based starvation-freedom and per-class bounded queues, structured
QueueFullError/ShedError rejections, the SLO-aware SheddingPolicy
(downgrade / overload shed / deadline-infeasibility shed / graceful
degradation latch+recovery), the page-pool invariant audit, the engine
supervisor (transient dispatch faults, NaN-logit guard, backpressure,
poison quarantine — non-poison outputs bit-identical to a fault-free
run), and a seeded chaos soak with Poisson arrivals over 100+ requests.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import (FaultPlan, PagePool, QueueFullError,
                               Request, ServingEngine, ShedError,
                               SheddingPolicy, SlotScheduler)
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry import server as tserver


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


def _engine(net=None, **kw):
    if net is None:
        net, _ = _tiny()
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(net, **kw)


class Tick:
    """Injectable engine clock — deadline/backoff tests advance time
    explicitly instead of racing wall time."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


def _reqs(n=6, max_new=6, prompt_seed=7, seed_base=100):
    """A deterministic sampled workload: calling twice yields equal
    (prompt, seed) pairs, so baseline and faulted runs see the same
    requests without sharing mutable Request objects."""
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


# ---------------------------------------------------------------------------
# page-pool invariant audit
# ---------------------------------------------------------------------------

def test_page_pool_audit_clean_leak_and_mismatch():
    pool = PagePool(8)
    pages = pool.alloc(3)
    assert pool.audit(leases=[pages]) == []
    # the same pages leased by nothing the caller can explain -> leak
    violations = pool.audit(leases=[])
    assert violations
    with pytest.raises(MXNetError):
        pool.audit(leases=[], raise_on_error=True)
    # refcount above the lease count is a mismatch too
    pool.incref(pages[:1])
    assert pool.audit(leases=[pages])
    pool.decref(pages[:1])
    # an idle zero-ref page is legal only as a prefix-tree member
    idle = pool.decref(pages[:1])
    assert idle == pages[:1]
    assert pool.audit(leases=[pages[1:]])
    assert pool.audit(leases=[pages[1:]], members=idle) == []


# ---------------------------------------------------------------------------
# priority classes: ordering, bounds, starvation-freedom
# ---------------------------------------------------------------------------

def test_priority_classes_admit_most_urgent_first():
    s = SlotScheduler(2, num_priorities=3)
    for r in (Request([1], 1, priority=2, request_id="bulk"),
              Request([1], 1, priority=1, request_id="norm"),
              Request([1], 1, priority=0, request_id="inter")):
        s.submit(r)
    admitted = [r.id for _, r in s.admit()]
    assert admitted == ["inter", "norm"]
    assert s.queued_ids == ["bulk"]


def test_per_class_bounds_reject_structured_and_isolate_classes():
    s = SlotScheduler(1, max_queue=[None, 1, 1])
    s.submit(Request([1], 1, priority=1))
    with pytest.raises(QueueFullError) as ei:
        s.submit(Request([1], 1, priority=1))
    e = ei.value
    assert e.reason == "queue_full"
    assert e.priority == 1
    assert e.queue_depth == 1
    assert e.active_slots == 0
    # a full bulk class never blocks the interactive class
    s.submit(Request([1], 1, priority=0))
    assert s.num_queued == 2


def test_aging_prevents_priority_starvation():
    s = SlotScheduler(1, aging_every=4)
    s.submit(Request([1], 1, priority=2, request_id="old"))
    admitted = []
    for i in range(8):
        s.submit(Request([1], 1, priority=0, request_id=f"hot{i}"))
        for slot, req in s.admit():
            admitted.append(req.id)
            s.release(slot)
        if "old" in admitted:
            break
    # under a steady high-priority stream the low-priority request is
    # still admitted within one aging period
    assert "old" in admitted
    assert len(admitted) <= s.aging_every


# ---------------------------------------------------------------------------
# structured rejections at the engine boundary
# ---------------------------------------------------------------------------

def test_engine_queue_full_rejection_carries_context():
    eng = _engine(num_slots=1, max_queue=1)
    eng.submit(Request([1, 2, 3], 2, request_id="seated"))
    with pytest.raises(QueueFullError) as ei:
        eng.submit(Request([4, 5, 6], 2, request_id="bounced"))
    e = ei.value
    assert e.queue_depth == 1 and e.active_slots == 0
    assert "queue_depth=1" in str(e) and "active_slots=0" in str(e)
    # the rejection is a terminal timeline with the same context
    tl = [t for t in telemetry.request_log.recent(50)
          if t["request_id"] == "bounced"][-1]
    assert tl["status"] == "rejected"
    assert tl["reason"] == "queue_full"
    assert tl["queue_depth"] == 1
    assert eng.stats["shed"] == 1
    eng.serve()


# ---------------------------------------------------------------------------
# deadlines (injectable clock -> deterministic)
# ---------------------------------------------------------------------------

def _run_deadline_schedule():
    clk = Tick()
    eng = _engine(num_slots=1, clock=clk)
    a = Request([1, 2, 3], 4, request_id="da")
    b = Request([4, 5, 6], 4, request_id="db", deadline_ms=50.0)
    eng.submit(a)
    eng.submit(b)
    done = list(eng.step())          # admits a; b queued behind it
    clk.advance(0.2)                 # 200ms > b's 50ms budget
    done += eng.step()
    while eng.has_work:
        done += eng.step()
    audit = eng.audit_pages()
    return {r.id: (r.status, list(r.output_tokens)) for r in done}, audit


def test_deadline_sheds_queued_request_before_admission():
    results, audit = _run_deadline_schedule()
    assert results["db"][0] == "shed"
    assert results["db"][1] == []          # never touched a slot
    assert results["da"][0] == "finished"
    assert audit == []
    # deterministic: the same schedule replays to the same shed set
    assert _run_deadline_schedule()[0] == results


def test_deadline_cancels_running_request_keeps_partial_output():
    clk = Tick()
    eng = _engine(num_slots=1, clock=clk)
    r = Request([1, 2, 3], 16, request_id="dr", deadline_ms=100.0)
    eng.submit(r)
    eng.step()
    assert r.status == "running"
    emitted = len(r.output_tokens)
    assert emitted >= 1
    clk.advance(1.0)
    done = eng.step()                # cancelled at the dispatch boundary
    assert [x.id for x in done] == ["dr"]
    assert r.status == "deadline"
    assert len(r.output_tokens) == emitted       # partial output kept
    assert not eng.has_work
    assert eng.audit_pages() == []
    assert eng.stats["shed"] == 1
    tl = [t for t in telemetry.request_log.recent(50)
          if t["request_id"] == "dr"][-1]
    assert tl["status"] == "finished"
    assert tl["events"][-1]["reason"] == "deadline"


# ---------------------------------------------------------------------------
# SLO-aware shedding policy
# ---------------------------------------------------------------------------

def test_policy_sheds_overload_but_protects_priority_floor():
    eng = _engine(num_slots=1,
                  policy=SheddingPolicy(queue_low=1, queue_high=2))
    eng.submit(Request([1, 2, 3], 2, priority=0))
    eng.submit(Request([1, 2, 3], 2, priority=0))
    with pytest.raises(ShedError) as ei:
        eng.submit(Request([1, 2, 3], 2, priority=1, request_id="bulk"))
    assert ei.value.reason == "overload"
    assert ei.value.queue_depth == 2
    # the protected class still queues at level 2
    eng.submit(Request([1, 2, 3], 2, priority=0))
    assert eng.scheduler.num_queued == 3
    assert eng.stats["shed"] == 1
    eng.serve()


def test_policy_downgrades_default_traffic_when_elevated():
    eng = _engine(num_slots=1,
                  policy=SheddingPolicy(queue_low=1, queue_high=10))
    eng.submit(Request([1, 2, 3], 2, priority=0))
    r = Request([1, 2, 3], 2, priority=1)
    eng.submit(r)                    # queue at the low watermark
    assert r.priority == 2
    assert eng.policy.downgrades == 1
    eng.serve()


def test_policy_sheds_infeasible_deadline_with_retry_after():
    clk = Tick(10.0)
    eng = _engine(num_slots=1, clock=clk,
                  policy=SheddingPolicy(queue_low=1, queue_high=4))
    eng._finish_times.extend([9.0, 10.0])      # 1 finish/s drain rate
    eng.submit(Request([1, 2, 3], 2, priority=0))
    eng.submit(Request([1, 2, 3], 2, priority=0))
    # ~2s estimated queue wait; a 500ms budget cannot make it
    with pytest.raises(ShedError) as ei:
        eng.submit(Request([1, 2, 3], 2, priority=0, deadline_ms=500.0,
                           request_id="late"))
    e = ei.value
    assert e.reason == "deadline"
    assert e.retry_after_s == pytest.approx(2.0)
    assert "retry_after~" in str(e)
    eng.serve()


def test_sustained_overload_degrades_then_recovers():
    eng = _engine(num_slots=1, speculative=True,
                  policy=SheddingPolicy(queue_low=1, queue_high=2,
                                        degrade_after=2, recover_after=2))
    name = f"engine{eng._eid}"
    reqs = [Request([1, 2, 3], 2, priority=0, request_id=f"g{i}")
            for i in range(6)]
    for r in reqs:
        eng.submit(r)
    degraded_seen = False
    steps = 0
    while eng.has_work and steps < 100:
        eng.step()
        steps += 1
        if eng._degraded:
            degraded_seen = True
            assert name in tserver.degraded_reasons()
            assert eng.stats["degraded"] == 1
    assert degraded_seen
    # the serving loop idles after the backlog drains; calm ticks clear
    # the latch and re-enable speculation
    for _ in range(4):
        eng.step()
    assert not eng._degraded
    assert name not in tserver.degraded_reasons()
    assert eng.stats["degraded"] == 0
    # degraded decoding fell back to the plain program: greedy outputs
    # are still exactly the full-recompute oracle's
    assert all(r.status == "finished" for r in reqs)
    outs = {tuple(r.output_tokens) for r in reqs}
    assert len(outs) == 1            # identical prompts, identical output
    assert eng.audit_pages() == []


def test_statusz_exposes_robustness_block():
    eng = _engine(policy=SheddingPolicy())
    st = eng._statusz()
    rb = st["robustness"]
    assert rb["degraded"] is False
    assert rb["overload_level"] == 0
    assert rb["policy"]["level"] == 0
    assert rb["shed"] == {}
    assert rb["quarantined"] == 0
    assert st["config"]["max_retries"] == eng.max_retries


# ---------------------------------------------------------------------------
# dispatch-hook seam
# ---------------------------------------------------------------------------

def test_dispatch_hook_phases_and_legacy_compat():
    eng = _engine(num_slots=1)
    phases = []

    def hook(engine, phase="step", requests=()):
        phases.append((phase, tuple(r.id for r in requests)))

    eng.dispatch_hook = hook
    eng.serve([Request([1, 2, 3], 3, request_id="h")])
    kinds = [p for p, _ in phases]
    assert ("prefill", ("h",)) in phases
    assert "decode" in kinds and "step" in kinds
    # a legacy hook (positional engine only) fires once per step
    legacy = []
    eng.dispatch_hook = lambda engine: legacy.append(1)
    eng.serve([Request([1, 2, 3], 3, request_id="h2")])
    assert len(legacy) == kinds.count("step")


# ---------------------------------------------------------------------------
# engine supervisor: transient faults, NaN guard, backpressure, poison
# ---------------------------------------------------------------------------

def test_supervisor_recovers_transient_faults_bit_identical():
    net, _ = _tiny()
    want = _outputs(_engine(net).serve(_reqs()))
    assert len(want) == 6
    eng = _engine(net, max_retries=8, retry_backoff_s=0.0)
    plan = FaultPlan(seed=1, dispatch_exception=0.3, max_faults=6)
    plan.install(eng)
    try:
        done = eng.serve(_reqs())
    finally:
        plan.uninstall()
    assert plan.counts["dispatch_exception"] >= 1
    assert all(r.status == "finished" for r in done)
    # rolled-back requests restarted with their RNG counter resumed:
    # sampled outputs are bit-identical to the fault-free run
    assert _outputs(done) == want
    assert eng.stats["dispatch_errors"] >= 1
    assert eng.stats["dispatch_retries"] >= 1
    assert eng.stats["requests_failed"] == 0
    assert eng.audit_pages() == []


def test_nan_logit_guard_discards_and_reprefills_bit_identical():
    net, _ = _tiny()
    want = _outputs(_engine(net).serve(_reqs()))
    eng = _engine(net, max_retries=8, retry_backoff_s=0.0)
    plan = FaultPlan(seed=2, nan_logits=0.25, max_faults=2)
    plan.install(eng)
    try:
        done = eng.serve(_reqs())
    finally:
        plan.uninstall()
    assert plan.counts["nan_logits"] >= 1
    assert _outputs(done) == want
    assert eng.stats["requests_failed"] == 0
    assert eng.audit_pages() == []


def test_backpressure_and_alloc_failures_never_blame_requests():
    net, _ = _tiny()
    want = _outputs(_engine(net, prefix_cache=True).serve(_reqs()))
    eng = _engine(net, prefix_cache=True, max_retries=3,
                  retry_backoff_s=0.0)
    plan = FaultPlan(seed=5, pool_exhaustion=0.4, exhaust_steps=2,
                     alloc_failure=0.4, max_faults=5)
    plan.install(eng)
    try:
        done = eng.serve(_reqs())
    finally:
        plan.uninstall()
    assert plan.counts["pool_exhaustion"] + plan.counts["alloc_failure"] >= 1
    assert _outputs(done) == want
    # backpressure is not a request's fault: nothing quarantined even
    # with the default-sized retry budget
    assert eng.stats["requests_failed"] == 0
    assert eng.audit_pages() == []


def test_poison_request_quarantined_innocents_bit_identical(tmp_path):
    net, _ = _tiny()
    want = _outputs(_engine(net).serve(_reqs()))
    eng = _engine(net, max_retries=3, retry_backoff_s=0.0)
    rec = flight.install(out_dir=str(tmp_path / "fd"), stall_timeout=1e9,
                         queue_full_threshold=10 ** 6)
    plan = FaultPlan(poison={"r2": "decode"})
    plan.install(eng)
    try:
        done = eng.serve(_reqs())
    finally:
        plan.uninstall()
        flight.uninstall()
    bad = [r for r in done if r.id == "r2"]
    assert bad and bad[0].status == "failed"
    assert eng.stats["requests_failed"] == 1
    # every co-batched innocent finished bit-identical to fault-free
    assert _outputs(done) == {k: v for k, v in want.items() if k != "r2"}
    assert eng.audit_pages() == []
    # the first caught fault latched exactly one flight dump
    assert f"dispatch_error:engine{eng._eid}" in rec.latched
    assert len(rec.dumps) == 1
    tl = [t for t in telemetry.request_log.recent(100)
          if t["request_id"] == "r2"][-1]
    assert tl["status"] == "failed"
    assert tl["events"][-1]["reason"] == "error"


# ---------------------------------------------------------------------------
# chaos soak: Poisson arrivals, mixed faults, poison — bit-identical
# ---------------------------------------------------------------------------

def test_chaos_soak_poisson_arrivals_bit_identical():
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
            reqs.append(Request(prompt, n_new,
                                request_id=f"c{i}", do_sample=True,
                                temperature=0.8, seed=1000 + i))
        return reqs

    net, _ = _tiny()
    want = _outputs(_engine(net, num_slots=4).serve(mk()))
    assert len(want) == N

    eng = _engine(net, num_slots=4, max_retries=8, retry_backoff_s=0.0)
    plan = FaultPlan(seed=3, dispatch_exception=0.05, nan_logits=0.05,
                     pool_exhaustion=0.05, exhaust_steps=2,
                     alloc_failure=0.05, slow_dispatch=0.02, slow_s=1e-4,
                     poison=poison, max_faults=40)
    plan.install(eng)
    arrivals = np.random.default_rng(13)
    pending = mk()[::-1]
    done, steps = [], 0
    try:
        while (pending or eng.has_work) and steps < 20000:
            for _ in range(int(arrivals.poisson(3.0))):
                if pending:
                    eng.submit(pending.pop())
            done.extend(eng.step())
            steps += 1
    finally:
        plan.uninstall()
    while eng.has_work and steps < 20000:
        done.extend(eng.step())
        steps += 1
    assert steps < 20000, "chaos soak did not converge"

    got = _outputs(done)
    for rid in poison:
        assert rid not in got
        (bad,) = [r for r in done if r.id == rid]
        assert bad.status == "failed"
    assert got == {k: v for k, v in want.items() if k not in poison}
    assert eng.stats["requests_failed"] == len(poison)
    assert eng.stats["dispatch_errors"] >= 1
    assert eng.audit_pages() == []


def test_chaos_soak_with_adapters_keeps_both_pools_clean():
    """Adapter-enabled chaos: faults during prefill/decode must roll
    adapter pins back exactly like page leases — at drain BOTH audits
    are clean and every non-poison output is bit-identical to the
    fault-free adapter run."""
    from mxnet_tpu.serving import AdapterPool, random_lora
    N = 48
    names = ["fa", "fb", "fc", None]      # mixed wear, incl. null

    def mk():
        rng = np.random.default_rng(17)
        return [Request(rng.integers(1, 97,
                                     size=int(rng.integers(2, 10))),
                        int(rng.integers(2, 6)), request_id=f"a{i}",
                        adapter_id=names[i % len(names)],
                        tenant=f"t{i % 2}")
                for i in range(N)]

    net, cfg = _tiny(max_len=64)

    def mk_engine(**kw):
        pool = AdapterPool(cfg, slots=3, max_rank=2)  # 2 usable slots
        for j, name in enumerate(n for n in names if n):
            pool.register(name, random_lora(cfg, rank=2, seed=40 + j,
                                            scale=0.05))
        return _engine(net, num_slots=4, max_length=64,
                       adapter_pool=pool, **kw), pool

    base_eng, _ = mk_engine()
    want = _outputs(base_eng.serve(mk()))

    eng, pool = mk_engine(max_retries=8, retry_backoff_s=0.0)
    plan = FaultPlan(seed=5, dispatch_exception=0.05, nan_logits=0.05,
                     pool_exhaustion=0.05, exhaust_steps=2,
                     max_faults=25)
    plan.install(eng)
    arrivals = np.random.default_rng(19)
    pending = mk()[::-1]
    done, steps = [], 0
    try:
        while (pending or eng.has_work) and steps < 20000:
            for _ in range(int(arrivals.poisson(3.0))):
                if pending:
                    eng.submit(pending.pop())
            done.extend(eng.step())
            steps += 1
    finally:
        plan.uninstall()
    while eng.has_work and steps < 20000:
        done.extend(eng.step())
        steps += 1
    assert steps < 20000, "adapter chaos soak did not converge"
    assert _outputs(done) == want
    assert eng.audit_pages() == []
    assert eng.audit_adapters() == []
    assert pool.num_pinned == 0           # every fault path unpinned
    assert eng.stats["dispatch_errors"] >= 1
