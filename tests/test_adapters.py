"""Multi-tenant LoRA serving tests (tier-1, ISSUE 10).

Covers: the AdapterPool slab ledger (register/acquire/release, LRU
eviction, pin-while-in-use exhaustion as backpressure, the audit
invariant check), the null-adapter bit-identity guarantee across the
plain / prefix-cache / speculative engines, the merged-weight dense
oracle (``W + (B A)^T * alpha/r``) including a mixed batch where every
slot wears a different adapter, compile-flat adapter churn (adapter
identity is runtime data, never a shape axis), per-tenant quotas +
deficit-weighted fair admission, and the router's adapter-affinity
placement key.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import (AdapterPool, AdapterPoolExhausted,
                               Request, ServingEngine, SheddingPolicy,
                               SlotScheduler, TenantQuota,
                               TenantQuotaError, merged_weights,
                               random_lora)
from mxnet_tpu.telemetry import cost


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


def _engine(net, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(net, **kw)


def _reqs(prompts, max_new=6, **kw):
    return [Request(p, max_new, request_id=f"r{i}", **kw)
            for i, p in enumerate(prompts)]


def _outputs(done):
    return {r.id: list(r.output_tokens) for r in done}


def _prompts(n=4, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 97, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _merged_net(weights):
    """A fresh tiny model with `weights`' LoRA deltas baked densely
    into every attention projection — the oracle engine's model."""
    net, _ = _tiny()
    for li, blk in enumerate(net.backbone.blocks()):
        attn = blk.attn
        for pname in ("query", "key", "value", "proj"):
            layer = getattr(attn, pname)
            w = layer.weight.data().asnumpy()
            layer.weight.set_data(
                mx.nd.array(merged_weights(w, weights, pname, li)))
    return net


# ---------------------------------------------------------------------------
# AdapterPool ledger
# ---------------------------------------------------------------------------

def test_pool_register_validation():
    _, cfg = _tiny()
    with pytest.raises(MXNetError):
        AdapterPool(cfg, slots=1)
    pool = AdapterPool(cfg, slots=3, max_rank=4)
    w = random_lora(cfg, rank=2)
    for bad_id in (None, 0):
        with pytest.raises(MXNetError):
            pool.register(bad_id, w)
    with pytest.raises(MXNetError):      # rank above the pad budget
        pool.register("big", random_lora(cfg, rank=8))
    shaped = dict(w, A=w["A"][:, :1])    # wrong layer count
    with pytest.raises(MXNetError):
        pool.register("shape", shaped)
    with pytest.raises(MXNetError):      # acquire before register
        pool.acquire("ghost")
    pool.register("ok", w)
    assert pool.has("ok") and pool.has(None) and pool.has(0)
    assert not pool.has("ghost")
    assert pool.num_registered == 1 and pool.num_resident == 0


def test_pool_acquire_release_lru_and_null():
    _, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=2)   # 2 usable slots
    for name in ("a", "b", "c"):
        pool.register(name, random_lora(cfg, rank=2))
    assert pool.acquire(None) == 0 and pool.acquire(0) == 0
    sa = pool.acquire("a")
    sb = pool.acquire("b")
    assert sa != sb and 0 not in (sa, sb)
    assert pool.page_ins == 2 and pool.num_resident == 2
    pool.release("a")
    pool.release("b")
    # both stay warm until a page-in needs a slot; 'a' is the LRU
    assert pool.num_resident == 2 and pool.num_pinned == 0
    sc = pool.acquire("c")
    assert sc == sa and pool.evictions == 1
    assert pool.slot_of("a") is None and pool.slot_of("b") == sb
    # re-acquiring the warm resident is a hit: no page-in
    pins_before = pool.page_ins
    assert pool.acquire("b") == sb and pool.page_ins == pins_before
    assert pool.audit(assignments=["c", "b"]) == []


def test_pool_exhaustion_is_loud_and_pins_protect():
    _, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=2)
    for name in ("a", "b", "c"):
        pool.register(name, random_lora(cfg, rank=2))
    pool.acquire("a")
    pool.acquire("b")
    with pytest.raises(AdapterPoolExhausted):
        pool.acquire("c")
    with pytest.raises(MXNetError):      # evicting a pinned adapter
        pool.evict("a")
    with pytest.raises(MXNetError):      # re-registering while pinned
        pool.register("a", random_lora(cfg, rank=2))
    pool.release("a")
    assert pool.acquire("c") is not None      # LRU-evicts unpinned 'a'
    with pytest.raises(MXNetError):           # pin underflow
        pool.release("a")
    pool.release("b")
    pool.release("c")
    assert pool.audit() == []


def test_pool_audit_catches_leaked_and_missing_pins():
    _, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=2)
    pool.register("a", random_lora(cfg, rank=2))
    slot = pool.acquire("a")
    # pin with no active-slot assignment = a leak
    v = pool.audit(assignments=[])
    assert any("leaked" in s for s in v)
    # assignment without residency
    v = pool.audit(assignments=["a", "a"])
    assert any("pin count" in s for s in v)
    with pytest.raises(MXNetError):
        pool.audit(assignments=[], raise_on_error=True)
    pool.release("a")
    assert pool.audit(assignments=[]) == []
    # corrupt the ledger behind the API: double residency
    pool._adapter_at[slot] = "a"
    pool._adapter_at[2 if slot != 2 else 1] = "a"
    assert any("resident" in s for s in pool.audit())


# ---------------------------------------------------------------------------
# tenant quotas + fair-share admission (scheduler level)
# ---------------------------------------------------------------------------

def test_tenant_max_queue_bound_sheds_at_submit():
    s = SlotScheduler(2, tenant_quotas={"t": TenantQuota(max_queue=2)})
    s.submit(Request([1], 1, request_id="a", tenant="t"))
    s.submit(Request([1], 1, request_id="b", tenant="t"))
    with pytest.raises(TenantQuotaError) as ei:
        s.submit(Request([1], 1, request_id="c", tenant="t"))
    assert ei.value.reason == "tenant_quota"
    # other tenants are untouched by t's bound
    s.submit(Request([1], 1, request_id="d", tenant="u"))


def test_tenant_max_active_keeps_requests_queued():
    s = SlotScheduler(3, tenant_quotas={"t": TenantQuota(max_active=1)})
    for i in range(3):
        s.submit(Request([1], 1, request_id=f"t{i}", tenant="t"))
    s.submit(Request([1], 1, request_id="u0", tenant="u"))
    admitted = [r.id for _, r in s.admit(0.0)]
    # only ONE of t's requests may hold a slot; u fills another;
    # the third slot stays empty rather than over-admitting t
    assert sum(r.startswith("t") for r in admitted) == 1
    assert "u0" in admitted and len(admitted) == 2
    assert s.tenant_active("t") == 1 and s.tenant_queued("t") == 2


def test_deficit_weighted_fair_pick_follows_weights():
    s = SlotScheduler(1, tenant_quotas={
        "heavy": TenantQuota(weight=3.0),
        "light": TenantQuota(weight=1.0)})
    for i in range(40):
        s.submit(Request([1], 1, request_id=f"h{i}", tenant="heavy"))
        s.submit(Request([1], 1, request_id=f"l{i}", tenant="light"))
    order = []
    for _ in range(24):
        (slot, req), = s.admit(0.0)
        order.append(req.tenant)
        s.release(slot)
    # ~3:1 service ratio (boundary rounding aside), starvation-free:
    # light is served steadily, never parked behind heavy's backlog
    h, l = order.count("heavy"), order.count("light")
    assert h + l == 24 and h >= 2 * l and l >= 5
    for i in range(0, 24, 6):
        assert "light" in order[i:i + 6]


def test_tenancy_rides_through_snapshot():
    s = SlotScheduler(2, tenant_quotas={"t": TenantQuota(max_active=1)})
    s.submit(Request([1, 2], 2, request_id="a", tenant="t",
                     adapter_id="x"))
    s.admit(0.0)
    snap = s.snapshot()
    (active,) = snap["active"].values()
    assert active["tenant"] == "t" and active["adapter_id"] == "x"
    assert snap["tenants"]["t"]["max_active"] == 1
    assert snap["tenants"]["t"]["active"] == 1


# ---------------------------------------------------------------------------
# null-adapter bit-identity (the pre-PR engine is the oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    "plain", "prefix", pytest.param("spec", marks=pytest.mark.slow)])
def test_null_adapter_output_bit_identical(mode):
    net, cfg = _tiny()
    kw = {}
    if mode == "prefix":
        kw = dict(prefix_cache=True)
    elif mode == "spec":
        kw = dict(speculative=True, spec_tokens=3)
    prompts = _prompts(6, seed=4)
    mk = lambda: _reqs(prompts, max_new=7, do_sample=True,  # noqa: E731
                       temperature=0.8)
    for i, r in enumerate(mk()):
        r.seed = 50 + i
    want = _outputs(_engine(net, **kw).serve(mk()))

    pool = AdapterPool(cfg, slots=4, max_rank=4)
    pool.register("unused", random_lora(cfg, rank=4, seed=9))
    eng = _engine(net, adapter_pool=pool, **kw)
    reqs = mk()
    for r in reqs[::2]:
        r.adapter_id = 0          # explicit null spelling
    got = _outputs(eng.serve(reqs))
    assert got == want
    assert eng.audit_adapters() == [] and eng.audit_pages() == []


# ---------------------------------------------------------------------------
# merged-weight dense oracle
# ---------------------------------------------------------------------------

def test_adapter_matches_merged_weight_oracle():
    net, cfg = _tiny()
    w = random_lora(cfg, rank=3, seed=7, scale=0.05)
    pool = AdapterPool(cfg, slots=4, max_rank=4)
    pool.register("fin", w)
    prompts = _prompts(4, seed=1)
    got = _outputs(_engine(net, adapter_pool=pool).serve(
        _reqs(prompts, adapter_id="fin")))
    want = _outputs(_engine(_merged_net(w)).serve(_reqs(prompts)))
    assert got == want


def test_mixed_adapter_batch_each_slot_its_own_oracle():
    net, cfg = _tiny()
    adapters = {f"a{i}": random_lora(cfg, rank=2 + i % 3, seed=20 + i,
                                     scale=0.05) for i in range(3)}
    pool = AdapterPool(cfg, slots=5, max_rank=4)
    for name, w in adapters.items():
        pool.register(name, w)
    eng = _engine(net, num_slots=4, adapter_pool=pool)
    prompts = _prompts(4, seed=2)
    wear = ["a0", "a1", "a2", None]    # every slot a different adapter
    reqs = [Request(p, 6, request_id=f"m{i}", adapter_id=wear[i])
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    # co-batched: 4 slots, 4 requests — all decoded in one program
    assert eng.stats["prefills"] == 4
    for i, r in enumerate(reqs):
        oracle_net = net if wear[i] is None \
            else _merged_net(adapters[wear[i]])
        (want,) = _engine(oracle_net).serve(
            [Request(prompts[i], 6, request_id="o")])
        assert list(r.output_tokens) == list(want.output_tokens), \
            f"slot {i} adapter {wear[i]!r}"
    assert eng.audit_adapters() == []


# ---------------------------------------------------------------------------
# adapter churn: runtime data, never a shape axis
# ---------------------------------------------------------------------------

def test_adapter_churn_is_compile_flat():
    net, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=2)   # 2 usable slots...
    names = [f"a{i}" for i in range(5)]            # ...5 adapters
    for i, name in enumerate(names):
        pool.register(name, random_lora(cfg, rank=2, seed=30 + i))
    eng = _engine(net, adapter_pool=pool)
    prompt = list(range(3, 11))

    def compiles():
        progs = cost.report()["programs"]
        return sum(s["compiles"] for p, s in progs.items()
                   if p.startswith(f"engine{eng._eid}/"))

    # warm every program shape once (one prefill bucket, greedy decode)
    eng.serve([Request(prompt, 4, request_id="warm", adapter_id="a0")])
    eng.mark_warm()
    c0 = compiles()
    for round_ in range(3):            # churn through ALL the adapters
        eng.serve([Request(prompt, 4, request_id=f"c{round_}/{n}",
                           adapter_id=n) for n in names])
    assert compiles() == c0, "adapter churn must not retrace"
    assert eng.warmed
    # the slab really thrashed: more page-ins than slots
    assert pool.page_ins > pool.slots
    assert eng.stats["adapter_page_ins"] == pool.page_ins
    assert eng.audit_adapters() == []


def test_adapter_slab_exhaustion_is_backpressure():
    net, cfg = _tiny()
    pool = AdapterPool(cfg, slots=2, max_rank=2)   # ONE usable slot
    pool.register("x", random_lora(cfg, rank=2, seed=1))
    pool.register("y", random_lora(cfg, rank=2, seed=2))
    eng = _engine(net, retry_backoff_s=0.0, adapter_pool=pool)
    done = eng.serve([Request([5, 6, 7], 4, request_id="rx",
                              adapter_id="x"),
                      Request([5, 6, 8], 4, request_id="ry",
                              adapter_id="y")])
    # both finish — exhaustion requeues (nobody blamed, no quarantine)
    assert {r.id: r.status for r in done} == {"rx": "finished",
                                              "ry": "finished"}
    assert eng.stats["requests_failed"] == 0
    assert eng.audit_adapters() == [] and eng.audit_pages() == []


def test_unknown_adapter_rejected_at_submit():
    net, cfg = _tiny()
    eng = _engine(net)                         # no pool at all
    with pytest.raises(MXNetError, match="adapter"):
        eng.submit(Request([1, 2], 2, request_id="a", adapter_id="x"))
    pool = AdapterPool(cfg, slots=3, max_rank=2)
    eng2 = _engine(net, adapter_pool=pool)
    with pytest.raises(MXNetError, match="not registered"):
        eng2.submit(Request([1, 2], 2, request_id="b", adapter_id="x"))
    assert eng2.stats["requests_rejected"] == 1


# ---------------------------------------------------------------------------
# engine-level tenancy: quota shed accounting + statusz
# ---------------------------------------------------------------------------

def test_engine_tenant_quota_shed_taxonomy():
    net, cfg = _tiny()
    eng = _engine(net, tenant_quotas={
        "over": TenantQuota(max_queue=1),
        "ok": TenantQuota(weight=2.0)})
    prompts = _prompts(6, seed=3)
    done, shed = [], []
    for i, p in enumerate(prompts):
        t = "over" if i % 2 else "ok"
        r = Request(p, 3, request_id=f"q{i}", tenant=t)
        try:
            eng.submit(r)
        except TenantQuotaError as e:
            assert e.reason == "tenant_quota" and e.tenant == "over"
            shed.append(r)
    assert shed and all(r.tenant == "over" for r in shed)
    while eng.has_work:
        done.extend(eng.step())
    ts = eng.tenant_stats()
    assert ts["over"]["shed"]["tenant_quota"] == len(shed)
    assert ts["ok"].get("shed", {}) == {}
    sz = eng._statusz()
    assert "over" in sz["tenants"] and sz["config"]["adapter_pool"] is False
    # the per-tenant shed family carries the same count
    fam = telemetry.get("serving_tenant_shed_total")
    assert fam.labels(eng._eid, "over", "tenant_quota").value == len(shed)


def test_policy_tenant_queue_share_sheds_hogs():
    net, _ = _tiny()
    eng = _engine(net, policy=SheddingPolicy(queue_low=2, queue_high=50,
                                             tenant_queue_share=0.5))
    # fill the queue with one tenant up to the elevated watermark
    eng.submit(Request([1, 2, 3], 2, request_id="h0", tenant="hog"))
    eng.submit(Request([1, 2, 3], 2, request_id="h1", tenant="hog"))
    # elevated now (queue_low=2), and hog holds 2/2 > 0.5 of the queue
    from mxnet_tpu.serving import ShedError
    with pytest.raises(ShedError) as ei:
        eng.submit(Request([1, 2, 3], 2, request_id="h2", tenant="hog"))
    assert ei.value.reason == "tenant_share"
    # a different tenant still gets in
    eng.submit(Request([1, 2, 3], 2, request_id="ok", tenant="calm"))
    eng.serve()
    assert eng.tenant_stats()["hog"]["shed"]["tenant_share"] == 1


# ---------------------------------------------------------------------------
# migration: adapter_id + tenant ride export/adopt bit-identically
# ---------------------------------------------------------------------------

def test_export_adopt_preserves_adapter_and_tenant():
    net, cfg = _tiny()
    w = random_lora(cfg, rank=2, seed=5, scale=0.05)

    def mk_engine():
        pool = AdapterPool(cfg, slots=3, max_rank=2)
        pool.register("fin", w)
        return _engine(net, adapter_pool=pool)

    prompts = _prompts(3, seed=6)
    mk = lambda: [Request(p, 6, request_id=f"g{i}", adapter_id="fin",  # noqa: E731
                          tenant="t0") for i, p in enumerate(prompts)]
    want = _outputs(mk_engine().serve(mk()))

    src, dst = mk_engine(), mk_engine()
    for r in mk():
        src.submit(r)
    src.step()                      # some requests now mid-flight
    moved = src.export_requests()
    assert src.audit_adapters() == []      # pins rolled back
    assert [r.adapter_id for r in moved] == ["fin"] * 3
    assert [r.tenant for r in moved] == ["t0"] * 3
    done = []
    for r in moved:
        dst.adopt(r, migrated_from="src")
    while dst.has_work:
        done.extend(dst.step())
    assert _outputs(done) == want
    assert dst.audit_adapters() == []


# ---------------------------------------------------------------------------
# router: adapter affinity in the placement key
# ---------------------------------------------------------------------------

def test_router_affinity_key_includes_adapter():
    from mxnet_tpu.serving import ServingRouter
    net, _ = _tiny()
    engines = [_engine(net) for _ in range(3)]
    router = ServingRouter(engines, require_warm=False)
    prompt = list(range(1, 9))
    cands = list(range(3))
    base = router._affinity_idx(Request(prompt, 2, request_id="n"),
                                cands)
    picks = {router._affinity_idx(
        Request(prompt, 2, request_id=f"a{i}", adapter_id=f"ad{i}"),
        cands) for i in range(8)}
    # deterministic per adapter...
    again = router._affinity_idx(
        Request(prompt, 2, request_id="x", adapter_id="ad0"), cands)
    assert again == router._affinity_idx(
        Request(prompt, 2, request_id="y", adapter_id="ad0"), cands)
    # ...and the adapter id actually moves placement for some adapters
    assert len(picks | {base}) > 1
    # null adapter spellings hash exactly like the pre-PR key
    assert router._affinity_idx(
        Request(prompt, 2, request_id="z", adapter_id=0), cands) == base
