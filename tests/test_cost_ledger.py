"""Device-cost observability: program cost registry / MFU / roofline
(telemetry/cost.py), compile attribution + steady-state retrace
detection, and the HBM ledger (telemetry/ledger.py).

The MFU acceptance bar (ISSUE 6): the registered XLA cost_analysis
FLOPs for the decode, verify, and prefill programs must agree with
hand-derived GPT-2 FLOP counts within 5% on the CPU oracle path, and
the MFU gauge math is pinned against a mocked cost_analysis with
hand-set peaks.
"""
import json
import math
import os
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import cost, flight, ledger
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.serving import Request, ServingEngine

# -- hand-derived GPT-2 FLOP model (matmul terms; elementwise ops are
# the <5% slack the assertions allow) ---------------------------------------
# per layer, per query position: qkv+proj projections 8C², MLP 16C²,
# attention over the full T_max buffer 4*C*T (qk + av); LM head 2*C*V.


def hand_decode_flops(B, C, L, V, T, steps=1):
    return steps * (L * (24 * B * C * C + 4 * B * C * T)
                    + 2 * B * C * V)


def hand_unified_flops(B, W, R, C, L, V, T):
    """The body over all B x W rows, the head over the B x R rows the
    program samples (R = 1, or spec_tokens with speculation)."""
    return L * (24 * B * W * C * C + 4 * B * W * C * T) \
        + 2 * B * R * C * V


def hand_prefill_flops(Tb, C, L, V, T):
    return L * (24 * Tb * C * C + 4 * Tb * T * C) + 2 * Tb * C * V


C, H, L, V, T = 256, 4, 2, 512, 64
B, PAGE, SPEC_S = 4, 16, 4


@pytest.fixture(scope="module")
def gpt2_engines():
    """One plain (greedy decode) and one speculative engine over
    a shared GPT-2, both served once — compiled programs, registered
    costs, goodput counters and ledger providers all live."""
    cfg = GPT2Config(vocab_size=V, units=C, num_layers=L, num_heads=H,
                     max_length=T, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.02))
    eng = ServingEngine(net, num_slots=B, max_length=T, page_size=PAGE,
                        attn_impl="xla")
    done = eng.serve([Request(list(range(1, 11)), 6, request_id=i)
                      for i in range(B)])
    assert len(done) == B
    spec = ServingEngine(net, num_slots=B, max_length=T, page_size=PAGE,
                         attn_impl="xla", speculative=True,
                         spec_tokens=SPEC_S)
    pat = [5, 6, 7]
    sdone = spec.serve([Request(pat * 4, 8, request_id=100 + i)
                        for i in range(B)])
    assert len(sdone) == B
    return net, eng, spec


# -- CostedFunction / compile attribution -----------------------------------

def test_costed_function_compiles_once_and_registers_cost():
    import jax

    fn = jax.jit(lambda a, b: a @ b + 1.0, donate_argnums=(0,))
    cf = cost.CostedFunction(fn, "test/matmul64")
    x = jnp.ones((64, 64), jnp.float32)
    y = jnp.ones((64, 64), jnp.float32)
    out1 = cf(x, y)
    out2 = cf(jnp.ones((64, 64), jnp.float32), y)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    assert float(out1[0, 0]) == 65.0
    rec = cost.get("test/matmul64")
    assert rec["compiles"] == 1          # the second call reused AOT
    assert rec["compile_seconds"] > 0
    # XLA:CPU reports flops: 2*64^3 matmul + 64^2 add
    assert rec["flops"] == pytest.approx(2 * 64 ** 3 + 64 ** 2, rel=0.01)
    assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    c = telemetry.get("compiles_total").labels("test/matmul64")
    assert int(c.value) == 1


def test_costed_function_cost_scale():
    import jax

    fn = jax.jit(lambda a: a * 2.0)
    cf = cost.CostedFunction(fn, "test/scaled", cost_scale=8.0)
    base = cost.CostedFunction(jax.jit(lambda a: a * 2.0),
                               "test/unscaled")
    x = jnp.ones((32, 32), jnp.float32)
    cf(x), base(x)
    s, u = cost.get("test/scaled"), cost.get("test/unscaled")
    assert s["flops"] == pytest.approx(8.0 * u["flops"])


def test_mfu_math_against_mocked_cost_analysis(monkeypatch):
    """The MFU gauge is flops / wall / peak, bandwidth is bytes / wall,
    and the roofline classification compares AI with the ridge — all
    pinned with hand-set numbers."""
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_TPU_PEAK_BANDWIDTH", "1e11")
    cost.register_program("mock/attn", flops=2e9, bytes_accessed=1e9)
    rec = cost.note_dispatch("mock/attn", 0.004)
    assert rec is not None and rec.flops == 2e9
    mfu = telemetry.get("cost_mfu").labels("mock/attn").value
    assert mfu == pytest.approx(2e9 / 0.004 / 1e12)        # 0.5
    bw = telemetry.get(
        "cost_achieved_bandwidth_bytes_per_sec").labels("mock/attn")
    assert bw.value == pytest.approx(1e9 / 0.004)
    # AI = 2 flop/byte < ridge 10 -> memory bound
    assert telemetry.get("cost_arithmetic_intensity").labels(
        "mock/attn").value == pytest.approx(2.0)
    assert telemetry.get("cost_compute_bound").labels(
        "mock/attn").value == 0.0
    assert telemetry.get("cost_ridge_intensity").value == \
        pytest.approx(10.0)
    # a compute-bound program: AI 50 > ridge 10
    cost.register_program("mock/gemm", flops=5e9, bytes_accessed=1e8)
    assert telemetry.get("cost_compute_bound").labels(
        "mock/gemm").value == 1.0
    snap = cost.get("mock/attn")
    assert snap["mfu"] == pytest.approx(mfu)
    assert cost.report()["programs"]["mock/attn"]["bound"] == "memory"


def test_note_dispatch_disabled_is_noop():
    cost.register_program("mock/toggle", flops=1e6)
    before = int(telemetry.get("cost_dispatches_total")
                 .labels("mock/toggle").value)
    cost.set_enabled(False)
    try:
        assert cost.note_dispatch("mock/toggle", 0.001) is None
    finally:
        cost.set_enabled(True)
    assert int(telemetry.get("cost_dispatches_total")
               .labels("mock/toggle").value) == before
    assert cost.note_dispatch("mock/toggle", 0.001) is not None


# -- GPT-2 FLOP agreement (the 5% acceptance bar) ---------------------------

def test_gpt2_program_flops_agree_with_hand_math(gpt2_engines):
    _, eng, spec = gpt2_engines
    progs = cost.report()["programs"]

    # ONE unified program per engine: a fixed B x W forward serving
    # prefill chunks, decode steps, and verify rows alike — its FLOPs
    # are the verify model's with S = dispatch width, but for the head,
    # which sees the sampled rows alone
    W = eng._width
    uni = progs[f"engine{eng._eid}/unified/W{W}/greedy"]
    hand = hand_unified_flops(B, W, 1, C, L, V, T)
    assert abs(uni["flops"] / hand - 1) < 0.05

    Ws = spec._width
    ver = progs[f"engine{spec._eid}/unified/W{Ws}/S{SPEC_S}/greedy"]
    hand = hand_unified_flops(B, Ws, SPEC_S, C, L, V, T)
    assert abs(ver["flops"] / hand - 1) < 0.05

    # every program compiled exactly once across the whole serve —
    # and NO prefill program family exists at all
    assert not any("/prefill/" in p for p in progs)
    for s in (uni, ver):
        assert s["compiles"] == 1
        assert s["dispatches"] >= 1
    # a CPU has no peak: no MFU, bandwidth share or roofline side is
    # published under a host run's name
    pf, pb, _ = cost.peaks()
    assert pf is None and pb is None
    assert not {"mfu", "bandwidth_util", "bound"} & set(uni)


def test_unknown_accelerator_kind_is_an_error():
    class Dev:
        platform, device_kind = "tpu", "TPU v99 mystery"

    with pytest.raises(mx.MXNetError, match="v99 mystery"):
        cost.device_peaks(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert cost.device_peaks(Dev()) == (197e12, 819e9)


def test_goodput_counters(gpt2_engines):
    _, eng, spec = gpt2_engines
    s = eng.stats
    progs = cost.report()["programs"]
    uni = progs[f"engine{eng._eid}/unified/W{eng._width}/greedy"]
    # every dispatch runs the ONE unified program — prefill work rides
    # the same key, so goodput is flops x dispatch count, full stop
    expect = uni["flops"] * s["decode_dispatches"]
    assert s["model_flops"] == pytest.approx(expect, rel=1e-6)
    assert s["wasted_flops"] == 0                  # no speculation
    g = telemetry.get("serving_flops_per_token").labels(eng._eid)
    assert g.value == pytest.approx(s["model_flops"]
                                    / s["tokens_emitted"], rel=1e-6)
    sp = spec.stats
    assert sp["model_flops"] > 0
    if sp["spec_rollbacks"]:
        assert 0 < sp["wasted_flops"] < sp["model_flops"]


# -- steady state + retrace storm -------------------------------------------

def test_steady_state_flat_then_retrace_storm_latches(gpt2_engines,
                                                      tmp_path):
    _, eng, _ = gpt2_engines

    def compiles():
        progs = cost.report()["programs"]
        return sum(s["compiles"] for p, s in progs.items()
                   if p.startswith(f"engine{eng._eid}/"))

    eng.mark_warm()
    rec = flight.install(out_dir=str(tmp_path), stall_timeout=1e6)
    try:
        c0 = compiles()
        # steady-state soak over prompt lengths the engine has NEVER
        # seen — including one spanning multiple chunks. The unified
        # dispatch has no shape axis tied to prompt length, so the
        # registry stays compile-flat: the bucketed engine's
        # "new length => new program" retrace class is structurally
        # gone (ISSUE 11's acceptance bar)
        done = eng.serve([Request(list(range(3, 13)), 4,
                                  request_id=200 + i) for i in range(B)])
        done += eng.serve([Request(list(range(1, 21)), 3,
                                   request_id=300)])
        done += eng.serve([Request(list(range(1, 41)), 3,
                                   request_id=301)])
        assert len(done) == B + 2
        assert compiles() == c0
        assert flight.latched_reasons() == []
        assert rec.dumps == []
        # the latch path itself is still armed: ANY engine program
        # compiling after mark_warm() is a retrace storm. Wrap a fresh
        # program under the engine's key space and force a compile.
        import jax
        storm = eng._wrap_program(jax.jit(lambda x: x + 1),
                                  "synthetic/churn")
        storm(jnp.ones((4,), jnp.float32))
        assert compiles() == c0 + 1
        reason = f"retrace_storm:engine{eng._eid}/synthetic/churn"
        assert flight.latched_reasons() == [reason]
        assert len(rec.dumps) == 1
        state = json.load(open(os.path.join(rec.dumps[0], "state.json")))
        assert state["reason"] == reason
        assert state["detail"]["program"] == \
            f"engine{eng._eid}/synthetic/churn"
        # latched: a second churn event on the same key dumps nothing
        storm2 = eng._wrap_program(jax.jit(lambda x: x + 2),
                                   "synthetic/churn")
        storm2(jnp.ones((4,), jnp.float32))
        assert len(rec.dumps) == 1
    finally:
        flight.uninstall()
        eng._steady = False


# -- HBM ledger -------------------------------------------------------------

def test_ledger_dedupe_int_and_detail():
    x = jnp.ones((100,), jnp.float32)           # 400 B
    y = jnp.ones((50,), jnp.float32)            # 200 B
    z = jnp.ones((25,), jnp.float32)            # 100 B
    ledger.register("t/a", lambda: {"arrs": [x, y]})
    ledger.register("t/b", lambda: {"arrs": [y, z], "raw": 1000,
                                    "info": ledger.Detail(5000)})
    try:
        snap = ledger.snapshot()
        comp = snap["components"]
        assert comp["t/a"]["arrs"]["bytes"] == 600
        # y was already claimed by t/a (providers walk in sorted order)
        assert comp["t/b"]["arrs"]["bytes"] == 100
        assert comp["t/b"]["raw"]["bytes"] == 1000
        assert comp["t/b"]["info"] == {"bytes": 5000, "detail": True}
        assert telemetry.get("ledger_bytes").labels(
            "t/b/info").value == 5000
        # Detail excluded from the accounted total
        others = snap["accounted_bytes"] - 600 - 100 - 1000
        assert others >= 0                       # other live providers
        live = snap["live_array_bytes"]
        assert live is not None and live >= snap["accounted_bytes"] - 1000
        assert snap["unattributed_bytes"] == live - snap["accounted_bytes"]
    finally:
        ledger.unregister("t/a")
        ledger.unregister("t/b")
    assert "t/a" not in ledger.providers()


def test_ledger_engine_reconciliation(gpt2_engines):
    net, eng, spec = gpt2_engines
    snap = ledger.snapshot()
    comp = snap["components"][f"engine/{eng._eid}"]
    assert comp["kv_pages"]["bytes"] == \
        int(eng._kp.nbytes) + int(eng._vp.nbytes)
    w_bytes = sum(int(p.data()._data.nbytes)
                  for p in net.collect_params().values())
    both = [snap["components"][f"engine/{e._eid}"] for e in (eng, spec)]
    # the two engines share one parameter set: dedupe means exactly one
    # full claim between them
    assert sum(c["weights"]["bytes"] for c in both) == w_bytes
    assert min(c["weights"]["bytes"] for c in both) == 0
    assert comp["slot_state"]["bytes"] > 0
    # everything accounted is live — the ledger can never exceed it
    assert snap["live_array_bytes"] >= snap["accounted_bytes"]
    # idle engine: full page budget free again
    assert eng.admission_capacity_estimate() == B
    assert int(telemetry.get("serving_admission_capacity")
               .labels(eng._eid).value) == B


def test_memory_watermarks_live_array_path():
    from mxnet_tpu.telemetry import memory

    base = memory.sample()
    big = jnp.ones((1 << 16,), jnp.float32)          # 256 KiB
    after = memory.sample()
    assert after["live_array_bytes"] >= \
        base["live_array_bytes"] + big.nbytes - 1
    assert after["live_array_bytes_peak"] >= after["live_array_bytes"]
    peak = after["live_array_bytes_peak"]
    del big
    final = memory.sample()
    assert final["live_array_bytes_peak"] >= peak    # monotonic
    assert final["live_array_count"] > 0
    assert telemetry.get("memory_live_array_bytes").value == \
        final["live_array_bytes"]


# -- server endpoints -------------------------------------------------------

def test_compilez_memz_statusz_healthz_endpoints(gpt2_engines,
                                                 tmp_path):
    _, eng, _ = gpt2_engines
    srv = telemetry.IntrospectionServer(0)
    try:
        def fetch(path):
            return urllib.request.urlopen(srv.url + path,
                                          timeout=10).read().decode()

        compz = json.loads(fetch("/compilez"))
        assert (f"engine{eng._eid}/unified/W{eng._width}/greedy"
                in compz["programs"])
        assert compz["peak_flops"] is None      # CPU: no peak
        memz = json.loads(fetch("/memz"))
        assert memz["accounted_bytes"] > 0
        assert f"engine/{eng._eid}" in memz["components"]
        status = json.loads(fetch("/statusz"))
        assert status["rss_bytes"] is None or status["rss_bytes"] > 0
        assert status["versions"]["python"]
        assert "jax" in status["versions"]
        assert status["flight_latched"] == []
        assert fetch("/healthz") == "ok\n"
        rec = flight.install(out_dir=str(tmp_path), stall_timeout=1e6)
        try:
            rec.trigger("unit_test_reason", {"why": "healthz"})
            body = fetch("/healthz")
            assert body.startswith("degraded:")
            assert "unit_test_reason" in body
            rec.rearm()
            assert fetch("/healthz") == "ok\n"
        finally:
            flight.uninstall()
    finally:
        srv.stop()


# -- training-side integration ----------------------------------------------

def test_trainer_wall_attribution_and_optimizer_state_ledger():
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import Trainer, loss as gloss, nn

    net = nn.Dense(4, flatten=False, in_units=8)
    net.initialize(mx.init.Normal(0.1))
    trainer = Trainer(net.collect_params(),
                      opt.SGD(learning_rate=0.1, momentum=0.9))
    lfn = gloss.L2Loss()
    rng = np.random.default_rng(0)
    before = cost.get("trainer.step")
    before = before["dispatches"] if before else 0
    for _ in range(3):
        x = mx.nd.array(rng.standard_normal((4, 8)), dtype="float32")
        y = mx.nd.array(rng.standard_normal((4, 4)), dtype="float32")
        with mx.autograd.record():
            out = lfn(net(x), y)
        out.backward()
        trainer.step(batch_size=4)
    rec = cost.get("trainer.step")
    assert rec["dispatches"] == before + 3
    assert rec["flops"] is None              # eager: wall-only
    snap = ledger.snapshot()
    mine = [c for name, c in snap["components"].items()
            if name.startswith("trainer/")
            and c.get("optimizer_state", {}).get("bytes", 0) > 0]
    assert mine, "momentum state should be accounted by some trainer"


def test_trainstep_register_cost_analysis():
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import TrainStep

    net = nn.Dense(3, flatten=False, in_units=4)
    net.initialize(mx.init.Normal(0.1))
    step = TrainStep(net, gloss.L2Loss(), opt.SGD(learning_rate=0.1),
                     mesh=None)
    r = np.random.default_rng(0)
    x = mx.nd.array(r.standard_normal((2, 4)), dtype="float32")
    y = mx.nd.array(r.standard_normal((2, 3)), dtype="float32")
    float(step(x, y).asscalar())
    key = step._cost_key + "/step"
    rec = cost.get(key)
    assert rec is not None and rec["dispatches"] >= 1
    out = step.register_cost_analysis()
    assert out is not None and out["flops"] > 0
    # dispatch after registration publishes a live MFU gauge
    float(step(x, y).asscalar())
    assert not math.isnan(
        telemetry.get("cost_mfu").labels(key).value)
    snap = ledger.snapshot()
    comp = snap["components"][step._cost_key]
    assert comp["params"]["bytes"] > 0
