"""Mesh/sharding/TrainStep tests on the 8-device virtual CPU mesh.

The analog of the reference's multi-process-on-one-box kvstore tests
(SURVEY.md §4 'Distributed'): deterministic numeric checks that sharded
execution matches single-device execution.
"""
import numpy as np
import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt, parallel as par
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.parallel import PartitionSpec as P


def _make_net(seed=0):
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16),
            nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    return net


def _sync(src, dst):
    sp, dp_ = src.collect_params(), dst.collect_params()
    for k in sp:
        dp_[k].set_data(sp[k].data())


def test_make_mesh():
    mesh = par.make_mesh(dp=8)
    assert mesh.shape == {"dp": 8}
    mesh2 = par.make_mesh(dp=-1, tp=2)
    assert mesh2.shape == {"dp": 4, "tp": 2}
    with pytest.raises(mx.MXNetError):
        par.make_mesh(dp=3)  # 8 not divisible


def test_trainstep_single_device_matches_eager():
    # fused step (no mesh) must match eager autograd+optimizer numerics
    X = np.random.default_rng(0).standard_normal((16, 16)).astype(np.float32)
    Y = np.random.default_rng(1).integers(0, 4, 16).astype(np.int32)

    net_a = _make_net(seed=42)
    net_b = _make_net(seed=42)
    _sync(net_a, net_b)
    lfn = gloss.SoftmaxCrossEntropyLoss()

    # eager reference path
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer
    tr = Trainer(net_a.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    xa, ya = mx.nd.array(X), mx.nd.array(Y, dtype="int32")
    eager_losses = []
    for _ in range(5):
        with autograd.record():
            l = lfn(net_a(xa), ya)
        l.backward()
        tr.step(batch_size=16)
        eager_losses.append(float(l.mean().asscalar()))

    # fused TrainStep path (rescale matches: mean loss => rescale 1)
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    step = par.TrainStep(net_b, lfn, o, mesh=None)
    fused_losses = []
    for _ in range(5):
        fused_losses.append(float(step(xa, ya).asscalar()))
    np.testing.assert_allclose(eager_losses, fused_losses, rtol=1e-4)
    step.sync_params()
    np.testing.assert_allclose(
        net_a.collect_params()["0.weight"].data().asnumpy(),
        net_b.collect_params()["0.weight"].data().asnumpy(), rtol=1e-4,
        atol=1e-5)


def test_trainstep_dp_mesh_matches_single():
    X = np.random.default_rng(2).standard_normal((32, 16)).astype(np.float32)
    Y = np.random.default_rng(3).integers(0, 4, 32).astype(np.int32)
    lfn = gloss.SoftmaxCrossEntropyLoss()

    net_s = _make_net(seed=7)
    o_s = opt.SGD(learning_rate=0.1, momentum=0.9)
    step_s = par.TrainStep(net_s, lfn, o_s, mesh=None)

    net_m = _make_net(seed=7)
    _sync(net_s, net_m)
    o_m = opt.SGD(learning_rate=0.1, momentum=0.9)
    mesh = par.make_mesh(dp=8)
    step_m = par.TrainStep(net_m, lfn, o_m, mesh=mesh,
                           batch_specs=(P("dp"), P("dp")))

    for i in range(3):
        ls = float(step_s(mx.nd.array(X), mx.nd.array(Y, dtype="int32")).asscalar())
        lm = float(step_m(mx.nd.array(X), mx.nd.array(Y, dtype="int32")).asscalar())
        np.testing.assert_allclose(ls, lm, rtol=1e-5)
    step_s.sync_params()
    step_m.sync_params()
    np.testing.assert_allclose(
        net_s.collect_params()["1.weight"].data().asnumpy(),
        net_m.collect_params()["1.weight"].data().asnumpy(),
        rtol=1e-4, atol=1e-5)


def test_trainstep_tp_sharding_matches():
    """Megatron-ish: shard first Dense out-dim and second Dense in-dim over
    tp=2; results must match the replicated run."""
    X = np.random.default_rng(4).standard_normal((8, 16)).astype(np.float32)
    Y = np.random.default_rng(5).integers(0, 4, 8).astype(np.int32)
    lfn = gloss.SoftmaxCrossEntropyLoss()

    net_r = _make_net(seed=9)
    o_r = opt.Adam(learning_rate=0.01)
    step_r = par.TrainStep(net_r, lfn, o_r, mesh=None)

    net_t = _make_net(seed=9)
    _sync(net_r, net_t)
    params = net_t.collect_params()
    params["0.weight"].sharding = P("tp", None)   # column parallel (out, in)
    params["0.bias"].sharding = P("tp")
    params["1.weight"].sharding = P(None, "tp")   # row parallel
    o_t = opt.Adam(learning_rate=0.01)
    mesh = par.make_mesh(dp=4, tp=2)
    step_t = par.TrainStep(net_t, lfn, o_t, mesh=mesh,
                           batch_specs=(P("dp"), P("dp")))

    for _ in range(3):
        lr_ = float(step_r(mx.nd.array(X), mx.nd.array(Y, dtype="int32")).asscalar())
        lt = float(step_t(mx.nd.array(X), mx.nd.array(Y, dtype="int32")).asscalar())
        np.testing.assert_allclose(lr_, lt, rtol=1e-4)

    # sharded params really are distributed
    arr = step_t._param_arrays[0]
    assert len(arr.sharding.device_set) == 8


def test_sharding_rules():
    rules = par.ShardingRules([
        (r"\.weight$", P("tp", None)),
    ], default=None)
    net = _make_net()
    par.apply_sharding_rules(net, rules)
    params = net.collect_params()
    assert params["0.weight"].sharding == P("tp", None)
    assert params["0.bias"].sharding is None


def test_megatron_rules_patterns():
    rules = par.megatron_dense_rules()
    assert rules.spec_for("encoder.layer0.attn.query.weight") == \
        P("tp", None)
    assert rules.spec_for("encoder.layer0.attn.proj.weight") == \
        P(None, "tp")
    assert rules.spec_for("embedding.weight") == P("tp", None)
    assert rules.spec_for("encoder.layer0.ln.gamma") is None


def test_evalstep():
    net = _make_net(seed=11)
    mesh = par.make_mesh(dp=8)
    ev = par.EvalStep(net, mesh=mesh)
    X = np.random.default_rng(6).standard_normal((16, 16)).astype(np.float32)
    out = ev(mx.nd.array(X))
    ref = net(mx.nd.array(X))
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), rtol=1e-5,
                               atol=1e-6)


def test_trainstep_honors_wd_mult():
    # wd_mult=0 on the bias (standard practice) must suppress weight decay
    # in the fused step, matching the eager Trainer's _get_wd behavior.
    X = np.random.default_rng(5).standard_normal((8, 16)).astype(np.float32)

    net = nn.Dense(4, in_units=16)
    net.initialize(mx.init.Xavier())
    net.bias.wd_mult = 0.0
    bias0 = net.bias.data().asnumpy().copy()
    w0 = net.weight.data().asnumpy().copy()

    class MeanLoss:
        def __call__(self, out):
            return out.mean()

    o = opt.SGD(learning_rate=0.1, wd=0.5)
    step = par.TrainStep(net, MeanLoss(), o, mesh=None, n_net_inputs=1)
    step(mx.nd.array(X))
    step.sync_params()

    # d(mean(xW^T+b))/db = 1/4 per unit; no wd term on the bias
    g_bias = np.full((4,), 1.0 / 4, np.float32)
    np.testing.assert_allclose(net.bias.data().asnumpy(),
                               bias0 - 0.1 * g_bias, rtol=1e-5, atol=1e-6)
    # weight DOES get decayed: w1 = w0 - lr*(g + wd*w0)
    g_w = np.tile(X.mean(axis=0) / 4, (4, 1)).astype(np.float32)
    np.testing.assert_allclose(net.weight.data().asnumpy(),
                               w0 - 0.1 * (g_w + 0.5 * w0), rtol=1e-4,
                               atol=1e-5)


def test_comm_report_prices_dp_collectives():
    """parallel.comm_report reads the collectives out of a compiled step
    and prices them with the ring model (VERDICT r4 weak #9)."""
    mesh = par.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    net = nn.Dense(16, in_units=32)
    mx.rng.seed(0)
    net.initialize(mx.init.Xavier())
    step = par.TrainStep(net, gloss.L2Loss(), opt.SGD(learning_rate=0.1),
                         mesh=mesh)
    r = np.random.default_rng(0)
    x = mx.nd.array(r.standard_normal((8, 32)), dtype="float32")
    y = mx.nd.array(r.standard_normal((8, 16)), dtype="float32")
    float(step(x, y).asscalar())
    report = par.comm_report(step)
    assert "all_reduce" in report, report
    assert "total wire time" in report
    rows = par.collective_summary(
        step._lowered().compile().as_text())
    assert any(row["kind"] == "all_reduce" and row["bytes"] > 0
               for row in rows), rows
    # the ring model itself
    assert par.ring_cost_bytes("all_reduce", 1000, 4) == 1500
    assert par.ring_cost_bytes("all_gather", 1000, 4) == 750
    assert par.ring_cost_bytes("collective_permute", 1000, 4) == 1000
    assert par.ring_cost_bytes("all_reduce", 1000, 1) == 0


def test_trainstep_state_keeps_its_layout_across_calls():
    """A column-parallel weight makes the partitioner WANT to return its
    replicated bias sharded like the gradient that updated it; the step
    must hand its state back in the layout it declared, or the second
    call is refused ("Sharding passed to jit does not match")."""
    net = _make_net(seed=3)
    net.collect_params()["1.weight"].sharding = P("tp", None)
    mesh = par.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(),
                         opt.Adam(learning_rate=0.01), mesh=mesh,
                         batch_specs=(P("dp"), P("dp")))
    X = mx.nd.array(np.random.default_rng(4).standard_normal(
        (8, 16)).astype(np.float32))
    Y = mx.nd.array(np.random.default_rng(5).integers(0, 4, 8),
                    dtype="int32")
    for _ in range(3):
        assert np.isfinite(float(step(X, Y).asscalar()))
    assert np.isfinite(step.run_steps(X, Y, steps=2).asnumpy()).all()
    assert np.isfinite(step.run_steps(X, Y, steps=2).asnumpy()).all()
    for spec, arr in zip(step.param_sharding_specs(), step._param_arrays):
        assert arr.sharding.is_equivalent_to(
            par.NamedSharding(mesh, spec), arr.ndim)
