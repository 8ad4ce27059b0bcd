"""INT8 quantization, custom-op registry, config catalog, preemption
handler tests (SURVEY.md §2.3 quantization row, §2.3 custom ops, §5.6
config, §5.3 failure recovery)."""
import os
import signal

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------

def test_quantize_dequantize_roundtrip():
    from mxnet_tpu.contrib.quantization import dequantize, quantize_v2
    x = mx.nd.array(np.linspace(-2, 2, 64).astype(np.float32))
    q, mn, mxr = quantize_v2(x)
    assert str(q.dtype) == "int8"
    back = dequantize(q, mn, mxr)
    np.testing.assert_allclose(back.asnumpy(), x.asnumpy(), atol=2 / 127)


def test_quantize_net_matches_float_within_tolerance():
    from mxnet_tpu.contrib.quantization import QuantizedDense, quantize_net
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation="relu"))
    net.add(nn.Dense(4, in_units=32))
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.2))
    r = np.random.default_rng(0)
    calib = [mx.nd.array(r.standard_normal((8, 16)), dtype="float32")
             for _ in range(4)]
    ref = net(calib[0]).asnumpy()
    quantize_net(net, calib)
    assert any(isinstance(c, QuantizedDense)
               for c in net._children.values())
    got = net(calib[0]).asnumpy()
    # int8 per-tensor symmetric: a few percent of the activation scale
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() < 0.1 * scale, \
        np.abs(got - ref).max() / scale


def test_quantize_net_hybridized():
    """The standard PTQ flow: hybridize, calibrate, quantize (review
    regression: hooks must calibrate eagerly, stale traces cleared)."""
    from mxnet_tpu.contrib.quantization import QuantizedDense, quantize_net
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8))
    net.add(nn.Dense(2, in_units=16))
    mx.rng.seed(1)
    net.initialize(mx.init.Normal(0.2))
    net.hybridize()
    r = np.random.default_rng(1)
    calib = [mx.nd.array(r.standard_normal((4, 8)), dtype="float32")]
    ref = net(calib[0]).asnumpy()  # populate the jit cache first
    quantize_net(net, calib)
    assert any(isinstance(c, QuantizedDense)
               for c in net._children.values())
    got = net(calib[0]).asnumpy()
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() < 0.1 * scale


def test_quantize_v2_validates_range_pair():
    from mxnet_tpu.contrib.quantization import quantize_v2
    with pytest.raises(MXNetError, match="together"):
        quantize_v2(mx.nd.array([1.0]), min_calib_range=-1.0)


def test_compression_params_validation():
    store = mx.kv.create("local")
    store.set_gradient_compression({})   # explicit empty = no-op
    assert store._compressor is None
    with pytest.raises(MXNetError, match="'type'"):
        store.set_gradient_compression({"threshold": 0.5})


def test_trainer_forwards_compression_params():
    from mxnet_tpu.gluon import Trainer, nn
    net = nn.Dense(1, in_units=1)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                 kvstore="dist_sync",
                 compression_params={"type": "2bit", "threshold": 0.25})
    tr._init_kvstore()  # single process: store discarded but configured
    # prove the path runs without error and validates the params
    with pytest.raises(MXNetError):
        Trainer(net.collect_params(), "sgd", kvstore="dist_sync",
                compression_params={"type": "1bit"})._init_kvstore()


# ---------------------------------------------------------------------------
# custom ops
# ---------------------------------------------------------------------------

def test_register_op_modern_path_tapes_and_jits():
    import mxnet_tpu.operator as mxop

    myop = mxop.register_op("my_cube", lambda x: x ** 3)
    x = mx.nd.array([1.0, 2.0])
    np.testing.assert_allclose(myop(x).asnumpy(), [1.0, 8.0])
    from mxnet_tpu.ops.registry import get_op
    assert get_op("my_cube") is myop  # lands in the global registry
    x.attach_grad()
    with mx.autograd.record():
        y = myop(x).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [3.0, 12.0])


def test_register_op_custom_vjp():
    import mxnet_tpu.operator as mxop

    def f(x):
        return x * 2

    def fwd(x):
        return x * 2, None

    def bwd(res, g):
        return (g * 100.0,)  # deliberately wrong to prove it's used

    op = mxop.register_op("weird_grad", f, grad=(fwd, bwd),
                          register_global=False)
    x = mx.nd.array([3.0])
    x.attach_grad()
    with mx.autograd.record():
        y = op(x).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [100.0])


def test_legacy_custom_op_class_api():
    import mxnet_tpu.operator as mxop

    @mxop.register("scale2")
    class Scale2Prop(mxop.CustomOpProp):
        def create_operator(self, ctx, in_shapes, in_dtypes):
            class Scale2(mxop.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    self.assign(out_data[0], req[0], in_data[0] * 2.0)
            return Scale2()

    out = mx.nd.Custom(mx.nd.array([1.0, 2.0]), op_type="scale2")
    np.testing.assert_allclose(out.asnumpy(), [2.0, 4.0])
    with pytest.raises(MXNetError, match="registered"):
        mx.nd.Custom(mx.nd.array([1.0]), op_type="nope")


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

def test_config_catalog():
    assert mx.config.get("MXTPU_DECODE_THREADS") == 0
    os.environ["MXTPU_DECODE_THREADS"] = "3"
    try:
        assert mx.config.get("MXTPU_DECODE_THREADS") == 3
    finally:
        del os.environ["MXTPU_DECODE_THREADS"]
    with pytest.raises(MXNetError, match="unknown"):
        mx.config.get("NOT_A_KNOB")
    desc = mx.config.describe()
    assert "MXNET_ENGINE_TYPE" in desc and "MXTPU_DECODE_THREADS" in desc
    os.environ["MXNET_TOTALLY_BOGUS_KNOB"] = "1"
    try:
        assert "MXNET_TOTALLY_BOGUS_KNOB" in mx.config.check_env()
    finally:
        del os.environ["MXNET_TOTALLY_BOGUS_KNOB"]
    os.environ["DMLC_NUM_WORKER"] = "xyz"
    try:
        with pytest.raises(MXNetError, match="valid int"):
            mx.config.get("DMLC_NUM_WORKER")
    finally:
        del os.environ["DMLC_NUM_WORKER"]


# ---------------------------------------------------------------------------
# preemption handler
# ---------------------------------------------------------------------------

def test_preemption_handler_saves_then_exits(tmp_path):
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.checkpoint import (TrainCheckpoint,
                                      install_preemption_handler)
    from mxnet_tpu.gluon import loss as gloss, nn

    net = nn.Dense(2, in_units=4)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.1))
    step = par.TrainStep(net, gloss.L2Loss(),
                         opt.SGD(learning_rate=0.01), mesh=None)
    r = np.random.default_rng(0)
    x = mx.nd.array(r.standard_normal((4, 4)), dtype="float32")
    y = mx.nd.array(r.standard_normal((4, 2)), dtype="float32")
    for _ in range(3):
        step(x, y)
    ckpt = TrainCheckpoint(str(tmp_path / "pre"))
    fired = {}
    remove = install_preemption_handler(
        ckpt, step, get_step=lambda: step.step_count,
        get_cursor=lambda: {"batch": 3}, signals=[signal.SIGUSR1])
    # replace the chained default action so the test process survives
    try:
        orig_raise = signal.raise_signal

        def fake_raise(signum):
            fired["signum"] = signum

        signal.raise_signal = fake_raise
        os.kill(os.getpid(), signal.SIGUSR1)
    finally:
        signal.raise_signal = orig_raise
        remove()
    assert fired.get("signum") == signal.SIGUSR1
    assert ckpt.latest_step() == 3
    cursor = ckpt.restore(step)
    assert cursor == {"batch": 3}
    ckpt.close()


def test_quantized_conv_matches_float_within_tolerance():
    """int8 conv (int32 MXU accumulation) ≈ f32 conv (parity:
    quantized_conv + requantize)."""
    from mxnet_tpu.contrib.quantization import QuantizedConv2D
    from mxnet_tpu.gluon import nn as gnn

    conv = gnn.Conv2D(8, 3, padding=1, strides=2, in_channels=4,
                      use_bias=True)
    mx.rng.seed(0)
    conv.initialize(mx.init.Xavier())
    r = np.random.default_rng(0)
    x = mx.nd.array(r.standard_normal((2, 4, 12, 12)), dtype="float32")
    ref = conv(x).asnumpy()
    q = QuantizedConv2D(conv, act_amax=float(np.abs(x.asnumpy()).max()))
    got = q(x).asnumpy()
    assert got.shape == ref.shape
    denom = np.abs(ref).max()
    assert np.abs(got - ref).max() / denom < 0.06, \
        np.abs(got - ref).max() / denom


def test_quantize_net_conv_resnet_block():
    """A conv->BN->relu->conv block quantized via quantize_net stays
    within tolerance of the float forward (VERDICT r4 #8 'quantized
    resnet block ≈ fp32')."""
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu.gluon import nn as gnn

    net = gnn.HybridSequential()
    net.add(gnn.Conv2D(8, 3, padding=1, in_channels=3),
            gnn.Activation("relu"),
            gnn.Conv2D(8, 1, in_channels=8),
            gnn.GlobalAvgPool2D(), gnn.Dense(4, in_units=8))
    mx.rng.seed(1)
    net.initialize(mx.init.Xavier())
    r = np.random.default_rng(1)
    calib = [mx.nd.array(r.standard_normal((2, 3, 16, 16)),
                         dtype="float32") for _ in range(4)]
    ref = net(calib[0]).asnumpy()
    quantize_net(net, calib, calib_mode="entropy")
    from mxnet_tpu.contrib.quantization import (QuantizedConv2D,
                                                QuantizedDense)
    kinds = [type(c).__name__ for c in net._children.values()]
    assert "QuantizedConv2D" in kinds and "QuantizedDense" in kinds
    got = net(calib[0]).asnumpy()
    denom = np.abs(ref).max()
    assert np.abs(got - ref).max() / denom < 0.12, \
        np.abs(got - ref).max() / denom


def test_entropy_and_percentile_calibration_clip_outliers():
    """With a heavy outlier, entropy/percentile thresholds sit far below
    |max| (the whole point of calibrate.cc); minmax tracks the outlier."""
    from mxnet_tpu.contrib.quantization import calib_ranges
    from mxnet_tpu.gluon import nn as gnn

    net = gnn.HybridSequential()
    net.add(gnn.Dense(4, in_units=16))
    mx.rng.seed(2)
    net.initialize(mx.init.Xavier())
    r = np.random.default_rng(3)
    base = r.standard_normal((64, 16)).astype(np.float32)
    base[0, 0] = 1000.0  # one wild outlier
    data = [mx.nd.array(base, dtype="float32")]
    d = net._children and list(net._children.values())
    mm = calib_ranges(net, data, calib_mode="minmax")
    en = calib_ranges(net, data, calib_mode="entropy")
    pc = calib_ranges(net, data, calib_mode="percentile",
                      percentile=99.9)
    (k,) = mm.keys()
    assert mm[k] >= 999.0
    assert en[k] < 100.0, en
    assert pc[k] < 100.0, pc
