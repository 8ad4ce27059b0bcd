"""Neural-network core ops.

Reference parity: src/operator/nn/** (convolution, fully_connected,
batch_norm, layer_norm, group_norm, pooling, activation, softmax, dropout)
and src/operator/rnn-inl.h (fused RNN). Kernel bodies are XLA primitives:
conv_general_dilated / dot_general hit the MXU directly (replacing the
reference's cuDNN/cuBLAS wrappers, SURVEY.md §2.3 row "cuDNN/cuBLAS
wrappers"), reduce_window replaces pooling kernels, and lax.scan replaces
the cuDNN fused RNN. Layout is NCHW for API parity; XLA:TPU's layout
assignment rewrites to its preferred tiling internally.
"""
from __future__ import annotations

import builtins
import math as _pymath
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .. import rng as _rng
from ..autograd import is_training
from .registry import op

# ---------------------------------------------------------------------------
# fully connected / dense
# ---------------------------------------------------------------------------

# w8 weight serving (ISSUE 19): int8 weight codes with per-out-tile f32
# dequant scales, fused into the matmul. The registry maps id(codes
# array) -> (codes, scale): `apply_op` strips NDArray wrappers before the
# kernel runs, so weight identity — not an attribute — is the only signal
# that survives into FullyConnected. The serving engine registers its
# traced code arrays inside the unified body (same trace-time ctx
# discipline as gpt2's `_adapter_ctx`/`_tp_ctx`) and deregisters in a
# `finally`; `weight_quant.quantize_dense_weights` registers eager code
# arrays persistently for vision-model dense layers. Entries hold a
# strong ref to the codes array so an id() is never recycled while
# registered.
_W8_SCALES = {}


def register_w8_weight(codes, scale):
    """Register `scale` as the per-out-tile dequant scales for the int8
    `codes` array. scale is 1-D f32 with size dividing codes.shape[0];
    FullyConnected applies it to the matmul OUTPUT (valid because the
    scale depends only on the out index), so HBM weight traffic stays
    one byte per element."""
    _W8_SCALES[id(codes)] = (codes, scale)
    return codes


def deregister_w8_weight(codes):
    _W8_SCALES.pop(id(codes), None)


def _w8_dequant_matmul(x, codes):
    """x @ codes.T with the registered per-out-tile scales applied as an
    output epilogue: y[..., o] = (x @ codes.T)[..., o] * scale[o // tile].
    XLA fuses the int8->f32 convert into the dot's operand read, and the
    epilogue into the dot's consumer, so the weight slab is read at one
    byte per element."""
    entry = _W8_SCALES.get(id(codes))
    if entry is None:
        raise MXNetError(
            "int8 weight reached FullyConnected without registered w8 "
            "dequant scales (register_w8_weight)")
    scale = entry[1]
    acc = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    y = jnp.matmul(x, codes.astype(acc).T)
    tile = y.shape[-1] // scale.shape[0]
    if tile * scale.shape[0] != y.shape[-1]:
        raise MXNetError(
            f"w8 scale count {scale.shape[0]} does not divide out dim "
            f"{y.shape[-1]}")
    y = jnp.reshape(y, y.shape[:-1] + (scale.shape[0], tile))
    y = y * scale.astype(acc)[..., None]
    return jnp.reshape(y, y.shape[:-2] + (scale.shape[0] * tile,))


@op("FullyConnected")
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    """Parity: src/operator/nn/fully_connected.cc. weight is (num_hidden, K)
    as in the reference; lowered to dot_general (MXU). An int8 weight is a
    w8 code array: the registered per-out-tile scales are applied to the
    matmul output before the bias (fused dequant, ISSUE 19)."""
    x = data
    if flatten and x.ndim > 2:
        x = jnp.reshape(x, (x.shape[0], -1))
    if weight.dtype == jnp.int8:
        y = _w8_dequant_matmul(x, weight)
    else:
        y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


fully_connected = FullyConnected

# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

def _tup(v, n, fill=0):
    """Normalize an int/tuple/None window param to an n-tuple. The
    reference treats an absent or all-zero stride/dilate tuple as "use
    the default" (dmlc::Parameter empty-tuple convention), so when
    `fill` is nonzero an all-zero value also resolves to the fill."""
    if v is None:
        return (fill,) * n
    v = (v,) * n if isinstance(v, int) else tuple(int(i) for i in v)
    if fill and v and builtins.all(i == 0 for i in v):
        return (fill,) * n
    return v


@op("Convolution")
def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, cudnn_tune=None, cudnn_off=False, workspace=None):
    """Parity: src/operator/nn/convolution.cc. NCHW/OIHW semantics; XLA
    emits an MXU conv. Supports 1D/2D/3D by kernel rank, grouped conv via
    feature_group_count."""
    nd = weight.ndim - 2
    stride = _tup(stride, nd, fill=1)
    dilate = _tup(dilate, nd, fill=1)
    pad = _tup(pad, nd)
    spatial = "DHW"[-nd:] if nd <= 3 else None
    if spatial is None:
        raise MXNetError(f"unsupported conv rank {nd}")
    lhs_spec = "NC" + spatial
    rhs_spec = "OI" + spatial
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    # NOTE: no preferred_element_type here — XLA:TPU accumulates bf16 convs
    # in f32 on the MXU regardless, and this jax version's conv transpose
    # rule rejects mixed primal/cotangent dtypes when it is set (bf16
    # training would crash in backward)
    y = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        y = y + jnp.reshape(bias, (1, -1) + (1,) * nd)
    return y


conv = Convolution


@op("Deconvolution")
def Deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, target_shape=None,
                  num_filter=None, num_group=1, no_bias=True, layout=None,
                  cudnn_tune=None, cudnn_off=False, workspace=None):
    """Parity: src/operator/nn/deconvolution.cc — gradient of conv w.r.t.
    input, i.e. transposed convolution."""
    nd = weight.ndim - 2
    stride = _tup(stride, nd, fill=1)
    dilate = _tup(dilate, nd, fill=1)
    pad = _tup(pad, nd)
    adj = _tup(adj, nd)
    if num_group != 1:
        xs = jnp.split(data, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        parts = [_deconv(x, w, stride, pad, dilate, adj) for x, w in zip(xs, ws)]
        y = jnp.concatenate(parts, axis=1)
    else:
        y = _deconv(data, weight, stride, pad, dilate, adj)
    if bias is not None and not no_bias:
        y = y + jnp.reshape(bias, (1, -1) + (1,) * nd)
    return y


def _deconv(x, w, stride, pad, dilate, adj):
    nd = w.ndim - 2
    spatial = "DHW"[-nd:]
    # transposed conv = lhs-dilated conv with flipped kernel, IO swapped
    w_flip = w
    for ax in range(2, 2 + nd):
        w_flip = jnp.flip(w_flip, axis=ax)
    w_flip = jnp.swapaxes(w_flip, 0, 1)  # (I,O,...) -> treat I as output
    k = [(w.shape[2 + i] - 1) * dilate[i] for i in range(nd)]
    padding = [(k[i] - pad[i], k[i] - pad[i] + adj[i]) for i in range(nd)]
    lhs_spec = "NC" + spatial
    rhs_spec = "OI" + spatial
    dn = lax.conv_dimension_numbers(x.shape, w_flip.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    return lax.conv_general_dilated(
        x, w_flip, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@op("BatchNorm")
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
              momentum=0.9, fix_gamma=True, use_global_stats=False,
              output_mean_var=False, axis=1, cudnn_off=False):
    """Parity: src/operator/nn/batch_norm.cc. Pure-functional: in training
    returns (y, batch_mean, batch_var); the Gluon layer owns the moving-stat
    update (the reference mutates them inside the kernel via FMutateInputs —
    impossible and unnecessary under XLA purity).

    TPU formulation: training stats are ONE pass — E[x] and E[x^2] as two
    side reductions XLA fuses into the producing conv's epilogue — and the
    normalize is folded to y = x*a + b with per-channel a, b precomputed in
    f32 then cast to the activation dtype, so the apply pass is a single
    bf16 FMA instead of subtract/convert/mul chains."""
    red = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1
                   for i in range(data.ndim))
    g32 = (jnp.ones_like(gamma) if fix_gamma else gamma).astype(jnp.float32)
    training = is_training() and not use_global_stats
    if training:
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        if data.dtype == jnp.bfloat16:
            # ONE pass: E[x^2] - E[x]^2 with f32 accumulation. Safe for
            # bf16 inputs only: representable bf16 data has
            # std >= ~0.004*|mean| (the mantissa spacing), which bounds
            # the f32 cancellation error at <1% of the true variance —
            # while f32 inputs can carry |mean|/std > 3e3 where this
            # formula is catastrophically wrong, so they use two-pass.
            # Clamp guards the residual negative-epsilon case for rsqrt.
            var = jnp.maximum(
                jnp.mean(x32 * x32, axis=red) - mean * mean, 0.0)
        else:
            var = jnp.var(x32, axis=red)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
    inv = lax.rsqrt(var + eps)
    # per-channel scale/shift in f32, applied in activation dtype: one FMA
    a = (g32 * inv).astype(data.dtype)
    b = (beta.astype(jnp.float32) - g32 * inv * mean).astype(data.dtype)
    y = data * jnp.reshape(a, bshape) + jnp.reshape(b, bshape)
    if training or output_mean_var:
        return (y, mean.astype(moving_mean.dtype), var.astype(moving_var.dtype))
    return y


@op("LayerNorm")
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Parity: src/operator/nn/layer_norm.cc (fast CUDA path → XLA fuses the
    reductions+scale into one kernel on TPU)."""
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    y = ((x32 - mean) * inv).astype(data.dtype)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    y = y * jnp.reshape(gamma, bshape) + jnp.reshape(beta, bshape)
    if output_mean_var:
        return (y, jnp.squeeze(mean, axis), jnp.squeeze(var, axis))
    return y


@op("GroupNorm")
def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5,
              output_mean_var=False):
    """Parity: src/operator/nn/group_norm.cc. NC+ layout, groups over C."""
    n, c = data.shape[0], data.shape[1]
    g = num_groups
    xg = jnp.reshape(data.astype(jnp.float32), (n, g, c // g, -1))
    mean = jnp.mean(xg, axis=(2, 3), keepdims=True)
    var = jnp.var(xg, axis=(2, 3), keepdims=True)
    y = (xg - mean) * lax.rsqrt(var + eps)
    y = jnp.reshape(y, data.shape).astype(data.dtype)
    bshape = (1, c) + (1,) * (data.ndim - 2)
    y = y * jnp.reshape(gamma, bshape) + jnp.reshape(beta, bshape)
    if output_mean_var:
        return (y, jnp.reshape(mean, (n, g)), jnp.reshape(var, (n, g)))
    return y


@op("InstanceNorm")
def InstanceNorm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=red, keepdims=True)
    var = jnp.var(x32, axis=red, keepdims=True)
    y = ((x32 - mean) * lax.rsqrt(var + eps)).astype(data.dtype)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return y * jnp.reshape(gamma, bshape) + jnp.reshape(beta, bshape)


@op("L2Normalization")
def L2Normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        keep = True
    elif mode == "channel":
        red = (1,)
        keep = True
    elif mode == "spatial":
        red = tuple(range(2, data.ndim))
        keep = True
    else:
        raise MXNetError(f"unknown L2Normalization mode {mode}")
    n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=keep) + eps)
    return data / n


@op("LRN")
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (NCHW)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    ssum = lax.reduce_window(
        padded, 0.0, lax.add,
        (1, nsize) + (1,) * (data.ndim - 2),
        (1, 1) + (1,) * (data.ndim - 2), "valid")
    return data / jnp.power(knorm + alpha / nsize * ssum, beta)


@op("rms_norm")
def rms_norm(data, gamma, axis=-1, eps=1e-6):
    """RMSNorm (modern-LLM staple; no reference analog, provided natively)."""
    x32 = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    y = (x32 * lax.rsqrt(ms + eps)).astype(data.dtype)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    return y * jnp.reshape(gamma, bshape)


# ---------------------------------------------------------------------------
# activation
# ---------------------------------------------------------------------------

@op("Activation")
def Activation(data, act_type="relu"):
    """Parity: src/operator/nn/activation.cc."""
    return _act(data, act_type)


def _act(x, act_type):
    if act_type == "relu":
        return jax.nn.relu(x)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return x / (1 + jnp.abs(x))
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(x)
    if act_type == "mish":
        return x * jnp.tanh(jax.nn.softplus(x))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if act_type == "silu" or act_type == "swish":
        return jax.nn.silu(x)
    raise MXNetError(f"unknown act_type {act_type}")


@op("LeakyReLU")
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334):
    """Parity: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu).
    rrelu uses the fixed mean slope in inference and sampled slope in
    training, as the reference does."""
    x = data
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "prelu":
        g = gamma
        if g.ndim < x.ndim:
            g = jnp.reshape(g, (1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x > 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * (jnp.exp(x) - 1))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        if is_training():
            k = _rng.next_key()
            s = jax.random.uniform(k, x.shape, jnp.float32, lower_bound,
                                   upper_bound).astype(x.dtype)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(x > 0, x, s * x)
    raise MXNetError(f"unknown LeakyReLU act_type {act_type}")


softplus = op("softplus")(lambda x: jax.nn.softplus(x))
gelu = op("gelu")(lambda x, approximate=False: jax.nn.gelu(x, approximate=approximate))
silu = op("silu")(lambda x: jax.nn.silu(x))
hard_sigmoid = op("hard_sigmoid")(
    lambda x, alpha=0.2, beta=0.5: jnp.clip(alpha * x + beta, 0, 1))
log_sigmoid = op("log_sigmoid")(lambda x: jax.nn.log_sigmoid(x))

# ---------------------------------------------------------------------------
# softmax family
# ---------------------------------------------------------------------------

@op("softmax")
def softmax(data, length=None, axis=-1, temperature=None, use_length=False):
    """Parity: src/operator/nn/softmax.cc (incl. masked/length variant)."""
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        pos = jnp.arange(x.shape[axis])
        bshape = [1] * x.ndim
        bshape[axis] = x.shape[axis]
        mask = jnp.reshape(pos, bshape) < jnp.reshape(
            jnp.asarray(length), (-1,) + (1,) * (x.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@op("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


@op("masked_softmax")
def masked_softmax(data, mask, axis=-1, temperature=1.0):
    x = data / temperature if temperature != 1.0 else data
    x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    return jnp.where(mask, out, 0.0)


@op("softmin")
def softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@op("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    lsm = jax.nn.log_softmax(data, axis=-1)
    lbl = jnp.asarray(label, jnp.int32)
    nll = -jnp.take_along_axis(lsm, lbl[..., None], axis=-1)[..., 0]
    return jnp.sum(nll)


@op("SoftmaxOutput")
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1,
                  multi_output=False, use_ignore=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0):
    """Legacy symbolic-era op: forward = softmax (the CE gradient part is
    handled by the loss in Gluon-era code)."""
    return jax.nn.softmax(data, axis=-1)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

@op("Dropout")
def Dropout(data, p=0.5, mode="training", axes=None, cudnn_off=False):
    """Parity: src/operator/nn/dropout-inl.h — inverted dropout, engine RNG.
    Active only in autograd training mode (or mode='always')."""
    if p <= 0 or (mode != "always" and not is_training()):
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    k = _rng.next_key()
    keep = jax.random.bernoulli(k, 1.0 - p, shape)
    return jnp.where(keep, data / (1.0 - p), jnp.zeros((), data.dtype))


dropout = Dropout

# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@op("Pooling")
def Pooling(data, kernel=None, pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, cudnn_off=False, layout=None):
    """Parity: src/operator/nn/pooling.cc via lax.reduce_window."""
    nd = data.ndim - 2
    if global_pool:
        red = tuple(range(2, data.ndim))
        if pool_type == "max":
            out = jnp.max(data, axis=red, keepdims=True)
        elif pool_type in ("avg", "sum"):
            out = (jnp.mean if pool_type == "avg" else jnp.sum)(
                data, axis=red, keepdims=True)
        elif pool_type == "lp":
            out = jnp.power(jnp.sum(jnp.power(jnp.abs(data), 2), axis=red,
                                    keepdims=True), 0.5)
        else:
            raise MXNetError(f"unknown pool_type {pool_type}")
        return out
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd, fill=1)
    pad = _tup(pad, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode output: widen right pad so ceil division is covered
        extra = []
        for i in range(nd):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            out_sz = _pymath.ceil((in_sz - kernel[i]) / stride[i]) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - in_sz
            extra.append(builtins.max(0, need))
        padding = ((0, 0), (0, 0)) + tuple(
            (p, p + e) for p, e in zip(pad, extra))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / float(_pymath.prod(kernel))
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.square(jnp.abs(data)), 0.0, lax.add,
                              window, strides, padding)
        return jnp.sqrt(s)
    raise MXNetError(f"unknown pool_type {pool_type}")


pooling = Pooling


@op("UpSampling")
def UpSampling(data, scale=2, sample_type="nearest", num_args=1):
    """Parity: src/operator/nn/upsampling.cc (nearest)."""
    if sample_type != "nearest":
        raise MXNetError("UpSampling bilinear: use contrib.BilinearResize2D")
    n, c, h, w = data.shape
    out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return out


@op("BilinearResize2D")
def BilinearResize2D(data, height=None, width=None, scale_height=None,
                     scale_width=None, mode="size", align_corners=True):
    n, c, h, w = data.shape
    if height is None:
        height = int(h * scale_height)
        width = int(w * scale_width)
    return jax.image.resize(data, (n, c, height, width), method="bilinear")


# ---------------------------------------------------------------------------
# fused RNN (parity: src/operator/rnn-inl.h; implemented as lax.scan)
# ---------------------------------------------------------------------------

def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def unpack_rnn_params(parameters, mode, num_layers, input_size, state_size,
                      bidirectional=False, proj_size=None):
    """Unpack the reference's flat cuDNN-layout parameter vector:
    all weights (per layer, per direction: W_i2h then W_h2h), then all
    biases (b_i2h then b_h2h). Gate order: LSTM [i,f,g,o], GRU [r,z,n]."""
    G = _gates(mode)
    D = 2 if bidirectional else 1
    H = state_size
    idx = 0
    layers = []
    p = parameters
    for layer in range(num_layers):
        I = input_size if layer == 0 else H * D
        dirs = []
        for d in range(D):
            w_i2h = lax.dynamic_slice(p, (idx,), (G * H * I,)).reshape(G * H, I)
            idx += G * H * I
            w_h2h = lax.dynamic_slice(p, (idx,), (G * H * H,)).reshape(G * H, H)
            idx += G * H * H
            dirs.append({"w_i2h": w_i2h, "w_h2h": w_h2h})
        layers.append(dirs)
    for layer in range(num_layers):
        for d in range(D):
            b_i2h = lax.dynamic_slice(p, (idx,), (G * H,))
            idx += G * H
            b_h2h = lax.dynamic_slice(p, (idx,), (G * H,))
            idx += G * H
            layers[layer][d]["b_i2h"] = b_i2h
            layers[layer][d]["b_h2h"] = b_h2h
    return layers


def rnn_param_size(mode, num_layers, input_size, state_size,
                   bidirectional=False):
    G = _gates(mode)
    D = 2 if bidirectional else 1
    H = state_size
    total = 0
    for layer in range(num_layers):
        I = input_size if layer == 0 else H * D
        total += D * (G * H * I + G * H * H + 2 * G * H)
    return total


def _cell_step(mode, params, x, states):
    """One timestep. x: (B, I); states: (h,) or (h, c)."""
    G_pre = jnp.matmul(x, params["w_i2h"].T) + params["b_i2h"] + \
        jnp.matmul(states[0], params["w_h2h"].T) + params["b_h2h"]
    H = states[0].shape[-1]
    if mode == "lstm":
        i, f, g, o = jnp.split(G_pre, 4, axis=-1)
        c = jax.nn.sigmoid(f) * states[1] + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, (h, c)
    if mode == "gru":
        # GRU with linear_before_reset=True (cuDNN/reference semantics)
        xr, xz, xn = jnp.split(jnp.matmul(x, params["w_i2h"].T) +
                               params["b_i2h"], 3, axis=-1)
        hr, hz, hn = jnp.split(jnp.matmul(states[0], params["w_h2h"].T) +
                               params["b_h2h"], 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h = (1 - z) * n + z * states[0]
        return h, (h,)
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu
    h = act(G_pre)
    return h, (h,)


def _run_layer(mode, params, xs, h0, c0, reverse=False):
    """xs: (T, B, I). Returns (T, B, H), h_T, c_T."""
    init = (h0, c0) if mode == "lstm" else (h0,)

    def step(carry, x):
        out, new = _cell_step(mode, params, x, carry)
        return new, out

    final, ys = lax.scan(step, init, xs, reverse=reverse)
    hT = final[0]
    cT = final[1] if mode == "lstm" else None
    return ys, hT, cT


@op("RNN")
def RNN(data, parameters, state, state_cell=None, state_size=None,
        num_layers=1, mode="lstm", bidirectional=False, p=0.0,
        state_outputs=True, projection_size=None, use_sequence_length=False,
        sequence_length=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None):
    """Parity: src/operator/rnn-inl.h fused RNN. data: (T, B, I); state:
    (L*D, B, H). Implemented as stacked lax.scan — XLA unrolls/pipelines
    per-step matmuls onto the MXU (the cuDNN-fused-RNN replacement)."""
    if projection_size is not None:
        raise MXNetError("RNN projection_size not supported")
    T, B, I = data.shape
    H = state_size
    D = 2 if bidirectional else 1
    layers = unpack_rnn_params(parameters, mode, num_layers, I, H,
                               bidirectional)
    x = data
    h_outs, c_outs = [], []
    for li, dirs in enumerate(layers):
        h0f = state[li * D]
        c0f = state_cell[li * D] if mode == "lstm" else None
        yf, hf, cf = _run_layer(mode, dirs[0], x, h0f, c0f)
        if bidirectional:
            h0b = state[li * D + 1]
            c0b = state_cell[li * D + 1] if mode == "lstm" else None
            yb, hb, cb = _run_layer(mode, dirs[1], x, h0b, c0b, reverse=True)
            x = jnp.concatenate([yf, yb], axis=-1)
            h_outs += [hf, hb]
            if mode == "lstm":
                c_outs += [cf, cb]
        else:
            x = yf
            h_outs.append(hf)
            if mode == "lstm":
                c_outs.append(cf)
        if p > 0 and li < num_layers - 1 and is_training():
            k = _rng.next_key()
            keep = jax.random.bernoulli(k, 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
    outs = [x]
    if state_outputs:
        outs.append(jnp.stack(h_outs, axis=0))
        if mode == "lstm":
            outs.append(jnp.stack(c_outs, axis=0))
    return tuple(outs) if len(outs) > 1 else outs[0]


# ---------------------------------------------------------------------------
# attention (reference: src/operator/contrib/transformer.cu interleaved
# matmuls — here one fused op; Pallas flash kernel plugs in underneath for
# long sequences, see mxnet_tpu/ops/attention.py)
# ---------------------------------------------------------------------------

def _target_platform(x):
    """Platform the op will execute on: an active Device scope wins (so the
    check_consistency cpu-vs-accelerator oracle stays honest), else the
    committed placement of the input, else jax's default backend."""
    from ..base import current_scope
    dev = current_scope("device")
    if dev is not None:
        try:
            return dev.jax_device.platform
        except Exception:
            pass  # scope names an unavailable backend — fall through
    devices = getattr(x, "devices", None)
    if devices is not None:
        try:
            ds = devices()
            if ds:
                return next(iter(ds)).platform
        except Exception:
            pass
    return jax.default_backend()

def _fused_attention(q, k, v, mask, scale, causal, dropout_p, key, layout):
    """The fused Pallas kernel — partitioned by hand under a multi-device
    mesh. The SPMD partitioner cannot split a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned"), so with a mesh active
    the call runs in a shard_map: batch over "dp", heads over "tp" — the
    activation layout megatron_dense_rules produces. Attention is
    independent per batch row and per head, so the map needs no
    collective; an axis that does not divide its dim is left replicated.
    Each shard folds its mesh position into the dropout key (the kernel
    seeds from LOCAL grid ids, which repeat across shards)."""
    from . import pallas_attention as _pa
    from ..parallel.mesh import (AXIS_DP, AXIS_TP, PartitionSpec,
                                 current_mesh, shard_map_compat)

    def call(qb, kb, vb, mb, kk):
        return _pa.fused_attention(qb, kb, vb, mask=mb, scale=scale,
                                   causal=causal, dropout_p=dropout_p,
                                   key=kk, layout=layout)

    mesh = current_mesh()
    h_dim = 2 if layout == "BTHD" else 1
    manual = jax.sharding.get_abstract_mesh().manual_axes
    ba, ha = (
        ax if (mesh is not None and ax in mesh.axis_names
               and mesh.shape[ax] > 1 and ax not in manual
               and q.shape[dim] % mesh.shape[ax] == 0) else None
        for ax, dim in ((AXIS_DP, 0), (AXIS_TP, h_dim)))
    if ba is None and ha is None:
        return call(q, k, v, mask, key)
    qspec = [ba, None, None, None]
    qspec[h_dim] = ha
    qspec = PartitionSpec(*qspec)
    args, in_specs = [q, k, v], [qspec, qspec, qspec]
    if mask is not None:
        args.append(jnp.broadcast_to(mask, (q.shape[0],) + mask.shape[1:]))
        in_specs.append(PartitionSpec(ba, None, None, None))
    if key is not None:
        args.append(key)
        in_specs.append(PartitionSpec())

    def local(qb, kb, vb, *rest):
        rest = list(rest)
        mb = rest.pop(0) if mask is not None else None
        kk = rest.pop(0) if key is not None else None
        if kk is not None:
            for ax in (ba, ha):
                if ax is not None:
                    kk = jax.random.fold_in(kk, lax.axis_index(ax))
        return call(qb, kb, vb, mb, kk)

    return shard_map_compat(local, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=qspec, check_rep=False)(*args)


def _sp_auto_impl(q, k, mask, train_drop):
    """The sequence-parallel route impl='auto' should take, or None.

    Selected by mesh axis mapping — no model-code changes (SURVEY.md
    §5.7): requires an active mesh with a real sp axis, self-attention
    shapes divisible by the mesh axes, no attention-prob dropout, and a
    key-padding-style mask. Between the two SP kernels: 'ulysses' (head
    all-to-all, 2 collectives, full-T scores) when the per-device head
    count divides by sp and T is moderate; 'ring' (ppermute KV rotation,
    O(T_local) memory) otherwise."""
    from ..parallel.mesh import AXIS_SP, current_mesh
    from ..parallel.sp import sp_enabled
    mesh = current_mesh()
    if train_drop or not sp_enabled(mesh):
        return None
    n_sp = mesh.shape[AXIS_SP]
    B, H, Tq, _ = q.shape
    Tk = k.shape[-2]
    if Tq != Tk or Tq % n_sp:
        return None
    if mask is not None and (mask.shape[1] != 1 or mask.shape[-2] != 1):
        return None  # per-query masks don't shard; key padding only
    for ax, dim in (("dp", B), ("tp", H)):
        if ax in mesh.axis_names and dim % mesh.shape[ax]:
            return None
    n_tp = mesh.shape["tp"] if "tp" in mesh.axis_names else 1
    if (H // n_tp) % n_sp == 0 and Tq <= 4096:
        return "ulysses"
    return "ring"


@op("dot_product_attention")
def dot_product_attention(q, k, v, mask=None, scale=None, causal=False,
                          dropout_p=0.0, impl="auto", layout="BHTD"):
    """q,k,v: (B, H, T, D) — or (B, T, H, D) with layout="BTHD", the
    shape a head-split reshape produces directly; the fused Pallas
    kernel and the XLA einsum path consume BTHD natively (no physical
    relayout copies — measured ~6.6 ms/step on BERT-base), other impls
    transpose internally. impl:
    'auto'|'xla'|'fused'|'flash'|'ring'|'ulysses'.

    'fused' is the Pallas TPU kernel (ops/pallas_attention.py): whole-row
    softmax→dropout→PV in VMEM with the dropout mask drawn from the
    on-core hardware PRNG — the hot path for T <= 1024 (BERT/GPT-2
    shapes), with or without dropout. 'flash' is the blockwise O(T)
    kernel in ops/attention.py for long sequences; 'ring' and 'ulysses'
    the sequence-parallel paths (ppermute KV rotation vs head
    all-to-all; parallel/sp.py). 'auto' picks a sequence-parallel path
    whenever the active mesh has a real sp axis and shapes/dropout allow
    (ulysses when per-device heads divide by sp and T is moderate, ring
    otherwise — so sequence parallelism needs no model-code changes),
    else fused on TPU when shapes allow, flash for long no-dropout
    sequences, else one XLA softmax-attention. Fully-masked rows yield
    zeros on every path."""
    if mask is not None and mask.ndim == 2:
        # (B, Tk) key-padding → canonical (B, 1, 1, Tk) for every path
        mask = mask[:, None, None, :]
    train_drop = dropout_p > 0 and is_training()
    if layout == "BTHD":
        # native-BTHD routes first (fused kernel / XLA einsum); anything
        # else transposes to canonical BHTD and re-enters
        bhtd = lambda x: jnp.swapaxes(x, 1, 2)
        if impl in ("auto", "fused"):
            from . import pallas_attention as _pa
            if (_target_platform(q) == "tpu"
                    and _pa.supported(q, k, mask, layout="BTHD")
                    and (impl == "fused" or _sp_auto_impl(
                        bhtd(q), bhtd(k), mask, train_drop) is None)):
                key = _rng.next_key() if train_drop else None
                return _fused_attention(
                    q, k, v, mask, scale, causal,
                    dropout_p if train_drop else 0.0, key, "BTHD")
        if impl == "xla":
            d = q.shape[-1]
            s = scale if scale is not None else 1.0 / _pymath.sqrt(d)
            logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k) * s).astype(
                jnp.float32)
            if causal:
                Tq, Tk = logits.shape[-2], logits.shape[-1]
                cm = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
                logits = jnp.where(cm, logits, -jnp.inf)
            if mask is not None:
                logits = jnp.where(mask, logits, -jnp.inf)
            w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            if causal or mask is not None:
                any_valid = jnp.isfinite(logits).any(axis=-1,
                                                     keepdims=True)
                w = jnp.where(any_valid, w, jnp.zeros((), w.dtype))
            if train_drop:
                kk = _rng.next_key()
                keep = jax.random.bernoulli(kk, 1.0 - dropout_p, w.shape)
                w = jnp.where(keep, w / (1.0 - dropout_p),
                              jnp.zeros((), w.dtype))
            return jnp.einsum("bhqk,bkhd->bqhd", w, v)
        # raw_fn: the plain jax-array function (we are already inside
        # the op funnel; re-entering the NDArray wrapper would nest tapes)
        out = dot_product_attention.raw_fn(
            bhtd(q), bhtd(k), bhtd(v), mask=mask, scale=scale,
            causal=causal, dropout_p=dropout_p, impl=impl)
        return jnp.swapaxes(out, 1, 2)
    if impl == "auto":
        sp_impl = _sp_auto_impl(q, k, mask, train_drop)
        if sp_impl is not None:
            impl = sp_impl
    if impl in ("ring", "ulysses"):
        # sequence-parallel paths: T sharded over the mesh's "sp" axis —
        # ring rotates KV via ppermute (O(T_local) memory); ulysses
        # all-to-alls to head sharding (2 collectives, full-T scores).
        # parallel/sp.py; SURVEY.md §5.7.
        from ..parallel import sp as _sp
        if train_drop:
            raise MXNetError(
                f"impl={impl!r} does not support attention-probability "
                "dropout (the mask would need to be consistent across "
                "devices); set attention dropout to 0 under sequence "
                "parallelism")
        fn = _sp.ring_attention if impl == "ring" \
            else _sp.ulysses_attention
        return fn(q, k, v, mask=mask, causal=causal, scale=scale)
    if impl in ("auto", "fused"):
        from . import pallas_attention as _pa
        on_tpu = _target_platform(q) == "tpu"
        ok = on_tpu and _pa.supported(q, k, mask)
        if ok:
            key = _rng.next_key() if train_drop else None
            return _fused_attention(
                q, k, v, mask, scale, causal,
                dropout_p if train_drop else 0.0, key, "BHTD")
        if impl == "fused":
            # An explicit request must not silently measure a different
            # kernel; only impl='auto' may fall back quietly.
            warnings.warn(
                "impl='fused' requested but the Pallas kernel is unavailable "
                f"(platform={_target_platform(q)!r}, "
                f"shape_supported={_pa.supported(q, k, mask)}); falling back "
                "to the XLA path", stacklevel=2)
    if impl == "flash" or (impl == "auto" and dropout_p == 0.0
                           and q.shape[-2] >= 1024):
        from . import attention as _att
        if _att.flash_eligible(q, k, v, mask, dropout_p):
            return _att.flash_attention_data(q, k, v, mask=mask, scale=scale,
                                             causal=causal)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / _pymath.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    logits = logits.astype(jnp.float32)
    if causal:
        Tq, Tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if causal or mask is not None:
        # fully-masked rows: zeros, matching the flash kernel (softmax over
        # all -inf would yield NaN)
        any_valid = jnp.isfinite(logits).any(axis=-1, keepdims=True)
        w = jnp.where(any_valid, w, jnp.zeros((), w.dtype))
    if dropout_p > 0 and is_training():
        kk = _rng.next_key()
        keep = jax.random.bernoulli(kk, 1.0 - dropout_p, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_p), jnp.zeros((), w.dtype))
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)
