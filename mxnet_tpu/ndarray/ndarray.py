"""NDArray: the imperative tensor handle.

Reference parity: include/mxnet/ndarray.h + python/mxnet/ndarray/ndarray.py.
The reference NDArray is a shared Chunk (Storage handle + engine variable)
with async semantics: every op returns immediately, synchronization happens
at wait_to_read()/asnumpy()/waitall(). Here the chunk is a `jax.Array`,
whose PjRt buffer is exactly that async handle — dispatch is async by
construction and `block_until_ready` is the sync point, so the reference's
user-visible contract (program order per array, errors surfacing at sync)
is preserved without rebuilding the ThreadedEngine (SURVEY.md §7.1).

Differences by design (documented de-scopes):
  * Slices/views are functional (no aliased writes through views); `x[i] = v`
    mutates `x` itself via a functional scatter + rebind, bumping the
    handle's version so the autograd tape stays consistent.
  * NumPy broadcasting semantics everywhere (the reference's mx.np — its v2
    primary API — not the legacy mx.nd broadcast_* split).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from .. import autograd, device as _device
from ..base import MXNetError

__all__ = ["NDArray", "array", "waitall", "from_jax", "newaxis"]

newaxis = None


def _default_dtype(value):
    if isinstance(value, (bool, _np.bool_)):
        return jnp.bool_
    if isinstance(value, (int, _np.integer)):
        return jnp.int32
    return jnp.float32


class NDArray:
    """Imperative tensor. Wraps a jax.Array; integrates with the autograd
    tape (see mxnet_tpu.autograd) and the Device layer."""

    __slots__ = ("_data", "_node", "_grad", "_grad_req", "_version")

    # numpy should defer binary-op dispatch to us
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None, dtype=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array) or dtype is not None or ctx is not None:
            if dtype is None and not hasattr(data, "dtype"):
                dtype = _default_dtype(data) if _np.isscalar(data) else None
            data = jnp.asarray(data, dtype=dtype)
            if ctx is not None:
                data = jax.device_put(data, ctx.jax_device)
        self._data = data
        self._node = None  # autograd provenance ('node', Node, idx)
        self._grad = None
        self._grad_req = "null"
        self._version = 0

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def itemsize(self):
        return self._data.dtype.itemsize

    @property
    def nbytes(self):
        return self.size * self.itemsize

    @property
    def context(self):
        devs = getattr(self._data, "devices", None)
        if devs is None:
            return _device.cpu(0)
        return _device.from_jax_device(next(iter(self._data.devices())))

    ctx = context
    device = context

    @property
    def stype(self):
        """Storage type. Dense-only: the reference's row_sparse/csr storage
        is de-scoped on TPU (XLA has no sparse buffers); see
        ndarray/sparse.py for the documented shim."""
        return "default"

    @property
    def T(self):
        return self.transpose()

    # ------------------------------------------------------------------
    # synchronization (parity: async engine semantics)
    # ------------------------------------------------------------------
    def wait_to_read(self):
        self._data.block_until_ready()

    def wait_to_write(self):
        self._data.block_until_ready()

    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # autograd surface (parity: ndarray.py attach_grad/grad/backward/detach)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        self._grad = NDArray(jnp.zeros(self.shape, self.dtype))
        self._grad_req = grad_req
        self._node = None

    @property
    def grad(self):
        return self._grad

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph=retain_graph,
                          train_mode=train_mode)

    def detach(self):
        out = NDArray(self._data)
        return out

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = jnp.zeros(self.shape, self.dtype)

    # internal: rebind value in place (mutation with tape consistency)
    def _assign_from(self, other: "NDArray"):
        if other.shape != self.shape:
            raise MXNetError(
                f"in-place assign shape mismatch {other.shape} vs {self.shape}")
        self._data = jnp.asarray(other._data, self.dtype)
        self._node = other._node
        self._version += 1

    def _rebind(self, data, node=None):
        self._data = data
        self._node = node
        self._version += 1

    # ------------------------------------------------------------------
    # conversion / placement
    # ------------------------------------------------------------------
    def astype(self, dtype, copy=True):
        if not copy and jnp.dtype(dtype) == self.dtype:
            return self
        from ..ops import tensor as _t
        return _t.cast(self, dtype=dtype)

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device))

    as_in_ctx = as_in_context

    def to_device(self, ctx):
        return self.as_in_context(ctx)

    def copyto(self, other):
        """Parity: NDArray.copyto — cross-device copy (async via PjRt)."""
        if isinstance(other, _device.Device):
            return self.as_in_context(other)
        other._assign_from(NDArray(jax.device_put(
            self._data, other.context.jax_device)))
        return other

    def copy(self):
        return NDArray(jnp.copy(self._data))

    def as_nd_ndarray(self):
        return self

    def as_np_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        from ..ops import tensor as _t
        return _t._getitem(self, key)

    def __setitem__(self, key, value):
        from ..ops import tensor as _t
        _t._setitem(self, key, value)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # arithmetic dunders — dispatch through the op registry for tape hooks
    # ------------------------------------------------------------------
    def _binop(self, name, other, reverse=False):
        from ..ops import math as _m
        fn = getattr(_m, name)
        if isinstance(other, (list, tuple, _np.ndarray)):
            other = NDArray(jnp.asarray(other))
        if reverse:
            return fn(other, self)
        return fn(self, other)

    def __add__(self, o):
        return self._binop("add", o)

    def __radd__(self, o):
        return self._binop("add", o, True)

    def __sub__(self, o):
        return self._binop("subtract", o)

    def __rsub__(self, o):
        return self._binop("subtract", o, True)

    def __mul__(self, o):
        return self._binop("multiply", o)

    def __rmul__(self, o):
        return self._binop("multiply", o, True)

    def __truediv__(self, o):
        return self._binop("divide", o)

    def __rtruediv__(self, o):
        return self._binop("divide", o, True)

    def __floordiv__(self, o):
        return self._binop("floor_divide", o)

    def __rfloordiv__(self, o):
        return self._binop("floor_divide", o, True)

    def __mod__(self, o):
        return self._binop("mod", o)

    def __rmod__(self, o):
        return self._binop("mod", o, True)

    def __pow__(self, o):
        return self._binop("power", o)

    def __rpow__(self, o):
        return self._binop("power", o, True)

    def __matmul__(self, o):
        return self._binop("matmul", o)

    def __rmatmul__(self, o):
        return self._binop("matmul", o, True)

    def __neg__(self):
        return self._binop("multiply", -1)

    def __abs__(self):
        from ..ops import math as _m
        return _m.abs(self)

    def __eq__(self, o):
        return self._binop("equal", o)

    def __ne__(self, o):
        return self._binop("not_equal", o)

    def __lt__(self, o):
        return self._binop("less", o)

    def __le__(self, o):
        return self._binop("less_equal", o)

    def __gt__(self, o):
        return self._binop("greater", o)

    def __ge__(self, o):
        return self._binop("greater_equal", o)

    def __and__(self, o):
        return self._binop("bitwise_and", o)

    def __or__(self, o):
        return self._binop("bitwise_or", o)

    def __xor__(self, o):
        return self._binop("bitwise_xor", o)

    def __invert__(self):
        from ..ops import math as _m
        return _m.logical_not(self) if self.dtype == jnp.bool_ else _m.bitwise_not(self)

    def __hash__(self):
        return id(self)

    # in-place ops: mutate this handle (rebind buffer, keep identity)
    def _iop(self, name, other):
        res = self._binop(name, other)
        self._assign_from(res)
        return self

    def __iadd__(self, o):
        return self._iop("add", o)

    def __isub__(self, o):
        return self._iop("subtract", o)

    def __imul__(self, o):
        return self._iop("multiply", o)

    def __itruediv__(self, o):
        return self._iop("divide", o)

    def __bool__(self):
        if self.size != 1:
            raise MXNetError(
                "The truth value of an NDArray with multiple elements is "
                "ambiguous")
        return bool(self.asnumpy().reshape(())[()])

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        if self.ndim == 0 and jnp.issubdtype(self.dtype, jnp.integer):
            return int(self.asscalar())
        raise TypeError("only integer scalar arrays can be converted to index")

    def __repr__(self):
        return f"{self.asnumpy()!r}\n<NDArray {self.shape} @{self.context}>"

    def __str__(self):
        return str(self.asnumpy())

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # jax interop: NDArray is a valid jax input pytree leaf via this
    def __jax_array__(self):
        return self._data

    # ------------------------------------------------------------------
    # method mirrors of common ops (parity: NDArray methods)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        from ..ops import tensor as _t
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return _t.reshape(self, shape=shape)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        from ..ops import tensor as _t
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _t.transpose(self, axes=axes if axes else None)

    def swapaxes(self, a1, a2):
        from ..ops import tensor as _t
        return _t.swapaxes(self, a1, a2)

    def flatten(self):
        from ..ops import tensor as _t
        return _t.flatten(self)

    def expand_dims(self, axis):
        from ..ops import tensor as _t
        return _t.expand_dims(self, axis=axis)

    def squeeze(self, axis=None):
        from ..ops import tensor as _t
        return _t.squeeze(self, axis=axis)

    def broadcast_to(self, shape):
        from ..ops import tensor as _t
        return _t.broadcast_to(self, shape=shape)

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def repeat(self, repeats, axis=None):
        from ..ops import tensor as _t
        return _t.repeat(self, repeats=repeats, axis=axis)

    def tile(self, reps):
        from ..ops import tensor as _t
        return _t.tile(self, reps=reps)

    def slice_axis(self, axis, begin, end):
        from ..ops import tensor as _t
        return _t.slice_axis(self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=None, mode="clip"):
        from ..ops import tensor as _t
        return _t.take(self, indices, axis=axis, mode=mode)

    def clip(self, a_min=None, a_max=None):
        from ..ops import math as _m
        return _m.clip(self, a_min, a_max)

    def abs(self):
        from ..ops import math as _m
        return _m.abs(self)

    def sign(self):
        from ..ops import math as _m
        return _m.sign(self)

    def sqrt(self):
        from ..ops import math as _m
        return _m.sqrt(self)

    def square(self):
        from ..ops import math as _m
        return _m.square(self)

    def exp(self):
        from ..ops import math as _m
        return _m.exp(self)

    def log(self):
        from ..ops import math as _m
        return _m.log(self)

    def sum(self, axis=None, keepdims=False, dtype=None):
        from ..ops import math as _m
        return _m.sum(self, axis=axis, keepdims=keepdims, dtype=dtype)

    def mean(self, axis=None, keepdims=False, dtype=None):
        from ..ops import math as _m
        return _m.mean(self, axis=axis, keepdims=keepdims, dtype=dtype)

    def max(self, axis=None, keepdims=False):
        from ..ops import math as _m
        return _m.max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        from ..ops import math as _m
        return _m.min(self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        from ..ops import math as _m
        return _m.prod(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        from ..ops import tensor as _t
        return _t.argmax(self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        from ..ops import tensor as _t
        return _t.argmin(self, axis=axis, keepdims=keepdims)

    def argsort(self, axis=-1, is_ascend=True):
        from ..ops import tensor as _t
        return _t.argsort(self, axis=axis, is_ascend=is_ascend)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        from ..ops import tensor as _t
        return _t.topk(self, axis=axis, k=k, ret_typ=ret_typ,
                       is_ascend=is_ascend)

    def dot(self, other):
        from ..ops import math as _m
        return _m.dot(self, other)

    def norm(self, ord=2, axis=None, keepdims=False):
        from ..ops import math as _m
        return _m.norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def softmax(self, axis=-1):
        from ..ops import nn as _n
        return _n.softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        from ..ops import nn as _n
        return _n.log_softmax(self, axis=axis)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from ..ops import tensor as _t
        return _t.one_hot(self, depth=depth, on_value=on_value,
                          off_value=off_value)

    def pad(self, mode="constant", pad_width=None, constant_value=0):
        from ..ops import tensor as _t
        return _t.pad(self, pad_width=pad_width, mode=mode,
                      constant_value=constant_value)

    def split(self, num_outputs, axis=0):
        from ..ops import tensor as _t
        return _t.split(self, num_outputs=num_outputs, axis=axis)

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError(
                "sparse storage types are de-scoped on TPU (dense XLA "
                "buffers only); see mxnet_tpu/ndarray/sparse.py")
        return self


def from_jax(x) -> NDArray:
    return NDArray(x)


def array(source_array, ctx=None, dtype=None) -> NDArray:
    """Parity: mx.nd.array — python lists/scalars default to float32 (the
    reference's convention); numpy/jax inputs keep their dtype."""
    if isinstance(source_array, NDArray):
        source_array = source_array._data
    if dtype is None and not hasattr(source_array, "dtype"):
        dtype = _np.float32
    data = jnp.asarray(source_array, dtype=dtype)
    if ctx is not None:
        data = jax.device_put(data, ctx.jax_device)
    return NDArray(data)


def waitall():
    """Parity: mx.nd.waitall — block until all async work completes:
    every ordered effect has run and every live array holds its value."""
    jax.effects_barrier()
    jax.block_until_ready(jax.live_arrays())
