"""mx.kvstore — the KVStore façade over TPU-native collectives.

Reference parity: include/mxnet/kvstore.h — KVStore::Create("local" /
"device" / "nccl" / "dist_sync" / "dist_async" / "dist_sync_device") with
Init/Push/Pull/PushPull/Broadcast and an optional server-side Updater
(SURVEY.md §2.4). TPU-native mapping (SURVEY.md §5.8): there is no custom
transport — the *performance* path is in-program XLA collectives compiled
into the fused TrainStep; this façade provides the KVStore API surface for
source compatibility and the *out-of-program* cross-process reductions
(gradient aggregation for the eager Trainer, metric/stat reduction),
implemented over the `jax.distributed` runtime:

  * single-process types ("local", "device", "nccl"): pure host-side
    aggregation — device count is irrelevant because a sharded array is
    one logical value (the reference needed per-GPU comm here; XLA
    doesn't);
  * "dist_sync"/"dist_sync_device": multi-process allreduce via a global
    device array (jax.experimental.multihost_utils), riding the same
    coordination service `jax.distributed.initialize` sets up over
    ICI/DCN on pods, gRPC on CPU test clusters;
  * "dist_async": de-scoped — ps-lite's HogWild mode has no TPU
    equivalent and sync DP is strictly dominant on dedicated meshes
    (SURVEY.md §5.8); raises with that explanation.

Process bootstrap (`tools/launch.py` parity): `init_distributed()` reads
the DMLC_* env the reference's launcher sets (or explicit arguments) and
calls jax.distributed.initialize.
"""
from __future__ import annotations

import os
import time as _time
import warnings

import numpy as _np

from . import telemetry as _telemetry
from .base import MXNetError

# out-of-program collective accounting (the in-program XLA collectives
# are budgeted statically by parallel.comm.comm_report instead — they
# never surface to the host, so there is nothing to time here)
_allreduce_bytes = _telemetry.counter(
    "kvstore_allreduce_bytes_total",
    "payload bytes through out-of-program kvstore allreduce",
    labelnames=("store",))
_allreduce_seconds = _telemetry.histogram(
    "kvstore_allreduce_seconds",
    "wall time of one out-of-program kvstore allreduce",
    labelnames=("store",))
_bcast_bytes = _telemetry.counter(
    "kvstore_broadcast_bytes_total",
    "payload bytes through kvstore root broadcast",
    labelnames=("store",))
_pushpull_total = _telemetry.counter(
    "kvstore_pushpull_total", "kvstore pushpull key-operations",
    labelnames=("store",))

__all__ = ["KVStore", "create", "init_distributed", "KVStoreBase"]

_DESCOPE_ASYNC = (
    "kvstore type 'dist_async' is de-scoped on TPU: the reference's "
    "parameter-server HogWild mode has no XLA equivalent and synchronous "
    "data parallelism is strictly dominant on dedicated meshes "
    "(SURVEY.md §5.8); use 'dist_sync'")


def init_distributed(coordinator=None, num_processes=None, process_id=None):
    """Initialize the multi-process runtime (idempotent).

    Reads the reference launcher's env when args are omitted:
    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT (coordinator), DMLC_NUM_WORKER
    (process count), DMLC_WORKER_ID (rank). Returns (rank, size)."""
    import jax

    # NOTE: jax.process_count()/devices() must NOT be called before
    # jax.distributed.initialize — they would initialize the backend.
    if jax.distributed.is_initialized():
        return jax.process_index(), jax.process_count()
    if coordinator is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT")
        if uri and port:
            coordinator = f"{uri}:{port}"
    if num_processes is None:
        num_processes = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if process_id is None:
        process_id = int(os.environ.get("DMLC_WORKER_ID", "0"))
    if coordinator is None or num_processes <= 1:
        return 0, 1
    # multi-process CPU backends need a cross-process collectives impl
    # (the TPU backend has ICI/DCN built in); must be set pre-init
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return jax.process_index(), jax.process_count()


class KVStoreBase:
    """Backend registry (parity: python/mxnet/kvstore/base.py — Horovod/
    BytePS plug in behind the same API in the reference)."""

    _backends = {}

    @classmethod
    def register(cls, klass):
        cls._backends[klass.__name__.lower()] = klass
        return klass


def _as_list(x):
    return x if isinstance(x, (list, tuple)) else [x]


def _nd():
    from .ndarray.ndarray import NDArray
    return NDArray


@KVStoreBase.register
class KVStore:
    """The single-process store ("local"/"device"/"nccl") and base class.

    Push semantics match the reference: pushed values for a key are summed;
    without an updater the merged sum REPLACES the stored value, with an
    updater `updater(key, merged, stored)` runs where the weights live
    (update_on_kvstore)."""

    def __init__(self, type_name="local"):
        self._type = type_name
        self._store = {}
        self._updater = None
        self._updater_obj = None
        self._optimizer = None
        self._compression = None

    # -- identity ---------------------------------------------------------
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    # -- reduction core (overridden by the dist store) --------------------
    def _allreduce(self, arr, key=None):
        return arr

    def _bcast_from_root(self, arr):
        return arr

    @staticmethod
    def _data_of(v):
        import jax.numpy as jnp
        NDArray = _nd()
        return v._data if isinstance(v, NDArray) else jnp.asarray(v)

    def _merge(self, value, key=None):
        # a key's value may be one array or a list of per-device arrays
        # (reference: comm reduce across GPUs); sum then cross-process
        datas = [self._data_of(v) for v in _as_list(value)]
        merged = datas[0]
        for d in datas[1:]:
            merged = merged + d
        return self._allreduce(merged, key)

    @staticmethod
    def _pairs(key, value):
        """Align keys with values: single key takes `value` whole (which
        may itself be a per-device list); a key list zips positionally."""
        keys = _as_list(key)
        if len(keys) == 1:
            return [(keys[0], value)]
        return list(zip(keys, value))

    # -- API --------------------------------------------------------------
    def init(self, key, value):
        for k, v in self._pairs(key, value):
            v0 = _as_list(v)[0]
            self._store[k] = self._bcast_from_root(self._data_of(v0))

    def push(self, key, value, priority=0):
        for k, v in self._pairs(key, value):
            if k not in self._store:
                raise MXNetError(f"key {k!r} not initialized; call init()")
            merged = self._merge(v, k)
            if self._updater is not None:
                stored = _nd()(self._store[k])
                self._updater(k, _nd()(merged), stored)
                self._store[k] = stored._data
            else:
                self._store[k] = merged

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if out is None:
            raise MXNetError("pull requires out= (an NDArray or list to "
                             "receive the value)")
        results = []
        for k, o in self._pairs(key, out):
            if k not in self._store:
                raise MXNetError(f"key {k!r} not initialized; call init()")
            stored = self._store[k]
            for oo in _as_list(o):
                oo._rebind(stored.astype(oo.dtype)
                           if oo.dtype != stored.dtype else stored)
            results.append(o)
        return results[0] if len(results) == 1 else results

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (the reference's fast path). With no updater
        installed the reduced sum both replaces the stored value and lands
        in out (defaulting to value itself, matching the reference's
        in-place semantics) — Trainer.allreduce_grads relies on this."""
        if out is None:
            out = value
        if self._updater is None:
            vp = dict(self._pairs(key, value))
            for k, o in self._pairs(key, out):
                if k not in self._store:
                    raise MXNetError(
                        f"key {k!r} not initialized; call init()")
                merged = self._merge(vp[k], k)
                self._store[k] = merged
                for oo in _as_list(o):
                    oo._rebind(merged)
                _pushpull_total.labels(self._type).inc()
            return out
        self.push(key, value, priority)
        return self.pull(key, out=out, priority=priority)

    def broadcast(self, key, value, out=None, priority=0):
        """Parity: KVStore::Broadcast — rank 0's value to every worker."""
        self.init(key, value)
        if out is not None:
            return self.pull(key, out=out, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        raise MXNetError(
            "row_sparse_pull: sparse storage is de-scoped on TPU "
            "(dense-only; see mxnet_tpu/ndarray/sparse.py)")

    # -- updater / optimizer ----------------------------------------------
    def set_updater(self, updater):
        self._updater = updater

    def set_optimizer(self, optimizer):
        """update_on_kvstore semantics: optimizer runs where weights live."""
        from . import optimizer as _opt
        self._optimizer = optimizer
        self._updater_obj = _opt.get_updater(optimizer)
        self._updater = self._updater_obj

    def set_gradient_compression(self, compression_params):
        """Parity: kvstore.set_gradient_compression({'type': '2bit',
        'threshold': t}). Applied on the multi-process reduce path;
        a single-process store has no wire to compress, so there it
        only records the setting.

        Two compressors (gradient_compression.py): '2bit' — the
        reference's threshold quantizer, 16x smaller wire payload —
        and 'int8' — EQuARX-style blockwise-scaled int8
        ({'type': 'int8', 'block': n}, ~4x smaller), both with error
        feedback. The metered allreduce bytes are the compressor's
        `wire_bytes`, i.e. compressed bytes on the wire, never the
        logical gradient size."""
        self._compression = dict(compression_params or {})
        if not self._compression:
            self._compressor = None  # explicit disable / no-op
            return
        if "type" not in self._compression:
            raise MXNetError(
                "compression_params requires a 'type' key (the reference "
                "rejects it too); use {'type': '2bit', 'threshold': t}")
        ctype = self._compression["type"]
        if ctype == "2bit":
            from .gradient_compression import TwoBitCompressor
            self._compressor = TwoBitCompressor(
                float(self._compression.get("threshold", 0.5)))
        elif ctype == "int8":
            from .gradient_compression import Int8BlockCompressor
            self._compressor = Int8BlockCompressor(
                int(self._compression.get("block", 256)))
        else:
            raise MXNetError(
                f"unsupported gradient compression type {ctype!r} "
                "(the reference and this rebuild support '2bit'; this "
                "rebuild adds 'int8')")
        if self.num_workers == 1:
            warnings.warn(
                "gradient compression set on a single-process kvstore: "
                "nothing to compress (no cross-process wire)", stacklevel=2)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._optimizer is None:
            raise MXNetError("no optimizer installed on this kvstore")
        with open(fname, "wb") as f:
            f.write(self._updater_obj.get_states(
                dump_optimizer=dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._optimizer is None:
            raise MXNetError("no optimizer installed on this kvstore")
        with open(fname, "rb") as f:
            self._updater_obj.set_states(f.read())


class _DistSyncKVStore(KVStore):
    """Multi-process synchronous store over jax.distributed."""

    def __init__(self, type_name="dist_sync"):
        super().__init__(type_name)
        init_distributed()
        import jax
        self._rank = jax.process_index()
        self._size = jax.process_count()

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    # warn once per process when a big tensor takes the host-bound path
    _BIG_WARNED = False
    _BIG_BYTES = 8 << 20

    def _allreduce(self, arr, key=None):
        if self._size == 1:
            return arr
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        t0 = _time.perf_counter()
        comp = getattr(self, "_compressor", None)
        if comp is not None and key is not None and arr.size >= 16:
            packed = comp.compress(key, arr)
            gathered = multihost_utils.process_allgather(
                _np.asarray(packed))          # (P, n_words)
            total = None
            for row in gathered:
                d = comp.decompress(jnp.asarray(row), arr.shape)
                total = d if total is None else total + d
            # meter the compressor's wire contract, not the payload
            # array's incidental representation: wire_bytes(shape) ==
            # compress(...).nbytes for every compressor (pinned by
            # tests/test_compression.py), so the counter reports
            # compressed bytes-on-wire consistently
            _allreduce_bytes.labels(self._type).inc(
                int(comp.wire_bytes(arr.shape)))
            _allreduce_seconds.labels(self._type).observe(
                _time.perf_counter() - t0)
            return total.astype(arr.dtype)
        if (not _DistSyncKVStore._BIG_WARNED
                and arr.size * arr.dtype.itemsize > self._BIG_BYTES):
            _DistSyncKVStore._BIG_WARNED = True
            warnings.warn(
                "kvstore dist_sync reduced a tensor >8MB via host "
                "allgather — this path is a per-key synchronous API "
                "facade, NOT the performance path. For real multi-process "
                "training use parallel.TrainStep over a mesh, where XLA "
                "collectives reduce gradients on ICI inside the step "
                "(SURVEY.md §5.8)", stacklevel=3)
        gathered = multihost_utils.process_allgather(_np.asarray(arr))
        out = jnp.asarray(gathered.sum(axis=0))
        _allreduce_bytes.labels(self._type).inc(
            int(arr.size * arr.dtype.itemsize))
        _allreduce_seconds.labels(self._type).observe(
            _time.perf_counter() - t0)
        return out

    def _bcast_from_root(self, arr):
        if self._size == 1:
            return arr
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        _bcast_bytes.labels(self._type).inc(
            int(arr.size * arr.dtype.itemsize))
        return jnp.asarray(
            multihost_utils.broadcast_one_to_all(_np.asarray(arr)))

    def barrier(self):
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("mxnet_tpu.kvstore.barrier")


def create(name="local"):
    """Parity: mx.kv.create. Types: local | device | nccl (single-process
    aliases — XLA owns intra-process device comm), dist_sync |
    dist_sync_device | dist (multi-process sync), dist_async (de-scoped)."""
    if not isinstance(name, str):
        raise MXNetError("kvstore name must be a string")
    name = name.lower()
    if name in ("local", "device", "nccl", "local_allreduce_cpu",
                "local_allreduce_device"):
        return KVStore(name)
    if name in ("dist_sync", "dist_sync_device", "dist"):
        return _DistSyncKVStore(name)
    if name == "dist_async":
        raise MXNetError(_DESCOPE_ASYNC)
    if name in KVStoreBase._backends:
        return KVStoreBase._backends[name]()
    raise MXNetError(f"unknown kvstore type {name!r}")
