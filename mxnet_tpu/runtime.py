"""mx.runtime — feature introspection.

Reference parity: python/mxnet/runtime.py — Features / feature_list()
backed by src/libinfo.cc compile-time flags (SURVEY.md §2.1 "Init &
lifecycle", §5.6 layer 3). Here the "build flags" are runtime properties
of the JAX/XLA stack, probed once on first access.
"""
from __future__ import annotations

import os

from .base import MXNetError

__all__ = ["Feature", "Features", "feature_list", "jit_cache_stats",
           "reset_jit_cache_stats", "enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache():
    """Give JAX's persistent compilation cache a home, unless the
    environment already placed it; returns the directory in use.

    With JAX_COMPILATION_CACHE_DIR set JAX honours it by itself and
    nothing is set here. Otherwise the cache is <checkout>/.jax_cache —
    a fixed path, because a later process only finds the entries of an
    earlier one at the same path. The ONE place the cache is configured:
    every entry point (benchmarks/run.py, chip_smoke.py, the fleet
    worker, examples/) calls it before its first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def jit_cache_stats():
    """Process-wide trace-cache counters ({'retraces', 'evictions'}) for
    the bounded LRU jit caches (HybridBlock._jit_cache and
    GPT2._generate_cache). A steadily climbing retrace count in steady
    state means shape churn is defeating the caches — pad or bucket the
    inputs. Bound sizes: MXNET_TPU_JIT_CACHE_SIZE (default 64) and
    MXNET_TPU_GENERATE_CACHE_SIZE (default 16)."""
    from .gluon.block import jit_cache_stats as _stats
    return _stats()


def reset_jit_cache_stats():
    from .gluon.block import reset_jit_cache_stats as _reset
    _reset()


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _probe():
    import jax

    feats = {}

    def have_platform(p):
        try:
            return len(jax.devices(p)) > 0
        except RuntimeError:
            return False

    feats["CPU"] = True
    feats["TPU"] = have_platform("tpu")
    feats["CUDA"] = have_platform("gpu")  # parity name for the flag
    feats["BF16"] = True                  # first-class on every XLA backend
    feats["F16C"] = True
    feats["INT64_TENSOR_SIZE"] = True     # jax uses 64-bit sizes natively
    feats["SIGNAL_HANDLER"] = True        # python default faulthandler path
    try:
        import jax.experimental.pallas  # noqa: F401
        feats["PALLAS"] = True
    except ImportError:
        feats["PALLAS"] = False
    feats["DIST_KVSTORE"] = True          # kvstore.py + jax.distributed
    feats["X64"] = bool(jax.config.read("jax_enable_x64"))
    # de-scoped reference features, reported disabled for honest probing
    for off in ("CUDNN", "NCCL", "TENSORRT", "ONEDNN", "MKLDNN", "OPENCV",
                "BLAS_MKL", "TVM_OP", "CAFFE", "PROFILER_NVTX"):
        feats[off] = False
    return feats


class Features(dict):
    """Parity: mx.runtime.Features — dict of Feature with is_enabled()."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            cls.instance.update(
                {k: Feature(k, v) for k, v in _probe().items()})
        return cls.instance

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise MXNetError(f"unknown feature '{feature_name}'; known: "
                             f"{sorted(self)}")
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
