"""mx.config — the typed runtime-knob catalog.

Reference parity: SURVEY.md §5.6 layer (1), the env-var surface
(`dmlc::GetEnv("MXNET_…")` read at point of use, catalogued in the
reference's env_var.md). Here every knob the framework reads is declared
ONCE in this catalog with type, default and doc — `describe()` prints the
env_var.md analog, `get()` is the typed accessor modules use, and unknown
MXNET_*/MXTPU_* vars in the environment are reported by `check_env()`
(the reference silently ignores typos; we don't).

Layers (2) and (3) of the reference's config system map to typed
layer/op kwargs (dmlc::Parameter analog) and `mx.runtime.Features`
(build-flag introspection) respectively.
"""
from __future__ import annotations

import os

from .base import MXNetError

__all__ = ["Knob", "KNOBS", "get", "describe", "check_env"]


class Knob:
    def __init__(self, name, typ, default, doc):
        self.name = name
        self.type = typ
        self.default = default
        self.doc = doc

    def read(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        try:
            if self.type is bool:
                return raw.lower() in ("1", "true", "yes", "on")
            return self.type(raw)
        except ValueError:
            raise MXNetError(
                f"env {self.name}={raw!r} is not a valid {self.type.__name__}")


KNOBS = {k.name: k for k in [
    # engine (SURVEY §5.6: MXNET_ENGINE_TYPE family)
    Knob("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
         "Execution mode: ThreadedEnginePerDevice (async PjRt dispatch) "
         "or NaiveEngine (synchronous; errors surface at the faulting op "
         "— the debug recipe, engine.py)"),
    # data pipeline
    Knob("MXTPU_DECODE_THREADS", int, 0,
         "io.ImageRecordIter decode thread count (0 = min(8, cores))"),
    # autograd
    Knob("MXTPU_TAPE_PRIMALS", bool, True,
         "Keep each taped op's primal function + input buffers on the "
         "tape so backward(create_graph=True) (higher-order grad) can "
         "re-derive VJPs. Costs retention of input buffers that "
         "residual-free ops (add/reshape/...) would otherwise free "
         "before backward; set 0 on memory-constrained first-order "
         "training (create_graph then raises)."),
    # distributed bootstrap (reference launcher env, kvstore.py)
    Knob("DMLC_PS_ROOT_URI", str, "", "coordinator host (launcher env)"),
    Knob("DMLC_PS_ROOT_PORT", str, "", "coordinator port (launcher env)"),
    Knob("DMLC_NUM_WORKER", int, 1, "process count (launcher env)"),
    Knob("DMLC_WORKER_ID", int, 0, "process rank (launcher env)"),
    # jax passthroughs the framework sets/reads
    Knob("JAX_DEFAULT_PRNG_IMPL", str, "",
         "PRNG impl; 'rbg' is the TPU's hardware generator (dropout "
         "masks), set by the BERT benchmark cell's configuration"),
    Knob("XLA_FLAGS", str, "",
         "XLA flags; tests force --xla_force_host_platform_device_count=8 "
         "for the virtual mesh"),
]}


def get(name):
    """Typed read of a declared knob (env value or default)."""
    if name not in KNOBS:
        raise MXNetError(f"unknown config knob {name!r}; see "
                         "mx.config.describe()")
    return KNOBS[name].read()


def describe():
    """The env_var.md analog: every knob, its type, default, and doc."""
    lines = []
    for k in KNOBS.values():
        cur = os.environ.get(k.name)
        cur_s = f" [set: {cur}]" if cur is not None else ""
        lines.append(f"{k.name} ({k.type.__name__}, "
                     f"default {k.default!r}){cur_s}\n    {k.doc}")
    return "\n".join(lines)


def check_env():
    """Return MXNET_*/MXTPU_* env vars that match no declared knob —
    likely typos (the reference silently ignores these)."""
    unknown = []
    for name in os.environ:
        if (name.startswith("MXNET_") or name.startswith("MXTPU_")) \
                and name not in KNOBS:
            unknown.append(name)
    return sorted(unknown)
