"""Device mesh management.

Reference parity: the reference has no mesh concept — its parallelism is
KVStore data-parallel over explicit device lists plus manual group2ctx
placement (SURVEY.md §2.4). The TPU-native design replaces ALL of that with
one `jax.sharding.Mesh` over named axes; every parallelism flavor (dp / tp /
pp / sp / ep / ZeRO-style fsdp) is a PartitionSpec over these axes, and XLA
compiles the collectives onto ICI/DCN (SURVEY.md §5.8).

Canonical axis names used across the framework:
    "dp"   — data parallel (batch dim)
    "fsdp" — sharded-parameter data parallel (ZeRO; batch + param shards)
    "tp"   — tensor parallel (hidden/head dims)
    "sp"   — sequence/context parallel (ring attention)
    "pp"   — pipeline stages
    "ep"   — expert parallel (MoE)
Any subset may appear; absent axes simply have size 1.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as _np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["Mesh", "PartitionSpec", "NamedSharding", "make_mesh",
           "current_mesh", "mesh_scope", "set_default_mesh", "named_sharding",
           "shard_map_compat", "axis_enabled", "serving_tp_mesh",
           "AXIS_DP", "AXIS_TP", "AXIS_PP", "AXIS_SP", "AXIS_EP", "AXIS_FSDP"]


def shard_map_compat(f, mesh, in_specs, out_specs, check_rep=False):
    """jax.shard_map with the framework's default: no replication check
    (psum-assembled outputs defeat jax's conservative inference)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


AXIS_DP, AXIS_FSDP, AXIS_TP = "dp", "fsdp", "tp"
AXIS_SP, AXIS_PP, AXIS_EP = "sp", "pp", "ep"


class _MeshState(threading.local):
    def __init__(self):
        self.stack = []
        self.default = None


_state = _MeshState()


def make_mesh(axes=None, devices=None, **axis_sizes):
    """Create a named-axis device mesh.

    make_mesh({"dp": 4, "tp": 2}) or make_mesh(dp=4, tp=2). A size of -1
    (at most one axis) absorbs the remaining devices. devices defaults to
    all of jax.devices()."""
    if axes is None:
        axes = axis_sizes
    elif axis_sizes:
        raise MXNetError("pass axes either as a dict or as kwargs, not both")
    if not axes:
        raise MXNetError("mesh needs at least one named axis")
    devices = list(jax.devices()) if devices is None else list(devices)
    names = list(axes.keys())
    sizes = [int(s) for s in axes.values()]
    n_dev = len(devices)
    if sizes.count(-1) > 1:
        raise MXNetError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        if n_dev % known:
            raise MXNetError(
                f"{n_dev} devices not divisible by fixed axes {axes}")
        sizes[sizes.index(-1)] = n_dev // known
    total = int(_np.prod(sizes))
    if total != n_dev:
        raise MXNetError(
            f"mesh {dict(zip(names, sizes))} wants {total} devices, "
            f"have {n_dev}")
    arr = _np.asarray(devices).reshape(sizes)
    return Mesh(arr, names)


def set_default_mesh(mesh):
    _state.default = mesh


def current_mesh():
    if _state.stack:
        return _state.stack[-1]
    return _state.default


@contextmanager
def mesh_scope(mesh):
    _state.stack.append(mesh)
    try:
        yield mesh
    finally:
        _state.stack.pop()


def axis_enabled(mesh=None, axis=AXIS_TP):
    """True iff an active (or given) mesh has a real (size > 1) named
    axis. Shared predicate for every lane that degrades to the unsharded
    path when its axis is absent or trivial (sp ring attention, serving
    tensor parallelism)."""
    mesh = mesh if mesh is not None else current_mesh()
    return (mesh is not None and axis in mesh.axis_names
            and mesh.shape[axis] > 1)


def serving_tp_mesh(tp, devices=None):
    """One-axis {AXIS_TP} mesh over the first `tp` local devices.

    The serving engine's tensor-parallel mode is a compile-time choice:
    the mesh shape is fixed at engine construction and never appears as
    a runtime axis, so shard count changes recompile (by design) and
    steady state stays compile-flat. Returns None for tp == 1 — the
    unsharded engine path takes no mesh at all."""
    tp = int(tp)
    if tp < 1:
        raise MXNetError(f"tp must be >= 1, got {tp}")
    if tp == 1:
        return None
    devices = list(jax.devices()) if devices is None else list(devices)
    if tp > len(devices):
        raise MXNetError(
            f"serving tp={tp} needs {tp} devices, have {len(devices)} "
            "(on CPU, force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return make_mesh({AXIS_TP: tp}, devices=devices[:tp])


def named_sharding(spec, mesh=None):
    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call make_mesh + mesh_scope / "
                         "set_default_mesh first")
    if spec is None:
        spec = PartitionSpec()
    return NamedSharding(mesh, spec)
