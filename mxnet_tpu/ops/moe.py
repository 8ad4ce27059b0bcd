"""The grouped expert feed-forward of a dropless mixture of experts.

Rows arrive SORTED BY EXPERT: the first group_sizes[0] rows belong to held
expert 0, the next group_sizes[1] to expert 1, and so on; rows at or past
sum(group_sizes) belong to nobody. Expert e computes

    y = act(x W1[e]) W2[e]

on its own rows, `activation` one of

    relu2    act(h) = relu(h)^2, W1 (D, F)
    swiglu   act([g | u]) = silu(g) * u, W1 (D, 2F): the gate and the up
             matrix side by side, read together, one product for both

How many rows there are, and how they fall to the experts,
is runtime data (the routing of one dispatch); only the bound M is static.

The Mosaic kernel walks (row tile, expert) VISITS: a tile of `tm` rows is
visited once by every expert that has rows in it, in order, so the visits
of one tile are consecutive and its output block stays in VMEM between
them; each visit computes the whole tile with that expert's weights and
keeps the expert's own rows. The number of visits is a traced scalar and is
the grid's size: tiles past the last real row are never visited, and an
expert without rows is never fetched. The hidden width is cut into `tf`
columns a grid step; where both of an expert's matrices fit the block
budget it is not cut at all, and consecutive visits by one expert (a group
longer than a tile) then fetch its weights once.

The dense form is `jax.lax.ragged_dot` twice: the CPU's path and the
kernel's oracle.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_paths import note_path, note_tile
from .pallas_attention import _compiler_params

__all__ = ["expert_ffn", "relu2", "swiglu", "ACTIVATIONS"]

_ROW_TILE = 128
# both weight blocks of a grid step, double-buffered, stay under this
_WEIGHT_BLOCK_BYTES = 48 * 1024 * 1024


def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def swiglu(x):
    """silu(g) * u of [g | u], the two halves of the last axis."""
    g, u = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(g) * u


# activation -> (the function over W1's columns, W1's columns a hidden one)
ACTIVATIONS = {"relu2": (relu2, 1), "swiglu": (swiglu, 2)}


def _unsupported_reason(x, w2):
    M, D = x.shape
    F = w2.shape[1]
    if D % 128 or F % 128:
        return f"widths {D} and {F} must be multiples of 128 lanes"
    if M % _ROW_TILE:
        return f"{M} rows are not a multiple of the {_ROW_TILE}-row tile"
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {x.dtype} is neither float32 nor bfloat16"
    return None


def _resolve_impl(impl, interpret, x, w2):
    if impl != "auto":
        return impl
    if interpret:
        return "pallas"
    if jax.default_backend() != "tpu":
        return "xla"
    why = _unsupported_reason(x, w2)
    if why is None:
        return "pallas"
    warnings.warn("expert_ffn: impl='auto' on TPU is using ragged_dot "
                  f"instead of the Mosaic kernel because {why}",
                  stacklevel=3)
    return "xla"


def _expert_ffn_xla(x, w1, w2, group_sizes, act):
    h = act(lax.ragged_dot(x, w1, group_sizes,
                           preferred_element_type=jnp.float32))
    return lax.ragged_dot(h.astype(x.dtype), w2, group_sizes,
                          preferred_element_type=jnp.float32).astype(x.dtype)


def _visits(group_sizes, tm, tiles):
    """(offsets (G+1,), expert of each visit, tile of each visit, number
    of visits): expert g visits every tile its rows touch, experts in
    order, so a tile's visits are consecutive. At most tiles + G - 1."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    count = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(count)
    bound = tiles + G - 1
    v = jnp.arange(bound, dtype=jnp.int32)
    # visits past the last are never run; they name the last expert's tile
    gid = jnp.minimum(jnp.searchsorted(v_end, v, side="right"),
                      G - 1).astype(jnp.int32)
    tid = jnp.clip(first[gid] + v - (v_end - count)[gid], 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    return offsets, gid, tid.astype(jnp.int32), v_end[-1].astype(jnp.int32)


def _ffn_kernel(off_ref, gid_ref, tid_ref, x_ref, *refs, tm, nf, act):
    """refs: W1's block (two of them, the gate's and the up matrix's
    columns of this cut, where the activation is gated and the hidden
    width is cut), W2's block, out, the accumulator."""
    *w1_refs, w2_ref, o_ref, acc_ref = refs
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h = act(jnp.concatenate(
        [lax.dot_general(x, w[...], (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
         for w in w1_refs], axis=-1))
    acc_ref[...] += lax.dot_general(
        h.astype(x.dtype), w2_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _store():
        g = gid_ref[v]
        row = tid_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        # the other rows of the tile are another visit's, before or after
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _hidden_tile(D, F, itemsize, matrices=2):
    """The widest cut of the hidden width, in whole 128-lane columns that
    divide it, whose weight blocks (`matrices` of D x tf: two, and the gate
    besides where the activation is gated) fit the budget
    double-buffered."""
    tf = F
    while tf > 128 and (2 * matrices * D * tf * itemsize
                        > _WEIGHT_BLOCK_BYTES or F % tf or tf % 128):
        tf -= 128
    return tf


def _expert_ffn_pallas(x, w1, w2, group_sizes, interpret, activation):
    M, D = x.shape
    G, F, _ = w2.shape
    act, parts = ACTIVATIONS[activation]
    tm = _ROW_TILE if M % _ROW_TILE == 0 else M
    tiles = M // tm
    tf = F if interpret else _hidden_tile(D, F, x.dtype.itemsize, 1 + parts)
    nf = F // tf
    note_tile("expert_ffn", rows=tm, hidden=tf)
    offsets, gid, tid, n_visits = _visits(group_sizes.astype(jnp.int32),
                                          tm, tiles)
    # W1 whole where the hidden width is not cut (a gated pair then comes
    # in ONE block, [gate | up]); cut, each of its parts brings its own
    # columns of the cut
    w1_specs = [pl.BlockSpec((None, D, parts * F),
                             lambda v, f, off, gid, tid: (gid[v], 0, 0))] \
        if nf == 1 else [
            pl.BlockSpec((None, D, tf),
                         lambda v, f, off, gid, tid, part=part:
                         (gid[v], 0, part * nf + f))
            for part in range(parts)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_visits, nf),
        in_specs=[
            pl.BlockSpec((tm, D), lambda v, f, off, gid, tid: (tid[v], 0)),
            *w1_specs,
            pl.BlockSpec((None, tf, D),
                         lambda v, f, off, gid, tid: (gid[v], f, 0)),
        ],
        out_specs=pl.BlockSpec((tm, D),
                               lambda v, f, off, gid, tid: (tid[v], 0)),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ffn_kernel, tm=tm, nf=nf, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        interpret=interpret,
        name="expert_ffn",
        compiler_params=_compiler_params(
            interpret, dimension_semantics=("arbitrary", "arbitrary")),
    )(offsets, gid, tid, x, *([w1] * len(w1_specs)), w2)


def expert_ffn(x, w1, w2, group_sizes, impl="auto", interpret=False,
               activation="relu2"):
    """act(x W1[e]) W2[e] for rows sorted by expert.

    x:           (M, D) rows, expert 0's first. M is the static bound.
    w1, w2:      (G, D, F) and (G, F, D), the held experts' matrices;
                 under `activation="swiglu"` w1 is (G, D, 2F), [gate | up].
    activation:  'relu2' (relu(h)^2) or 'swiglu' (silu(gate) * up).
    group_sizes: (G,) int32 rows per expert; their sum may be less than M.
    impl: 'auto' (the Mosaic kernel on a TPU, ragged_dot elsewhere, or on a
    TPU with a warning where the shapes break the kernel's rules),
    'pallas' (interpret=True runs it on a CPU), 'xla'.
    Returns (M, D) in x's dtype. Rows at or past sum(group_sizes) are NOT
    defined (the kernel never visits their tiles): mask them.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown expert activation {activation!r}: "
                         f"{sorted(ACTIVATIONS)}")
    impl = _resolve_impl(impl, interpret, x, w2)
    note_path("expert_ffn", impl)
    if impl == "pallas":
        return _expert_ffn_pallas(x, w1, w2, group_sizes, interpret,
                                  activation)
    if impl != "xla":
        raise ValueError(f"unknown expert_ffn impl {impl!r}")
    return _expert_ffn_xla(x, w1, w2, group_sizes,
                           ACTIVATIONS[activation][0])
