"""State-space duality (Mamba-2) chunk update for the serving dispatch.

One call advances every slot's recurrent state by one dispatch of up to W
rows and gives the rows' outputs, in the idiom of
ops/pallas_attention.ragged_span_attention: a fixed (slots, W) shape whose
live rows are runtime data (`q_counts`), the whole state pool as the
operand with the layer picked in the BlockSpec, and the pool written back
in place.

Per slot and head, with a_t = dt_t * A (<= 0) and c_t = a_1 + ... + a_t:

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T              (P, N)
    y_t = S_t C_t + D x_t

over the W rows of a chunk is the same as

    y   = exp(c) * (C S_0^T)  +  ((C B^T) * L) x  +  D x
    S_W = exp(c_W) S_0  +  (x * w)^T B
    L[t, s] = exp(c_t - c_s) dt_s for s <= t, else 0
    w_s     = exp(c_W - c_s) dt_s

three matrix products a head (read-out of the carried state, the
decay-masked intra-chunk form, the state's update) and one a group (C B^T).
Rows at or past q_counts[b] take dt = 0: they neither decay nor feed the
state, and emit zeros. A slot marked `fresh` reads zeros for S_0 whatever
the pool holds.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_paths import note_path
from .pallas_attention import NEG_INF, _compiler_params

__all__ = ["ssd_chunk_update"]


# the state block of one grid step: as many heads of one group as fit
_STATE_BLOCK_BYTES = 2 * 1024 * 1024


def _unsupported_reason(x, B, state):
    """Why the Mosaic kernel cannot take this call on a TPU (None when it
    can; interpret mode runs any shape)."""
    W, H, P = x.shape[1:]
    N = B.shape[-1]
    if (P % 128 and 128 % P) or N % 128:
        return (f"head_dim {P} must divide or be a multiple of 128 lanes, "
                f"and state size {N} a multiple")
    if P < 128 and (H // B.shape[2]) % (128 // P):
        return (f"{H // B.shape[2]} heads a group do not fill tiles of "
                f"{128 // P} heads of {P}")
    if W % 8:
        return f"{W} rows break the sublane rule (multiple of 8)"
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {x.dtype} is neither float32 nor bfloat16"
    if state.dtype != jnp.float32:
        return f"the state pool is {state.dtype}, not float32"
    return None


def _resolve_impl(impl, interpret, x, B, state):
    if impl != "auto":
        return impl
    if interpret:
        return "pallas"
    if jax.default_backend() != "tpu":
        return "xla"
    why = _unsupported_reason(x, B, state)
    if why is None:
        return "pallas"
    warnings.warn("ssd_chunk_update: impl='auto' on TPU is using the XLA "
                  f"einsum form instead of the Mosaic kernel because {why}",
                  stacklevel=3)
    return "xla"


def _masked(dt, A, q_counts):
    """Which rows are live, dt with the dead rows' steps at 0, and the
    running log-decay c."""
    live = jnp.arange(dt.shape[1])[None, :] < q_counts[:, None]  # (Bt, W)
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)
    cs = jnp.cumsum(dt * A.astype(jnp.float32), axis=1)         # (Bt, W, H)
    return live, dt, cs


def _ssd_chunk_xla(x, dt, A, B, C, D, s0, q_counts):
    """The chunk form in einsums, float32 throughout: the CPU's path and
    the kernel's oracle. s0 (Bt, H, P, N) float32."""
    Bt, W, H, P = x.shape
    G = B.shape[2]
    live, dt, cs = _masked(dt, A, q_counts)
    f32 = jnp.float32
    xf = jnp.where(live[..., None, None], x, 0).astype(f32)
    xg = xf.reshape(Bt, W, G, H // G, P)
    Bf = jnp.where(live[..., None, None], B, 0).astype(f32)
    Cf = C.astype(f32)
    sg = s0.reshape(Bt, G, H // G, P, -1)
    tri = jnp.arange(W)[:, None] >= jnp.arange(W)[None, :]
    diff = cs[:, :, None, :] - cs[:, None, :, :]                # (Bt,t,s,H)
    L = jnp.exp(jnp.where(tri[None, :, :, None], diff, NEG_INF)) \
        * dt[:, None, :, :]
    cb = jnp.einsum("btgn,bsgn->btsg", Cf, Bf)
    M = L.reshape(Bt, W, W, G, H // G) * cb[..., None]
    y = jnp.einsum("btsgh,bsghp->btghp", M, xg)
    y = y + jnp.exp(cs).reshape(Bt, W, G, H // G)[..., None] \
        * jnp.einsum("btgn,bghpn->btghp", Cf, sg)
    y = y.reshape(Bt, W, H, P) + D.astype(f32)[None, None, :, None] * xf
    y = jnp.where(live[..., None, None], y, 0.0)
    w = jnp.exp(cs[:, -1:, :] - cs) * dt                        # (Bt, W, H)
    upd = jnp.einsum("bsghp,bsgn->bghpn",
                     xg * w.reshape(Bt, W, G, H // G)[..., None], Bf)
    s1 = jnp.exp(cs[:, -1])[:, :, None, None] * s0 \
        + upd.reshape(s0.shape)
    return y.astype(x.dtype), s1


def _ssd_kernel(qc_ref, fresh_ref, dec_ref, d_ref, rows_ref, x_ref, b_ref,
                c_ref, s_ref, y_ref, so_ref, *, W, hb, P, q):
    """One (slot, block of hb heads of one group). rows_ref holds, per
    head of the block and along the W lanes, its running log-decay c
    (rows 0..hb-1), its masked dt (hb..2hb-1) and the rows' weights in
    the state's update w (2hb..3hb-1); dec_ref (slots, H) the chunk's
    whole decay exp(c_W) and d_ref (H,) D, as scalars: a (1, 1) value
    does not broadcast to a tile.

    Heads narrower than the 128 lanes of a tile are taken q at a time
    (128 // P on the chip): their inputs are one (W, 128) tile side by
    side, their states (q, P, N) stacked are one (128, N) matrix, so the
    state's read-out and update are ONE full-width product for all q, and
    what differs by head (decay, dt, D) is laid over lanes or rows with a
    select. Only the intra-chunk product is made once a head, each as
    wide as the tile, and the head's own lanes kept. With P a multiple
    of 128, q is 1 and no select is emitted."""
    b = pl.program_id(0)
    h0 = pl.program_id(1) * hb
    qn = qc_ref[b]
    f32 = jnp.float32
    PW = q * P

    @pl.when(qn == 0)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)
        so_ref[...] = s_ref[...]

    @pl.when(qn > 0)
    def _work():
        cd = x_ref.dtype
        N = s_ref.shape[-1]
        # all False for a fresh slot (fresh is 0 or 1), else all True
        s_row = lax.broadcasted_iota(jnp.int32, (PW, N), 0)
        kept = s_row >= fresh_ref[b] * PW
        lane = lax.broadcasted_iota(jnp.int32, (W, PW), 1)
        live = lax.broadcasted_iota(jnp.int32, (W, 1), 0) < qn
        Bm = jnp.where(live, b_ref[0], jnp.zeros_like(b_ref[0]))  # (W, N)
        Cm = c_ref[0]
        cb = lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)        # (W, W)
        t_i = lax.broadcasted_iota(jnp.int32, (W, W), 0)
        s_i = lax.broadcasted_iota(jnp.int32, (W, W), 1)
        tri, eye = s_i <= t_i, s_i == t_i
        # a (1, W) row as a (W, 1) column, without a transpose
        col = lambda r: jnp.sum(jnp.where(eye, r, 0.0), axis=1,
                                keepdims=True)

        def over(index, values):
            """values[k] where index // P == k: one value a head, laid
            over the heads' lanes (or the stacked states' rows)."""
            out = values[-1]
            for k in range(q - 2, -1, -1):
                out = jnp.where(index < (k + 1) * P, values[k], out)
            return out

        for i in range(hb // q):
            heads = range(i * q, (i + 1) * q)
            cs_r = [rows_ref[0, 0, j:j + 1, :] for j in heads]   # (1, W)
            dt_r = [rows_ref[0, 0, hb + j:hb + j + 1, :] for j in heads]
            w_c = [col(rows_ref[0, 0, 2 * hb + j:2 * hb + j + 1, :])
                   for j in heads]
            cs_c = [col(r) for r in cs_r]
            x = x_ref[0, :, i * PW:(i + 1) * PW]                # (W, PW)
            x = jnp.where(live, x, jnp.zeros_like(x))
            # a fresh slot reads zeros whatever the pool holds, a NaN
            # from the slot's last owner included
            s0 = s_ref[0, i * q] if q == 1 \
                else s_ref[0, i * q:(i + 1) * q].reshape(PW, N)
            s0 = jnp.where(kept, s0, 0.0)                       # (PW, N)
            y = over(lane, [
                lax.dot_general(
                    (cb * jnp.exp(jnp.where(tri, c - r, NEG_INF)) * d)
                    .astype(cd), x, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                for c, r, d in zip(cs_c, cs_r, dt_r)])
            y = y + over(lane, [jnp.exp(c) for c in cs_c]) \
                * lax.dot_general(Cm, s0.astype(cd),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=f32)
            y = y + over(lane, [d_ref[h0 + j] for j in heads]) \
                * x.astype(f32)
            y_ref[0, :, i * PW:(i + 1) * PW] = jnp.where(
                live, y, 0.0).astype(y_ref.dtype)
            upd = lax.dot_general(
                (x.astype(f32) * over(lane, w_c)).astype(cd), Bm,
                (((0,), (0,)), ((), ())),
                preferred_element_type=f32)                     # (PW, N)
            s1 = over(s_row, [dec_ref[b, h0 + j] for j in heads]) * s0 \
                + upd
            if q == 1:
                so_ref[0, i] = s1
            else:
                so_ref[0, i * q:(i + 1) * q] = s1.reshape(q, P, N)


def _heads_per_block(hpg, P, N):
    """(hb, q): the most heads of one group whose float32 state fits the
    block, and how many of them share a tile of 128 lanes (1 where a head
    fills it; as many as divide the group where it is narrower)."""
    q = max(1, 128 // P)
    while hpg % q:
        q -= 1
    hb = hpg
    while hb > q and (hb * P * N * 4 > _STATE_BLOCK_BYTES or hpg % hb
                      or hb % q):
        hb -= 1
    return hb, q


def _ssd_chunk_pallas(x, dt, A, B, C, D, state, q_counts, fresh, layer,
                      interpret):
    Bt, W, H, P = x.shape
    G, N = B.shape[2:]
    hpg = H // G
    hb, q = _heads_per_block(hpg, P, N)
    nb = H // hb
    _, dt, cs = _masked(dt, A, q_counts)
    by_block = lambda a: a.transpose(0, 2, 1).reshape(Bt, nb, hb, W)
    rows = jnp.concatenate(
        [by_block(cs), by_block(dt),
         by_block(jnp.exp(cs[:, -1:] - cs) * dt)], axis=2)  # (Bt,nb,3hb,W)

    def x_index(b, j, *_):
        return (b, 0, j)

    def bc_index(b, j, *_):
        return (b, 0, (j * hb) // hpg)

    def state_index(b, j, *_):
        return (layer, b, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Bt, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 3 * hb, W), lambda b, j, *_: (b, j, 0, 0)),
            pl.BlockSpec((1, W, hb * P), x_index),
            pl.BlockSpec((1, W, N), bc_index),
            pl.BlockSpec((1, W, N), bc_index),
            # the layer axis is squeezed: the kernel sees (1, hb, P, N)
            pl.BlockSpec((None, 1, hb, P, N), state_index),
        ],
        out_specs=[
            pl.BlockSpec((1, W, hb * P), x_index),
            pl.BlockSpec((None, 1, hb, P, N), state_index),
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, W=W, hb=hb, P=P, q=q),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bt, W, H * P), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the four prefetched scalars) is the pool
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssd_chunk_update",
        compiler_params=_compiler_params(
            interpret, dimension_semantics=("parallel", "parallel")),
    )(q_counts.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.exp(cs[:, -1]), D.astype(jnp.float32), rows,
      x.reshape(Bt, W, H * P), B.reshape(Bt, W, G * N),
      C.reshape(Bt, W, G * N), state)
    return y.reshape(Bt, W, H, P), state


def ssd_chunk_update(x, dt, A, B, C, D, state, q_counts, layer,
                     impl="auto", interpret=False, fresh=None):
    """One chunk of the Mamba-2 recurrence for every slot.

    x:        (Bt, W, H, P) the rows' inputs, per head.
    dt:       (Bt, W, H) step sizes, after their softplus.
    A, D:     (H,) per head; A is negative.
    B, C:     (Bt, W, G, N) per group; head h uses group h // (H / G).
    state:    (L, Bt, H, P, N) float32, the WHOLE pool; `layer` (a static
              int) picks the layer, in the kernel's BlockSpec.
    q_counts: (Bt,) live rows per slot; rows past the count leave the
              state alone and emit zeros.
    fresh:    (Bt,) bool, or None: slots that read zeros for their state.
    impl: 'auto' (the Mosaic kernel on a TPU, einsums elsewhere, or on a
    TPU with a warning where the shapes break its rules), 'pallas'
    (interpret=True runs it on a CPU), 'xla'.
    Returns (y (Bt, W, H, P) in x's dtype, the updated pool).
    """
    Bt = x.shape[0]
    if fresh is None:
        fresh = jnp.zeros((Bt,), bool)
    impl = _resolve_impl(impl, interpret, x, B, state)
    note_path("ssd_chunk_update", impl)
    if impl == "pallas":
        return _ssd_chunk_pallas(x, dt, A, B, C, D, state, q_counts, fresh,
                                 layer, interpret)
    if impl != "xla":
        raise ValueError(f"unknown ssd_chunk_update impl {impl!r}")
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer])
    y, s1 = _ssd_chunk_xla(x, dt, A, B, C, D, s0, q_counts)
    return y, state.at[layer].set(s1.astype(state.dtype))
