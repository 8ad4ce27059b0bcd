"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692): the chunk update of the serving dispatch.

One call advances every slot's recurrent state by one dispatch of up to W
rows and gives the rows' outputs, in the idiom of ops/ssm.ssd_chunk_update:
a fixed (slots, W) shape whose live rows are runtime data (`q_counts`), the
whole state pool as the operand with the layer picked in the BlockSpec, the
pool written back in place.

Per slot and head, S a (K keys, V values) matrix, g_t <= 0 a log-decay PER
KEY CHANNEL and b_t in (0, 1):

    S_t = Diag(exp g_t) S_{t-1}
    S_t = S_t + b_t k_t (v_t - S_t^T k_t)^T             the delta rule
    o_t = S_t^T q_t

With G the running sum of g over the chunk's rows and u_t the row each
step adds (S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T), the W rows of a chunk
are the same as

    A[t, s]  = sum_c b_t k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <  t
    Aq[t, s] = sum_c     q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    U   = (I + A)^-1 (b v - (b k exp G) S_0)                    (W, V)
    o   = (q exp G) S_0 + Aq U
    S_W = Diag(exp G_W) S_0 + (k exp(G_W - G))^T U

The (I - b k k^T) term is what Mamba-2's form does not have: a chunk's rows
depend on each other through the unit lower triangular (I + A). The kernel
never forms its inverse whole. It inverts the 16-row diagonal blocks: a
pair of rows exactly ([[1, 0], [x, 1]]^-1 = [[1, 0], [-x, 1]]), then pairs of
sub-blocks merged, [[P, 0], [X, Q]]^-1 = [[P^-1, 0], [-Q^-1 X P^-1, Q^-1]],
from 2 rows up to 16: six products, a head's W/16 blocks side by side in
ONE (16, W) matrix whose right operand is the (W, W) block diagonal of the
same blocks. (The finite product (I - N)(I + N^2)(I + N^4)(I + N^8) costs
the same six and is exact in exact arithmetic, but where a block's keys
repeat its terms reach C(15, 7) = 6435 before they cancel: 5e-4 of error in
float32 on such rows, 1e-6 merged; tests/test_kimi_linear.py.) U then
comes by forward substitution over the row blocks,
U_i = T_ii (rhs_i - sum_{j<i} A_ij U_j). Every one of these products is
float32 at HIGHEST. A row block's scores are formed against the columns
s < r0 + 16 alone, all that a mask keeps.

A head is a chain of some twenty small products in which nearly each waits
for the last, and Mosaic keeps MXU work in program order. So a pass of the
kernel's loop takes up to 8 heads in LOCKSTEP, each stage written with the
loop over those heads innermost: a product is followed by the other heads'
independent ones, not by its own successor (3109 scheduled cycles a head
at the parent, 676 now; in the cell, 32 slots x 32 heads, 2.18 -> 0.48 ms a
call: PERF.md, PR 36).

The pairwise decay exp(G_t - G_s) is a VECTOR over the key channels, so A is
not (k k^T) times a matrix of scalars. And G falls by up to ~100 over 64
rows at the published initialisation (A in [1, 16], dt up to 0.1), so
exp(-G_s) alone overflows float32: no quotient of two exponentials is ever
formed. A row block of 16 takes its first row's G as the reference R:
exp(G_t - R) <= 1 on its own rows, exp(R - G_s) <= 1 for every earlier row
and at most the block's own decay for a row inside it (e^26 at the
strongest published decay). The exponent is clamped at 80: a channel that
decays by more than e^80 within 16 rows loses the pairs inside that block.

The state leaf holds S TRANSPOSED, (V, K) a head: the per-key decay then
runs along the lanes. Rows at or past q_counts[b] take g = 0 and b = 0: they
neither decay nor feed the state, and emit zeros. A slot marked `fresh`
reads zeros for S_0 whatever the pool holds.
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

__all__ = ["kda_chunk_update"]

# rows of a diagonal block of (I + A): its decay must stay under e^80
_SUB = 16
_MAX_EXPONENT = 80.0
# the state block of one grid step: as many heads as fit
_STATE_BLOCK_BYTES = 1024 * 1024
# heads a pass of the kernel's loop takes in lockstep, at most. Scheduled
# cycles a head at 1 / 2 / 4 / 8: 2781 / 1543 / 933 / 684, and on the chip
# 2.25 / 1.40 / 0.99 / 0.82 ms a call at the cell's shape (PERF.md, PR 36).
# At 8 a thirtieth of the cycles still wait for a product; the traced body
# and its compile time grow with every head
_PASS = 8
# the kernel's float32 products (A, Aq, the diagonal blocks' inverses, U)
# keep float32 on the MXU. At the default precision they are bfloat16
# passes: on the chip 1.90 ms a call in place of 2.68 at the cell's shape
# with the same error on random keys (PERF.md, PR 34), but where keys repeat
# the entries of a block's inverse are differences of like terms, which
# bfloat16 cannot carry
_HI = lax.Precision.HIGHEST


def _unsupported_reason(q, v, state):
    """Why the Mosaic kernel cannot take this call on a TPU (None when it
    can; interpret mode runs any shape)."""
    W, _, K = q.shape[1:]
    V = v.shape[-1]
    if K % 128 or V % 128:
        return f"key size {K} and value size {V} must be multiples of 128 lanes"
    if W % _SUB or (W // _SUB) & (W // _SUB - 1):
        return f"{W} rows are not a power of two of {_SUB}-row blocks"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {q.dtype} is neither float32 nor bfloat16"
    if state.dtype != jnp.float32:
        return f"the state pool is {state.dtype}, not float32"
    return None


def _resolve_impl(impl, interpret, q, v, state):
    if impl != "auto":
        return impl
    if interpret:
        return "pallas"
    if jax.default_backend() != "tpu":
        return "xla"
    why = _unsupported_reason(q, v, state)
    if why is None:
        return "pallas"
    warnings.warn("kda_chunk_update: impl='auto' on TPU is using the dense "
                  f"form instead of the Mosaic kernel because {why}",
                  stacklevel=3)
    return "xla"


def _masked(q, k, v, g, beta, q_counts):
    """The rows as both forms take them: q, k, b*k and b*v with the dead
    rows at zero, and G, the running sum of the live rows' log-decay."""
    live = (jnp.arange(q.shape[1])[None, :] < q_counts[:, None])
    live = live[:, :, None, None]                           # (Bt, W, 1, 1)
    f32 = jnp.float32
    b = jnp.where(live[..., 0], beta.astype(f32), 0.0)[..., None]
    G = jnp.cumsum(jnp.where(live, g.astype(f32), 0.0), axis=1)
    zero = lambda a: jnp.where(live, a, jnp.zeros_like(a))
    q, k, v = zero(q), zero(k), zero(v)
    kb = (k.astype(f32) * b).astype(k.dtype)
    vb = (v.astype(f32) * b).astype(v.dtype)
    return q, k, kb, vb, G


def _kda_chunk_xla(q, k, kb, vb, G, s0):
    """The chunk form in einsums, float32 throughout, every pairwise decay
    formed from its own difference and (I + A) solved by substitution: the
    CPU's path and the kernel's oracle. One slot at a time (the pairwise
    decays are (W, W, H, K)). s0: (Bt, H, K, V)."""
    W = q.shape[1]
    f32 = jnp.float32
    t_i, s_i = jnp.arange(W)[:, None], jnp.arange(W)[None, :]

    def one(args):
        q, k, kb, vb, G, s0 = (a.astype(f32) for a in args)
        diff = G[:, None] - G[None, :]                      # (t, s, H, K)
        D = jnp.exp(jnp.where((s_i <= t_i)[:, :, None, None], diff,
                              -jnp.inf))
        Aq = jnp.einsum("thk,shk,tshk->hts", q, k, D, precision=_HI)
        A = jnp.einsum("thk,shk,tshk->hts", kb, k, D, precision=_HI)
        A = jnp.where(s_i < t_i, A, 0.0) + jnp.eye(W, dtype=f32)
        rhs = vb - jnp.einsum("thk,hkv->thv", kb * jnp.exp(G), s0,
                              precision=_HI)
        U = jax.scipy.linalg.solve_triangular(
            A, rhs.transpose(1, 0, 2), lower=True, unit_diagonal=True)
        o = jnp.einsum("thk,hkv->thv", q * jnp.exp(G), s0, precision=_HI) \
            + jnp.einsum("hts,hsv->thv", Aq, U, precision=_HI)
        s1 = jnp.exp(G[-1])[:, :, None] * s0 + jnp.einsum(
            "shk,hsv->hkv", k * jnp.exp(G[-1:] - G), U, precision=_HI)
        return o, s1

    return lax.map(one, (q, k, kb, vb, G, s0))


def _kda_kernel(qc_ref, fresh_ref, layer_ref, q_ref, k_ref, kb_ref, vb_ref,
                g_ref, s_ref, o_ref, so_ref, *, W, hb, K, V, C, P):
    """One (slot, block of hb heads). q, k, b*k, b*v and G arrive as
    (W, hb*K) column blocks, the state as (hb, V, K): S transposed. A pass
    of the rolled loop takes P heads in LOCKSTEP: each stage below loops
    over the pass's heads innermost, so that in program order, which is the
    order Mosaic keeps MXU work in, a product is followed by the other
    heads' independent ones and not by the one that waits for it."""
    del layer_ref                                # the pool's index map reads it
    b = pl.program_id(0)
    qn = qc_ref[b]
    f32 = jnp.float32

    @pl.when(qn == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = s_ref[...]

    @pl.when(qn > 0)
    def _work():
        cd = q_ref.dtype
        dot = lambda a, b_, dims, precision=None: lax.dot_general(
            a, b_, (dims, ((), ())), preferred_element_type=f32,
            precision=precision)
        hi = _HI
        nt, nn, tn = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))
        blocks = list(enumerate(range(0, W, C)))
        iota = lambda shape, d: lax.broadcasted_iota(jnp.int32, shape, d)
        # what no head changes, once a grid step. A row block's scores are
        # (C, r0 + C): the columns s < r0 + C are all a mask keeps.
        # Queries see s <= t; the block's own strictly lower part is N
        upto, own = [], []
        for _, r0 in blocks:
            t, s = iota((C, r0 + C), 0) + r0, iota((C, r0 + C), 1)
            upto.append(s <= t)
            own.append((s >= r0) & (s < t))
        # a head's W // C diagonal blocks side by side, (C, W): the identity
        # in that form, and the (W, W) block diagonal a product takes as its
        # right operand
        row, col = iota((C, W), 0), iota((C, W), 1) % C
        eye = (col == row).astype(f32)
        # by size: the entries that couple two neighbouring sub-blocks of
        # `size` rows, which merge into one of 2 * size
        halves, size = {}, 1
        while size < C:
            halves[size] = (row // (2 * size) == col // (2 * size)) \
                & (row // size != col // size)
            size *= 2
        diagonal = iota((W, W), 0) // C == iota((W, W), 1) // C
        spread = lambda m: jnp.where(
            diagonal, jnp.concatenate([m] * (W // C), axis=0), 0.0)
        # all False for a fresh slot (fresh is 0 or 1), else all True
        kept = iota((V, K), 0) >= fresh_ref[b] * V
        live = iota((W, 1), 0) < qn

        def heads(u, _):
            at = [u * P + p for p in range(P)]
            kc = [pl.ds(pl.multiple_of(i * K, K), K) for i in at]
            vc = [pl.ds(pl.multiple_of(i * V, V), V) for i in at]
            G = [g_ref[0, :, c] for c in kc]                    # (W, K)
            q = [q_ref[0, :, c].astype(f32) for c in kc]
            k = [k_ref[0, :, c].astype(f32) for c in kc]
            kb = [kb_ref[0, :, c].astype(f32) for c in kc]
            vb = [vb_ref[0, :, c].astype(f32) for c in vc]
            # a fresh slot reads zeros whatever the pool holds, a NaN
            # from the slot's last owner included
            s0 = [jnp.where(kept, s_ref[0, i], 0.0) for i in at]  # (V, K)
            # Aq and A, a block of C rows at a time against its own
            # reference: no factor passes exp(_MAX_EXPONENT)
            Aq, A, N = ([[] for _ in at] for _ in range(3))
            for j, r0 in blocks:
                n = r0 + C
                for p in range(P):
                    ref = G[p][r0:r0 + 1]
                    decay = jnp.exp(G[p][r0:n] - ref)           # (C, K)
                    rows = jnp.concatenate(
                        [q[p][r0:n] * decay, kb[p][r0:n] * decay], axis=0)
                    cols = k[p][:n] * jnp.exp(
                        jnp.minimum(ref - G[p][:n], _MAX_EXPONENT))
                    both = dot(rows, cols, nt, hi)              # (2C, n)
                    Aq[p].append(jnp.where(upto[j], both[:C], 0.0))
                    A[p].append(both[C:, :r0] if r0 else None)
                    block = jnp.where(own[j], both[C:], 0.0)
                    if n < W:
                        block = jnp.concatenate(
                            [block, jnp.zeros((C, W - n), f32)], axis=1)
                    N[p].append(block)
            # the products with the carried state: (q exp G) S_0 over
            # (b k exp G) S_0
            carried = []
            for p in range(P):
                eG = jnp.exp(G[p])
                carried.append(dot(
                    jnp.concatenate([q[p] * eG, kb[p] * eG], axis=0)
                    .astype(cd), s0[p].astype(cd), nt))         # (2W, V)
            # the diagonal blocks' inverses, every matrix a (C, W) row of
            # blocks: exact for pairs of rows, then pairs of sub-blocks
            # merged, [[P, 0], [X, Q]]^-1 = [[P^-1, 0], [-Q^-1 X P^-1,
            # Q^-1]], up to the block
            N = [sum(n[1:], n[0]) for n in N]
            T, size = [eye - jnp.where(halves[1], n, 0.0) for n in N], 2
            while size < C:
                X = [dot(t, spread(jnp.where(halves[size], n, 0.0)), nn, hi)
                     for t, n in zip(T, N)]
                T = [t - dot(x, spread(t), nn, hi) for t, x in zip(T, X)]
                size *= 2
            # U = (I + A)^-1 rhs by forward substitution over the row blocks
            U = [[] for _ in at]
            for j, r0 in blocks:
                rhs = [vb[p][r0:r0 + C] - carried[p][W + r0:W + r0 + C]
                       for p in range(P)]
                if r0:
                    rhs = [rhs[p] - dot(A[p][j], jnp.concatenate(
                        U[p], axis=0), nn, hi) for p in range(P)]
                for p in range(P):
                    U[p].append(dot(T[p][:, r0:r0 + C], rhs[p], nn, hi))
            for p, i in enumerate(at):
                Uc = jnp.concatenate(U[p], axis=0).astype(cd)   # (W, V)
                o = carried[p][:W] + jnp.concatenate(
                    [dot(Aq[p][j].astype(cd), Uc[:r0 + C], nn)
                     for j, r0 in blocks], axis=0)
                last = G[p][W - 1:W]                            # (1, K)
                o_ref[0, :, vc[p]] = jnp.where(live, o, 0.0).astype(
                    o_ref.dtype)
                so_ref[0, i] = s0[p] * jnp.exp(last) + dot(
                    Uc, (k[p] * jnp.exp(last - G[p])).astype(cd), tn)
            return _

        lax.fori_loop(0, hb // P, heads, None)


def _largest_divisor(n, most):
    return max(d for d in range(1, most + 1) if n % d == 0)


def _tile(W, H, K, V):
    """(heads a grid step: as many as fit the state block; rows of a
    diagonal block; heads a pass of the kernel's loop), from the shapes."""
    hb = H
    while hb > 1 and (hb * K * V * 4 > _STATE_BLOCK_BYTES or H % hb):
        hb -= 1
    return hb, _largest_divisor(W, _SUB), _largest_divisor(hb, _PASS)


# one jitted function, `layer` a traced scalar: a model's layers, and every
# program of a process that calls it at the same shapes, share ONE trace of
# the kernel (its body is eight heads long)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_pallas(q, k, kb, vb, G, state, q_counts, fresh, layer,
                      interpret):
    Bt, W, H, K = q.shape
    V = vb.shape[-1]
    hb, C, P = _tile(W, H, K, V)

    def rows_index(b, j, *_):
        return (b, 0, j)

    def state_index(b, j, counts, fresh, layer):
        return (layer[0], b, j, 0, 0)

    keys = pl.BlockSpec((1, W, hb * K), rows_index)
    values = pl.BlockSpec((1, W, hb * V), rows_index)
    # the layer axis is squeezed: the kernel sees (1, hb, V, K)
    pool = pl.BlockSpec((None, 1, hb, V, K), state_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Bt, H // hb),
        in_specs=[keys, keys, keys, values, keys, pool],
        out_specs=[values, pool],
    )
    flat = lambda a: a.reshape(Bt, W, -1)
    o, state = pl.pallas_call(
        functools.partial(_kda_kernel, W=W, hb=hb, K=K, V=V, C=C, P=P),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bt, W, H * V), vb.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the three prefetched scalars) is the pool
        input_output_aliases={8: 1},
        interpret=interpret,
        name="kda_chunk_update",
        compiler_params=_compiler_params(
            interpret, dimension_semantics=("parallel", "parallel")),
    )(q_counts.astype(jnp.int32), fresh.astype(jnp.int32),
      layer.reshape(1), flat(q), flat(k), flat(kb), flat(vb), flat(G), state)
    return o.reshape(Bt, W, H, V), state


def kda_chunk_update(q, k, v, g, beta, state, q_counts, layer, impl="auto",
                     interpret=False, fresh=None):
    """One chunk of the gated delta rule for every slot.

    q, k:     (Bt, W, H, K) queries and keys as the rule takes them (each
              head's L2-normalised, the query scaled).
    v:        (Bt, W, H, V).
    g:        (Bt, W, H, K) float32 log-decay a key channel, <= 0.
    beta:     (Bt, W, H) the rule's step, in (0, 1).
    state:    (L, Bt, H, V, K) float32, the WHOLE pool, S transposed;
              `layer` (an int, or a traced int32 scalar) picks the layer,
              in the kernel's BlockSpec.
    q_counts: (Bt,) live rows per slot; rows past the count leave the
              state alone and emit zeros.
    fresh:    (Bt,) bool, or None: slots that read zeros for their state.
    impl: 'auto' (the Mosaic kernel on a TPU, the dense form elsewhere, or
    on a TPU with a warning where the shapes break the kernel's rules),
    'pallas' (interpret=True runs it on a CPU), 'xla'.
    Returns (o (Bt, W, H, V) in v's dtype, the updated pool).
    """
    Bt = q.shape[0]
    if fresh is None:
        fresh = jnp.zeros((Bt,), bool)
    impl = _resolve_impl(impl, interpret, q, v, state)
    note_path("kda_chunk_update", impl)
    rows = _masked(q, k, v, g, beta, q_counts)
    if impl == "pallas":
        hb, C, P = _tile(q.shape[1], q.shape[2], q.shape[3], v.shape[-1])
        note_tile("kda_chunk_update",
                  **{"heads": hb, "rows": q.shape[1], "block": C, "pass": P})
        return _kda_chunk_pallas(*rows, state, q_counts, fresh,
                                 jnp.asarray(layer, jnp.int32),
                                 interpret=interpret)
    if impl != "xla":
        raise ValueError(f"unknown kda_chunk_update impl {impl!r}")
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer])
    o, s1 = _kda_chunk_xla(*rows, s0.transpose(0, 1, 3, 2))
    return o.astype(v.dtype), state.at[layer].set(
        s1.transpose(0, 1, 3, 2).astype(state.dtype))
