"""Pallas TPU fused attention with in-kernel dropout.

Reference parity: src/operator/contrib/transformer.cu
(interleaved_matmul_selfatt_qk/valatt — the reference's fused BERT
attention) + the engine-RNG dropout of src/operator/nn/dropout-inl.h,
fused into ONE kernel here.

Why this kernel exists: at BERT shapes (T≈512) the XLA einsum attention is
MXU-bound and fine, but attention-probability dropout materializes a
(B, H, T, T) random mask from the host-seeded PRNG stream — measured at
~37 ms of a 177 ms step (21%) on v5e. This kernel keeps the whole
softmax→dropout→PV pipeline in VMEM and draws the mask from the TPU
core's hardware PRNG (pltpu.prng_random_bits), seeded deterministically
per (step_seed, batch, head) so the backward pass regenerates the exact
mask instead of storing it (the flash-attention recompute trick applied
to the dropout mask).

Scope: whole-row kernel — each (batch, head) grid cell holds its full
(Tq, Tk) score tile in VMEM. That is the right shape for T ≤ ~1024 (BERT
512 / GPT-2 1024, both target workloads); longer sequences take the
blockwise scan path in ops/attention.py (O(T) memory).

Masking: supports an additive key bias of shape (B, Tk) (the key-padding
mask MultiHeadAttention uses) and causal masking. Fully-masked rows
yield zeros, matching dot_product_attention's contract.
"""
from __future__ import annotations

import functools
import math
import warnings

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_paths import note_path, note_tile

NEG_INF = -1e30
# Whole-row VMEM budget cap. Verified on v5e: T=1024 forward+backward
# compiles and runs for both f32 and bf16 (Mosaic reuses the (T, T)
# scratch tiles); beyond it the blockwise scan path takes over.
MAX_FUSED_T = 1024
# Mosaic's default scoped-VMEM limit is below what a whole-row (T, T) f32
# score tile plus its temporaries needs; every kernel here asks for the
# same raised limit (a v5e core has 128 MiB of VMEM).
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _compiler_params(interpret, dimension_semantics=None):
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES,
                                dimension_semantics=dimension_semantics)


def _scores(q_ref, k_ref, bias_ref, scale, causal, tq, tk):
    # operands stay in their native dtype (bf16 rides the MXU single-pass);
    # accumulation is f32 via preferred_element_type
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    # bias ref holds the whole (B, Tk) array; pick this grid cell's row
    s = s + bias_ref[pl.program_id(0)][None, :].astype(jnp.float32)
    if causal:
        qpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(qpos + (tk - tq) >= kpos, s, NEG_INF)
    return s


def _softmax_parts(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows (m == NEG_INF) must contribute zeros, not exp(0)
    e = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.sum(e, axis=-1, keepdims=True)
    return e, l


def _software_bits(s0, s1, shape):
    """Counter-based software PRNG (murmur3 finalizer mixing) used when the
    hardware PRNG is unavailable (interpret mode on CPU). Deterministic in
    (s0, s1, position) so the backward pass regenerates the same mask."""
    pos = (lax.broadcasted_iota(jnp.uint32, shape, 0)
           * jnp.uint32(shape[1])
           + lax.broadcasted_iota(jnp.uint32, shape, 1))

    def mix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        return x ^ (x >> 16)

    return mix(mix(pos ^ s0) ^ s1)


def _keep_mask(seed_ref, p_drop, shape, interpret=False):
    # one seed per (batch, head) grid cell; the hardware PRNG accepts at
    # most two seed words, so both 32-bit key words are used and the cell
    # index is folded into the second (distinct cells and distinct keys
    # both perturb the seed)
    cell = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    if interpret:
        bits = _software_bits(seed_ref[0].astype(jnp.uint32),
                              (seed_ref[1] ^ cell).astype(jnp.uint32),
                              shape)
    else:
        pltpu.prng_seed(seed_ref[0], seed_ref[1] ^ cell)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(min(int(p_drop * 2.0 ** 32), 2 ** 32 - 1))


def _fwd_kernel(seed_ref, bias_ref, q_ref, k_ref, v_ref, o_ref, *,
                scale, p_drop, causal, tq, tk, interpret=False):
    s = _scores(q_ref, k_ref, bias_ref, scale, causal, tq, tk)
    e, l = _softmax_parts(s)
    inv_keep = 1.0
    if p_drop > 0.0:
        keep = _keep_mask(seed_ref, p_drop, (tq, tk), interpret)
        e = jnp.where(keep, e, 0.0)
        inv_keep = 1.0 / (1.0 - p_drop)
    v = v_ref[0, 0]
    o = lax.dot_general(e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    o = o * (inv_keep / jnp.maximum(l, 1e-30))
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _bwd_kernel(seed_ref, bias_ref, q_ref, k_ref, v_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, scale, p_drop, causal, tq, tk,
                interpret=False):
    s = _scores(q_ref, k_ref, bias_ref, scale, causal, tq, tk)
    e, l = _softmax_parts(s)
    p = e / jnp.maximum(l, 1e-30)           # pre-dropout softmax
    inv_keep = 1.0
    a = p
    if p_drop > 0.0:
        # same seed → same mask (the recompute trick; _keep_mask is pure)
        keep = _keep_mask(seed_ref, p_drop, (tq, tk), interpret)
        inv_keep = 1.0 / (1.0 - p_drop)
        a = jnp.where(keep, p, 0.0) * inv_keep
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    da = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)   # (Tq, Tk)
    dp = da * inv_keep
    if p_drop > 0.0:
        dp = jnp.where(keep, dp, 0.0)
    d_row = jnp.sum(a * da, axis=-1, keepdims=True)  # = rowsum(dO ⊙ O)
    ds = (p * (dp - d_row) * scale).astype(q_ref.dtype)
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    dq_ref[0, 0] = lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[0, 0] = lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    dv_ref[0, 0] = lax.dot_general(
        a.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)


def _specs(B, H, tq, tk, D):
    qspec = pl.BlockSpec((1, 1, tq, D), lambda b, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, tk, D), lambda b, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM)
    # bias blocks as the whole (B, Tk) array: a (1, Tk) block would violate
    # the sublane-divisibility rule for arbitrary B
    bspec = pl.BlockSpec((B, tk), lambda b, h: (0, 0),
                         memory_space=pltpu.VMEM)
    return qspec, kspec, bspec


# ---------------------------------------------------------------------------
# packed-layout kernels: q/k/v as (B, T, H*D) — the raw projection output.
# Heads are STATIC column slices inside the kernel (grid over B only), so
# the caller pays no (B,T,H,D)->(B,H,T,D) relayout copy in HBM — measured
# ~6.6 ms/step of pure transpose traffic on BERT-base. Block shapes
# (1, T, C) satisfy the Mosaic (8, 128)-divisibility rule for every
# transformer width (C is a multiple of 128), which per-head BTHD blocks
# (…, 1, D) cannot. Dropout seeds are b*H + h — bit-identical masks to the
# per-(b, h)-grid BHTD kernels.
# ---------------------------------------------------------------------------

def _head_scores(q, k, bias_ref, scale, causal, tq, tk):
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[pl.program_id(0)][None, :].astype(jnp.float32)
    if causal:
        qpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(qpos + (tk - tq) >= kpos, s, NEG_INF)
    return s


def _packed_keep_mask(seed_ref, p_drop, shape, h, H, interpret):
    cell = pl.program_id(0) * H + h
    if interpret:
        bits = _software_bits(seed_ref[0].astype(jnp.uint32),
                              (seed_ref[1] ^ cell).astype(jnp.uint32),
                              shape)
    else:
        pltpu.prng_seed(seed_ref[0], seed_ref[1] ^ cell)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(min(int(p_drop * 2.0 ** 32), 2 ** 32 - 1))


def _fwd_kernel_packed(seed_ref, bias_ref, q_ref, k_ref, v_ref, o_ref, *,
                       scale, p_drop, causal, tq, tk, H, D,
                       interpret=False):
    for h in range(H):
        c0, c1 = h * D, (h + 1) * D
        q = q_ref[0, :, c0:c1]
        k = k_ref[0, :, c0:c1]
        s = _head_scores(q, k, bias_ref, scale, causal, tq, tk)
        e, l = _softmax_parts(s)
        inv_keep = 1.0
        if p_drop > 0.0:
            keep = _packed_keep_mask(seed_ref, p_drop, (tq, tk), h, H,
                                     interpret)
            e = jnp.where(keep, e, 0.0)
            inv_keep = 1.0 / (1.0 - p_drop)
        v = v_ref[0, :, c0:c1]
        o = lax.dot_general(e.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        o = o * (inv_keep / jnp.maximum(l, 1e-30))
        o_ref[0, :, c0:c1] = o.astype(o_ref.dtype)


def _bwd_kernel_packed(seed_ref, bias_ref, q_ref, k_ref, v_ref, do_ref,
                       dq_ref, dk_ref, dv_ref, *, scale, p_drop, causal,
                       tq, tk, H, D, interpret=False):
    for h in range(H):
        c0, c1 = h * D, (h + 1) * D
        q = q_ref[0, :, c0:c1]
        k = k_ref[0, :, c0:c1]
        s = _head_scores(q, k, bias_ref, scale, causal, tq, tk)
        e, l = _softmax_parts(s)
        p = e / jnp.maximum(l, 1e-30)
        inv_keep = 1.0
        a = p
        if p_drop > 0.0:
            keep = _packed_keep_mask(seed_ref, p_drop, (tq, tk), h, H,
                                     interpret)
            inv_keep = 1.0 / (1.0 - p_drop)
            a = jnp.where(keep, p, 0.0) * inv_keep
        v = v_ref[0, :, c0:c1]
        do = do_ref[0, :, c0:c1]
        da = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dp = da * inv_keep
        if p_drop > 0.0:
            dp = jnp.where(keep, dp, 0.0)
        d_row = jnp.sum(a * da, axis=-1, keepdims=True)
        ds = (p * (dp - d_row) * scale).astype(q_ref.dtype)
        dq_ref[0, :, c0:c1] = lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_ref[0, :, c0:c1] = lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)
        dv_ref[0, :, c0:c1] = lax.dot_general(
            a.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)


def _packed_specs(B, tq, tk, C):
    qspec = pl.BlockSpec((1, tq, C), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, tk, C), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((B, tk), lambda b: (0, 0),
                         memory_space=pltpu.VMEM)
    return qspec, kspec, bspec


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_packed(q, k, v, bias, seed, scale, p_drop, causal, H,
                  interpret):
    return _fused_packed_fwd(q, k, v, bias, seed, scale, p_drop, causal,
                             H, interpret)[0]


def _fused_packed_fwd(q, k, v, bias, seed, scale, p_drop, causal, H,
                      interpret):
    B, Tq, C = q.shape
    Tk = k.shape[1]
    qspec, kspec, bspec = _packed_specs(B, Tq, Tk, C)
    kernel = functools.partial(_fwd_kernel_packed, scale=scale,
                               p_drop=p_drop, causal=causal, tq=Tq,
                               tk=Tk, H=H, D=C // H, interpret=interpret)
    out = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec,
                  qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(seed, bias, q, k, v)
    return out, (q, k, v, bias, seed)


def _fused_packed_bwd(scale, p_drop, causal, H, interpret, res, g):
    q, k, v, bias, seed = res
    B, Tq, C = q.shape
    Tk = k.shape[1]
    qspec, kspec, bspec = _packed_specs(B, Tq, Tk, C)
    kernel = functools.partial(_bwd_kernel_packed, scale=scale,
                               p_drop=p_drop, causal=causal, tq=Tq,
                               tk=Tk, H=H, D=C // H, interpret=interpret)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec,
                  qspec, kspec, kspec, qspec],
        out_specs=(qspec, kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(seed, bias, q, k, v, g)
    return dq, dk, dv, jnp.zeros_like(bias), \
        _np.zeros(seed.shape, jax.dtypes.float0)


_fused_packed.defvjp(_fused_packed_fwd, _fused_packed_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused(q, k, v, bias, seed, scale, p_drop, causal, interpret):
    return _fused_fwd(q, k, v, bias, seed, scale, p_drop, causal,
                      interpret)[0]


def _fused_fwd(q, k, v, bias, seed, scale, p_drop, causal, interpret):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qspec, kspec, bspec = _specs(B, H, Tq, Tk, D)
    kernel = functools.partial(_fwd_kernel, scale=scale, p_drop=p_drop,
                               causal=causal, tq=Tq, tk=Tk,
                               interpret=interpret)
    out = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec,
                  qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(seed, bias, q, k, v)
    return out, (q, k, v, bias, seed)


def _fused_bwd(scale, p_drop, causal, interpret, res, g):
    q, k, v, bias, seed = res
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qspec, kspec, bspec = _specs(B, H, Tq, Tk, D)
    kernel = functools.partial(_bwd_kernel, scale=scale, p_drop=p_drop,
                               causal=causal, tq=Tq, tk=Tk,
                               interpret=interpret)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec,
                  qspec, kspec, kspec, qspec],
        out_specs=(qspec, kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(seed, bias, q, k, v, g)
    return dq, dk, dv, jnp.zeros_like(bias), \
        _np.zeros(seed.shape, jax.dtypes.float0)


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# ragged paged attention (the serving hot path) — ONE span kernel for
# prefill chunks, plain decode, and speculative verification.
#
# Each slot attends over its own live KV pages only. The dense alternative
# (PagedKVCache._gather) re-materializes the FULL (B, max_length, H, D)
# cache view from HBM every decoded token — at GPT-2 774M serving shapes
# that is max_length/live_length times more HBM traffic than the tokens
# actually alive. The kernel follows the ragged paged attention design
# (arxiv 2604.15464): the page table and per-slot lengths ride in
# scalar-prefetch SMEM so the BlockSpec index_map DMAs exactly the pages
# the slot owns, and HBM traffic scales with the LIVE length, not
# max_length.
#
# Grid (slots, ceil(P / KB)): a step consumes a BLOCK of KB consecutive
# logical pages of its slot, KB*S keys. The block arrives as KB page
# operands of the same pool, operand i of step p holding logical page
# p*KB + i through _span_block_table, the scalar-prefetch table of
# physical pages a step an operand; each operand is pipelined (double-
# buffered) on its own. At a step where operand i has no live page (past
# the slot's extent, a slot shorter than the block, an idle slot) the
# table repeats the page the operand held the step before, slots
# included: Pallas elides the DMA of a repeated block index, so each live
# page is fetched once and no other. P that KB does not divide needs no
# padding of the page table: the block table is as wide as the grid and
# the position mask below drops the keys.
#
# For each KV head the step runs ONCE over the block: (Sr, D) x (D, KB*S)
# scores in float32, one mask, one row max / exp / row sum over
# (Sr, KB*S), (Sr, KB*S) x (KB*S, D) with the probabilities cast to the
# page dtype, one rescale of the (Sr, D) accumulator. The block's keys of
# a head are the KB pages' column slices set one under another
# (sublane-aligned, so the concatenation moves no lane); int8 pages are
# widened and multiplied by their own (page, head) scale first, read at
# the block table's page, so HBM traffic stays one byte an element.
#
# The heads are walked in passes of _SPAN_PASS_HEADS heads whose columns
# are whole 128-lane tiles (_span_passes), a pass ONE dynamic
# tile-aligned lane slice of every operand in a ROLLED loop: the body is
# traced and lowered for one pass, not H times (36 layers x 20 heads
# unrolled were most of the 42 s the GPT-2 program took to trace on the
# chip's host: PERF.md, PR 28), and a pass still holds independent heads
# for the scheduler to interleave.
#
# KB is chosen by _span_block_pages from what the call's shapes show (the
# page size, the stacked rows, P): never from a model's name, never from an
# argument. kernel_paths.TILES records the block each call was built with.
#
# Each of the B slots in a (B, Sq, H, D) dispatch consumes q_counts[b]
# query tokens: a decode slot 1, a speculative verify S, a prefill chunk
# C, an idle slot 0. Query row j of slot b sits at absolute position
# lengths[b]-1+j, so it may attend key positions < lengths[b]+j — the
# per-position CAUSAL OFFSET — and rows >= q_counts[b] are dead: they
# accumulate nothing and emit exact zeros. The grid skips dead rows AND
# dead blocks (a slot's page extent stretches only to
# lengths[b] + q_counts[b] - 1; an idle slot visits no block at all);
# inside the last live block, keys past the extent are masked by position.
#
# Layout: the kernel takes the WHOLE pool as PagedKVCache stores it,
# (L, num_pages, S, H*D), and picks its layer and page in the page
# BlockSpec's index, so the operand is the donated pool itself and no
# program slices or copies it. (Reshaping a pool that ends in (H, D) is
# not free on the chip: the tiled layout pads (20, 64) to (24, 128), and
# XLA answers with copies of the whole pool.) Heads are static 64-aligned
# column slices exactly like the packed training kernels above, so the
# (8, 128) Mosaic rule holds for every transformer width. The online-
# softmax accumulators live in VMEM scratch, one row per query position,
# and persist across the sequential minor block-grid dimension.
# ---------------------------------------------------------------------------

def _ragged_unsupported_reason(q, k_pages):
    """Why the ragged Pallas kernel cannot take this call on real TPU
    hardware (None when it can; interpret mode runs any shape).
    q: (B, H, D) or (B, Sq, H, D); k_pages: the packed pool
    (L, num_pages, S, H*D)."""
    D = q.shape[-1]
    S, HD = k_pages.shape[2:]
    H = HD // D
    if (H * D) % 128 or D % 64:
        return (f"heads*head_dim={H}*{D} is not a multiple of 128 lanes "
                "with 64-aligned head slices")
    if S % 8:
        return f"page size {S} breaks the sublane rule (multiple of 8)"
    if k_pages.dtype == jnp.int8 and S % 32:
        return f"int8 pages need page size {S} to be a multiple of 32"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {q.dtype} is neither float32 nor bfloat16"
    return None


def ragged_supported(q, k_pages):
    """Can the ragged Pallas kernel take this call on real TPU
    hardware? (Interpret mode runs any shape.)"""
    return _ragged_unsupported_reason(q, k_pages) is None


def _resolve_ragged_impl(impl, interpret, q, k_pages):
    """'auto' -> 'pallas' | 'xla'. On a TPU the only way 'auto' reaches
    the dense reference is an unsupported shape, and that is said out
    loud: the dense path re-reads max_length of cache per token, so a
    benchmark must never land on it unnoticed."""
    if impl != "auto":
        return impl
    if interpret:
        return "pallas"
    if jax.default_backend() != "tpu":
        return "xla"
    why = _ragged_unsupported_reason(q, k_pages)
    if why is None:
        return "pallas"
    warnings.warn(
        "ragged paged attention: impl='auto' on TPU is using the dense "
        f"XLA reference instead of the Pallas kernel because {why}",
        stacklevel=3)
    return "xla"


# What bounds the span kernel's block of keys, measured on a v5e (PR 28,
# PERF.md section 6): a grid step costs about 7 us at GPT-2's 20 heads
# whatever its keys and 5 ns a key on top, so the block is as wide as the
# slot has pages, up to the widest measured; at 320 stacked rows a head
# 256 keys (320 KB of float32 scores a head) beat both 128 and 512
_SPAN_MAX_KEYS = 1024
_SPAN_SCORE_BYTES = 512 * 1024
# heads a pass of the rolled head loop: the scheduler interleaves their
# independent chains (10 a pass were 4% faster at GPT-2's widths and cost
# twice the lowering; 2 a pass were 23% slower at Falcon-H1's)
_SPAN_PASS_HEADS = 4


def _span_block_pages(S, Sr, P):
    """KB, the pages one grid step of the span kernel attends: as many
    as keep KB*S within _SPAN_MAX_KEYS and the (Sr, KB*S) float32 scores
    of a head within _SPAN_SCORE_BYTES; a power of two, so that equal
    shapes give equal blocks; at least one page and at most the P a slot
    has. All static at trace time."""
    keys = min(_SPAN_SCORE_BYTES // (4 * Sr), _SPAN_MAX_KEYS)
    kb = 1
    while 2 * kb * S <= keys and 2 * kb <= P:
        kb *= 2
    return kb


def _span_live_pages(lengths, q_counts, S):
    """Pages a slot's queries reach: the furthest live query (row
    q_count - 1) sits at position length + q_count - 2; an idle slot
    (q_count 0) owns none at all — the ceil formula alone would still
    give it ceil((length - 1) / S)."""
    return jnp.where(q_counts == 0, 0,
                     (lengths + q_counts - 1 + S - 1) // S)


def _span_block_table(page_table, lengths, q_counts, S, KB):
    """(B, ceil(P / KB) * KB) int32: at [b, p*KB + i] the physical page
    that operand i of the span kernel holds at grid step (b, p). That is
    logical page p*KB + i of slot b while it is live (the extent
    stretched to cover the slot's furthest live query, nothing for an
    idle slot); at every other step the page the operand held at the
    step BEFORE, in the grid's own order, slots included: the block
    index repeats, the pipeline skips the DMA, and the kernel fetches
    each live page once and no other (but what the operands hold before
    their first live page: the first slot's pages). Computed in XLA, a
    few small integer operations, identical in every layer of a
    program; the index_maps and the int8 kernel's scale lookup only
    read it, so a scale always belongs to the page the DMA fetched."""
    B, P = page_table.shape
    steps = B * -(-P // KB)
    n_live = _span_live_pages(lengths, q_counts, S)
    j = jnp.arange(steps // B * KB)
    live = (j[None, :] < n_live[:, None]).reshape(steps, KB)
    phys = page_table[:, jnp.minimum(j, P - 1)].reshape(steps, KB)
    held = lax.cummax(jnp.where(live, jnp.arange(steps)[:, None], 0), axis=0)
    return jnp.take_along_axis(phys, held, axis=0).reshape(B, -1)


def _span_passes(H, D):
    """(heads, rolled): how the span kernel walks its H packed heads. A
    pass takes `heads` of them, up to _SPAN_PASS_HEADS, whose columns are
    whole 128-lane tiles (four heads of 64, of 128), so that a pass is
    ONE tile-aligned lane slice of every operand, dynamic in a ROLLED
    loop over the passes: the body is traced and lowered for one pass,
    not H times. One pass that holds every head runs inline; widths that
    give no such pass (the interpreted tests' small heads) run a head a
    pass inline."""
    for heads in range(min(_SPAN_PASS_HEADS, H), 0, -1):
        if H % heads == 0 and (heads * D) % 128 == 0:
            return heads, heads < H
    return 1, False


def _ragged_span_kernel(pages_ref, len_ref, qc_ref, *refs, scale, S, Sq, H,
                        D, KB, group=1, quant=False):
    """H KV heads, each against a block of Sq rows: `group` query heads
    stacked, Sq // group positions each (plain multi-head: group 1), and
    KB pages of keys a step. pages_ref: _span_block_table's. refs:
    [k_scale, v_scale] (int8 pages: the per-(page, head) float32 scales,
    in SMEM beside it), q, KB pages of K, KB pages of V, out, and the
    running max, denominator and numerator."""
    kscale_ref = vscale_ref = None
    if quant:
        kscale_ref, vscale_ref, *refs = refs
    q_ref, (o_ref, m_ref, l_ref, acc_ref) = refs[0], refs[1 + 2 * KB:]
    k_refs, v_refs = refs[1:1 + KB], refs[1 + KB:1 + 2 * KB]
    heads, rolled = _span_passes(H, D)
    lanes = heads * D

    def each_pass(body):
        """body(first head, columns) for every pass over the heads."""
        if rolled:
            lax.fori_loop(0, H // heads, lambda j, _: body(
                j * heads, pl.ds(pl.multiple_of(j * lanes, lanes), lanes)),
                None)
        else:
            for j in range(H // heads):
                body(j * heads, pl.ds(j * lanes, lanes))

    b = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[b]
    qn = qc_ref[b]
    n_live = _span_live_pages(length, qn, S)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * KB < n_live)
    def _accumulate():
        # rows are query positions, columns token positions in this
        # block; row j's causal window is pos < length + j, and rows past
        # the slot's span are fully masked (they emit zeros). A page of
        # the block past the live extent holds another page's keys: its
        # positions are >= length + qn - 1, so the same mask drops them
        rows = lax.broadcasted_iota(jnp.int32, (Sq, KB * S), 0)
        # under grouped KV heads a row's query position is its index
        # within its own head's stack, built from compares alone
        for _ in range(1, group):
            rows = rows - jnp.where(rows >= Sq // group, Sq // group, 0)
        cols = p * (KB * S) \
            + lax.broadcasted_iota(jnp.int32, (Sq, KB * S), 1)
        valid = (cols < length + rows) & (rows < qn)
        if quant:
            phys = [pages_ref[b, p * KB + i] for i in range(KB)]
            head_of_lane = lax.broadcasted_iota(
                jnp.int32, (1, lanes), 1) // D

        def block(page_refs, scale_ref, h0, columns):
            """A pass's (KB*S, lanes) keys or values of this block: the
            KB pages' column slices one under another (sublane-aligned:
            no lane moves), int8 pages widened and multiplied each by
            its own (page, head) scales first."""
            parts = [r[0, :, columns] for r in page_refs]
            if quant:
                for i, x in enumerate(parts):
                    sc = scale_ref[phys[i], h0]
                    for t in range(1, heads):
                        sc = jnp.where(head_of_lane < t, sc,
                                       scale_ref[phys[i], h0 + t])
                    parts[i] = x.astype(jnp.float32) * sc
            return parts[0] if KB == 1 else jnp.concatenate(parts, axis=0)

        def attend(h0, columns):
            qs = q_ref[0, :, columns]                      # (Sq, lanes)
            ks = block(k_refs, kscale_ref, h0, columns)    # (KB*S, lanes)
            vs = block(v_refs, vscale_ref, h0, columns)
            for t in range(heads):
                h, c0, c1 = h0 + t, t * D, (t + 1) * D
                s = lax.dot_general(qs[:, c0:c1], ks[:, c0:c1],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                s = jnp.where(valid, s * scale, NEG_INF)   # (Sq, KB*S)
                m_prev = m_ref[h][:, :1]                   # (Sq, 1)
                l_prev = l_ref[h][:, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                e = jnp.where(m_new <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
                alpha = jnp.where(m_new <= NEG_INF / 2, 1.0,
                                  jnp.exp(m_prev - m_new))
                v = vs[:, c0:c1]
                pv = lax.dot_general(e.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                acc_ref[h] = acc_ref[h] * alpha + pv
                l_new = l_prev * alpha + jnp.sum(e, axis=-1, keepdims=True)
                l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
                m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

        each_pass(attend)

    @pl.when(p == pl.num_programs(1) - 1)
    def _emit():
        def emit(h0, columns):
            outs = [acc_ref[h0 + t]
                    / jnp.maximum(l_ref[h0 + t][:, :1], 1e-30)
                    for t in range(heads)]
            o_ref[0, :, columns] = (
                outs[0] if heads == 1
                else jnp.concatenate(outs, axis=1)).astype(o_ref.dtype)

        each_pass(emit)


def _ragged_mq_reference(q, k_pages, v_pages, page_table, lengths, scale,
                         k_scale=None, v_scale=None, layer=0):
    """Dense XLA fallback/oracle for the multi-query kernel: full gather
    of `layer`'s pages out of the packed pools, unpacked to (H, D) here,
    per-position causal-offset mask — query j of slot b attends key
    positions < lengths[b] + j. int8 pools dequant on the gathered view
    with the per-(page, head) scales — the same math the fused kernel
    epilogue applies in VMEM."""
    B, Sq, H, D = q.shape
    P, S = page_table.shape[1], k_pages.shape[2]
    Hkv = k_pages.shape[3] // D
    g = jnp.take(k_pages[layer], page_table,
                 axis=0).reshape(B, P, S, Hkv, D)
    gv = jnp.take(v_pages[layer], page_table,
                  axis=0).reshape(B, P, S, Hkv, D)
    if k_scale is not None:
        ks = jnp.take(k_scale[layer], page_table, axis=0)  # (B, P, H)
        vs = jnp.take(v_scale[layer], page_table, axis=0)
        g = g.astype(jnp.float32) * ks[:, :, None, :, None]
        gv = gv.astype(jnp.float32) * vs[:, :, None, :, None]
    if Hkv != H:        # grouped: KV head h serves query heads h*G ..
        g, gv = (jnp.repeat(a, H // Hkv, axis=3) for a in (g, gv))
    k = g.reshape(B, P * S, H, D)
    v = gv.reshape(B, P * S, H, D)
    s = jnp.einsum("bjhd,bthd->bjht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    limit = lengths[:, None] + jnp.arange(Sq)[None, :]     # (B, Sq)
    mask = (jnp.arange(P * S)[None, None, :]
            < limit[:, :, None])[:, :, None, :]            # (B, Sq, 1, T)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.sum(e, axis=-1, keepdims=True)
    w = e / jnp.maximum(l, 1e-30)
    return jnp.einsum("bjht,bthd->bjhd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def _ragged_span_reference(q, k_pages, v_pages, page_table, lengths,
                           q_counts, scale, k_scale=None, v_scale=None,
                           layer=0):
    """Dense XLA fallback/oracle for the span kernel: the multi-query
    causal-offset math, with query rows >= q_counts[b] dead — they emit
    exact zeros (the row-mask contract the unified dispatch relies on:
    garbage rows of a mixed batch can never leak into live output)."""
    out = _ragged_mq_reference(q, k_pages, v_pages, page_table, lengths,
                               scale, k_scale=k_scale, v_scale=v_scale,
                               layer=layer)
    rows = jnp.arange(q.shape[1])[None, :] < q_counts[:, None]  # (B, Sq)
    return jnp.where(rows[:, :, None, None], out,
                     jnp.zeros_like(out))


def ragged_span_attention(q, k_pages, v_pages, page_table, lengths,
                          q_counts=None, scale=None, impl="auto",
                          interpret=False, k_scale=None, v_scale=None,
                          layer=0, num_kv_heads=None):
    """Span ragged paged-attention: ONE fixed-shape program for mixed
    prefill-chunk / decode / speculative-verify / idle work.

    q:              (B, Sq, H, D) — up to Sq query tokens per slot,
                    already written to the cache at positions
                    lengths-1 .. lengths+q_counts-2.
    k_pages/v_pages:(L, num_pages, S, H_kv*D) — the WHOLE page pools as
                    PagedKVCache stores them (heads packed, column
                    h*D + d); `layer` (a static int) picks the layer,
                    inside the kernel's page BlockSpec.
    num_kv_heads:   H_kv, where fewer KV heads than the H query heads
                    (None: as many). KV head h serves the G = H / H_kv
                    query heads h*G .. h*G+G-1; their rows are STACKED
                    along the kernel's row axis against that head's page
                    columns (row g*Sq + j of column block h is query head
                    h*G + g at position j), so one (G*Sq, D) x (D, KB*S)
                    product a head a block of pages does the work of G.
                    Grid, page index maps and DMAs do not change.
    page_table:     (B, P) int32 — physical pages per slot.
    lengths:        (B,) int32 — live tokens through query 0 (its own
                    position included); query j attends key positions
                    < lengths[b] + j (the per-position causal offset).
    q_counts:       (B,) int32 — live query rows per slot (decode=1,
                    verify=S, prefill chunk=C, idle=0); rows past the
                    count emit exact zeros. None means every row is
                    live (the multi-query/verify case).
    k_scale/v_scale:(L, num_pages, H) f32 — per-(page, head) dequant
                    scales of int8 page pools, the whole leaves like the
                    pools; both set or both None. The
                    Pallas path fuses the dequant into the page DMA
                    epilogue; the XLA path dequants the gathered view.
    impl: 'auto' (kernel on TPU; dense XLA elsewhere, or on TPU with a
    warning when ragged_supported says no), 'pallas' (force the kernel;
    interpret=True runs it on CPU), 'xla'.
    Returns (B, Sq, H, D) in q's dtype.
    """
    B, Sq, Hq, D = q.shape
    S = k_pages.shape[2]
    P = page_table.shape[1]
    s = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    quant = k_scale is not None
    H = Hq if num_kv_heads is None else int(num_kv_heads)
    G = Hq // H
    if Hq % H or k_pages.shape[3] != H * D:
        raise ValueError(
            f"{Hq} query heads over {H} KV heads of {D} do not match "
            f"{k_pages.dtype} pages {k_pages.shape[3]} wide")
    if q_counts is None:
        q_counts = jnp.full((B,), Sq, jnp.int32)
    impl = _resolve_ragged_impl(impl, interpret, q, k_pages)
    note_path("ragged_span_attention", impl)
    if impl == "xla":
        return _ragged_span_reference(q, k_pages, v_pages, page_table,
                                      lengths, q_counts, s,
                                      k_scale=k_scale, v_scale=v_scale,
                                      layer=layer)
    if impl != "pallas":
        raise ValueError(f"unknown ragged attention impl {impl!r}")
    Sr = G * Sq
    KB = _span_block_pages(S, Sr, P)
    note_tile("ragged_span_attention", pages=KB, keys=KB * S, rows=Sr)
    qp = q.reshape(B, Sq, H * D) if G == 1 else \
        q.reshape(B, Sq, H, G, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sr, H * D)
    lengths = lengths.astype(jnp.int32)
    q_counts = q_counts.astype(jnp.int32)
    pages = _span_block_table(page_table.astype(jnp.int32), lengths,
                              q_counts, S, KB)

    # the index_maps take the grid position and then every scalar-prefetch
    # operand: the block table, the lengths, the counts and, over int8
    # pages, the two scale leaves
    def page_index(i):
        return lambda b, p, pages, *_: (layer, pages[b, p * KB + i], 0, 0)

    def q_index(b, p, *_prefetched):
        return (b, 0, 0)

    # the layer axis is squeezed: the kernel sees KB pages (1, S, H*D) of
    # each pool, every one an operand of its own over the SAME pool
    page_specs = [pl.BlockSpec((None, 1, S, H * D), page_index(i))
                  for i in range(KB)]
    scales = (k_scale[layer].astype(jnp.float32),
              v_scale[layer].astype(jnp.float32)) if quant else ()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(scales),
        grid=(B, pl.cdiv(P, KB)),
        in_specs=[pl.BlockSpec((1, Sr, H * D), q_index)] + 2 * page_specs,
        out_specs=pl.BlockSpec((1, Sr, H * D), q_index),
        scratch_shapes=[
            pltpu.VMEM((H, Sr, 128), jnp.float32),   # running max
            pltpu.VMEM((H, Sr, 128), jnp.float32),   # running denominator
            pltpu.VMEM((H, Sr, D), jnp.float32),     # running numerator
        ],
    )
    kernel = functools.partial(_ragged_span_kernel, scale=s, S=S, Sq=Sr,
                               H=H, D=D, KB=KB, group=G, quant=quant)
    operands = (pages, lengths, q_counts, *scales, qp,
                *([k_pages] * KB), *([v_pages] * KB))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sr, H * D), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, dimension_semantics=("parallel", "arbitrary")),
    )(*operands)
    if G == 1:
        return out.reshape(B, Sq, H, D)
    return out.reshape(B, G, Sq, H, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B, Sq, Hq, D)


def _ragged_reference(q, k_pages, v_pages, page_table, lengths, scale):
    """Dense oracle for ragged_decode_attention: the Sq=1 row of the
    multi-query reference."""
    return _ragged_mq_reference(q[:, None], k_pages, v_pages, page_table,
                                lengths, scale)[:, 0]


def ragged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                            scale=None, impl="auto", interpret=False,
                            layer=0):
    """Ragged paged-attention for one decode step: the Sq=1 call of
    ragged_span_attention with every row live.

    q: (B, H, D) — the current token's query per slot; lengths: (B,)
    LIVE tokens per slot, including the token just written (a slot with
    length 0 yields 0s). Returns (B, H, D) in q's dtype."""
    return ragged_span_attention(q[:, None], k_pages, v_pages, page_table,
                                 lengths, q_counts=None, scale=scale,
                                 impl=impl, interpret=interpret,
                                 layer=layer)[:, 0]


def ragged_mq_decode_attention(q, k_pages, v_pages, page_table, lengths,
                               scale=None, impl="auto", interpret=False,
                               layer=0):
    """Multi-query ragged paged-attention (every query row live): the
    q_counts=None span kernel. Kept as the verify-path entry point; see
    ragged_span_attention for the full contract."""
    return ragged_span_attention(q, k_pages, v_pages, page_table,
                                 lengths, q_counts=None, scale=scale,
                                 impl=impl, interpret=interpret,
                                 layer=layer)


def supported(q, k, mask, layout="BHTD"):
    """Can the fused kernel take this call? (shape/dtype/mask gate —
    dropout works on every supported shape, so it is not a criterion)"""
    t_ax = -2 if layout == "BHTD" else -3
    Tq, Tk = q.shape[t_ax], k.shape[t_ax]
    if layout == "BTHD" and q.shape[-1] % 64:
        # the packed kernel slices heads as static lane blocks at
        # multiples of D; Mosaic handles 64-aligned offsets, smaller
        # head dims fall back to the relayout path
        return False
    if Tk > MAX_FUSED_T or Tq > MAX_FUSED_T:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if mask is not None and not _is_key_padding(mask, q.shape, Tk):
        return False
    return True


def _is_key_padding(mask, qshape, tk):
    """True for masks broadcastable as (B, 1, 1, Tk) or (B, Tk)."""
    if mask.ndim == 2:
        return mask.shape[-1] == tk
    if mask.ndim == 4:
        return (mask.shape[1] == 1 and mask.shape[2] == 1
                and mask.shape[-1] == tk)
    return False


def fused_attention(q, k, v, mask=None, scale=None, causal=False,
                    dropout_p=0.0, key=None, interpret=False,
                    layout="BHTD"):
    """Fused softmax(QKᵀ·s + bias)→dropout→·V. layout "BHTD" takes
    (B, H, T, D) tensors; "BTHD" takes (B, T, H, D) straight from the
    head-split reshape — no relayout copies on either side.

    mask: optional key-padding mask, (B, Tk) or (B, 1, 1, Tk), True=attend.
    key: JAX PRNG key for the dropout mask (required when dropout_p > 0).
    """
    if layout == "BHTD":
        B, H, Tq, D = q.shape
        Tk = k.shape[2]
    else:
        B, Tq, H, D = q.shape
        Tk = k.shape[1]
    d = q.shape[-1]
    s = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    if mask is None:
        bias = jnp.zeros((B, Tk), jnp.float32)
    else:
        m2 = mask.reshape(mask.shape[0], mask.shape[-1])
        bias = jnp.where(m2, 0.0, NEG_INF).astype(jnp.float32)
        if bias.shape[0] == 1 and B > 1:
            bias = jnp.broadcast_to(bias, (B, Tk))
    if dropout_p > 0.0:
        if key is None:
            raise ValueError("dropout_p > 0 requires a PRNG key")
        kd = jax.random.key_data(key).reshape(-1)
        kd32 = lax.bitcast_convert_type(kd, jnp.int32).reshape(-1)
        if kd32.size >= 2:
            seed = kd32[-2:]
        else:  # single-word keys (e.g. rbg) zero-pad the first seed word
            seed = jnp.concatenate([jnp.zeros((1,), jnp.int32), kd32])
    else:
        seed = jnp.zeros((2,), jnp.int32)
    if layout == "BHTD":
        return _fused(q, k, v, bias, seed, s, float(dropout_p),
                      bool(causal), bool(interpret))
    # BTHD: the head dim merges back into the projection width (a free
    # minor-dim reshape) and the packed kernel slices heads statically
    qp = q.reshape(B, Tq, H * D)
    kp = k.reshape(B, Tk, H * D)
    vp = v.reshape(B, Tk, H * D)
    out = _fused_packed(qp, kp, vp, bias, seed, s, float(dropout_p),
                        bool(causal), H, bool(interpret))
    return out.reshape(B, Tq, H, D)
