"""Memory-efficient attention kernels: blockwise (flash) and ring.

Reference parity: the reference's fastest attention is the fused
`interleaved_matmul_selfatt_qk/valatt` strided-batch GEMM pair
(src/operator/contrib/transformer.cu) — still O(T²) memory. This module
provides the TPU-native upgrades (SURVEY.md §5.7):

  * flash_attention_data — blockwise online-softmax attention, O(T) memory,
    implemented as a lax.scan over KV blocks so XLA fuses each block's
    QK^T·softmax·V into MXU work without materializing the (T,T) matrix.
    On TPU, unmasked self-attention whose length the kernel tiles divide
    runs jax.experimental.pallas.ops.tpu.flash_attention (hand-tiled VMEM
    pipeline; see pallas_flash_eligible); every other call — and every
    CPU test — takes the scan path, which has identical semantics.
  * ring_attention_data — sequence-parallel attention: Q stays put, KV
    blocks rotate around the mesh's "sp" axis via lax.ppermute, combining
    partial softmax statistics exactly as flash does across local blocks.
    Used by parallel/sp when the sequence axis is sharded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def flash_eligible(q, k, v, mask, dropout_p):
    return dropout_p == 0.0 and q.dtype in (jnp.float32, jnp.bfloat16,
                                            jnp.float16)


# tile edge of jax's Pallas TPU flash kernel at its default BlockSizes
_PALLAS_FLASH_BLOCK = 128


def pallas_flash_eligible(q, k, mask):
    """Will flash_attention_data run jax's Pallas TPU flash kernel for
    this call? A decision from the platform and the shapes alone (unmasked
    self-attention whose length the kernel's tiles divide) — so a caller
    can tell which path produced a number, and a failure of the chosen
    kernel is an error, never a quiet switch to the scan."""
    return (jax.default_backend() == "tpu" and mask is None
            and q.shape[-2] == k.shape[-2]
            and q.shape[-2] % _PALLAS_FLASH_BLOCK == 0)


def flash_attention_data(q, k, v, mask=None, scale=None, causal=False,
                         block_k=512):
    """Blockwise attention over (B, H, Tq, D) x (B, H, Tk, D).

    mask: broadcastable to (B, H, Tq, Tk), True = attend."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if pallas_flash_eligible(q, k, mask):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        return flash_attention(q, k, v, causal=causal, sm_scale=s)
    B, H, Tq, D = q.shape
    Tk = k.shape[-2]
    block_k = min(block_k, Tk)
    n_blocks = (Tk + block_k - 1) // block_k
    pad = n_blocks * block_k - Tk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(B, H, n_blocks, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(B, H, n_blocks, block_k, D).transpose(2, 0, 1, 3, 4)
    if mask is not None:
        m = jnp.broadcast_to(mask, (B, H, Tq, Tk))
        if pad:
            m = jnp.pad(m, ((0, 0), (0, 0), (0, 0), (0, pad)))
        mb = m.reshape(B, H, Tq, n_blocks, block_k).transpose(3, 0, 1, 2, 4)
    else:
        mb = None
    q32 = q.astype(jnp.float32)
    kv_pos0 = jnp.arange(n_blocks) * block_k
    q_pos = jnp.arange(Tq)

    def step(carry, xs):
        acc, row_max, row_sum = carry
        if mb is None:
            k_blk, v_blk, pos0 = xs
            blk_mask = None
        else:
            k_blk, v_blk, pos0, blk_mask = xs
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            k_blk.astype(jnp.float32)) * s
        if pad:
            kpos = pos0 + jnp.arange(block_k)
            logits = jnp.where(kpos[None, None, None, :] < Tk, logits,
                               NEG_INF)
        if causal:
            # same convention as the baseline's tril(..., Tk - Tq): query i
            # attends keys j <= i + (Tk - Tq) (decode-style aligned ends)
            kpos = pos0 + jnp.arange(block_k)
            cm = (q_pos[None, None, :, None] + (Tk - Tq)) >= \
                kpos[None, None, None, :]
            logits = jnp.where(cm, logits, NEG_INF)
        if blk_mask is not None:
            logits = jnp.where(blk_mask, logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        # Rows with no valid key yet have new_max == NEG_INF, which would
        # make exp(NEG_INF - NEG_INF) = 1 for every key; such rows must
        # contribute zero so fully-masked queries yield zeros, not mean(V).
        dead = new_max <= NEG_INF / 2
        p = jnp.where(dead[..., None], 0.0,
                      jnp.exp(logits - new_max[..., None]))
        row_sum = row_sum * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return (acc, new_max, row_sum), None

    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    max0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    sum0 = jnp.zeros((B, H, Tq), jnp.float32)
    xs = (kb, vb, kv_pos0) if mb is None else (kb, vb, kv_pos0, mb)
    (acc, _, row_sum), _ = lax.scan(step, (acc0, max0, sum0), xs)
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention_data(q, k, v, axis_name, causal=False, scale=None,
                        mask=None):
    """Ring attention over a sharded sequence axis (inside shard_map).

    Each device holds local Q/K/V blocks of shape (B, H, T_local, D); KV
    rotates around the ring via ppermute, online-softmax combining per hop
    (Liu et al.; SURVEY.md §5.7). causal masking uses global positions, so
    callers must shard the sequence contiguously (block i = positions
    [i*T_local, (i+1)*T_local)).

    mask: optional LOCAL key-padding block of shape (B, T_local), True =
    attend — the caller's (B, Tk) global mask sharded along Tk; it rotates
    around the ring alongside its KV block."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    q32 = q.astype(jnp.float32)
    q_pos = idx * T + jnp.arange(T)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, hop_i):
        acc, row_max, row_sum, k_cur, v_cur, m_cur = carry
        src_idx = (idx - hop_i) % n  # whose block we currently hold
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            k_cur.astype(jnp.float32)) * s
        if causal:
            kpos = src_idx * T + jnp.arange(T)
            cm = q_pos[None, None, :, None] >= kpos[None, None, None, :]
            logits = jnp.where(cm, logits, NEG_INF)
        if m_cur is not None:
            logits = jnp.where(m_cur[:, None, None, :], logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        corr = jnp.exp(row_max - new_max)
        # see flash_attention_data: fully-masked-so-far rows must emit 0
        dead = new_max <= NEG_INF / 2
        p = jnp.where(dead[..., None], 0.0,
                      jnp.exp(logits - new_max[..., None]))
        row_sum = row_sum * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        m_nxt = (lax.ppermute(m_cur, axis_name, perm)
                 if m_cur is not None else None)
        return (acc, new_max, row_sum, k_nxt, v_nxt, m_nxt), None

    acc0 = jnp.zeros((B, H, T, D), jnp.float32)
    max0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    sum0 = jnp.zeros((B, H, T), jnp.float32)
    (acc, _, row_sum, _, _, _), _ = lax.scan(
        hop, (acc0, max0, sum0, k, v, mask), jnp.arange(n))
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.astype(q.dtype)
