"""Fused Pallas attention kernel: interpret-mode correctness on CPU. The
hardware-PRNG dropout contract (same key -> same mask) needs the chip and
is checked by chip_smoke.py's kernels phase.

Parity target: dot_product_attention semantics (ops/nn.py) — the fused
kernel must be a drop-in for the XLA path including key-padding masks,
causal masking, and fully-masked-row zeros.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.nn import dot_product_attention as dpa


def _qkv(B=2, H=3, Tq=64, Tk=64, D=16, dtype=jnp.float32, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda t: jnp.asarray(r.standard_normal((B, H, t, D)), dtype)  # noqa: E731
    return mk(Tq), mk(Tk), mk(Tk)


def test_fused_matches_xla_interpret():
    q, k, v = _qkv()
    mask = jnp.asarray(np.random.default_rng(1).random((2, 1, 1, 64)) > 0.2)
    out = pa.fused_attention(q, k, v, mask=mask, interpret=True)
    ref = dpa.raw_fn(q, k, v, mask=mask, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_causal_and_cross_interpret():
    q, k, v = _qkv(Tq=32, Tk=64)
    out = pa.fused_attention(q, k, v, causal=True, interpret=True)
    ref = dpa.raw_fn(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_fully_masked_rows_zero_interpret():
    q, k, v = _qkv(B=1, H=1)
    mask = np.ones((1, 1, 1, 64), bool)
    mask[..., :] = False  # every key masked for every query
    out = pa.fused_attention(q, k, v, mask=jnp.asarray(mask),
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_fused_grads_match_xla_interpret():
    q, k, v = _qkv()
    mask = jnp.asarray(np.random.default_rng(2).random((2, 1, 1, 64)) > 0.2)
    g1 = jax.grad(lambda *a: pa.fused_attention(*a, mask=mask,
                                                interpret=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: dpa.raw_fn(*a, mask=mask, impl="xla").sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_supported_gating():
    q, k, v = _qkv()
    assert pa.supported(q, k, None)
    assert pa.supported(q, k, jnp.ones((2, 1, 1, 64), bool))
    # general (B,H,Tq,Tk) masks are not key-padding → not supported
    assert not pa.supported(q, k, jnp.ones((2, 3, 64, 64), bool))
    ql, kl, _ = _qkv(Tq=2048, Tk=2048, D=8)
    assert not pa.supported(ql, kl, None)  # too long for whole-row
    qi = q.astype(jnp.int32)
    assert not pa.supported(qi, k, None)


def test_2d_mask_canonicalized_on_every_path():
    # a (B, Tk) mask must work on the XLA fallback too (review regression)
    q, k, v = _qkv()
    m2 = jnp.asarray(np.random.default_rng(3).random((2, 64)) > 0.3)
    out2 = dpa.raw_fn(q, k, v, mask=m2, impl="xla")
    out4 = dpa.raw_fn(q, k, v, mask=m2[:, None, None, :], impl="xla")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out4))


def _software_keep_mask(key, B, H, Tq, Tk, p_drop):
    """Materialize the exact mask the interpret-mode kernel draws, by
    replaying its software PRNG per (batch, head) grid cell."""
    kd = jax.random.key_data(key).reshape(-1).astype(np.uint32)
    s0 = np.int32(kd[-2]) if kd.size >= 2 else np.int32(0)
    s1 = np.int32(kd[-1])
    thresh = jnp.uint32(min(int(p_drop * 2.0 ** 32), 2 ** 32 - 1))
    rows = []
    for b in range(B):
        row = []
        for h in range(H):
            cell = b * H + h
            bits = pa._software_bits(
                jnp.uint32(np.uint32(s0)),
                jnp.uint32(np.uint32(s1 ^ np.int32(cell))),
                (Tq, Tk))
            row.append(bits >= thresh)
        rows.append(jnp.stack(row))
    return jnp.stack(rows)  # (B, H, Tq, Tk) keep mask


def _masked_dropout_attention(q, k, v, keep, p_drop):
    """XLA reference: softmax attention with an explicitly materialized
    dropout mask (the oracle for the kernel's regenerate-in-bwd trick)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    w = jnp.where(keep, w / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(q.dtype), v)


def test_fused_dropout_interpret_determinism():
    q, k, v = _qkv(B=2, H=2)
    key = jax.random.PRNGKey(42)
    o1 = pa.fused_attention(q, k, v, dropout_p=0.3, key=key, interpret=True)
    o2 = pa.fused_attention(q, k, v, dropout_p=0.3, key=key, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = pa.fused_attention(q, k, v, dropout_p=0.3,
                            key=jax.random.PRNGKey(7), interpret=True)
    assert bool(jnp.any(o1 != o3))
    # dropout actually dropped something
    plain = pa.fused_attention(q, k, v, interpret=True)
    assert bool(jnp.any(o1 != plain))


def test_fused_dropout_uses_both_key_words():
    # keys sharing the final 32-bit word must NOT share a mask (advisor
    # finding: the old seed kept only kd[-1:])
    q, k, v = _qkv(B=1, H=1)
    mk = lambda w0, w1: jax.random.wrap_key_data(  # noqa: E731
        jnp.asarray([w0, w1], jnp.uint32))
    o1 = pa.fused_attention(q, k, v, dropout_p=0.3, key=mk(1, 5),
                            interpret=True)
    o2 = pa.fused_attention(q, k, v, dropout_p=0.3, key=mk(2, 5),
                            interpret=True)
    assert bool(jnp.any(o1 != o2))


def test_fused_dropout_forward_matches_materialized_mask():
    q, k, v = _qkv(B=2, H=2)
    key = jax.random.PRNGKey(3)
    p_drop = 0.25
    keep = _software_keep_mask(key, 2, 2, 64, 64, p_drop)
    out = pa.fused_attention(q, k, v, dropout_p=p_drop, key=key,
                             interpret=True)
    ref = _masked_dropout_attention(q, k, v, keep, p_drop)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_dropout_grads_match_materialized_mask():
    # the load-bearing property: bwd regenerates the SAME mask as fwd, so
    # gradients must equal those of the mask-materialized XLA reference
    q, k, v = _qkv(B=2, H=2)
    key = jax.random.PRNGKey(11)
    p_drop = 0.25
    keep = _software_keep_mask(key, 2, 2, 64, 64, p_drop)
    g1 = jax.grad(lambda *a: pa.fused_attention(
        *a, dropout_p=p_drop, key=key, interpret=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _masked_dropout_attention(
        *a, keep, p_drop).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_fused_dropout_interpret_unbiased():
    q, k, v = _qkv(B=1, H=2)
    outs = jnp.stack([pa.fused_attention(q, k, v, dropout_p=0.3,
                                         key=jax.random.PRNGKey(i),
                                         interpret=True)
                      for i in range(24)])
    plain = pa.fused_attention(q, k, v, interpret=True)
    rel = float(jnp.abs(outs.mean(0) - plain).mean()
                / jnp.abs(plain).mean())
    assert rel < 0.25, rel


# ---------------------------------------------------------------------------
# packed (BTHD) kernel — the default training path of MultiHeadAttention /
# GPT2Attention (layout="BTHD" head splits with no relayout transposes)
# ---------------------------------------------------------------------------

def _to_bthd(x):
    return jnp.swapaxes(x, 1, 2)


def test_packed_matches_bhtd_interpret():
    q, k, v = _qkv(B=2, H=4, Tq=64, Tk=64, D=64)
    mask = jnp.asarray(np.random.default_rng(1).random((2, 64)) > 0.2)
    ref = pa.fused_attention(q, k, v, mask=mask, interpret=True)
    out = pa.fused_attention(_to_bthd(q), _to_bthd(k), _to_bthd(v),
                             mask=mask, interpret=True, layout="BTHD")
    np.testing.assert_array_equal(np.asarray(_to_bthd(out)),
                                  np.asarray(ref))


def test_packed_grads_match_bhtd_interpret():
    q, k, v = _qkv(B=2, H=4, Tq=64, Tk=64, D=64)

    def loss_bhtd(q, k, v):
        return pa.fused_attention(q, k, v, causal=True,
                                  interpret=True).sum()

    def loss_bthd(q2, k2, v2):
        return pa.fused_attention(q2, k2, v2, causal=True, interpret=True,
                                  layout="BTHD").sum()

    g1 = jax.grad(loss_bhtd, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_bthd, argnums=(0, 1, 2))(
        _to_bthd(q), _to_bthd(k), _to_bthd(v))
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(_to_bthd(b)))


def test_packed_dropout_same_masks_as_bhtd_interpret():
    """Seeds are b*H + h in both kernels — masks must be bit-identical."""
    q, k, v = _qkv(B=2, H=4, Tq=64, Tk=64, D=64)
    key = jax.random.PRNGKey(5)
    d1 = pa.fused_attention(q, k, v, dropout_p=0.3, key=key,
                            interpret=True)
    d2 = pa.fused_attention(_to_bthd(q), _to_bthd(k), _to_bthd(v),
                            dropout_p=0.3, key=key, interpret=True,
                            layout="BTHD")
    np.testing.assert_array_equal(np.asarray(_to_bthd(d2)),
                                  np.asarray(d1))


def test_bthd_xla_branch_matches_canonical():
    """dot_product_attention(layout='BTHD', impl='xla') == canonical."""
    q, k, v = _qkv(B=2, H=3, Tq=32, Tk=48, D=16)
    mask = jnp.asarray(np.random.default_rng(2).random((2, 1, 1, 48)) > 0.3)
    ref = dpa.raw_fn(q, k, v, mask=mask, causal=True, impl="xla")
    out = dpa.raw_fn(_to_bthd(q), _to_bthd(k), _to_bthd(v), mask=mask,
                     causal=True, impl="xla", layout="BTHD")
    np.testing.assert_allclose(np.asarray(_to_bthd(out)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # fully-masked row yields zeros on the BTHD branch too
    mask0 = jnp.zeros((2, 1, 1, 48), bool)
    out0 = dpa.raw_fn(_to_bthd(q), _to_bthd(k), _to_bthd(v), mask=mask0,
                      impl="xla", layout="BTHD")
    assert float(jnp.abs(out0).max()) == 0.0


def test_bthd_fallback_path_matches_canonical():
    """Unsupported-impl BTHD calls transpose internally and re-enter."""
    q, k, v = _qkv(B=2, H=3, Tq=64, Tk=64, D=16)
    ref = dpa.raw_fn(q, k, v, causal=True, impl="flash")
    out = dpa.raw_fn(_to_bthd(q), _to_bthd(k), _to_bthd(v), causal=True,
                     impl="flash", layout="BTHD")
    np.testing.assert_allclose(np.asarray(_to_bthd(out)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_packed_unsupported_head_dim_gated():
    """D not a multiple of 64: supported() must route away from the
    packed kernel (Mosaic lane-slice alignment)."""
    q, k, v = _qkv(B=2, H=3, Tq=64, Tk=64, D=32)
    assert not pa.supported(_to_bthd(q), _to_bthd(k), None, layout="BTHD")
    assert pa.supported(q, k, None)  # BHTD path unaffected
