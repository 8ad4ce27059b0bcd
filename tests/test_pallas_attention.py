"""Fused Pallas attention kernel: interpret-mode correctness on CPU. The
hardware-PRNG dropout contract (same key -> same mask) needs the chip and
is checked by chip_smoke.py's kernels phase.

Parity target: dot_product_attention semantics (ops/nn.py) — the fused
kernel must be a drop-in for the XLA path including key-padding masks,
causal masking, and fully-masked-row zeros.
"""
import hashlib

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


# ---------------------------------------------------------------------------
# The span kernel's step builds its mask from a row vector of query
# positions and a column vector of key positions, and dead rows are zeroed
# when the slot emits. Eight query heads a KV head of 128 (64 stacked
# rows), pages of 8, seven pages a slot: at the block the rule picks (4
# pages) and at a page a step (7 steps a slot), so that slots hold blocks
# every live row sees whole, blocks at the causal edge or a window's start,
# and both.
# ---------------------------------------------------------------------------

SPAN_LENGTHS = [1, 45, 30, 20, 41, 49]     # query 0's position + 1
# a first chunk, a decode, a short chunk, an idle slot, two full chunks
SPAN_COUNTS = [8, 1, 3, 0, 8, 8]


def _span_case(pages="float32", seed=0, Hq=16, Hkv=2, D=128, P=7, S=8,
               Sq=8):
    """(q, k pool, v pool, table, the scales kwargs) for len(SPAN_LENGTHS)
    slots, two layers a pool, the table a permutation."""
    rng = np.random.default_rng(seed)
    B = len(SPAN_LENGTHS)
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    kv = [rng.standard_normal((2, B * P, S, Hkv * D)) for _ in range(2)]
    table = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    if pages == "float32":
        return q, *(jnp.asarray(x, jnp.float32) for x in kv), table, {}
    codes, scales = [], []
    for x in kv:                    # a scale a (page, head), as stored
        x = x.reshape(2, B * P, S, Hkv, D)
        sc = np.abs(x).max(axis=(2, 4)) / 127.0
        codes.append(jnp.asarray(np.round(x / sc[:, :, None, :, None])
                                 .reshape(2, B * P, S, Hkv * D), jnp.int8))
        scales.append(jnp.asarray(sc, jnp.float32))
    return q, *codes, table, dict(k_scale=scales[0], v_scale=scales[1])


def _span_call(q, kp, vp, table, kw, window=None, **extra):
    return np.asarray(pa.ragged_span_attention(
        q, kp, vp, table, jnp.asarray(SPAN_LENGTHS, jnp.int32),
        q_counts=jnp.asarray(SPAN_COUNTS, jnp.int32), impl="pallas",
        interpret=True, layer=1, num_kv_heads=kp.shape[-1] // q.shape[-1],
        window=window, **kw, **extra))


def _dense_span(q, kp, vp, table, kw, window=None):
    """Query j of slot b (position L - 1 + j) over the keys at positions
    max(0, L + j - window) .. L - 1 + j, each read from its page, by a
    plain float64 softmax; dead rows zero."""
    B, Sq, Hq, D = q.shape
    S, Hkv = kp.shape[2], kp.shape[3] // D
    k, v = (np.asarray(x[1], np.float64).reshape(-1, S, Hkv, D)
            for x in (kp, vp))
    if kw:
        k = k * np.asarray(kw["k_scale"][1])[:, None, :, None]
        v = v * np.asarray(kw["v_scale"][1])[:, None, :, None]
    t = np.asarray(table)
    out = np.zeros(q.shape)
    for b, (L, n) in enumerate(zip(SPAN_LENGTHS, SPAN_COUNTS)):
        for j in range(n):
            lo = 0 if window is None else max(0, L + j - window)
            pos = np.arange(lo, L + j)
            K, V = (x[t[b, pos // S], pos % S] for x in (k, v))
            for h in range(Hq):
                s = K[:, h // (Hq // Hkv)] @ np.asarray(q[b, j, h]) \
                    / np.sqrt(D)
                w = np.exp(s - s.max())
                out[b, j, h] = (w / w.sum()) @ V[:, h // (Hq // Hkv)]
    return out


def _dead():
    return np.arange(8)[None, :] >= np.asarray(SPAN_COUNTS)[:, None]


@pytest.mark.parametrize("kb", [None, 1])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("pages", ["float32", "int8"])
def test_span_kernel_grouped_and_window_calls(monkeypatch, pages, window,
                                              kb):
    """Grouped (8 query heads a KV head of 128) and window calls over
    slots whose blocks are seen whole, cut and both, a decode slot, a
    short chunk and an idle one, float and int8 pages: the kernel against
    its XLA form and a dense float64 softmax, dead rows exact zeros."""
    if kb is not None:
        monkeypatch.setattr(pa, "_span_block_pages", lambda S, Sr, P: kb)
    q, kp, vp, table, kw = _span_case(pages)
    out = _span_call(q, kp, vp, table, kw, window)
    ref = pa._ragged_span_reference(
        q, kp, vp, table, jnp.asarray(SPAN_LENGTHS, jnp.int32),
        jnp.asarray(SPAN_COUNTS, jnp.int32), 1 / np.sqrt(128), layer=1,
        window=window, **kw)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, _dense_span(q, kp, vp, table, kw,
                                                window), atol=5e-5)
    dead = _dead()
    assert (out[dead] == 0).all() and not np.signbit(out[dead]).any()
    assert (np.abs(out[~dead]).max(axis=-1) > 0).all()


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_span_kernel_dead_rows_are_zeros_whatever_their_queries(bad,
                                                                window):
    """Dead rows accumulate whatever they meet and are zeroed when the
    slot emits: a non-finite query in every dead row (the idle slot's
    too) leaves them exact zeros and the live rows the same bits."""
    q, kp, vp, table, kw = _span_case()
    clean = _span_call(q, kp, vp, table, kw, window)
    dirty = _span_call(jnp.where(jnp.asarray(_dead())[:, :, None, None],
                                 bad, q), kp, vp, table, kw, window)
    dead = _dead()
    assert (dirty[dead] == 0).all() and not np.signbit(dirty[dead]).any()
    assert (dirty.view(np.uint32) == clean.view(np.uint32)).all()


@pytest.mark.parametrize("kb", [None, 1])
def test_span_kernel_a_window_as_wide_as_every_position_changes_nothing(
        monkeypatch, kb):
    """A window of 64 keys over 56 positions: the same blocks, a mask
    that drops nothing more; the same bits."""
    if kb is not None:
        monkeypatch.setattr(pa, "_span_block_pages", lambda S, Sr, P: kb)
    q, kp, vp, table, kw = _span_case()
    plain = _span_call(q, kp, vp, table, kw)
    wide = _span_call(q, kp, vp, table, kw, window=64)
    assert (wide.view(np.uint32) == plain.view(np.uint32)).all()


# sha256 of the whole output of _span_call on _span_case(pages, seed=5) at
# the kernel's own scale, as the kernel that built a whole (rows, keys) tile
# of positions a step computed it (the commit before the mask was built
# from vectors): the live rows' numbers and the dead rows' zeros did not
# move by a bit. Keyed by (pages, window, pages a step; None: the rule's)
PARENT_BITS = {
    ("float32", None, None):
        "ff988d9bae381711d5e73ea0b419c16632341755093eeeaaa6e9b1c3738d50a3",
    ("float32", None, 1):
        "0862a64aeb7311be33dcf91196c819645ce57603fa072ac066c10d830fe16ee0",
    ("float32", 24, None):
        "33ca53d7ace7a4e21a3b2594f053c5c2f658c7d7c35ccbd12c80c099a7c94885",
    ("float32", 24, 1):
        "ad48604cffa4a04ddc93e0e03c2e654d6bcbb8ab32f971aa5293f8ad77c185dd",
    ("int8", None, None):
        "9997bf891240ad7af6db4e60ba4f95760f9dbdd914637535cb44ef8eda368c40",
    ("int8", None, 1):
        "9fd859e2fed248ae376d0732abdf26ad296c117bd24958701ed686b3e5ba60bb",
    ("int8", 24, None):
        "1bcb478c56480c333b6511f14b1cb5f69f5fcadf1ba2bec5db62e070cb98d189",
    ("int8", 24, 1):
        "e6bd3a7bb9d638589865dee07e3036e4a7d29f1d1eab64fc92461e8c4c4945e9",
}


@pytest.mark.parametrize("case", sorted(PARENT_BITS, key=str),
                         ids=lambda c: "-".join(map(str, c)))
def test_span_kernel_output_is_the_tile_masked_kernels_bits(monkeypatch,
                                                            case):
    """The vector mask, the row statistics kept in their lanes and the
    dead rows zeroed at the emit give every row the bits of the kernel
    that masked a whole tile of positions: a select that keeps what the
    tile's mask kept, the same exp, the same sums."""
    pages, window, kb = case
    if kb is not None:
        monkeypatch.setattr(pa, "_span_block_pages", lambda S, Sr, P: kb)
    out = _span_call(*_span_case(pages, seed=5), window)
    assert hashlib.sha256(out.tobytes()).hexdigest() == PARENT_BITS[case]
