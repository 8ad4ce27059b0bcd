"""Kimi Linear (models/kimi_linear.py) held to its plain float32 reference
(benchmarks/reference/kimi_linear.py: the delta rule token by token, latent
attention unabsorbed, a loop over experts with a mask, no cache) at a tiny
size on seeded weights: the full forward, the chunked and paged serving
path through ServingEngine logit by logit, the two kernels this
configuration brought under the Pallas interpreter (the chunk update at the
published initialisation's strongest decay, the latent span attention
across page boundaries), the gated expert kernel, the four shares of one
expert layer, every perturbation the cell's limit must catch, and what the
engine allocates and refuses for a page row with no head axis."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import kimi_linear as ref
from benchmarks.weights_per_parameter import seed_weights
from mxnet_tpu import models, parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.nn import MOE_COUNTERS
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.kda import kda_chunk_update
from mxnet_tpu.ops.moe import expert_ffn
from mxnet_tpu.serving import Request, ServingEngine

from test_falcon_h1 import _serve_and_capture

TINY = dict(vocab_size=512, units=128, pattern="KKLKL", dense_layers=1,
            dense_hidden_size=256, num_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kda_heads=4, kda_head_dim=16, kda_low_rank=16,
            conv_kernel=4, chunk_size=16, num_experts=16, top_k=4,
            held_experts=(4, 4), expert_hidden_size=64,
            shared_hidden_size=64, routed_scaling_factor=2.446,
            max_length=256, dtype="float32")


def _model(seed=3, std=0.02, **over):
    """(net, its config as the reference's kwargs, its parameters), the
    KDA layers' A, dt and convolutions drawn as the cell draws them."""
    cfg = models.kimi_linear_48b_config(**{**TINY, **over})
    net = models.KimiLinearForCausalLM(cfg)
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, seed, cfg.dtype, std=std)
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    return net, dict(vars(cfg)), params


def _ids(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 512, shape),
                       jnp.int32)


def _err(got, want):
    """Largest difference in units of the reference's own spread."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


# float32 against float32: rounding alone, in units of the spread
TIGHT = 2e-4


def test_full_forward_matches_the_reference():
    net, kw, params = _model()
    ids = _ids(0, 2, 37)        # not a multiple of the 16-row chunk
    want = ref.logits(params, kw, ids)
    assert _err(par.EvalStep(net)(ids)._data, want) < TIGHT
    # a layer's mixer and its feed-forward each count among their own kind
    assert [b.kind for b in net.blocks()] == list("KKLKL")
    assert [b.index for b in net.blocks()] == [0, 1, 0, 2, 1]
    assert [b.expert_index for b in net.blocks()] == [None, 0, 1, 2, 3]
    # the head over chosen positions alone is the same head
    at = jnp.asarray([[3, 36], [0, 20]])
    some = ref.logits(params, kw, ids, positions=at)
    np.testing.assert_allclose(
        np.asarray(some), np.asarray(jnp.take_along_axis(
            want, at[:, :, None], axis=1)), rtol=1e-6, atol=1e-6)


def test_the_published_config_is_the_27_layers():
    cfg = models.kimi_linear_48b_config()
    latent = [i + 1 for i, k in enumerate(cfg.pattern) if k == "L"]
    assert latent == [4, 8, 12, 16, 20, 24, 27] and cfg.num_layers == 27
    assert cfg.held_experts == (0, 256)
    with pytest.raises(MXNetError, match="K or L"):
        models.KimiLinearConfig(pattern="KM")


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_serving_engine_logits_match_the_reference(impl):
    """Ragged chunked prefill then decode through ServingEngine, logit by
    logit: five requests through two slots (a slot serves requests in
    succession and must not see the earlier one's state), one chunk a
    dispatch, prompt lengths that are no multiple of the chunk and cross
    page boundaries. The latent layers hold ONE pool."""
    net, kw, params = _model()
    rng = np.random.default_rng(5)
    requests = [Request(rng.integers(0, 512, n), 7, request_id=f"r{i}")
                for i, n in enumerate((37, 21, 2, 18, 33))]
    eng, rows = _serve_and_capture(
        net, requests, num_slots=2, max_length=64, page_size=16,
        chunk_tokens=16, prefill_chunk_budget=16, attn_impl=impl)
    for r in requests:
        seq = np.concatenate([r.prompt, r.output_tokens])
        want = ref.logits(params, kw, jnp.asarray(seq[None], jnp.int32))[0]
        got = rows[r.id]
        assert sorted(got) == list(range(len(seq) - 1)), r.id
        got = jnp.stack([got[i] for i in range(len(seq) - 1)])
        assert _err(got, want[:-1]) < TIGHT, r.id
        assert list(np.argmax(np.asarray(want[len(r.prompt) - 1:-1]), -1)) \
            == list(r.output_tokens), r.id
    st = eng.stats
    path = "xla" if impl == "xla" else "pallas"
    # (the stored row is a whole lane tile: the page write takes it)
    assert st["kernel_paths"] == {f"latent_span_attention/{path}": 2,
                                  f"kda_chunk_update/{path}": 3,
                                  f"expert_ffn/{path}": 4,
                                  f"kv_page_write/{path}": 2}
    assert st["kernel_tiles"] == ({} if impl == "xla" else {
        "latent_span_attention/pages=4,keys=64,rows=64,tile=64": 2,
        "kda_chunk_update/heads=4,rows=16,block=16,pass=4": 3,
        "expert_ffn/rows=128,hidden=64": 4})
    moe = [dict(zip(MOE_COUNTERS, row)) for row in
           st["model_counters"]["moe"]]
    assert len(moe) == 4
    for layer in moe:
        assert layer["dispatches"] == st["decode_dispatches"]
        assert layer["rows"] == 111 + 30
        assert 0 < layer["pairs"] <= 4 * layer["rows"]
    # a whole prompt chunk does not fit an eighth of the 2 x 16 grid: those
    # ticks took the full feed-forward, decode ticks and a prompt's short
    # last chunk the compact one, and every logit above matched
    dispatches, compact = st["model_counters"]["live_rows"]
    assert dispatches == st["decode_dispatches"]
    assert 0 < compact < dispatches


def _kda_rows(rng, Bt, W, H, D, strongest=True):
    """q, k, v, g, beta as the mixer hands them to the rule; with
    `strongest`, head 0 decays as the published initialisation's strongest
    draw does (A = 16, dt = 0.1: 1.7 a token, ~100 over 64 rows)."""
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((Bt, W, H, D))) * D ** -0.5
    k = unit(rng.standard_normal((Bt, W, H, D)))
    v = rng.standard_normal((Bt, W, H, D))
    g = -rng.uniform(1, 16, (1, 1, H, 1)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(0.1), (Bt, W, H, D)))
    if strongest:
        g[:, :, 0, :D // 2] = -1.7
    beta = rng.uniform(0.05, 0.95, (Bt, W, H))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


def _token_rule(q, k, v, g, beta, s0, counts):
    """The recurrence one token at a time, in float64. s0 (Bt, H, K, V)."""
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    S, o = np.asarray(s0, np.float64).copy(), np.zeros(v.shape)
    for b in range(q.shape[0]):
        for t in range(int(counts[b])):
            S[b] = np.exp(g[b, t])[:, :, None] * S[b]
            seen = np.einsum("hkv,hk->hv", S[b], k[b, t])
            S[b] += beta[b, t][:, None, None] * k[b, t][:, :, None] \
                * (v[b, t] - seen)[:, None, :]
            o[b, t] = np.einsum("hkv,hk->hv", S[b], q[b, t])
    return o, S


@pytest.mark.parametrize("impl, interpret", [("xla", False),
                                             ("pallas", True)])
@pytest.mark.parametrize("H", [1, 2, 3])
@pytest.mark.parametrize("W", [16, 64])
def test_kda_chunk_update_is_the_token_recurrence(impl, interpret, W, H):
    """At the strongest published decay the running log-decay falls by
    ~100 over 64 rows, where exp(-G) alone overflows float32: both forms
    stay finite and equal to the token recurrence, with ragged spans, dead
    rows, an idle slot and a `fresh` slot whose pool holds a NaN, in the
    one layer of the pool they are told; with one head, a pass of two and
    a pass of three (the kernel walks a grid step's heads in lockstep)."""
    rng = np.random.default_rng(W)
    Bt, D = 4, 16
    rows = _kda_rows(rng, Bt, W, H, D)
    assert float(jnp.cumsum(rows[3], 1).min()) < (-25 if W == 16 else -100)
    pool = rng.standard_normal((2, Bt, H, D, D)).astype(np.float32)
    pool[1, 1] = np.nan                         # slot 1's last owner's
    counts = np.array([W, W - 3, 0, 5], np.int32)
    fresh = np.array([False, True, False, False])
    s0 = pool[1].transpose(0, 1, 3, 2).copy()
    s0[1] = 0
    want_o, want_s = _token_rule(*rows, s0, counts)
    o, new = kda_chunk_update(*rows, jnp.asarray(pool), jnp.asarray(counts),
                              1, impl=impl, interpret=interpret,
                              fresh=jnp.asarray(fresh))
    o, new = np.asarray(o), np.asarray(new)
    live = (np.arange(W)[None, :] < counts[:, None])[:, :, None, None]
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, want_o * live, atol=3e-5)
    assert not o[~np.broadcast_to(live, o.shape)].any()     # dead rows
    got_s = new[1].transpose(0, 1, 3, 2)
    for b in (0, 1, 3):
        np.testing.assert_allclose(got_s[b], want_s[b], atol=3e-5)
    np.testing.assert_array_equal(new[1, 2], pool[1, 2])    # the idle slot
    np.testing.assert_array_equal(new[0], pool[0])          # the other layer


def _repeated_keys(rng, Bt, W, H, D, keys_a_block):
    """`_kda_rows` with every 16-row block's keys its first one or two,
    over and over, a weak decay and beta near one: the block's strictly
    lower N has rank one or two and nothing damps its powers, whose terms
    in (I - N)(I + N^2)(I + N^4)(I + N^8) reach C(15, 7) = 6435 before
    they cancel."""
    q, k, v, _, _ = _kda_rows(rng, Bt, W, H, D, strongest=False)
    k = k.reshape(Bt, W // 16, 16, H, D)[:, :, np.arange(16) % keys_a_block]
    g = -1e-3 * rng.uniform(0.5, 1.5, (Bt, W, H, D))
    beta = rng.uniform(0.9, 0.99, (Bt, W, H))
    return [q, k.reshape(Bt, W, H, D), v, jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32)]


@pytest.mark.parametrize("impl, interpret", [("xla", False),
                                             ("pallas", True)])
@pytest.mark.parametrize("keys_a_block", [1, 2])
def test_kda_chunk_update_holds_where_keys_repeat(impl, interpret,
                                                  keys_a_block):
    """Every row of a 16-row block the same unit key, or two keys
    alternating. With one key the finite product of the block's powers, the
    kernel's first form, misses by 5e-4 in exact float32; sub-blocks merged
    from pairs of rows up, and the dense form's substitution, hold."""
    rng = np.random.default_rng(keys_a_block)
    Bt, W, H, D = 2, 64, 2, 16
    rows = _repeated_keys(rng, Bt, W, H, D, keys_a_block)
    pool = rng.standard_normal((1, Bt, H, D, D)).astype(np.float32)
    counts = np.array([W, W - 5], np.int32)
    want_o, want_s = _token_rule(*rows, pool[0].transpose(0, 1, 3, 2),
                                 counts)
    o, new = kda_chunk_update(*rows, jnp.asarray(pool), jnp.asarray(counts),
                              0, impl=impl, interpret=interpret)
    live = (np.arange(W)[None, :] < counts[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(o), want_o * live, atol=3e-5)
    np.testing.assert_allclose(np.asarray(new)[0].transpose(0, 1, 3, 2),
                               want_s, atol=3e-5)


def test_kda_decay_is_one_a_channel_and_the_rule_corrects():
    """What tells this rule from Mamba-2's: with the decay averaged over a
    head's channels, or without the (v - S^T k) correction, the token
    recurrence gives something else."""
    rng = np.random.default_rng(1)
    rows = _kda_rows(rng, 1, 16, 2, 16)
    s0 = np.zeros((1, 2, 16, 16))
    o, _ = _token_rule(*rows, s0, [16])
    g = rows[3]
    flat = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    o_head, _ = _token_rule(*rows[:3], flat, rows[4], s0, [16])
    assert np.abs(o - o_head).max() > 0.01 * np.abs(o).max()
    got, _ = kda_chunk_update(*rows, jnp.zeros((1, 1, 2, 16, 16)),
                              jnp.asarray([16]), 0, impl="xla")
    np.testing.assert_allclose(np.asarray(got), o, atol=3e-5)


@pytest.mark.parametrize("block_keys", [8, 32, 512])
def test_latent_span_attention_is_the_unabsorbed_form(block_keys,
                                                      monkeypatch):
    """The absorbed kernel over ONE pool of [c | r | 0] rows against the
    unabsorbed attention computed head by head from the same latent:
    slots at different lengths, a chunk that crosses a page boundary, a
    decode row, an idle slot, pages permuted, blocks of one page, of a few
    and of all a slot has."""
    monkeypatch.setattr(pa, "_LATENT_BLOCK_KEYS", block_keys)
    rng = np.random.default_rng(0)
    B, Sq, H, R, rope, nope, V, S, P = 4, 8, 4, 32, 8, 16, 16, 8, 6
    Wd = 48
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    up = f32(rng.standard_normal((H, nope + V, R)) * 0.3)
    c = f32(rng.standard_normal((B, P * S, R)))
    r = f32(rng.standard_normal((B, P * S, rope)))
    table = rng.permutation(B * P + 3)[:B * P].reshape(B, P).astype(np.int32)
    pool = np.zeros((2, B * P + 3, S, Wd), np.float32)
    rows = np.concatenate([c, r, np.zeros((B, P * S, Wd - R - rope))], -1)
    pool[1, table.reshape(-1)] = rows.reshape(B * P, S, Wd)
    q = f32(rng.standard_normal((B, Sq, H, nope + rope)))
    # tokens through query 0, and live query rows
    lengths = np.array([13, 1, 40, 20], np.int32)
    counts = np.array([8, 1, 8, 0], np.int32)
    qa = jnp.concatenate(
        [jnp.einsum("bthd,hdc->bthc", q[..., :nope], up[:, :nope]),
         q[..., nope:], jnp.zeros((B, Sq, H, Wd - R - rope))], -1)
    scale = (nope + rope) ** -0.5
    got = pa.latent_span_attention(
        qa, jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths),
        jnp.asarray(counts), value_width=R, scale=scale, impl="pallas",
        interpret=True, layer=1)
    got = jnp.einsum("bthc,hdc->bthd", got, up[:, nope:])
    dense = pa.latent_span_attention(
        qa, jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths),
        jnp.asarray(counts), value_width=R, scale=scale, impl="xla", layer=1)
    dense = jnp.einsum("bthc,hdc->bthd", dense, up[:, nope:])
    kn = jnp.einsum("btc,hdc->bthd", c, up[:, :nope])
    v = jnp.einsum("btc,hdc->bthd", c, up[:, nope:])
    for b in range(B):
        for j in range(Sq):
            if j >= counts[b]:
                assert not np.asarray(got[b, j]).any()
                continue
            n = lengths[b] + j
            s = (jnp.einsum("hd,thd->ht", q[b, j, :, :nope], kn[b, :n])
                 + q[b, j, :, nope:] @ r[b, :n].T) * scale
            want = jnp.einsum("ht,thd->hd", jax.nn.softmax(s, -1), v[b, :n])
            np.testing.assert_allclose(np.asarray(got[b, j]),
                                       np.asarray(want), atol=2e-5)
            np.testing.assert_allclose(np.asarray(dense[b, j]),
                                       np.asarray(want), atol=2e-5)


def test_one_pool_page_write_leaves_the_scatters_bytes():
    """PagedKVCache with `row_width`: one pool, no V; the page write and
    the row scatter leave the same bytes, across a page boundary, for a
    dead tail and an idle slot; the K/V methods that need a head refuse."""
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.standard_normal((3, 1, 16, 128)), jnp.float32)
    lengths = jnp.asarray([9, 0, 30], jnp.int32)
    spans = jnp.asarray([16, 5, 0], jnp.int32)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        cache = models.PagedKVCache.create(
            2, 3, 1, 64, 128, page_size=16, lengths=lengths, row_width=128,
            attn_impl=impl)
        assert cache.v_pages is None and cache.k_pages.shape[-1] == 128
        cache.spans = spans
        assert cache._page_write_impl(16) == impl
        out[impl] = np.asarray(cache.write_decode(1, rows, None).k_pages)
    np.testing.assert_array_equal(out["xla"], out["pallas_interpret"])
    assert out["xla"][1].any() and not out["xla"][0].any()
    with pytest.raises(MXNetError, match="no values"):
        cache.write_decode(0, rows, rows)
    with pytest.raises(MXNetError, match="head axis"):
        models.PagedKVCache.create(1, 1, 1, 64, 128, row_width=128,
                                   kv_dtype="int8")


@pytest.mark.parametrize("activation, parts", [("relu2", 1), ("swiglu", 2)])
def test_expert_ffn_activations_kernel_and_ragged_dot_agree(activation,
                                                            parts):
    """One kernel, the activation a parameter: uneven groups, an empty
    expert, rows past the last group, against a loop over the experts."""
    rng = np.random.default_rng(0)
    M, D, F, G = 256, 32, 48, 5
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((G, D, parts * F)) * 0.1,
                     jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((G, F, D)) * 0.1, jnp.float32)
    sizes = jnp.asarray([30, 0, 100, 7, 60], jnp.int32)
    dense = expert_ffn(x, w1, w2, sizes, impl="xla", activation=activation)
    kernel = expert_ffn(x, w1, w2, sizes, impl="pallas", interpret=True,
                        activation=activation)
    at = np.concatenate([[0], np.cumsum(sizes)])
    for e in range(G):
        h = np.asarray(x[at[e]:at[e + 1]]) @ np.asarray(w1[e])
        if activation == "relu2":
            h = np.maximum(h, 0) ** 2
        else:
            h = h[:, :F] / (1 + np.exp(-h[:, :F])) * h[:, F:]
        want = h @ np.asarray(w2[e])
        for got in (dense, kernel):
            np.testing.assert_allclose(np.asarray(got[at[e]:at[e + 1]]),
                                       want, atol=2e-5)
    with pytest.raises(ValueError, match="activation"):
        expert_ffn(x, w1, w2, sizes, activation="gelu")


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """Four holders of a quarter of the experts each, the same router and
    the same shared expert: their routed sums add up to the uncut layer's,
    so the four outputs add up to the uncut reference's layer once the
    shared expert, which every rank computes, is counted once."""
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(0.2 * rng.standard_normal((16, 128, 128)), jnp.float32)
    w2 = jnp.asarray(0.2 * rng.standard_normal((16, 64, 128)), jnp.float32)
    ranks = []
    for rank in range(4):
        net, kw, params = _model(std=0.2, held_experts=(4 * rank, 4))
        ranks.append((net.blocks()[2].ffn, kw, params))
    first = ranks[0][0]
    for rank, (moe, _, _) in enumerate(ranks):
        moe.experts.expert_w1.set_data(w1[4 * rank:4 * rank + 4])
        moe.experts.expert_w2.set_data(w2[4 * rank:4 * rank + 4])
        for mine, theirs in zip(moe.collect_params().values(),
                                first.collect_params().values()):
            if "expert_w" not in mine.name:
                mine.set_data(theirs.data())
    u = jnp.asarray(rng.standard_normal((2, 24, 128)), jnp.float32)
    live = jnp.ones((2, 24), bool)
    parts = [moe.forward(u, live)[0] for moe, _, _ in ranks]
    shared = first.shared.forward(u)
    # the uncut reference: all sixteen experts held
    kw = dict(ranks[0][1], held_experts=(0, 16))
    prefix = "layer2.ffn."
    named = {prefix + k: p.data()._data
             for k, p in first.collect_params().items()}
    named[prefix + "experts.expert_w1"] = w1
    named[prefix + "experts.expert_w2"] = w2
    w = lambda name: jnp.asarray(named[prefix + name], jnp.float32)
    cast = lambda a: jnp.asarray(a, jnp.float32)
    want = ref.expert_layer(u, w, kw, named, prefix, cast)
    assert _err(sum(parts) - 3 * shared, want) < TIGHT
    assert _err(parts[0], want) > 0.05       # one share is not the layer


# weights of standard deviation 0.1 and a sequence of five chunks: every
# reading then lies well beyond the cell's limit, as on the chip all but the
# two of the latent layer do (reference/kimi_linear.py, TOLERANCE)
@pytest.mark.parametrize("name", sorted(ref.PERTURBATIONS))
def test_every_perturbation_exceeds_the_limit(name):
    """Each of the reference's PERTURBATIONS, ONE thing wrong, moves the
    logits of the unchanged model by more than TOLERANCE, read as the
    cell's runner reads it: the cell's limit can tell it."""
    from benchmarks.runners.serve_long import beyond, errors
    net, kw, params = _model(std=0.1, units=256)
    ids = _ids(4, 1, 80)
    own = par.EvalStep(net)(ids)._data
    read = errors([own], [ref.logits(params, kw, ids)])
    assert all(read[k] < 0.01 * limit for k, limit in ref.TOLERANCE.items())
    moved = errors([own], [ref.logits(params, kw, ids,
                                      **ref.PERTURBATIONS[name])])
    assert beyond(moved, ref.TOLERANCE) == sorted(ref.TOLERANCE), name
    assert moved["logit_abs"] > ref.ARGMAX_MARGIN, name


def test_the_bfloat16_state_control_moves_the_logits_under_the_limit():
    """What the configuration keeps in float32 (the KDA state after every
    token, the softplus, the router's scores, every norm) rounded to
    bfloat16 in the reference: the logits move, by less than any limit
    tells. It is read beside the perturbations and listed apart."""
    from benchmarks.runners.serve_long import beyond, errors
    net, kw, params = _model(std=0.1, units=256)
    ids = _ids(4, 1, 80)
    want = ref.logits(params, kw, ids)
    (name, reading), = ref.CONTROLS.items()
    moved = errors([want], [ref.logits(params, kw, ids, **reading)])
    assert 1e-4 < moved["logit_rms"] < min(ref.TOLERANCE.values()), name
    assert beyond(moved, ref.TOLERANCE) == []
    assert name not in ref.PERTURBATIONS


def test_the_engine_allocates_one_pool_and_refuses_by_name():
    """A latent layer's slot state is ONE page pool `row_width` wide (no
    V pool, no head axis), a KDA layer's is recurrent leaves; the byte
    counts say so; and what splits, scales or ships pages by head refuses
    this model by name, as does everything recurrent state refuses."""
    net, kw, _ = _model()
    spec = net.state_spec()
    # 32 + 8 columns stored in the next whole 128-lane tile
    assert spec["row_width"] == 128 and spec["value_width"] == 32
    assert "num_kv_heads" not in spec and "head_dim" not in spec
    assert (spec["kv_layers"], spec["recurrent_layers"]) == (2, 3)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16)
    state = eng._device_state()
    assert sorted(state) == ["k", "rec"]
    assert state["k"].shape == (2, 8, 16, 128)
    assert {k: v.shape for k, v in state["rec"].items()} == {
        "conv": (3, 2, 3, 3 * 64), "kda": (3, 2, 4, 16, 16), "moe": (4, 5),
        "live_rows": (2,)}
    st = eng.stats
    assert st["kv_pool_bytes"] == 2 * 8 * 16 * 128 * 4
    assert st["kv_page_bytes"] == 2 * 16 * 128 * 4         # one pool
    assert st["kv_bytes_per_token"] == 2 * 128 * 4
    assert st["recurrent_state_bytes"] == 3 * 2 * (
        4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert st["expert_weight_bytes"] == 4 * 4 * 3 * 128 * 64 * 4
    for knob, value in (("tp", 2), ("kv_dtype", "int8"),
                        ("host_kv_bytes", 1 << 20), ("prefix_cache", True),
                        ("speculative", True), ("weight_dtype", "int8"),
                        ("adapter_pool", object())):
        extra = {"prefix_cache": True} if knob == "host_kv_bytes" else {}
        with pytest.raises(MXNetError, match=knob):
            ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                          chunk_tokens=16, **{knob: value}, **extra)


def test_a_pool_with_no_head_axis_refuses_tp_and_int8_without_state():
    """The refusal is the pool's, not the recurrent state's: a model of
    latent layers alone is refused `tp` and `kv_dtype` by name too, and
    serves with a prefix cache (one pool is copied page by page like
    two)."""
    net, kw, params = _model(pattern="LL", dense_layers=2)
    assert net.state_spec()["recurrent"] == {}
    for knob, value in (("tp", 2), ("kv_dtype", "int8"),
                        ("host_kv_bytes", 1 << 20)):
        extra = {"prefix_cache": True} if knob == "host_kv_bytes" else {}
        with pytest.raises(MXNetError, match="no head axis"):
            ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                          chunk_tokens=16, **{knob: value}, **extra)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16, prefix_cache=True)
    prompt = np.random.default_rng(0).integers(0, 512, 40)
    first, second = (Request(prompt, 5, request_id=i) for i in "ab")
    eng.serve([first])
    eng.serve([second])
    assert first.output_tokens == second.output_tokens
    assert eng.stats["prefix_hits"] == 1
    want = ref.logits(params, kw, jnp.asarray(np.concatenate(
        [prompt, first.output_tokens])[None], jnp.int32))[0]
    assert list(np.argmax(np.asarray(want[39:-1]), -1)) \
        == list(first.output_tokens)
