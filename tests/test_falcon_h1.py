"""Falcon-H1 (models/falcon_h1.py) held to its plain float32 reference
(benchmarks/reference/falcon_h1.py: a token-by-token recurrence, dense
attention, no cache) at a tiny size on seeded weights: the full forward,
both branches on their own, the chunked and paged serving path through
ServingEngine logit by logit, both kernels under the Pallas interpreter,
and the cases that must FAIL the comparison."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import falcon_h1 as ref
from benchmarks.weights import seed_weights
from mxnet_tpu import models, parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.ssm import ssd_chunk_update
from mxnet_tpu.serving import Request, ServingEngine

TINY = dict(vocab_size=512, units=128, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=32, hidden_size=256, ssm_heads=4,
            ssm_head_dim=32, ssm_state=16, ssm_groups=2, conv_kernel=4,
            chunk_size=16, max_length=256, dtype="float32")
UNIT = dict(embedding_multiplier=1.0, lm_head_multiplier=1.0,
            attention_in_multiplier=1.0, attention_out_multiplier=1.0,
            key_multiplier=1.0, ssm_in_multiplier=1.0,
            ssm_multipliers=(1.0,) * 5, ssm_out_multiplier=1.0,
            mlp_multipliers=(1.0, 1.0))


def _model(seed=3, std=0.02, **over):
    """(net, its config as the reference's kwargs, its parameters)."""
    cfg = models.falcon_h1_34b_config(**{**TINY, **over})
    net = models.FalconH1ForCausalLM(cfg)
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


@pytest.mark.parametrize("cached", [False, True])
def test_forward_is_head_of_hidden_and_rows_commute(cached):
    """`forward` is `head(hidden(...))` bit for bit, and the head of picked
    rows is the picked rows of the whole head (the multiplier is per row;
    the matmul may accumulate in another order: float tolerance)."""
    net, _, _ = _model(lm_head_multiplier=0.0078125)
    ids = _ids(2, 2, 16)

    def cache():
        if not cached:
            return None
        c = net.make_cache(2, 64, page_size=16, attn_impl="xla")
        c.spans = jnp.asarray([16, 9], jnp.int32)
        return c

    out = net.forward(ids, cache())
    h, new = net.hidden(ids, cache())
    whole = net.head(h)._data
    assert (new is None) == (not cached)
    assert jnp.array_equal(whole, (out[0] if cached else out)._data)
    rows = jnp.asarray([[15], [8]])
    picked = net.head(jnp.take_along_axis(h._data, rows[:, :, None], 1))
    np.testing.assert_allclose(
        np.asarray(picked._data),
        np.asarray(jnp.take_along_axis(whole, rows[:, :, None], 1)),
        rtol=1e-5, atol=1e-7)


def test_full_forward_matches_the_reference():
    net, kw, params = _model()
    ids = _ids(0, 2, 37)        # not a multiple of the 16-row chunk
    want = ref.logits(params, kw, ids)
    assert _err(par.EvalStep(net)(ids)._data, want) < TIGHT


@pytest.mark.parametrize("unit", [False, True],
                         ids=["published_multipliers", "unit_multipliers"])
def test_each_branch_matches_the_reference_on_its_own(unit):
    """At the published multipliers key_multiplier flattens the softmax
    and attention_out_multiplier shrinks the branch, so a wrong rotary or
    head grouping would hide in any limit on the logits: each branch's own
    output is compared, also with every multiplier 1 and weights of
    standard deviation 0.2. Whole sequences, and the cached path in ragged
    chunks."""
    net, kw, params = _model(std=0.2, **UNIT) if unit else _model()
    c, t = net.config, 37
    u = jnp.asarray(np.random.default_rng(1).standard_normal((2, t, c.units)),
                    jnp.float32)
    block = net.blocks()[1]
    w = lambda name: jnp.asarray(params[f"layer1.{name}"], jnp.float32)
    want_a = ref.attention_branch(u, w, kw)
    want_m = ref.mixer_branch(u, w, kw)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    got_a, _ = block._attention(u, None, 1, pos)
    got_m, _ = block._mixer(u, None, 1, None)
    assert _err(got_a, want_a) < TIGHT and _err(got_m, want_m) < TIGHT
    # the cached path: chunks of 16 rows, the last ragged, slot 1 one
    # row behind slot 0
    cache = net.make_cache(2, 64, page_size=16, attn_impl="xla")
    got_a, got_m, at = [], [], 0
    while at < t:
        n = min(16, t - at)
        rows = jnp.pad(u[:, at:at + n], ((0, 0), (0, 16 - n), (0, 0)))
        cache.spans = jnp.full((2,), n, jnp.int32)
        pos = cache.length[:, None] + jnp.arange(16)[None]
        a, cache = block._attention(rows, cache, 1, pos)
        m, cache = block._mixer(rows, cache, 1, cache.length == 0)
        got_a.append(a[:, :n])
        got_m.append(m[:, :n])
        cache = cache.advance(n)
        at += n
    assert _err(jnp.concatenate(got_a, 1), want_a) < TIGHT
    assert _err(jnp.concatenate(got_m, 1), want_m) < TIGHT


def _serve_and_capture(net, requests, **engine_kw):
    """Serve `requests` and return, per request, the logits the unified
    program's hidden states give at each of its positions (captured at
    the model's `hidden`, inside the engine's program, and put through the
    model's `head` whole: the engine itself heads only the rows it
    samples), keyed by request id."""
    seen = []
    hidden = net.hidden

    def spy(inputs, cache=None):
        h, new = hidden(inputs, cache)
        jax.debug.callback(
            lambda *a: seen.append([np.asarray(x) for x in a]),
            inputs._data, net.head(h)._data, cache.spans, cache.length)
        return h, new

    net.hidden = spy
    try:
        eng = ServingEngine(net, **engine_kw)
        done = eng.serve(requests)
        jax.effects_barrier()
    finally:
        del net.hidden
    assert all(r.status == "finished" for r in done)
    rows = {r.id: {} for r in requests}
    owner = {}
    for toks, logits, spans, length in seen:
        for b in range(len(spans)):
            n = int(spans[b])
            if not n:
                continue
            if length[b] == 0:              # a first chunk names its owner
                owner[b] = next(
                    r.id for r in requests
                    if not rows[r.id] and len(r.prompt) >= n
                    and (r.prompt[:n] == toks[b, :n]).all())
            for j in range(n):
                rows[owner[b]][int(length[b]) + j] = logits[b, j]
    return eng, rows


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_serving_engine_logits_match_the_reference(impl):
    """Ragged chunked prefill then decode through ServingEngine, logit by
    logit. Five requests through two slots, so a slot serves requests in
    succession and the later ones must not see the earlier one's state; a
    prefill budget of one chunk a dispatch, so a slot sits idle between
    two chunks of its own prompt and dead rows must leave its state and
    convolution tail untouched; prompt lengths that are no multiple of the
    chunk, one shorter than the convolution's reach."""
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
    assert st["state_resets"] == 5
    # a whole prompt chunk does not fit an eighth of the 2 x 16 grid: those
    # ticks took the full feed-forward, decode ticks and a prompt's short
    # last chunk the compact one, and every logit above matched
    dispatches, compact = st["model_counters"]["live_rows"]
    assert dispatches == st["decode_dispatches"]
    assert 0 < compact < dispatches
    assert st["recurrent_state_bytes"] == 2 * 2 * (
        4 * 32 * 16 * 4 + 3 * (4 * 32 + 2 * 2 * 16) * 4)
    path = "xla" if impl == "xla" else "pallas"
    # the tiny model's 2 KV heads of 32 are no whole lane tile: its new
    # K/V rows keep the row scatter under either knob
    assert st["kernel_paths"] == {f"ragged_span_attention/{path}": 2,
                                  f"ssd_chunk_update/{path}": 2,
                                  "kv_page_write/xla": 2}
    # the block the two span calls were built with (all 4 pages of 16
    # tokens a slot has; 2 query heads a KV head x 16 rows), beside
    # kernel_paths; the dense form has none
    assert st["kernel_tiles"] == ({} if impl == "xla" else {
        "ragged_span_attention/pages=4,keys=64,rows=32": 2})


def _cached_mixer(net, u, between=None):
    """The mixer of layer 1 over `u` (1, T, units) through the cached path
    in chunks of 16 rows; `between(cache)` may tamper with the cache at
    each chunk boundary."""
    block = net.blocks()[1]
    cache = net.make_cache(1, 64, page_size=16, attn_impl="xla")
    out, at, t = [], 0, u.shape[1]
    while at < t:
        n = min(16, t - at)
        cache.spans = jnp.full((1,), n, jnp.int32)
        m, cache = block._mixer(
            jnp.pad(u[:, at:at + n], ((0, 0), (0, 16 - n), (0, 0))), cache,
            1, cache.length == 0)
        cache = cache.advance(n)
        out.append(m[:, :n])
        at += n
        if between is not None and at < t:
            cache = between(cache)
    return jnp.concatenate(out, 1)


def test_the_comparison_fails_without_the_carried_state_or_in_bfloat16():
    """Tightness: the limit the tests hold the mixer to must catch a state
    that is dropped at a chunk boundary and a state kept in bfloat16. On
    the mixer's own output, with every multiplier 1 and weights of
    standard deviation 0.2: at the published multipliers and N(0, 0.02)
    weights the carried state moves the LOGITS by a hundred-thousandth of
    their spread, and no limit on them could see either fault."""
    net, kw, params = _model(std=0.2, **UNIT)
    u = jnp.asarray(np.random.default_rng(7).standard_normal(
        (1, 40, net.config.units)), jnp.float32)
    w = lambda name: jnp.asarray(params[f"layer1.{name}"], jnp.float32)
    want = ref.mixer_branch(u, w, kw)
    assert _err(_cached_mixer(net, u), want) < TIGHT
    zeroed = lambda cache: cache.with_recurrent(dict(
        cache.recurrent, ssm=jnp.zeros_like(cache.recurrent["ssm"])))
    assert _err(_cached_mixer(net, u, zeroed), want) > 100 * TIGHT
    low, _, _ = _model(std=0.2, state_dtype="bfloat16", **UNIT)
    assert _err(_cached_mixer(low, u), want) > 10 * TIGHT
    # and the reference says the same of itself
    assert _err(ref.mixer_branch(u, w, kw, reset_every=16), want) \
        > 100 * TIGHT
    assert _err(ref.mixer_branch(u, w, kw, state_dtype=jnp.bfloat16),
                want) > 10 * TIGHT


def _scan_tokens(x, dt, A, B, C, D, s0, counts):
    """The recurrence one token at a time, dead rows skipped."""
    Bt, W, H, P = x.shape
    G = B.shape[2]
    y = np.zeros((Bt, W, H, P), np.float64)
    S = np.array(s0, np.float64)
    for b in range(Bt):
        for t in range(int(counts[b])):
            for h in range(H):
                g = h // (H // G)
                S[b, h] = np.exp(dt[b, t, h] * A[h]) * S[b, h] \
                    + dt[b, t, h] * np.outer(x[b, t, h], B[b, t, g])
                y[b, t, h] = S[b, h] @ C[b, t, g] + D[h] * x[b, t, h]
    return y, S


def test_ssd_chunk_update_kernel_einsums_and_token_scan_agree():
    rng = np.random.default_rng(2)
    Bt, W, H, P, G, N, L = 5, 16, 4, 32, 2, 16, 3
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    x, B, C = f(Bt, W, H, P), f(Bt, W, G, N), f(Bt, W, G, N)
    dt = np.log1p(np.exp(f(Bt, W, H)))
    A, D = -np.exp(0.3 * f(H)), 1 + 0.1 * f(H)
    state = f(L, Bt, H, P, N)
    counts = np.array([16, 1, 0, 7, 3], np.int32)    # slot 2 is dead
    fresh = np.array([True, False, False, False, True])
    s0 = np.where(fresh[:, None, None, None], 0.0, state[1])
    want_y, want_s = _scan_tokens(x, dt, A, B, C, D, s0, counts)
    want_s[2] = state[1, 2]                          # untouched, not zeroed
    for impl, interpret in (("xla", False), ("pallas", True)):
        y, new = ssd_chunk_update(
            *map(jnp.asarray, (x, dt, A, B, C, D, state, counts)), 1,
            impl=impl, interpret=interpret, fresh=jnp.asarray(fresh))
        np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[1], want_s, rtol=2e-4, atol=2e-4)
        assert (np.asarray(new[0]) == state[0]).all()
        assert (np.asarray(new[2]) == state[2]).all()
        assert (np.asarray(new[1, 2]) == state[1, 2]).all()
        assert not np.asarray(y[2]).any() and not np.asarray(y[1, 1:]).any()


@pytest.mark.parametrize("group", [1, 2])
def test_span_kernel_under_grouped_kv_heads(group):
    """ragged_span_attention with `group` query heads a KV head,
    interpreted, against its dense reference and against plain multi-head
    attention over pages with each KV head repeated; at group size 1 the
    argument changes nothing: the same lowering, the same bits."""
    rng = np.random.default_rng(4)
    B, Sq, Hkv, D, S, P = 4, 8, 2, 32, 8, 6
    Hq, N = Hkv * group, B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((2, N, S, Hkv * D)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    lengths = jnp.asarray([1, 9, 30, 17], jnp.int32)
    counts = jnp.asarray([8, 3, 1, 0], jnp.int32)
    call = lambda **kw: pa.ragged_span_attention(
        q, kp, vp, table, lengths, q_counts=counts, layer=1,
        num_kv_heads=Hkv, **kw)
    got = call(impl="pallas", interpret=True)
    np.testing.assert_allclose(got, call(impl="xla"), rtol=2e-5, atol=2e-5)
    wide = lambda p: jnp.repeat(p.reshape(2, N, S, Hkv, D), group,
                                axis=3).reshape(2, N, S, Hq * D)
    plain = pa.ragged_span_attention(
        q, wide(kp), wide(vp), table, lengths, q_counts=counts, layer=1,
        impl="pallas", interpret=True)
    np.testing.assert_allclose(got, plain, rtol=2e-6, atol=2e-6)
    assert not np.asarray(got[3]).any() and not np.asarray(got[1, 3:]).any()
    if group == 1:
        assert (np.asarray(got) == np.asarray(plain)).all()
        lowered = lambda **kw: jax.jit(lambda q, kp, vp: (
            pa.ragged_span_attention(
                q, kp, vp, table, lengths, q_counts=counts, layer=1,
                impl="pallas", interpret=True, **kw))).lower(
                    q, kp, vp).as_text()
        assert lowered(num_kv_heads=Hkv) == lowered()
    with pytest.raises(ValueError, match="query heads over"):
        pa.ragged_span_attention(q, kp, vp, table, lengths, num_kv_heads=3)


@pytest.mark.parametrize("pages", ["float32", "int8"])
@pytest.mark.parametrize("group,kb", [(2, None), (5, None), (5, 2), (5, 3)])
def test_span_kernel_blocks_under_grouped_kv_heads(monkeypatch, group, kb,
                                                   pages):
    """The block of pages under stacked query heads (Falcon-H1 stacks 5):
    P = 10 pages that neither the rule's block (8 pages here) nor 3
    divides, contexts that end one key before, on and one key after a
    block's edge, a span across it, an idle slot between busy ones and
    counts of 0, 1 and Sq in one call; float and int8 pages; against the
    dense reference, dead rows exact zeros."""
    if kb is not None:
        monkeypatch.setattr(pa, "_span_block_pages", lambda S, Sr, P: kb)
    rng = np.random.default_rng(6)
    B, Sq, Hkv, D, S, P = 6, 8, 2, 32, 8, 10
    KB = kb or pa._span_block_pages(S, group * Sq, P)
    assert KB == (kb or 8)
    W, Hq, N = KB * S, Hkv * group, B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    kp, vp = (rng.standard_normal((2, N, S, Hkv, D)) for _ in range(2))
    kw = {}
    if pages == "int8":
        ks, vs = (np.abs(x).max(axis=(2, 4)) / 127.0 for x in (kp, vp))
        kp, vp = (np.round(x / sc[:, :, None, :, None])
                  for x, sc in ((kp, ks), (vp, vs)))
        kw = dict(k_scale=jnp.asarray(ks, jnp.float32),
                  v_scale=jnp.asarray(vs, jnp.float32))
    kp, vp = (jnp.asarray(x.reshape(2, N, S, Hkv * D),
                          jnp.int8 if kw else jnp.float32) for x in (kp, vp))
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    lengths = jnp.asarray([W - 1, W, 5, W + 1, W - 3, P * S - 7], jnp.int32)
    counts = jnp.asarray([1, 1, 0, 1, 8, 8], jnp.int32)
    call = lambda **k: pa.ragged_span_attention(
        q, kp, vp, table, lengths, q_counts=counts, layer=1,
        num_kv_heads=Hkv, **kw, **k)
    got = np.asarray(call(impl="pallas", interpret=True))
    np.testing.assert_allclose(got, call(impl="xla"), rtol=2e-5, atol=2e-5)
    dead = np.arange(Sq)[None, :] >= np.asarray(counts)[:, None]
    assert (got[dead] == 0).all() and np.abs(got[~dead]).min() > 0


@pytest.mark.parametrize("feature, kwargs", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("speculative", dict(speculative=True)),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("tp", dict(tp=2)),
    ("kv_dtype", dict(kv_dtype="int8")),
    ("weight_dtype", dict(weight_dtype="int8")),
    ("adapter_pool", dict(adapter_pool=object())),
    ("export_handoff", None),
    ("export_requests", None),
])
def test_engine_refuses_what_knows_nothing_of_recurrent_state(feature,
                                                              kwargs):
    """Every feature that leases, copies, shards or ships pages refuses a
    model that declares recurrent state, by name, at construction (the
    two exports when called)."""
    net, _, _ = _model()
    base = dict(num_slots=2, max_length=64, page_size=16, attn_impl="xla")
    if kwargs is None:
        eng = ServingEngine(net, **base)
        with pytest.raises(MXNetError, match=feature):
            getattr(eng, feature)(*(["r0"] if feature == "export_handoff"
                                    else []))
        return
    with pytest.raises(MXNetError, match=feature + " is not supported"):
        ServingEngine(net, **base, **kwargs)


def test_gpt2_declares_pages_only():
    net = models.GPT2ForCausalLM(models.gpt2_small_config(
        num_layers=1, units=64, num_heads=2, vocab_size=64, max_length=64))
    assert net.state_spec() == {"num_layers": 1, "num_kv_heads": 2,
                                "head_dim": 32, "recurrent": {}}
    net.initialize()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16)
    assert eng.stats["recurrent_state_bytes"] == 0
    assert sorted(eng._device_state()) == ["k", "v"]
