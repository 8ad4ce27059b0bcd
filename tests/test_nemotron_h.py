"""Nemotron-H (models/nemotron_h.py) held to its plain float32 reference
(benchmarks/reference/nemotron_h.py: a token-by-token recurrence, dense
attention, a loop over experts with a mask, no cache) at a tiny size on
seeded weights: the full forward, each layer kind on its own, the chunked
and paged serving path through ServingEngine logit by logit, the expert
layer's routing, dead rows, the four shares of one layer, the kernels this
configuration brought under the Pallas interpreter, and what the engine
allocates for each layer kind."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h as ref
from benchmarks.weights_per_parameter import seed_weights
from mxnet_tpu import models, parallel as par
from mxnet_tpu.gluon import nn
from mxnet_tpu.models.nemotron_h import MOE_COUNTERS
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.moe import expert_ffn, relu2
from mxnet_tpu.ops.ssm import ssd_chunk_update
from mxnet_tpu.serving import Request, ServingEngine

from test_falcon_h1 import _scan_tokens, _serve_and_capture

TINY = dict(vocab_size=512, units=128, pattern="MEM*EME*", num_heads=4,
            num_kv_heads=2, head_dim=32, ssm_heads=8, ssm_head_dim=16,
            ssm_state=16, ssm_groups=2, conv_kernel=4, chunk_size=16,
            num_experts=16, top_k=3, held_experts=(4, 4), latent_size=64,
            expert_hidden_size=128, shared_hidden_size=256, max_length=256,
            dtype="float32")


def _model(seed=3, std=0.02, bias_std=None, **over):
    """(net, its config as the reference's kwargs, its parameters).
    `bias_std` redraws the routers' correction bias at that spread."""
    cfg = models.nemotron3_super_120b_config(**{**TINY, **over})
    net = models.NemotronHForCausalLM(cfg)
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, seed, cfg.dtype, std=std)
    if bias_std is not None:
        rng = np.random.default_rng(seed)
        for k, p in net.collect_params().items():
            if k.endswith("gate_bias"):
                p.set_data(jnp.asarray(
                    bias_std * rng.standard_normal(p.shape), jnp.float32))
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
    # the layers are of one kind each and count what they hold
    assert [b.kind for b in net.blocks()] == list("MEM*EME*")
    assert [b.index for b in net.blocks()] == [0, 0, 1, 0, 1, 2, 2, 1]


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_layer_kind_matches_the_reference_on_its_own(kind):
    """One layer's f from a normalised input, weights of standard
    deviation 0.2 so that a wrong grouping or a missing scale shows:
    whole sequences, and the cached path in ragged chunks with slot 1 a
    row behind slot 0 for the two kinds that keep state."""
    net, kw, params = _model(std=0.2, bias_std=0.2)
    c, t = net.config, 37
    u = jnp.asarray(np.random.default_rng(1).standard_normal((2, t, c.units)),
                    jnp.float32)
    i = {"M": 2, "*": 3, "E": 4}[kind]
    block = net.blocks()[i]
    prefix = f"layer{i}.mixer."
    w = lambda name: jnp.asarray(params[prefix + name], jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    if kind == "M":
        want = ref.mixer_layer(u, w, kw)
        whole = lambda rows, cache: block.mixer.forward(
            rows, cache, block.index,
            None if cache is None else cache.length == 0)
    elif kind == "*":
        want = ref.attention_layer(u, w, kw)
        whole = lambda rows, cache: block.mixer.forward(
            rows, cache, block.index, pos)
    else:
        want = ref.expert_layer(u, w, kw, params, prefix)
        got, counts = block.mixer.forward(u, jnp.ones((2, t), bool))
        assert _err(got, want) < TIGHT
        assert dict(zip(MOE_COUNTERS, np.asarray(counts)))["rows"] == 2 * t
        return
    got, _ = whole(u, None)
    assert _err(got, want) < TIGHT
    cache = net.make_cache(2, 64, page_size=16, attn_impl="xla")
    out, at = [], 0
    while at < t:
        n = min(16, t - at)
        rows = jnp.pad(u[:, at:at + n], ((0, 0), (0, 16 - n), (0, 0)))
        cache.spans = jnp.full((2,), n, jnp.int32)
        f, cache = whole(rows, cache)
        out.append(f[:, :n])
        cache = cache.advance(n)
        at += n
    assert _err(jnp.concatenate(out, 1), want) < TIGHT


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_serving_engine_logits_match_the_reference(impl):
    """Ragged chunked prefill then decode through ServingEngine, logit by
    logit: five requests through two slots (a slot serves requests in
    succession), one chunk a dispatch (a slot sits idle between two chunks
    of its own prompt), prompt lengths that are no multiple of the chunk.
    Every layer kind maps its own index to its page layer, its state layer
    or its counter row."""
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
    # (the tiny model's KV rows are no whole lane tile: the row scatter)
    assert st["kernel_paths"] == {f"ragged_span_attention/{path}": 2,
                                  f"ssd_chunk_update/{path}": 3,
                                  f"expert_ffn/{path}": 3,
                                  "kv_page_write/xla": 2}
    # 2 slots x 16 rows x 3 choices = 96 pairs at most: one row tile
    assert st["kernel_tiles"] == ({} if impl == "xla" else {
        "ragged_span_attention/pages=4,keys=64,rows=32": 2,
        "expert_ffn/rows=96,hidden=128": 3})
    # every expert layer saw every dispatch and every live row, dead rows
    # none: 111 prompt tokens + 5 x 6 decode rows
    moe = [dict(zip(MOE_COUNTERS, row)) for row in
           st["model_counters"]["moe"]]
    assert len(moe) == 3
    for layer in moe:
        assert layer["dispatches"] == st["decode_dispatches"]
        assert layer["rows"] == 111 + 30
        assert 0 < layer["pairs"] <= 3 * layer["rows"]
        assert layer["largest_group"] * 4 >= layer["pairs"]
        assert layer["experts_touched"] <= 4 * layer["dispatches"]
    # a whole prompt chunk does not fit an eighth of the 2 x 16 grid: those
    # ticks took the full feed-forward, decode ticks and a prompt's short
    # last chunk the compact one, and every logit above matched
    dispatches, compact = st["model_counters"]["live_rows"]
    assert dispatches == st["decode_dispatches"]
    assert 0 < compact < dispatches
    eng.reset_stats()
    assert np.asarray(eng.stats["model_counters"]["moe"]).sum() == 0


def _chosen(net, ids):
    """The experts the program's own whole-sequence forward chose, per
    expert layer: spied at the layer's router."""
    seen, route = [], nn.DroplessMoE.route

    def spy(self, u):
        weights, experts = route(self, u)
        seen.append(np.asarray(experts))
        return weights, experts

    nn.DroplessMoE.route = spy
    try:
        net.hidden(ids)
    finally:
        nn.DroplessMoE.route = route
    return seen


def test_routing_is_the_references_exactly_in_float32():
    """Top-k is discontinuous, so in float32 on the CPU the program's
    chosen sets are held to the reference's with no tolerance, with the
    bias drawn at the scores' own spread so that it decides choices; and
    the choice without the bias is another choice."""
    net, kw, params = _model(bias_std=0.25)
    ids = _ids(8, 2, 40)
    want, without = [], []
    ref.logits(params, kw, ids, chosen_out=want)
    ref.logits(params, kw, ids, chosen_out=without, no_bias=True)
    got = _chosen(net, ids)
    assert len(got) == len(want) == 3
    sets = lambda a: np.sort(np.asarray(a).reshape(-1, kw["top_k"]), -1)
    for g, w, wo in zip(got, want, without):
        assert (sets(g) == sets(w)).all()
        assert (sets(w) != sets(wo)).any(axis=-1).mean() > 0.2


def test_dead_rows_and_other_slots_change_nothing():
    """An expert layer's output for a slot's live rows does not depend on
    what its dead rows or another slot's rows hold, dead rows cost no pair,
    and a dead row's routed sum is zero (the shared expert is dense)."""
    net, _, _ = _model(std=0.2)
    moe = net.blocks("E")[0].mixer
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((3, 16, 128)), jnp.float32)
    spans = jnp.asarray([16, 5, 0])
    live = jnp.arange(16)[None, :] < spans[:, None]
    got, counts = moe.forward(u, live)
    noise = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    other, counts2 = moe.forward(jnp.where(live[..., None], u, noise)
                                 .at[0].set(noise[0]), live)
    np.testing.assert_array_equal(np.asarray(got[1, :5]),
                                  np.asarray(other[1, :5]))
    c = dict(zip(MOE_COUNTERS, np.asarray(counts)))
    assert c["rows"] == 21 and c["pairs"] <= 21 * 3
    # the routed part alone: zero on dead rows
    routed, _ = moe.experts.forward(
        jnp.matmul(u.reshape(48, 128), moe.latent_in.weight.data()._data.T),
        live.reshape(-1), route_on=u.reshape(48, 128))
    assert not np.asarray(routed.reshape(3, 16, -1)[~np.asarray(live)]).any()
    assert np.asarray(routed.reshape(3, 16, -1)[0]).any()


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """Four holders of a quarter of the experts each, the same router and
    the same shared expert: their routed sums add up to the uncut layer's,
    so the four outputs add up to the uncut reference's layer once the
    shared expert, which every rank computes, is counted once."""
    shares = []
    for rank in range(4):
        net, kw, params = _model(std=0.2, held_experts=(4 * rank, 4))
        shares.append((net.blocks("E")[1].mixer, kw, params))
    # one draw of the router, the projections and the shared expert; each
    # rank's stacked weights are ITS rows of one (16, ...) draw
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(0.2 * rng.standard_normal((16, 64, 128)), jnp.float32)
    w2 = jnp.asarray(0.2 * rng.standard_normal((16, 128, 64)), jnp.float32)
    first = shares[0][0]
    for rank, (moe, _, _) in enumerate(shares):
        moe.experts.expert_w1.set_data(w1[4 * rank:4 * rank + 4])
        moe.experts.expert_w2.set_data(w2[4 * rank:4 * rank + 4])
        for name in ("latent_in", "latent_out", "shared_up", "shared_down"):
            getattr(moe, name).weight.set_data(
                getattr(first, name).weight.data())
        moe.experts.gate.weight.set_data(first.experts.gate.weight.data())
        moe.experts.gate_bias.set_data(first.experts.gate_bias.data())
    u = jnp.asarray(rng.standard_normal((2, 24, 128)), jnp.float32)
    live = jnp.ones((2, 24), bool)
    parts = [moe.forward(u, live)[0] for moe, _, _ in shares]
    shared = jnp.matmul(relu2(jnp.matmul(
        u, first.shared_up.weight.data()._data.T)),
        first.shared_down.weight.data()._data.T)
    # the uncut reference: all sixteen experts held
    kw = dict(shares[0][1], held_experts=(0, 16))
    prefix = "layer4.mixer."
    params = dict(shares[0][2])
    params.update({k: p.data()._data for k, p in
                   shares[0][0].collect_params().items()})
    named = {prefix + k: v for k, v in params.items()
             if not k.startswith("layer")}
    named[prefix + "experts.expert_w1"] = w1
    named[prefix + "experts.expert_w2"] = w2
    w = lambda name: jnp.asarray(named[prefix + name], jnp.float32)
    want = ref.expert_layer(u, w, kw, named, prefix)
    assert _err(sum(parts) - 3 * shared, want) < TIGHT
    assert _err(parts[0], want) > 0.05       # one share is not the layer


def test_the_perturbations_the_limit_must_catch_move_the_layer():
    """What the cell's comparison has to see (PERF.md, PR 32), each on one
    expert layer's own output with weights of standard deviation 0.2: the
    scaling factor left out, the shared expert left out, the choice made
    without the bias, the held range shifted by one expert."""
    net, kw, params = _model(std=0.2, bias_std=0.25)
    prefix = "layer4.mixer."
    w = lambda name: jnp.asarray(params[prefix + name], jnp.float32)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, 128)),
                    jnp.float32)
    got, _ = net.blocks()[4].mixer.forward(u, jnp.ones((2, 24), bool))
    want = ref.expert_layer(u, w, kw, params, prefix)
    assert _err(got, want) < TIGHT
    for knob in (dict(no_scale=True), dict(no_shared=True),
                 dict(no_bias=True), dict(held_shift=1)):
        moved = ref.expert_layer(u, w, kw, params, prefix, **knob)
        assert _err(moved, want) > 100 * TIGHT, knob


def test_expert_ffn_kernel_ragged_dot_and_a_loop_agree():
    """Uneven groups, an empty expert, a group across a tile's edge, rows
    past the last group: the kernel visits (tile, expert) pairs and keeps
    each expert's own rows."""
    rng = np.random.default_rng(0)
    M, D, F, G = 384, 128, 256, 5
    sizes = [130, 0, 7, 121, 60]                    # 318 of 384 rows
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.float32)
    w1 = jnp.asarray(0.1 * rng.standard_normal((G, D, F)), jnp.float32)
    w2 = jnp.asarray(0.1 * rng.standard_normal((G, F, D)), jnp.float32)
    want, at = np.zeros((M, D), np.float32), 0
    for g, n in enumerate(sizes):
        want[at:at + n] = relu2(x[at:at + n] @ w1[g]) @ w2[g]
        at += n
    gs = jnp.asarray(sizes, jnp.int32)
    for impl, interpret in (("xla", False), ("pallas", True)):
        got = expert_ffn(x, w1, w2, gs, impl=impl, interpret=interpret)
        np.testing.assert_allclose(np.asarray(got)[:at], want[:at],
                                   rtol=2e-5, atol=2e-5)
    # no rows at all: nothing is visited, nothing fails
    expert_ffn(x, w1, w2, jnp.zeros((G,), jnp.int32), impl="pallas",
               interpret=True).block_until_ready()


def test_ssd_chunk_update_at_head_size_64():
    """Two heads of 64 share a tile of 128 lanes in the kernel: kernel,
    einsums and token scan agree, 8 heads a group in two blocks of lanes,
    a dead slot, a fresh one."""
    rng = np.random.default_rng(2)
    Bt, W, H, P, G, N, L = 4, 16, 8, 64, 2, 128, 2
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    x, B, C = f(Bt, W, H, P), f(Bt, W, G, N), f(Bt, W, G, N)
    dt = np.log1p(np.exp(f(Bt, W, H)))
    A, D = -np.exp(0.3 * f(H)), 1 + 0.1 * f(H)
    state = f(L, Bt, H, P, N)
    counts = np.array([16, 1, 0, 7], np.int32)
    fresh = np.array([True, False, False, False])
    s0 = np.where(fresh[:, None, None, None], 0.0, state[1])
    want_y, want_s = _scan_tokens(x, dt, A, B, C, D, s0, counts)
    want_s[2] = state[1, 2]
    from mxnet_tpu.ops import ssm
    assert ssm._heads_per_block(H // G, P, N) == (4, 2)
    assert ssm._heads_per_block(16, 128, 256) == (16, 1)    # Falcon-H1's
    for impl, interpret in (("xla", False), ("pallas", True)):
        y, new = ssd_chunk_update(
            *map(jnp.asarray, (x, dt, A, B, C, D, state, counts)), 1,
            impl=impl, interpret=interpret, fresh=jnp.asarray(fresh))
        np.testing.assert_allclose(y, want_y, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(new[1], want_s, rtol=5e-4, atol=5e-4)
        assert (np.asarray(new[0]) == state[0]).all()
        assert (np.asarray(new[1, 2]) == state[1, 2]).all()
        assert not np.asarray(y[2]).any() and not np.asarray(y[1, 1:]).any()


def test_span_kernel_at_sixteen_query_heads_a_kv_head():
    rng = np.random.default_rng(4)
    B, Sq, Hkv, D, S, P, group = 3, 8, 2, 32, 8, 6, 16
    Hq, N = Hkv * group, B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((1, N, S, Hkv * D)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    lengths = jnp.asarray([9, 30, 17], jnp.int32)
    counts = jnp.asarray([8, 1, 0], jnp.int32)
    call = lambda **kw: pa.ragged_span_attention(
        q, kp, vp, table, lengths, q_counts=counts, layer=0,
        num_kv_heads=Hkv, **kw)
    got = call(impl="pallas", interpret=True)
    np.testing.assert_allclose(got, call(impl="xla"), rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[2]).any() and np.asarray(got[1, 0]).any()


def _pool_bytes(eng):
    state = eng._device_state()
    return {k: (sum(a.nbytes for a in v.values()) if isinstance(v, dict)
                else v.nbytes) for k, v in state.items()}


def test_the_engine_allocates_by_layer_kind():
    """Pages for the attention layers alone, recurrent leaves for the
    mixers alone, nothing for an expert layer but a row of counters; and
    for GPT-2 and Falcon-H1, every layer of which holds what the model
    holds, exactly what one `num_layers` gave."""
    base = dict(num_slots=2, max_length=64, page_size=16, attn_impl="xla")
    net, _, _ = _model()
    spec = net.state_spec()
    assert (spec["num_layers"], spec["kv_layers"],
            spec["recurrent_layers"]) == (8, 2, 3)
    assert spec["counters"] == {"moe": ((3, 5), "int32"),
                                "live_rows": ((2,), "int32")}
    assert spec["expert_weight_bytes"] == 3 * 4 * 2 * 64 * 128 * 4
    eng = ServingEngine(net, **base)
    pages = 2 * (64 // 16)
    assert eng._kp.shape == (2, pages, 16, 2 * 32)
    conv, ssm = 3 * (8 * 16 + 2 * 2 * 16) * 4, 8 * 16 * 16 * 4
    assert _pool_bytes(eng) == {
        "k": 2 * pages * 16 * 64 * 4, "v": 2 * pages * 16 * 64 * 4,
        "rec": 3 * 2 * (conv + ssm) + 3 * 5 * 4 + 2 * 4}
    st = eng.stats
    assert st["recurrent_state_bytes"] == 3 * 2 * (conv + ssm)
    assert st["kv_page_bytes"] == 2 * 2 * 16 * 64 * 4
    assert (st["kv_layers"], st["recurrent_layers"]) == (2, 3)
    assert st["expert_weight_bytes"] == spec["expert_weight_bytes"]

    from test_falcon_h1 import _model as falcon
    fnet, _, _ = falcon()
    fspec = fnet.state_spec()
    assert "kv_layers" not in fspec and "recurrent_layers" not in fspec
    feng = ServingEngine(fnet, **base)
    fconv, fssm = 3 * (4 * 32 + 2 * 2 * 16) * 4, 4 * 32 * 16 * 4
    assert _pool_bytes(feng) == {
        "k": 2 * pages * 16 * 64 * 4, "v": 2 * pages * 16 * 64 * 4,
        "rec": 2 * 2 * (fconv + fssm) + 2 * 4}
    fst = feng.stats
    assert (fst["kv_layers"], fst["recurrent_layers"],
            fst["expert_weight_bytes"], fst["model_counters"]) \
        == (2, 2, 0, {"live_rows": [0, 0]})

    gnet = models.GPT2ForCausalLM(models.GPT2Config(
        vocab_size=128, units=64, num_layers=3, num_heads=4, max_length=64,
        dtype="float32"))
    gnet.initialize()
    geng = ServingEngine(gnet, **base)
    assert _pool_bytes(geng) == {"k": 3 * pages * 16 * 64 * 4,
                                 "v": 3 * pages * 16 * 64 * 4}
    gst = geng.stats
    assert (gst["kv_layers"], gst["recurrent_layers"],
            gst["recurrent_state_bytes"]) == (3, 0, 0)


def test_a_recurrent_model_with_experts_is_still_refused_by_name():
    from mxnet_tpu.base import MXNetError
    net, _, _ = _model()
    with pytest.raises(MXNetError, match="prefix_cache"):
        ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                      attn_impl="xla", prefix_cache=True)
    with pytest.raises(MXNetError, match="held experts"):
        nn.DroplessMoE(64, 128, 16, 3, held=(14, 4))
    with pytest.raises(MXNetError, match="layer pattern"):
        models.NemotronHConfig(pattern="MEX")
