"""Trinity (models/trinity.py) held to its plain float32 reference
(benchmarks/reference/trinity.py: whole sequences, no cache, the window a
mask) at a tiny size whose window is shorter than its prompts: the full
forward, the chunked and paged serving path through ServingEngine logit by
logit across ring wrap-around and rows that straddle the window's edge, the
window kernel against its XLA form and a dense masked softmax, the span
kernel without a window as it was, the pools sized by kind, every refusal
by name, the `window_keys` counter, and every perturbation the cell's limit
must catch."""
import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import trinity as ref
from mxnet_tpu import models, parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import Request, ServingEngine

from test_falcon_h1 import _serve_and_capture

S, F = "sliding_attention", "full_attention"
# a window of 24 keys over rings of pages of 8 rows, at most 8 rows a slot a
# dispatch: 5 ring pages (40 positions), which prompts of 60 wrap
TINY = dict(vocab_size=512, units=128, layer_types=[S, S, F, S],
            num_heads=4, num_kv_heads=2, head_dim=64, dense_layers=1,
            dense_hidden_size=192, num_experts=8, top_k=2,
            expert_hidden_size=32, shared_hidden_size=32,
            routed_scaling_factor=2.0, window=24, max_length=1024,
            ring_page_size=8, dtype="float32")
ENGINE = dict(num_slots=2, max_length=128, page_size=8, chunk_tokens=8,
              prefill_chunk_budget=16)


def _model(seed=3, **over):
    """(net, its config as the reference's kwargs, its parameters): the
    norms about one, everything else N(0, 0.02); the head at the spread
    of the cell's logits (0.02 x sqrt(2048 / 128))."""
    cfg = models.trinity_mini_config(**{**TINY, **over})
    net = models.TrinityForCausalLM(cfg)
    net.collect_params().setattr("grad_req", "null")
    from benchmarks.runners.serve_long import seed_weights as drawn
    drawn(net, seed, cfg.dtype, {"head.weight": 0.08})
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    return net, dict(vars(cfg)), params


def _reference(params, kw, ids, **reading):
    """The reference's logits, jitted (op by op it takes seconds)."""
    return jax.jit(functools.partial(ref.logits, kwargs=kw, **reading))(
        params, ids=ids)


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
    ids = _ids(0, 2, 61)
    want = _reference(params, kw, ids)
    assert _err(par.EvalStep(net)(ids)._data, want) < TIGHT
    # the window does something at this length: the same model with every
    # layer full is another model
    assert _err(_reference(params, kw, ids, no_window=True), want) > 0.05


def test_the_published_config():
    cfg = models.trinity_mini_config()
    assert cfg.num_layers == 32 and cfg.layer_types[3::4] == [F] * 8
    assert cfg.layer_types.count(S) == 24 and cfg.dense_layers == 2
    assert (cfg.window, cfg.ring_page_size, cfg.ring_pages) \
        == (2048, 64, 34)
    # R covers a window and a chunk's 64 rows from anywhere in a page
    assert cfg.ring_pages == -(-(2048 + 64 - 1) // 64) + 1
    # the state by kind, at the tiny widths and the published pattern
    net, _, _ = _model(layer_types=[S, S, S, F] * 2, dense_layers=2)
    spec = net.state_spec()
    assert (spec["num_layers"], spec["kv_layers"], spec["window_layers"],
            spec["recurrent_layers"], spec["ring_pages"]) == (8, 2, 6, 6, 5)
    ring = ((5, 8, 128), "float32")
    assert spec["recurrent"] == {"ring_k": ring, "ring_v": ring}
    assert spec["counters"]["moe"] == ((6, 5), "int32")
    assert spec["counters"]["window_keys"] == ((2,), "int32")
    assert spec["expert_weight_bytes"] == 6 * 8 * 3 * 128 * 32 * 4


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_serving_engine_logits_match_the_reference(impl):
    """Ragged chunked prefill then decode through ServingEngine, logit by
    logit: five requests through two slots (a slot serves requests in
    succession; a ring keeps whatever the slot's last request left in it),
    8-row chunks against a window of 24 over rings of 5 pages of 8: the
    60-token prompt wraps every ring twice, and a chunk's rows straddle the
    window's edge at every length past 24."""
    net, kw, params = _model()
    rng = np.random.default_rng(5)
    requests = [Request(rng.integers(0, 512, n), 6, request_id=f"r{i}")
                for i, n in enumerate((60, 21, 2, 35, 29))]
    eng, rows = _serve_and_capture(net, requests, attn_impl=impl, **ENGINE)
    seqs = [np.concatenate([r.prompt, r.output_tokens]) for r in requests]
    # one reference call: a position's logits read no later position, so
    # the sequences are padded at their ends to one length
    longest = max(map(len, seqs))
    wants = _reference(params, kw, jnp.asarray(np.stack(
        [np.pad(q, (0, longest - len(q))) for q in seqs]), jnp.int32))
    for r, seq, want in zip(requests, seqs, wants):
        want = want[:len(seq)]
        got = rows[r.id]
        assert sorted(got) == list(range(len(seq) - 1)), r.id
        got = jnp.stack([got[i] for i in range(len(seq) - 1)])
        assert _err(got, want[:-1]) < TIGHT, r.id
        assert list(np.argmax(np.asarray(want[len(r.prompt) - 1:-1]), -1)) \
            == list(r.output_tokens), r.id
    path = "xla" if impl == "xla" else "pallas"
    # a page write and a kernel a layer; two query heads a KV head stack 16
    # rows, and the window kernel's grid spans the 5 pages of a ring
    assert eng.stats["kernel_paths"] == {
        f"kv_page_write/{path}": 4, f"ragged_span_attention/{path}": 1,
        f"window_span_attention/{path}": 3, f"expert_ffn/{path}": 3}
    tiles = eng.stats["kernel_tiles"]
    if impl != "xla":
        assert tiles["window_span_attention/pages=4,keys=32,rows=16"] == 3
        assert tiles["ragged_span_attention/pages=16,keys=128,rows=16"] == 1


def _ring_case(B=4, Sq=8, Hq=4, Hkv=2, D=64, S=8, W=24, layers=2, seed=0):
    """A ring pool of `layers` layers, its arithmetic table over 16 logical
    pages, queries, lengths and counts: a slot at its start, a slot whose
    first windowed page lies mid-table after the ring wrapped, a slot with
    a partial chunk, a dead slot."""
    R = pa.window_pages(S, Sq, W)
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(rng.normal(size=(layers, B * R, S, Hkv * D)),
                               jnp.float32)
    table = jnp.arange(B)[:, None] * R + jnp.arange(16)[None, :] % R
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    lengths = jnp.array([1, 83, 30, 50], jnp.int32)[:B]    # position + 1
    counts = jnp.array([8, 8, 3, 0], jnp.int32)[:B]
    return R, pool(), pool(), table, q, lengths, counts


def _dense_window(q, k, v, R, S, W, lengths, counts, layer):
    """Query j of slot b (position lengths - 1 + j) over the keys at
    positions max(0, p - W + 1) .. p, read from ring page (p // S) mod R,
    by a plain softmax; dead rows zero."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[-1] // D
    kk, vv = np.asarray(k[layer]), np.asarray(v[layer])
    out = np.zeros(q.shape, np.float32)
    for b in range(B):
        for j in range(int(counts[b])):
            p = int(lengths[b]) - 1 + j
            keys = range(max(0, p - W + 1), p + 1)
            for h in range(Hq):
                c = slice(h // (Hq // Hkv) * D, (h // (Hq // Hkv) + 1) * D)
                at = [(b * R + (t // S) % R, t % S) for t in keys]
                K = np.stack([kk[r, i, c] for r, i in at])
                V = np.stack([vv[r, i, c] for r, i in at])
                s = K @ np.asarray(q[b, j, h]) / np.sqrt(D)
                w = np.exp(s - s.max())
                out[b, j, h] = (w / w.sum()) @ V
    return out


@pytest.mark.parametrize("Sq, Hq, Hkv", [(8, 4, 2), (8, 2, 2), (3, 4, 1)])
def test_window_span_attention_against_its_xla_form_and_a_dense_softmax(
        Sq, Hq, Hkv):
    R, k, v, table, q, lengths, counts = _ring_case(Sq=Sq, Hq=Hq, Hkv=Hkv)
    counts = jnp.minimum(counts, Sq)
    run = lambda impl, interpret: pa.ragged_span_attention(
        q[:, :Sq], k, v, table, lengths, q_counts=counts, impl=impl,
        interpret=interpret, layer=1, num_kv_heads=Hkv, window=24)
    kernel, dense = run("pallas", True), run("xla", False)
    want = _dense_window(q[:, :Sq], k, v, R, 8, 24, lengths, counts, 1)
    np.testing.assert_allclose(np.asarray(kernel), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense), want, atol=2e-5)
    # the dead slot and the partial chunk's dead rows are exact zeros
    assert not np.asarray(kernel)[3].any()
    assert not np.asarray(kernel)[2, 3:].any()


def test_the_window_kernel_visits_no_page_before_the_window():
    """The block table the window kernel's index maps read: slot 1 (query
    0 at position 82, window from 59) starts at logical page 7 and visits
    7 .. 10 (ring pages 2, 3, 4, 0) and no earlier page; an idle slot
    visits none (its steps repeat the page held before)."""
    R, _, _, table, _, lengths, counts = _ring_case()
    first = pa._window_first_page(lengths, counts, 8, 24)
    assert list(np.asarray(first)) == [0, 7, 0, 0]
    blocks = pa._span_block_table(table, lengths, counts, 8, 4,
                                  first=first, blocks=2)
    got = np.asarray(blocks).reshape(4, 2, 4)
    assert list(got[1, 0]) == [1 * R + n % R for n in (7, 8, 9, 10)]
    # slot 0 needs one page (0): an operand holds, before its first live
    # page, the first slot's page, and at a step past the live ones the
    # page of the step before (no DMA)
    assert list(got[0, 0]) == list(got[0, 1]) == [0, 1, 2, 3]
    assert (got[2, 1] == got[2, 0]).all() and (got[3] == got[2, 0]).all()


# jax.make_jaxpr of the span kernel with no window (impl 'pallas') as the
# commit before the window kernel traced it, sha256 of its text: a window
# is a kernel of its own, and without one the kernel is what it was
SPAN_JAXPR = {
    (3, 16, 4, 2, 64, 8, 16):
        "ed5c482778bbc488994f9c36e870154bea8675482a710601bf209bb6d08d8d24",
    (2, 8, 4, 4, 32, 6, 8):
        "1c88ccf84b6b0e4b685608c2fde7056a3a67240498a92b803ed9deee7e398a75",
}


@pytest.mark.parametrize("geometry", sorted(SPAN_JAXPR),
                         ids=lambda g: "x".join(map(str, g)))
def test_window_none_is_the_span_kernel_as_it_was(geometry):
    B, Sq, Hq, Hkv, D, P, S = geometry
    sds = lambda s, d: jax.ShapeDtypeStruct(s, jnp.dtype(d))
    pool = sds((2, B * P, S, Hkv * D), "float32")
    f = lambda q, kp, vp, t, l, c: pa.ragged_span_attention(
        q, kp, vp, t, l, q_counts=c, impl="pallas", layer=1,
        num_kv_heads=Hkv)
    text = str(jax.make_jaxpr(f)(
        sds((B, Sq, Hq, D), "float32"), pool, pool, sds((B, P), "int32"),
        sds((B,), "int32"), sds((B,), "int32")))
    assert hashlib.sha256(text.encode()).hexdigest() == SPAN_JAXPR[geometry]
    assert "window" not in text


def test_window_none_output_is_the_span_kernels_bit_for_bit():
    """Interpreted, window=None and no window argument give the same
    bytes; a window as wide as every position gives the same numbers."""
    B, Sq, Hq, Hkv, D, P, S = 3, 8, 4, 2, 64, 8, 8
    rng = np.random.default_rng(1)
    k, v = (jnp.asarray(rng.normal(size=(2, B * P, S, Hkv * D)),
                        jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    table = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths, counts = jnp.array([1, 40, 57]), jnp.array([8, 0, 8])
    call = lambda **w: pa.ragged_span_attention(
        q, k, v, table, lengths, q_counts=counts, impl="pallas",
        interpret=True, layer=1, num_kv_heads=Hkv, **w)
    plain = np.asarray(call())
    assert (np.asarray(call(window=None)) == plain).all()
    np.testing.assert_allclose(np.asarray(call(window=P * S)), plain,
                               atol=1e-6)


def test_the_pools_are_sized_by_kind():
    net, _, _ = _model()
    eng = ServingEngine(net, **ENGINE)
    state = eng._device_state()
    pages = ENGINE["num_slots"] * ENGINE["max_length"] // 8
    # the one full layer keeps whole-depth pages in the pool
    assert state["k"].shape == state["v"].shape == (1, pages, 8, 128)
    # the three window layers a ring of 5 pages a slot, K and V
    ring = (3, 2, 5, 8, 128)
    rec = {k: v.shape for k, v in state["rec"].items()}
    assert rec == {"ring_k": ring, "ring_v": ring, "moe": (3, 5),
                   "live_rows": (2,), "window_keys": (2,)}
    st = eng.stats
    assert st["kv_pool_bytes"] == 2 * pages * 8 * 128 * 4
    assert st["kv_ring_bytes"] == st["recurrent_state_bytes"] \
        == 2 * int(np.prod(ring)) * 4
    assert (st["kv_layers"], st["kv_window_layers"], st["kv_ring_pages"],
            st["recurrent_layers"]) == (1, 3, 5, 3)
    gauges = {m: eng._metrics[m].value
              for m in ("kv_window_layers", "kv_ring_pages")}
    assert gauges == {"kv_window_layers": 3, "kv_ring_pages": 5}
    # a page of the pool is the full layer's alone
    assert st["kv_page_bytes"] == 2 * 1 * 8 * 128 * 4


def test_a_ring_is_a_pool_behind_an_arithmetic_table():
    net, _, _ = _model()
    cache = net.make_cache(2, 64, page_size=8)
    ring = cache.ring()
    assert ring.k_pages.shape == (3, 2 * 5, 8, 128)
    assert ring.page_table.shape == (2, 8)
    assert list(np.asarray(ring.page_table[1])) == [5, 6, 7, 8, 9, 5, 6, 7]
    marked = ring._with_pages(ring.k_pages + 1.0, ring.v_pages - 1.0)
    back = cache.with_ring(marked)
    assert float(back.recurrent["ring_k"].min()) == 1.0
    assert float(back.recurrent["ring_v"].max()) == -1.0
    assert back.recurrent["ring_k"].shape == (3, 2, 5, 8, 128)


def test_a_dispatch_wider_than_the_ring_allows_is_refused():
    net, _, _ = _model()
    cache = net.make_cache(1, 64, page_size=8)
    cache.spans = jnp.array([9], jnp.int32)
    with pytest.raises(MXNetError, match="at most a page of 8 rows"):
        net.hidden(jnp.zeros((1, 9), jnp.int32), cache)


@pytest.mark.parametrize("knob, value", [
    ("prefix_cache", True), ("host_kv_bytes", 1 << 20),
    ("speculative", True), ("kv_dtype", "int8"), ("tp", 2),
    ("weight_dtype", "int8"), ("adapter_pool", object())])
def test_what_the_rings_and_blocks_do_not_carry_is_refused_by_name(
        knob, value):
    net, _, _ = _model()
    with pytest.raises(MXNetError, match=f"{knob} is not supported for "
                                         "TrinityForCausalLM: "):
        ServingEngine(net, **ENGINE, **{knob: value})


def test_the_window_keys_counter_is_a_count_made_by_hand():
    """Three slots in one dispatch of 8 rows, pages of 8, a window of 24:
    slot 0 feeds 8 rows from 0 (page 0: 1 visited of 1), slot 1 one row at
    position 30 (pages 0-3: its window reaches back to 7), slot 2 8 rows
    from 100 (its first query's window starts at 77, page 9; its last row
    is on page 13: 5 of 14). Three window layers."""
    net, _, _ = _model(layer_types=[S, F, S, S], dense_layers=4)
    cache = net.make_cache(3, 128, page_size=8)
    step = jax.jit(lambda ids, c: net.hidden(ids, c)[1])
    cache.length = jnp.array([0, 30, 100], jnp.int32)
    cache.spans = jnp.array([8, 1, 8], jnp.int32)
    cache = step(jnp.zeros((3, 8), jnp.int32), cache)
    visited, whole = (1 + 4 + 5), (1 + 4 + 14)
    assert list(np.asarray(cache.recurrent["window_keys"])) == \
        [3 * visited, 3 * whole]
    # an idle slot counts nothing
    cache.length = jnp.array([8, 31, 108], jnp.int32)
    cache.spans = jnp.array([0, 0, 0], jnp.int32)
    again = step(jnp.zeros((3, 8), jnp.int32), cache)
    assert list(np.asarray(again.recurrent["window_keys"])) == \
        [3 * visited, 3 * whole]


def test_the_engine_counts_window_keys_and_the_share_falls_with_length():
    net, _, _ = _model()
    eng = ServingEngine(net, **ENGINE)
    rng = np.random.default_rng(2)
    eng.serve([Request(rng.integers(0, 512, 100), 2) for _ in range(2)])
    visited, whole = eng.stats["model_counters"]["window_keys"]
    # 100-token prompts: the window's 4-5 pages against up to 13
    assert 0 < visited < 0.6 * whole


@pytest.mark.parametrize("name", sorted(ref.PERTURBATIONS))
def test_every_perturbation_exceeds_the_limit(name):
    """At the tiny size, on a 61-token sequence past the window: each
    wrong reference is further from the sound one than the cell's limit,
    a position's median root mean square."""
    _, kw, params = _model()
    ids = _ids(4, 1, 61)
    want = _reference(params, kw, ids)
    got = _reference(params, kw, ids, **ref.PERTURBATIONS[name])
    p50 = float(jnp.median(jnp.sqrt(jnp.mean((got - want) ** 2, -1))))
    assert p50 > ref.TOLERANCE["logit_rms_p50"], (name, p50)


def test_the_costs_count_the_window():
    kw = dict(models.trinity_mini_config().__dict__, dtype="bfloat16")
    # a 64-row chunk at context 10000: full layer 10001 .. 10064 keys a row
    full = ref.attention_cost(kw, [(10000, 64)])
    # (the published 32 layers: 8 full, 24 window)
    assert full["flops"] == 8 * 4 * 4096 * (64 * 10000 + 64 * 65 // 2)
    win = ref.window_attention_cost(kw, [(10000, 64)])
    assert win["flops"] == 24 * 4 * 4096 * 64 * 2048
    # pages 124 (first key 7953) to 157 (position 10063): 34 pages
    assert win["bytes"] == 24 * (2 * 34 * 64 * 512 + 2 * 64 * 4096) * 2
    assert ref.flops_per_item(kw, 10000) - ref.flops_per_item(kw, 0) == \
        4 * 4096 * (8 * 10000 + 24 * 2048)
