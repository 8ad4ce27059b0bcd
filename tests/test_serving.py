"""Continuous-batching serving engine + ragged paged-attention tests.

The oracle for every decode-path test is the reference's way: a full
uncached causal forward over the whole prefix (the torch-oracle
discipline — dtype-aware tolerances, CPU interpret-mode kernels).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM, PagedKVCache
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import Request, ServingEngine, SlotScheduler


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


def _greedy_full(net, prompt, n_new):
    """Full-recompute greedy decode (the reference oracle)."""
    ids = np.asarray(prompt, np.int32)[None]
    out = []
    for _ in range(n_new):
        logits = net(mx.nd.array(ids, dtype="int32"))
        nxt = int(logits.asnumpy()[0, -1].argmax())
        out.append(nxt)
        ids = np.concatenate([ids, [[nxt]]], axis=1)
    return out


# ---------------------------------------------------------------------------
# ragged paged-attention kernel
# ---------------------------------------------------------------------------

def _pool(B=3, H=2, D=16, S=8, P=4, dtype=jnp.float32, seed=0):
    """One-layer pools, packed as PagedKVCache stores them:
    (1, N, S, H*D), heads-major in the last axis."""
    rng = np.random.default_rng(seed)
    N = B * P
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((N, S, H, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((N, S, H, D)), dtype)
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    return q, kp.reshape(1, N, S, H * D), vp.reshape(1, N, S, H * D), table


@pytest.mark.parametrize("lengths", [[5, 17, 32], [0, 1, 8],
                                     [32, 32, 32], [0, 0, 0]])
def test_ragged_kernel_matches_dense_reference(lengths):
    q, kp, vp, table = _pool()
    L = jnp.asarray(lengths, jnp.int32)
    ref = pa._ragged_reference(q, kp, vp, table, L, 1.0 / np.sqrt(16))
    out = pa.ragged_decode_attention(q, kp, vp, table, L, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_kernel_under_jit_and_scan():
    """The engine calls the kernel inside jit(lax.scan(...)) — the
    scalar-prefetch grid must trace there too."""
    q, kp, vp, table = _pool()
    L = jnp.asarray([3, 9, 25], jnp.int32)

    def step(carry, _):
        out = pa.ragged_decode_attention(q, kp, vp, table, carry,
                                         interpret=True)
        return carry + 1, out

    _, outs = jax.jit(lambda l: jax.lax.scan(step, l, None, length=2))(L)
    for i in range(2):
        ref = pa._ragged_reference(q, kp, vp, table, L + i,
                                   1.0 / np.sqrt(16))
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ragged_kernel_bf16_tolerance():
    q, kp, vp, table = _pool(dtype=jnp.bfloat16)
    L = jnp.asarray([7, 20, 13], jnp.int32)
    ref = pa._ragged_reference(q.astype(jnp.float32),
                               kp.astype(jnp.float32),
                               vp.astype(jnp.float32), table, L,
                               1.0 / np.sqrt(16))
    out = pa.ragged_decode_attention(q, kp, vp, table, L, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_ragged_supported_gating():
    q, kp, _, _ = _pool(H=2, D=64, S=8)   # H*D = 128
    assert pa.ragged_supported(q, kp)
    q2, kp2, _, _ = _pool(H=2, D=16, S=8)  # H*D = 32: lane rule fails
    assert not pa.ragged_supported(q2, kp2)
    q3, kp3, _, _ = _pool(H=2, D=64, S=4)  # sublane rule fails
    assert not pa.ragged_supported(q3, kp3)
    assert not pa.ragged_supported(q.astype(jnp.int32), kp)


# ---------------------------------------------------------------------------
# ragged cache semantics
# ---------------------------------------------------------------------------

def test_write_decode_lands_at_per_slot_offsets():
    B, H, D, S = 3, 1, 2, 4
    lengths = jnp.asarray([0, 5, 9], jnp.int32)
    cache = PagedKVCache.create(1, B, H, 12, D, page_size=S,
                                lengths=lengths)
    val = jnp.arange(B, dtype=jnp.float32).reshape(B, 1, 1, 1) + 1.0
    val = jnp.broadcast_to(val, (B, H, 1, D))
    cache = cache.write_decode(0, val, 2 * val)
    pool = np.asarray(cache.k_pages)[0]       # (num_pages, S, H*D)
    table = np.asarray(cache.page_table)
    for b, length in enumerate([0, 5, 9]):
        page, slot = divmod(length, S)
        assert pool[table[b, page], slot, 0] == b + 1.0
    # nothing else was touched
    assert (pool != 0).sum() == B * D


def test_write_decode_full_slot_drops_instead_of_clobbering():
    B, H, D, S = 2, 1, 2, 4
    cache = PagedKVCache.create(1, B, H, 8, D, page_size=S,
                                lengths=jnp.asarray([8, 3], jnp.int32))
    live = jnp.ones((1, cache.k_pages.shape[1], S, H * D))
    cache = PagedKVCache(live, live, cache.page_table, cache.length)
    val = jnp.full((B, H, 1, D), 7.0)
    cache = cache.write_decode(0, val, val)
    pool = np.asarray(cache.k_pages)[0]
    table = np.asarray(cache.page_table)
    # slot 0 is at capacity: every one of ITS pages still holds 1.0
    assert (pool[table[0]] == 1.0).all()
    # slot 1 wrote at position 3
    assert pool[table[1, 0], 3, 0] == 7.0


def test_ragged_key_mask_per_slot():
    cache = PagedKVCache.create(1, 2, 1, 8, 2, page_size=4,
                                lengths=jnp.asarray([2, 5], jnp.int32))
    assert cache.ragged
    m = np.asarray(cache.key_mask(extra=1))
    assert m.shape == (2, 8)
    np.testing.assert_array_equal(m[0], [1, 1, 1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(m[1], [1, 1, 1, 1, 1, 1, 0, 0])


# ---------------------------------------------------------------------------
# ragged decode parity through the model (the acceptance-criteria test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_ragged_decode_logits_match_full_forward(attn_impl):
    """Mixed per-slot lengths: one ragged paged decode step must produce
    the SAME next-token logits as a full uncached forward of each slot's
    prefix — the kernel in interpret mode on CPU, dtype-aware f32
    tolerances."""
    net, cfg = _tiny()
    rng = np.random.default_rng(0)
    S, P = 8, 4
    prefixes = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                for n in (3, 13, 26)]          # mixed lengths, mid-page
    B = len(prefixes)
    cache = net.make_cache(B, S * P, paged=True, page_size=S,
                           lengths=np.zeros(B, np.int32),
                           attn_impl=attn_impl)
    # prefill each slot individually through the batch-1 dense path
    # (exactly what ServingEngine._admit compiles)
    kp, vp = cache.k_pages, cache.v_pages
    for b, ids in enumerate(prefixes):
        row = cache.page_table[b][None]
        c1 = PagedKVCache(kp, vp, row, jnp.zeros((), jnp.int32))
        _, c1 = net(mx.nd.array(ids[None, :-1], dtype="int32"), c1)
        kp, vp = c1.k_pages, c1.v_pages
    lengths = jnp.asarray([len(p) - 1 for p in prefixes], jnp.int32)
    ragged = PagedKVCache(kp, vp, cache.page_table, lengths,
                          attn_impl=attn_impl)
    # one ragged decode step: each slot feeds its own last token
    last = np.stack([p[-1] for p in prefixes])[:, None]
    logits, _ = net(mx.nd.array(last, dtype="int32"), ragged)
    got = logits.asnumpy()[:, 0, :]
    for b, ids in enumerate(prefixes):
        full = net(mx.nd.array(ids[None], dtype="int32")).asnumpy()
        np.testing.assert_allclose(got[b], full[0, -1], rtol=2e-4,
                                   atol=2e-5, err_msg=f"slot {b}")


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_unified_dispatch_writes_packed_heads_major_rows(kv_dtype):
    """After the engine's unified dispatches the pool row of (layer,
    page, slot) holds that token's K (and V) for ALL heads, packed
    heads-major (column h*D + d): compared with the (H, D) rows the
    dense forward writes into a contiguous KVCache. Float pages equal
    them. An int8 row holds each head's codes: round(x / s) under the
    scale s that head's page had reached at that token (layer 0, where
    no quantized attention lies upstream: within one code), and in every
    layer a head's D codes point where its D values point. A D-major or
    head-swapped row fails each of these."""
    net, cfg = _tiny(heads=4)
    H, D, S = cfg.num_heads, cfg.units // cfg.num_heads, 8
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 21).tolist()
    dense = net.make_cache(1, 64)
    _, dense = net(mx.nd.array(np.asarray(prompt, np.int32)[None],
                               dtype="int32"), dense)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=S,
                        chunk_tokens=8, attn_impl="xla",
                        kv_dtype=kv_dtype)
    eng.submit(Request(prompt, 2))
    while eng._pending_tokens() or not eng._mapped.any():
        eng.step()
    slot = int(np.flatnonzero(eng._mapped)[0])
    table = eng._table_host[slot]
    assert eng._kp.shape == (cfg.num_layers, 2 * 64 // S, S, H * D)
    for pool, want in ((eng._kp, dense.k), (eng._vp, dense.v)):
        pool, want = np.asarray(pool), np.asarray(want)  # (L,1,H,T,D)
        for layer in range(cfg.num_layers):
            for t in range(len(prompt)):
                row = pool[layer, table[t // S], t % S].reshape(H, D)
                ref = want[layer, 0, :, t, :]
                if kv_dtype is None:
                    np.testing.assert_allclose(row, ref, rtol=1e-5,
                                               atol=1e-6)
                    continue
                cos = (row * ref).sum(-1) / (
                    np.linalg.norm(row, axis=-1)
                    * np.linalg.norm(ref, axis=-1))
                assert (cos > 0.9).all(), (layer, t, cos)
                if layer == 0:
                    # the monotone scale: the running absmax / 127 over
                    # this page's tokens up to t
                    t0 = t - t % S
                    run = np.abs(want[0, 0, :, t0:t + 1]).max(axis=(1, 2))
                    code = np.round(ref / (run[:, None] / 127.0))
                    assert np.abs(row - code).max() <= 1, (t, row, code)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_engine_greedy_stream_equals_full_forward(attn_impl):
    """The engine's greedy stream over the packed pool, through the dense
    reference and through the span kernel (interpret mode) with its
    layer in the BlockSpec, is the full-forward greedy stream."""
    net, cfg = _tiny()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (3, 11)]
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        chunk_tokens=8, attn_impl=attn_impl)
    assert eng.generate(prompts, 4) == [_greedy_full(net, p, 4)
                                        for p in prompts]


@pytest.mark.slow
def test_engine_greedy_matches_full_recompute():
    net, cfg = _tiny()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (3, 9, 17, 5)]
    want = [_greedy_full(net, p, 8) for p in prompts]
    # fewer slots than requests → slots recycle mid-run; block of 3 →
    # admissions happen between decode dispatches. xla attention: the
    # interpret-mode kernel has its own parity test above, and the slow
    # lane's Poisson soak runs the engine on pallas_interpret
    eng = ServingEngine(net, num_slots=3, max_length=64, page_size=8,
                        attn_impl="xla")
    got = eng.generate(prompts, 8)
    assert got == want
    assert eng.stats["requests_finished"] == 4


def test_engine_eos_and_budget_free_slots_early():
    net, cfg = _tiny()
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, cfg.vocab_size, 4).tolist()
    free_run = _greedy_full(net, p0, 8)
    eos = free_run[2]          # force an early stop on the 3rd token
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla")
    r_eos = Request(p0, 8, eos_token_id=eos)
    r_long = Request(rng.integers(0, cfg.vocab_size, 6).tolist(), 8)
    done = eng.serve([r_eos, r_long])
    assert len(done) == 2
    # eos is emitted, then the request stops — nothing after it
    assert r_eos.output_tokens == free_run[:3]
    assert len(r_long.output_tokens) == 8
    # the freed slot went back to the pool
    assert eng.scheduler.num_free == 2


def test_engine_sampled_reproducible_across_admission_order():
    """The per-request RNG stream depends only on (seed, token index):
    shuffled submission order and a different slot count must emit
    bit-identical tokens per request."""
    net, cfg = _tiny()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (3, 7, 11, 5)]

    def run(order, slots):
        eng = ServingEngine(net, num_slots=slots, max_length=64,
                            page_size=8, attn_impl="xla")
        reqs = [Request(prompts[i], 6, do_sample=True, temperature=0.8,
                        top_k=20, top_p=0.95, seed=100 + i,
                        request_id=i) for i in order]
        eng.serve(reqs)
        return {r.id: r.output_tokens for r in reqs}

    a = run([0, 1, 2, 3], 2)
    b = run([3, 1, 0, 2], 4)
    assert a == b


def test_engine_mixed_sampling_modes_one_program():
    """Greedy and sampled requests share one compiled decode program
    (per-slot knobs are arrays, not compile-time constants)."""
    net, cfg = _tiny()
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, 5).tolist()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla")
    greedy = Request(p, 6, request_id="g")
    sampled = Request(p, 6, do_sample=True, temperature=0.7, top_k=10,
                      seed=9, request_id="s")
    eng.serve([greedy, sampled])
    assert greedy.output_tokens == _greedy_full(net, p, 6)
    assert len(sampled.output_tokens) == 6
    assert all(0 <= t < cfg.vocab_size for t in sampled.output_tokens)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_free_admit_release():
    s = SlotScheduler(2)
    r = [Request([1], 4, request_id=i) for i in range(4)]
    for x in r:
        s.submit(x)
    admitted = s.admit()
    assert [(sl, rq.id) for sl, rq in admitted] == [(0, 0), (1, 1)]
    assert s.num_free == 0 and s.num_queued == 2
    assert s.admit() == []                     # no free slots
    assert s.release(0).id == 0
    assert [(sl, rq.id) for sl, rq in s.admit()] == [(0, 2)]
    with pytest.raises(mx.MXNetError):
        s.release(1 + 1)                       # never-admitted slot


def test_scheduler_fifo_no_starvation():
    """A steady stream of later arrivals can never starve the oldest
    queued request: admission is strict FIFO."""
    s = SlotScheduler(1)
    first = Request([1], 4, request_id="first")
    s.submit(first)
    (slot0, got), = s.admit()
    assert got.id == "first"
    s.submit(Request([1], 4, request_id="late-0"))
    order = []
    for i in range(5):
        s.submit(Request([1], 4, request_id=f"late-{i + 1}"))
        s.release(slot0)
        (slot0, nxt), = s.admit()
        order.append(nxt.id)
    assert order == [f"late-{i}" for i in range(5)]


def test_scheduler_drain():
    s = SlotScheduler(2)
    for i in range(3):
        s.submit(Request([1], 4, request_id=i))
    assert s.has_work
    s.admit()
    s.release(0)
    s.release(1)
    s.admit()
    assert s.num_queued == 0 and s.num_active == 1
    s.release(0)
    assert not s.has_work                      # fully drained


def test_engine_drains_more_requests_than_slots():
    net, cfg = _tiny()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 1 + (i % 4)).tolist()
               for i in range(7)]
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla")
    outs = eng.generate(prompts, 1 + 3)
    assert len(outs) == 7
    assert all(len(o) == 4 for o in outs)
    assert not eng.has_work
    assert eng.scheduler.num_free == 2


def test_engine_rejects_oversized_prompt():
    net, _ = _tiny()
    eng = ServingEngine(net, num_slots=1, max_length=16, page_size=8,
                        attn_impl="xla")
    with pytest.raises(mx.MXNetError):
        eng.submit(Request(list(range(17)), 4))


@pytest.mark.parametrize("name", ["decode_block", "prefill_bucket",
                                  "spec_max_ngram", "spec_min_ngram"])
def test_engine_refuses_retired_arguments(name):
    """The bucketed engine's two tuning knobs and the proposer's n-gram
    bounds are gone from the constructor: a config that still names one
    fails loudly instead of being silently ignored."""
    net, _ = _tiny()
    with pytest.raises(TypeError, match=name):
        ServingEngine(net, num_slots=1, max_length=16, page_size=8,
                      attn_impl="xla", **{name: 2})


def test_engine_respects_capacity_budget():
    """A request whose budget exceeds the slot's remaining KV capacity
    is truncated to what fits instead of writing out of bounds."""
    net, cfg = _tiny()
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab_size, 12).tolist()
    eng = ServingEngine(net, num_slots=1, max_length=16, page_size=8,
                        attn_impl="xla")
    (req,) = eng.serve([Request(p, 50)])
    # 12 prompt tokens, 16-slot capacity: 4 writes + the final sampled
    # token = 5 generated
    assert len(req.output_tokens) == 5
    assert req.output_tokens == _greedy_full(net, p, 5)


# ---------------------------------------------------------------------------
# bounded trace caches (LRU satellite)
# ---------------------------------------------------------------------------

def test_hybrid_jit_cache_is_bounded_and_counts_retraces():
    from mxnet_tpu.gluon import nn

    net = nn.Dense(4, flatten=False, in_units=3)
    net.initialize()
    net.hybridize()
    net._jit_cache.maxsize = 4
    mx.runtime.reset_jit_cache_stats()
    for t in range(1, 8):                      # 7 shapes through a 4-cache
        net(mx.nd.array(np.zeros((2, t, 3), np.float32)))
    stats = mx.runtime.jit_cache_stats()
    assert len(net._jit_cache) == 4
    assert stats["retraces"] >= 7
    assert stats["evictions"] >= 3
    before = mx.runtime.jit_cache_stats()["retraces"]
    net(mx.nd.array(np.zeros((2, 7, 3), np.float32)))   # cached: no trace
    assert mx.runtime.jit_cache_stats()["retraces"] == before


def test_generate_cache_is_bounded():
    net, cfg = _tiny()
    import os
    os.environ["MXNET_TPU_GENERATE_CACHE_SIZE"] = "2"
    try:
        prompt = np.zeros((1, 3), np.int32)
        for n in (1, 2, 3):
            net.generate(mx.nd.array(prompt, dtype="int32"), n)
        assert len(net._generate_cache) == 2
    finally:
        del os.environ["MXNET_TPU_GENERATE_CACHE_SIZE"]


def test_program_registry_is_flat():
    """The unified dispatch kills the prefill bucket axis: arbitrary
    prompt lengths — including lengths never seen in warmup — compile
    NOTHING new. At most two programs exist per engine lifetime
    (greedy-only and mixed-sampling flavors)."""
    net, cfg = _tiny()
    eng = ServingEngine(net, num_slots=1, max_length=64, page_size=8,
                        attn_impl="xla")
    rng = np.random.default_rng(7)
    for n in (3, 11, 19, 27):       # four different prompt lengths...
        eng.serve([Request(rng.integers(0, cfg.vocab_size, n).tolist(),
                           2)])
    assert len(eng._programs) == 1  # ...ONE greedy program serves all
    eng.serve([Request([1, 2, 3], 2, do_sample=True, seed=0)])
    assert len(eng._programs) == 2  # plus the mixed-sampling flavor
    eng.mark_warm()
    from mxnet_tpu.telemetry import cost as _cost
    before = {fn.program: _cost.get(fn.program)["compiles"]
              for fn in eng._programs.values()}
    for n in (5, 23, 31):           # lengths the engine has NEVER seen
        eng.serve([Request(rng.integers(0, cfg.vocab_size, n).tolist(),
                           2)])
    assert len(eng._programs) == 2
    after = {fn.program: _cost.get(fn.program)["compiles"]
             for fn in eng._programs.values()}
    assert after == before          # steady state: zero new compiles


# ---------------------------------------------------------------------------
# the head over the rows that are sampled, and no others (ISSUE 31)
# ---------------------------------------------------------------------------

def _tiny_falcon():
    from benchmarks.weights import seed_weights
    from mxnet_tpu import models
    cfg = models.falcon_h1_34b_config(
        vocab_size=512, units=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, hidden_size=256, ssm_heads=4,
        ssm_head_dim=32, ssm_state=16, ssm_groups=2, conv_kernel=4,
        chunk_size=16, max_length=256, dtype="float32")
    net = models.FalconH1ForCausalLM(cfg)
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, 3, cfg.dtype, std=0.02)
    return net, cfg


# (model, engine arguments, R: the rows a slot puts through the head)
_HEAD_CASES = {
    "gpt2": (_tiny, dict(page_size=8), 1),
    "gpt2-speculative": (_tiny, dict(page_size=8, speculative=True,
                                     spec_tokens=4), 4),
    "falcon_h1": (_tiny_falcon, dict(page_size=16), 1),
}


def _head_engine(case):
    make, engine_kw, rows = _HEAD_CASES[case]
    net, cfg = make()
    eng = ServingEngine(net, num_slots=3, max_length=64, attn_impl="xla",
                        **engine_kw)
    return eng, cfg, rows


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _shapes(jaxpr):
    """The shape of every array a jaxpr computes, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in _sub_jaxprs(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("case", list(_HEAD_CASES))
def test_unified_program_holds_no_logits_of_unsampled_rows(case):
    """Walk the jaxpr of the unified program the engine built: the widest
    array that ends in the vocabulary has slots x R rows (R = 1, or
    spec_tokens with speculation), never slots x W. The head's weight,
    transposed to (units, vocab) for the matmul, is the one other array
    that ends so."""
    eng, cfg, rows = _head_engine(case)
    jaxprs = []
    build = eng._build_unified

    class Traced:
        """Stands where the jitted program stood, for CostedFunction's
        one `lower(*args)`, and keeps the jaxpr it lowers."""
        def __init__(self, fn):
            self.fn = fn

        def lower(self, *args):
            traced = self.fn.trace(*args)
            jaxprs.append(traced.jaxpr)
            return traced.lower()

    eng._build_unified = lambda greedy_only=False: Traced(
        build(greedy_only))
    eng.serve([Request([1, 2, 3, 4, 5], 3)])
    (jaxpr,) = jaxprs
    ending = [sh for sh in _shapes(jaxpr.jaxpr)
              if sh and sh[-1] == cfg.vocab_size
              and sh != (cfg.units, cfg.vocab_size)]
    widest = max(ending, key=lambda sh: int(np.prod(sh[:-1])))
    assert widest == (3, rows, cfg.vocab_size)
    assert eng._width > rows


@pytest.mark.parametrize("case", list(_HEAD_CASES))
def test_head_rows_counts_slots_times_r_a_dispatch(case):
    eng, cfg, rows = _head_engine(case)
    rng = np.random.default_rng(4)
    eng.serve([Request(rng.integers(0, cfg.vocab_size, n).tolist(), 5)
               for n in (20, 3, 9, 17)])
    st = eng.stats
    assert st["decode_dispatches"] > 5
    assert st["head_rows"] == st["decode_dispatches"] * 3 * rows
    assert (f'serving_head_rows_total{{engine="{eng._eid}"}} '
            f'{st["head_rows"]}') in mx.telemetry.render_prometheus()


def test_gpt2_forward_is_head_of_hidden_and_rows_commute():
    """`forward` is `head(hidden(...))` bit for bit, with and without a
    cache, and the head of picked rows is the picked rows of the whole
    head (the matmul may accumulate in another order: float tolerance)."""
    net, cfg = _tiny()
    ids = mx.nd.array(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (3, 11)), dtype="int32")
    h, none = net.hidden(ids)
    assert none is None
    whole = net.head(h).asnumpy()
    assert np.array_equal(whole, net.forward(ids).asnumpy())
    logits, _ = net.forward(ids, net.make_cache(3, 32))
    hc, cache = net.hidden(ids, net.make_cache(3, 32))
    assert int(cache.length) == 11
    assert np.array_equal(net.head(hc).asnumpy(), logits.asnumpy())
    rows = jnp.asarray([[10], [0], [4]])
    picked = net.head(mx.nd.NDArray(jnp.take_along_axis(
        h._data, rows[:, :, None], axis=1))).asnumpy()
    np.testing.assert_allclose(
        picked, np.take_along_axis(whole, np.asarray(rows)[:, :, None], 1),
        rtol=1e-5, atol=1e-6)


def test_engine_refuses_a_model_without_hidden_and_head():
    net, _ = _tiny()

    class ForwardOnly:
        config = net.config
        forward = net.forward
        collect_params = net.collect_params

    with pytest.raises(mx.MXNetError, match="ForwardOnly has no `hidden`"):
        ServingEngine(ForwardOnly(), num_slots=1, max_length=32,
                      page_size=8, attn_impl="xla")


def _mid_prefill(eng, req):
    """Step `eng` until `req` has fed one chunk of its prompt and its next
    chunk is not its last; returns its slot."""
    for _ in range(20):
        eng.step()
        slot = next((s for s in eng.scheduler.active_slots
                     if eng.scheduler.request_at(s) is req), None)
        if slot is not None and eng._lengths[slot] > 0:
            assert eng._pending[slot].size > eng.chunk_tokens
            return slot
    raise AssertionError("the request never began its prefill")


def _record_bad_slots(eng):
    flagged = []
    on_bad = eng._on_bad_slots

    def spy(bad, msg):
        flagged.append([eng.scheduler.request_at(s).id for s in bad])
        return on_bad(bad, msg)

    eng._on_bad_slots = spy
    return flagged


@pytest.mark.parametrize("pool", ["_kp", "_vp"])
def test_finite_guard_reaches_rows_nobody_samples(pool):
    """A NaN in a K/V page that only rows nobody samples read, the rows of
    a NON-final prefill chunk, still flags that slot, and no other: the
    guard reads the final hidden states of every live row. The flagged
    request re-prefills and every stream is what it is without the fault."""
    net, cfg = _tiny()

    def requests():
        r = np.random.default_rng(11)
        return [Request(r.integers(1, cfg.vocab_size, n).tolist(), 6,
                        request_id=f"r{i}")
                for i, n in enumerate((5, 30, 4))]

    def engine():
        return ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                             attn_impl="xla", max_retries=4,
                             retry_backoff_s=0.0)

    want = {r.id: r.output_tokens for r in engine().serve(requests())}
    eng = engine()
    flagged = _record_bad_slots(eng)
    reqs = requests()
    for r in reqs:
        eng.submit(r)
    slot = _mid_prefill(eng, reqs[1])
    page = int(eng._table_host[slot][0])
    arr = getattr(eng, pool)
    setattr(eng, pool, arr.at[:, page].set(jnp.asarray(np.nan, arr.dtype)))
    done = []
    while eng.has_work:
        done.extend(eng.step())
    assert flagged == [["r1"]]
    assert {r.id: r.output_tokens for r in done} == want
    assert eng.stats["requests_failed"] == 0
    assert eng.audit_pages() == []


@pytest.mark.parametrize("row,flags", [(1, True), (6, False)])
def test_finite_guard_reads_live_hidden_rows_only(row, flags):
    """The guard's own reach, apart from what attention carries: a
    non-finite final hidden state in a live row that is NOT the one the
    head sees flags its slot; in a dead row it flags nothing."""
    net, cfg = _tiny()
    hidden = net.hidden

    def poisoned(inputs, cache=None):
        h, new = hidden(inputs, cache)
        # slot 0, position `row`, in dispatches where slot 0 feeds
        # exactly 5 rows (its one prompt chunk): row 1 is live and not
        # the last, row 6 is dead
        hit = (cache.spans[0] == 5) & (jnp.arange(h.shape[0]) == 0)
        bad = hit[:, None, None] \
            & (jnp.arange(h.shape[1]) == row)[None, :, None]
        return mx.nd.NDArray(jnp.where(bad, jnp.nan, h._data)), new

    net.hidden = poisoned
    try:
        eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                            attn_impl="xla", max_retries=2,
                            retry_backoff_s=0.0)
        flagged = _record_bad_slots(eng)
        done = eng.serve([Request([5, 4, 3, 2, 1], 4, request_id="a"),
                          Request([7, 8, 9], 4, request_id="b")])
    finally:
        del net.hidden
    by_id = {r.id: r for r in done}
    assert by_id["b"].status == "finished"
    assert by_id["b"].output_tokens == _greedy_full(net, [7, 8, 9], 4)
    if flags:
        assert flagged and all(f == ["a"] for f in flagged)
        assert by_id["a"].status == "failed"
    else:
        assert flagged == []
        assert by_id["a"].output_tokens == _greedy_full(
            net, [5, 4, 3, 2, 1], 4)


# ---------------------------------------------------------------------------
# long soak (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_soak_poisson_arrivals():
    """Longer mixed-traffic soak: staggered arrivals, mixed lengths and
    sampling modes, every greedy request checked against the oracle."""
    net, cfg = _tiny()
    rng = np.random.default_rng(8)
    eng = ServingEngine(net, num_slots=4, max_length=64, page_size=8,
                        attn_impl="pallas_interpret")
    reqs = []
    for i in range(12):
        n = int(rng.integers(1, 30))
        sample = bool(i % 3 == 0)
        reqs.append(Request(rng.integers(0, cfg.vocab_size, n).tolist(),
                            int(rng.integers(1, 12)), do_sample=sample,
                            temperature=0.9, top_k=25, seed=i,
                            request_id=i))
    # staggered submission: a third up front, the rest trickle in while
    # the engine is mid-decode (admission between compiled dispatches)
    pending = list(reqs)
    for r in pending[:4]:
        eng.submit(r)
    trickle = pending[4:]
    done = []
    while eng.has_work or trickle:
        if trickle:
            eng.submit(trickle.pop(0))
        done.extend(eng.step())
    assert len(done) == 12
    for r in reqs:
        cap = min(r.max_new_tokens, eng.max_length - r.prompt_len + 1)
        assert len(r.output_tokens) == cap
        if not r.do_sample:
            assert r.output_tokens == _greedy_full(net, r.prompt, cap)
