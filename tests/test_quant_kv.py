"""ISSUE 14: int8 KV-cache pages with fused in-kernel dequant.

Three layers of oracle. The quantized span kernel (int8 pages +
per-(page, head) f32 scales on the scalar-prefetch lane) is checked
against the dense XLA reference over the same mixed batches the fp
kernel is — decode, verify, prefill-chunk, and idle rows riding ONE
dispatch. The cache-level quantizer is checked for its load-bearing
invariants: scales are MONOTONE (a written code is never re-rounded)
and codes are a pure function of the token stream, independent of the
prefill chunking. Deep-layer VALUES are not chunk-independent, though
— a mid-chunk row reads page scales that already reflect the whole
chunk — so restart continuation and migration re-prefill REPLAY the
recorded write schedule (Request.kv_history) to stay bit-identical.
The engine is checked end-to-end: co-scheduling independence on a
fixed chunk grid, restart replay under injected faults, a greedy
tolerance oracle vs the fp32 engine, a sampled frequency test,
compile-flat steady state, prefix-cache CoW with scale copy,
speculative verify, the quantized adapter slab vs the merged-weight
dense oracle, byte-denominated pool sizing, and the router
kill-mid-decode migration keeping quantized outputs identical to a
fault-free quantized run.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM, PagedKVCache
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import (FaultPlan, ReplicaFaultPlan, Request,
                               ServingEngine, ServingRouter)
from mxnet_tpu.serving.adapters import AdapterPool, merged_weights, \
    random_lora
from mxnet_tpu.serving.page_pool import PagePool
from mxnet_tpu.telemetry import cost as _cost

_NET = {}


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64, seed=3):
    key = (vocab, layers, units, heads, max_len, seed)
    if key not in _NET:
        cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                         num_heads=heads, max_length=max_len, dropout=0.0,
                         attention_dropout=0.0)
        net = GPT2ForCausalLM(cfg)
        mx.rng.seed(seed)
        net.initialize(mx.init.Normal(0.05))
        _NET[key] = (net, cfg)
    return _NET[key]


def _prompts(n=6, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 97, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _serve(net, prompts, max_new=8, sampled=False, ids=None, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_length", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    eng = ServingEngine(net, **kw)
    skw = dict(do_sample=True, temperature=0.8, top_k=20,
               top_p=0.95) if sampled else {}
    ids = list(range(len(prompts))) if ids is None else list(ids)
    reqs = [Request(p, max_new, request_id=ids[i], seed=100 + ids[i],
                    **skw)
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    return {r.id: list(r.output_tokens) for r in reqs}, eng


# ---------------------------------------------------------------------------
# quantized span kernel vs the dense oracle
# ---------------------------------------------------------------------------

def _quant_pool(B=5, H=2, D=16, S=8, P=4, Sq=8, qdtype=jnp.float32,
                seed=0, layers=1):
    """int8 page pools with realistic per-(page, head) scales: codes
    are real quantizations of gaussian slabs, so dequantized values
    exercise the fused epilogue with non-degenerate magnitudes. Packed
    as PagedKVCache stores them: codes (layers, N, S, H*D) heads-major,
    scales (layers, N, H)."""
    rng = np.random.default_rng(seed)
    N = B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), qdtype)
    k = rng.standard_normal((layers, N, S, H, D))
    v = rng.standard_normal((layers, N, S, H, D))
    ks = np.abs(k).max(axis=(2, 4)) / 127.0            # (layers, N, H)
    vs = np.abs(v).max(axis=(2, 4)) / 127.0
    kq = np.clip(np.round(k / ks[:, :, None, :, None]), -127, 127)
    vq = np.clip(np.round(v / vs[:, :, None, :, None]), -127, 127)
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    return (q, jnp.asarray(kq.reshape(layers, N, S, H * D), jnp.int8),
            jnp.asarray(vq.reshape(layers, N, S, H * D), jnp.int8),
            table, jnp.asarray(ks, jnp.float32),
            jnp.asarray(vs, jnp.float32))


def test_quant_span_kernel_mixed_batch_one_dispatch():
    """The serving dispatch shape: decode (1), verify (4), full chunk
    (8), idle (0) and a ragged tail (5) in ONE quantized dispatch —
    fused-dequant kernel vs the dense dequant oracle, dead rows exact
    zeros."""
    q, kq, vq, table, ks, vs = _quant_pool()
    L = jnp.asarray([9, 17, 1, 30, 12], jnp.int32)
    qc = jnp.asarray([1, 4, 8, 0, 5], jnp.int32)
    ref = pa._ragged_span_reference(q, kq, vq, table, L, qc,
                                    1.0 / np.sqrt(16),
                                    k_scale=ks, v_scale=vs)
    out = pa.ragged_span_attention(q, kq, vq, table, L, q_counts=qc,
                                   interpret=True, k_scale=ks,
                                   v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dead = np.arange(8)[None, :] >= np.asarray(qc)[:, None]
    assert (np.asarray(out)[dead] == 0).all()


def test_quant_span_kernel_bf16_query():
    q, kq, vq, table, ks, vs = _quant_pool(qdtype=jnp.bfloat16, seed=1)
    L = jnp.asarray([5, 1, 24, 13, 8], jnp.int32)
    qc = jnp.asarray([3, 7, 2, 6, 1], jnp.int32)
    ref = pa._ragged_span_reference(q, kq, vq, table, L, qc,
                                    1.0 / np.sqrt(16),
                                    k_scale=ks, v_scale=vs)
    out = pa.ragged_span_attention(q, kq, vq, table, L, q_counts=qc,
                                   interpret=True, k_scale=ks,
                                   v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_quant_span_kernel_sq1_matches_mq_reference():
    """Sq=1 through the quantized span path equals the single-query
    dequant math — quantized decode rides the span kernel, so this IS
    the decode correctness check."""
    q, kq, vq, table, ks, vs = _quant_pool(Sq=1, seed=2)
    L = jnp.asarray([4, 11, 27, 2, 19], jnp.int32)
    ref = pa._ragged_mq_reference(q, kq, vq, table, L, 1.0 / np.sqrt(16),
                                  k_scale=ks, v_scale=vs)
    out = pa.ragged_span_attention(q, kq, vq, table, L, interpret=True,
                                   k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq", [1, 64])
def test_quant_span_kernel_reads_its_layer_of_the_packed_pool(sq):
    """int8 pools and their scale leaves go in whole, `layer` picks the
    page blocks (BlockSpec) and the prefetched scales (k_scale[layer]):
    kernel vs the dense dequant oracle on that layer, Sq=1 and Sq=64."""
    q, kq, vq, table, ks, vs = _quant_pool(S=64, Sq=sq, seed=3, layers=3)
    L = jnp.asarray([9, 70, 1, 256 - sq + 1, 130], jnp.int32)
    qc = jnp.asarray([sq, max(sq // 2, 1), sq, sq, 0], jnp.int32)
    ref = pa._ragged_span_reference(q, kq, vq, table, L, qc,
                                    1.0 / np.sqrt(16), k_scale=ks,
                                    v_scale=vs, layer=1)
    out = pa.ragged_span_attention(q, kq, vq, table, L, q_counts=qc,
                                   impl="pallas", interpret=True,
                                   k_scale=ks, v_scale=vs, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    other = pa.ragged_span_attention(q, kq, vq, table, L, q_counts=qc,
                                     impl="pallas", interpret=True,
                                     k_scale=ks, v_scale=vs, layer=2)
    assert not np.array_equal(np.asarray(out), np.asarray(other))


def test_ragged_supported_int8_min_tile_gate():
    """Real-TPU support gate: int8 page blocks need the (32, 128) min
    tile, so S % 32 pools must fall back to XLA on hardware. The same
    shapes at fp32 (S % 8 only) stay supported."""
    H, D, S = 2, 64, 8
    q = jnp.zeros((3, H, D), jnp.float32)
    assert pa.ragged_supported(q, jnp.zeros((1, 4, S, H * D), jnp.float32))
    assert not pa.ragged_supported(q, jnp.zeros((1, 4, S, H * D), jnp.int8))
    assert pa.ragged_supported(q, jnp.zeros((1, 4, 32, H * D), jnp.int8))


# ---------------------------------------------------------------------------
# cache-level quantizer invariants
# ---------------------------------------------------------------------------

def _cache(B=2, P=4, S=8, H=2, D=16, L=1):
    return PagedKVCache.create(L, B, H, P * S, D, page_size=S,
                               kv_dtype="int8")


def test_quant_cache_create_validates_dtype():
    with pytest.raises(MXNetError):
        PagedKVCache.create(1, 1, 2, 16, 4, page_size=8,
                            kv_dtype="bfloat16")
    c = _cache()
    assert c.quantized and c.k_pages.dtype == jnp.int8
    assert c.k_scale.shape == (1, c.k_pages.shape[1], 2)


def test_quant_codes_independent_of_chunking():
    """THE load-bearing invariant: int8 codes and scales are a pure
    function of the token stream — any chunking of the same stream
    (one shot, page-aligned, ragged, token-at-a-time) lands identical
    device state. Migration re-prefill and restart continuation are
    bit-identical BECAUSE of this."""
    rng = np.random.default_rng(0)
    T, H, D = 20, 2, 16
    k = rng.standard_normal((2, H, T, D)).astype(np.float32)
    v = rng.standard_normal((2, H, T, D)).astype(np.float32)

    def feed(chunks):
        c = _cache()
        t0 = 0
        for n in chunks:
            _, _, c = c.write_prompt(0, jnp.asarray(k[:, :, t0:t0 + n]),
                                     jnp.asarray(v[:, :, t0:t0 + n]))
            c = c.advance(n)
            t0 += n
        return c

    a = feed([20])
    for chunks in ([8, 8, 4], [5, 7, 8], [1] * 20):
        b = feed(chunks)
        np.testing.assert_array_equal(np.asarray(a.k_pages),
                                      np.asarray(b.k_pages))
        np.testing.assert_array_equal(np.asarray(a.v_pages),
                                      np.asarray(b.v_pages))
        np.testing.assert_array_equal(np.asarray(a.k_scale),
                                      np.asarray(b.k_scale))
        np.testing.assert_array_equal(np.asarray(a.v_scale),
                                      np.asarray(b.v_scale))


def test_quant_scales_monotone_no_rewrite_of_history():
    """Appending tokens to a page NEVER re-rounds already-written
    codes: prior pages' slabs and the filled region of the current
    page are byte-stable across the append."""
    rng = np.random.default_rng(1)
    H, D = 2, 16
    k1 = rng.standard_normal((1, H, 20, D)).astype(np.float32)
    big = 50.0 * rng.standard_normal((1, H, 4, D)).astype(np.float32)

    def state(c):
        return np.asarray(c.k_pages).copy(), np.asarray(c.k_scale).copy()

    c = PagedKVCache.create(1, 1, H, 32, D, page_size=8,
                            kv_dtype="int8")
    _, _, c = c.write_prompt(0, jnp.asarray(k1), jnp.asarray(k1))
    c = c.advance(20)
    k0, s0 = state(c)
    # a huge-magnitude append bumps page 2's scale but must not touch
    # pages 0/1 (full) or page 2's first 4 already-written slots
    _, _, c = c.write_prompt(0, jnp.asarray(big), jnp.asarray(big))
    k1_, s1 = state(c)
    table = np.asarray(c.page_table)[0]
    np.testing.assert_array_equal(k0[0, table[:2]], k1_[0, table[:2]])
    np.testing.assert_array_equal(k0[0, table[2], :4],
                                  k1_[0, table[2], :4])
    np.testing.assert_array_equal(s0[0, table[:2]], s1[0, table[:2]])
    assert (s1[0, table[2]] >= s0[0, table[2]]).all()
    assert (s1[0, table[2]] > s0[0, table[2]]).any()


def test_quant_gather_dequant_tolerance():
    """Round-trip fidelity in the stable-scale regime: when each
    page's FIRST token carries that page's absmax (the monotone scale
    is then final from the first write), every dequantized element is
    within half a quantization step of the fp input. Early-position
    inflation only appears when later tokens GROW the page scale —
    the monotonicity test above covers that contract."""
    rng = np.random.default_rng(2)
    k = rng.standard_normal((1, 2, 16, 16)).astype(np.float32)
    k[:, :, 0] *= 10.0                   # page 0's max leads
    k[:, :, 8] *= 10.0                   # page 1's max leads
    c = PagedKVCache.create(1, 1, 2, 16, 16, page_size=8,
                            kv_dtype="int8")
    kk, _, c = c.write_prompt(0, jnp.asarray(k), jnp.asarray(k))
    got = np.asarray(kk)[:, :, :16]
    # per-(page, head) bound: |dequant - x| <= scale / 2, expanded to
    # each position through the page table
    s = np.asarray(c.k_scale)[0]                      # (N, H)
    bound = s[np.asarray(c.page_table)[0]]            # (P, H)
    bound = np.repeat(bound, 8, axis=0).T[None]       # (1, H, T)
    assert (np.abs(got - k) <= bound[..., None] / 2 + 1e-7).all()


def test_make_cache_kv_dtype_needs_paged():
    net, cfg = _tiny()
    with pytest.raises(MXNetError):
        net.make_cache(2, 64, paged=False, kv_dtype="int8")
    c = net.make_cache(2, 64, paged=True, page_size=8, kv_dtype="int8")
    assert c.quantized


# ---------------------------------------------------------------------------
# engine: tolerance oracle, schedule independence, steady state
# ---------------------------------------------------------------------------

def test_engine_int8_greedy_tolerance_oracle():
    """Greedy tolerance oracle: the int8 engine tracks the fp32 engine
    wherever fp32's argmax margin is decisive. A tiny random-weight
    model makes near-ties common, so the committed bound is
    margin-aware: first tokens must agree whenever fp32's top-2 logit
    gap exceeds 1% of its magnitude, and the majority of full greedy
    streams must match end-to-end."""
    net, cfg = _tiny()
    prompts = _prompts(6)
    fp, _ = _serve(net, prompts)
    q8, eng = _serve(net, prompts, kv_dtype="int8")
    assert eng.audit_pages() == []
    seq_match = sum(fp[i] == q8[i] for i in range(len(prompts)))
    assert seq_match >= len(prompts) // 2
    # margin-aware first-token check against the dense fp forward
    for i, p in enumerate(prompts):
        lg = net(mx.nd.array(np.asarray(p, np.int32)[None],
                             dtype="int32")).asnumpy()[0, -1]
        top2 = np.sort(lg)[-2:]
        if top2[1] - top2[0] > 0.01:
            assert q8[i][0] == int(lg.argmax()), f"prompt {i}"


@pytest.mark.slow
def test_engine_int8_schedule_independent_bit_identity():
    """On a FIXED chunk grid (same chunk_tokens, non-binding prefill
    budget) int8 outputs are independent of co-scheduling: slot count,
    submission order, queueing and sampled traffic never move a
    request's chunk boundaries, and per-slot compute is positionally
    isolated. The grid itself IS part of the numerics, though — a
    mid-chunk row reads page scales that already reflect the whole
    chunk, so deep-layer codes depend on where the chunks end. That is
    why restarts and migration REPLAY the recorded schedule instead of
    re-chunking (test_engine_int8_restart_replay_bit_identical)."""
    net, cfg = _tiny()
    prompts = _prompts(4, seed=5)
    for sampled in (False, True):
        a, _ = _serve(net, prompts, sampled=sampled, kv_dtype="int8",
                      num_slots=2, chunk_tokens=8,
                      prefill_chunk_budget=64)
        b, _ = _serve(net, prompts, sampled=sampled, kv_dtype="int8",
                      num_slots=4, chunk_tokens=8,
                      prefill_chunk_budget=64)
        # reversed submission keeps each prompt's id (and so its RNG
        # seed); only the schedule changes
        n = len(prompts)
        c, _ = _serve(net, list(reversed(prompts)), sampled=sampled,
                      ids=list(reversed(range(n))), kv_dtype="int8",
                      num_slots=3, chunk_tokens=8,
                      prefill_chunk_budget=64)
        assert a == b == c


def test_engine_int8_restart_replay_bit_identical():
    """Transient dispatch faults roll requests back mid-flight; the
    quantized re-prefill must REPLAY the recorded write schedule
    (recorded prompt chunks, then each emitted token as a 1-token
    chunk) so the continuation is bit-identical to the fault-free run
    — re-chunking the emitted tail would re-quantize deep layers under
    different scale views and drift."""
    net, cfg = _tiny()
    prompts = _prompts(5, seed=11)
    want, _ = _serve(net, prompts, sampled=True, kv_dtype="int8",
                     num_slots=2, chunk_tokens=8,
                     prefill_chunk_budget=64)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", kv_dtype="int8",
                        chunk_tokens=8, prefill_chunk_budget=64,
                        max_retries=8, retry_backoff_s=0.0)
    reqs = [Request(p, 8, request_id=i, seed=100 + i, do_sample=True,
                    temperature=0.8, top_k=20, top_p=0.95)
            for i, p in enumerate(prompts)]
    plan = FaultPlan(seed=2, dispatch_exception=0.25, max_faults=5)
    plan.install(eng)
    try:
        done = eng.serve(reqs)
    finally:
        plan.uninstall()
    assert plan.counts["dispatch_exception"] >= 1
    assert all(r.status == "finished" for r in done)
    assert {r.id: list(r.output_tokens) for r in reqs} == want
    assert eng.stats["dispatch_retries"] >= 1
    assert eng.audit_pages() == []


def test_engine_int8_compile_flat_steady_state():
    """steady_state_compiles == 0 with quantized pages: prompt lengths
    never seen in warmup, prefix-cache attach, fully-cached CoW
    resubmission, and adapter traffic compile NOTHING after
    mark_warm() — including the scale-zeroing admission scatter, whose
    padded fixed-shape index must hold it to ONE jit entry."""
    net, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=2, dtype="int8")
    pool.register("a", random_lora(cfg, rank=2, seed=41))
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", kv_dtype="int8",
                        prefix_cache=True, adapter_pool=pool)
    rng = np.random.default_rng(7)
    shared = rng.integers(1, 97, size=16).tolist()
    eng.serve([Request(shared + [5], 3, request_id="warm"),
               Request([1, 2, 3], 3, request_id="warm2",
                       adapter_id="a"),
               Request([4, 4], 3, request_id="warm3", do_sample=True,
                       seed=0)])
    eng.mark_warm()
    before = {fn.program: _cost.get(fn.program)["compiles"]
              for fn in eng._programs.values()}
    for n in (5, 23, 31):           # lengths never seen
        eng.serve([Request(rng.integers(1, 97, size=n).tolist(), 3)])
    eng.serve([Request(shared + [9], 3)])        # prefix attach
    eng.serve([Request(shared, 2)])              # fully cached -> CoW
    eng.serve([Request([8, 9, 10], 3, adapter_id="a", do_sample=True,
                       seed=1)])
    after = {fn.program: _cost.get(fn.program)["compiles"]
             for fn in eng._programs.values()}
    assert after == before
    assert len(eng._programs) == 2
    assert eng._zero_scales_fn._cache_size() == 1
    assert eng.audit_pages() == []


def test_engine_int8_prefix_cache_attach_bit_identical():
    """Prefix-cache attach on int8 pages: the second request re-uses
    the first's quantized pages (scales shared read-only) and its
    output equals the cache-off quantized run — chunk-independence
    again, since attach just changes WHERE prefill starts."""
    net, cfg = _tiny()
    rng = np.random.default_rng(9)
    shared = rng.integers(1, 97, size=16).tolist()
    prompts = [shared + [7], shared + [11], shared]   # last: CoW split
    want, _ = _serve(net, prompts, kv_dtype="int8", num_slots=1)
    eng = ServingEngine(net, num_slots=1, max_length=64, page_size=8,
                        attn_impl="xla", kv_dtype="int8",
                        prefix_cache=True)
    reqs = [Request(p, 8, request_id=i) for i, p in enumerate(prompts)]
    eng.serve(reqs)
    assert {r.id: list(r.output_tokens) for r in reqs} == want
    assert eng.stats["prefix_hits"] >= 1
    assert eng.audit_pages() == []


def test_engine_int8_speculative_verify():
    """Speculative verify on quantized pages is tolerance-only
    (rejected drafts legitimately bump page scales), but greedy spec
    traffic must still track the spec-off quantized engine closely and
    keep the accounting clean."""
    net, cfg = _tiny()
    prompt = [3, 5, 3, 5, 3, 5, 3]      # lookup drafter always fires
    off, _ = _serve(net, [prompt] * 4, max_new=8, kv_dtype="int8")
    on, eng = _serve(net, [prompt] * 4, max_new=8, kv_dtype="int8",
                     speculative=True, spec_tokens=3)
    assert eng.stats["spec_draft_tokens"] > 0
    assert eng.audit_pages() == []
    agree = sum(sum(x == y for x, y in zip(off[i], on[i]))
                for i in range(4))
    total = sum(len(off[i]) for i in range(4))
    assert agree >= int(0.7 * total), (off, on)


def test_engine_int8_sampled_frequency_matches_fp():
    """PR 4-style distribution check: the marginal of the first
    sampled token over many seeds through int8 pages must match the
    fp32 engine's marginal in total variation."""
    net, cfg = _tiny(vocab=17, layers=1, units=16, heads=2, max_len=32,
                     seed=11)
    prompt = [3, 5, 3, 5, 3]
    N = 240

    def run(kv):
        eng = ServingEngine(net, num_slots=4, max_length=32,
                            page_size=8, attn_impl="xla", kv_dtype=kv)
        reqs = [Request(prompt, 2, do_sample=True, temperature=1.2,
                        seed=i, request_id=i) for i in range(N)]
        eng.serve(reqs)
        toks = np.asarray([r.output_tokens[0] for r in reqs])
        return np.bincount(toks, minlength=cfg.vocab_size) / N

    f_fp, f_q8 = run(None), run("int8")
    assert float(np.abs(f_q8 - f_fp).sum()) < 0.20   # total variation


# ---------------------------------------------------------------------------
# byte-denominated capacity: the freed HBM is real admitted pages
# ---------------------------------------------------------------------------

def test_engine_hbm_budget_admits_more_int8_pages():
    """At ONE fixed byte budget the int8 engine's pool holds ~4x the
    fp32 engine's pages (the >= 1.8x capacity claim with margin), and
    the page_bytes gauges expose the per-token cost drop."""
    net, cfg = _tiny()
    budget = 200_000
    fp = ServingEngine(net, num_slots=4, max_length=64, page_size=8,
                       attn_impl="xla", hbm_budget_bytes=budget)
    q8 = ServingEngine(net, num_slots=4, max_length=64, page_size=8,
                       attn_impl="xla", hbm_budget_bytes=budget,
                       kv_dtype="int8")
    assert fp.page_pool.page_bytes > q8.page_pool.page_bytes
    ratio = q8.page_pool.num_pages / fp.page_pool.num_pages
    # both pools are clamped at B*P when the budget is loose — shrink
    # the budget until fp32 is page-limited to expose the ratio
    tight = fp.page_pool.page_bytes * 16
    fp2 = ServingEngine(net, num_slots=4, max_length=64, page_size=8,
                        attn_impl="xla", hbm_budget_bytes=tight)
    q82 = ServingEngine(net, num_slots=4, max_length=64, page_size=8,
                        attn_impl="xla", hbm_budget_bytes=tight,
                        kv_dtype="int8")
    assert fp2.page_pool.num_pages == 16
    assert q82.page_pool.num_pages / fp2.page_pool.num_pages >= 1.8
    assert q82.admission_capacity_estimate() \
        >= fp2.admission_capacity_estimate()
    # a page-limited engine still serves EVERYTHING via backpressure
    reqs = [Request(p, 4, request_id=i)
            for i, p in enumerate(_prompts(6, seed=13))]
    fp2.serve(reqs)
    assert {r.status for r in reqs} == {"finished"}
    assert fp2.audit_pages() == []


def test_engine_hbm_budget_below_one_slot_raises():
    net, cfg = _tiny()
    with pytest.raises(MXNetError):
        ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                      attn_impl="xla", hbm_budget_bytes=100)
    with pytest.raises(MXNetError):
        ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                      attn_impl="xla", kv_dtype="fp16")


def test_engine_int8_gauges_ledger_statusz():
    net, cfg = _tiny()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", kv_dtype="int8",
                        hbm_budget_bytes=10 ** 6)
    s = eng.stats
    pb = eng.page_pool.page_bytes
    assert s["kv_quant_enabled"] == 1
    assert s["kv_page_bytes"] == pb
    assert s["kv_bytes_per_token"] == pb / 8
    # the honest page cost: int8 k+v slabs + f32 scales, all layers
    L, H, D = cfg.num_layers, cfg.num_heads, cfg.units // cfg.num_heads
    assert pb == 2 * L * 8 * H * D * 1 + 2 * L * H * 4
    cfg_rows = eng._statusz()["config"]
    assert cfg_rows["kv_dtype"] == "int8"
    assert cfg_rows["kv_page_bytes"] == pb
    assert cfg_rows["hbm_budget_bytes"] == 10 ** 6
    led = eng._hbm_ledger()
    assert len(led["kv_pages"]) == 4     # codes + scales, k and v
    kv_bytes = sum(int(a.nbytes) for a in led["kv_pages"])
    assert kv_bytes == pb * eng.page_pool.num_pages
    fp = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                       attn_impl="xla")
    assert fp.stats["kv_quant_enabled"] == 0
    assert fp.stats["kv_page_bytes"] == fp.page_pool.page_bytes
    assert len(fp._hbm_ledger()["kv_pages"]) == 2


# ---------------------------------------------------------------------------
# PagePool: byte sizing + scale-leaf audit
# ---------------------------------------------------------------------------

def test_page_pool_from_bytes():
    pool = PagePool.from_bytes(10_000, 1056)
    assert pool.num_pages == 9 and pool.page_bytes == 1056
    with pytest.raises(MXNetError):
        PagePool.from_bytes(1000, 1056)
    with pytest.raises(MXNetError):
        PagePool.from_bytes(1000, 0)


def test_page_pool_audit_scales():
    pool = PagePool(4)
    ok = np.asarray([0.0, 0.5, 1.0, 2.0])
    assert pool.audit(scales=ok) == []
    bad = ok.copy()
    bad[1] = np.nan
    bad[3] = -1.0
    v = pool.audit(scales=bad)
    assert len(v) == 2 and all("corrupt quant scale" in x for x in v)
    assert pool.audit(scales=np.zeros(3)) != []
    with pytest.raises(MXNetError):
        pool.audit(scales=bad, raise_on_error=True)


# ---------------------------------------------------------------------------
# quantized adapter slab vs the merged-weight dense oracle
# ---------------------------------------------------------------------------

def _merged_net(weights):
    cfg0 = _tiny()[1]
    cfg = GPT2Config(vocab_size=cfg0.vocab_size, units=cfg0.units,
                     num_layers=cfg0.num_layers,
                     num_heads=cfg0.num_heads,
                     max_length=cfg0.max_length, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    for li, blk in enumerate(net.backbone.blocks()):
        attn = blk.attn
        for pname in ("query", "key", "value", "proj"):
            layer = getattr(attn, pname)
            w = layer.weight.data().asnumpy()
            layer.weight.set_data(
                mx.nd.array(merged_weights(w, weights, pname, li)))
    return net


def test_quant_adapter_pool_matches_merged_weight_oracle():
    """The int8 slab's dequant (codes x scales) reproduces the
    round-tripped weights EXACTLY, so the served output must equal a
    dense engine whose projections bake in effective_weights() — the
    same greedy-exact bar the fp adapter test sets."""
    net, cfg = _tiny()
    pool = AdapterPool(cfg, slots=3, max_rank=4, dtype="int8")
    w = random_lora(cfg, rank=3, alpha=8.0, seed=21)
    pool.register("t", w)
    eff = pool.effective_weights("t")
    assert not np.allclose(eff["A"], w["A"])     # quantization bit
    prompts = _prompts(4, seed=17)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", adapter_pool=pool)
    reqs = [Request(p, 6, request_id=i, adapter_id="t")
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    got = {r.id: list(r.output_tokens) for r in reqs}
    oracle = ServingEngine(_merged_net(eff), num_slots=2, max_length=64,
                           page_size=8, attn_impl="xla")
    wreqs = [Request(p, 6, request_id=i)
             for i, p in enumerate(prompts)]
    oracle.serve(wreqs)
    want = {r.id: list(r.output_tokens) for r in wreqs}
    assert got == want
    assert eng.audit_adapters() == []


def test_quant_adapter_slab_bytes_drop():
    _, cfg = _tiny()
    fp = AdapterPool(cfg, slots=4, max_rank=4)
    q8 = AdapterPool(cfg, slots=4, max_rank=4, dtype="int8")
    assert q8.quantized and not fp.quantized
    assert q8.slab_bytes() < 0.3 * fp.slab_bytes()
    assert q8.a_scale is not None and q8.b_scale is not None


@pytest.mark.slow
def test_quant_adapter_with_int8_kv_end_to_end():
    """Both quantizations at once — int8 KV pages AND the int8 adapter
    slab — serve cleanly, and on a fixed chunk grid the outputs are
    independent of slot count."""
    net, cfg = _tiny()
    prompts = _prompts(3, seed=23)

    def _pool():
        p = AdapterPool(cfg, slots=3, max_rank=2, dtype="int8")
        p.register("z", random_lora(cfg, rank=2, seed=31))
        return p

    def run(slots):
        eng = ServingEngine(net, num_slots=slots, max_length=64,
                            page_size=8, attn_impl="xla",
                            kv_dtype="int8", chunk_tokens=8,
                            prefill_chunk_budget=64,
                            adapter_pool=_pool())
        reqs = [Request(p, 6, request_id=i, adapter_id="z")
                for i, p in enumerate(prompts)]
        eng.serve(reqs)
        assert eng.audit_pages() == [] and eng.audit_adapters() == []
        return {r.id: list(r.output_tokens) for r in reqs}

    assert run(1) == run(3)


# ---------------------------------------------------------------------------
# router: kill mid-decode, quantized outputs migrate bit-identically
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_kill_mid_decode_int8_bit_identical():
    """Satellite 1's acceptance: a replica killed mid-decode with
    kv_dtype="int8" migrates its in-flight requests and every output
    equals the fault-free quantized run. No device scale state moves:
    the write SCHEDULE rides each Request (kv_history), and the
    survivor's re-prefill replays it — recorded prompt chunks, then
    each emitted token as a 1-token chunk — re-quantizing the stream
    into identical codes under identical scale views. Budgets are
    non-binding so the fault-free baseline shares the chunk grid."""
    net, _ = _tiny()

    def _engine():
        return ServingEngine(net, num_slots=2, max_length=32,
                             page_size=8, attn_impl="xla",
                             kv_dtype="int8", chunk_tokens=8,
                             prefill_chunk_budget=64)

    def _reqs():
        rng = np.random.default_rng(7)
        out = []
        for i in range(10):
            prompt = rng.integers(1, 97, size=int(rng.integers(3, 9)))
            out.append(Request(prompt.tolist(), 6, request_id=i,
                               do_sample=(i % 2 == 0), seed=100 + i))
        return out

    base = ServingEngine(net, num_slots=4, max_length=32, page_size=8,
                         attn_impl="xla", kv_dtype="int8",
                         chunk_tokens=8, prefill_chunk_budget=64)
    want_reqs = _reqs()
    base.serve(want_reqs)
    want = {r.id: list(r.output_tokens) for r in want_reqs}
    engines = [_engine(), _engine()]
    router = ServingRouter(engines)
    plan = ReplicaFaultPlan(kill={4: 0}).install(router)
    try:
        reqs = _reqs()
        for r in reqs:
            router.submit(r)
        n = 0
        while router.has_work and n < 5000:
            router.step()
            n += 1
    finally:
        plan.uninstall()
    assert plan.counts["kill"] == 1
    assert {r.status for r in reqs} == {"finished"}
    assert {r.id: list(r.output_tokens) for r in reqs} == want
    assert router.stats["migrated"] >= 1
    assert engines[1].audit_pages() == []
    assert engines[0].audit_pages() == []
