"""Speculative decoding: prompt-lookup drafting + multi-query ragged
verification.

Three layers of oracle: the dense XLA reference for the multi-query
kernel, exact greedy bit-identity spec-on vs spec-off through the
engine (the acceptance criterion), and a frequency test against the
filtered target distribution for the rejection sampler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM, PagedKVCache
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import (PromptLookupProposer, Request,
                               ServingEngine, filtered_logits,
                               sample_tokens, slot_keys, verify_tokens)


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64, seed=3):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(seed)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


def _greedy_full(net, prompt, n_new):
    ids = np.asarray(prompt, np.int32)[None]
    out = []
    for _ in range(n_new):
        logits = net(mx.nd.array(ids, dtype="int32"))
        nxt = int(logits.asnumpy()[0, -1].argmax())
        out.append(nxt)
        ids = np.concatenate([ids, [[nxt]]], axis=1)
    return out


# ---------------------------------------------------------------------------
# multi-query ragged kernel vs the dense oracle
# ---------------------------------------------------------------------------

def _pool(B=3, H=2, D=16, S=8, P=4, Sq=4, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    N = B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((N, S, H, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((N, S, H, D)), dtype)
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    # packed one-layer pools, as PagedKVCache stores them
    return q, kp.reshape(1, N, S, H * D), vp.reshape(1, N, S, H * D), table


@pytest.mark.parametrize("lengths", [[5, 17, 29], [1, 8, 23],
                                     [29, 29, 29], [1, 1, 1]])
@pytest.mark.parametrize("sq", [1, 2, 4])
def test_mq_kernel_matches_dense_reference(lengths, sq):
    q, kp, vp, table = _pool(Sq=sq)
    L = jnp.asarray(lengths, jnp.int32)
    ref = pa._ragged_mq_reference(q, kp, vp, table, L, 1.0 / np.sqrt(16))
    out = pa.ragged_mq_decode_attention(q, kp, vp, table, L,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mq_kernel_sq1_degenerates_to_single_query():
    """Sq=1 must reproduce the single-query ragged kernel exactly (same
    mask, same online-softmax walk)."""
    q, kp, vp, table = _pool(Sq=1)
    L = jnp.asarray([3, 12, 27], jnp.int32)
    mq = pa.ragged_mq_decode_attention(q, kp, vp, table, L,
                                       interpret=True)
    single = pa.ragged_decode_attention(q[:, 0], kp, vp, table, L,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(mq[:, 0]),
                                  np.asarray(single))


def test_mq_kernel_per_position_causal_offsets():
    """Row j of the oracle/kernel sees exactly lengths+j keys: row j
    computed at lengths L must equal row 0 computed at lengths L+j."""
    q, kp, vp, table = _pool(Sq=3)
    L = jnp.asarray([4, 9, 20], jnp.int32)
    out = pa.ragged_mq_decode_attention(q, kp, vp, table, L,
                                        interpret=True)
    for j in range(3):
        row = pa.ragged_mq_decode_attention(q[:, j:j + 1], kp, vp, table,
                                            L + j, interpret=True)
        np.testing.assert_allclose(np.asarray(out[:, j]),
                                   np.asarray(row[:, 0]), rtol=2e-5,
                                   atol=2e-5)


def test_mq_kernel_bf16_tolerance():
    q, kp, vp, table = _pool(Sq=4, dtype=jnp.bfloat16)
    L = jnp.asarray([7, 20, 13], jnp.int32)
    ref = pa._ragged_mq_reference(q.astype(jnp.float32),
                                  kp.astype(jnp.float32),
                                  vp.astype(jnp.float32), table, L,
                                  1.0 / np.sqrt(16))
    out = pa.ragged_mq_decode_attention(q, kp, vp, table, L,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# multi-token ragged cache writes
# ---------------------------------------------------------------------------

def test_write_decode_multitoken_lands_at_per_slot_offsets():
    B, H, D, S, t = 2, 1, 2, 4, 3
    lengths = jnp.asarray([1, 6], jnp.int32)
    cache = PagedKVCache.create(1, B, H, 12, D, page_size=S,
                                lengths=lengths)
    val = (jnp.arange(B * t, dtype=jnp.float32).reshape(B, 1, t, 1)
           + 1.0)
    val = jnp.broadcast_to(val, (B, H, t, D))
    cache = cache.write_decode(0, val, 2 * val)
    pool = np.asarray(cache.k_pages)[0]
    table = np.asarray(cache.page_table)
    for b, length in enumerate([1, 6]):
        for j in range(t):
            page, slot = divmod(length + j, S)
            assert pool[table[b, page], slot, 0] == b * t + j + 1.0
    assert (pool != 0).sum() == B * t * D   # nothing else touched


def test_write_decode_multitoken_drops_past_capacity_and_locked():
    B, H, D, S, t = 2, 1, 2, 4, 3
    # slot 0 one position from capacity (7 of 8); slot 1 writes into a
    # LOCKED page: every dropped position must leave the pool untouched
    cache = PagedKVCache.create(1, B, H, 8, D, page_size=S,
                                lengths=jnp.asarray([7, 2], jnp.int32))
    lock = np.zeros(cache.k_pages.shape[1], bool)
    lock[int(cache.page_table[1, 0])] = True
    cache = PagedKVCache(cache.k_pages, cache.v_pages, cache.page_table,
                         cache.length, page_lock=jnp.asarray(lock))
    val = jnp.full((B, H, t, D), 7.0)
    cache = cache.write_decode(0, val, val)
    pool = np.asarray(cache.k_pages)[0]
    table = np.asarray(cache.page_table)
    # slot 0: position 7 written, 8 and 9 dropped (capacity)
    assert pool[table[0, 1], 3, 0] == 7.0
    assert (pool[table[0]] != 0).sum() == D
    # slot 1: positions 2, 3 aimed at the locked page 0 -> dropped;
    # position 4 lands in page 1
    assert (pool[table[1, 0]] == 0).all()
    assert pool[table[1, 1], 0, 0] == 7.0


# ---------------------------------------------------------------------------
# prompt-lookup proposer
# ---------------------------------------------------------------------------

def test_proposer_drafts_cycle_continuation():
    p = PromptLookupProposer(max_draft=4, max_ngram=3)
    hist = [1, 2, 3, 1, 2, 3, 1, 2]
    # last 3-gram [3,1,2] first occurs at 2 -> continuation h[5:],
    # capped at the history end
    np.testing.assert_array_equal(p.propose(hist), [3, 1, 2])


def test_proposer_falls_back_to_shorter_ngrams_and_empty():
    p = PromptLookupProposer(max_draft=3, max_ngram=3)
    # no 3- or 2-gram repeat, but the last token recurs -> 1-gram match
    np.testing.assert_array_equal(p.propose([7, 9, 5, 2, 9]), [5, 2, 9])
    assert p.propose([1, 2, 3, 4]).size == 0       # nothing recurs
    assert p.propose([1]).size == 0                # too short to match


def test_proposer_draft_capped_by_history_end():
    p = PromptLookupProposer(max_draft=8, max_ngram=2)
    np.testing.assert_array_equal(p.propose([4, 4]), [4])


# ---------------------------------------------------------------------------
# verification: greedy rule and distribution preservation
# ---------------------------------------------------------------------------

def _verify(logits, drafts, n_draft, seeds, do_sample=True, temp=1.0,
            top_k=0, top_p=1.0, counters=None):
    B, S, V = logits.shape
    arr = lambda v, dt: jnp.full((B,), v, dt)  # noqa: E731
    return verify_tokens(
        jnp.asarray(logits), jnp.asarray(drafts, jnp.int32),
        jnp.asarray(n_draft, jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.zeros((B,), jnp.int32) if counters is None
        else jnp.asarray(counters, jnp.int32),
        arr(do_sample, bool), arr(temp, jnp.float32),
        arr(top_k, jnp.int32), arr(top_p, jnp.float32))


def test_verify_greedy_accepts_exact_prefix():
    V, S = 11, 4
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((1, S, V)).astype(np.float32)
    tgt = logits.argmax(-1)[0]                    # per-position argmax
    # drafts [tgt0, tgt1, WRONG]: accept 2, then emit tgt2 at position 2
    drafts = np.asarray([[tgt[0], tgt[1], (tgt[2] + 1) % V]])
    emitted, n_acc = _verify(logits, drafts, [3], [0], do_sample=False)
    assert int(n_acc[0]) == 2
    np.testing.assert_array_equal(np.asarray(emitted)[0, :3], tgt[:3])
    # all drafts right -> all accepted + the bonus position
    drafts = np.asarray([[tgt[0], tgt[1], tgt[2]]])
    emitted, n_acc = _verify(logits, drafts, [3], [0], do_sample=False)
    assert int(n_acc[0]) == 3
    np.testing.assert_array_equal(np.asarray(emitted)[0], tgt)


def test_verify_zero_drafts_bit_matches_plain_sampler():
    """A dispatch with no drafts must emit EXACTLY what the spec-off
    sampler draws for the same (seed, token index) — same key, same
    filtered distribution."""
    V, B = 23, 6
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((B, 1, V)).astype(np.float32)
    seeds = np.arange(B)
    counters = np.asarray([0, 3, 1, 7, 2, 5])
    emitted, n_acc = _verify(logits, np.zeros((B, 0)), [0] * B, seeds,
                             temp=0.7, top_k=5, top_p=0.9,
                             counters=counters)
    keys = slot_keys(jnp.asarray(seeds, jnp.int32),
                     jnp.asarray(counters, jnp.int32))
    want = sample_tokens(jnp.asarray(logits[:, 0]), keys,
                         jnp.ones((B,), bool),
                         jnp.full((B,), 0.7, jnp.float32),
                         jnp.full((B,), 5, jnp.int32),
                         jnp.full((B,), 0.9, jnp.float32))
    np.testing.assert_array_equal(np.asarray(emitted)[:, 0],
                                  np.asarray(want))
    assert int(np.asarray(n_acc).sum()) == 0


@pytest.mark.parametrize("top_k,top_p", [(0, 1.0), (4, 1.0), (0, 0.7)])
def test_verify_rejection_sampling_preserves_distribution(top_k, top_p):
    """Speculative rejection sampling against a point-mass proposal must
    leave the emitted marginal EXACTLY the filtered target distribution
    — frequency test over many independent seeds, one fixed logits row,
    a deliberately mediocre draft."""
    V, N = 13, 4000
    rng = np.random.default_rng(2)
    row = rng.standard_normal(V).astype(np.float32)
    logits = np.broadcast_to(row, (N, 1, V)).reshape(N, 1, V)
    p = np.asarray(jax.nn.softmax(filtered_logits(
        jnp.asarray(row)[None], jnp.ones((1,), jnp.float32),
        jnp.full((1,), top_k, jnp.int32),
        jnp.full((1,), top_p, jnp.float32))))[0]
    draft = int(np.argsort(-row)[min(2, V - 1)])   # mid-probability token
    logits2 = np.concatenate([logits, logits], axis=1)  # S = 2
    emitted, n_acc = _verify(logits2, np.full((N, 1), draft), [1] * N,
                             np.arange(N), top_k=top_k, top_p=top_p)
    first = np.asarray(emitted)[:, 0]
    freq = np.bincount(first, minlength=V) / N
    assert float(np.abs(freq - p).sum()) < 0.08    # total variation
    # the draft was accepted a nontrivial fraction of the time (its own
    # mass), so the test exercised BOTH the accept and the reject path
    acc = float((np.asarray(n_acc) > 0).mean())
    assert abs(acc - p[draft]) < 0.05


# ---------------------------------------------------------------------------
# engine: bit-identity, reproducibility, composition
# ---------------------------------------------------------------------------

def _mixed_prompts(cfg, rng, n=6):
    """Repetitive + random prompts: the repetitive ones make the
    prompt-lookup drafter fire, the random ones keep the zero-draft
    path hot."""
    pat = rng.integers(0, cfg.vocab_size, 3).tolist()
    out = []
    for i in range(n):
        if i % 2:
            out.append(rng.integers(
                0, cfg.vocab_size, int(rng.integers(3, 12))).tolist())
        else:
            out.append(pat * (2 + i % 3) + pat[:1 + i % 2])
    return out


def test_engine_spec_greedy_bit_identical_interleaved():
    """The acceptance criterion: greedy output spec-on == spec-off, bit
    for bit, with more requests than slots (slots recycle, admissions
    interleave with speculative dispatches) — and drafts actually got
    accepted, so the equality covers the multi-token path."""
    net, cfg = _tiny()
    rng = np.random.default_rng(4)
    prompts = _mixed_prompts(cfg, rng)
    eng_off = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                            attn_impl="xla")
    off = eng_off.generate(prompts, 9)
    eng_on = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                           attn_impl="xla", speculative=True,
                           spec_tokens=4)
    on = eng_on.generate(prompts, 9)
    assert on == off
    s = eng_on.stats
    assert s["spec_accepted_tokens"] > 0
    assert s["spec_draft_tokens"] == (s["spec_accepted_tokens"]
                                      + s["spec_rollbacks"])
    assert off == [_greedy_full(net, p, 9) for p in prompts]


def test_engine_spec_greedy_bit_identical_interpret_kernel():
    """Same bit-identity through the multi-query Pallas kernel in
    interpret mode (the CPU stand-in for the TPU path)."""
    net, cfg = _tiny()
    rng = np.random.default_rng(5)
    prompts = _mixed_prompts(cfg, rng, n=3)
    off = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="pallas_interpret").generate(prompts, 6)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="pallas_interpret", speculative=True,
                        spec_tokens=3)
    assert eng.generate(prompts, 6) == off
    assert eng.stats["spec_accepted_tokens"] > 0


def test_engine_spec_with_prefix_cache_bit_identical():
    """Speculation composes with the prefix cache: shared-prefix
    admissions lease locked pages, rejected drafts must never scribble
    on them, and the output still matches the plain engine."""
    net, cfg = _tiny()
    rng = np.random.default_rng(6)
    shared = rng.integers(0, cfg.vocab_size, 17).tolist()
    pat = rng.integers(0, cfg.vocab_size, 3).tolist()
    prompts = [shared + pat * 2, shared + [3], pat * 5,
               shared + pat * 2]          # last one: full-prompt CoW hit
    off = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla"
                        ).generate(prompts, 8)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", speculative=True, spec_tokens=4,
                        prefix_cache=True)
    assert eng.generate(prompts, 8) == off
    s = eng.stats
    assert s["prefix_hits"] > 0 and s["spec_accepted_tokens"] > 0


def test_engine_spec_eos_and_budget_inside_accepted_run():
    """An eos emitted mid-acceptance must truncate the run (nothing
    after the eos), and budgets cap multi-token emissions exactly."""
    net, cfg = _tiny()
    rng = np.random.default_rng(5)
    pat = rng.integers(0, cfg.vocab_size, 3).tolist()
    p0 = pat * 4
    free_run = _greedy_full(net, p0, 8)
    # this run is [t,t,t,t,t,u,u,u]: eos=u first appears at index 5,
    # deep inside a run of accepted drafts
    eos = free_run[5]
    assert eos not in free_run[:5]
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", speculative=True, spec_tokens=4)
    r_eos = Request(p0, 8, eos_token_id=eos)
    r_budget = Request(pat * 3, 3)
    eng.serve([r_eos, r_budget])
    assert r_eos.output_tokens == free_run[:6]
    assert len(r_budget.output_tokens) == 3
    assert r_budget.output_tokens == _greedy_full(net, pat * 3, 3)
    assert eng.scheduler.num_free == 2


@pytest.mark.slow
def test_engine_spec_sampled_reproducible_across_schedules():
    """Sampled spec-on output depends only on (seed, token index,
    history): admission order and slot count must not change it."""
    net, cfg = _tiny()
    rng = np.random.default_rng(8)
    prompts = _mixed_prompts(cfg, rng, n=4)

    def run(order, slots):
        eng = ServingEngine(net, num_slots=slots, max_length=64,
                            page_size=8, attn_impl="xla",
                            speculative=True, spec_tokens=4)
        reqs = [Request(prompts[i], 7, do_sample=True, temperature=0.8,
                        top_k=20, top_p=0.95, seed=100 + i,
                        request_id=i) for i in order]
        eng.serve(reqs)
        return {r.id: r.output_tokens for r in reqs}

    assert run([0, 1, 2, 3], 2) == run([3, 1, 0, 2], 4)


def test_engine_spec_sampled_frequency_matches_spec_off():
    """End-to-end distribution preservation on a tiny vocab: the
    marginal of the SECOND emitted token (the first decode-dispatch
    token — drafted for most requests) over many seeds must match the
    spec-off engine's marginal."""
    net, cfg = _tiny(vocab=17, layers=1, units=16, heads=2, max_len=32,
                     seed=11)
    prompt = [3, 5, 3, 5, 3, 5, 3]      # lookup always fires
    N = 240

    def run(speculative):
        kw = dict(speculative=True, spec_tokens=3) if speculative else {}
        eng = ServingEngine(net, num_slots=4, max_length=32, page_size=8,
                            attn_impl="xla", **kw)
        reqs = [Request(prompt, 2, do_sample=True, temperature=1.2,
                        seed=i, request_id=i) for i in range(N)]
        eng.serve(reqs)
        toks = np.asarray([r.output_tokens[1] for r in reqs])
        return np.bincount(toks, minlength=cfg.vocab_size) / N

    f_off, f_on = run(False), run(True)
    assert float(np.abs(f_on - f_off).sum()) < 0.20   # total variation


def test_engine_spec_stats_and_telemetry_consistency():
    net, cfg = _tiny()
    rng = np.random.default_rng(9)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", speculative=True, spec_tokens=4)
    eng.generate(_mixed_prompts(cfg, rng, n=4), 8)
    s = eng.stats
    assert s["spec_draft_tokens"] > 0
    assert 0 < s["spec_accepted_tokens"] <= s["spec_draft_tokens"]
    assert s["spec_rollbacks"] == (s["spec_draft_tokens"]
                                   - s["spec_accepted_tokens"])
    # one verification forward per dispatch in spec mode
    assert s["decode_steps"] == s["decode_dispatches"]
    assert s["tokens_emitted"] >= s["spec_accepted_tokens"]


# ---------------------------------------------------------------------------
# filtered_logits edge cases (the sampling-refactor satellite)
# ---------------------------------------------------------------------------

def _filt(row, temp=1.0, top_k=0, top_p=1.0):
    out = filtered_logits(jnp.asarray(row, jnp.float32)[None],
                          jnp.asarray([temp], jnp.float32),
                          jnp.asarray([top_k], jnp.int32),
                          jnp.asarray([top_p], jnp.float32))
    return np.asarray(out)[0]


def test_filtered_logits_top_k_one_keeps_only_argmax():
    row = np.asarray([0.1, 2.0, -1.0, 0.5])
    out = _filt(row, top_k=1)
    assert np.isfinite(out[1])
    assert np.isinf(out[[0, 2, 3]]).all()


def test_filtered_logits_top_p_zero_keeps_top1():
    row = np.asarray([0.1, 2.0, -1.0, 0.5])
    out = _filt(row, top_p=0.0)
    assert np.isfinite(out[1]) and np.isinf(out[[0, 2, 3]]).all()


def test_filtered_logits_disabled_filters_are_noops():
    row = np.random.default_rng(0).standard_normal(9)
    np.testing.assert_array_equal(_filt(row, top_k=0, top_p=1.0),
                                  row.astype(np.float32))


def test_filtered_logits_tied_logits_keep_k_tokens():
    """Exact ties must not leak extra tokens past top_k: exactly k
    survive (argsort breaks ties deterministically)."""
    row = np.zeros(6, np.float32)
    out = _filt(row, top_k=3)
    assert np.isfinite(out).sum() == 3
    # and nucleus with ties: top_p just over 1/3 keeps 3 of 6 equal-mass
    out = _filt(row, top_p=0.34)
    assert np.isfinite(out).sum() == 3


def test_filtered_logits_temperature_scales_before_filter():
    row = np.asarray([1.0, 0.5, 0.0])
    np.testing.assert_allclose(_filt(row, temp=0.5),
                               row.astype(np.float32) / 0.5)


def test_sample_tokens_mixed_greedy_sampled_batch():
    """Greedy rows ignore temperature/filters entirely; sampled rows
    draw only surviving tokens."""
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((4, 12)).astype(np.float32)
    keys = slot_keys(jnp.arange(4, dtype=jnp.int32),
                     jnp.zeros(4, jnp.int32))
    out = sample_tokens(jnp.asarray(logits), keys,
                        jnp.asarray([False, True, False, True]),
                        jnp.full((4,), 0.01, jnp.float32),   # peaky
                        jnp.asarray([0, 2, 0, 2], jnp.int32),
                        jnp.ones((4,), jnp.float32))
    out = np.asarray(out)
    top2 = np.argsort(-logits, axis=-1)[:, :2]
    for b in (0, 2):
        assert out[b] == logits[b].argmax()
    for b in (1, 3):
        assert out[b] in top2[b]
