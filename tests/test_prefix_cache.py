"""Prefix-cache subsystem tests: ref-counted page pool, radix-tree
prompt reuse, CoW invariants, engine bit-identity and churn safety.

Acceptance criteria (ISSUE 3): cache-on output bit-identical to
cache-off for the same requests/RNG streams; eviction bounds the tree
under churn with refcounts returning to baseline; CoW prevents any
write to a shared page; concurrent submit() racing QueueFullError keeps
the rejection counter exact.
"""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM, PagedKVCache
from mxnet_tpu.serving import (PagePool, PrefixCache, QueueFullError,
                               Request, ServingEngine)


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64, seed=3):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(seed)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


# ---------------------------------------------------------------------------
# PagePool — the ref-counted allocator
# ---------------------------------------------------------------------------

def test_pool_alloc_free_roundtrip():
    pool = PagePool(8)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.num_free == 5 and pool.num_allocated == 3
    assert all(pool.refcount(p) == 1 for p in a)
    assert pool.free(pool.decref(a)) == a
    assert pool.num_free == 8 and pool.num_allocated == 0


def test_pool_share_and_masks():
    pool = PagePool(4)
    a, b = pool.alloc(2)
    pool.incref([a])                       # second lease on a
    assert pool.refcount(a) == 2
    np.testing.assert_array_equal(pool.shared_mask(),
                                  [i == a for i in range(4)])
    np.testing.assert_array_equal(pool.exclusive_mask(),
                                  [i == b for i in range(4)])
    assert pool.decref([a]) == []          # still one lease left
    assert pool.decref([a]) == [a]


def test_pool_misuse_raises():
    pool = PagePool(2)
    (p,) = pool.alloc(1)
    with pytest.raises(mx.MXNetError):
        pool.alloc(5)                      # exhausted
    with pytest.raises(mx.MXNetError):
        pool.free([p])                     # live refcount
    pool.decref([p])
    with pytest.raises(mx.MXNetError):
        pool.decref([p])                   # underflow
    with pytest.raises(mx.MXNetError):
        pool.incref([1])                   # never allocated
    pool.free([p])
    with pytest.raises(mx.MXNetError):
        pool.free([p])                     # double free


def test_pool_cow_split():
    pool = PagePool(4)
    (p,) = pool.alloc(1)
    # exclusive: write in place, no copy
    assert pool.cow(p) == (p, False)
    pool.incref([p])                       # now shared
    dst, needs_copy = pool.cow(p)
    assert needs_copy and dst != p
    assert pool.refcount(p) == 1           # our lease moved to dst
    assert pool.refcount(dst) == 1


# ---------------------------------------------------------------------------
# PrefixCache — radix-tree semantics
# ---------------------------------------------------------------------------

def _cache(pages=16, S=4, budget=None):
    pool = PagePool(pages)
    return pool, PrefixCache(pool, S, budget_pages=budget)


def test_radix_insert_match_release():
    pool, pc = _cache()
    toks = list(range(10))                 # 2 full pages of 4 + tail
    pages = pool.alloc(2)
    assert pc.insert(toks, pages) == 2
    assert pc.num_pages == 2
    # exact-prefix match takes a lease per page, in prefix order
    got = pc.match(toks)
    assert got == pages
    assert all(pool.refcount(p) == 2 for p in pages)
    # longest-prefix: same first page, diverging second
    other = list(range(4)) + [99, 98, 97, 96]
    got2 = pc.match(other)
    assert got2 == pages[:1]
    assert pool.refcount(pages[0]) == 3
    pc.release(got + got2)
    pc.release(pages)                      # drop the test's alloc leases
    # zero-ref tree pages stay materialized (evictable), not freed
    assert all(pool.refcount(p) == 0 for p in pages)
    assert pc.num_pages == 2 and pool.num_allocated == 2


def test_radix_short_prompt_is_miss():
    pool, pc = _cache(S=8)
    assert pc.match([1, 2, 3]) == []       # < one page: nothing to share
    assert pc.misses == 1


def test_radix_lru_eviction_and_budget():
    pool, pc = _cache(pages=16, S=2, budget=3)
    a = pool.alloc(2)
    pc.insert([1, 2, 3, 4], a)             # chain a0 -> a1
    b = pool.alloc(2)
    pc.insert([9, 9, 8, 8], b)             # chain b0 -> b1
    pc.release(a + b)                      # all idle now
    # budget 3 < 4 pages: the LRU leaf goes — a's chain was touched
    # first, so its leaf a1 is the oldest evictable
    assert pc.num_pages == 3
    assert pc.evicted_pages == 1
    assert a[1] not in pc.member_mask().nonzero()[0]
    # interior nodes are never evicted while they have children: b0
    # still has b1 under it, so the next eviction takes a0 (leaf now)
    pc.budget_pages = 2
    pc.enforce_budget()
    assert pc.num_pages == 2
    assert pc.match([9, 9, 8, 8]) == b     # b's chain survived intact
    pc.release(b)


def test_radix_leased_pages_are_pinned():
    pool, pc = _cache(pages=4, S=2, budget=0)
    a = pool.alloc(1)
    pc.insert([5, 6], a)
    # lease still held by the "slot" (refcount 1): budget 0 cannot evict
    pc.enforce_budget()
    assert pc.num_pages == 1
    pc.release(a)                          # lease dropped -> evicted
    assert pc.num_pages == 0 and pool.num_free == 4


def test_radix_reclaim_frees_pool_pages():
    pool, pc = _cache(pages=4, S=2)
    a = pool.alloc(2)
    pc.insert([1, 2, 3, 4], a)
    pc.release(a)
    assert pool.num_free == 2
    assert pc.reclaim(3)                   # must evict one cached page
    assert pool.num_free >= 3
    assert pc.evicted_pages >= 1


# ---------------------------------------------------------------------------
# PagedKVCache satellites: table validation, offset prefill, CoW guard
# ---------------------------------------------------------------------------

def test_create_rejects_out_of_range_page_table():
    bad = np.array([[0, 1], [2, 7]], np.int32)     # page 7 of a 4-pool
    with pytest.raises(mx.MXNetError):
        PagedKVCache.create(1, 2, 1, 8, 2, page_size=4, num_pages=4,
                            page_table=bad)
    with pytest.raises(mx.MXNetError):
        PagedKVCache.create(1, 2, 1, 8, 2, page_size=4, num_pages=4,
                            page_table=np.array([[0, -1], [2, 3]]))
    # in-range tables still work
    ok = PagedKVCache.create(1, 2, 1, 8, 2, page_size=4, num_pages=4,
                             page_table=np.array([[3, 2], [1, 0]]))
    assert ok.page_table.shape == (2, 2)


def test_write_prompt_at_page_aligned_offset():
    S = 4
    cache = PagedKVCache.create(1, 1, 1, 16, 2, page_size=S)
    k = jnp.ones((1, 1, 2 * S, 2))
    # land the chunk at position 8 (page 2) by setting length first
    cache = PagedKVCache(cache.k_pages, cache.v_pages, cache.page_table,
                         jnp.asarray(2 * S, jnp.int32))
    _, _, cache = cache.write_prompt(0, k, 2 * k)
    pool = np.asarray(cache.k_pages)[0]
    table = np.asarray(cache.page_table)[0]
    assert (pool[table[0]] == 0).all() and (pool[table[1]] == 0).all()
    assert (pool[table[2]] == 1).all() and (pool[table[3]] == 1).all()


def test_write_prompt_rejects_ragged():
    cache = PagedKVCache.create(1, 2, 1, 8, 2, page_size=4,
                                lengths=jnp.zeros(2, jnp.int32))
    with pytest.raises(mx.MXNetError):
        cache.write_prompt(0, jnp.ones((2, 1, 4, 2)), jnp.ones((2, 1, 4, 2)))


def test_write_decode_drops_write_to_locked_page():
    """The CoW invariant, in-program: a page marked shared by page_lock
    is read-only for decode writes — the scatter drops."""
    B, H, D, S = 2, 1, 2, 4
    cache = PagedKVCache.create(1, B, H, 8, D, page_size=S,
                                lengths=jnp.asarray([1, 1], jnp.int32))
    # slot 0 writes into page_table[0,0]=0 (unlocked); slot 1 targets
    # page_table[1,0]=2, which the mask marks shared
    lock = jnp.zeros(4, bool).at[2].set(True)
    cache = PagedKVCache(cache.k_pages, cache.v_pages, cache.page_table,
                         cache.length, page_lock=lock)
    val = jnp.full((B, H, 1, D), 7.0)
    cache = cache.write_decode(0, val, val)
    pool = np.asarray(cache.k_pages)[0]
    assert pool[0, 1, 0] == 7.0            # unlocked write landed
    assert (pool[2] == 0).all()            # locked write dropped


# ---------------------------------------------------------------------------
# engine integration — the acceptance criteria
# ---------------------------------------------------------------------------

def _mixed_requests(cfg, rng, n=8, shared_frac=0.75, prefix_len=24,
                    max_new=6):
    """Interleaved traffic: most prompts extend one long shared system
    prefix with unique suffixes, the rest are fully distinct; greedy
    and sampled modes alternate."""
    system = rng.integers(0, cfg.vocab_size, prefix_len).tolist()
    reqs = []
    for i in range(n):
        if rng.random() < shared_frac:
            tail = rng.integers(0, cfg.vocab_size,
                                int(rng.integers(1, 9))).tolist()
            prompt = system + tail
        else:
            prompt = rng.integers(0, cfg.vocab_size,
                                  int(rng.integers(4, 20))).tolist()
        reqs.append(dict(prompt=prompt, max_new_tokens=max_new,
                         do_sample=bool(i % 2), temperature=0.8,
                         top_k=20, top_p=0.95, seed=300 + i,
                         request_id=i))
    return reqs


def _run(net, req_kws, **engine_kw):
    eng = ServingEngine(net, num_slots=3, max_length=64, page_size=8,
                        attn_impl="xla", **engine_kw)
    reqs = [Request(**kw) for kw in req_kws]
    eng.serve(reqs)
    return eng, {r.id: r.output_tokens for r in reqs}


def test_engine_prefix_cache_bit_identical_to_disabled():
    """The reproducibility guarantee extended: enabling the prefix cache
    must not change a single sampled or greedy token."""
    net, cfg = _tiny()
    rng = np.random.default_rng(11)
    kws = _mixed_requests(cfg, rng, n=10)
    eng_off, out_off = _run(net, kws)
    eng_on, out_on = _run(net, kws, prefix_cache=True)
    assert out_on == out_off
    s = eng_on.stats
    assert s["prefix_hits"] > 0
    assert s["prefix_tokens_saved"] > 0
    # the saved tokens really were not recomputed
    assert s["prefill_tokens"] + s["prefix_tokens_saved"] == \
        eng_off.stats["prefill_tokens"]
    assert eng_off.stats["prefix_hits"] == 0


def test_engine_prefix_cache_cow_fully_cached_prompt():
    """A prompt that is an exact multiple of the page size and fully
    cached triggers the copy-on-write split: only ONE token is
    recomputed, outputs stay identical, and the shared cached page is
    never written."""
    net, cfg = _tiny()
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, 16).tolist()   # 2 pages of 8
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", prefix_cache=True)
    (r1,) = eng.serve([Request(prompt, 5, request_id="a")])
    # the whole prompt is now cached; snapshot the tree's pages
    pc = eng.prefix_cache
    assert pc.num_pages >= 2
    tree_pages = sorted(pc._by_page)
    before = np.asarray(eng._kp[:, tree_pages])
    (r2,) = eng.serve([Request(prompt, 5, request_id="b")])
    assert r2.output_tokens == r1.output_tokens
    s = eng.stats
    assert s["prefix_tokens_saved"] >= 15      # Tp - 1 via CoW
    after = np.asarray(eng._kp[:, tree_pages])
    np.testing.assert_array_equal(before, after)


def test_engine_prefix_cache_hit_skips_prefill_tokens():
    net, cfg = _tiny()
    rng = np.random.default_rng(13)
    system = rng.integers(0, cfg.vocab_size, 32).tolist()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", prefix_cache=True)
    eng.serve([Request(system + [1, 2], 3, request_id=0)])
    base = eng.stats["prefill_tokens"]
    eng.serve([Request(system + [3, 4, 5], 3, request_id=1)])
    # the second request recomputed only its 3-token tail (bucketed)
    assert eng.stats["prefill_tokens"] - base <= 8
    assert eng.stats["prefix_tokens_saved"] >= 32


def test_engine_churn_respects_budget_and_refcount_baseline():
    """Admit/release far past the page budget: eviction keeps the tree
    within budget, every lease returns to zero after drain, and the
    pool's allocated set is exactly the retained tree pages."""
    net, cfg = _tiny()
    rng = np.random.default_rng(14)
    budget = 8
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla", prefix_cache=True,
                        prefix_cache_pages=budget)
    # 12 distinct prompts x 3 pages each = 36 pages of churn through an
    # 8-page budget
    reqs = [Request(rng.integers(0, cfg.vocab_size, 24).tolist(), 2,
                    request_id=i) for i in range(12)]
    eng.serve(reqs)
    pc, pool = eng.prefix_cache, eng.page_pool
    assert pc.num_pages <= budget
    assert eng.stats["prefix_evicted_pages"] > 0
    assert (pool.refcounts() == 0).all()       # every lease released
    assert pool.num_allocated == pc.num_pages  # only the tree holds pages
    # pool never grew past its physical size: free + allocated == total
    assert pool.num_free + pool.num_allocated == pool.num_pages


def test_engine_prefix_cache_disabled_pool_drains_clean():
    net, cfg = _tiny()
    rng = np.random.default_rng(15)
    eng = ServingEngine(net, num_slots=2, max_length=32, page_size=8,
                        attn_impl="xla")
    eng.serve([Request(rng.integers(0, cfg.vocab_size, 9).tolist(), 3,
                       request_id=i) for i in range(5)])
    assert eng.page_pool.num_free == eng.page_pool.num_pages
    assert (eng.page_pool.refcounts() == 0).all()


def test_engine_mid_flight_sharing():
    """A second request with the same prompt admitted while the first
    is STILL decoding attaches the first's pages mid-flight (refcount
    > 1 on the shared pages). Under chunked prefill the prompt's pages
    enter the radix tree when its FINAL chunk lands — a same-round
    co-admission can't share (the shared KV doesn't exist yet), but
    any admission after that dispatch does."""
    net, cfg = _tiny()
    rng = np.random.default_rng(16)
    prompt = rng.integers(0, cfg.vocab_size, 16).tolist()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", prefix_cache=True,
                        chunk_tokens=8)
    r1 = Request(prompt, 8, request_id="x")
    eng.submit(r1)
    # two 8-token chunks: the final one lands on the second dispatch
    # and adopts the prompt's pages into the tree
    eng.step()
    eng.step()
    assert len(r1.output_tokens) >= 1       # first token landed
    r2 = Request(prompt, 8, request_id="y")
    eng.submit(r2)
    eng.step()                              # r2 attaches, r1 mid-decode
    assert eng.stats["prefix_pages_shared"] >= 1
    assert r1.status == "running"
    while eng.has_work:
        eng.step()
    assert r1.output_tokens == r2.output_tokens


# ---------------------------------------------------------------------------
# cancel() — the robustness satellite
# ---------------------------------------------------------------------------

def test_cancel_queued_request():
    net, cfg = _tiny()
    rng = np.random.default_rng(17)
    eng = ServingEngine(net, num_slots=1, max_length=32, page_size=8,
                        attn_impl="xla")
    keep = Request(rng.integers(0, cfg.vocab_size, 4).tolist(), 3,
                   request_id="keep")
    drop = Request(rng.integers(0, cfg.vocab_size, 4).tolist(), 3,
                   request_id="drop")
    eng.submit(keep)
    eng.submit(drop)
    got = eng.cancel("drop")
    assert got is drop
    assert eng.cancel("never-submitted") is False
    assert eng.cancel("drop") is False      # idempotent double-cancel
    done = []
    while eng.has_work:
        done.extend(eng.step())
    assert [r.id for r in done] == ["keep"]
    assert drop.output_tokens == []
    assert eng.stats["requests_cancelled"] == 1
    assert eng.scheduler.num_free == 1


def test_cancel_running_request_frees_slot_and_pages():
    """Cancelling mid-decode releases the slot and its page leases
    immediately — an abandoned request no longer holds its slot until
    max_new_tokens."""
    net, cfg = _tiny()
    rng = np.random.default_rng(18)
    eng = ServingEngine(net, num_slots=1, max_length=32, page_size=8,
                        attn_impl="xla", prefix_cache=True)
    hog = Request(rng.integers(0, cfg.vocab_size, 6).tolist(), 24,
                  request_id="hog")
    nxt = Request(rng.integers(0, cfg.vocab_size, 6).tolist(), 4,
                  request_id="next")
    eng.submit(hog)
    eng.submit(nxt)
    eng.step()                              # hog admitted + one block
    assert eng.scheduler.slot_of("hog") == 0
    emitted_before = len(hog.output_tokens)
    got = eng.cancel("hog")
    assert got is hog
    assert eng.scheduler.num_active == 0
    assert (eng.page_pool.refcounts() <= 1).all()
    done = []
    while eng.has_work:
        done.extend(eng.step())
    assert [r.id for r in done] == ["next"]
    assert len(hog.output_tokens) == emitted_before   # nothing after
    assert len(nxt.output_tokens) == 4
    assert eng.stats["requests_cancelled"] == 1
    # cancelled slots never count as finished
    assert eng.stats["requests_finished"] == 1


# ---------------------------------------------------------------------------
# concurrent submit() racing QueueFullError — counter exactness
# ---------------------------------------------------------------------------

def test_concurrent_submit_rejection_counter_is_exact():
    """Multithreaded soak: every submit() either lands in the queue or
    raises QueueFullError and bumps the rejection counter — rejected ==
    submitted - admitted, no drops, no double counts."""
    net, cfg = _tiny()
    eng = ServingEngine(net, num_slots=2, max_length=16, page_size=8,
                        attn_impl="xla", max_queue=6)
    n_threads, per_thread = 6, 20
    admitted = []
    rejected = []
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        rng = np.random.default_rng(tid)
        barrier.wait()
        for i in range(per_thread):
            req = Request(rng.integers(0, cfg.vocab_size, 3).tolist(), 1,
                          request_id=f"{tid}-{i}")
            try:
                eng.submit(req)
                admitted.append(req.id)
            except QueueFullError:
                rejected.append(req.id)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    submitted = n_threads * per_thread
    assert len(admitted) + len(rejected) == submitted
    assert eng.stats["requests_rejected"] == len(rejected)
    # drain what was admitted; the engine serves exactly that set
    done = []
    while eng.has_work:
        done.extend(eng.step())
    assert sorted(r.id for r in done) == sorted(admitted)
    assert eng.stats["requests_finished"] == len(admitted)
