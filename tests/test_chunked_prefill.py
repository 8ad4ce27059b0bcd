"""ISSUE 11: the unified chunked-prefill dispatch.

Two layers of oracle. The span kernel (per-slot query counts) is
checked against the dense XLA reference over mixed batches — decode,
verify, prefill-chunk, and idle rows riding ONE dispatch — plus the
q_counts edge cases and bf16. The engine is checked against a GOLDEN
token capture (tests/data/chunked_prefill_golden.json) recorded from
the pre-unification bucketed engine on mixed greedy/sampled traffic
across the plain, prefix-cache (incl. fully-cached CoW), speculative,
and adapter paths: the unified engine must reproduce every stream
bit-for-bit, at ANY chunk_tokens setting.

Plus the chunked-admission fairness bar: a long prompt streaming in
chunks must not stall other slots' decode — every running request
keeps emitting one token per dispatch while the long prefill is in
flight.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import Request, ServingEngine
from mxnet_tpu.serving.adapters import AdapterPool

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "chunked_prefill_golden.json")


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64, seed=3):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(seed)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


# ---------------------------------------------------------------------------
# span kernel vs the dense oracle
# ---------------------------------------------------------------------------

def _pool(B=5, H=2, D=16, S=8, P=4, Sq=8, dtype=jnp.float32, seed=0,
          layers=1):
    """Pools packed as PagedKVCache stores them: (layers, N, S, H*D),
    heads-major in the last axis, every layer other data."""
    rng = np.random.default_rng(seed)
    N = B * P
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((layers, N, S, H * D)), dtype)
    vp = jnp.asarray(rng.standard_normal((layers, N, S, H * D)), dtype)
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    return q, kp, vp, table


def test_span_kernel_mixed_batch_one_dispatch():
    """One dispatch carrying every work kind at once: decode (1),
    verify (4), full-width prefill chunk (Sq), idle (0), and a
    non-page-aligned chunk tail (5) — kernel vs dense oracle, and dead
    rows emit EXACT zeros."""
    q, kp, vp, table = _pool()
    L = jnp.asarray([9, 17, 1, 30, 12], jnp.int32)
    qc = jnp.asarray([1, 4, 8, 0, 5], jnp.int32)
    ref = pa._ragged_span_reference(q, kp, vp, table, L, qc,
                                    1.0 / np.sqrt(16))
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qc,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dead = np.arange(8)[None, :] >= np.asarray(qc)[:, None]
    assert (np.asarray(out)[dead] == 0).all()
    assert (np.asarray(ref)[dead] == 0).all()


@pytest.mark.parametrize("qc", [[0, 0, 0, 0, 0], [1, 1, 1, 1, 1],
                                [8, 8, 8, 8, 8], [3, 7, 2, 6, 1]])
def test_span_kernel_q_counts_edges(qc):
    """q_counts edges: all-idle, all-decode, all-full, and ragged
    non-aligned tails."""
    q, kp, vp, table = _pool(seed=1)
    L = jnp.asarray([5, 1, 24, 13, 8], jnp.int32)
    qcj = jnp.asarray(qc, jnp.int32)
    ref = pa._ragged_span_reference(q, kp, vp, table, L, qcj,
                                    1.0 / np.sqrt(16))
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qcj,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_span_kernel_full_counts_match_mq_kernel():
    """q_counts = Sq everywhere IS the multi-query verify kernel —
    same mask, same online-softmax walk, bitwise."""
    q, kp, vp, table = _pool(seed=2)
    L = jnp.asarray([4, 11, 27, 2, 19], jnp.int32)
    full = pa.ragged_span_attention(
        q, kp, vp, table, L, q_counts=jnp.full((5,), 8, jnp.int32),
        interpret=True)
    mq = pa.ragged_mq_decode_attention(q, kp, vp, table, L,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(mq))


def test_span_kernel_bf16_tolerance():
    q, kp, vp, table = _pool(dtype=jnp.bfloat16, seed=3)
    L = jnp.asarray([7, 20, 13, 3, 26], jnp.int32)
    qc = jnp.asarray([2, 8, 0, 1, 6], jnp.int32)
    ref = pa._ragged_span_reference(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), table, L, qc, 1.0 / np.sqrt(16))
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qc,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("sq", [1, 64])
@pytest.mark.parametrize("layer", [0, 2])
def test_span_kernel_reads_its_layer_of_the_packed_pool(sq, layer):
    """The kernel is handed the whole (L, N, S, H*D) pools and picks
    `layer` in its page BlockSpec: at Sq=1 (decode) and Sq=64 (a full
    prefill chunk) it equals the dense reference on that layer, and a
    one-layer pool cut out by hand gives the same bits."""
    q, kp, vp, table = _pool(S=64, Sq=sq, seed=5, layers=3)
    L = jnp.asarray([9, 70, 1, 256 - sq + 1, 130], jnp.int32)
    qc = jnp.asarray([sq, max(sq // 2, 1), sq, sq, 0], jnp.int32)
    ref = pa._ragged_span_reference(q, kp, vp, table, L, qc,
                                    1.0 / np.sqrt(16), layer=layer)
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qc,
                                   impl="pallas", interpret=True,
                                   layer=layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    alone = pa.ragged_span_attention(
        q, kp[layer:layer + 1], vp[layer:layer + 1], table, L,
        q_counts=qc, impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))


def test_span_kernel_rows_equal_isolated_chunks():
    """Chunk-size invariance at the kernel level: rows [0, c) computed
    in one call with q_counts=c must equal the same rows computed as
    two smaller spans (the second at lengths + c1) — the algebra the
    engine's bit-identity across chunk_tokens settings rests on."""
    q, kp, vp, table = _pool(seed=4)
    L = jnp.asarray([4, 9, 1, 15, 22], jnp.int32)
    whole = pa.ragged_span_attention(
        q, kp, vp, table, L, q_counts=jnp.full((5,), 6, jnp.int32),
        interpret=True)
    first = pa.ragged_span_attention(
        q[:, :4], kp, vp, table, L, q_counts=jnp.full((5,), 4, jnp.int32),
        interpret=True)
    second = pa.ragged_span_attention(
        q[:, 4:6], kp, vp, table, L + 4,
        q_counts=jnp.full((5,), 2, jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(whole[:, :4]),
                               np.asarray(first), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole[:, 4:6]),
                               np.asarray(second), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the block of pages a grid step attends: its edges
# ---------------------------------------------------------------------------
# S=8, P=7 (a prime: no block larger than a page divides it), Sq=8. W is
# the block's keys; (lengths, q_counts) are given in units the case names.

def _edge_cases(W):
    return {
        # the furthest live key is the last of a block, the first of the
        # next, and the one before the block's last
        "context_ends_one_before_a_block_boundary": ([W - 1], [1]),
        "context_ends_on_a_block_boundary": ([W], [1]),
        "context_ends_one_after_a_block_boundary": ([W + 1], [1]),
        "span_crosses_a_block_boundary": ([W - 3], [8]),
        "span_ends_on_the_last_key_of_the_table": ([7 * 8 - 7], [8]),
        "idle_slot_between_two_busy_ones": ([W + 5, W + 5, W + 5],
                                            [8, 0, 8]),
        "counts_0_1_and_Sq_in_one_call": ([2 * W - 1, 3, W + 2], [0, 1, 8]),
        "one_page_slots_shorter_than_a_block": ([1, 8, 2], [1, 1, 7]),
    }


def _int8_pool(kp, vp, H=2):
    """(codes, scales) of float pools (L, N, S, H*D), a scale a page a
    head, as PagedKVCache quantizes."""
    out = []
    for x in (kp, vp):
        L, N, S, HD = x.shape
        x = np.asarray(x).reshape(L, N, S, H, HD // H)
        sc = np.abs(x).max(axis=(2, 4)) / 127.0            # (L, N, H)
        codes = np.round(x / sc[:, :, None, :, None]).reshape(L, N, S, HD)
        out += [jnp.asarray(codes, jnp.int8), jnp.asarray(sc, jnp.float32)]
    return out


@pytest.mark.parametrize("pages", ["float32", "int8"])
@pytest.mark.parametrize("kb", [None, 2, 3])
@pytest.mark.parametrize("case", sorted(_edge_cases(0)))
def test_span_kernel_block_edges(monkeypatch, case, kb, pages):
    """Every edge of the key block against the dense reference, dead rows
    EXACT zeros: at the block the adaptive rule picks here (4 pages of
    the 7) and at 2 and 3 pages a step, over float and int8 pages, the
    page table a permutation so that no block is contiguous in the pool."""
    if kb is not None:
        monkeypatch.setattr(pa, "_span_block_pages", lambda S, Sr, P: kb)
    W = 8 * (kb or pa._span_block_pages(8, 8, 7))
    assert W == 8 * (kb or 4)
    lens, counts = _edge_cases(W)[case]
    q, kp, vp, table = _pool(B=len(lens), P=7, seed=11)
    assert (np.diff(np.asarray(table), axis=1) != 1).any()
    kw = {}
    if pages == "int8":
        kp, ks, vp, vs = _int8_pool(kp, vp)
        kw = dict(k_scale=ks, v_scale=vs)
    L, qc = jnp.asarray(lens, jnp.int32), jnp.asarray(counts, jnp.int32)
    ref = pa._ragged_span_reference(q, kp, vp, table, L, qc,
                                    1.0 / np.sqrt(16), **kw)
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qc,
                                   impl="pallas", interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dead = np.arange(8)[None, :] >= np.asarray(counts)[:, None]
    assert (np.asarray(out)[dead] == 0).all()
    live = ~dead & (np.asarray(lens) > 0)[:, None]
    assert np.abs(np.asarray(out)[live]).min() > 0


def test_span_kernel_fetches_each_live_page_once_and_no_other():
    """The block table, walked as the pipeline walks the grid (an operand
    starts a DMA when its block index differs from the step before):
    every live page of every slot is fetched exactly once, by the
    operand of its place in the block; a slot shorter than a block, an idle slot
    and the steps past a slot's extent fetch NOTHING (the operand keeps
    the page it held, across slots too); and every index stays inside a
    table that KB does not divide."""
    S, KB, P = 8, 4, 7
    table = np.arange(5 * P, dtype=np.int32).reshape(5, P)[::-1]
    lens = np.asarray([P * S - 7, 4 * S + 1, 2, 30, 3 * S], np.int32)
    counts = np.asarray([8, 1, 1, 0, 2], np.int32)
    n_live = [7, 5, 1, 0, 4]
    pages = np.asarray(pa._span_block_table(
        jnp.asarray(table), jnp.asarray(lens), jnp.asarray(counts), S, KB))
    assert pages.shape == (5, 2 * KB)
    assert set(pages.ravel()) <= set(table.ravel())
    steps = pages.reshape(-1, KB)               # grid order, (b, p) flat
    fetched = [int(pg) for i in range(KB) for n, pg in enumerate(steps[:, i])
               if n == 0 or pg != steps[n - 1, i]]
    live = [int(pg) for b in range(5) for pg in table[b, :n_live[b]]]
    assert sorted(fetched) == sorted(live)      # slot 0 is live from step 0
    for b in range(5):                          # and where it belongs
        for j in range(n_live[b]):
            assert pages[b, j] == table[b, j]


def test_span_kernel_records_the_block_it_was_built_with():
    from mxnet_tpu.ops import kernel_paths
    key = ("ragged_span_attention", "pages=4,keys=32,rows=8")
    before = kernel_paths.TILES.get(key, 0)
    q, kp, vp, table = _pool(P=7)
    L = jnp.asarray([9, 17, 1, 30, 12], jnp.int32)
    pa.ragged_span_attention(q, kp, vp, table, L, interpret=True)
    assert kernel_paths.TILES[key] == before + 1
    n = sum(kernel_paths.TILES.values())
    pa.ragged_span_attention(q, kp, vp, table, L, impl="xla")
    assert sum(kernel_paths.TILES.values()) == n      # no kernel, no tile


@pytest.mark.parametrize("S,Sr,P,want", [
    (64, 64, 16, 16),     # gpt2_774m.doc_backlog: a slot's 1024 keys a step
    (64, 320, 10, 4),     # falcon_h1_34b.chat_backlog: 5 heads x 64 rows
    (64, 1, 16, 16),      # a decode-only call: the key limit binds
    (64, 64, 2, 2),       # never more than the slot's pages
    (64, 64, 64, 16),     # nor more than 1024 keys
    (64, 1024, 16, 2),    # rows so tall that two pages fill the scores
    (256, 64, 8, 4),      # wide pages
    (64, 64, 10, 8),      # a power of two under a P that is none
    (8, 8, 7, 4),         # the tests' own shapes
])
def test_span_block_rule(S, Sr, P, want):
    """The adaptive rule as ops/pallas_attention.py documents it: up to
    1024 keys and 512 KiB of float32 scores a head, a power of two, at
    most the slot's pages."""
    assert pa._span_block_pages(S, Sr, P) == want


@pytest.mark.parametrize("H,D,want", [
    (20, 64, (4, True)),      # GPT-2 774M: five passes of 256 lanes
    (12, 64, (4, True)),
    (4, 128, (4, False)),     # Falcon-H1's KV heads: one pass, inline
    (8, 128, (4, True)),
    (6, 64, (2, True)),       # 4 does not divide 6: two heads, 128 lanes
    (2, 16, (1, False)),      # these tests' heads: a head at a time
    (3, 64, (1, False)),      # 192 columns: no whole tile of heads
])
def test_span_passes(H, D, want):
    assert pa._span_passes(H, D) == want


@pytest.mark.parametrize("pages", ["float32", "int8"])
@pytest.mark.parametrize("H,D", [(8, 64), (6, 64), (4, 128), (3, 64)])
def test_span_kernel_head_loop_rolled_and_unrolled(H, D, pages):
    """The head loop as the chip runs it: rolled over passes of four or
    two 64-wide heads (a dynamic lane slice of every operand, the int8
    scales picked by lane), one inline pass of four 128-wide heads, and a
    head at a time where 3 x 64 columns give no whole tile; blocks of 4
    of 6 pages, mixed work, against the dense reference."""
    q, kp, vp, table = _pool(B=4, H=H, D=D, P=6, seed=13)
    kw = {}
    if pages == "int8":
        kp, ks, vp, vs = _int8_pool(kp, vp, H)
        kw = dict(k_scale=ks, v_scale=vs)
    L = jnp.asarray([31, 33, 7, 41], jnp.int32)
    qc = jnp.asarray([8, 1, 0, 5], jnp.int32)
    ref = pa._ragged_span_reference(q, kp, vp, table, L, qc,
                                    1.0 / np.sqrt(D), **kw)
    out = pa.ragged_span_attention(q, kp, vp, table, L, q_counts=qc,
                                   impl="pallas", interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    dead = np.arange(8)[None, :] >= np.asarray(qc)[:, None]
    assert (np.asarray(out)[dead] == 0).all()


# ---------------------------------------------------------------------------
# engine bit-identity vs the pre-unification golden capture
# ---------------------------------------------------------------------------
# The workloads below are byte-for-byte the ones the golden file was
# captured with on the bucketed (pre-ISSUE 11) engine at its last
# commit — same model seed, same request streams, same sampling
# settings. Do not change them without re-deriving the golden file.

def _plain_reqs(cfg, rng, tag, n=6, sampled_every=2):
    out = []
    for i in range(n):
        plen = int(rng.integers(1, 30))
        p = rng.integers(0, cfg.vocab_size, plen).tolist()
        out.append(Request(p, int(rng.integers(2, 10)),
                           do_sample=(i % sampled_every == 0),
                           temperature=0.8, top_k=20, top_p=0.95,
                           seed=1000 + i, request_id=f"{tag}-{i}"))
    return out


@pytest.fixture(scope="module")
def golden():
    return json.load(open(GOLDEN))


def _serve(eng, rs):
    eng.serve(rs)
    return {r.id: r.output_tokens for r in rs}


@pytest.mark.parametrize("chunk_tokens", [None, 4, 64])
def test_engine_plain_bit_identity(golden, chunk_tokens):
    """Mixed greedy/sampled traffic: the unified engine reproduces the
    bucketed engine's streams bit-for-bit — and the chunk size is
    invisible in the tokens (1-token-at-a-time prefill, page-sized,
    and whole-prompt chunks all emit the same streams)."""
    net, cfg = _tiny()
    rng = np.random.default_rng(42)
    eng = ServingEngine(net, num_slots=3, max_length=64, page_size=8,
                        attn_impl="xla", chunk_tokens=chunk_tokens)
    assert _serve(eng, _plain_reqs(cfg, rng, "plain")) == golden["plain"]


def test_engine_prefix_cache_bit_identity(golden):
    """Shared-prefix traffic (incl. a fully-cached prompt -> CoW
    resume): cache hits seed the chunk cursor past the shared pages
    and the emitted streams stay bit-identical."""
    net, cfg = _tiny()
    rng = np.random.default_rng(42)
    _plain_reqs(cfg, rng, "burn")           # advance rng as captured
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", prefix_cache=True)
    base = rng.integers(0, cfg.vocab_size, 16).tolist()
    prs = [Request(base + rng.integers(0, cfg.vocab_size,
                                       int(rng.integers(0, 6))).tolist(),
                   6, do_sample=(i % 2 == 0), temperature=0.9, top_k=15,
                   seed=2000 + i, request_id=f"px-{i}")
           for i in range(5)]
    prs.append(Request(base, 4, request_id="px-full"))  # fully cached
    assert _serve(eng, prs) == golden["prefix"]


def test_engine_speculative_bit_identity(golden):
    """Speculative engines dispatch the SAME unified program with
    n_draft=0 during prefill (and in degraded mode) — verify rows and
    the final-chunk first-token sample stay bit-identical."""
    net, cfg = _tiny()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", speculative=True, spec_tokens=4)
    pat = [5, 6, 7, 8]
    srs = [Request(pat * 3, 8, do_sample=(i == 0), temperature=0.7,
                   top_k=12, seed=3000 + i, request_id=f"sp-{i}")
           for i in range(4)]
    assert _serve(eng, srs) == golden["spec"]


def test_engine_adapter_bit_identity(golden):
    net, cfg = _tiny()
    rng = np.random.default_rng(42)
    _plain_reqs(cfg, rng, "burn")
    rng.integers(0, cfg.vocab_size, 16)     # prefix-base draw
    for i in range(5):
        rng.integers(0, cfg.vocab_size, int(rng.integers(0, 6)))
    pool = AdapterPool(cfg, slots=2, max_rank=4)
    wrng = np.random.default_rng(7)
    r = 2
    pool.register("ad1", {
        "A": wrng.standard_normal(
            (4, cfg.num_layers, cfg.units, r)).astype(np.float32) * 0.05,
        "B": wrng.standard_normal(
            (4, cfg.num_layers, r, cfg.units)).astype(np.float32) * 0.05,
        "alpha": 4.0, "rank": r})
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", adapter_pool=pool)
    ars = [Request(rng.integers(0, cfg.vocab_size, 7).tolist(), 5,
                   do_sample=(i == 1), temperature=0.8, top_k=10,
                   seed=4000 + i, request_id=f"ad-{i}",
                   adapter_id="ad1" if i % 2 else None)
           for i in range(4)]
    assert _serve(eng, ars) == golden["adapter"]


# ---------------------------------------------------------------------------
# chunked-admission fairness: long prefills must not starve decoders
# ---------------------------------------------------------------------------

def test_long_prefill_does_not_starve_decoders():
    """The starvation bar: while a long prompt streams its chunks, the
    already-running slots keep emitting EXACTLY one token per dispatch
    — chunked prefill rides along, it never displaces decode rows.
    (The bucketed engine froze every decoder for the whole monolithic
    prefill dispatch.)"""
    net, cfg = _tiny()
    rng = np.random.default_rng(9)
    eng = ServingEngine(net, num_slots=3, max_length=64, page_size=8,
                        attn_impl="xla", chunk_tokens=8)
    short = [Request(rng.integers(0, cfg.vocab_size, 3).tolist(), 20,
                     request_id=f"s{i}") for i in range(2)]
    for r in short:
        eng.submit(r)
    eng.step()                        # both shorts prefill (one chunk)
    eng.step()                        # ...and start decoding
    counts = {r.id: len(r.output_tokens) for r in short}
    assert all(c >= 1 for c in counts.values())
    long = Request(rng.integers(0, cfg.vocab_size, 48).tolist(), 2,
                   request_id="long")
    eng.submit(long)
    # 48 tokens / chunk_tokens=8 -> 6 chunk dispatches before the
    # long prompt's first token; the shorts advance 1/dispatch anyway
    steps_to_first = 0
    while not long.output_tokens:
        eng.step()
        steps_to_first += 1
        for r in short:
            if r.status == "running":
                got = len(r.output_tokens) - counts[r.id]
                assert got == 1, \
                    f"{r.id} got {got} tokens while long prefill ran"
                counts[r.id] = len(r.output_tokens)
    assert steps_to_first == 48 // 8
    assert eng.stats["prefill_chunks"] >= 6 + 2
    assert eng.stats["prefill_pending"] == 0


def test_prefill_chunk_budget_round_robins_concurrent_prompts():
    """Two long prompts under a budget that covers only ONE chunk per
    dispatch: the rotating cursor alternates slots, both finish, and
    no dispatch exceeds the budget."""
    net, cfg = _tiny()
    rng = np.random.default_rng(10)
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=8,
                        attn_impl="xla", chunk_tokens=8,
                        prefill_chunk_budget=8)
    longs = [Request(rng.integers(0, cfg.vocab_size, 24).tolist(), 2,
                     request_id=f"L{i}") for i in range(2)]
    for r in longs:
        eng.submit(r)
    steps = 0
    pending_seen = []
    while eng.has_work:
        eng.step()
        steps += 1
        pending_seen.append(eng.stats["prefill_pending"])
        assert steps < 50
    # 2 prompts x 3 chunks = 6 chunk dispatches minimum at 1/dispatch
    assert eng.stats["prefill_chunks"] == 6
    for r in longs:
        assert r.status == "finished"
        assert len(r.output_tokens) == 2
    # the queue drained monotonically 8 tokens a step while prefilling
    assert pending_seen[0] == 48 - 8
    assert pending_seen[1] == 48 - 16


def test_prefill_pending_gauge_and_ttft_histogram():
    """The chunk-queue gauge rises at admission and drains to zero;
    the per-prompt-length TTFT histogram lands the request in its
    power-of-two bucket."""
    from mxnet_tpu import telemetry

    net, cfg = _tiny()
    rng = np.random.default_rng(11)
    eng = ServingEngine(net, num_slots=1, max_length=64, page_size=8,
                        attn_impl="xla", chunk_tokens=8)
    eng.serve([Request(rng.integers(0, cfg.vocab_size, 20).tolist(), 2,
                       request_id="t")])
    assert eng.stats["prefill_pending"] == 0
    assert eng.stats["prefill_chunks"] == 3      # ceil(20 / 8)
    h = telemetry.get("serving_ttft_by_prompt_seconds")
    child = h.labels(str(eng._eid), "le32", "cold")   # 16 < 20 <= 32
    assert child.count == 1
