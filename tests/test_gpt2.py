"""GPT-2 + KV-cache decode tests (parity target: GluonNLP GPT-2 text
generation, SURVEY.md §3.5/M9). The oracle: cached decode must match the
reference's way — full-recompute greedy decode — token for token."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import (GPT2Config, GPT2ForCausalLM, KVCache,
                              PagedKVCache)


def _tiny(vocab=97, layers=2, units=32, heads=2, max_len=64):
    cfg = GPT2Config(vocab_size=vocab, units=units, num_layers=layers,
                     num_heads=heads, max_length=max_len, dropout=0.0,
                     attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    return net, cfg


def _greedy_full_recompute(net, ids, n_new):
    """The reference's decode: re-run the whole prefix every step."""
    ids = np.asarray(ids)
    for _ in range(n_new):
        logits = net(mx.nd.array(ids, dtype="int32"))
        nxt = logits.asnumpy()[:, -1, :].argmax(-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids[:, -n_new:]


def test_forward_shapes():
    net, cfg = _tiny()
    logits = net(mx.nd.array(np.zeros((2, 8)), dtype="int32"))
    assert logits.shape == (2, 8, cfg.vocab_size)


def test_cached_forward_matches_full():
    """Prefill+decode through the cache == one full causal forward."""
    net, cfg = _tiny()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 10)).astype(np.int32)
    full = net(mx.nd.array(ids, dtype="int32")).asnumpy()

    cache = net.make_cache(2, 16)
    out1, cache = net(mx.nd.array(ids[:, :7], dtype="int32"), cache)
    outs = [out1.asnumpy()]
    for t in range(7, 10):
        o, cache = net(mx.nd.array(ids[:, t:t + 1], dtype="int32"), cache)
        outs.append(o.asnumpy())
    step = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(step, full, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True])
def test_greedy_decode_matches_full_recompute(paged):
    net, cfg = _tiny()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    n_new = 8
    want = _greedy_full_recompute(net, prompt, n_new)
    got = net.generate(mx.nd.array(prompt, dtype="int32"), n_new,
                       paged=paged, page_size=8).asnumpy()
    np.testing.assert_array_equal(got, want)


def test_generate_eos_padding():
    net, cfg = _tiny()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, (2, 4)).astype(np.int32)
    free_run = net.generate(mx.nd.array(prompt, dtype="int32"), 6).asnumpy()
    eos = int(free_run[0, 2])  # force an early stop on row 0's 3rd token
    got = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                       eos_token_id=eos).asnumpy()
    # tokens before the hit match the unconstrained run; the eos token is
    # emitted; everything after is eos padding
    np.testing.assert_array_equal(got[0, :3], free_run[0, :3])
    assert (got[0, 3:] == eos).all()


def test_sampled_decode_reproducible_and_valid():
    net, cfg = _tiny()
    prompt = np.zeros((2, 3), np.int32)
    a = net.generate(mx.nd.array(prompt, dtype="int32"), 5, do_sample=True,
                     temperature=0.8, top_k=10, seed=7).asnumpy()
    b = net.generate(mx.nd.array(prompt, dtype="int32"), 5, do_sample=True,
                     temperature=0.8, top_k=10, seed=7).asnumpy()
    np.testing.assert_array_equal(a, b)
    assert ((a >= 0) & (a < cfg.vocab_size)).all()


def test_top_p_sampling():
    net, cfg = _tiny()
    prompt = np.zeros((2, 3), np.int32)
    # top_p=0 keeps ONLY the top token → exactly greedy
    tp = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                      do_sample=True, top_p=0.0, seed=1).asnumpy()
    greedy = net.generate(mx.nd.array(prompt, dtype="int32"), 6).asnumpy()
    np.testing.assert_array_equal(tp, greedy)
    # p=1 keeps the whole distribution == plain sampling, same seed
    full = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                        do_sample=True, top_p=1.0, seed=4).asnumpy()
    plain = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                         do_sample=True, seed=4).asnumpy()
    np.testing.assert_array_equal(full, plain)
    # truncating nucleus is reproducible and in-vocab; combines w/ top_k
    a = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                     do_sample=True, top_p=0.9, top_k=20, seed=4).asnumpy()
    b = net.generate(mx.nd.array(prompt, dtype="int32"), 6,
                     do_sample=True, top_p=0.9, top_k=20, seed=4).asnumpy()
    np.testing.assert_array_equal(a, b)
    assert ((a >= 0) & (a < cfg.vocab_size)).all()


def test_kv_cache_contiguous_roundtrip():
    cache = KVCache.create(num_layers=2, batch=2, num_heads=3, max_length=8,
                           head_dim=4)
    k = jnp.ones((2, 3, 1, 4))
    k_all, v_all, cache = cache.write(1, k, 2 * k)
    assert k_all.shape == (2, 3, 8, 4)
    np.testing.assert_allclose(np.asarray(k_all[:, :, 0]), 1.0)
    np.testing.assert_allclose(np.asarray(v_all[:, :, 0]), 2.0)
    np.testing.assert_allclose(np.asarray(k_all[:, :, 1:]), 0.0)
    cache = cache.advance(1)
    assert int(cache.length) == 1
    np.testing.assert_array_equal(np.asarray(cache.key_mask()),
                                  [True] + [False] * 7)


def test_paged_cache_gather_through_permuted_table():
    """Real paging: a permuted page table must give the same view."""
    rng = np.random.default_rng(0)
    B, H, T, D, S = 2, 2, 16, 4, 4
    k = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    ident = PagedKVCache.create(1, B, H, T, D, page_size=S)
    ka, va, _ = ident.write_prompt(0, k, v)
    np.testing.assert_allclose(np.asarray(ka), np.asarray(k), rtol=1e-6)

    perm = rng.permutation(B * (T // S)).astype(np.int32)
    table = perm.reshape(B, T // S)
    permuted = PagedKVCache.create(1, B, H, T, D, page_size=S,
                                   page_table=jnp.asarray(table))
    kp, vp, _ = permuted.write_prompt(0, k, v)
    np.testing.assert_allclose(np.asarray(kp), np.asarray(k), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vp), np.asarray(v), rtol=1e-6)


def test_paged_decode_write_lands_in_right_page():
    B, H, D, S = 1, 1, 2, 4
    cache = PagedKVCache.create(1, B, H, 8, D, page_size=S)
    for t in range(6):
        val = jnp.full((B, H, 1, D), float(t + 1))
        k_all, _, cache = cache.write(0, val, val)
        cache = cache.advance(1)
    got = np.asarray(k_all)[0, 0, :, 0]
    np.testing.assert_allclose(got, [1, 2, 3, 4, 5, 6, 0, 0])
    # 6 tokens span 2 physical pages of size 4
    pool = np.asarray(cache.k_pages)[0]
    assert (pool[0, :, 0] == [1, 2, 3, 4]).all()
    assert (pool[1, :2, 0] == [5, 6]).all()


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices for the tp=2 mesh")
def test_generate_tensor_parallel_matches_single_device():
    """Sharded decode: generate() over a tp mesh with megatron-sharded
    params must emit the same greedy tokens as single-device."""
    from mxnet_tpu import parallel as par

    net, cfg = _tiny(vocab=96, heads=4, units=32)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (2, 5)).astype(np.int32)
    want = net.generate(mx.nd.array(prompt, dtype="int32"), 8).asnumpy()
    par.apply_sharding_rules(net, par.megatron_dense_rules(tp_axis="tp"))
    mesh = par.make_mesh(tp=2, devices=jax.devices()[:2])
    got = net.generate(mx.nd.array(prompt, dtype="int32"), 8,
                       mesh=mesh).asnumpy()
    np.testing.assert_array_equal(got, want)
    # paged cache shards too
    got_p = net.generate(mx.nd.array(prompt, dtype="int32"), 8,
                         mesh=mesh, paged=True, page_size=8).asnumpy()
    np.testing.assert_array_equal(got_p, want)


def test_gpt2_774m_config_param_count():
    cfg = mx.models.gpt2_774m_config()
    # published GPT-2 large is ~774M params
    assert 0.72e9 < cfg.num_params() < 0.82e9
