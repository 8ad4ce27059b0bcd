"""models/hybrid.py `over_live_rows`: a dispatch's row-wise feed-forward
over its live rows alone where they fit an eighth of the grid. Counts and
identities on the CPU: the two forms on scattered live rows, the
conditionals a program holds, a tiny Falcon-H1 behind ServingEngine whose
ticks fit or do not, and the counter that says which."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import models
from mxnet_tpu.models import hybrid
from mxnet_tpu.serving import Request, ServingEngine

B, W, C = 4, 16, 24
T = B * W // hybrid.COMPACT_GRID_SHARE


def _feed_forward(seed=0):
    """A row-wise function of (rows, C) that does NOT map zero to zero."""
    rng = np.random.default_rng(seed)
    w1 = jnp.asarray(rng.standard_normal((C, 40)) / C ** 0.5, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((40, C)) / 40, jnp.float32)
    return lambda rows: jnp.tanh(rows @ w1 + 0.5) @ w2 + 1.0


def test_the_size_is_one_constant_of_the_shape():
    assert hybrid.COMPACT_GRID_SHARE == 8 and T == 8
    fits, first, rank = hybrid.pick_live_rows(jnp.ones((B, W), bool))
    assert (bool(fits), first.shape, rank.shape) == (False, (T,), (B * W,))
    # a grid of under eight rows still has a row to gather
    assert hybrid.pick_live_rows(jnp.ones((1, 1), bool))[1].shape == (1,)


@pytest.mark.parametrize("count", [0, 1, T - 1, T, T + 1, B * W])
def test_compact_and_full_agree_on_live_rows_and_dead_rows_get_zeros(count):
    """Live rows scattered over the grid, not a prefix of it. Up to T of
    them the compact form: every live row has the full form's value, every
    dead row an exact zero. Past T the full form, dead rows and all."""
    rng = np.random.default_rng(count)
    flat = np.zeros(B * W, bool)
    flat[rng.permutation(B * W)[:count]] = True
    live = jnp.asarray(flat.reshape(B, W))
    v = jnp.asarray(rng.standard_normal((B, W, C)), jnp.float32)
    fn = _feed_forward()
    want = np.asarray(fn(v.reshape(-1, C)))
    got = jax.jit(lambda v, live: hybrid.over_live_rows(
        fn, v, live, hybrid.pick_live_rows(live)))(v, live)
    assert got.shape == (B, W, C)
    got = np.asarray(got).reshape(-1, C)
    if count <= T:
        np.testing.assert_allclose(got[flat], want[flat], rtol=1e-6,
                                   atol=1e-6)
        assert (got[~flat] == 0).all() and np.abs(want[~flat]).min() > 0
    else:
        np.testing.assert_array_equal(got, want)
    fits, first, rank = map(np.asarray, hybrid.pick_live_rows(live))
    assert fits == (count <= T)
    k = min(count, T)
    assert list(first[:k]) == list(np.flatnonzero(flat)[:k])
    assert list(rank[flat][:k]) == list(range(k))
    assert rank.min() >= 0 and rank.max() < T and first.max() < B * W


def test_without_a_pick_the_function_is_called_directly():
    v = jnp.ones((B, W, C), jnp.float32)
    fn = _feed_forward()
    jaxpr = jax.make_jaxpr(lambda v: hybrid.over_live_rows(
        fn, v, jnp.ones((B, W), bool), None))(v)
    assert _conditionals(jaxpr.jaxpr) == 0
    np.testing.assert_array_equal(
        hybrid.over_live_rows(fn, v, None, None).reshape(-1, C),
        fn(v.reshape(-1, C)))


# -- the programs -------------------------------------------------------------

def _conditionals(jaxpr):
    """`cond` equations in a jaxpr and everything nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _conditionals(sub)
    return n


def _falcon(**over):
    from test_falcon_h1 import _model
    return _model(**over)[0]


def _kimi():
    from test_kimi_linear import _model
    return _model()[0]


def _nemotron():
    from test_nemotron_h import _model
    return _model()[0]


# (the model, its wrapped feed-forwards: Falcon's MLP a layer; Kimi's dense
# layer, not its four 64-wide shared experts; Nemotron's three shared experts)
@pytest.mark.parametrize("make, wrapped", [(_falcon, 2), (_kimi, 1),
                                           (_nemotron, 3)],
                         ids=["falcon_h1", "kimi_linear", "nemotron_h"])
def test_a_program_holds_one_conditional_a_wrapped_feed_forward(make,
                                                                wrapped):
    net = make()
    ids = jnp.zeros((2, 16), jnp.int32)
    spans = jnp.asarray([16, 1], jnp.int32)

    def cached(ids, spans):
        cache = net.make_cache(2, 64, page_size=16, attn_impl="xla")
        cache.spans = spans
        h, cache = net.hidden(ids, cache)
        return h._data, cache.recurrent["live_rows"]

    assert _conditionals(jax.make_jaxpr(cached)(ids, spans).jaxpr) == wrapped
    whole = jax.make_jaxpr(lambda ids: net.hidden(ids)[0]._data)(ids)
    assert _conditionals(whole.jaxpr) == 0
    # one dispatch counted, and 17 live rows do not fit 4
    assert list(np.asarray(cached(ids, spans)[1])) == [1, 0]
    assert list(np.asarray(cached(ids, jnp.asarray([3, 1]))[1])) == [1, 1]


# -- behind the engine --------------------------------------------------------

def _serve(net, **engine_kw):
    rng = np.random.default_rng(11)
    requests = [Request(rng.integers(0, 512, n), 6, request_id=f"r{i}")
                for i, n in enumerate((5, 9, 12, 7, 3, 11, 8, 6, 10, 4))]
    eng = ServingEngine(net, num_slots=4, max_length=64, page_size=16,
                        chunk_tokens=16, attn_impl="xla", **engine_kw)
    done = eng.serve(requests)
    assert all(r.status == "finished" for r in done)
    return eng, [list(r.output_tokens) for r in requests]


def test_the_streams_are_the_same_whether_the_ticks_fit_or_not(monkeypatch):
    """Ten short requests through four slots of 16 rows (T = 8), one
    greedy stream a request however the feed-forward ran: 16 prompt tokens
    a tick (decode ticks fit, ticks with a prompt mostly do not); all four
    slots prefilling at once; T the whole grid, so every tick fits; T one
    row, so no tick with two live rows does."""
    net = _falcon()
    eng, streams = _serve(net, prefill_chunk_budget=16)
    dispatches, compact = eng.stats["model_counters"]["live_rows"]
    assert dispatches == eng.stats["decode_dispatches"]
    assert 0 < compact < dispatches

    at_once, same = _serve(net, prefill_chunk_budget=64)
    assert same == streams
    dispatches, compact = at_once.stats["model_counters"]["live_rows"]
    assert 0 < compact < dispatches == at_once.stats["decode_dispatches"]

    monkeypatch.setattr(hybrid, "COMPACT_GRID_SHARE", 1)
    always, same = _serve(net, prefill_chunk_budget=16)
    assert same == streams
    dispatches, compact = always.stats["model_counters"]["live_rows"]
    assert compact == dispatches == always.stats["decode_dispatches"]

    monkeypatch.setattr(hybrid, "COMPACT_GRID_SHARE", 10 ** 6)
    never, same = _serve(net, prefill_chunk_budget=16)
    assert same == streams
    dispatches, compact = never.stats["model_counters"]["live_rows"]
    assert compact < dispatches // 2

    eng.reset_stats()
    assert eng.stats["model_counters"]["live_rows"] == [0, 0]
    eng.serve([Request(np.arange(5), 2, request_id="again")])
    assert eng.stats["model_counters"]["live_rows"] == [2, 2]
