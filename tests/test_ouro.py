"""Ouro (models/ouro.py) held to its plain float32 reference
(benchmarks/reference/ouro.py: the passes a Python loop over whole sequences,
no cache) at a tiny size on seeded weights: the full forward and the exit
distribution, the chunked and paged serving path through ServingEngine logit
by logit, the loop itself (the blocks traced once, the cache layer a traced
integer), the span kernel with its layer a runtime scalar, every
perturbation the cell's limit must catch, what the engine allocates for a
cache with more layers than the model has weights, the exit counters, and
which engine features ride on pages alone."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import ouro as ref
from benchmarks.weights_per_parameter import seed_weights
from mxnet_tpu import models, parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.serving import Request, ServingEngine

from test_falcon_h1 import _serve_and_capture

TINY = dict(vocab_size=512, units=128, num_layers=3, num_heads=4,
            num_kv_heads=4, head_dim=32, hidden_size=256, max_length=1024,
            dtype="float32")


# what the cell's configuration draws again, and why: as first drawn the
# model forgets its input (benchmarks/configs/ouro_2_6b.json, assumed.draw)
DRAW = {"embed.weight": 1.0, "out_norm.weight": 0.1,
        "attn.query.weight": 0.035, "attn.key.weight": 0.035}


def _model(seed=3, std=0.02, draw=None, **over):
    """(net, its config as the reference's kwargs, its parameters): the
    norms about one, everything else N(0, std); with `draw`, the parameters
    it names drawn again as the cell's runner draws them."""
    cfg = models.ouro_2_6b_config(**{**TINY, **over})
    net = models.OuroForCausalLM(cfg)
    net.collect_params().setattr("grad_req", "null")
    if draw:
        from benchmarks.runners.serve_long import seed_weights as drawn
        drawn(net, seed, cfg.dtype, draw)
    else:
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


def test_full_forward_and_exit_pdf_match_the_reference():
    net, kw, params = _model()
    ids = _ids(0, 2, 37)
    want, want_pdf = ref.logits(params, kw, ids, with_exit_pdf=True)
    assert _err(par.EvalStep(net)(ids)._data, want) < TIGHT
    h, cache, pdf = net.hidden_and_exit(ids)
    assert cache is None and pdf.shape == (4, 2, 37)
    np.testing.assert_allclose(np.asarray(pdf), np.asarray(want_pdf),
                               atol=1e-6)
    # a distribution over the four passes, and no pass takes it all
    np.testing.assert_allclose(np.asarray(pdf.sum(0)), 1.0, atol=1e-6)
    assert 0.02 < float(pdf.min()) and float(pdf.max()) < 0.9
    # the head over chosen positions alone is the same head, the pdf too
    at = jnp.asarray([[3, 36], [0, 20]])
    some, some_pdf = ref.logits(params, kw, ids, positions=at,
                                with_exit_pdf=True)
    np.testing.assert_allclose(
        np.asarray(some), np.asarray(jnp.take_along_axis(
            want, at[:, :, None], axis=1)), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(some_pdf), np.asarray(jnp.take_along_axis(
            want_pdf, at[None], axis=2)), atol=1e-6)


def test_the_published_config_is_the_whole_model():
    cfg = models.ouro_2_6b_config()
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.units) == (48, 4, 2048)
    assert cfg.num_params() == 2_667_974_657
    net = models.OuroForCausalLM(cfg)     # nothing is allocated by this
    spec = net.state_spec()
    assert (spec["num_layers"], spec["kv_layers"], spec["loop_steps"]) \
        == (48, 192, 4)
    assert (spec["num_kv_heads"], spec["head_dim"]) == (16, 128)
    assert spec["recurrent"] == {}
    assert spec["counters"] == {"exit": ((5,), "float32")}
    # rows that leave the stack early are not built
    with pytest.raises(MXNetError, match="early_exit_threshold"):
        models.ouro_2_6b_config(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ref.logits({}, {"early_exit_threshold": 0.9}, None)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_serving_engine_logits_match_the_reference(impl):
    """Ragged chunked prefill then decode through ServingEngine, logit by
    logit: five requests through two slots (a slot serves requests in
    succession, and every one of its 12 cache layers must hold the later
    request's rows alone), one chunk a dispatch, prompt lengths that are no
    multiple of the chunk and cross page boundaries."""
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
    # the three blocks are traced ONCE and run four times: three calls of
    # each kernel, not twelve (4 heads of 32 are a whole lane tile: the
    # page write takes the pool)
    assert st["kernel_paths"] == {f"ragged_span_attention/{path}": 3,
                                  f"kv_page_write/{path}": 3}
    assert st["kernel_tiles"] == ({} if impl == "xla" else {
        "ragged_span_attention/pages=4,keys=64,rows=16": 3})
    assert (st["kv_layers"], st["loop_steps"]) == (12, 4)


def test_the_looped_program_holds_its_blocks_once():
    """The jaxpr of a dispatch through the cache: ONE scan of four steps
    whose body holds a span call and a page write a block."""
    net, kw, _ = _model()
    cache = net.make_cache(2, 64, page_size=16, attn_impl="pallas_interpret")
    cache.spans = jnp.asarray([16, 1], jnp.int32)
    text = str(jax.make_jaxpr(lambda ids, c: net.hidden(ids, c)[0]._data)(
        _ids(1, 2, 16), cache))
    assert text.count("name=ragged_span_attention") == 3
    # (the page write is one jitted function the three blocks share)
    assert 0 < text.count("name=kv_page_write") <= 6
    assert text.count("scan[") == 1 and "length=4" in text


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("impl, interpret", [("xla", False),
                                             ("pallas", True)])
def test_span_attention_takes_its_layer_at_run_time(impl, interpret, quant):
    """ragged_span_attention with a TRACED layer equals the same call with
    that layer as a Python int, on every cache layer of a 2-pass, 3-layer
    pool, and each layer's result is its own."""
    rng = np.random.default_rng(7)
    L, B, P, S, H, D, Sq = 6, 2, 4, 16, 4, 32, 16
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k, v = f(L, B * P, S, H * D), f(L, B * P, S, H * D)
    scales = {}
    if quant:
        k, v = (jnp.clip(jnp.round(a * 40), -127, 127).astype(jnp.int8)
                for a in (k, v))
        scales = dict(k_scale=jnp.abs(f(L, B * P, H)) / 40 + 0.01,
                      v_scale=jnp.abs(f(L, B * P, H)) / 40 + 0.01)
    q = f(B, Sq, H, D)
    table = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    lengths = jnp.asarray([23, 41], jnp.int32)
    counts = jnp.asarray([16, 5], jnp.int32)
    call = lambda layer: pa.ragged_span_attention(
        q, k, v, table, lengths, q_counts=counts, impl=impl,
        interpret=interpret, layer=layer, **scales)
    traced = jax.jit(call)
    outs = []
    for layer in range(L):
        fixed = call(layer)
        np.testing.assert_array_equal(
            np.asarray(traced(jnp.int32(layer))), np.asarray(fixed))
        outs.append(np.asarray(fixed))
    for a, b in zip(outs, outs[1:]):
        assert np.abs(a - b).max() > 0.1


def test_a_pass_attends_its_own_cache_layers():
    """After a chunk through the cache, cache layer step * 3 + l holds what
    pass `step` of block l wrote: every one of the twelve is written and no
    two hold the same keys."""
    net, kw, _ = _model()
    cache = net.make_cache(1, 64, page_size=16)
    cache.spans = jnp.asarray([16], jnp.int32)
    _, new = net.hidden(_ids(2, 1, 16), cache)
    first_page = np.asarray(new.k_pages[:, int(new.page_table[0, 0])])
    assert first_page.shape == (12, 16, 128)
    assert (np.abs(first_page).max(axis=(1, 2)) > 0).all()
    for a in range(12):
        for b in range(a + 1, 12):
            assert np.abs(first_page[a] - first_page[b]).max() > 1e-3, (a, b)
    assert int(new.length[0]) == 16


@functools.lru_cache(maxsize=1)
def _drawn_as_the_cell():
    """A model drawn as the cell draws it, deep and wide enough that the
    readings stand where the chip's do (512 columns, 4 heads of 128, 40
    blocks, 512 positions; the head drawn so that the logits' spread is the
    cell's 0.905), its own logits and the reference's."""
    net, kw, params = _model(draw={**DRAW, "head.weight": 0.04}, units=512,
                             num_heads=4, num_kv_heads=4, head_dim=128,
                             hidden_size=1408, num_layers=40)
    ids = _ids(4, 1, 512)
    return kw, params, ids, par.EvalStep(net)(ids)._data


@pytest.mark.parametrize("name", sorted(ref.PERTURBATIONS))
def test_every_perturbation_exceeds_the_limit(name):
    """Each of the reference's PERTURBATIONS, ONE thing wrong, moves the
    logits of the unchanged model by more than TOLERANCE, read as the
    cell's runner reads it: the cell's limit can tell it."""
    from benchmarks.runners.serve_long import beyond, errors
    kw, params, ids, own = _drawn_as_the_cell()
    read = errors([own], [ref.logits(params, kw, ids)])
    assert all(read[k] < 0.01 * limit for k, limit in ref.TOLERANCE.items())
    moved = errors([own], [ref.logits(params, kw, ids,
                                      **ref.PERTURBATIONS[name])])
    assert beyond(moved, ref.TOLERANCE) == sorted(ref.TOLERANCE), name
    assert moved["logit_abs"] > 0.5 * ref.ARGMAX_MARGIN, name


def test_the_model_as_first_drawn_forgets_its_input():
    """Why the cell draws its weights again: norms of weight one blow the
    attention's near-uniform average up to a unit row a block, and the
    logits of two DIFFERENT sequences come out nearly the same; drawn as the
    cell draws them they do not."""
    cos = lambda a, b: float(jnp.vdot(a, b) / jnp.linalg.norm(a)
                             / jnp.linalg.norm(b))
    alike = {}
    for tag, draw in (("first", None), ("cell", DRAW)):
        net, kw, params = _model(draw=draw, units=256, num_heads=2,
                                 num_kv_heads=2, head_dim=128,
                                 hidden_size=704, num_layers=24)
        a, b = (ref.logits(params, kw, _ids(s, 1, 256))[0, -1]
                for s in (1, 2))
        nxt = ref.logits(params, kw, _ids(1, 1, 256))[0, -2]
        alike[tag] = (cos(a, nxt), cos(a, b))
    assert alike["first"][0] > 0.97       # the position before: the same
    assert alike["cell"][0] < 0.8 and abs(alike["cell"][1]) < 0.3


def test_the_float32_parts_control_is_read_beside_the_perturbations():
    """What the configuration keeps in float32 (norms, rotary, softmax,
    gate) rounded to bfloat16 in the reference moves the logits, and is
    listed apart from the perturbations."""
    from benchmarks.runners.serve_long import errors
    net, kw, params = _model()
    ids = _ids(4, 1, 80)
    want = ref.logits(params, kw, ids)
    (name, reading), = ref.CONTROLS.items()
    moved = errors([want], [ref.logits(params, kw, ids, **reading)])
    assert 1e-4 < moved["logit_rms"] < 0.1, name
    assert name not in ref.PERTURBATIONS


def test_the_engine_pools_follow_the_cache_layers():
    """A cache with more layers than the model has weights: the pools, the
    bytes a page and a token cost and the gauges follow `kv_layers`
    (passes x blocks), not `num_layers`."""
    net, kw, _ = _model()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16)
    state = eng._device_state()
    assert sorted(state) == ["k", "rec", "v"]
    assert state["k"].shape == state["v"].shape == (12, 8, 16, 128)
    assert {k: (v.shape, str(v.dtype)) for k, v in state["rec"].items()} \
        == {"exit": ((5,), "float32")}
    st = eng.stats
    assert (st["kv_layers"], st["loop_steps"], st["recurrent_layers"]) \
        == (12, 4, 0)
    assert st["kv_pool_bytes"] == 2 * 12 * 8 * 16 * 128 * 4
    assert st["kv_page_bytes"] == 2 * 12 * 16 * 128 * 4
    assert st["kv_bytes_per_token"] == 2 * 12 * 128 * 4
    assert st["recurrent_state_bytes"] == 0
    # the cell's own numbers, from the model's declaration alone
    spec = models.OuroForCausalLM(models.ouro_2_6b_config()).state_spec()
    assert 2 * spec["kv_layers"] * spec["num_kv_heads"] * spec["head_dim"] \
        * 2 == 1_572_864
    # a model that runs each layer once says nothing and reads 1
    gpt2 = models.GPT2ForCausalLM(models.GPT2Config(
        vocab_size=64, units=32, num_layers=2, num_heads=2, max_length=64))
    gpt2.initialize()
    assert ServingEngine(gpt2, num_slots=1, max_length=32,
                         page_size=8).stats["loop_steps"] == 1


def test_exit_counters_sum_the_reference_pdf_over_the_rows_read():
    """stats["model_counters"]["exit"] = [rows, p_1 .. p_4] summed over the
    rows the head reads (each slot's last live row of a dispatch), folded
    when stats are read and cleared by reset_stats()."""
    net, kw, params = _model()
    eng = ServingEngine(net, num_slots=1, max_length=64, page_size=16,
                        chunk_tokens=16, prefill_chunk_budget=16)
    rng = np.random.default_rng(9)
    want = np.zeros(5)
    for n, new in ((37, 5), (20, 3)):
        r = Request(rng.integers(0, 512, n), new)
        eng.serve([r])
        seq = np.concatenate([r.prompt, r.output_tokens])
        _, pdf = ref.logits(params, kw, jnp.asarray(seq[None], jnp.int32),
                            with_exit_pdf=True)
        # a chunk's last row, then the position of every token fed back
        read = [min(at + 16, n) - 1 for at in range(0, n, 16)] \
            + list(range(n, n + new - 1))
        want += np.concatenate([[len(read)],
                                np.asarray(pdf[:, 0, read]).sum(-1)])
    got = np.asarray(eng.stats["model_counters"]["exit"])
    assert got[0] == want[0] == eng.stats["decode_dispatches"]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got[1:].sum(), got[0], rtol=1e-5)
    eng.reset_stats()
    assert eng.stats["model_counters"]["exit"] == [0.0] * 5


def _greedy(params, kw, prompt, new):
    seq = list(prompt)
    for _ in range(new):
        lg = ref.logits(params, kw, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(lg[0, -1])))
    return seq[len(prompt):]


@pytest.mark.parametrize("feature", ["prefix_cache", "host_kv_bytes"])
def test_what_rides_on_pages_alone_serves_this_model(feature):
    """A page id covers all twelve cache layers, so the prefix cache and
    the host tier take this model as they take any other: the tokens of
    the reference's greedy forward, with hits, spills and page-ins."""
    net, kw, params = _model(std=0.05)
    knobs = {"prefix_cache": dict(prefix_cache=True),
             "host_kv_bytes": dict(prefix_cache=True, prefix_cache_pages=3,
                                   host_kv_bytes=1 << 22)}[feature]
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16, **knobs)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 512, 35)
    prompts = [np.concatenate([shared, rng.integers(0, 512, n)])
               for n in (3, 9, 14)] + [rng.integers(0, 512, 40)]
    requests = [Request(p, 5, request_id=f"p{i}")
                for i, p in enumerate(prompts)]
    for r in requests + [Request(prompts[0], 5, request_id="again")]:
        eng.serve([r])          # one after another: later ones can hit
        assert list(r.output_tokens) == _greedy(params, kw, r.prompt, 5), \
            r.id
    st = eng.stats
    assert st["prefix_hits"] >= 3
    if feature == "host_kv_bytes":
        assert st["kv_spill_pages"] > 0 and st["kv_pagein_pages"] > 0


def test_int8_pages_serve_this_model_as_its_own_paged_path():
    """int8 pages: the codes and the per-(cache layer, page, head) scales
    ride through the loop with the pools. The monotone page scale is a
    tolerance design (tests/test_quant_kv.py holds it; on seeded weights it
    inflates a page's early keys by up to half), so the engine is held
    EXACTLY to the model's own paged path over one int8 slot fed the same
    chunks; the float32 reference is several tenths of the logits' spread
    away, and is not the yardstick here."""
    net, kw, params = _model()
    eng = ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16, kv_dtype="int8")
    state = eng._device_state()
    assert state["k"].dtype == jnp.int8
    assert state["ks"].shape == state["vs"].shape == (12, 8, 4)
    assert eng.stats["kv_page_bytes"] == 2 * 12 * 16 * 128 + 2 * 12 * 4 * 4
    rng = np.random.default_rng(13)
    requests = [Request(rng.integers(0, 512, n), 5, request_id=f"q{i}")
                for i, n in enumerate((37, 21, 33))]
    # one slot: the requests follow each other through it, a chunk of 16 a
    # dispatch and then a row a token
    eng, rows = _serve_and_capture(
        net, requests, num_slots=1, max_length=64, page_size=16,
        chunk_tokens=16, kv_dtype="int8")
    for r in requests:
        n = len(r.prompt)
        cache = net.make_cache(1, 64, page_size=16, kv_dtype="int8")
        seq, own = np.concatenate([r.prompt, r.output_tokens[:-1]]), []
        cuts = list(range(0, n, 16)) + list(range(n, len(seq) + 1))
        for at, end in zip(cuts, cuts[1:]):
            toks = np.zeros((1, 16), np.int32)
            toks[0, :end - at] = seq[at:end]
            # (the forward advances by the grid's width: the caller says
            # where the slot stands, as the engine does)
            cache.length = jnp.asarray([at], jnp.int32)
            cache.spans = jnp.asarray([end - at], jnp.int32)
            h, cache = net.hidden(jnp.asarray(toks), cache)
            own.extend(net.head(h)._data[0, :end - at])
        got = jnp.stack([rows[r.id][i] for i in range(len(seq))])
        # a code on a rounding boundary may fall either way in two
        # programs: a hundredth of the spread, where a wrong scale, layer
        # or page reads over one
        assert _err(got, jnp.stack(own)) < 0.02, r.id
        lg = ref.logits(params, kw, jnp.asarray(seq[None], jnp.int32))[0]
        assert 0.5 < _err(got, lg) < 6.0, r.id     # the design's tolerance
    assert eng.stats["kv_quant_enabled"] == 1


@pytest.mark.parametrize("knob, value", [
    ("tp", 2), ("weight_dtype", "int8"), ("adapter_pool", object()),
    ("speculative", True)])
def test_what_the_blocks_do_not_carry_is_refused_by_name(knob, value):
    net, kw, _ = _model()
    with pytest.raises(MXNetError, match=f"{knob} is not supported for "
                                         "OuroForCausalLM"):
        ServingEngine(net, num_slots=2, max_length=64, page_size=16,
                      chunk_tokens=16, **{knob: value})
