"""What PR 27 added to the benchmark: the configuration falcon_h1_34b and
its cell falcon_h1_34b.chat_backlog as entries and data, five reader files
for six per-layer metrics, the reference's cost functions, and the
per-parameter weights with the thin runner that uses them. The cell itself
runs under `--check` in test_bench_cells.py, with every other cell."""
import collections
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import cells  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    gqa_span_attention_roofline, mosaic_kernel_ms,
    recurrent_state_hbm_share, ssd_chunk_roofline, state_resets_per_dispatch)
from benchmarks.reference import falcon_h1 as ref  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "falcon_h1_34b.chat_backlog"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "falcon_h1_34b.json")))
KW = CONFIG["model"]["kwargs"]

# tiiuae/Falcon-H1-34B-Instruct config.json, as the model-configs catalog
# holds it: every key that says something about the model's shape
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}


# -- entries and data ---------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    entry = next(c for c in BENCH["configs"] if c["name"] == "falcon_h1_34b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "no") != v}
    assert differs == {"num_hidden_layers"} and CONFIG[
        "num_hidden_layers"] == 6
    assert "12-stage pipeline" in CONFIG["deployment"]
    # what the program is built from says the same as the published keys
    same = {"vocab_size": "vocab_size", "units": "hidden_size",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "hidden_size": "intermediate_size", "ssm_heads": "mamba_n_heads",
            "ssm_head_dim": "mamba_d_head", "ssm_state": "mamba_d_state",
            "ssm_groups": "mamba_n_groups", "conv_kernel": "mamba_d_conv",
            "chunk_size": "mamba_chunk_size", "rms_norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta"}
    same.update({k: k for k in PUBLISHED if k.endswith(
        ("_multiplier", "_multipliers"))})
    assert len([k for k in same if "multiplier" in k]) == 9    # 14 numbers
    for ours, theirs in same.items():
        assert KW[ours] == PUBLISHED[theirs], ours
    assert KW["num_layers"] == 6
    assert KW["ssm_heads"] * KW["ssm_head_dim"] == PUBLISHED["mamba_d_ssm"]
    assert (KW["dtype"], KW["state_dtype"]) == ("bfloat16", "float32")
    eng = CONFIG["engine"]
    assert eng["prefill_chunk_budget"] == eng["num_slots"] * 64
    assert (eng["max_length"], eng["page_size"], eng["chunk_tokens"]) \
        == (640, 64, 64)
    assert CONFIG["check"] == {"prompt_lens": [100, 150], "new_tokens": 24}


def test_the_traffic_is_the_issues_letter_for_letter():
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "chat_backlog.json")))
    assert traffic["generator"] == "request_stream"
    assert traffic["arrivals"] == {"process": "backlog", "count": 1200}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 128,
                                     "sigma": 0.8, "min": 16, "max": 448}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 64,
                                     "sigma": 0.6, "min": 16, "max": 192}
    assert traffic["sampling"] == {"do_sample": False}
    # how the cell is judged is said beside them, and is no parameter of
    # the generator
    assert traffic["judged_by"] == "emitted_tokens"
    # the longest request fits a slot
    assert 448 + 192 <= CONFIG["engine"]["max_length"]


def test_the_cell_joins_what_reads_nothing_of_the_model_and_brings_six():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("falcon_h1_34b", "chat_backlog", 1)
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    joined = {"serve_tokens_per_s", "dispatch_ms_p50.backlog",
              "step_device_ms.backlog", "device_idle_share.backlog",
              "peak_hbm_share.backlog", "program_temp_hbm_share.backlog",
              "kv_page_fill_share.backlog", "useful_row_share",
              "kv_pool_used_share_peak"} | {
        m["name"] for m in BENCH["per_layer"]
        if m["name"].startswith("tick_host_ms.")}
    new = {"mosaic_kernel_ms.ssd_chunk", "mosaic_kernel_ms.span",
           "ssd_chunk_roofline", "gqa_span_attention_roofline",
           "recurrent_state_hbm_share", "state_resets_per_dispatch"}
    everywhere = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                  if "workloads" not in m}
    assert listed == joined | new | everywhere
    # kernel_seconds sums every Mosaic call, and this program has two kinds
    assert not listed & {"attn_call_ms.backlog",
                         "ragged_span_attention_roofline.backlog"}
    assert [m["name"] for m in BENCH["per_layer"][-6:]] == [
        "mosaic_kernel_ms.ssd_chunk", "mosaic_kernel_ms.span",
        "ssd_chunk_roofline", "gqa_span_attention_roofline",
        "recurrent_state_hbm_share", "state_resets_per_dispatch"]
    for m in BENCH["per_layer"][-6:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == "%" or "roofline" not in m["name"]


# -- the five readers, on runs made by hand -----------------------------------

def _run(reduction=None, peaks=True, **facts):
    """What a reader is handed: the runner's facts, the reduced trace, the
    chip's peaks and the cell (for the reference's cost functions)."""
    return types.SimpleNamespace(
        facts=facts, peaks=cells.peaks("TPU v5 lite") if peaks else None,
        tracer=types.SimpleNamespace(reduction=reduction),
        cell=cells.Cell(CELL), say=lambda text: None)


READERS = [(mosaic_kernel_ms, "ssd_chunk"), (mosaic_kernel_ms, "span"),
           (ssd_chunk_roofline, None), (gqa_span_attention_roofline, None),
           (recurrent_state_hbm_share, None),
           (state_resets_per_dispatch, None)]


@pytest.mark.parametrize("reader, label", READERS,
                         ids=lambda x: getattr(x, "__name__", x))
def test_a_reader_with_nothing_to_read_returns_none(reader, label):
    """No trace and no engine counters, as a program that lacks what this
    PR added gives: nothing is read and nothing is raised."""
    assert reader.read(_run(), label) is None
    assert reader.read(_run(peaks=False), label) is None


def _traced_run():
    """Four traced dispatches of 32 slots x 64 rows: one request of 100
    prompt tokens admitted in step 1 (two chunks, then decode rows), and a
    trace with both kinds of Mosaic call, as the v5e names them."""
    steps = [(i, i + 0.9, 3, 1, 0, 100 + i) for i in range(5)]
    reduction = {
        "by_op": {"ssd_chunk_update mosaic (bf16[32,64,4096], ...)": 0.016,
                  "unified mosaic bf16[32,320,512]": 0.008,
                  "fusion.1 fusion bf16[32,64,261120]": 0.3},
        "spans": collections.Counter({"serving.dispatch": 4})}
    return _run(
        reduction, kind="serve", steps=steps, traced_steps=[1, 2, 3, 4],
        timelines=[{"admit": 1.1, "prompt_len": 100, "first": 2.8,
                    "tokens": [2.8, 3.8, 4.8]}],
        engine_stats={"decode_dispatches": 5, "state_resets": 2,
                      "recurrent_state_bytes": 811_204_608,
                      "kernel_paths": {"ssd_chunk_update/pallas": 6,
                                       "ragged_span_attention/pallas": 6}},
        slots=32, width=64, model_kwargs=KW)


def test_the_two_kinds_of_mosaic_call_are_told_apart_by_the_calls_name():
    run = _traced_run()
    assert mosaic_kernel_ms.read(run, "ssd_chunk") == pytest.approx(4.0)
    assert mosaic_kernel_ms.read(run, "span") == pytest.approx(2.0)
    with pytest.raises(ValueError, match="no Mosaic kernel of kind"):
        mosaic_kernel_ms.read(run, "flash")
    # a trace without the kind stops the run: a kernel that fell to its
    # dense form must not read as the fastest kernel of all
    del run.tracer.reduction["by_op"][
        "ssd_chunk_update mosaic (bf16[32,64,4096], ...)"]
    assert mosaic_kernel_ms.read(run, "ssd_chunk") is None
    assert ssd_chunk_roofline.read(run) is None
    assert mosaic_kernel_ms.read(run, "span") == pytest.approx(2.0)
    # but for a run whose engine names no kernel paths at all
    # (test_bench_units.py's, made by hand with GPT-2's trace)
    del run.facts["engine_stats"]["kernel_paths"]
    assert mosaic_kernel_ms.read(run, "ssd_chunk") == 0.0
    assert ssd_chunk_roofline.read(run) == 0.0


def test_each_roofline_is_its_own_cost_over_its_own_kernels_time():
    run = _traced_run()
    # the traced steps fed (0, 64), (64, 36), then decode rows at 100, 101
    rows = [[(0, 64)], [(64, 36)], [(100, 1)], [(101, 1)]]
    peaks = run.peaks
    for reader, cost_fn, secs in (
            (ssd_chunk_roofline, ref.ssm_cost, 0.016),
            (gqa_span_attention_roofline, ref.attention_cost, 0.008)):
        cost = [cost_fn(KW, r) for r in rows]
        floor = max(sum(c["flops"] for c in cost) / peaks["bf16_flops_per_s"],
                    sum(c["bytes"] for c in cost) / peaks["hbm_bytes_per_s"])
        assert reader.read(run) == pytest.approx(100 * floor / secs)
        assert 0 < reader.read(run) < 100


def test_the_two_counters_are_the_engines_own():
    run = _traced_run()
    assert recurrent_state_hbm_share.read(run) == pytest.approx(
        100 * 811_204_608 / (16 * 2 ** 30))
    assert state_resets_per_dispatch.read(run) == pytest.approx(0.4)
    # under --check there is no chip: a count over the published constant
    run.peaks = None
    assert recurrent_state_hbm_share.read(run) == pytest.approx(
        100 * 811_204_608 / (16 * 2 ** 30))


# -- the reference's arithmetic -----------------------------------------------

def test_ssm_cost_counts_the_state_once_each_way_and_live_rows_only():
    h, p, n, g, layers = 32, 128, 256, 2, 6
    one = ref.ssm_cost(KW, [(500, 1)])         # a decode row, any context
    assert one == ref.ssm_cost(KW, [(0, 1)])
    state = 2 * h * p * n * 4
    row = (2 * h * p + 2 * g * n) * 2 + 4 * h
    assert one["bytes"] == layers * (state + row)
    assert one["flops"] == layers * 2 * (2 * h * p * n + g * n + h * p)
    chunk = ref.ssm_cost(KW, [(0, 64), (64, 1)])
    assert chunk["bytes"] == layers * (2 * state + 65 * row)
    assert ref.ssm_cost(KW, []) == {"flops": 0, "bytes": 0}


def test_attention_cost_reads_four_heads_and_computes_twenty():
    layers, cq, ckv = 6, 20 * 128, 4 * 128
    got = ref.attention_cost(KW, [(100, 1)])
    assert got["flops"] == layers * 4 * cq * (100 + 1)
    assert got["bytes"] == layers * (2 * 101 * ckv + 2 * cq) * 2


def test_flops_per_item_counts_the_matrices_the_scores_and_the_state():
    from mxnet_tpu import models
    cfg = models.falcon_h1_34b_config(**KW)
    small = cfg.num_params() - 261120 * 5120 - 5120      # embedding, norm
    for i in range(6):
        small -= 2 * 5120 + 4096 + 5120 * 5 + 3 * 32     # norms, conv, A D dt
    assert ref._matmul_params(KW) == small
    assert ref.flops_per_item(KW, 100) == 2 * small + 6 * (
        4 * 20 * 128 * 100 + 4 * 32 * 128 * 256)


# -- weights too many for one draw --------------------------------------------

def test_per_parameter_weights_are_a_function_of_the_seed_and_mark_scales():
    import jax.numpy as jnp
    from benchmarks import weights_per_parameter as wpp
    from mxnet_tpu import models
    tiny = cells.merge(KW, CONFIG["tiny"]["model"]["kwargs"])

    def made(seed):
        net = models.FalconH1ForCausalLM(models.falcon_h1_34b_config(**tiny))
        wpp.seed_weights(net, seed, "float32")
        return {k: np.asarray(p.data()._data)
                for k, p in net.collect_params().items()}

    a, b, c = made(5), made(5), made(2 ** 31 + 7)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)
    assert a["head.weight"].dtype == jnp.float32
    assert abs(a["head.weight"].std() - 0.02) < 2e-3
    assert abs(a["head.weight"].mean()) < 2e-3
    for scale in ("final_norm.weight", "layer0.mamba.norm.weight",
                  "layer1.mamba.D"):
        assert abs(a[scale].mean() - 1.0) < 0.02, scale
    # no two parameters share a stream
    assert (a["layer0.gate.weight"] != a["layer0.up.weight"]).any()
    # the mixer's own parameters as its published initialization draws
    # them: A in [1, 16], dt = softplus(dt_bias) in [1e-3, 1e-1], the
    # convolution within 1/sqrt(its kernel's length)
    for layer in ("layer0", "layer1"):
        A = np.exp(a[f"{layer}.mamba.A_log"])
        dt = np.log1p(np.exp(a[f"{layer}.mamba.dt_bias"]))
        assert (1.0 <= A).all() and (A <= 16.0).all()
        assert (0.99e-3 <= dt).all() and (dt <= 1.01e-1).all()
        for conv in ("conv_weight", "conv_bias"):
            w = a[f"{layer}.mamba.{conv}"]
            assert np.abs(w).max() <= 0.5 and 0.2 < w.std() < 0.35


def test_with_those_weights_the_logits_hang_on_the_carried_state():
    """Why the mixer is not drawn N(0, 0.02) like the rest: the comparison
    that decides `correct` could not see a lost or leaked recurrent state.
    At the tiny size and the published multipliers, zeroing the carried
    state at every chunk boundary moves the reference's logits a hundred
    times further with these weights than with weights.py's one draw. (At
    the published widths it is a fifth of the logits' spread against a
    thousandth: PERF.md, PR 27.)"""
    import jax.numpy as jnp
    from benchmarks import weights, weights_per_parameter as wpp
    from mxnet_tpu import models
    tiny = cells.merge(KW, CONFIG["tiny"]["model"]["kwargs"])
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, tiny["vocab_size"], (2, 48)))

    def moved(seed_weights):
        net = models.FalconH1ForCausalLM(models.falcon_h1_34b_config(**tiny))
        seed_weights(net, 11, "float32")
        params = {k: p.data()._data for k, p in net.collect_params().items()}
        whole = ref.logits(params, tiny, ids)
        lost = ref.logits(params, tiny, ids, reset_every=16)
        return float(jnp.abs(lost - whole).max() / whole.std())

    assert moved(wpp.seed_weights) > 100 * moved(weights.seed_weights)


def test_the_large_runner_is_the_serving_runner_with_those_weights():
    from benchmarks import weights, weights_per_parameter
    from benchmarks.runners import serve, serve_large
    seen = []
    plain = serve.run
    serve.run = lambda run: seen.append(serve.seed_weights)

    def ran(paths, tiny=None):
        run = types.SimpleNamespace(
            facts={"engine_stats": {"kernel_paths": paths}},
            cell=types.SimpleNamespace(tiny=tiny, traffic={}),
            result={"correct": True}, say=lambda text: None)
        serve_large.run(run)
        return run.result["correct"]

    try:
        kernels = {"ssd_chunk_update/pallas": 6,
                   "ragged_span_attention/pallas": 6}
        assert ran(kernels)
        # on the chip a kernel traced with its dense form is not correct;
        # under --check (the CPU, tiny sizes) the dense form is the path
        dense = dict(kernels, **{"ssd_chunk_update/xla": 6})
        assert not ran(dense) and ran(dense, tiny={"model": {}})
    finally:
        serve.run = plain
    assert seen == [weights_per_parameter.seed_weights] * 3
    assert serve.seed_weights is weights.seed_weights      # and put back
    assert CONFIG["runner"] == "serve_large"


@pytest.mark.parametrize("judged_by, want", [("emitted_tokens", 5 / 4.0),
                                             (None, 250.0)])
def test_a_decode_heavy_cell_is_judged_by_the_tokens_it_emits(judged_by,
                                                              want):
    """Where the traffic says so, serve_tokens_per_s is the output tokens
    that came within the window: no prompt is booked, and a token after the
    window's last instant does not count. Elsewhere the serving runner's
    own count stands."""
    from benchmarks.runners import serve, serve_large
    timelines = [
        {"prompt_len": 448, "first": 0.5, "tokens": [0.5, 1.5, 2.5]},
        {"prompt_len": 16, "first": 3.0, "tokens": [3.0, 4.0, 4.5]},
        {"prompt_len": 300, "first": None, "tokens": []}]
    assert serve_large.emitted_per_s(timelines, 4.0) == 5 / 4.0
    said = []
    plain = serve.run

    def ran(run):
        run.facts.update(engine_stats={}, timelines=timelines)
        run.end_to_end["serve_tokens_per_s"] = 250.0

    serve.run = ran
    try:
        run = types.SimpleNamespace(
            facts={}, end_to_end={}, seconds=4.0, result={},
            cell=types.SimpleNamespace(
                tiny=None, traffic={"judged_by": judged_by}),
            say=said.append)
        serve_large.run(run)
    finally:
        serve.run = plain
    assert run.end_to_end["serve_tokens_per_s"] == want
    # both counts are said where the judged one is not the runner's own
    assert [("1.250" in t, "250.000" in t) for t in said] \
        == [(True, True)] * (judged_by is not None)
