"""What PR 39 added to the benchmark: the configuration ouro_2_6b (the
published model, uncut) and its cell ouro_2_6b.fewshot_backlog as entries
and data, a traffic file, one reader file and two per-layer entries that
read the span kernel by its own name, and the reference's cost functions.
The cell itself runs under `--check` in test_bench_cells.py, with every
other cell; here its tightness readings do, and the kernel calls the four
other served models report, which the span kernel's new operand and name
must not have moved."""
import collections
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import cells  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    attn_call_ms, looped_span_attention_roofline, mosaic_kernel_ms,
    named_kernel_ms)
from benchmarks.reference import ouro as ref  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "ouro_2_6b.fewshot_backlog"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "ouro_2_6b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "fewshot_backlog.json")))
KW = CONFIG["model"]["kwargs"]
NEW = ["looped_span_attention_roofline",
       "named_kernel_ms.ragged_span_attention"]


# -- entries and data ---------------------------------------------------------

def test_the_configuration_is_the_published_one_uncut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == CONFIG["reduced"] == []
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/ouro_2_6b.json"
    assert len(entry["why"]) <= 200
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"Ouro-2.6B"' in ln)
        assert {k for k, v in row["config"].items()
                if CONFIG.get(k, "no") != v} == set()
    for ours, theirs in (
            ("units", "hidden_size"), ("num_layers", "num_hidden_layers"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("hidden_size", "intermediate_size"),
            ("vocab_size", "vocab_size"), ("rms_norm_eps", "rms_norm_eps"),
            ("rope_theta", "rope_theta"),
            ("total_ut_steps", "total_ut_steps"),
            ("early_exit_threshold", "early_exit_threshold"),
            ("max_length", "max_position_embeddings")):
        assert KW[ours] == CONFIG[theirs], ours
    assert KW["dtype"] == "bfloat16"
    # what is drawn again is named with the end of a parameter's name, and
    # the configuration says why
    assert CONFIG["draw"] == {"embed.weight": 1.0, "out_norm.weight": 0.1,
                              "attn.query.weight": 0.035,
                              "attn.key.weight": 0.035}
    assert "does not depend on its input" in CONFIG["assumed"]["draw"]
    # what the config lacks is listed with where the modelling code sets it
    for key in ("bias", "rotary", "sandwich_norms", "norm_between_passes",
                "exit_gate", "cache_layers", "num_slots", "max_length",
                "lengths", "check", "draw"):
        assert key in CONFIG["assumed"], key


def test_the_bytes_the_configuration_reckons():
    """5.336 GB of weights and 1.5 MiB of cache a token, from the shapes."""
    d, f, v = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * f + 4 * d
    total = 48 * layer + 2 * v * d + d + d + 1
    assert layer == 51_388_416 and total == 2_667_974_657
    assert round(total * 2 / 1e9, 3) == 5.336
    assert "5.336 GB" in CONFIG["deployment"]
    token = 4 * 48 * 2 * 16 * 128 * 2
    assert token == 1_572_864 and "1,572,864" in CONFIG["deployment"]
    engine = CONFIG["engine"]
    assert engine == {"num_slots": 5, "max_length": 1024, "page_size": 64,
                      "chunk_tokens": 64, "prefill_chunk_budget": 320}
    pool = engine["num_slots"] * engine["max_length"] * token
    assert round(pool / 1e9, 3) == 8.053
    # the cache, not the weights, is the larger part of what the chip holds
    assert pool > total * 2
    assert 0.25 * 16e9 < pool + total * 2 < 16.91e9
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"] \
        <= engine["max_length"]
    # what serve_long's company queues behind the check must fit a slot
    check = CONFIG["check"]
    assert check == {"prompt_lens": [200, 900], "new_tokens": 16}
    ticks = -(-max(check["prompt_lens"]) // 64) + check["new_tokens"]
    assert ticks * 64 // 2 + 8 <= engine["max_length"]
    tiny = cells.merge(CONFIG, CONFIG["tiny"])
    ticks = -(-max(tiny["check"]["prompt_lens"]) // 16) \
        + tiny["check"]["new_tokens"]
    assert max(ticks * 16 // 2 + 8, 8 * 16 + 2) \
        <= tiny["engine"]["max_length"]
    assert tiny["model"]["kwargs"]["total_ut_steps"] == 4


def test_the_traffic_is_a_file_of_the_generators_parameters():
    assert TRAFFIC["generator"] == "request_stream"
    assert TRAFFIC["arrivals"] == {"process": "backlog", "count": 1200}
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 512,
                                     "max": 960}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 12,
                                     "sigma": 0.6, "min": 4, "max": 32}
    assert TRAFFIC["sampling"] == {"do_sample": False}
    assert TRAFFIC["judged_by"] == "fed_and_emitted_tokens"
    from benchmarks.generators import request_stream
    specs = request_stream.generate(TRAFFIC, 1000, 2 ** 31 + 5, 51.0)
    assert len(specs) == 1200 and all(s["due"] == 0 for s in specs)
    lens = [len(s["prompt"]) for s in specs]
    assert min(lens) >= 512 and max(lens) <= 960
    assert 720 < sum(lens) / 1200 < 752


def test_the_cell_joins_what_reads_it_rightly_and_brings_two():
    """By NAME, not by position: a later PR appends after these."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "ouro_2_6b",
                     "traffic": "fewshot_backlog", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    joined = {"serve_tokens_per_s", "dispatch_ms_p50.backlog",
              "useful_row_share", "kv_pool_used_share_peak",
              "kv_page_fill_share.backlog", "step_device_ms.backlog",
              "device_idle_share.backlog", "peak_hbm_share.backlog",
              "program_temp_hbm_share.backlog",
              "named_kernel_ms.kv_page_write", "kv_pool_hbm_share"} | {
        m["name"] for m in BENCH["per_layer"]
        if m["name"].startswith("tick_host_ms.")}
    everywhere = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                  if "workloads" not in m}
    assert listed == joined | set(NEW) | everywhere
    assert len([n for n in listed if n.startswith("tick_host_ms.")]) == 9
    # each of these books kv_page_write's time to the span kernel
    assert not listed & {
        "attn_call_ms.backlog", "ragged_span_attention_roofline.backlog",
        "mosaic_kernel_ms.ssd_chunk", "mosaic_kernel_ms.span",
        "moe_kernel_ms.span", "moe_kernel_ms.expert_ffn",
        "gqa_span_attention_roofline"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "serve_tokens_per_s"
        assert m["layer"] == "kernels" and m["source"] == "device_trace"
        assert (m["unit"] == "%") == ("roofline" in name)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cell reports setup_s, one other end-to-end metric and per-layer
    # metrics, as the contract asks of every cell
    c = cells.Cell(CELL)
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    assert len(c.per_layer) >= 25


# -- the readers, on runs made by hand ----------------------------------------

def _run(reduction=None, peaks=True, **facts):
    return types.SimpleNamespace(
        facts=facts, peaks=cells.peaks("TPU v5 lite") if peaks else None,
        tracer=types.SimpleNamespace(reduction=reduction),
        cell=cells.Cell(CELL), say=lambda text: None)


@pytest.mark.parametrize("reader, label", [
    (looped_span_attention_roofline, None),
    (named_kernel_ms, "ragged_span_attention")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_reader_with_nothing_to_read_returns_none(reader, label):
    """No trace and no engine counters, as a program that lacks what this
    PR added gives: nothing is read and nothing is raised."""
    assert reader.read(_run(), label) is None
    assert reader.read(_run(peaks=False), label) is None


def _traced_run(span="ragged_span_attention"):
    """Two traced dispatches of two slots whose span calls carry `span` as
    their name beside kv_page_write's, the engine's counters, and timelines
    from which `rows_of_steps` rebuilds what each dispatch fed: request 0
    feeds chunks of a 128-token prompt, request 1 decodes."""
    reduction = {
        "by_op": {
            f"{span} mosaic bf16[5,64,2048]": 0.030,
            "kv_page_write mosaic (bf16[192,80,64,2048], ...)": 0.012,
            "fusion fusion bf16[5,64,11264]": 0.1},
        "by_kind": {"mosaic": 0.042, "fusion": 0.1},
        "spans": collections.Counter({"serving.dispatch": 2})}
    timelines = [
        {"admit": 0.15, "prompt_len": 128, "first": 0.25,
         "tokens": [0.25, 0.35]},
        {"admit": 0.0, "prompt_len": 64, "first": 0.05,
         "tokens": [0.05, 0.15, 0.25]}]
    steps = [(0.0, 0.1), (0.1, 0.2), (0.2, 0.3), (0.3, 0.4)]
    return _run(
        reduction, kind="serve", timelines=timelines, steps=steps,
        traced_steps=[1, 2], width=64, slots=2, model_kwargs=KW,
        engine_stats={"decode_dispatches": 4, "prefill_tokens": 192,
                      "tokens_emitted": 5,
                      "kernel_paths": {"ragged_span_attention/pallas": 48,
                                       "kv_page_write/pallas": 48}})


def test_the_span_kernel_is_read_by_its_own_name_beside_the_page_write():
    run = _traced_run()
    assert named_kernel_ms.read(run, "ragged_span_attention") \
        == pytest.approx(15.0)
    assert named_kernel_ms.read(run, "kv_page_write") == pytest.approx(6.0)
    # the readers by exclusion give what they gave: every Mosaic call,
    # whatever its name, the page write's among them
    assert attn_call_ms.read(run, "backlog") == pytest.approx(21.0)
    assert mosaic_kernel_ms.read(run, "span") == pytest.approx(21.0)
    # a parent's program, whose span calls are named after the jit around
    # them: nothing of that name, and the run stops
    old = _traced_run(span="unified")
    assert named_kernel_ms.read(old, "ragged_span_attention") is None
    assert looped_span_attention_roofline.read(old) is None
    assert attn_call_ms.read(old, "backlog") == pytest.approx(21.0)


def test_the_roofline_is_the_costed_work_over_the_span_kernels_own_time():
    run = _traced_run()
    # step 1: request 0's first chunk (context 0, 64 rows) and request 1's
    # second token (context 64, one row); step 2: request 0's second chunk
    # (context 64) and request 1's third token (context 65)
    rows = [[(0, 64), (64, 1)], [(64, 64), (65, 1)]]
    cost = {k: sum(ref.attention_cost(KW, r)[k] for r in rows)
            for k in ("flops", "bytes")}
    floor = max(cost["flops"] / run.peaks["bf16_flops_per_s"],
                cost["bytes"] / run.peaks["hbm_bytes_per_s"])
    got = looped_span_attention_roofline.read(run)
    assert got == pytest.approx(100 * floor / 0.030)
    assert 0 < got < 100


# -- the reference's arithmetic, by hand --------------------------------------

SMALL = dict(units=8, num_layers=3, num_heads=2, num_kv_heads=1, head_dim=4,
             hidden_size=16, vocab_size=32, total_ut_steps=2,
             dtype="bfloat16")


def test_flops_per_item_counts_every_pass_and_the_head_once():
    # a layer: q 8x8, k and v 8x4 each, o 8x8, three matrices of 8x16
    layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert layer == 576
    matmul = 2 * (3 * layer + 8) + 8 * 32
    assert ref.flops_per_item(SMALL, 0) == 2 * matmul
    # q k^T and p v over 2 query heads of 4, in each of 2 x 3 cache layers
    assert ref.flops_per_item(SMALL, 10) - ref.flops_per_item(SMALL, 0) \
        == 6 * 4 * 2 * 4 * 10
    # the cell: 19.9 GFLOP a token before attention
    assert round(ref.flops_per_item(KW, 0) / 1e9, 1) == 19.9
    assert ref.flops_per_item(KW, 1000) - ref.flops_per_item(KW, 0) \
        == 192 * 4 * 2048 * 1000


def test_attention_cost_reads_each_live_page_once_a_cache_layer():
    # one slot, 10 keys in its pages, 3 query rows: rows attend 11, 12, 13
    cost = ref.attention_cost(SMALL, [(10, 3)])
    assert cost["flops"] == 6 * 4 * (2 * 4) * (11 + 12 + 13)
    # K and V of 13 tokens at 1 KV head of 4, q in and out at 2 heads of 4
    assert cost["bytes"] == 6 * (2 * 13 * 4 + 2 * 3 * 8) * 2
    # the cell: 192 cache layers, a token's keys and values 8 KB a layer
    full = ref.attention_cost(KW, [(960, 64)])
    assert full["bytes"] == 192 * (2 * 1024 * 2048 + 2 * 64 * 2048) * 2
    two = ref.attention_cost(KW, [(960, 64), (10, 1)])
    assert two["flops"] > full["flops"] and two["bytes"] > full["bytes"]


def test_the_limits_lie_in_order():
    assert set(ref.TOLERANCE) == {"logit_rms"}
    assert 0 < ref.STREAM_MARGIN <= ref.ARGMAX_MARGIN
    assert 0.5 < ref.STREAM_AGREE < 1
    assert set(ref.PERTURBATIONS) == {
        "three_passes_for_four", "pass_reads_the_pass_before_its_cache",
        "all_passes_share_the_last_pass_cache",
        "final_norm_not_applied_between_passes", "output_norms_dropped",
        "rope_theta_1e4", "every_matrix_in_float8"}
    assert not set(ref.CONTROLS) & set(ref.PERTURBATIONS)
    # the reference imports nothing of the program
    src = open(os.path.join(ROOT, "benchmarks", "reference",
                            "ouro.py")).read()
    assert "mxnet_tpu" not in src.split('"""', 2)[2]


# -- the tightness readings, at the tiny sizes --------------------------------

def test_the_tightness_readings_run_under_check():
    """runners/serve_long.py's own `main`, which prints what the limits lie
    between: the tiny sizes on the CPU, the paged path against the
    reference and two wrong references."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.serve_long", "--workload",
         CELL, "--check", "--seed", "2147483651", "--only",
         "three_passes_for_four", "pass_reads_the_pass_before_its_cache"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])["2147483651"]
    assert out["reference"]["beyond"] == []
    assert out["reference"]["logit_rms"] < 1e-4
    for name in ("three_passes_for_four",
                 "pass_reads_the_pass_before_its_cache"):
        assert out[name]["beyond"] == ["logit_rms"], name


# -- the other served models' kernel calls ------------------------------------

# what each model's unified program reported at its cell's tiny sizes before
# the span kernel took its layer at run time and a name (the parent commit,
# the same snippet): a span call and a page write a block with pages, and
# the tiles they were built with
REPORTED = {
    "gpt2_774m": (
        {"kv_page_write/pallas": 2, "ragged_span_attention/pallas": 2},
        {"ragged_span_attention/pages=8,keys=128,rows=16": 2}),
    "falcon_h1_34b": (
        {"kv_page_write/xla": 2, "ragged_span_attention/pallas": 2,
         "ssd_chunk_update/pallas": 2},
        {"ragged_span_attention/pages=8,keys=128,rows=32": 2}),
    "nemotron3_super_120b": (
        {"expert_ffn/pallas": 2, "kv_page_write/xla": 2,
         "ragged_span_attention/pallas": 2, "ssd_chunk_update/pallas": 2},
        {"expert_ffn/rows=192,hidden=128": 2,
         "ragged_span_attention/pages=8,keys=128,rows=32": 2}),
    "kimi_linear_48b": (
        {"expert_ffn/pallas": 3, "kda_chunk_update/pallas": 3,
         "kv_page_write/pallas": 1, "latent_span_attention/pallas": 1},
        {"expert_ffn/rows=128,hidden=64": 3,
         "kda_chunk_update/heads=4,rows=16,block=16,pass=4": 3,
         "latent_span_attention/pages=16,keys=256,rows=64,tile=64": 1}),
    "ouro_2_6b": (
        {"kv_page_write/pallas": 3, "ragged_span_attention/pallas": 3},
        {"ragged_span_attention/pages=16,keys=256,rows=16": 3}),
}


@pytest.mark.parametrize("config", sorted(REPORTED))
def test_a_served_models_program_reports_the_kernel_calls_it_did(config):
    import numpy as np
    from benchmarks.weights_per_parameter import seed_weights
    from mxnet_tpu import models
    from mxnet_tpu.serving import Request, ServingEngine
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      config + ".json")))
    cfg = cells.merge(cfg, cfg["tiny"])
    kw = cfg["model"]["kwargs"]
    net = getattr(models, cfg["model"]["class"])(
        getattr(models, cfg["model"]["config_fn"])(**kw))
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, 1, kw["dtype"])
    eng = ServingEngine(net, attn_impl="pallas_interpret", **cfg["engine"])
    done = eng.serve([Request(np.arange(3, 40) % 50, 3)])
    assert done[0].status == "finished" and len(done[0].output_tokens) == 3
    st = eng.stats
    assert (st["kernel_paths"], st["kernel_tiles"]) == REPORTED[config]
