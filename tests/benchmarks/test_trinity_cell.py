"""The benchmark's configuration trinity_mini and its
cell trinity_mini.longdoc32k_backlog as entries and data, a traffic file,
three reader files and four per-layer entries (the window kernel read by its
own name, its roofline and the full layer's, the share of the keys the
window layers visit), and the reference's cost functions and perturbations.
The cell itself runs under `--check` in test_bench_cells.py, with every
other cell; here its tightness readings do."""
import collections
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import cells  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    global_span_attention_roofline, named_kernel_ms, window_key_share,
    window_span_attention_roofline)
from benchmarks.reference import trinity as ref  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "trinity_mini.longdoc32k_backlog"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "trinity_mini.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "longdoc32k_backlog.json")))
KW = CONFIG["model"]["kwargs"]
NEW = ["named_kernel_ms.window_span_attention",
       "window_span_attention_roofline", "global_span_attention_roofline",
       "window_key_share"]
S, F = "sliding_attention", "full_attention"


# -- entries and data ---------------------------------------------------------

def test_the_configuration_is_the_published_one_but_what_reduced_names():
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity_mini")
    reduced = ["num_hidden_layers", "layer_types", "num_dense_layers"]
    assert entry["reduced"] == CONFIG["reduced"] == reduced
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/trinity_mini.json"
    assert len(entry["why"]) <= 200
    # an architecture catalog (JSON lines, one model a line), where given
    catalog = os.environ.get("MODEL_CATALOG", "")
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"Trinity-Mini"' in ln)
        assert {k for k, v in row["config"].items()
                if CONFIG.get(k, "no") != v} == set(reduced)
        assert CONFIG["published"] == {k: row["config"][k] for k in reduced}
    # the published layers 1-5: the second dense layer, then window, FULL,
    # window, window with experts
    assert CONFIG["published"]["layer_types"][1:6] == CONFIG["layer_types"] \
        == KW["layer_types"] == [S, S, F, S, S]
    assert CONFIG["num_hidden_layers"] == 5 and CONFIG["num_dense_layers"] \
        == KW["dense_layers"] == 1
    # no width, no expert, no word of the vocabulary is cut
    for ours, theirs in (
            ("units", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("dense_hidden_size", "intermediate_size"),
            ("num_experts", "num_experts"), ("top_k", "num_experts_per_tok"),
            ("expert_hidden_size", "moe_intermediate_size"),
            ("shared_hidden_size", "moe_intermediate_size"),
            ("routed_scaling_factor", "route_scale"),
            ("window", "sliding_window"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps"), ("vocab_size", "vocab_size"),
            ("max_length", "max_position_embeddings")):
        assert KW[ours] == CONFIG[theirs], ours
    assert "held_experts" not in KW and KW["dtype"] == "bfloat16"
    assert CONFIG["mup_enabled"] is True     # the program always scales
    # what the config lacks is listed with where the modelling code sets it
    for key in ("qk_norm", "output_gate", "rotary", "embedding_scale",
                "sandwich_norms", "window_edge", "ring", "dtype", "weights",
                "num_slots", "max_length", "check"):
        assert key in CONFIG["assumed"], key
    assert (CONFIG["runner"], CONFIG["reference"]) == ("serve_long",
                                                       "trinity")


def test_the_bytes_the_configuration_reckons():
    """8.483 GB of weights from the program's own parameter shapes, a pool
    of 2.152 GB for the one full layer and rings of 0.570 GB for the four
    window layers: 11.2 GB; with one pool geometry 19.2 GB."""
    from mxnet_tpu import models
    net = models.TrinityForCausalLM(models.trinity_mini_config(**KW))
    shapes = {k: p.shape for k, p in net.collect_params().items()}
    total = sum(int(np.prod(s)) for s in shapes.values())
    experts = sum(int(np.prod(s)) for k, s in shapes.items()
                  if k.endswith(("expert_w1", "expert_w2")))
    assert total == 4_241_534_720 and round(total * 2 / 1e9, 3) == 8.483
    assert round(experts * 2 / 1e9, 3) == 6.442
    assert "4.2415B parameters, 8.483 GB" in CONFIG["deployment"]
    engine = CONFIG["engine"]
    assert engine == {"num_slots": 32, "max_length": 32832, "page_size": 64,
                      "chunk_tokens": 64, "prefill_chunk_budget": 2048}
    token = 2 * 4 * 128 * 2                      # K and V a layer, bf16
    pages = 32 * 32832 // 64
    pool = pages * 64 * token
    rings = 4 * 32 * 34 * 64 * token
    assert pages == 16_416 and round(pool / 1e9, 3) == 2.152
    assert round(rings / 1e9, 3) == 0.570
    assert round((total * 2 + pool + rings) / 1e9, 1) == 11.2
    one_geometry = 5 * pool
    assert round(one_geometry / 1e9, 2) == 10.76
    assert round((total * 2 + one_geometry) / 1e9, 1) == 19.2 > 16.91
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"] \
        <= engine["max_length"]
    # what serve_long's company queues behind the check fits a slot
    check = CONFIG["check"]
    assert check == {"prompt_lens": [200, 8200], "new_tokens": 24}
    ticks = -(-max(check["prompt_lens"]) // 64) + check["new_tokens"]
    assert ticks * 64 // 2 + 8 <= engine["max_length"]
    # the check's long prompt wraps every ring and lies past the window
    assert max(check["prompt_lens"]) > 3 * 34 * 64 > KW["window"]
    tiny = cells.merge(CONFIG, CONFIG["tiny"])
    assert tiny["model"]["kwargs"]["window"] \
        < max(tiny["check"]["prompt_lens"])


def test_the_traffic_is_a_file_of_the_generators_parameters():
    assert TRAFFIC["generator"] == "request_stream"
    assert TRAFFIC["arrivals"] == {"process": "backlog", "count": 480}
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 8192,
                                     "max": 32768}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 12,
                                     "sigma": 0.6, "min": 4, "max": 32}
    assert TRAFFIC["sampling"] == {"do_sample": False}
    assert TRAFFIC["judged_by"] == "fed_and_emitted_tokens"
    assert "2k window" in TRAFFIC["who"]
    from benchmarks.generators import request_stream
    specs = request_stream.generate(TRAFFIC, 1000, 2 ** 31 + 5, 51.0)
    assert len(specs) == 480 and all(s["due"] == 0 for s in specs)
    lens = [len(s["prompt"]) for s in specs]
    assert min(lens) >= 8192 and max(lens) <= 32768
    assert 20000 < sum(lens) / 480 < 21000


def test_the_cell_joins_what_reads_it_rightly():
    """By NAME, not by position: a later PR appends after these."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "trinity_mini",
                     "traffic": "longdoc32k_backlog", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    joined = {"serve_tokens_per_s", "dispatch_ms_p50.backlog",
              "useful_row_share", "kv_pool_used_share_peak",
              "kv_page_fill_share.backlog", "step_device_ms.backlog",
              "device_idle_share.backlog", "peak_hbm_share.backlog",
              "program_temp_hbm_share.backlog", "moe_kernel_ms.expert_ffn",
              "expert_ffn_roofline", "routed_pairs_per_live_row",
              "experts_touched_share", "expert_load_max_over_mean",
              "expert_weights_hbm_share", "named_kernel_ms.kv_page_write",
              "named_kernel_ms.ragged_span_attention", "kv_pool_hbm_share",
              "recurrent_state_hbm_share", "state_resets_per_dispatch"} | {
        m["name"] for m in BENCH["per_layer"]
        if m["name"].startswith("tick_host_ms.")}
    everywhere = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                  if "workloads" not in m}
    assert listed == joined | set(NEW) | everywhere
    assert len([n for n in listed if n.startswith("tick_host_ms.")]) == 9
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    # appended, never put first
    for name in joined - {"serve_tokens_per_s"}:
        assert by_name[name]["workloads"][-1] == CELL, name
        assert by_name[name]["workloads"][0] != CELL, name
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert (m["unit"] == "%") == ("roofline" in name or "share" in name)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert by_name["window_key_share"]["source"] == "program_counter"
    assert by_name["window_key_share"]["layer"] == "kv_cache"
    c = cells.Cell(CELL)
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    # at most a quarter of the cells on four chips; this one on one
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= len(BENCH["workloads"]) // 4


# -- the readers, on runs made by hand ----------------------------------------

def _run(reduction=None, peaks=True, **facts):
    return types.SimpleNamespace(
        facts=facts, peaks=cells.peaks("TPU v5 lite") if peaks else None,
        tracer=types.SimpleNamespace(reduction=reduction),
        cell=cells.Cell(CELL), say=lambda text: None)


@pytest.mark.parametrize("reader, label", [
    (window_span_attention_roofline, None),
    (global_span_attention_roofline, None), (window_key_share, None),
    (named_kernel_ms, "window_span_attention")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_reader_with_nothing_to_read_returns_none(reader, label):
    """No trace and no engine counters, as a program that lacks what this
    PR added gives: nothing is read and nothing is raised."""
    assert reader.read(_run(), label) is None
    assert reader.read(_run(peaks=False), label) is None


def test_a_reader_of_a_run_without_the_counters_returns_a_number():
    """An engine that reports stats but no kernel paths and no
    `window_keys` (the run made by hand in test_bench_units.py, and a
    parent's program): 0.0, not None."""
    stats = {"decode_dispatches": 5, "prefill_tokens": 100,
             "tokens_emitted": 3}
    reduction = {"by_op": {"unified mosaic bf16[16,64,1280]": 0.2},
                 "by_kind": {"mosaic": 0.2},
                 "spans": collections.Counter({"serving.dispatch": 4})}
    run = _run(reduction, kind="serve", engine_stats=stats,
               traced_steps=[1, 2], steps=[(0, 1)], timelines=[], width=64,
               model_kwargs=KW)
    assert window_key_share.read(run) == 0.0
    assert window_span_attention_roofline.read(run) == 0.0
    assert global_span_attention_roofline.read(run) == 0.0
    assert named_kernel_ms.read(run, "window_span_attention") == 0.0


def _traced_run():
    """Two traced dispatches of two slots whose full layer's span call and
    window layers' calls carry their own names beside kv_page_write's, the
    engine's counters, and timelines from which `rows_of_steps` rebuilds
    what each dispatch fed: request 0 feeds chunks of a 128-token prompt
    from context 9000 on, request 1 decodes."""
    reduction = {
        "by_op": {
            "ragged_span_attention mosaic bf16[2,512,512]": 0.008,
            "window_span_attention mosaic bf16[2,512,512]": 0.012,
            "kv_page_write mosaic (bf16[1,1026,64,512], ...)": 0.002,
            "fusion fusion bf16[2,64,2048]": 0.1},
        "by_kind": {"mosaic": 0.022, "fusion": 0.1},
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
                      "model_counters": {"window_keys": [40, 160]},
                      "kernel_paths": {"ragged_span_attention/pallas": 1,
                                       "window_span_attention/pallas": 4,
                                       "kv_page_write/pallas": 5}})


def test_each_kernel_is_read_by_its_own_name():
    run = _traced_run()
    assert named_kernel_ms.read(run, "window_span_attention") \
        == pytest.approx(6.0)
    assert named_kernel_ms.read(run, "ragged_span_attention") \
        == pytest.approx(4.0)
    assert window_key_share.read(run) == pytest.approx(25.0)


def test_each_roofline_is_its_costed_work_over_its_kernels_own_time():
    run = _traced_run()
    # step 1: request 0's first chunk (context 0, 64 rows) and request 1's
    # second token (context 64); step 2: request 0's second chunk (context
    # 64) and request 1's third token (context 65)
    rows = [[(0, 64), (64, 1)], [(64, 64), (65, 1)]]
    for reader, cost_fn, secs in (
            (window_span_attention_roofline, ref.window_attention_cost,
             0.012),
            (global_span_attention_roofline, ref.attention_cost, 0.008)):
        cost = {k: sum(cost_fn(KW, r)[k] for r in rows)
                for k in ("flops", "bytes")}
        floor = max(cost["flops"] / run.peaks["bf16_flops_per_s"],
                    cost["bytes"] / run.peaks["hbm_bytes_per_s"])
        got = reader.read(run)
        assert got == pytest.approx(100 * floor / secs)
        assert 0 < got < 100


# -- the reference's arithmetic, by hand --------------------------------------

SMALL = dict(units=8, layer_types=[S, F, S], num_heads=2, num_kv_heads=1,
             head_dim=4, dense_layers=1, dense_hidden_size=16,
             num_experts=4, top_k=2, expert_hidden_size=6,
             shared_hidden_size=6, vocab_size=32, window=5,
             ring_page_size=4, dtype="bfloat16")


def test_the_window_cost_counts_the_window_and_the_pages_visited():
    # one slot, 10 keys before, 3 rows: they see 5 keys each in a window
    # layer (positions 6-10, 7-11, 8-12) and 11, 12, 13 in the full one
    win = ref.window_attention_cost(SMALL, [(10, 3)])
    assert win["flops"] == 2 * 4 * 8 * (5 + 5 + 5)
    # pages 1 (key 6) to 3 (position 12) of 4 keys, K and V at 4 columns,
    # the 3 queries in and out at 8
    assert win["bytes"] == 2 * (2 * 3 * 4 * 4 + 2 * 3 * 8) * 2
    full = ref.attention_cost(SMALL, [(10, 3)])
    assert full["flops"] == 4 * 8 * (11 + 12 + 13)
    assert full["bytes"] == (2 * 13 * 4 + 2 * 3 * 8) * 2
    # a short context: every key is in the window
    assert ref.window_attention_cost(SMALL, [(1, 2)])["flops"] \
        == 2 * 4 * 8 * (2 + 3)


def test_flops_per_item_counts_the_window():
    # a layer's attention: q, gate and o 8x8 each, k and v 8x4 each
    attn = 3 * 8 * 8 + 2 * 8 * 4
    dense = 3 * 8 * 16
    expert = 8 * 4 + 3 * 8 * 6 + 2 * 3 * 8 * 6
    matmul = 3 * attn + dense + 2 * expert + 8 * 32
    assert ref.flops_per_item(SMALL, 0) == 2 * matmul
    assert ref.flops_per_item(SMALL, 100) - ref.flops_per_item(SMALL, 0) \
        == 4 * 8 * (100 + 2 * 5)
    # the cell: the full layer over every key, the four window layers over
    # at most 2048
    assert ref.flops_per_item(KW, 32768) - ref.flops_per_item(KW, 0) \
        == 4 * 4096 * (32768 + 4 * 2048)


def test_the_limits_lie_in_order():
    # the limit is on a position's median: the root mean square over all
    # positions carries the few routing flips (reference/trinity.py)
    assert set(ref.TOLERANCE) == {"logit_rms_p50"}
    assert 0 < ref.STREAM_MARGIN <= ref.ARGMAX_MARGIN
    assert 0.5 < ref.STREAM_AGREE < 1
    assert {"every_matrix_in_float8", "no_window_every_layer_full",
            "rope_on_full_layers_too", "no_output_gate", "no_qk_norm",
            "no_embedding_scale"} <= set(ref.PERTURBATIONS)
    assert not set(ref.CONTROLS) & set(ref.PERTURBATIONS)
    # the reference imports nothing of the program
    src = open(os.path.join(ROOT, "benchmarks", "reference",
                            "trinity.py")).read()
    assert "mxnet_tpu" not in src.split('"""', 2)[2]


# -- the tightness readings, at the tiny sizes --------------------------------

def test_every_perturbation_reads_beyond_the_limit_under_check():
    """runners/serve_long.py's own `main`, which prints what the limits lie
    between: the tiny sizes on the CPU, the paged path against the
    reference and against every perturbation."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.serve_long", "--workload",
         CELL, "--check", "--seed", "2147483651", "--only",
         *sorted(ref.PERTURBATIONS)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])["2147483651"]
    assert out["reference"]["beyond"] == []
    assert out["reference"]["logit_rms"] < 1e-4
    for name in ref.PERTURBATIONS:
        assert out[name]["beyond"] == ["logit_rms_p50"], (name, out[name])
