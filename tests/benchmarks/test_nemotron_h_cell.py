"""What PR 32 added to the benchmark: the configuration
nemotron3_super_120b and its cell nemotron3_super_120b.doc_backlog as
entries and data, six reader files for seven per-layer metrics (and the
module of counters they share), the reference's cost functions and
perturbations, and tightness.py. The cell itself runs under `--check` in
test_bench_cells.py, with every other cell."""
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
    expert_ffn_roofline, expert_load_max_over_mean, expert_weights_hbm_share,
    experts_touched_share, moe_kernel_ms, mosaic_kernel_ms,
    routed_pairs_per_live_row, ssd_chunk_roofline)
from benchmarks.reference import nemotron_h as ref  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "nemotron3_super_120b.doc_backlog"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "nemotron3_super_120b.json")))
KW = CONFIG["model"]["kwargs"]
PATTERN_88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
              "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


# -- entries and data ---------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_in_depth_and_experts():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3_super_120b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "num_nextn_predict_layers"]
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if "NVIDIA-Nemotron-3-Super-120B-A12B-BF16" in ln)
        differs = {k for k, v in row["config"].items()
                   if CONFIG.get(k, "no") != v}
        assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                           "num_nextn_predict_layers"}
        assert row["config"]["hybrid_override_pattern"] == PATTERN_88
    # the published sizes stand beside the held ones
    assert CONFIG["published"] == {
        "num_hidden_layers": 88, "hybrid_override_pattern": PATTERN_88,
        "n_routed_experts": 512, "num_nextn_predict_layers": 1}
    assert (CONFIG["num_hidden_layers"], CONFIG["hybrid_override_pattern"],
            CONFIG["n_routed_experts"], CONFIG["held_experts"]) \
        == (11, "MEM*EMEMEME", 512, [0, 128])
    # stage 3 of the published pattern cut at multiples of 11, and every
    # stage holds 5 mixers, 5 expert layers and 1 attention layer
    assert PATTERN_88[33:44] == KW["pattern"] == "MEM*EMEMEME"
    for s in range(8):
        stage = collections.Counter(PATTERN_88[11 * s:11 * s + 11])
        assert stage == {"M": 5, "E": 5, "*": 1}
    assert "8-stage pipeline" in CONFIG["deployment"]
    assert "rotary" in CONFIG["assumed"]
    # what the program is built from says the same as the published keys
    same = {"vocab_size": "vocab_size", "units": "hidden_size",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "ssm_heads": "mamba_num_heads", "ssm_head_dim": "mamba_head_dim",
            "ssm_state": "ssm_state_size", "ssm_groups": "n_groups",
            "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
            "num_experts": "n_routed_experts",
            "top_k": "num_experts_per_tok", "latent_size": "moe_latent_size",
            "expert_hidden_size": "moe_intermediate_size",
            "shared_hidden_size": "moe_shared_expert_intermediate_size",
            "routed_scaling_factor": "routed_scaling_factor",
            "rms_norm_eps": "norm_eps",
            "max_length": "max_position_embeddings"}
    for ours, theirs in same.items():
        assert KW[ours] == CONFIG[theirs], ours
    assert KW["held_experts"] == [0, 128] and KW["rotary"] is False
    assert (KW["dtype"], KW["state_dtype"]) == ("bfloat16", "float32")
    eng = CONFIG["engine"]
    assert eng["prefill_chunk_budget"] == eng["num_slots"] * 64
    assert (eng["max_length"], eng["page_size"], eng["chunk_tokens"]) \
        == (1024, 64, 64)
    assert CONFIG["check"] == {"prompt_lens": [100, 150], "new_tokens": 24}
    assert (CONFIG["runner"], CONFIG["reference"]) \
        == ("serve_large", "nemotron_h")


def test_the_published_sizes_give_the_names_120b_and_12b():
    from mxnet_tpu import models
    c = models.nemotron3_super_120b_config()
    assert (c.pattern, c.num_experts, c.held_experts) \
        == (PATTERN_88, 512, (0, 512))
    expert = 2 * 1024 * 2688
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    kw = dict(KW, pattern=PATTERN_88, held_experts=[0, 512])
    dense = ref._matmul_params(dict(kw, top_k=0))
    # every matrix and the embedding; a token passes 22 experts a layer
    # and gathers its embedding row
    total = dense + 40 * 512 * expert + 131072 * 4096
    active = dense + 40 * 22 * expert
    assert round(total / 1e9, 2) == 120.67 and round(active / 1e9, 2) \
        == 12.23
    assert ref._matmul_params(kw) == active
    assert round(outside / 1e6, 2) == 54.53 and round(expert / 1e6, 3) \
        == 5.505
    # this chip's stage: 10.9 GB at 2 bytes a parameter, 7.05 of experts
    stage = ref._matmul_params(dict(KW, top_k=0)) + 131072 * 4096 \
        + 5 * 128 * expert
    assert 10.85e9 < 2 * stage < 10.95e9
    assert round(2 * 5 * 128 * expert / 1e9, 2) == 7.05


def test_the_traffic_is_gpt2s_file_unchanged():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nemotron3_super_120b", "doc_backlog", 1)
    assert "four ranks" in cell["why"]
    traffic = cells.Cell(CELL).traffic
    assert traffic == cells.Cell("gpt2_774m.doc_backlog").traffic
    assert traffic["arrivals"] == {"process": "backlog", "count": 1200}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CONFIG["engine"]["max_length"]
    assert "judged_by" not in traffic       # prompts booked whole


def test_the_cell_joins_what_reads_it_rightly_and_brings_seven():
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    joined = {"serve_tokens_per_s", "dispatch_ms_p50.backlog",
              "step_device_ms.backlog", "device_idle_share.backlog",
              "peak_hbm_share.backlog", "program_temp_hbm_share.backlog",
              "kv_page_fill_share.backlog", "useful_row_share",
              "kv_pool_used_share_peak", "mosaic_kernel_ms.ssd_chunk",
              "ssd_chunk_roofline", "recurrent_state_hbm_share",
              "state_resets_per_dispatch"} | {
        m["name"] for m in BENCH["per_layer"]
        if m["name"].startswith("tick_host_ms.")}
    new = ["moe_kernel_ms.expert_ffn", "moe_kernel_ms.span",
           "expert_ffn_roofline", "routed_pairs_per_live_row",
           "experts_touched_share", "expert_load_max_over_mean",
           "expert_weights_hbm_share"]
    everywhere = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                  if "workloads" not in m}
    assert listed == joined | set(new) | everywhere
    # these book every Mosaic call that is not `ssd_chunk_update` to the
    # span kernel, and would book the expert kernel to it
    assert not listed & {"attn_call_ms.backlog", "mosaic_kernel_ms.span",
                         "ragged_span_attention_roofline.backlog",
                         "gqa_span_attention_roofline"}
    # held by name, not by place: a later PR appends after them
    brought = [m for m in BENCH["per_layer"] if m["name"] in new]
    assert [m["name"] for m in brought] == new
    for m in brought:
        assert m["workloads"][0] == CELL
        assert m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == "%" or "roofline" not in m["name"]


# -- the readers, on runs made by hand ----------------------------------------

def _run(reduction=None, peaks=True, **facts):
    return types.SimpleNamespace(
        facts=facts, peaks=cells.peaks("TPU v5 lite") if peaks else None,
        tracer=types.SimpleNamespace(reduction=reduction),
        cell=cells.Cell(CELL), say=lambda text: None)


READERS = [(moe_kernel_ms, "expert_ffn"), (moe_kernel_ms, "span"),
           (expert_ffn_roofline, None), (routed_pairs_per_live_row, None),
           (experts_touched_share, None), (expert_load_max_over_mean, None),
           (expert_weights_hbm_share, None)]


@pytest.mark.parametrize("reader, label", READERS,
                         ids=lambda x: getattr(x, "__name__", x))
def test_a_reader_with_nothing_to_read_returns_none(reader, label):
    """No trace and no engine counters, as a program that lacks what this
    PR added gives: nothing is read and nothing is raised."""
    assert reader.read(_run(), label) is None
    assert reader.read(_run(peaks=False), label) is None


# five expert layers over 10 dispatches of the window: (dispatches, live
# rows, pairs, experts touched, largest group), cumulative
MOE = [[10, 10000, 60000, 900, 4000]] * 5


def _traced_run():
    """Four traced dispatches with the cell's own trace names, as the v5e
    gave them (my chip run, PR 32), and the engine's own counters."""
    reduction = {
        "by_op": {"expert_ffn mosaic bf16[45056,1024]": 0.032,
                  "ssd_chunk_update mosaic (bf16[32,64,8192], ...)": 0.010,
                  "unified mosaic bf16[32,1024,256]": 0.004,
                  "fusion fusion bf16[32,64,18560]": 0.3},
        "spans": collections.Counter({"serving.dispatch": 4})}
    return _run(
        reduction, kind="serve",
        engine_stats={"decode_dispatches": 10,
                      "expert_weight_bytes": 7_046_430_720,
                      "model_counters": {"moe": MOE},
                      "kernel_paths": {"ssd_chunk_update/pallas": 5,
                                       "expert_ffn/pallas": 5,
                                       "ragged_span_attention/pallas": 1}},
        slots=32, width=64, model_kwargs=KW)


def test_three_kinds_of_mosaic_call_are_told_apart_by_name():
    run = _traced_run()
    assert moe_kernel_ms.read(run, "expert_ffn") == pytest.approx(8.0)
    assert moe_kernel_ms.read(run, "span") == pytest.approx(1.0)
    assert mosaic_kernel_ms.read(run, "ssd_chunk") == pytest.approx(2.5)
    # the accepted span reader would book the expert kernel to the span
    # kernel: why the cell does not list it
    assert mosaic_kernel_ms.read(run, "span") == pytest.approx(9.0)
    with pytest.raises(ValueError, match="no Mosaic kernel of kind"):
        moe_kernel_ms.read(run, "ssd_chunk")
    # a trace without the kind stops the run: a kernel that fell to
    # ragged_dot must not read as the fastest kernel of all
    del run.tracer.reduction["by_op"]["expert_ffn mosaic bf16[45056,1024]"]
    assert moe_kernel_ms.read(run, "expert_ffn") is None
    assert expert_ffn_roofline.read(run) is None
    # but for a run whose engine names no kernel paths at all
    # (test_bench_units.py's, made by hand with GPT-2's trace)
    del run.facts["engine_stats"]["kernel_paths"]
    assert moe_kernel_ms.read(run, "expert_ffn") == 0.0
    assert expert_ffn_roofline.read(run) == 0.0


def test_the_expert_roofline_is_the_counted_work_over_the_kernels_time():
    run = _traced_run()
    # a dispatch, over the five layers: 5 x 6000 pairs, 5 x 90 touched;
    # four traced dispatches
    cost = ref.expert_cost(KW, 4 * 5 * 6000, 4 * 5 * 90)
    assert cost["flops"] == 4 * 1024 * 2688 * 120000
    assert cost["bytes"] == (2 * 1024 * 2688 * 1800 + 2 * 1024 * 120000) * 2
    peaks = run.peaks
    floor = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    assert expert_ffn_roofline.read(run) == pytest.approx(100 * floor / 0.032)
    assert 0 < expert_ffn_roofline.read(run) < 100
    # the chunk kernel's reader takes this reference's ssm_cost unchanged
    assert hasattr(ref, ssd_chunk_roofline.COST)


def test_the_counters_are_the_expert_layers_own():
    run = _traced_run()
    assert routed_pairs_per_live_row.read(run) == pytest.approx(6.0)
    assert experts_touched_share.read(run) == pytest.approx(
        100 * 900 / (10 * 128))
    assert expert_load_max_over_mean.read(run) == pytest.approx(
        4000 / (60000 / 128))
    assert expert_weights_hbm_share.read(run) == pytest.approx(
        100 * 7_046_430_720 / (16 * 2 ** 30))
    # under --check there is no chip: a count over the published constant
    run.peaks = None
    assert expert_weights_hbm_share.read(run) == pytest.approx(41.015625)
    # an engine without experts counts none
    del run.facts["engine_stats"]["model_counters"]
    del run.facts["engine_stats"]["expert_weight_bytes"]
    for reader in (routed_pairs_per_live_row, experts_touched_share,
                   expert_load_max_over_mean, expert_weights_hbm_share):
        assert reader.read(run) == 0.0


# -- the reference's arithmetic -----------------------------------------------

def test_costs_count_the_layers_of_their_own_kind():
    one = ref.ssm_cost(KW, [(500, 1)])
    state = 2 * 128 * 64 * 128 * 4
    row = (2 * 128 * 64 + 2 * 8 * 128) * 2 + 4 * 128
    assert one["bytes"] == 5 * (state + row)            # five mixers
    assert one["flops"] == 5 * 2 * (2 * 128 * 64 * 128 + 8 * 128 + 128 * 64)
    got = ref.attention_cost(KW, [(100, 1)])            # one attention layer
    assert got["flops"] == 4 * 32 * 128 * 101
    assert got["bytes"] == (2 * 101 * 2 * 128 + 2 * 32 * 128) * 2
    per_token = ref.flops_per_item(KW, 100)
    assert per_token == 2 * ref._matmul_params(KW) \
        + 4 * 32 * 128 * 100 + 5 * 4 * 128 * 64 * 128
    # the held quarter of a token's 22 experts
    assert ref._matmul_params(KW) - ref._matmul_params(dict(KW, top_k=0)) \
        == 5 * 5.5 * 2 * 1024 * 2688


def test_the_perturbations_are_keywords_the_reference_takes():
    import inspect
    taken = set(inspect.signature(ref.logits).parameters) \
        | set(inspect.signature(ref.falcon_h1.mixer_branch).parameters) \
        | set(inspect.signature(ref.expert_layer).parameters)
    assert len(ref.PERTURBATIONS) == 6
    for name, kw in ref.PERTURBATIONS.items():
        assert set(kw) <= taken, name
    assert ref.TOLERANCE["logit_abs"] > 0


def test_tightness_runs_the_cell_under_check():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "tightness.py"),
         "--workload", CELL, "--seed", "5", "--check"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # float32 against float32: the model is the reference, chooses the
    # reference's experts exactly, and every perturbation that can show at
    # this length does (one chunk of 64 tokens: no state is carried)
    assert out["reference"] < 1e-4 and out["chosen_share"] == [1.0, 1.0]
    for name in ref.PERTURBATIONS:
        if name != "state_zeroed_every_64_tokens":
            assert out[name] > 100 * out["reference"], name
    assert out["bias_redrawn"]["choice_made_without_the_bias"] \
        > 100 * out["bias_redrawn"]["reference"]
