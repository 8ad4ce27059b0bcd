"""What PR 34 added to the benchmark: the configuration kimi_linear_48b and
its cell kimi_linear_48b.longdoc_backlog as entries and data, a traffic
file, the runner serve_long (its comparison at chosen positions and its
count of fed and emitted tokens), four reader files for six per-layer
metrics, and the reference's cost functions and perturbations. The cell
itself runs under `--check` in test_bench_cells.py, with every other cell."""
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
    kda_chunk_roofline, kv_pool_hbm_share, latent_span_attention_roofline,
    moe_kernel_ms, mosaic_kernel_ms, named_kernel_ms)
from benchmarks.reference import kimi_linear as ref  # noqa: E402
from benchmarks.runners import serve_long  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "kimi_linear_48b.longdoc_backlog"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "kimi_linear_48b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "longdoc_backlog.json")))
KW = CONFIG["model"]["kwargs"]
NEW = ["named_kernel_ms.kda_chunk_update",
       "named_kernel_ms.latent_span_attention",
       "named_kernel_ms.kv_page_write", "kda_chunk_roofline",
       "latent_span_attention_roofline", "kv_pool_hbm_share"]


# -- entries and data ---------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_in_depth_and_experts():
    entry = next(c for c in BENCH["configs"] if c["name"] == "kimi_linear_48b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts"]
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/kimi_linear_48b.json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if "Kimi-Linear-48B-A3B-Instruct" in ln)
        differs = {k for k, v in row["config"].items()
                   if CONFIG.get(k, "no") != v}
        assert differs == {"num_hidden_layers", "linear_attn_config"}
        assert CONFIG["published"]["linear_attn_config"] \
            == row["config"]["linear_attn_config"]
        # inside the cut group no width moves
        for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
            assert CONFIG["linear_attn_config"][key] \
                == row["config"]["linear_attn_config"][key]
    assert CONFIG["published"]["num_hidden_layers"] == 27
    assert CONFIG["num_hidden_layers"] == 9 == len(KW["pattern"])
    # published layers 1-9: latent attention at 4 and 8, the rest KDA
    assert CONFIG["linear_attn_config"]["full_attn_layers"] == [4, 8]
    assert CONFIG["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5, 6, 7, 9]
    assert KW["pattern"] == "KKKLKKKLK"
    assert [i + 1 for i, k in enumerate(KW["pattern"]) if k == "L"] == [4, 8]
    assert CONFIG["held_experts"] == KW["held_experts"] == [0, 64]
    assert "12 chips" in CONFIG["deployment"]
    assert "stage 0" in CONFIG["deployment"]
    assert "expert rank 0" in CONFIG["deployment"]
    # every size the published config lacks is listed with where it is set
    for key in ("kda_low_rank", "A_log", "dt_bias", "conv_weight", "qk_norm",
                "o_norm", "gate_bias", "row_width", "num_slots"):
        assert key in CONFIG["assumed"], key


def test_no_width_differs_from_the_published_config():
    for ours, theirs in (
            ("units", "hidden_size"), ("dense_hidden_size",
                                       "intermediate_size"),
            ("num_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("num_experts", "num_experts"),
            ("top_k", "num_experts_per_token"),
            ("expert_hidden_size", "moe_intermediate_size"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("vocab_size", "vocab_size"), ("rms_norm_eps", "rms_norm_eps"),
            ("dense_layers", "first_k_dense_replace")):
        assert KW[ours] == CONFIG[theirs], ours
    lin = CONFIG["linear_attn_config"]
    assert (KW["kda_heads"], KW["kda_head_dim"], KW["conv_kernel"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert KW["shared_hidden_size"] == CONFIG["num_shared_experts"] \
        * CONFIG["moe_intermediate_size"]
    assert "row_width" not in KW     # the layer's to lay out, not an option


def test_the_stage_is_the_bytes_the_configuration_reckons():
    """9.68 GB of weights, 7.25 of them routed experts, from the shapes."""
    d, hd, low = 2304, 4096, 128
    kda = 3 * d * hd + hd * d + 2 * (d * low + low * hd) + d * 32 \
        + 3 * hd * 4 + hd + 32 + 128
    latent = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 4096 * d
    expert = 3 * d * 1024
    moe = d * 256 + 256 + expert + 64 * expert
    total = 7 * kda + 2 * latent + 3 * d * 9216 + 8 * moe + 18 * d \
        + 2 * 163840 * d + d
    assert round(total * 2 / 1e9, 2) == 9.68
    assert round(8 * 64 * expert * 2 / 1e9, 2) == 7.25
    assert "9.68 GB" in CONFIG["deployment"]
    assert "7.25 GB" in CONFIG["deployment"]
    engine = CONFIG["engine"]
    assert engine == {"num_slots": 32, "max_length": 16448, "page_size": 64,
                      "chunk_tokens": 64, "prefill_chunk_budget": 2048}
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"] \
        <= engine["max_length"]


def test_the_traffic_is_a_file_of_the_generators_parameters():
    assert TRAFFIC["generator"] == "request_stream"
    assert TRAFFIC["arrivals"] == {"process": "backlog", "count": 600}
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 4096,
                                     "max": 16384}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 12,
                                     "sigma": 0.6, "min": 4, "max": 32}
    assert TRAFFIC["sampling"] == {"do_sample": False}
    assert TRAFFIC["judged_by"] == "fed_and_emitted_tokens"
    from benchmarks.generators import request_stream
    specs = request_stream.generate(TRAFFIC, 1000, 2 ** 31 + 5, 51.0)
    assert len(specs) == 600 and all(s["due"] == 0 for s in specs)
    lens = [len(s["prompt"]) for s in specs]
    assert min(lens) >= 4096 and max(lens) <= 16384
    assert 9900 < sum(lens) / 600 < 10600


def test_the_cell_joins_what_reads_it_rightly_and_brings_six():
    """By NAME, not by position: a later PR appends after these."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "kimi_linear_48b",
                     "traffic": "longdoc_backlog", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    joined = {"serve_tokens_per_s", "dispatch_ms_p50.backlog",
              "useful_row_share", "kv_pool_used_share_peak",
              "kv_page_fill_share.backlog", "step_device_ms.backlog",
              "device_idle_share.backlog", "peak_hbm_share.backlog",
              "program_temp_hbm_share.backlog", "recurrent_state_hbm_share",
              "state_resets_per_dispatch", "moe_kernel_ms.expert_ffn",
              "expert_ffn_roofline", "routed_pairs_per_live_row",
              "experts_touched_share", "expert_load_max_over_mean",
              "expert_weights_hbm_share"} | {
        m["name"] for m in BENCH["per_layer"]
        if m["name"].startswith("tick_host_ms.")}
    everywhere = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                  if "workloads" not in m}
    assert listed == joined | set(NEW) | everywhere
    assert len([n for n in listed if n.startswith("tick_host_ms.")]) == 9
    # each of these would book this program's other kernels to the wrong one
    assert not listed & {
        "attn_call_ms.backlog", "ragged_span_attention_roofline.backlog",
        "mosaic_kernel_ms.ssd_chunk", "mosaic_kernel_ms.span",
        "moe_kernel_ms.span", "ssd_chunk_roofline",
        "gqa_span_attention_roofline"}
    brought = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in brought] == NEW
    for m in brought:
        assert m["workloads"] == [CELL] or m["workloads"][0] == CELL
        assert m["moves"] == "serve_tokens_per_s"
        assert (m["unit"] == "%") == ("roofline" in m["name"]
                                      or "share" in m["name"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cell is appended to the lists it joins, after the accepted cells
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW:
            assert m["workloads"][-1] == CELL, m["name"]


# -- the readers, on runs made by hand ----------------------------------------

def _run(reduction=None, peaks=True, **facts):
    return types.SimpleNamespace(
        facts=facts, peaks=cells.peaks("TPU v5 lite") if peaks else None,
        tracer=types.SimpleNamespace(reduction=reduction),
        cell=cells.Cell(CELL), say=lambda text: None)


READERS = [(named_kernel_ms, "kda_chunk_update"),
           (named_kernel_ms, "latent_span_attention"),
           (named_kernel_ms, "kv_page_write"), (kda_chunk_roofline, None),
           (latent_span_attention_roofline, None), (kv_pool_hbm_share, None)]


@pytest.mark.parametrize("reader, label", READERS,
                         ids=lambda x: getattr(x, "__name__", x))
def test_a_reader_with_nothing_to_read_returns_none(reader, label):
    """No trace and no engine counters, as a program that lacks what this
    PR added gives: nothing is read and nothing is raised."""
    assert reader.read(_run(), label) is None
    assert reader.read(_run(peaks=False), label) is None


def _traced_run():
    """Two traced dispatches of two slots with the cell's own trace names,
    as the v5e gave them (my chip run, PR 34), the engine's counters, and
    timelines from which `rows_of_steps` rebuilds what each dispatch fed:
    request 0 feeds chunks of a 128-token prompt, request 1 decodes."""
    reduction = {
        "by_op": {
            "latent_span_attention mosaic bf16[32,2048,512]": 0.032,
            "kda_chunk_update mosaic (bf16[32,64,4096], ...)": 0.030,
            "expert_ffn mosaic bf16[16384,2304]": 0.022,
            "kv_page_write mosaic bf16[2,8224,64,640]": 0.0002,
            "fusion fusion bf16[32,64,12288]": 0.3},
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
                      "tokens_emitted": 5, "kv_pool_bytes": 1347420160,
                      "kernel_paths": {"kda_chunk_update/pallas": 7,
                                       "latent_span_attention/pallas": 2,
                                       "expert_ffn/pallas": 8,
                                       "kv_page_write/pallas": 2}})


def test_four_kinds_of_mosaic_call_are_read_by_their_own_names():
    run = _traced_run()
    read = lambda name: named_kernel_ms.read(run, name)
    assert read("kda_chunk_update") == pytest.approx(15.0)
    assert read("latent_span_attention") == pytest.approx(16.0)
    assert read("kv_page_write") == pytest.approx(0.1)
    assert moe_kernel_ms.read(run, "expert_ffn") == pytest.approx(11.0)
    # the accepted readers by exclusion would book all of them to the span
    # kernel: why the cell lists none of those
    assert mosaic_kernel_ms.read(run, "span") == pytest.approx(42.1)
    with pytest.raises(ValueError, match="name"):
        named_kernel_ms.read(run, None)
    # a trace without the call stops the run: a kernel that fell to its
    # dense form must not read as the fastest kernel of all
    del run.tracer.reduction["by_op"][
        "kda_chunk_update mosaic (bf16[32,64,4096], ...)"]
    assert read("kda_chunk_update") is None
    assert kda_chunk_roofline.read(run) is None
    # but for a run whose engine names no kernel paths at all
    # (test_bench_units.py's, made by hand with GPT-2's trace)
    del run.facts["engine_stats"]["kernel_paths"]
    assert read("kda_chunk_update") == 0.0
    assert kda_chunk_roofline.read(run) == 0.0
    assert read("latent_span_attention") == pytest.approx(16.0)


def test_the_rooflines_are_the_costed_work_over_each_kernels_own_time():
    run = _traced_run()
    # step 1: request 0's first chunk (context 0, 64 rows) and request 1's
    # second token (context 64, one row); step 2: request 0's second chunk
    # (context 64) and request 1's third token (context 65)
    rows = [[(0, 64), (64, 1)], [(64, 64), (65, 1)]]
    peaks = run.peaks
    for reader, cost_fn, secs in (
            (kda_chunk_roofline, ref.kda_cost, 0.030),
            (latent_span_attention_roofline, ref.attention_cost, 0.032)):
        cost = {k: sum(cost_fn(KW, r)[k] for r in rows)
                for k in ("flops", "bytes")}
        floor = max(cost["flops"] / peaks["bf16_flops_per_s"],
                    cost["bytes"] / peaks["hbm_bytes_per_s"])
        assert reader.read(run) == pytest.approx(100 * floor / secs)
        assert 0 < reader.read(run) < 100


def test_the_pool_share_counts_the_padded_row():
    run = _traced_run()
    # 2 layers x 32 slots x 257 pages x 64 rows x 640 columns x 2 bytes
    assert 2 * 32 * 257 * 64 * 640 * 2 == 1347420160
    assert kv_pool_hbm_share.read(run) == pytest.approx(
        100 * 1347420160 / (16 * 2 ** 30))
    run.peaks = None            # under --check: the published constant
    assert kv_pool_hbm_share.read(run) == pytest.approx(7.843017578125)
    del run.facts["engine_stats"]["kv_pool_bytes"]
    assert kv_pool_hbm_share.read(run) == 0.0


# -- the runner ---------------------------------------------------------------

def test_fed_and_emitted_tokens_is_the_engines_count_over_the_window():
    stats = {"prefill_tokens": 979060, "tokens_emitted": 1174}
    assert serve_long.fed_and_emitted_per_s(stats, 51.0) \
        == pytest.approx((979060 + 1174) / 51.0)
    assert "fed_and_emitted_tokens" in serve_long.__doc__
    assert CONFIG["runner"] == "serve_long"
    assert CONFIG["check"] == {"prompt_lens": [200, 8200], "new_tokens": 24}


def test_the_comparison_reads_the_largest_and_the_mean_difference():
    import jax.numpy as jnp
    own = [jnp.zeros((2, 4)), jnp.ones((1, 4))]
    want = [jnp.zeros((2, 4)).at[0, 1].set(0.5), jnp.ones((1, 4))]
    read = serve_long.errors(own, want)
    assert read["logit_abs"] == pytest.approx(0.5)
    assert read["logit_rms"] == pytest.approx((0.25 / 12) ** 0.5)
    # one position of three moved: the positions' median did not
    assert read["logit_rms_p50"] == 0.0
    assert serve_long.beyond(read, {"logit_abs": 0.4, "logit_rms": 0.2}) \
        == ["logit_abs"]
    # every limit is one of the readings; the engine's stream is held to
    # the paged path's best token at most positions and within a margin
    # of it everywhere
    assert set(ref.TOLERANCE) <= set(read)
    assert 0 < ref.STREAM_MARGIN <= ref.ARGMAX_MARGIN
    assert 0.5 < ref.STREAM_AGREE < 1


def test_the_company_keeps_every_other_slot_busy_past_the_longest_check():
    import numpy as np
    rng = np.random.default_rng(3)
    ticks = -(-8200 // 64) + 24
    first, behind = serve_long.company(rng, 163840, 32, 64, ticks)
    assert len(first) == 32     # every slot taken before the check's turn
    assert all(64 <= r.prompt_len <= 512 for r in first)
    longest = max(r.prompt_len for r in behind)
    assert ticks * 64 // 8 <= min(r.prompt_len for r in behind)
    assert longest <= ticks * 64 // 2
    slot_ticks = sum(-(-r.prompt_len // 64) + r.max_new_tokens
                     for r in behind)
    assert slot_ticks >= 31 * ticks
    assert len({r.id for r in first + behind}) == len(first) + len(behind)


def _tiny_cell_served(monkeypatch, spoil=None):
    """check_reference over the cell's tiny blocks, float32 on the CPU;
    `spoil(checks)` runs between the serving and the comparisons."""
    import jax
    from mxnet_tpu.serving import ServingEngine
    cell = cells.Cell(CELL, tiny=True)
    cfg = cell.config
    said = []
    run = types.SimpleNamespace(seed=11, cell=cell, say=said.append)
    net = serve_long.build(cfg, run.seed)
    eng = ServingEngine(net, **cfg["engine"])
    served = serve_long.serve_in_company

    def serve_then_spoil(eng, checks, first, behind):
        busy = served(eng, checks, first, behind)
        if spoil:
            spoil(checks)
        return busy

    monkeypatch.setattr(serve_long, "serve_in_company", serve_then_spoil)
    with jax.default_matmul_precision("highest"):
        ok = serve_long.check_reference(run, net, eng, ref,
                                        cfg["model"]["kwargs"], cfg["check"])
    return ok, said[-1]


def test_the_check_holds_the_engines_stream_to_the_paged_path(monkeypatch):
    """The engine serves the check prompts with every slot busy, in slots
    others have left, and its stream is the paged path's best token at
    every emitted position; ONE other token anywhere is not `correct`,
    whatever the logits' comparison says."""
    ok, line = _tiny_cell_served(monkeypatch)
    assert ok and line.endswith(": ok"), line
    assert "4 slots busy in 1.000" in line
    assert "best token at 12 of 12 positions" in line

    def one_other_token(checks):
        # the last token: no later position is conditioned on it
        stream = checks[1].output_tokens
        stream[-1] = (stream[-1] + 1) % 512

    ok, line = _tiny_cell_served(monkeypatch, one_other_token)
    assert not ok and line.endswith(": WRONG"), line
    assert "best token at 11 of 12 positions" in line


def test_the_configurations_draw_peaks_the_latent_softmax():
    """`draw` names the latent layers' three matrices by the end of their
    names; they are drawn again at their own spread, every other parameter
    as weights_per_parameter.py draws it, and the same seed draws the same."""
    import numpy as np
    assert set(CONFIG["draw"]) == {"mixer.query.weight",
                                   "mixer.kv_up.weight",
                                   "mixer.kv_down.weight"}
    assert "draw" in CONFIG["assumed"]
    cfg = cells.Cell(CELL, tiny=True).config
    net = serve_long.build(cfg, 5)
    again = serve_long.build(cfg, 5)
    params, drawn = net.collect_params(), 0
    for name, p in params.items():
        a = np.asarray(p.data()._data, np.float32)
        assert (a == np.asarray(again.collect_params()[name].data()._data,
                                np.float32)).all(), name
        for tail, std in CONFIG["draw"].items():
            if name.endswith(tail):
                drawn += 1
                assert name.startswith(("layer2.",)), name   # the L layer
                assert a.std() == pytest.approx(std, rel=0.1), name
        if name.endswith("out_proj.weight"):
            assert a.std() == pytest.approx(0.02, rel=0.1), name
    assert drawn == 3


# -- the reference's arithmetic -----------------------------------------------

def test_costs_count_the_layers_of_their_own_kind():
    one = ref.kda_cost(KW, [(5000, 1)])
    state = 2 * 32 * 128 * 128 * 4
    row = 32 * (4 * 128 * 2 + 4 * 128 + 4)
    assert one["bytes"] == 7 * (state + row)            # seven KDA layers
    assert one["flops"] == 7 * 2 * 32 * (3 * 128 * 128 + 4 * 128)
    got = ref.attention_cost(KW, [(100, 1)])            # two latent layers
    assert got["flops"] == 2 * 2 * 32 * (576 + 512) * 101
    # the stored row, padding counted; the absorbed query and the output
    assert got["bytes"] == 2 * (101 * 640 + 32 * (640 + 512)) * 2
    pairs = ref.expert_cost(KW, 1000, 10)
    assert pairs["flops"] == 6 * 2304 * 1024 * 1000
    assert pairs["bytes"] == (3 * 2304 * 1024 * 10 + 2 * 2304 * 1000) * 2
    per_token = ref.flops_per_item(KW, 100)
    assert per_token == 2 * ref._matmul_params(KW) \
        + 2 * 2 * 32 * (576 + 512) * 100 + 7 * 2 * 3 * 32 * 128 * 128
    # the held quarter of a token's 8 experts, three matrices each
    assert ref._matmul_params(KW) - ref._matmul_params(dict(KW, top_k=0)) \
        == 8 * 2 * 3 * 2304 * 1024


def test_the_perturbations_are_keywords_the_reference_takes():
    import inspect
    taken = set(inspect.signature(ref.logits).parameters) \
        | set(inspect.signature(ref.kda_layer).parameters) \
        | set(inspect.signature(ref.latent_layer).parameters) \
        | set(inspect.signature(ref.expert_layer).parameters)
    assert len(ref.PERTURBATIONS) == 9 and len(ref.CONTROLS) == 1
    for name, kw in {**ref.PERTURBATIONS, **ref.CONTROLS}.items():
        assert set(kw) <= taken, name
    src = open(os.path.join(ROOT, "benchmarks", "reference",
                            "kimi_linear.py")).read()
    assert "mxnet_tpu" not in src.split('"""', 2)[2]    # its own copy


def test_the_readings_run_the_cell_under_check():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.serve_long",
         "--workload", CELL, "--seed", "5", "--check"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])["5"]
    # float32 against float32: the system's paged path is the reference,
    # and every perturbation moves the logits of the 150-token sequence
    # (nine chunks of 16: state is carried)
    assert out["reference"]["logit_abs"] < 1e-4
    assert out["reference"]["beyond"] == []
    for name in {**ref.PERTURBATIONS, **ref.CONTROLS}:
        assert out[name]["logit_abs"] > 100 * out["reference"]["logit_abs"], \
            name
        assert out[name]["logit_rms"] > 100 * out["reference"]["logit_rms"], \
            name
    # the control moves the logits and no limit tells it
    assert out["state_router_and_norms_in_bfloat16"]["beyond"] == []


# -- the other served models trace what they traced before --------------------

def _gpt2():
    import mxnet_tpu as mx
    from mxnet_tpu import models
    net = models.GPT2ForCausalLM(models.GPT2Config(
        vocab_size=256, units=128, num_heads=2, num_layers=2, max_length=128,
        dtype="float32"))
    mx.rng.seed(0)
    net.initialize()
    return net


def _by_test_module(name):
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    return __import__(name)._model()[0]


# the counters of the parent commit (4048b0c), read there with this very
# traffic: the one-pool page write, the gated expert kernel and the latent
# span kernel join the program beside these calls, not in their place
TRACED_BEFORE = {
    "gpt2": (_gpt2, {"kv_page_write/pallas": 2,
                     "ragged_span_attention/pallas": 2},
             {"ragged_span_attention/pages=4,keys=64,rows=16": 2}),
    "nemotron_h": (lambda: _by_test_module("test_nemotron_h"),
                   {"ragged_span_attention/pallas": 2,
                    "ssd_chunk_update/pallas": 3, "expert_ffn/pallas": 3,
                    "kv_page_write/xla": 2},
                   {"expert_ffn/rows=96,hidden=128": 3,
                    "ragged_span_attention/pages=4,keys=64,rows=32": 2}),
    "falcon_h1": (lambda: _by_test_module("test_falcon_h1"),
                  {"ragged_span_attention/pallas": 2,
                   "ssd_chunk_update/pallas": 2, "kv_page_write/xla": 2},
                  {"ragged_span_attention/pages=4,keys=64,rows=32": 2}),
}


@pytest.mark.parametrize("family", sorted(TRACED_BEFORE))
def test_the_other_models_trace_the_calls_they_traced_before(family):
    import numpy as np
    from mxnet_tpu.serving import Request, ServingEngine
    make, paths, tiles = TRACED_BEFORE[family]
    eng = ServingEngine(make(), num_slots=2, max_length=64, page_size=16,
                        chunk_tokens=16, prefill_chunk_budget=32,
                        attn_impl="pallas_interpret")
    rng = np.random.default_rng(0)
    done = eng.serve([Request(rng.integers(0, 256, n), 4) for n in (20, 7)])
    assert all(r.status == "finished" for r in done)
    assert eng.stats["kernel_paths"] == paths
    assert eng.stats["kernel_tiles"] == tiles
    # two pools, as before: only a model that says `row_width` gets one
    assert "v" in eng._device_state()
    assert eng.stats["kv_pool_bytes"] == 2 * eng._kp.nbytes
