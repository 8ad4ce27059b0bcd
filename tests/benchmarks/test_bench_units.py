"""The benchmark's own arithmetic, on inputs made by hand: generators as
functions of the seed, TTFT from the due time, gap pooling, the percentile
rule, the trace reduction, the contract of BENCHMARK.json, and each plain
reference against its model at a tiny size."""
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import cells, stats  # noqa: E402
from benchmarks.generators import request_stream, resident_batch  # noqa: E402
from benchmarks.trace import reduce as tr  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CHAT = {"arrivals": {"process": "poisson", "rate_rps": 2.0},
        "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                       "min": 16, "max": 256},
        "output_len": {"dist": "uniform", "min": 4, "max": 32},
        "sampling": {"do_sample": True, "top_k": 40}}



# -- generators ---------------------------------------------------------------

def test_request_stream_is_a_function_of_the_seed_alone():
    a = request_stream.generate(CHAT, 1000, seed=3, seconds=30.0)
    b = request_stream.generate(CHAT, 1000, seed=3, seconds=30.0)
    c = request_stream.generate(CHAT, 1000, seed=4, seconds=30.0)
    assert len(a) == 60 == len(c)          # round(rate * seconds), exactly
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and (x["prompt"] == y["prompt"]).all()
        assert x["max_new_tokens"] == y["max_new_tokens"]
    # another seed: the same work in another order, at other instants
    assert [x["due"] for x in a] != [x["due"] for x in c]
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_new_tokens"] for r in a) \
        == sorted(r["max_new_tokens"] for r in c)
    dues = [x["due"] for x in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
    assert all(16 <= len(r["prompt"]) <= 256 and 4 <= r["max_new_tokens"] <= 32
               and 0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in a)


def test_request_stream_backlog_is_all_due_at_once():
    reqs = request_stream.generate(
        dict(CHAT, arrivals={"process": "backlog", "count": 25}), 1000, 0,
        30.0)
    assert len(reqs) == 25 and not any(r["due"] for r in reqs)
    assert [r["seed"] for r in reqs] == list(range(25))
    assert all(r["sampling"] == CHAT["sampling"] for r in reqs)


@pytest.mark.parametrize("change, what", [
    ({"arrivals": {"process": "gamma", "rate_rps": 2.0}}, "arrival process"),
    ({"prompt_len": {"dist": "fixed", "value": 8}}, "length distribution")])
def test_request_stream_refuses_parameters_it_does_not_know(change, what):
    with pytest.raises(ValueError, match=what):
        request_stream.generate(dict(CHAT, **change), 1000, 0, 30.0)


def test_request_stream_lengths_are_the_same_quantiles_in_every_block():
    """The amount of work is fixed: every block of 32 consecutive requests
    holds one value from each 32nd of the distribution."""
    reqs = request_stream.generate(
        dict(CHAT, arrivals={"process": "backlog", "count": 320}), 1000, 7,
        30.0)
    plens = np.array([len(r["prompt"]) for r in reqs])
    edges = np.quantile(plens, np.arange(1, 32) / 32)
    for block in plens.reshape(10, 32):
        ranks = np.searchsorted(edges, np.sort(block), side="left")
        assert (np.abs(ranks - np.arange(32)) <= 1).all()


def test_resident_batch_is_a_function_of_the_seed_alone():
    p = {"seq_len": 32, "masked": 5}
    a, b = (resident_batch.generate(p, 512, 1, 4) for _ in range(2))
    c = resident_batch.generate(p, 512, 2, 4)
    assert all((x == y).all() for x, y in zip(a, b))
    assert (a[0] != c[0]).any()
    ids, tt, vl, pos, labels = a
    assert ids.shape == (4, 32) and pos.shape == labels.shape == (4, 5)
    assert (vl == 32).all() and not tt.any()
    assert (np.diff(pos, axis=1) > 0).all() and pos.max() < 32
    assert all(x.dtype == np.int32 for x in a)


# -- timestamps to metrics ----------------------------------------------------

@pytest.mark.parametrize("q, want", [(50, 3), (90, 5), (99, 5), (20, 1),
                                     (21, 2)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_of_nothing_and_the_count_beyond():
    assert stats.percentile([], 90) is None
    assert stats.beyond(list(range(100)), 90) == 10
    assert stats.beyond(list(range(60)), 90) == 6


def test_ttft_counts_from_due_and_the_unserved_count_as_the_worst():
    reqs = [
        {"due": 1.0, "first": 1.5},      # submitted late or not: from due
        {"due": 2.0, "first": None},     # no token when the window ended
        {"due": 3.0, "first": 3.2, "failed": True},
        {"due": 4.0, "first": 10.5},     # first token after the window
    ]
    got = stats.ttft_ms(reqs, window_s=10.0)
    assert got == pytest.approx([500.0, 10000.0, 10000.0, 10000.0])


def test_gaps_are_pooled_over_requests_and_stop_at_the_window():
    reqs = [{"tokens": [1.0, 1.1, 1.3]}, {"tokens": [2.0]},
            {"tokens": [9.0, 9.5, 10.5]}]
    assert stats.gaps_ms(reqs, 10.0) == pytest.approx([100.0, 200.0, 500.0])


# -- the trace reduction ------------------------------------------------------

def _space(planes):
    """A ProfileData from {plane: {line: [(start_us, dur_us, name)]}}."""
    from jax.profiler import ProfileData
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for _, _, n in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = "".join(
            f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 ' + "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(a * 1e6)} "
                f"duration_ps: {int(d * 1e6)} }} " for a, d, n in evs) + "} "
            for lid, (line, evs) in enumerate(lines.items(), 1))
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        out.append(f'planes {{ id: {pid} name: "{plane}" {body}{meta}}}')
    return ProfileData.from_text_proto("\n".join(out))


def test_reduce_on_a_trace_made_by_hand():
    # one chip, a window of 1000 us. A while holds everything of the first
    # step; an all-reduce is under way for 300 us, 250 of them under compute.
    ops = [(100, 500, "while.1"),
           (100, 200, "fusion.1"), (300, 10, "all-reduce-start.3"),
           (310, 100, "custom-call.2"), (410, 150, "fusion.4"),
           (560, 40, "all-reduce-done.3"), (800, 100, "fusion.1")]
    host = [(0, 1000, "bench.traced"), (50, 600, "bench.step"),
            (60, 500, "serving.dispatch"), (650, 100, "bench.sleep"),
            (760, 200, "bench.step"), (10, 5, "PjitFunction(f)")]
    red = tr.reduce(_space({
        "/device:TPU:0": {"XLA Ops": ops, "Steps": [(0, 1000, "0")]},
        "/host:CPU": {"python3": host}}))
    us = 1e-6
    assert red["window_s"] == pytest.approx(1000 * us)
    assert red["busy_s"] == pytest.approx(600 * us)        # 100..600, 800..900
    assert red["by_kind"]["fusion"] == pytest.approx(450 * us)
    assert red["by_kind"]["custom-call"] == pytest.approx(100 * us)
    assert red["by_kind"]["all-reduce-done"] == pytest.approx(40 * us)
    assert "while" not in red["by_kind"]
    assert red["collective_s"] == pytest.approx(300 * us)
    assert red["collective_exposed_s"] == pytest.approx(50 * us)
    # gaps: 0..100 starts with no span open but the window's, 600..800
    # starts inside bench.step (it ends at 650), 900..1000 inside the
    # second bench.step
    assert red["idle_gaps"] == pytest.approx(
        {"bench.traced": 100 * us, "bench.step": 300 * us})
    assert red["spans"]["serving.dispatch"] == 1
    assert "PjitFunction(f)" not in red["spans"]


def test_reduce_averages_chips_and_knows_a_cpu_trace():
    two = tr.reduce(_space({
        "/device:TPU:0": {"XLA Ops": [(0, 100, "fusion.1")]},
        "/device:TPU:1": {"XLA Ops": [(0, 50, "fusion.1"),
                                      (50, 50, "all-reduce.1")]},
        "/host:CPU": {"python3": [(0, 200, "bench.traced")]}}))
    assert two["chips"] == 2 and two["busy_s"] == pytest.approx(100e-6)
    assert two["collective_s"] == two["collective_exposed_s"] \
        == pytest.approx(25e-6)
    assert tr.reduce(_space({"/host:CPU": {"t": [(0, 10, "bench.x")]}})) \
        is None
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.reduce(_space({"/device:TPU:0": {"Steps": [(0, 10, "0")]}}))


def test_reduce_on_a_trace_recorded_on_the_chip():
    """data/tiny_tpu.xplane.pb: two calls of a jitted scan of matmuls plus
    the fused_attention kernel, on a TPU v5e, under the benchmark's spans
    (PR 22). It pins what reduce.py expects of a real trace: the planes'
    and lines' names, instructions named by their whole text, the Mosaic
    kernel found by its call target, one clock for host and device."""
    red = tr.reduce(tr.load(os.path.join(os.path.dirname(__file__), "data",
                                         "tiny_tpu.xplane.pb")))
    assert red["chips"] == 1
    assert dict(red["spans"]) == {
        "bench.traced": 1, "bench.chain.dispatch": 2, "bench.chain.fetch": 2,
        "bench.sleep": 2}
    assert 0.008 < red["window_s"] < 0.009
    assert 0 < red["busy_s"] < 1e-4 < red["window_s"]
    assert red["by_kind"][tr.MOSAIC] == pytest.approx(1.437e-6, rel=1e-3)
    assert red["by_op"]["work mosaic bf16[2,128,128]"] \
        == red["by_kind"][tr.MOSAIC]
    assert {"fusion", "copy", "copy-done", "dynamic-update-slice"} \
        <= set(red["by_kind"]) and "while" not in red["by_kind"]
    # the chip idles while the host sleeps and while it starts the trace
    assert set(red["idle_gaps"]) <= {"bench.sleep", "bench.traced",
                                     "bench.chain.dispatch",
                                     "bench.chain.fetch"}
    assert sum(red["idle_gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


@pytest.mark.parametrize("text, want", [
    ("%copy.964 = bf16[36,256,64,20,64]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[36,256,64,20,64]{1,4,3,2,0:T(8,128)(2,1)} %vp.1)",
     ("copy copy bf16[36,256,64,20,64]", "copy")),
    ("%sort = (f32[16,50257]{1,0:T(8,128)}, s32[16,50257]{1,0:T(8,128)S(1)})"
     " sort(f32[16,50257]{1,0} %a, s32[16,50257]{1,0} %b), dimensions={1}",
     ("sort sort (f32[16,50257], ...)", "sort")),
    ('%unified.36 = bf16[16,64,1280]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
     's32[16,16]{1,0} %copy-done.3), custom_call_target="tpu_custom_call", '
     'frontend_attributes={kernel_metadata={}}',
     ("unified mosaic bf16[16,64,1280]", "mosaic")),
    ('%custom-call.15 = bf16[1024,1280]{1,0} custom-call(bf16[256,1280]{1,0}'
     ' %slice-done), custom_call_target="ConcatBitcast"',
     ("custom-call ConcatBitcast bf16[1024,1280]", "ConcatBitcast")),
    ("%all-reduce-start.2 = (f32[768]{0}, f32[768]{0}) all-reduce-start("
     "f32[768]{0} %g), replica_groups={{0,1,2,3}}",
     ("all-reduce-start all-reduce-start (f32[768], ...)",
      "all-reduce-start")),
    ("fusion.12", ("fusion.12", "fusion"))])
def test_parse_names_an_instruction_by_its_text(text, want):
    assert tr.parse(text) == want
    assert tr.is_collective(text) == want[1].startswith("all-reduce")


def test_self_times_do_not_count_a_nested_event_twice():
    got = tr.self_times([(0.0, 10.0, "outer"), (1.0, 4.0, "inner"),
                         (5.0, 6.0, "inner"), (12.0, 13.0, "alone")])
    assert got == {"outer": 6.0, "inner": 4.0, "alone": 1.0}
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]


def test_rows_of_steps_rebuilds_what_each_dispatch_attended():
    from benchmarks.runners.serve import rows_of_steps
    steps = [(i, i + 0.9, 0, 1, 0, 0) for i in range(6)]
    # 100 prompt tokens at width 64: chunks in steps 1 and 2, first token
    # with the second, then two decode ticks
    ok = {"admit": 1.1, "prompt_len": 100, "first": 2.8,
          "tokens": [2.8, 3.8, 4.8]}
    rows = rows_of_steps([ok], steps, [1, 2, 3, 4, 5], 64)
    assert rows == [[(0, 64)], [(64, 36)], [(100, 1)], [(101, 1)], []]
    # a first token a step later than the rule says: the rule does not hold
    assert rows_of_steps([dict(ok, first=3.8)], steps, [1], 64) is None


# -- readers and the result line, on a run made by hand ------------------------

class _Chip:
    """What the harness asks of a device."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"bytes_in_use": 2_000_000_000,
                "peak_bytes_in_use": 3_000_000_000}


def _run_made_by_hand(workload):
    """A harness Run of the real cell, filled as a runner on the chip fills
    it: a reduced trace, the runner's facts, set-up phases and compile
    counts. No device is touched."""
    import collections
    from benchmarks import harness
    from benchmarks.reference import gpt2
    cell = cells.Cell(workload)
    run = harness.Run(cell, 1, 51.0, 1, [_Chip()], t_start=0.0)
    run.say = lambda text: None
    run.tracer.reduction = {
        "window_s": 1.0, "chips": 1, "busy_s": 0.9,
        "by_kind": {tr.MOSAIC: 0.2, "fusion": 0.6, "copy": 0.1},
        "by_op": {"unified mosaic bf16[16,64,1280]": 0.2, "f fusion x": 0.7},
        "idle_gaps": {"bench.step": 0.1},
        "spans": collections.Counter({"serving.dispatch": 4})}
    run.phases = {"weights": 1.0, "reference": 2.0, "warmup": 3.0,
                  "import": 4.0}
    run.setup_s = 10.0
    run.setup_meter = {"compile_s": 5.0, "trace_lower_s": 6.0,
                       "compiles": 7, "cache_hits": 7}
    run.window_meter = dict.fromkeys(run.setup_meter, 0)
    run.held_in_window = [2_000_000_000]
    run.result.update(correct=True, attempted=10, failed=0)
    run.facts["program_temp_bytes"] = 5_000_000_000
    if cell.config["runner"] == "train":
        run.facts.update(
            kind="train", chips=1, items_per_step=32 * 512,
            steps_per_chain=10, step_s=0.09, traced_chains=[2, 3, 4],
            flops_per_item=6e8, attention_cost={"flops": 1e12, "bytes": 1e9})
        run.end_to_end["train_items_per_s_per_chip"] = 32 * 512 / 0.09
    else:
        # four steps; one request of 100 prompt tokens admitted in step 1
        steps = [(i, i + 0.9, 3, 1, 0, 100 + i) for i in range(5)]
        run.facts.update(
            kind="serve", chips=1, steps=steps, traced_steps=[1, 2, 3, 4],
            timelines=[{"admit": 1.1, "prompt_len": 100, "first": 2.8,
                        "tokens": [2.8, 3.8, 4.8]}],
            dispatches=[{"name": "serving.dispatch", "dur": 0.1 + 0.01 * i}
                        for i in range(5)],
            engine_stats={"decode_dispatches": 5, "prefill_tokens": 100,
                          "tokens_emitted": 3},
            slots=16, width=64, total_pages=256, page_size=64,
            model_kwargs=cell.config["model"]["kwargs"],
            attention_cost=gpt2.attention_cost)
        run.end_to_end["serve_tokens_per_s"] = 3400.0
    return run


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_listed_metric_of_a_cell_reaches_the_result_line(workload):
    """With what a chip run records, every per-layer metric BENCHMARK.json
    lists for the cell is in the `--trace 1` line and every end-to-end
    metric in the `--trace 0` line: equal sets, not subsets."""
    from benchmarks import report
    run = _run_made_by_hand(workload)
    line = report.result_line(run)
    assert set(line["metrics"]) == {m["name"] for m in run.cell.per_layer}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["device"]["busy_s"] == 0.9 and line["device"]["window_s"] == 1
    assert line["breakdown"]["device_ops"][0] == ["f fusion x", 0.7]
    run.trace = False
    line = report.result_line(run)
    assert set(line["metrics"]) == {m["name"] for m in run.cell.end_to_end}
    assert line["metrics"]["setup_s"] == {"value": 10.0, "unit": "s"}
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_a_reader_is_handed_what_follows_the_dot_of_its_metric():
    from benchmarks import report
    run = _run_made_by_hand("gpt2_774m.doc_backlog")
    got = report.per_layer(run)
    assert {k.partition(".")[2]: v["value"] for k, v in got.items()
            if k.startswith("setup_phase_s.")} == run.phases
    assert got["dispatch_ms_p50.backlog"]["value"] == pytest.approx(120.0)
    assert got["step_device_ms.backlog"]["value"] == pytest.approx(225.0)
    assert got["attn_call_ms.backlog"]["value"] == pytest.approx(50.0)
    assert got["useful_row_share"]["value"] == pytest.approx(
        100 * 103 / (5 * 16 * 64))
    assert got["kv_pool_used_share_peak"]["value"] == 100.0
    assert got["kv_page_fill_share.backlog"]["value"] == pytest.approx(
        100 * 102 / (256 * 64))
    assert got["device_idle_share.backlog"]["value"] == pytest.approx(10.0)
    train = report.per_layer(_run_made_by_hand("bert_base_mlm.phase2_t512"))
    assert train["step_device_ms.train"]["value"] == pytest.approx(30.0)
    assert train["train_mfu"]["value"] == pytest.approx(
        100 * 32 * 512 / 0.09 * 6e8 / 197e12)
    # the floor of 1e12 FLOP a step at 197 TFLOP/s, over 0.2 s / 30 steps
    assert train["fused_attention_roofline"]["value"] == pytest.approx(
        100 * (1e12 / 197e12) * 30 / 0.2)


def test_on_the_chip_a_listed_metric_with_nothing_to_read_is_an_error():
    from benchmarks import report
    run = _run_made_by_hand("gpt2_774m.doc_backlog")
    del run.facts["engine_stats"]
    with pytest.raises(RuntimeError, match="useful_row_share"):
        report.per_layer(run)
    # under --check it is left out, as every metric a CPU cannot give is
    got = report.per_layer(run, check=True)
    assert "useful_row_share" not in got and "compiles_in_window" in got
    assert all(m["source"] == "program_counter" for m in run.cell.per_layer
               if m["name"] in got)


def test_memory_is_what_was_held_in_the_window_plus_the_programs_own():
    """`memory_peak_bytes`: the arrays held when the window closed plus the
    temporaries of the window's program, alive together. The allocator's
    own peak over the process, set-up included, is a per-layer metric of
    its own and is not added to anything."""
    from benchmarks import report
    run = _run_made_by_hand("bert_base_mlm.phase2_t512")
    assert run.device()["memory_peak_bytes"] == 7_000_000_000
    got = report.per_layer(run)
    hbm = cells.peaks("TPU v5 lite")["hbm_bytes"]
    assert hbm == 16 * 2 ** 30
    assert got["peak_hbm_share.train"]["value"] == pytest.approx(
        100 * 3e9 / hbm)
    assert got["program_temp_hbm_share.train"]["value"] == pytest.approx(
        100 * 5e9 / hbm)
    run.held_in_window = [0]            # a backend without statistics
    assert run.device()["memory_peak_bytes"] == 0


# -- the contract -------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_meets_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    cfgs = {c["name"] for c in BENCH["configs"]}
    cells_ = BENCH["workloads"]
    assert {w["config"] for w in cells_} == cfgs
    assert len({(w["config"], w["traffic"]) for w in cells_}) == len(cells_)
    assert sum(w["chips"] == 4 for w in cells_) <= max(1, len(cells_) // 4)
    assert all(len(x["why"]) <= 200 for x in cells_ + BENCH["configs"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    assert all(0.01 <= m["bound"] <= 0.1 and m["source"] in
               ("host_clock", "device_trace") for m in e2e.values())
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    workload_names = {w["name"] for w in cells_}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= workload_names


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(workload):
    cell = cells.Cell(workload)
    cell.module("runners", cell.config["runner"])
    cell.module("reference", cell.config["reference"])
    cell.module("generators", cell.traffic["generator"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert hasattr(cell.module("layer_metrics", m["name"].split(".")[0]),
                       "read")
    tiny = cells.Cell(workload, tiny=True)
    assert tiny.config["model"]["kwargs"]["units"] \
        < cell.config["model"]["kwargs"]["units"]


def test_an_unknown_cell_device_or_reader_is_an_error():
    with pytest.raises(cells.CellError, match="no workload"):
        cells.Cell("no_such.cell")
    with pytest.raises(cells.CellError, match="no peaks"):
        cells.peaks("TPU v9 imaginary")
    assert cells.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(cells.CellError, match="layer_metrics/nothing.py"):
        cells.Cell(BENCH["workloads"][0]["name"]).module("layer_metrics",
                                                         "nothing")


def test_importing_the_benchmark_starts_no_backend():
    """Nothing under benchmarks/ touches a device (or libtpu) when it is
    imported: only a run does."""
    mods = sorted(
        os.path.relpath(p, ROOT)[:-3].replace(os.sep, ".")
        for p in glob.glob(os.path.join(ROOT, "benchmarks", "**", "*.py"),
                           recursive=True)
        if not p.endswith(("__init__.py", os.sep + "run.py")))
    code = ("import importlib, sys\n"
            f"for m in {mods!r}: importlib.import_module(m)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "assert not any('libtpu' in m for m in sys.modules), 'libtpu'\n")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))


# -- each reference against its model, tiny, on the CPU -----------------------

def _tiny_net(workload, seed=0):
    from mxnet_tpu import models
    from benchmarks.weights import seed_weights
    cell = cells.Cell(workload, tiny=True)
    kwargs = cell.config["model"]["kwargs"]
    net = getattr(models, cell.config["model"]["class"])(
        getattr(models, cell.config["model"]["config_fn"])(**kwargs))
    seed_weights(net, seed, kwargs["dtype"])
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    return cell, kwargs, net, params


def test_seeded_weights_are_made_from_the_seed_and_leave_nothing_at_zero():
    _, _, _, a = _tiny_net("bert_base_mlm.phase2_t512", seed=1)
    _, _, _, b = _tiny_net("bert_base_mlm.phase2_t512", seed=1)
    _, _, _, c = _tiny_net("bert_base_mlm.phase2_t512", seed=2)
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    assert any((np.asarray(a[k]) != np.asarray(c[k])).any() for k in a)
    for name, value in a.items():
        v = np.asarray(value)
        assert v.std() > 0, name            # no bias or scale left constant
        want = 1.0 if name.endswith("gamma") else 0.0
        assert abs(v.mean() - want) < 0.02, name


def test_bert_reference_agrees_with_the_model():
    from mxnet_tpu import parallel as par
    from benchmarks.reference import bert as ref
    cell, kwargs, net, params = _tiny_net("bert_base_mlm.phase2_t512")
    batch = resident_batch.generate(cell.traffic, kwargs["vocab_size"], 5, 3)
    ids, tt, vl, pos, labels = batch
    vl = np.array([32, 20, 7], np.int32)    # the padding mask is compared too
    got = par.EvalStep(net)(ids, tt, vl, pos).asnumpy()
    want = np.asarray(ref.masked_logits(params, kwargs, ids, tt, vl, pos))
    assert got.shape == want.shape == (3, 5, kwargs["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    assert float(ref.mlm_loss(want, labels)) == pytest.approx(
        np.log(kwargs["vocab_size"]), abs=0.5)
    # 6 * matmul weights, attention, and the head on the masked positions
    k = dict(units=2, hidden_size=3, num_layers=1, vocab_size=5)
    assert ref.flops_per_item(k, {"seq_len": 4, "masked": 2}) \
        == 6 * (16 + 12) + 12 * 2 * 4 + 0.5 * 6 * (4 + 10)


def test_gpt2_reference_agrees_with_the_model():
    from mxnet_tpu import parallel as par
    from benchmarks.reference import gpt2 as ref
    _, kwargs, net, params = _tiny_net("gpt2_774m.doc_backlog")
    ids = np.random.default_rng(0).integers(
        0, kwargs["vocab_size"], (2, 24)).astype(np.int32)
    got = par.EvalStep(net)(ids).asnumpy()
    want = np.asarray(ref.logits(params, kwargs, ids))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # causal: a later token changes no earlier position's logits
    ids2 = ids.copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % kwargs["vocab_size"]
    np.testing.assert_array_equal(
        np.asarray(ref.logits(params, kwargs, ids2))[:, :-1], want[:, :-1])
    k = dict(units=2, num_layers=1, vocab_size=5, dtype="float32")
    assert ref.flops_per_item(k, 10) == 2 * (16 + 32 + 10) + 4 * 2 * 10
    # a chunk of 2 rows on 3 keys attends 4 + 5 keys; a decode row on 9, 10
    assert ref.attention_cost(k, [(3, 2), (9, 1)]) == {
        "flops": 4 * 2 * (9 + 10), "bytes": (2 * 5 + 4 + 2 * 10 + 2) * 2 * 4}
