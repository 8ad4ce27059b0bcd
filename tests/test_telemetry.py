"""Unified telemetry subsystem tests (tier-1).

Covers the ISSUE-2 checklist: histogram bucket boundaries + percentile
math vs a numpy oracle, concurrent increments from threads, a Prometheus
exposition golden test, serving-engine metrics end-to-end (TTFT recorded
for every finished request in a mixed-length run), and the import +
snapshot round-trip with no device-trace side effects.
"""
import json
import math
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import (Histogram, Registry, exponential_buckets)


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

def test_exponential_bucket_boundaries():
    b = exponential_buckets(1e-4, 2.0, 8)
    assert len(b) == 8
    assert b[0] == pytest.approx(1e-4)
    for lo, hi in zip(b, b[1:]):
        assert hi == pytest.approx(2 * lo)
    with pytest.raises(MXNetError):
        exponential_buckets(0, 2.0, 4)
    with pytest.raises(MXNetError):
        exponential_buckets(1e-3, 1.0, 4)


def test_histogram_bucket_assignment_is_le():
    """Bounds are inclusive upper edges (prometheus `le` semantics):
    a value exactly on a bound lands in that bound's bucket."""
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"1": 2, "2": 2, "4": 1}
    assert snap["overflow"] == 1       # only 5.0
    assert snap["count"] == 6
    assert snap["min"] == 0.5 and snap["max"] == 5.0
    assert snap["sum"] == pytest.approx(14.0)


def test_histogram_percentiles_vs_numpy_oracle():
    """The interpolated estimate must stay within one exponential bucket
    (factor 2) of the exact sample percentile, across distributions."""
    rng = np.random.default_rng(7)
    for vals in (rng.lognormal(-4, 1.2, 4000),
                 rng.exponential(0.01, 4000),
                 np.full(100, 0.0123)):
        h = Histogram("h", buckets=exponential_buckets(1e-5, 2.0, 26))
        for v in vals:
            h.observe(v)
        for q in (50, 90, 99):
            oracle = float(np.percentile(vals, q))
            est = h.percentile(q)
            assert oracle / 2.05 <= est <= oracle * 2.05, (q, est, oracle)
    assert math.isnan(Histogram("h", buckets=(1.0,)).percentile(50))


def test_histogram_weighted_observe():
    h = Histogram("h", buckets=(1.0, 10.0))
    h.observe(0.5, count=10)
    assert h.count == 10
    assert h.sum == pytest.approx(5.0)
    assert h.percentile(99) <= 1.0


def test_histogram_merge_vs_numpy_oracle():
    """Bucket-wise merge (the fleet collector's combiner) must track
    the percentile of the POOLED samples, on a split where averaging
    per-worker percentiles is wildly wrong: worker A serves 99.5% of
    traffic at ~1 ms, worker B 0.5% at ~1 s. The pooled p99 is still
    ~1 ms (the slow worker owns under 1% of traffic), while
    mean-of-p99s lands at ~500 ms — 500x off — regardless of the
    traffic split."""
    rng = np.random.default_rng(11)
    fast = rng.lognormal(math.log(1e-3), 0.1, 995)
    slow = rng.lognormal(math.log(1.0), 0.1, 5)
    buckets = exponential_buckets(1e-5, 2.0, 26)
    ha = Histogram("h", buckets=buckets)
    hb = Histogram("h", buckets=buckets)
    for v in fast:
        ha.observe(v)
    for v in slow:
        hb.observe(v)
    merged = Histogram("h", buckets=buckets).merge(ha).merge(hb)
    pooled = np.concatenate([fast, slow])
    assert merged.count == 1000
    assert merged.count == ha.count + hb.count
    assert merged.sum == pytest.approx(float(pooled.sum()), rel=1e-9)
    for q in (50, 90, 99, 99.9):
        oracle = float(np.percentile(pooled, q))
        est = merged.percentile(q)
        assert oracle / 2.05 <= est <= oracle * 2.05, (q, est, oracle)
    # the strawman the merge exists to prevent: averaging worker p99s
    avg_p99 = (ha.percentile(99) + hb.percentile(99)) / 2
    oracle_p99 = float(np.percentile(pooled, 99))
    assert not (oracle_p99 / 2.05 <= avg_p99 <= oracle_p99 * 2.05)
    # mismatched bucket layouts must refuse, never silently mangle
    with pytest.raises(MXNetError):
        merged.merge(Histogram("h", buckets=(1.0, 2.0)))


def test_histogram_from_cumulative_roundtrip():
    """Exposition-format reconstruction (finite `le` bounds + trailing
    +Inf cumulative count) must reproduce per-bucket counts exactly and
    percentiles to one bucket's resolution — the path every scraped
    worker histogram takes through the fleet collector."""
    h = Histogram("h", buckets=(1e-3, 1e-2, 1e-1, 1.0))
    rng = np.random.default_rng(3)
    vals = rng.exponential(0.02, 500)
    for v in vals:
        h.observe(v)
    cum, acc = [], 0
    for c in h._counts:
        acc += c
        cum.append(acc)
    back = Histogram.from_cumulative(h.buckets, cum, h.sum, h.count,
                                     name="h")
    assert back._counts == h._counts
    assert back.count == h.count and back.sum == pytest.approx(h.sum)
    for q in (50, 90, 99):
        assert back.percentile(q) == pytest.approx(h.percentile(q),
                                                   rel=1.0)
    with pytest.raises(MXNetError):
        Histogram.from_cumulative((1.0, 2.0), [3, 2, 5], 1.0, 5)


def test_concurrent_increments_from_threads():
    reg = Registry()
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h_seconds", buckets=(1e-3, 1e-2, 1e-1))
    N, T = 10_000, 8

    def work():
        for i in range(N):
            c.inc()
            g.inc()
            h.observe(1e-3 * (1 + i % 3))

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert g.value == N * T
    assert h.count == N * T


def test_registry_get_or_create_and_kind_conflict():
    reg = Registry()
    a = reg.counter("x_total", "first")
    assert reg.counter("x_total") is a
    with pytest.raises(MXNetError):
        reg.gauge("x_total")
    with pytest.raises(MXNetError):
        reg.counter("x_total", labelnames=("k",))
    with pytest.raises(MXNetError):
        a.inc(-1)


def test_labeled_children_and_reset_in_place():
    reg = Registry()
    c = reg.counter("req_total", labelnames=("engine",))
    child = c.labels("0")
    child.inc(5)
    assert c.labels("0") is child          # interned
    assert c.labels(engine="0") is child   # kw form
    other = c.labels("1")
    other.inc(2)
    reg.reset()
    assert child.value == 0                # zeroed IN PLACE, same object
    child.inc()
    assert c.labels("0").value == 1 and other.value == 0


def test_prometheus_exposition_golden():
    reg = Registry()
    reg.counter("requests_total", "served requests").inc(3)
    reg.gauge("occupancy", labelnames=("engine",)).labels("0").set(2)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    want = "\n".join([
        '# HELP requests_total served requests',
        '# TYPE requests_total counter',
        'requests_total 3',
        '# TYPE occupancy gauge',
        'occupancy{engine="0"} 2',
        '# TYPE lat_seconds histogram',
        'lat_seconds_bucket{le="0.1"} 1',
        'lat_seconds_bucket{le="1"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        'lat_seconds_sum 5.55',
        'lat_seconds_count 3',
    ]) + "\n"
    got = reg.render_prometheus()
    # registries render sorted by name
    assert got == "\n".join([
        '# TYPE lat_seconds histogram',
        'lat_seconds_bucket{le="0.1"} 1',
        'lat_seconds_bucket{le="1"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        'lat_seconds_sum 5.55',
        'lat_seconds_count 3',
        '# TYPE occupancy gauge',
        'occupancy{engine="0"} 2',
        '# HELP requests_total served requests',
        '# TYPE requests_total counter',
        'requests_total 3',
    ]) + "\n", f"unexpected exposition:\n{got}\nwanted shape:\n{want}"


def test_gauge_callback_sampled_at_read():
    reg = Registry()
    g = reg.gauge("probe")
    box = {"v": 1.0}
    g.set_function(lambda: box["v"])
    assert g.value == 1.0
    box["v"] = 7.5
    assert reg.snapshot()["probe"]["value"] == 7.5


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl(tmp_path):
    telemetry.clear_events()
    path = telemetry.enable_jsonl(str(tmp_path / "spans.jsonl"))
    try:
        with telemetry.span("outer", phase="test"):
            with telemetry.span("inner"):
                pass
    finally:
        telemetry.disable_jsonl()
    evs = [e for e in telemetry.events()
           if e["name"] in ("outer", "inner")][-2:]
    inner, outer = evs
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert outer["dur"] >= inner["dur"] >= 0
    lines = [json.loads(l) for l in open(path)]
    assert [l["name"] for l in lines] == ["inner", "outer"]
    assert lines[1]["phase"] == "test"
    # durations accrue into the labeled span histogram
    hist = telemetry.get("span_duration_seconds")
    assert hist.labels("inner").count >= 1


def test_span_no_device_trace_side_effects():
    """Spans must not construct jax TraceAnnotations (or start traces)
    unless the mx.profiler device trace is running."""
    with telemetry.span("plain") as s:
        assert s._ann is None
    prof = sys.modules.get("mxnet_tpu.profiler")
    assert prof is None or prof._state["jax_trace"] is False


# ---------------------------------------------------------------------------
# snapshot round-trip (tier-1 acceptance: importable + serializable on CPU)
# ---------------------------------------------------------------------------

def test_snapshot_dump_roundtrip(tmp_path):
    import mxnet_tpu.telemetry  # noqa: F401  (import side of the check)

    telemetry.counter("roundtrip_total").inc(2)
    snap = telemetry.snapshot()
    assert snap["roundtrip_total"]["value"] >= 2
    path = telemetry.dump(str(tmp_path / "tel.json"))
    loaded = json.load(open(path))
    assert loaded["instruments"]["roundtrip_total"]["value"] \
        == snap["roundtrip_total"]["value"]
    # the whole snapshot must be JSON-clean (no inf/nan leaks)
    json.dumps(snap, allow_nan=False)
    text = telemetry.render_prometheus()
    assert "roundtrip_total 2" in text.replace(".0", "").replace(" 2 ", " 2 ") \
        or "roundtrip_total" in text


def test_jit_cache_stats_is_telemetry_backed():
    mx.runtime.reset_jit_cache_stats()
    from mxnet_tpu.gluon.block import LRUTraceCache

    cache = LRUTraceCache(2)
    for i in range(4):
        cache[i] = i
    stats = mx.runtime.jit_cache_stats()
    assert stats["retraces"] == 4 and stats["evictions"] == 2
    assert telemetry.get("jit_cache_retraces_total").value == 4
    mx.runtime.reset_jit_cache_stats()
    assert mx.runtime.jit_cache_stats() == {"retraces": 0, "evictions": 0}


def test_trainer_step_metrics():
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import Trainer, loss as gloss, nn

    before = telemetry.get("trainer_steps_total")
    before = before.value if before else 0
    net = nn.Dense(3, flatten=False, in_units=5)
    net.initialize(mx.init.Normal(0.1))
    trainer = Trainer(net.collect_params(), opt.SGD(learning_rate=0.05))
    lfn = gloss.L2Loss()
    x = mx.nd.array(np.ones((2, 5), np.float32))
    y = mx.nd.array(np.zeros((2, 3), np.float32))
    for _ in range(2):
        with mx.autograd.record():
            loss = lfn(net(x), y)
        loss.backward()
        trainer.step(batch_size=2)
    assert telemetry.get("trainer_steps_total").value == before + 2
    assert telemetry.get("trainer_step_seconds").count >= 2


# ---------------------------------------------------------------------------
# serving engine end-to-end
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    from mxnet_tpu.models import GPT2Config, GPT2ForCausalLM
    from mxnet_tpu.serving import ServingEngine

    cfg = GPT2Config(vocab_size=97, units=32, num_layers=2, num_heads=2,
                     max_length=64, dropout=0.0, attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(3)
    net.initialize(mx.init.Normal(0.05))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("attn_impl", "xla")
    return ServingEngine(net, **kw), cfg


def test_serving_engine_metrics_end_to_end():
    """Mixed-length run with slot recycling: TTFT and admission wait are
    recorded once per finished request, token latency covers every
    decoded token, and the dict stats view matches."""
    from mxnet_tpu.serving import Request

    eng, cfg = _tiny_engine()
    rng = np.random.default_rng(5)
    lens = (3, 9, 17, 5, 12)
    reqs = [Request(rng.integers(0, cfg.vocab_size, n).tolist(),
                    int(rng.integers(2, 7)), seed=i)
            for i, n in enumerate(lens)]
    done = eng.serve(reqs)
    assert len(done) == len(reqs)

    m = eng._metrics
    assert m["ttft"].count == len(reqs)
    assert m["admission_wait"].count == len(reqs)
    total_tokens = sum(len(r.output_tokens) for r in reqs)
    # prefill emits 1 token/request outside the decode-latency histogram
    assert m["token_latency"].count == total_tokens - len(reqs)
    assert m["ttft"].percentile(50) > 0

    s = eng.stats
    assert s["requests_finished"] == len(reqs)
    assert s["tokens_emitted"] == total_tokens
    assert s["prefills"] == len(reqs)
    assert s["requests_rejected"] == 0
    assert s["queue_depth"] == 0 and s["slot_occupancy"] == 0
    # unified dispatch: one model forward per dispatch, whatever mix
    # of chunk/decode rows it carried
    assert s["decode_steps"] == s["decode_dispatches"]
    assert s["prefill_chunks"] >= len(reqs)
    assert s["prefill_pending"] == 0           # everything drained

    # engine-local reset leaves identity intact and zeroes counts
    eng.reset_stats()
    assert eng.stats["requests_finished"] == 0
    assert eng._metrics["ttft"].count == 0


def test_serving_rejections_are_counted():
    from mxnet_tpu.serving import QueueFullError, Request

    eng, cfg = _tiny_engine(max_queue=1)
    long_prompt = list(range(1, 40))       # > max_length=32
    with pytest.raises(MXNetError):
        eng.submit(Request(long_prompt, 2))
    assert eng.stats["requests_rejected"] == 1
    eng.submit(Request([1, 2, 3], 2))
    with pytest.raises(QueueFullError):
        eng.submit(Request([4, 5, 6], 2))
    assert eng.stats["requests_rejected"] == 2
    assert eng.stats["queue_depth"] == 1
    done = eng.serve()
    assert len(done) == 1                  # the queued request completes


def test_two_engines_report_separately():
    from mxnet_tpu.serving import Request

    eng_a, cfg = _tiny_engine()
    eng_b, _ = _tiny_engine()
    eng_a.serve([Request([1, 2, 3], 2)])
    assert eng_a.stats["requests_finished"] == 1
    assert eng_b.stats["requests_finished"] == 0
    # the registry view aggregates both engines as labeled children
    inst = telemetry.get("serving_requests_finished_total")
    eids = {c["engine"] for c in inst.snapshot()["children"]}
    assert eng_a._eid in eids and eng_b._eid in eids


# ---------------------------------------------------------------------------
# profiler satellites (ISSUE 2): lazy annotations, counters out of the
# per-op time table
# ---------------------------------------------------------------------------

def test_profiler_scope_skips_annotation_when_inactive():
    assert mx.profiler.state() == "stop"
    with mx.profiler.scope("idle_region") as s:
        assert s._ann is None      # no TraceAnnotation constructed
    mx.profiler.set_state("run")
    try:
        with mx.profiler.scope("live_region") as s:
            assert s._ann is not None
    finally:
        mx.profiler.set_state("stop")


def test_profiler_counter_routed_to_own_section():
    mx.profiler.set_state("run")
    try:
        c = mx.profiler.Counter("queue_depth")
        c.set_value(5)
        c.increment(2)
        with mx.profiler.scope("some_region"):
            mx.nd.array([1.0]).sum().asscalar()
    finally:
        mx.profiler.set_state("stop")
    parsed = json.loads(mx.profiler.dumps(format="json"))
    # counters live under _counters, never as 0-duration time rows
    assert "counter::queue_depth" not in \
        [k for k in parsed if k != "_counters"]
    assert parsed["_counters"]["counter::queue_depth"] == 7
    table = mx.profiler.dumps()
    assert "Counters:" in table and "counter::queue_depth" in table


def test_telemetry_reachable_as_mx_attribute():
    assert mx.telemetry is telemetry
    assert callable(mx.telemetry.snapshot)


# ---------------------------------------------------------------------------
# memory watermarks
# ---------------------------------------------------------------------------

def test_memory_sampling_live_arrays():
    keep = mx.nd.array(np.ones((64, 64), np.float32))
    out = telemetry.memory.sample()
    assert out["live_array_count"] >= 1
    assert out["live_array_bytes"] >= keep._data.nbytes
    assert out["live_array_bytes_peak"] >= out["live_array_bytes"]
    assert telemetry.get("memory_live_array_bytes").value \
        == out["live_array_bytes"]
