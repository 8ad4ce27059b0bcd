"""Benchmarks for the two primary BASELINE.json metrics.

Default (what the driver runs): BOTH primary metrics, one JSON line each —
GluonCV-parity ResNet-50 v1b training img/sec/chip first, then BERT-base
MLM pretraining MFU last (the driver tail-parses the LAST line, so the
north-star metric stays there; vs_baseline is MFU / 0.35, the ≥35% v5e-64
north star). ResNet MFU comes from XLA's own per-program flop count
(compiled.cost_analysis()), not a hand napkin estimate.

`python bench.py --workload bert|resnet50` (or BENCH_WORKLOAD=...) runs a
single workload.
"""
import json
import os
import sys
import time
import traceback

import numpy as np


def peak_flops(device):
    """Per-chip bf16 peak FLOP/s from the one peak table
    (mxnet_tpu.telemetry.cost, sources there): None for a CPU — a host
    run yields no MFU — and an error for an accelerator kind the table
    does not know. BENCH_PEAK_FLOPS=<float> overrides the table."""
    env = os.environ.get("BENCH_PEAK_FLOPS")
    if env:
        return float(env)
    from mxnet_tpu.telemetry import cost
    return cost.device_peaks(device)[0]


def _mfu(achieved_flops, device):
    peak = peak_flops(device)
    return round(achieved_flops / peak, 4) if peak else None


def _is_oom(exc):
    """The one failure the batch-size loops retry at a smaller batch."""
    return "RESOURCE_EXHAUSTED" in str(exc)


def _emit(metric, value, unit, vs_baseline, extras=None, error=None):
    rec = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": vs_baseline}
    if extras:
        rec["extras"] = extras
    if error:
        rec["error"] = error
    print(json.dumps(rec))


def _device_cost_extras(eid=None):
    """Device-cost block for a serving round's extras: per-program MFU,
    roofline side, and compile attribution (telemetry.cost.report()),
    so BENCH_*.json rounds carry the device-cost trajectory
    tools/bench_compare.py consumes."""
    from mxnet_tpu import telemetry
    rep = telemetry.cost.report()
    progs = {}
    for p, s in rep["programs"].items():
        if eid is not None and not p.startswith(f"engine{eid}/"):
            continue
        if not s["dispatches"] and not s["compiles"]:
            continue
        progs[p] = {
            "flops": s["flops"],
            "mfu": round(s["mfu"], 6) if s.get("mfu") is not None
            else None,
            "bound": s.get("bound"),
            "compiles": s["compiles"],
            "compile_seconds": round(s["compile_seconds"], 3),
            "dispatches": s["dispatches"],
        }
    return {"device_kind": rep["device_kind"],
            "peak_flops": rep["peak_flops"],
            "peak_bandwidth_bytes_per_sec":
                rep["peak_bandwidth_bytes_per_sec"],
            "programs": progs}


def _engine_compiles(eid):
    """Total compiles attributed to one engine's programs."""
    from mxnet_tpu import telemetry
    rep = telemetry.cost.report()["programs"]
    return sum(s["compiles"] for p, s in rep.items()
               if p.startswith(f"engine{eid}/"))


def bench_bert(large=False):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import (BertForMaskedLM, bert_base_config,
                                  bert_large_config)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    seq_len = int(os.environ.get("BENCH_SEQ_LEN", 512))
    n_masked = int(os.environ.get("BENCH_MASKED", 76))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    mk_cfg = bert_large_config if large else bert_base_config
    cfg = mk_cfg(dtype="bfloat16" if on_tpu else "float32",
                 dropout=0.1, max_length=seq_len)
    if not on_tpu:  # CPU smoke config so the bench always completes
        cfg.num_layers = 2
        cfg.units, cfg.hidden_size, cfg.num_heads = 128, 512, 2
        seq_len = min(seq_len, 128)
        n_masked = 20
        steps = 3

    default_batches = "16,8,4" if large else "32,16,8"
    candidates = [int(b) for b in (os.environ.get("BENCH_BATCH")
                                   or default_batches).split(",")]
    rng = np.random.default_rng(0)
    lfn = gloss.SoftmaxCrossEntropyLoss()

    last_err = None
    for batch in candidates:
        try:
            net = BertForMaskedLM(cfg)
            net.initialize(mx.init.Normal(0.02))
            if on_tpu:
                net.cast("bfloat16")
            o = opt.AdamW(learning_rate=1e-4, wd=0.01)
            step = par.TrainStep(net, lfn, o, mesh=None, n_net_inputs=4)

            ids = mx.nd.array(
                rng.integers(0, cfg.vocab_size, (batch, seq_len)),
                dtype="int32")
            tt = mx.nd.array(np.zeros((batch, seq_len)), dtype="int32")
            vl = mx.nd.array(np.full((batch,), seq_len), dtype="int32")
            # per-row masked positions without replacement (argsort trick)
            perm = np.argsort(rng.random((batch, seq_len)), axis=-1)
            pos = mx.nd.array(np.sort(perm[:, :n_masked], axis=-1),
                              dtype="int32")
            labels = mx.nd.array(
                rng.integers(0, cfg.vocab_size, (batch, n_masked)),
                dtype="int32")

            # warmup (compile), then the timed section runs the K steps
            # device-chained (TrainStep.run_steps — the engine-bulk
            # analog): one dispatch, K optimizer steps, one fetch of the
            # losses (which waits for the device), so the per-step figure
            # is the device's sustained training rate.
            batch_args = (ids, tt, vl, pos, labels)
            float(step.run_steps(*batch_args, steps=steps)
                  .asnumpy()[-1])
            t0 = time.perf_counter()
            losses = step.run_steps(*batch_args, steps=steps)
            float(losses.asnumpy()[-1])
            dt = (time.perf_counter() - t0) / steps
            break
        except Exception as e:  # out of memory → try a smaller batch
            if not _is_oom(e):
                raise
            last_err = e
    else:
        _emit("bert_large_mlm_mfu" if large else "bert_base_mlm_mfu",
              0.0, "fraction", 0.0, error=str(last_err)[:200])
        return 1

    n_params = cfg.num_params()
    tokens_per_step = batch * seq_len
    # PaLM-appendix step FLOPs: 6*N per token + attention 12*L*C*T per token
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.units * seq_len
    step_flops = flops_per_token * tokens_per_step
    achieved = step_flops / dt
    mfu = _mfu(achieved, dev)
    tokens_per_sec = tokens_per_step / dt
    metric = "bert_large_mlm_mfu" if large else "bert_base_mlm_mfu"
    _emit(metric, mfu, "fraction",
          round(mfu / 0.35, 4) if mfu is not None else None, extras={
              "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
              "step_time_ms": round(dt * 1e3, 2),
              "batch": batch, "seq_len": seq_len,
              "params": n_params,
              "device": str(dev.device_kind),
              "achieved_tflops": round(achieved / 1e12, 2),
          })
    return 0


def bench_resnet50():
    """ResNet-50 v1b training throughput (BASELINE.json primary metric #2:
    'GluonCV ResNet-50 img/sec/chip'). vs_baseline compares against the
    ~1.4k img/sec/GPU fp16 V100 figure recorded in BASELINE.md (an
    order-of-magnitude recollection — the only reference-side number that
    exists for this workload)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models.vision import resnet50_v1b

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    steps = int(os.environ.get("BENCH_STEPS", 30))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", 224))
    classes = 1000
    candidates = [int(b) for b in (os.environ.get("BENCH_BATCH")
                                   or "256,128,64").split(",")]
    if not on_tpu:  # CPU smoke config
        candidates, steps, image_size, classes = [8], 2, 64, 100

    rng = np.random.default_rng(0)
    lfn = gloss.SoftmaxCrossEntropyLoss()
    last_err = None
    for batch in candidates:
        try:
            net = resnet50_v1b(classes=classes)
            net.initialize(mx.init.Xavier())
            if on_tpu:
                net.cast("bfloat16")
            x = mx.nd.array(
                rng.standard_normal((batch, 3, image_size, image_size)),
                dtype="bfloat16" if on_tpu else "float32")
            y = mx.nd.array(rng.integers(0, classes, (batch,)),
                            dtype="int32")
            net(x[:1])  # finish deferred shape inference before TrainStep
            o = opt.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4)
            step = par.TrainStep(net, lfn, o, mesh=None, n_net_inputs=1)
            # timed section device-chains the K steps (engine-bulk
            # analog); the single-step call also compiles the per-call
            # program whose XLA cost analysis provides the MFU flop count
            float(step(x, y).asscalar())
            float(step.run_steps(x, y, steps=steps).asnumpy()[-1])
            t0 = time.perf_counter()
            losses = step.run_steps(x, y, steps=steps)
            float(losses.asnumpy()[-1])
            dt = (time.perf_counter() - t0) / steps
            break
        except Exception as e:  # out of memory → try a smaller batch
            if not _is_oom(e):
                raise
            last_err = e
    else:
        _emit("resnet50_v1b_img_per_sec_per_chip", 0.0, "img/sec", 0.0,
              error=str(last_err)[:200])
        return 1

    img_per_sec = batch / dt
    # MFU from XLA's own flop count for the compiled step program — no
    # napkin math. Falls back to 3x the canonical 3.8 GFLOPs fwd estimate
    # (He et al. 2015, table 1) when cost analysis is unavailable.
    step_flops, flops_source = None, "analytic"
    try:
        # cost of the SINGLE-step program (the last-called program is the
        # K-chained one, whose flop count is K x one step)
        single_sig = tuple((tuple(d.shape), str(d.dtype))
                           for d in (x._data, y._data))
        cost = step.compiled_cost_analysis(sig=single_sig)
        if cost and cost.get("flops"):
            step_flops = float(cost["flops"])
            flops_source = "xla_cost_analysis"
    except Exception:
        pass
    if step_flops is None:
        step_flops = 3 * 3.8e9 * batch * (image_size / 224) ** 2
    achieved = step_flops / dt
    _emit("resnet50_v1b_img_per_sec_per_chip", round(img_per_sec, 1),
          "img/sec", round(img_per_sec / 1400.0, 4), extras={
              "mfu": _mfu(achieved, dev),
              "step_time_ms": round(dt * 1e3, 2),
              "batch": batch, "image_size": image_size,
              "device": str(dev.device_kind),
              "achieved_tflops": round(achieved / 1e12, 2),
              "flops_source": flops_source,
          })
    return 0


def bench_gpt2_decode():
    """GPT-2 774M autoregressive decode tokens/sec (BASELINE.json target
    workload 'GluonNLP GPT-2 774M'; SURVEY.md §3.5). Runs the static
    paged-KV-cache while_loop decode — one compiled program for the whole
    generation. No reference-side number exists (BASELINE.md row is
    TBD-verify), so vs_baseline is 0.0 with the context in extras."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    batch = int(os.environ.get("BENCH_DECODE_BATCH", 8))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", 128))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", 128))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 256
        batch, prompt_len, new_tokens = 2, 16, 16

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)
    ids = mx.nd.array(rng.integers(0, cfg.vocab_size, (batch, prompt_len)),
                      dtype="int32")
    out = net.generate(ids, new_tokens, paged=True, page_size=64)
    np.asarray(out.asnumpy())  # fetch = sync (compile + warmup)
    t0 = time.perf_counter()
    out = net.generate(ids, new_tokens, paged=True, page_size=64)
    out.asnumpy()
    dt = time.perf_counter() - t0
    toks = batch * new_tokens / dt
    _emit("gpt2_774m_decode_tokens_per_sec", round(toks, 1), "tokens/sec",
          0.0, extras={
              "batch": batch, "prompt_len": prompt_len,
              "new_tokens": new_tokens, "params": cfg.num_params(),
              "ms_per_token": round(dt / new_tokens * 1e3, 2),
              "device": str(dev.device_kind), "kv_cache": "paged(64)",
              "baseline": "none recorded (BASELINE.md GPT-2 row TBD)",
          })
    return 0


def bench_gpt2_serving():
    """GPT-2 continuous-batching serving throughput (serving/engine.py —
    the ragged paged-attention decode path). Poisson request arrivals
    with mixed prompt/output lengths; reports sustained tokens/sec plus
    p50/p99 per-token latency (first-token latency counts from
    submission; later tokens from the previous token, both at
    decode-block resolution). No reference-side number exists (the
    reference has no serving engine at all), so vs_baseline is 0.0."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    from mxnet_tpu import telemetry

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 8))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)

    def mk_requests(n, id0=0):
        out = []
        for i in range(n):
            plen = int(rng.integers(p_lo, p_hi + 1))
            out.append(Request(
                rng.integers(0, cfg.vocab_size, plen).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                        page_size=page, decode_block=block)
    # warmup: compile both unified-dispatch variants (prompt length no
    # longer selects a program — the greedy wave compiles one, the
    # all-sampled wave the other; the mix uses both, and a
    # steady-state compile now counts as churn)
    warm = [Request(list(range(1, b + 1)), 2, request_id=f"w{b}")
            for b in range(page, max(p_hi + page, page + 1), page)]
    eng.serve(warm)
    eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                       seed=0, request_id="w-sampled")])
    # steady state: every program is compiled; a compile inside the
    # measured loop from here on is a retrace storm
    eng.mark_warm()
    compiles_at_warm = _engine_compiles(eng._eid)
    # telemetry reflects the MEASURED run only, not the warmup compiles
    eng.reset_stats()
    telemetry.clear_events()

    reqs = mk_requests(n_requests, id0=1000)
    gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
        else np.zeros(n_requests)
    arrivals = np.cumsum(gaps)
    t0 = time.perf_counter()
    pending = list(zip(arrivals, reqs))
    while pending or eng.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending.pop(0)[1])
        if eng.has_work:
            eng.step()
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.01))
    dt = time.perf_counter() - t0

    unfinished = {r.id: r.status for r in reqs if r.status != "finished"}
    if unfinished:
        raise RuntimeError(
            f"{len(unfinished)} of {n_requests} requests did not finish: "
            f"{unfinished}")
    total_tokens = sum(len(r.output_tokens) for r in reqs)
    # per-token latency = each request's (finish - submit) / tokens; the
    # p50/p99 spread across requests captures queueing + slot contention
    tpot = np.asarray([(r.t_finish - r.t_submit)
                       / max(len(r.output_tokens), 1) for r in reqs])
    ttft = np.asarray([r.token_times[0] - r.t_submit for r in reqs])
    toks_per_sec = total_tokens / dt

    # the engine's own telemetry rides in the round's extras: queue
    # wait, TTFT, and per-token latency percentiles measured IN-PROCESS
    # (the request-derived tpot/ttft numbers below cross-check them)
    def _pcts(name):
        hist = telemetry.get(name).labels(eng._eid)
        if hist.count == 0:
            return None
        return {"p50_ms": round(hist.percentile(50) * 1e3, 2),
                "p99_ms": round(hist.percentile(99) * 1e3, 2),
                "count": hist.count}

    telemetry.memory.sample()
    mem = telemetry.get("memory_live_array_bytes_peak")
    tele_extras = {
        "queue_wait": _pcts("serving_admission_wait_seconds"),
        "ttft": _pcts("serving_ttft_seconds"),
        "token_latency": _pcts("serving_token_latency_seconds"),
        "decode_dispatch": _pcts("serving_decode_dispatch_seconds"),
        "stats": eng.stats,
        "live_array_bytes_peak": int(mem.value) if mem else None,
    }
    dc = _device_cost_extras(eng._eid)
    dc["steady_state_compiles"] = _engine_compiles(eng._eid) \
        - compiles_at_warm
    _emit("gpt2_serving_tokens_per_sec", round(toks_per_sec, 1),
          "tokens/sec", 0.0, extras={
              "telemetry": tele_extras,
              "device_cost": dc,
              "requests": n_requests, "slots": slots,
              "decode_block": block, "total_tokens": total_tokens,
              "makespan_s": round(dt, 3),
              "p50_token_latency_ms": round(
                  float(np.percentile(tpot, 50)) * 1e3, 2),
              "p99_token_latency_ms": round(
                  float(np.percentile(tpot, 99)) * 1e3, 2),
              "p50_first_token_ms": round(
                  float(np.percentile(ttft, 50)) * 1e3, 2),
              "prompt_lens": f"U[{p_lo},{p_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": "open-loop" if rate == 0
                          else f"poisson({rate}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "kv_cache": f"ragged paged({page})",
              "baseline": "none (reference has no serving path)",
          })
    return 0


def bench_gpt2_serving_prefix_reuse():
    """Shared-prefix serving: the SAME Poisson workload served twice —
    prefix cache off, then on — where 80% of prompts extend one long
    system prefix (the dominant production shape: system prompts,
    few-shot templates, multi-turn history). Reports cache-on sustained
    tokens/sec plus the prefilled-token reduction (the acceptance bar is
    >= 50% fewer prompt tokens computed) and the engine's prefix-cache
    telemetry (hits/misses/tokens-saved/pages-shared). No reference
    number exists (the reference has no serving path), so vs_baseline
    is the prefill-reduction fraction instead of a speed ratio."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 10))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    prefix_len, t_lo, t_hi, o_lo, o_hi = 512, 16, 64, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        prefix_len, t_lo, t_hi, o_lo, o_hi = 40, 1, 8, 4, 8
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, prefix_len).tolist()

    def mk_requests(id0=0):
        out = []
        for i in range(n_requests):
            if rng.random() < 0.8:       # the shared-prefix population
                tail = rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(t_lo, t_hi + 1)))
                prompt = system + tail.tolist()
            else:                        # cold prompts keep the miss path
                plen = int(rng.integers(prefix_len // 2, prefix_len))
                prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
            out.append(Request(
                prompt, int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    def run(prefix_cache):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, decode_block=block,
                            prefix_cache=prefix_cache)
        # warmup, off the clock: decode + every prefill bucket the
        # arrival mix can hit (cold prompts AND, under the cache, the
        # suffix/CoW buckets a shared-prefix hit compiles). DISTINCT
        # random prompts per bucket — nested-range prompts would prefix-
        # match each other under the cache and collapse into one small
        # suffix bucket, leaving the big buckets cold
        wrng = np.random.default_rng(99)
        hi = prefix_len + t_hi
        warm = [Request(wrng.integers(0, cfg.vocab_size, b).tolist(), 2,
                        request_id=f"w{b}")
                for b in range(page, min(hi + page, max_len) + 1, page)]
        warm += [Request(system, 2, request_id="ws0"),
                 Request(system, 2, request_id="ws1")]   # CoW bucket
        eng.serve(warm)
        eng.reset_stats()
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
        reqs = mk_requests(id0=2000 if prefix_cache else 1000)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0
        total_tokens = sum(len(r.output_tokens) for r in reqs)
        return eng.stats, total_tokens / dt, reqs

    # identical request streams: reseed the generator per run
    rng = np.random.default_rng(7)
    stats_off, tps_off, reqs_off = run(prefix_cache=False)
    rng = np.random.default_rng(7)
    stats_on, tps_on, reqs_on = run(prefix_cache=True)
    # correctness ride-along: same seeds/prompts => same tokens
    mismatch = sum(
        a.output_tokens != b.output_tokens
        for a, b in zip(reqs_off, reqs_on))
    reduction = 1.0 - stats_on["prefill_tokens"] / max(
        stats_off["prefill_tokens"], 1)
    hits = stats_on["prefix_hits"]
    hit_rate = hits / max(hits + stats_on["prefix_misses"], 1)
    _emit("gpt2_serving_prefix_reuse_tokens_per_sec", round(tps_on, 1),
          "tokens/sec", round(reduction, 4), extras={
              "prefill_tokens_cache_off": stats_off["prefill_tokens"],
              "prefill_tokens_cache_on": stats_on["prefill_tokens"],
              "prefill_token_reduction": round(reduction, 4),
              "tokens_per_sec_cache_off": round(tps_off, 1),
              "speedup": round(tps_on / max(tps_off, 1e-9), 3),
              "prefix_hit_rate": round(hit_rate, 4),
              "prefix_tokens_saved": stats_on["prefix_tokens_saved"],
              "prefix_pages_shared_final": stats_on["prefix_pages_shared"],
              "prefix_cache_pages_final": stats_on["prefix_cache_pages"],
              "output_mismatches": mismatch,
              "requests": n_requests, "slots": slots,
              "decode_block": block, "shared_prefix_len": prefix_len,
              "tail_lens": f"U[{t_lo},{t_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": "open-loop" if rate == 0
                          else f"poisson({rate}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "kv_cache": f"ragged paged({page}) + radix prefix cache",
              "baseline": "cache-off run above (reference has no "
                          "serving path)",
          })
    return 0 if mismatch == 0 and reduction >= 0.5 else 1


def bench_gpt2_serving_speculative():
    """Speculative decoding: the SAME Poisson request stream served
    twice — speculation off, then on — over a repetitive-suffix
    workload (the production shape prompt-lookup pays off on: code,
    templated JSON, multi-turn history, quoted retrieval context).
    Prompts carry a unique random head plus a repeated pattern tail,
    and the tiny random model's greedy continuations fall into cycles,
    so the n-gram drafter keeps finding matches. Reports spec-on
    sustained tokens/sec, the acceptance rate, and the greedy-mismatch
    count (the acceptance bar is ZERO — greedy spec-on output is
    bit-identical by construction). vs_baseline is the spec-on/spec-off
    speedup."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    spec_tokens = int(os.environ.get("BENCH_SPEC_TOKENS", 8))
    # greedy stream by default: the repetitive-suffix workload IS the
    # greedy/low-temperature shape (code completion, templated JSON),
    # and greedy is where bit-identity is checkable; sampled slots
    # accept less (acceptance = target mass of the draft) — set
    # BENCH_SPEC_SAMPLED to measure that trade-off
    sampled_frac = float(os.environ.get("BENCH_SPEC_SAMPLED", 0))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 24))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    h_lo, h_hi, pat_len, o_lo, o_hi = 8, 32, 8, 96, 256
    if not on_tpu:  # CPU smoke config — deep enough that the forward
        # (not the tiny-vocab verification) carries the dispatch cost,
        # the same balance as the real model
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 128, 512
        cfg.num_layers, cfg.num_heads, cfg.max_length = 4, 4, 256
        max_len, page = 256, 8
        h_lo, h_hi, pat_len, o_lo, o_hi = 2, 6, 4, 96, 192
        slots, block = min(slots, 4), min(block, 8)
        spec_tokens = min(spec_tokens, 8)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)

    def mk_requests(id0=0):
        out = []
        for i in range(n_requests):
            head = rng.integers(0, cfg.vocab_size,
                                int(rng.integers(h_lo, h_hi + 1)))
            pat = rng.integers(0, cfg.vocab_size, pat_len)
            reps = int(rng.integers(2, 5))
            prompt = head.tolist() + pat.tolist() * reps
            out.append(Request(
                prompt, int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(rng.random() < sampled_frac),
                temperature=0.8, top_k=40, seed=i, request_id=id0 + i))
        return out

    def run(speculative):
        kw = dict(speculative=True, spec_tokens=spec_tokens) \
            if speculative else dict(decode_block=block)
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, **kw)
        # warmup, off the clock: decode/verification program + every
        # prefill bucket the arrival mix can hit
        wrng = np.random.default_rng(99)
        hi = h_hi + pat_len * 4
        warm = [Request(wrng.integers(0, cfg.vocab_size, b).tolist(), 2,
                        request_id=f"w{b}")
                for b in range(page, min(hi + page, max_len) + 1, page)]
        eng.serve(warm)
        eng.reset_stats()
        reqs = mk_requests(id0=2000 if speculative else 1000)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0
        total_tokens = sum(len(r.output_tokens) for r in reqs)
        return eng.stats, total_tokens / dt, reqs, eng._eid

    # identical request streams: reseed the generator per run
    rng = np.random.default_rng(7)
    stats_off, tps_off, reqs_off, _ = run(speculative=False)
    rng = np.random.default_rng(7)
    stats_on, tps_on, reqs_on, eid_on = run(speculative=True)
    # correctness ride-along: greedy requests must match bit for bit
    # (sampled ones are distribution-preserving, not bit-identical)
    mismatch = sum(
        a.output_tokens != b.output_tokens
        for a, b in zip(reqs_off, reqs_on) if not a.do_sample)
    drafted = stats_on["spec_draft_tokens"]
    accepted = stats_on["spec_accepted_tokens"]
    acc_rate = accepted / max(drafted, 1)
    speedup = tps_on / max(tps_off, 1e-9)
    _emit("gpt2_serving_speculative_tokens_per_sec", round(tps_on, 1),
          "tokens/sec", round(speedup, 4), extras={
              "tokens_per_sec_spec_off": round(tps_off, 1),
              "speedup": round(speedup, 3),
              "acceptance_rate": round(acc_rate, 4),
              "spec_draft_tokens": drafted,
              "spec_accepted_tokens": accepted,
              "spec_rollbacks": stats_on["spec_rollbacks"],
              "tokens_per_dispatch_on": round(
                  stats_on["tokens_emitted"]
                  / max(stats_on["decode_dispatches"], 1), 2),
              "tokens_per_dispatch_off": round(
                  stats_off["tokens_emitted"]
                  / max(stats_off["decode_dispatches"], 1), 2),
              "greedy_mismatches": mismatch,
              "device_cost": _device_cost_extras(eid_on),
              "requests": n_requests, "slots": slots,
              "spec_tokens": spec_tokens, "decode_block_off": block,
              "head_lens": f"U[{h_lo},{h_hi}]",
              "pattern": f"{pat_len} tokens x U[2,4] reps",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": "open-loop" if rate == 0
                          else f"poisson({rate}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "kv_cache": f"ragged paged({page})",
              "baseline": "spec-off run above (reference has no "
                          "serving path)",
          })
    return 0 if mismatch == 0 and acc_rate > 0 else 1


def bench_gpt2_serving_introspection():
    """Live-observability overhead: the SAME Poisson request stream
    served under three configs, interleaved over BENCH_AB_REPS
    repetitions (medians) — tracing+cost-accounting off / on (the
    always-on in-path cost the <2% A/B budget bounds: lifecycle
    tracing, live server, AND the per-dispatch device-cost accounting
    with MFU/bandwidth gauges live on /metrics, PERF_NOTES rounds
    10-11) / on+scrape-load (Prometheus-cadence /metrics+/statusz+
    /requests plus /trace every 2 s — displaced-work
    cost, host-core-bound). Also emits the traced run as Chrome
    trace_event JSON (BENCH_TRACE_OUT, default trace.json) — the file
    loads directly in ui.perfetto.dev. vs_baseline is the on/off
    throughput ratio (1.0 = free)."""
    import threading
    import urllib.request

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 16))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    trace_out = os.environ.get("BENCH_TRACE_OUT", "trace.json")
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 8, 24
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)

    def mk_requests(id0=0):
        out = []
        for i in range(n_requests):
            plen = int(rng.integers(p_lo, p_hi + 1))
            out.append(Request(
                rng.integers(0, cfg.vocab_size, plen).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    reps = int(os.environ.get("BENCH_AB_REPS", 3))
    n_trace_events = [0]
    device_cost = [None]

    def run(tracing, scrape_load, id0):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, decode_block=block)
        warm = [Request(list(range(1, b + 1)), 2, request_id=f"w{b}")
                for b in range(page, max(p_hi + page, page + 1), page)]
        eng.serve(warm)
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id="w-sampled")])
        eng.mark_warm()
        eng.reset_stats()
        telemetry.reset()
        telemetry.request_log.enabled = tracing
        # the cost accounting's in-path work (note_dispatch + goodput
        # counters) rides the same on/off switch, so the A/B bounds the
        # WHOLE always-on observability tax
        telemetry.cost.set_enabled(tracing)
        srv, scrapers, stop = None, [], threading.Event()
        if tracing:
            srv = telemetry.serve(0)
        if scrape_load:
            def scrape(path, interval):
                while not stop.is_set():
                    try:
                        urllib.request.urlopen(
                            srv.url + path, timeout=5).read()
                    except Exception:
                        pass
                    stop.wait(interval)
            # the realistic scrape mix: cheap endpoints at an
            # aggressive prometheus cadence, the full perfetto export
            # at the on-demand cadence of a human with a trace UI open
            for path, interval in (("/metrics", 0.05),
                                   ("/statusz", 0.05),
                                   ("/requests?n=20", 0.05),
                                   ("/trace?last_ms=2000", 2.0)):
                t = threading.Thread(target=scrape,
                                     args=(path, interval), daemon=True)
                t.start()
                scrapers.append(t)
        reqs = mk_requests(id0=id0)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0
        total_tokens = sum(len(r.output_tokens) for r in reqs)
        stop.set()
        for t in scrapers:
            t.join(timeout=2)
        if tracing:
            trace = telemetry.chrome_trace()
            n_trace_events[0] = len(trace["traceEvents"])
            with open(trace_out, "w") as f:
                json.dump(trace, f)
            device_cost[0] = _device_cost_extras(eng._eid)
            telemetry.stop_server()
        telemetry.request_log.enabled = True
        telemetry.cost.set_enabled(True)
        return total_tokens / dt, reqs

    # Three configs, A/B'd over `reps` interleaved repetitions with the
    # IDENTICAL request stream (median kills the run-to-run noise that
    # dominates a single pair on a busy box):
    #   off    — tracing disabled, no server (the baseline)
    #   on     — lifecycle tracing + live server, nobody scraping:
    #            the ALWAYS-ON in-path cost the <2% budget bounds
    #   scrape — on + the scrape mix: displaced-work cost, which is
    #            host-core-bound (≈0 when cores are idle; worst-case
    #            1:1 displacement on a single-core host)
    configs = [("off", (False, False)), ("on", (True, False)),
               ("scrape", (True, True))]
    tps = {"off": [], "on": [], "scrape": []}
    reqs_by = {}
    for rep in range(reps):
        # rotate the within-rep order so monotonic machine drift
        # (cache/arena growth, thermal) doesn't bias one config
        order = configs[rep % 3:] + configs[:rep % 3]
        for mode, (tracing, load) in order:
            rng = np.random.default_rng(7)    # identical streams
            t, reqs_by[mode] = run(tracing, load,
                                   id0={"off": 1000, "on": 2000,
                                        "scrape": 3000}[mode])
            tps[mode].append(t)
    med = {k: float(np.median(v)) for k, v in tps.items()}
    mismatch = sum(
        a.output_tokens != b.output_tokens
        for mode in ("on", "scrape")
        for a, b in zip(reqs_by["off"], reqs_by[mode]))
    ratio = med["on"] / max(med["off"], 1e-9)
    _emit("gpt2_serving_introspection_tokens_per_sec",
          round(med["on"], 1), "tokens/sec", round(ratio, 4), extras={
              "tokens_per_sec_tracing_off": round(med["off"], 1),
              "tokens_per_sec_scraped": round(med["scrape"], 1),
              "overhead_fraction": round(1.0 - ratio, 4),
              "scrape_displacement_fraction": round(
                  1.0 - med["scrape"] / max(med["off"], 1e-9), 4),
              "reps": reps,
              "tokens_per_sec_all": {k: [round(x, 1) for x in v]
                                     for k, v in tps.items()},
              "trace_json": trace_out,
              "trace_events": n_trace_events[0],
              "device_cost": device_cost[0],
              "scrapes": {"/metrics": "50ms", "/statusz": "50ms",
                          "/requests?n=20": "50ms",
                          "/trace?last_ms=2000": "2s"},
              "output_mismatches": mismatch,
              "requests": n_requests, "slots": slots,
              "decode_block": block,
              "prompt_lens": f"U[{p_lo},{p_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": "open-loop" if rate == 0
                          else f"poisson({rate}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "budget": "<2% overhead (PERF_NOTES A/B criterion)",
          })
    return 0 if mismatch == 0 else 1


def bench_gpt2_serving_overload():
    """Overload hardening: the SAME Poisson request stream at ~2x the
    measured closed-loop capacity, served twice — shedding policy OFF
    (deadlines still enforced) and ON. Goodput counts requests that
    FINISH within their deadline, per second of makespan. OFF admits
    doomed work and wastes slot time on requests the deadline cancels
    mid-decode; ON sheds below-floor traffic at submit while the queue
    is past its watermarks (plus deadline-infeasible requests), so the
    survivors' goodput and TTFT p99 improve — that strict improvement
    is the bench's pass criterion, together with the policy's in-path
    cost staying under the 2% A/B budget (interleaved reps at feasible
    load with inert watermarks, so only the per-submit/per-step
    assessment arithmetic is on the clock). vs_baseline is
    goodput_on / goodput_off."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import (RejectedError, Request, ServingEngine,
                                   SheddingPolicy)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    64 if on_tpu else 48))
    overload = float(os.environ.get("BENCH_OVERLOAD_FACTOR", 2.0))
    reps = int(os.environ.get("BENCH_AB_REPS", 3))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    def mk_requests(n, id0, deadline_ms=None):
        # reseeded per call -> every run sees the identical stream;
        # every 4th request is protected interactive traffic (class 0),
        # the rest are sheddable default traffic (class 1)
        rng = np.random.default_rng(23)
        out = []
        for i in range(n):
            plen = int(rng.integers(p_lo, p_hi + 1))
            out.append(Request(
                rng.integers(0, cfg.vocab_size, plen).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i,
                priority=0 if i % 4 == 0 else 1,
                deadline_ms=deadline_ms))
        return out

    def new_engine(policy=None):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, decode_block=block,
                            policy=policy)
        warm = [Request(list(range(1, b + 1)), 2, request_id=f"w{b}")
                for b in range(page, max(p_hi + page, page + 1), page)]
        eng.serve(warm)
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id="w-s")])
        eng.reset_stats()
        return eng

    # phase 1: closed-loop capacity + service time (no deadlines)
    eng = new_engine()
    cap_reqs = mk_requests(n_requests, id0=1000)
    t0 = time.perf_counter()
    eng.serve(cap_reqs)
    capacity_rps = n_requests / (time.perf_counter() - t0)
    service_s = float(np.median([r.t_finish - r.t_admit
                                 for r in cap_reqs]))
    # a deadline a request meets comfortably at capacity (3x median
    # service), hopeless once the overloaded queue builds
    deadline_ms = max(3e3 * service_s, 50.0)
    rate = overload * capacity_rps

    def run(policy, id0):
        eng = new_engine(policy=policy)
        reqs = mk_requests(n_requests, id0=id0, deadline_ms=deadline_ms)
        arr = np.cumsum(np.random.default_rng(29).exponential(
            1.0 / rate, n_requests))
        rejected = 0
        t0 = time.perf_counter()
        pending = list(zip(arr, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                try:
                    eng.submit(pending.pop(0)[1])
                except RejectedError:
                    rejected += 1
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0
        good = [r for r in reqs if r.status == "finished"
                and (r.t_finish - r.t_submit) * 1e3 <= deadline_ms]
        ttft = telemetry.get("serving_ttft_seconds").labels(eng._eid)
        s = eng.stats
        return {
            "goodput_req_per_sec": round(len(good) / dt, 3),
            "finished_in_deadline": len(good),
            "finished_total": sum(r.status == "finished" for r in reqs),
            "rejected_at_submit": rejected,
            "expired_in_queue": sum(r.status == "shed" for r in reqs)
            - rejected,
            "deadline_cancelled": sum(r.status == "deadline"
                                      for r in reqs),
            "wasted_tokens": sum(len(r.output_tokens) for r in reqs
                                 if r.status == "deadline"),
            "shed_total": s["shed"],
            "degraded_now": s["degraded"],
            "ttft_p99_ms": round(ttft.percentile(99) * 1e3, 2)
            if ttft.count else None,
            "makespan_s": round(dt, 3),
        }

    # phase 2: the overloaded stream, shedding off vs on
    off = run(None, id0=2000)
    on = run(SheddingPolicy(), id0=3000)

    # phase 3: policy in-path overhead at FEASIBLE load — inert
    # watermarks keep the policy assessing (the real per-submit +
    # per-step cost) without ever changing the admitted work
    inert = SheddingPolicy(queue_low=10 ** 6, queue_high=10 ** 6)
    eng_off, eng_on = new_engine(), new_engine(policy=inert)
    t_off, t_on = [], []
    for rep in range(reps):
        for eng_ab, ts, id0 in ((eng_off, t_off, 4000),
                                (eng_on, t_on, 5000)):
            reqs = mk_requests(n_requests, id0=id0 + rep * 100)
            t0 = time.perf_counter()
            eng_ab.serve(reqs)
            ts.append(time.perf_counter() - t0)
    overhead = (float(np.median(t_on)) - float(np.median(t_off))) \
        / float(np.median(t_off))

    ratio = on["goodput_req_per_sec"] \
        / max(off["goodput_req_per_sec"], 1e-9)
    _emit("gpt2_serving_overload_goodput_req_per_sec",
          on["goodput_req_per_sec"], "req/sec", round(ratio, 4),
          extras={
              "shed_on": on, "shed_off": off,
              "goodput_ratio": round(ratio, 3),
              "capacity_req_per_sec": round(capacity_rps, 3),
              "offered_req_per_sec": round(rate, 3),
              "overload_factor": overload,
              "deadline_ms": round(deadline_ms, 1),
              "policy_overhead_frac": round(overhead, 4),
              "policy_overhead_budget": 0.02,
              "ab_reps": reps,
              "requests": n_requests, "slots": slots,
              "decode_block": block,
              "prompt_lens": f"U[{p_lo},{p_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": f"poisson({round(rate, 2)}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "baseline": "shed-off run above (reference has no "
                          "serving path)",
          })
    return 0 if on["goodput_req_per_sec"] > off["goodput_req_per_sec"] \
        and overhead < 0.02 else 1


def bench_gpt2_serving_router():
    """Fault-tolerant multi-replica serving: the SAME Poisson request
    stream served by 1 replica (fault-free reference) and by a
    2-replica ServingRouter that loses replica 0 to a seeded mid-run
    kill. The router exports the corpse's queued/in-flight requests
    and migrates them to the survivor, continuing each one
    bit-identically via the restart continuation — so the pass
    criteria are ZERO lost requests and ZERO output mismatches for
    every request both runs finished, with goodput (in-deadline
    finishes per second of makespan), TTFT p99, and the migrated count
    reported. vs_baseline is goodput_2rep_kill / goodput_1rep: the
    fleet's headroom means losing half its capacity mid-run should
    still roughly match the single replica the stream was sized
    for."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import (RejectedError, ReplicaFaultPlan,
                                   Request, ServingEngine, ServingRouter)

    # the bit-identity gate needs a counter-stable PRNG: under rbg
    # (main() sets it for TPU dropout throughput) XLA's RngBitGenerator
    # may emit different bits for the same per-request stream when the
    # decode batch composition differs, and the 1- vs 2-replica runs
    # necessarily batch differently. threefry is stable per
    # (seed, token_index) regardless of batching.
    prng_before = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "threefry2x32")

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    64 if on_tpu else 48))
    kill_step = int(os.environ.get("BENCH_ROUTER_KILL_STEP", 12))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    def mk_requests(n, id0, deadline_ms=None):
        # reseeded per call -> every run sees the identical stream;
        # every 3rd request extends one shared page-aligned prefix so
        # affinity routing has something to exploit
        rng = np.random.default_rng(41)
        shared = rng.integers(0, cfg.vocab_size, page).tolist()
        out = []
        for i in range(n):
            if i % 3 == 0 and p_hi > page:
                prompt = shared + rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(1, p_hi - page + 1))).tolist()
            else:
                prompt = rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(p_lo, p_hi + 1))).tolist()
            out.append(Request(prompt, int(rng.integers(o_lo, o_hi + 1)),
                               do_sample=True, temperature=0.8, top_k=40,
                               seed=i, request_id=id0 + i,
                               deadline_ms=deadline_ms))
        return out

    def new_engine():
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, decode_block=block)
        # warm prefill buckets up to p_hi + o_hi: a migrated request
        # re-prefills prompt+emitted, which lands in buckets a
        # prompt-only warmup never compiles — and a mid-run compile
        # would dominate the CPU-smoke makespan
        warm = [Request(list(range(1, b + 1)), 2, request_id=f"w{b}")
                for b in range(page, min(p_hi + o_hi + page, max_len),
                               page)]
        eng.serve(warm)
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id="w-s")])
        eng.reset_stats()
        return eng

    def merged_ttft_p99_ms(engines):
        fam = telemetry.get("serving_ttft_seconds")
        kids = [fam.labels(e._eid) for e in engines]
        kids = [k for k in kids if k.count]
        if not kids:
            return None
        merged = telemetry.Histogram("ttft_merge",
                                     buckets=kids[0].buckets)
        for k in kids:
            merged._counts = [a + b for a, b in
                              zip(merged._counts, k._counts)]
            merged._count += k.count
            merged._sum += k.sum
            merged._min = min(merged._min, k._min)
            merged._max = max(merged._max, k._max)
        return round(merged.percentile(99) * 1e3, 2)

    # phase 1: closed-loop single-replica capacity + service time
    eng = new_engine()
    cap_reqs = mk_requests(n_requests, id0=1000)
    t0 = time.perf_counter()
    eng.serve(cap_reqs)
    capacity_rps = n_requests / (time.perf_counter() - t0)
    service_s = float(np.median([r.t_finish - r.t_admit
                                 for r in cap_reqs]))
    # generous deadline (vs the overload bench's tight one): the
    # contrast here should come from the mid-run capacity loss, not
    # from deadline carnage drowning the failover signal
    deadline_ms = max(6e3 * service_s, 150.0)
    rate = 1.5 * capacity_rps      # brisk for 1 replica, easy for 2

    def run(n_replicas, id0, kill=False):
        engines = [new_engine() for _ in range(n_replicas)]
        router = ServingRouter(engines)
        plan = None
        if kill:
            plan = ReplicaFaultPlan(kill={kill_step: 0}).install(router)
        reqs = mk_requests(n_requests, id0=id0, deadline_ms=deadline_ms)
        arr = np.cumsum(np.random.default_rng(43).exponential(
            1.0 / rate, n_requests))
        rejected = 0
        t0 = time.perf_counter()
        pending = list(zip(arr, reqs))
        try:
            while pending or router.has_work:
                now = time.perf_counter() - t0
                while pending and pending[0][0] <= now:
                    try:
                        router.submit(pending.pop(0)[1])
                    except RejectedError:
                        rejected += 1
                if router.has_work:
                    router.step()
                elif pending:
                    time.sleep(min(pending[0][0] - now, 0.01))
        finally:
            if plan is not None:
                plan.uninstall()
        dt = time.perf_counter() - t0
        good = [r for r in reqs if r.status == "finished"
                and (r.t_finish - r.t_submit) * 1e3 <= deadline_ms]
        lost = [r for r in reqs
                if r.status not in ("finished", "shed", "deadline")]
        audits = [len(e.audit_pages()) for e in engines]
        s = router.stats
        return reqs, {
            "goodput_req_per_sec": round(len(good) / dt, 3),
            "finished_in_deadline": len(good),
            "finished_total": sum(r.status == "finished" for r in reqs),
            "rejected_at_submit": rejected,
            "deadline_cancelled": sum(r.status == "deadline"
                                      for r in reqs),
            "lost": len(lost),
            "migrated": s["migrated"],
            "routed_affinity": s["affinity"],
            "routed_spill": s["spill"],
            "replica_down": s["replica_down"],
            "ttft_p99_ms": merged_ttft_p99_ms(engines),
            "audit_leaks": sum(audits),
            "makespan_s": round(dt, 3),
        }

    try:
        ref_reqs, ref = run(1, id0=2000)
        kill_reqs, faulted = run(2, id0=3000, kill=True)
    finally:
        jax.config.update("jax_default_prng_impl", prng_before)

    # bit-identity across the kill: every request BOTH runs finished
    # must have byte-equal outputs (deadline/shed outcomes may differ —
    # capacities differ — but no finished output may diverge)
    ref_out = {r.id - 2000: list(r.output_tokens) for r in ref_reqs
               if r.status == "finished"}
    kill_out = {r.id - 3000: list(r.output_tokens) for r in kill_reqs
                if r.status == "finished"}
    both = set(ref_out) & set(kill_out)
    mismatches = sum(ref_out[i] != kill_out[i] for i in both)

    ratio = faulted["goodput_req_per_sec"] \
        / max(ref["goodput_req_per_sec"], 1e-9)
    _emit("gpt2_serving_router_goodput_req_per_sec",
          faulted["goodput_req_per_sec"], "req/sec", round(ratio, 4),
          extras={
              "two_replicas_with_kill": faulted,
              "one_replica_reference": ref,
              "goodput_ratio": round(ratio, 3),
              "output_mismatches": mismatches,
              "compared_outputs": len(both),
              "migrated": faulted["migrated"],
              "capacity_1rep_req_per_sec": round(capacity_rps, 3),
              "offered_req_per_sec": round(rate, 3),
              "deadline_ms": round(deadline_ms, 1),
              "kill_step": kill_step,
              "requests": n_requests, "slots": slots,
              "decode_block": block,
              "prompt_lens": f"U[{p_lo},{p_hi}] (1/3 shared prefix)",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": f"poisson({round(rate, 2)}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "baseline": "1-replica fault-free run above (reference "
                          "has no serving path)",
          })
    ok = (mismatches == 0 and faulted["lost"] == 0
          and faulted["audit_leaks"] == 0
          and faulted["replica_down"].get("kill") == 1
          and faulted["migrated"] >= 1)
    return 0 if ok else 1


def bench_gpt2_serving_multitenant():
    """Multi-tenant LoRA serving: ONE resident base model serves a
    Poisson stream from 3 tenants — two equal-weight well-behaved
    tenants and one hog submitting ~2x their rate under a TenantQuota
    — across more registered adapters than the slab holds, so the
    pool pages low-rank deltas in and out (LRU) while every dispatch
    reuses the SAME compiled programs (per-slot slab indices are
    runtime data). Reports aggregate tokens/sec, per-tenant TTFT p99,
    the adapter page-in rate (slab churn per prefill), Jain's
    fairness index over the equal tenants' token throughput, and
    steady_state_compiles. Pass criteria: ZERO compiles after warmup
    across adapter churn, clean page AND adapter audits, the hog
    visibly quota-capped (sheds > 0, every quota-admitted request
    still finishes), and fairness ≥ 0.8 between the equal tenants.
    vs_baseline is the Jain index (1.0 = perfectly fair)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import (AdapterPool, RejectedError, Request,
                                   ServingEngine, TenantQuota,
                                   random_lora)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    64 if on_tpu else 48))
    n_adapters = int(os.environ.get("BENCH_ADAPTERS", 6))
    pool_slots = int(os.environ.get("BENCH_ADAPTER_SLOTS", 4))
    rank = int(os.environ.get("BENCH_ADAPTER_RANK", 8 if on_tpu else 2))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    pool = AdapterPool(cfg, slots=pool_slots, max_rank=rank)
    adapters = [f"ft{i}" for i in range(n_adapters)]
    for i, name in enumerate(adapters):
        pool.register(name, random_lora(cfg, rank=rank, seed=60 + i,
                                        scale=0.02))
    # hog: bounded queue + half the decode slots; aria/bold: equal
    # weight, no hard cap — fairness between THEM is the Jain gate
    quotas = {"hog": TenantQuota(max_active=max(1, slots // 2),
                                 max_queue=max(2, slots // 2)),
              "aria": TenantQuota(weight=1.0),
              "bold": TenantQuota(weight=1.0)}
    eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                        page_size=page, decode_block=block,
                        adapter_pool=pool, tenant_quotas=quotas)

    def mk_requests(n, id0):
        # reseeded per call -> identical stream every run; the hog
        # owns every even index (2x each equal tenant's share), and
        # adapters rotate so consecutive admissions churn the slab
        rng = np.random.default_rng(47)
        out = []
        for i in range(n):
            tenant = "hog" if i % 2 == 0 else \
                ("aria" if i % 4 == 1 else "bold")
            out.append(Request(
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(p_lo, p_hi + 1))).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i, tenant=tenant,
                adapter_id=adapters[i % n_adapters]))
        return out

    # warmup: the unified dispatch with an adapter worn, greedy-only
    # first, then the sampled variant (separate serves — the program
    # specializes on the batch's sampling mix) — after this, adapter
    # churn must be free
    warm = [Request(list(range(1, b + 1)), 2, request_id=f"w{b}",
                    adapter_id=adapters[b % n_adapters])
            for b in range(page, min(p_hi + page, max_len), page)]
    eng.serve(warm)
    eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                       seed=0, request_id="w-s",
                       adapter_id=adapters[0])])
    eng.reset_stats()
    c0 = _engine_compiles(eng._eid)

    # phase 1: closed-loop capacity (quota-free tenant mix never hits
    # the hog cap here — serve() drains as fast as slots allow)
    cap_reqs = mk_requests(n_requests, id0=1000)
    t0 = time.perf_counter()
    done = eng.serve(cap_reqs)
    capacity_rps = len(done) / (time.perf_counter() - t0)
    eng.reset_stats()

    # phase 2: open-loop Poisson at ~1.5x capacity so queues form and
    # the hog's quota actually binds
    rate = 1.5 * capacity_rps
    reqs = mk_requests(n_requests, id0=2000)
    arr = np.cumsum(np.random.default_rng(49).exponential(
        1.0 / rate, n_requests))
    shed = {t: 0 for t in quotas}
    t0 = time.perf_counter()
    pending = list(zip(arr, reqs))
    while pending or eng.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            r = pending.pop(0)[1]
            try:
                eng.submit(r)
            except RejectedError:
                shed[r.tenant] += 1
        if eng.has_work:
            eng.step()
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.01))
    dt = time.perf_counter() - t0

    fin = [r for r in reqs if r.status == "finished"]
    tokens = sum(len(r.output_tokens) for r in fin)
    by_tenant = {t: [r for r in fin if r.tenant == t] for t in quotas}

    def ttft_p99_ms(rs):
        w = [(r.token_times[0] - r.t_submit) * 1e3 for r in rs
             if r.token_times]
        return round(float(np.percentile(w, 99)), 2) if w else None

    eq = [sum(len(r.output_tokens) for r in by_tenant[t])
          for t in ("aria", "bold")]
    jain = (sum(eq) ** 2 / (len(eq) * sum(x * x for x in eq))
            if sum(eq) else 0.0)
    steady_compiles = _engine_compiles(eng._eid) - c0
    s = eng.stats
    page_in_rate = pool.page_ins / max(s["prefills"], 1)
    tstats = eng.tenant_stats()
    lost = [r for r in reqs if r.status not in ("finished", "rejected")]

    _emit("gpt2_serving_multitenant_tokens_per_sec",
          round(tokens / dt, 2), "tokens/sec", round(jain, 4),
          extras={
              "fairness_jain_equal_tenants": round(jain, 4),
              "steady_state_compiles": steady_compiles,
              "adapter_page_ins": pool.page_ins,
              "adapter_page_in_rate_per_prefill": round(page_in_rate, 3),
              "adapter_evictions": pool.evictions,
              "adapters_registered": n_adapters,
              "adapter_slab_slots": pool_slots - 1,
              "adapter_rank": rank,
              "adapter_slab_bytes": pool.slab_bytes(),
              "ttft_p99_ms": {t: ttft_p99_ms(by_tenant[t])
                              for t in sorted(quotas)},
              "finished": {t: len(by_tenant[t]) for t in sorted(quotas)},
              "tokens": {t: sum(len(r.output_tokens)
                                for r in by_tenant[t])
                         for t in sorted(quotas)},
              "shed_at_submit": shed,
              "tenant_stats": tstats,
              "audit_leaks": len(eng.audit_pages())
              + len(eng.audit_adapters()),
              "capacity_req_per_sec": round(capacity_rps, 3),
              "offered_req_per_sec": round(rate, 3),
              "requests": n_requests, "slots": slots,
              "decode_block": block, "makespan_s": round(dt, 3),
              "prompt_lens": f"U[{p_lo},{p_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": f"poisson({round(rate, 2)}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "baseline": "Jain fairness index between the equal-weight "
                          "tenants (1.0 = perfectly fair)",
          })
    ok = (steady_compiles == 0
          and not eng.audit_pages() and not eng.audit_adapters()
          and not lost
          and shed["hog"] > 0 and not shed["aria"] and not shed["bold"]
          and pool.page_ins > pool_slots - 1   # churn actually happened
          and jain >= 0.8)
    return 0 if ok else 1


def bench_gpt2_serving_chunked():
    """Chunked-prefill serving: a Poisson mix of short prompts and
    long (2-4k-token on TPU) prompts served through the unified
    fixed-shape dispatch, run under two chunking configs on IDENTICAL
    request streams — `monolithic` (chunk_tokens = max_length: a whole
    prompt lands in one dispatch, the pre-chunking behaviour where
    every co-resident decoder stalls for the full prefill) and `paged`
    (chunk_tokens = page_size, the default: long prompts stream one
    page per tick next to everyone else's decode). Reports tokens/sec,
    TTFT p50/p99 split short vs long (cross-checked against the
    serving_ttft_by_prompt_seconds histogram children), decode
    inter-token p99, and steady_state_compiles per config. Because
    chunk size is runtime data to the one compiled program, BOTH
    configs must show zero steady-state compiles across arbitrary
    unbucketed prompt lengths, and greedy token streams must agree
    across configs (chunking is a pure scheduling knob; sampled
    streams may flip a near-boundary draw because the two dispatch
    widths are different XLA programs with different float rounding).
    Pass criteria: zero steady compiles, clean page audits, every
    request finished, greedy outputs identical across configs, and
    paged short-prompt TTFT p99 no worse than monolithic's +10%.
    vs_baseline is the monolithic / paged short-TTFT-p99 ratio
    (>1 = chunking helped)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    from mxnet_tpu import telemetry

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 20))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 4096, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    l_lo, l_hi = 2048, 3584
    if not on_tpu:  # CPU smoke config: "long" is long vs max_length,
        # and the model is kept wide enough that a W=128 dispatch
        # costs visibly more than a W=8 one (a toy net would be
        # dispatch-overhead-bound and hide the chunking win)
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 256, 1024
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 4, 128
        max_len, page = 128, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        l_lo, l_hi = 64, 96
        slots = min(slots, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    def mk_requests(n, id0):
        # reseeded per config -> both configs serve the SAME stream;
        # the first two prompts are long so the long-prefill stream is
        # in flight while every short request's TTFT clock runs
        rng = np.random.default_rng(11)
        out = []
        for i in range(n):
            is_long = i < 2 or rng.random() < 0.25
            lo, hi = (l_lo, l_hi) if is_long else (p_lo, p_hi)
            out.append(Request(
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(lo, hi + 1))).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    def ttft_hist_children(eid):
        # the per-prompt-length TTFT histogram, split by power-of-two
        # prompt bucket — the in-process cross-check for the
        # request-derived numbers below
        fam = telemetry.get("serving_ttft_by_prompt_seconds")
        out = {}
        for vals, child in fam._samples():
            if vals and vals[0] == str(eid) and child.count:
                out[vals[1]] = {
                    "count": child.count,
                    "p50_ms": round(child.percentile(50) * 1e3, 2),
                    "p99_ms": round(child.percentile(99) * 1e3, 2)}
        return out

    def run_config(tag, chunk_tokens):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, chunk_tokens=chunk_tokens)
        # warmup compiles BOTH unified variants — prompt length no
        # longer selects a program, so one short greedy serve plus one
        # short sampled serve cover every length the stream will throw
        # at it (served separately: a mixed batch only exercises the
        # sampled variant)
        eng.serve([Request(list(range(1, page + 1)), 2,
                           request_id=f"{tag}-warm-greedy")])
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id=f"{tag}-warm-sampled")])
        eng.mark_warm()
        c0 = _engine_compiles(eng._eid)
        eng.reset_stats()

        reqs = mk_requests(n_requests, id0=1000)
        rng = np.random.default_rng(13)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0

        fin = [r for r in reqs if r.status == "finished"]
        tokens = sum(len(r.output_tokens) for r in fin)

        def ttft_split(pred):
            w = [(r.token_times[0] - r.t_submit) * 1e3 for r in reqs
                 if pred(len(r.prompt)) and r.token_times]
            if not w:
                return None
            return {"count": len(w),
                    "p50_ms": round(float(np.percentile(w, 50)), 2),
                    "p99_ms": round(float(np.percentile(w, 99)), 2)}

        tl = telemetry.get("serving_token_latency_seconds").labels(
            eng._eid)
        s = eng.stats
        return {
            "chunk_tokens": chunk_tokens,
            "dispatch_width": eng._width,
            "tokens_per_sec": round(tokens / dt, 2),
            "ttft_short_ms": ttft_split(lambda p: p <= p_hi),
            "ttft_long_ms": ttft_split(lambda p: p >= l_lo),
            "ttft_by_prompt_bucket": ttft_hist_children(eng._eid),
            "decode_p99_ms": round(tl.percentile(99) * 1e3, 2)
            if tl.count else None,
            "steady_state_compiles": _engine_compiles(eng._eid) - c0,
            "prefill_chunks": s["prefill_chunks"],
            "decode_dispatches": s["decode_dispatches"],
            "finished": len(fin), "requests": n_requests,
            "makespan_s": round(dt, 3),
            "audit_leaks": len(eng.audit_pages()),
            "outputs": {r.id: (bool(r.do_sample), list(r.output_tokens))
                        for r in reqs},
            "device_cost": _device_cost_extras(eng._eid),
        }

    mono = run_config("monolithic", max_len)
    paged = run_config("paged", page)
    # the two configs compile DIFFERENT dispatch widths (W=max_len vs
    # W=page), i.e. different XLA programs whose float reductions may
    # round differently — greedy argmax streams must still agree
    # (chunking is a pure scheduling knob), while sampled streams may
    # legitimately flip a near-boundary draw; both are reported
    out_m, out_p = mono.pop("outputs"), paged.pop("outputs")
    identical = out_m == out_p
    greedy_identical = \
        {k: v for k, v in out_m.items() if not v[0]} \
        == {k: v for k, v in out_p.items() if not v[0]}

    def p99(block):
        return block["ttft_short_ms"]["p99_ms"] \
            if block["ttft_short_ms"] else None
    ratio = round(p99(mono) / p99(paged), 3) \
        if p99(mono) and p99(paged) else 0.0

    n_long = sum(1 for r in mk_requests(n_requests, 0)
                 if len(r.prompt) >= l_lo)
    _emit("gpt2_serving_chunked_tokens_per_sec",
          paged["tokens_per_sec"], "tokens/sec", ratio, extras={
              "short_ttft_p99_speedup_vs_monolithic": ratio,
              "identical_outputs_across_chunk_sizes": identical,
              "greedy_outputs_identical_across_chunk_sizes":
                  greedy_identical,
              "paged": paged, "monolithic": mono,
              "short_prompts": n_requests - n_long,
              "long_prompts": n_long, "slots": slots,
              "prompt_lens": f"short U[{p_lo},{p_hi}] + "
                             f"long U[{l_lo},{l_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}]",
              "arrivals": "open-loop" if rate == 0
                          else f"poisson({rate}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "baseline": "monolithic chunk_tokens=max_length (the "
                          "pre-chunking whole-prompt dispatch) on the "
                          "same stream",
          })
    # the gate lane tracks short-prompt TTFT directly (lower-better by
    # name) so a chunk-scheduling regression fails bench_compare even
    # when aggregate tokens/sec holds
    _emit("gpt2_serving_chunked_short_ttft_p99_ms", p99(paged) or 0.0,
          "ms", ratio, extras={
              "monolithic_p99_ms": p99(mono),
              "long_stream_in_flight": True,
          })
    ok = (paged["steady_state_compiles"] == 0
          and mono["steady_state_compiles"] == 0
          and not paged["audit_leaks"] and not mono["audit_leaks"]
          and paged["finished"] == n_requests
          and mono["finished"] == n_requests
          and greedy_identical
          and (not p99(mono) or not p99(paged)
               or p99(paged) <= 1.10 * p99(mono)))
    return 0 if ok else 1


def bench_gpt2_serving_quantkv():
    """Int8 KV pages vs fp32 at ONE fixed HBM budget — the capacity
    proof (docs/SERVING.md "Quantized KV pages"). The budget is sized
    so the fp32 engine is page-limited (half its natural pool): the
    byte-denominated `PagePool.from_bytes` sizing then hands the int8
    engine >= 1.8x (really ~2x here, pool-clamped; ~3.9x per byte) the
    ADMITTED pages, i.e. more concurrent slots, at identical W and
    zero steady-state compiles. Both engines serve the same Poisson
    stream (greedy + sampled mix); accuracy is gated two ways: a
    greedy tolerance oracle (per-token agreement vs the fp32 engine —
    int8 rounding may flip near-tie argmaxes, so agreement, not
    equality) and a paired-seed frequency test (first sampled token
    over many seeds; total-variation distance between the fp32 and
    int8 empirical marginals). Pass criteria: admitted-pages ratio
    >= 1.8, int8 goodput >= 0.9x fp32 at the same budget, greedy
    token agreement >= 0.6, frequency TV <= 0.30, zero steady
    compiles, clean audits, everything finished. vs_baseline is the
    int8/fp32 goodput ratio (>1 = the freed bytes bought throughput)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 20))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    n_freq = int(os.environ.get("BENCH_QUANTKV_FREQ_SEEDS", 200))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 96
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 256, 1024
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 4, 128
        max_len, page = 128, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    # ONE byte budget for both engines, sized so fp32 is page-limited:
    # half its natural pool (but never below one slot's worth of pages)
    L, H = cfg.num_layers, cfg.num_heads
    Dh = cfg.units // cfg.num_heads
    fp_page_bytes = 2 * L * page * H * Dh * 4
    pages_per_slot = max_len // page
    budget = fp_page_bytes * max(pages_per_slot,
                                 slots * pages_per_slot // 2)

    def mk_requests(n, id0):
        rng = np.random.default_rng(17)
        out = []
        for i in range(n):
            out.append(Request(
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(p_lo, p_hi + 1))).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    def run_config(tag, kv_dtype):
        # int8 numerics depend on the chunk grid, so BOTH configs pin
        # the same grid with a non-binding prefill budget — the
        # comparison varies storage dtype and nothing else
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, kv_dtype=kv_dtype,
                            hbm_budget_bytes=budget,
                            chunk_tokens=page,
                            prefill_chunk_budget=slots * page)
        eng.serve([Request(list(range(1, page + 1)), 2,
                           request_id=f"{tag}-warm-greedy")])
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id=f"{tag}-warm-sampled")])
        eng.mark_warm()
        c0 = _engine_compiles(eng._eid)
        eng.reset_stats()

        reqs = mk_requests(n_requests, id0=1000)
        rng = np.random.default_rng(13)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0

        fin = [r for r in reqs if r.status == "finished"]
        tokens = sum(len(r.output_tokens) for r in fin)
        s = eng.stats
        return eng, {
            "kv_dtype": s["kv_quant_enabled"] and "int8" or "float32",
            "admitted_pages": eng.page_pool.num_pages,
            "kv_page_bytes": s["kv_page_bytes"],
            "kv_bytes_per_token": s["kv_bytes_per_token"],
            "admission_capacity": s["admission_capacity"],
            "goodput_tokens_per_sec": round(tokens / dt, 2),
            "makespan_s": round(dt, 3),
            "finished": len(fin), "requests": n_requests,
            "steady_state_compiles": _engine_compiles(eng._eid) - c0,
            "warm_compiles": c0,
            "audit_leaks": len(eng.audit_pages()),
            "outputs": {r.id: (bool(r.do_sample), list(r.output_tokens))
                        for r in reqs},
            "device_cost": _device_cost_extras(eng._eid),
        }

    fp_eng, fp = run_config("fp32", None)
    q8_eng, q8 = run_config("int8", "int8")

    # greedy tolerance oracle: per-token agreement on greedy requests
    out_f, out_q = fp.pop("outputs"), q8.pop("outputs")
    agree = total = exact = n_greedy = 0
    for rid, (sampled, toks_f) in out_f.items():
        if sampled:
            continue
        toks_q = out_q[rid][1]
        n_greedy += 1
        exact += int(toks_f == toks_q)
        agree += sum(int(a == b) for a, b in zip(toks_f, toks_q))
        total += max(len(toks_f), len(toks_q))
    agreement = agree / total if total else 0.0

    # paired-seed frequency test: same uniform draws through both
    # engines, so the empirical first-token marginals only separate
    # where a draw lands between the two CDFs
    freq_prompt = list(range(3, 3 + max(3, p_lo)))
    counts = {}
    for tag, eng in (("fp", fp_eng), ("q8", q8_eng)):
        c = {}
        for s in range(n_freq):
            r = Request(freq_prompt, 1, do_sample=True, temperature=1.0,
                        top_k=8, seed=s, request_id=f"freq-{tag}-{s}")
            eng.serve([r])
            t = r.output_tokens[0]
            c[t] = c.get(t, 0) + 1
        counts[tag] = c
    support = set(counts["fp"]) | set(counts["q8"])
    tv = 0.5 * sum(abs(counts["fp"].get(t, 0) - counts["q8"].get(t, 0))
                   for t in support) / n_freq

    # the frequency serves ran through the already-warm engines: the
    # steady-compile and audit verdicts cover them too
    for eng, blk in ((fp_eng, fp), (q8_eng, q8)):
        blk["steady_state_compiles"] = \
            _engine_compiles(eng._eid) - blk.pop("warm_compiles")
        blk["audit_leaks"] = len(eng.audit_pages())
    pages_ratio = round(q8["admitted_pages"] / fp["admitted_pages"], 3)
    goodput_ratio = round(q8["goodput_tokens_per_sec"]
                          / max(fp["goodput_tokens_per_sec"], 1e-9), 3)
    extras = {
        "hbm_budget_bytes": budget,
        "capacity_at_bytes": {"admitted_pages": pages_ratio},
        "admitted_pages_ratio": pages_ratio,
        "greedy_token_agreement": round(agreement, 4),
        "greedy_exact_sequences": f"{exact}/{n_greedy}",
        "frequency_tv_distance": round(tv, 4),
        "frequency_seeds": n_freq,
        "int8": q8, "float32": fp,
        "slots": slots,
        "prompt_lens": f"U[{p_lo},{p_hi}]",
        "output_lens": f"U[{o_lo},{o_hi}]",
        "arrivals": "open-loop" if rate == 0 else f"poisson({rate}/s)",
        "params": cfg.num_params(),
        "device": str(dev.device_kind),
        "baseline": "fp32 pages at the SAME hbm_budget_bytes (page-"
                    "limited) on the same stream",
    }
    _emit("gpt2_serving_quantkv_goodput_tokens_per_sec",
          q8["goodput_tokens_per_sec"], "tokens/sec", goodput_ratio,
          extras=extras)
    # gate lanes: admitted pages (higher-better by explicit override)
    # and HBM per token (lower-better by name)
    _emit("gpt2_serving_quantkv_admitted_pages", q8["admitted_pages"],
          "pages", pages_ratio,
          extras={"fp32_admitted_pages": fp["admitted_pages"],
                  "ratio_vs_fp32": pages_ratio})
    _emit("gpt2_serving_quantkv_kv_bytes_per_token",
          q8["kv_bytes_per_token"], "bytes", pages_ratio,
          extras={"fp32_kv_bytes_per_token": fp["kv_bytes_per_token"]})
    ok = (pages_ratio >= 1.8
          and q8["steady_state_compiles"] == 0
          and fp["steady_state_compiles"] == 0
          and not q8["audit_leaks"] and not fp["audit_leaks"]
          and q8["finished"] == n_requests
          and fp["finished"] == n_requests
          and goodput_ratio >= 0.9
          and agreement >= 0.6
          and tv <= 0.30)
    return 0 if ok else 1


def bench_gpt2_serving_w8():
    """w8 weight serving vs fp32 at ONE fixed per-chip HBM budget that
    covers weights AND pages (docs/SERVING.md "Weight quantization").
    The budget is sized so the fp32 engine's weight slab is binding —
    it affords only half its natural page pool — and both engines run
    `hbm_budget_includes_weights=True`: the ~4x megatron weight-slab
    shrink (int8 codes + f32 per-out-tile dequant scales vs fp32)
    becomes real admitted KV pages, i.e. capacity, at identical W and
    zero steady-state compiles. Accuracy is gated exactly like the
    int8-KV lane: greedy per-token agreement vs the fp32 engine plus a
    paired-seed first-token frequency test (total variation). Pass
    criteria: weight-slab ratio >= 3, admitted-pages ratio >= 1.3, w8
    goodput >= 0.9x fp32, greedy agreement >= 0.6, frequency TV
    <= 0.30, zero steady compiles, clean audits, everything finished.
    vs_baseline is the w8/fp32 goodput ratio."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 20))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    n_freq = int(os.environ.get("BENCH_W8_FREQ_SEEDS", 200))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 96
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 256, 1024
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 4, 128
        max_len, page = 128, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    # ONE per-chip budget covering weights + pages, sized off the fp32
    # engine: its weight slab plus HALF its natural page pool — fp32 is
    # weight-limited, and every byte w8 frees is a page it can admit
    probe = ServingEngine(net, num_slots=slots, max_length=max_len,
                          page_size=page)
    wb_fp = probe.stats["weight_bytes_per_chip"]
    fp_page_bytes = probe.page_pool.page_bytes
    pages_per_slot = max_len // page
    fp_pages = max(pages_per_slot, slots * pages_per_slot // 2)
    budget = wb_fp + fp_page_bytes * fp_pages
    del probe

    def mk_requests(n, id0):
        rng = np.random.default_rng(17)
        out = []
        for i in range(n):
            out.append(Request(
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(p_lo, p_hi + 1))).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    def run_config(tag, weight_dtype):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, weight_dtype=weight_dtype,
                            hbm_budget_bytes=budget,
                            hbm_budget_includes_weights=True,
                            chunk_tokens=page,
                            prefill_chunk_budget=slots * page)
        eng.serve([Request(list(range(1, page + 1)), 2,
                           request_id=f"{tag}-warm-greedy")])
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id=f"{tag}-warm-sampled")])
        eng.mark_warm()
        c0 = _engine_compiles(eng._eid)
        eng.reset_stats()

        reqs = mk_requests(n_requests, id0=1000)
        rng = np.random.default_rng(13)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0

        fin = [r for r in reqs if r.status == "finished"]
        tokens = sum(len(r.output_tokens) for r in fin)
        s = eng.stats
        return eng, {
            "weight_dtype": eng.weight_dtype,
            "weight_bytes_total": s["weight_bytes_total"],
            "weight_bytes_per_chip": s["weight_bytes_per_chip"],
            "admitted_pages": eng.page_pool.num_pages,
            "goodput_tokens_per_sec": round(tokens / dt, 2),
            "makespan_s": round(dt, 3),
            "finished": len(fin), "requests": n_requests,
            "steady_state_compiles": _engine_compiles(eng._eid) - c0,
            "warm_compiles": c0,
            "audit_leaks": len(eng.audit_pages()),
            "outputs": {r.id: (bool(r.do_sample), list(r.output_tokens))
                        for r in reqs},
            "device_cost": _device_cost_extras(eng._eid),
        }

    fp_eng, fp = run_config("fp32", None)
    w8_eng, w8 = run_config("w8", "int8")

    # the SLAB the tentpole shrinks: the megatron col/row weights —
    # fp32 bytes vs int8 codes + f32 dequant scales for the same arrays
    slab_fp = sum(int(q.codes.size) * 4 for q in w8_eng._w8_plan)
    slab_w8 = sum(int(q.codes.size) + int(q.scale.size) * 4
                  for q in w8_eng._w8_plan)
    slab_ratio = round(slab_fp / slab_w8, 3)
    total_ratio = round(fp["weight_bytes_total"]
                        / w8["weight_bytes_total"], 3)

    # greedy tolerance oracle: per-token agreement on greedy requests
    out_f, out_w = fp.pop("outputs"), w8.pop("outputs")
    agree = total = exact = n_greedy = 0
    for rid, (sampled, toks_f) in out_f.items():
        if sampled:
            continue
        toks_w = out_w[rid][1]
        n_greedy += 1
        exact += int(toks_f == toks_w)
        agree += sum(int(a == b) for a, b in zip(toks_f, toks_w))
        total += max(len(toks_f), len(toks_w))
    agreement = agree / total if total else 0.0

    # paired-seed frequency test: same uniform draws through both
    # engines, marginals only separate where a draw lands between CDFs
    freq_prompt = list(range(3, 3 + max(3, p_lo)))
    counts = {}
    for tag, eng in (("fp", fp_eng), ("w8", w8_eng)):
        c = {}
        for s in range(n_freq):
            r = Request(freq_prompt, 1, do_sample=True, temperature=1.0,
                        top_k=8, seed=s, request_id=f"freq-{tag}-{s}")
            eng.serve([r])
            t = r.output_tokens[0]
            c[t] = c.get(t, 0) + 1
        counts[tag] = c
    support = set(counts["fp"]) | set(counts["w8"])
    tv = 0.5 * sum(abs(counts["fp"].get(t, 0) - counts["w8"].get(t, 0))
                   for t in support) / n_freq

    # the frequency serves ran through the already-warm engines
    for eng, blk in ((fp_eng, fp), (w8_eng, w8)):
        blk["steady_state_compiles"] = \
            _engine_compiles(eng._eid) - blk.pop("warm_compiles")
        blk["audit_leaks"] = len(eng.audit_pages())
    pages_ratio = round(w8["admitted_pages"] / fp["admitted_pages"], 3)
    goodput_ratio = round(w8["goodput_tokens_per_sec"]
                          / max(fp["goodput_tokens_per_sec"], 1e-9), 3)
    extras = {
        "hbm_budget_bytes": budget,
        "budget_includes_weights": True,
        "weight_slab_ratio": slab_ratio,
        "weight_total_ratio": total_ratio,
        "admitted_pages_ratio": pages_ratio,
        "greedy_token_agreement": round(agreement, 4),
        "greedy_exact_sequences": f"{exact}/{n_greedy}",
        "frequency_tv_distance": round(tv, 4),
        "frequency_seeds": n_freq,
        "int8": w8, "float32": fp,
        "slots": slots,
        "prompt_lens": f"U[{p_lo},{p_hi}]",
        "output_lens": f"U[{o_lo},{o_hi}]",
        "arrivals": "open-loop" if rate == 0 else f"poisson({rate}/s)",
        "params": cfg.num_params(),
        "device": str(dev.device_kind),
        "baseline": "fp32 weights at the SAME hbm_budget_bytes "
                    "(weight-limited, hbm_budget_includes_weights) on "
                    "the same stream",
    }
    _emit("gpt2_serving_w8_goodput_tokens_per_sec",
          w8["goodput_tokens_per_sec"], "tokens/sec", goodput_ratio,
          extras=extras)
    # gate lanes: weight slab bytes (lower-better by name) and admitted
    # pages (higher-better by explicit override in bench_compare)
    _emit("gpt2_serving_w8_weight_bytes", slab_w8, "bytes", slab_ratio,
          extras={"fp32_weight_slab_bytes": slab_fp,
                  "ratio_vs_fp32": slab_ratio,
                  "whole_model_ratio": total_ratio})
    _emit("gpt2_serving_w8_admitted_pages", w8["admitted_pages"],
          "pages", pages_ratio,
          extras={"fp32_admitted_pages": fp["admitted_pages"],
                  "ratio_vs_fp32": pages_ratio})
    ok = (slab_ratio >= 3.0
          and pages_ratio >= 1.3
          and w8["steady_state_compiles"] == 0
          and fp["steady_state_compiles"] == 0
          and not w8["audit_leaks"] and not fp["audit_leaks"]
          and w8["finished"] == n_requests
          and fp["finished"] == n_requests
          and goodput_ratio >= 0.9
          and agreement >= 0.6
          and tv <= 0.30)
    return 0 if ok else 1


def bench_gpt2_serving_kvspill():
    """Tiered KV cache A/B at ONE fixed HBM page budget (docs/
    SERVING.md "Tiered KV cache"): a Poisson shared-prefix stream
    whose distinct prefix working set is >= 3x the HBM page budget, so
    the prefix cache MUST evict between revisits. Spill OFF discards
    the evicted pages and re-prefills every revisit from scratch;
    spill ON moves them to a host-RAM tier and pages them back in on
    the radix hit — same fixed-shape dispatch, tier traffic outside
    the traced graph. The round also decomposes TTFT p99 into the
    phase budget (telemetry.PHASES) per KV tier (resident/spilled/
    cold) under the tiered load, and gates the OBSERVABILITY cost
    itself: a rotated-order A/B (3 runs per arm, best-of basis) of
    the same spill-on stream with request tracing + SLO accounting
    disabled vs enabled must show < 2% goodput overhead. Pass criteria: spill-on goodput >=
    1.3x spill-off, STRICTLY fewer prefilled tokens, 0 greedy output
    mismatches vs the spill-off engine (the tier's exactness
    contract), zero steady-state compiles on BOTH engines, clean page
    + host-tier audits, everything finished, a spilled-tier phase
    breakdown with real host_pagein time, obs overhead < 2%.
    vs_baseline is the on/off goodput ratio (>1 = page-in beat
    re-prefill)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 2))
    visits = int(os.environ.get("BENCH_KVSPILL_VISITS", 3))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 256, 1024
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 4, 128
        max_len, page = 128, 8

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    P = max_len // page
    # each family's shared prefix fills 3/4 of a slot's pages; the
    # rest is the unique tail + decode room
    prefix_pages = (3 * P) // 4
    prefix_len = prefix_pages * page
    L, H = cfg.num_layers, cfg.num_heads
    Dh = cfg.units // cfg.num_heads
    page_bytes = 2 * L * page * H * Dh * \
        (2 if cfg.dtype == "bfloat16" else 4)
    # HBM budget: the natural dispatch pool + only 4 retention pages —
    # far too small to keep any family's prefix resident between
    # revisits. The host tier gets room for the whole working set.
    budget_pages = slots * P + 4
    hbm_budget = page_bytes * budget_pages
    families = max(4, -(-3 * budget_pages // prefix_pages))
    working_set_pages = families * prefix_pages
    host_budget = page_bytes * (working_set_pages + 8 * P)

    rng = np.random.default_rng(17)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len).tolist()
                for _ in range(families)]

    def mk_requests(id0):
        # round-robin over families so every revisit arrives AFTER the
        # budget forced its prefix out of HBM
        out = []
        for v in range(visits):
            for f in range(families):
                out.append(Request(
                    prefixes[f] + [1 + v, 2 + f],  # unique tail
                    3, request_id=f"{id0}-v{v}f{f}"))
        return out

    def run_config(tag, host_bytes):
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, prefix_cache=True,
                            hbm_budget_bytes=hbm_budget,
                            host_kv_bytes=host_bytes,
                            chunk_tokens=page,
                            prefill_chunk_budget=slots * page)
        # warm the dispatch on full-length prefills (the budget fixes
        # the chunk grid) and the tail/decode shapes. Three distinct
        # long prefixes overflow the tiny retention budget, so the
        # spill engine ALSO compiles its tier gather here, and the
        # revisit of the first (now spilled) prefix compiles the
        # page-in scatter — tier jits never land inside measurement.
        warm = [[(w * 37 + t) % cfg.vocab_size
                 for t in range(1, prefix_len + 2)] for w in range(3)]
        for w, p in enumerate(warm):
            eng.serve([Request(p, 3, request_id=f"{tag}-warm-long{w}")])
        eng.serve([Request(warm[0], 3, request_id=f"{tag}-warm-again")])
        eng.serve([Request([7, 8, 9], 3, request_id=f"{tag}-warm-short")])
        eng.mark_warm()
        c0 = _engine_compiles(eng._eid)
        eng.reset_stats()

        reqs = mk_requests(id0=tag)
        rng = np.random.default_rng(13)
        gaps = rng.exponential(1.0 / rate, len(reqs)) if rate > 0 \
            else np.zeros(len(reqs))
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0

        fin = [r for r in reqs if r.status == "finished"]
        tokens = sum(len(r.output_tokens) for r in fin)
        s = eng.stats
        hits, misses = s["prefix_hits"], s["prefix_misses"]
        host_audit = [] if eng.host_pool is None else eng.host_pool.audit()
        return {
            "spill": host_bytes is not None,
            "goodput_tokens_per_sec": round(tokens / dt, 2),
            "makespan_s": round(dt, 3),
            "prefill_tokens": s["prefill_tokens"],
            "prefix_hits": hits, "prefix_misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 4),
            "prefix_tokens_saved": s["prefix_tokens_saved"],
            "kv_spill_pages": s["kv_spill_pages"],
            "kv_pagein_pages": s["kv_pagein_pages"],
            "kv_host_evictions": s["kv_host_evictions"],
            "finished": len(fin), "requests": len(reqs),
            "steady_state_compiles": _engine_compiles(eng._eid) - c0,
            "audit_leaks": len(eng.audit_pages()) + len(host_audit),
            "outputs": {r.id.split("-", 1)[1]: list(r.output_tokens)
                        for r in reqs},
            "device_cost": _device_cost_extras(eng._eid),
        }

    off = run_config("off", None)
    on = run_config("on", host_budget)

    # the tier's exactness contract: greedy outputs bit-identical to
    # the spill-off engine — page-in must never change a token
    out_off, out_on = off.pop("outputs"), on.pop("outputs")
    mismatches = sum(int(out_off[k] != out_on[k]) for k in out_off)

    # -- observability-overhead A/B (rotated order, best-of basis) -------
    # same spill-on stream, tracing + SLO accounting out of / in the
    # request path. Rotation cancels linear machine drift; the
    # BEST-OF-3 goodput per arm is the estimator (timeit-style
    # min-time: scheduler jitter and GC pauses only ever slow a run
    # down, so per-run goodput is one-sided noise that a mean would
    # launder into the gate)
    from mxnet_tpu import telemetry

    def obs_arm(instrumented, tag):
        telemetry.request_log.enabled = instrumented
        if instrumented:
            telemetry.slo.configure([
                telemetry.SLO("bench_ttft", ttft_p99_ms=60_000.0),
                telemetry.SLO("bench_goodput", goodput_min=1.0)])
        try:
            r = run_config(tag, host_budget)
        finally:
            telemetry.request_log.enabled = True
            telemetry.slo.slo_engine.configure(())
        r.pop("outputs")
        return r["goodput_tokens_per_sec"]

    order = (False, True, True, False, False, True)
    arm_goodput = [obs_arm(en, f"obs{i}")
                   for i, en in enumerate(order)]
    g_plain = max(g for en, g in zip(order, arm_goodput) if not en)
    g_traced = max(g for en, g in zip(order, arm_goodput) if en)
    obs_overhead = round(float(g_plain) / max(float(g_traced), 1e-9)
                         - 1.0, 4)

    # -- TTFT phase budget per KV tier, from the traced arms -------------
    def phase_breakdown(tags):
        rows = {}
        for tr in telemetry.request_log.recent(10**6):
            rid = str(tr["request_id"])
            if not any(rid.startswith(t + "-v") for t in tags):
                continue
            ft = [e for e in tr["events"] if e["event"] == "first_token"]
            if not ft:
                continue
            rows.setdefault(ft[-1].get("kv_tier", "cold"), []).append(
                (float(ft[-1]["ttft"]), tr.get("phases") or {}))
        out = {}
        for tier, samples in sorted(rows.items()):
            ttfts = [t for t, _ in samples]
            tot = {}
            for _, ph in samples:
                for k, v in ph.items():
                    tot[k] = tot.get(k, 0.0) + v
            grand = sum(tot.values()) or 1.0
            out[tier] = {
                "requests": len(samples),
                "ttft_p50_ms": round(
                    float(np.percentile(ttfts, 50)) * 1e3, 2),
                "ttft_p99_ms": round(
                    float(np.percentile(ttfts, 99)) * 1e3, 2),
                "phase_p99_ms": {
                    k: round(float(np.percentile(
                        [ph.get(k, 0.0) for _, ph in samples], 99))
                        * 1e3, 2) for k in sorted(tot)},
                "phase_share": {k: round(tot[k] / grand, 4)
                                for k in sorted(tot)},
            }
        return out

    breakdown = phase_breakdown(
        [f"obs{i}" for i, en in enumerate(order) if en])
    spilled = breakdown.get("spilled", {})

    goodput_ratio = round(on["goodput_tokens_per_sec"]
                          / max(off["goodput_tokens_per_sec"], 1e-9), 3)
    prefill_ratio = round(off["prefill_tokens"]
                          / max(on["prefill_tokens"], 1), 3)
    extras = {
        "hbm_budget_bytes": hbm_budget,
        "hbm_budget_pages": budget_pages,
        "host_budget_bytes": host_budget,
        "working_set_pages": working_set_pages,
        "working_set_over_budget": round(
            working_set_pages / budget_pages, 2),
        "prefix_families": families, "visits": visits,
        "prefix_len": prefix_len,
        "greedy_mismatches": mismatches,
        "ttft_phase_breakdown": breakdown,
        "obs_overhead": obs_overhead,
        "obs_goodput_traced": round(float(g_traced), 2),
        "obs_goodput_plain": round(float(g_plain), 2),
        "on": on, "off": off,
        "slots": slots,
        "arrivals": "open-loop" if rate == 0 else f"poisson({rate}/s)",
        "params": cfg.num_params(),
        "device": str(dev.device_kind),
        "baseline": "spill-off prefix cache at the SAME "
                    "hbm_budget_bytes on the same stream (evictions "
                    "discard; revisits re-prefill)",
    }
    _emit("gpt2_serving_kvspill_goodput_tokens_per_sec",
          on["goodput_tokens_per_sec"], "tokens/sec", goodput_ratio,
          extras=extras)
    # gate lanes: hit_rate (higher-better by name) and re-prefilled
    # tokens (lower-better by name) — both tracked by bench_compare
    # additive vs_baseline (1 + delta): the spill-off engine's hit
    # rate is typically 0.0 here, so a ratio would be unbounded
    _emit("gpt2_serving_kvspill_hit_rate", on["hit_rate"], "fraction",
          round(1.0 + on["hit_rate"] - off["hit_rate"], 3),
          extras={"off_hit_rate": off["hit_rate"]})
    _emit("gpt2_serving_kvspill_reprefill_tokens", on["prefill_tokens"],
          "tokens", prefill_ratio,
          extras={"off_prefill_tokens": off["prefill_tokens"]})
    # gate lane: tracing + SLO accounting must stay out of the serving
    # hot path — additive vs_baseline against the 2% budget
    _emit("gpt2_serving_kvspill_obs_overhead", obs_overhead, "fraction",
          round(1.0 + obs_overhead, 4),
          extras={"budget": 0.02,
                  "goodput_traced": round(float(g_traced), 2),
                  "goodput_plain": round(float(g_plain), 2),
                  "order": "rotated x3 per arm, best-of basis"})
    ok = (working_set_pages >= 3 * budget_pages
          and goodput_ratio >= 1.3
          and on["prefill_tokens"] < off["prefill_tokens"]
          and mismatches == 0
          and on["kv_spill_pages"] >= 1
          and on["kv_pagein_pages"] >= 1
          and on["steady_state_compiles"] == 0
          and off["steady_state_compiles"] == 0
          and not on["audit_leaks"] and not off["audit_leaks"]
          and on["finished"] == on["requests"]
          and off["finished"] == off["requests"]
          and obs_overhead < 0.02
          and spilled.get("requests", 0) > 0
          and spilled.get("phase_share", {}).get("host_pagein", 0) > 0)
    return 0 if ok else 1


def bench_gpt2_serving_tp():
    """Tensor-parallel serving A/B: the SAME Poisson stream served by
    a tp=1 engine and a tp=N engine (head-wise shard_map over the
    serving tp mesh; docs/SERVING.md "Tensor-parallel serving"), on a
    forced multi-device CPU mesh when no real mesh is present (main()
    injects --xla_force_host_platform_device_count for this workload).
    The headline is tokens/sec/CHIP — goodput divided by shard count,
    the number that transfers to a real mesh. On the CPU lane shards
    time-slice the same host cores, so this round is a correctness
    harness, not a speedup claim: the gates are the contract, not the
    ratio. Pass criteria: ZERO greedy token mismatches tp=N vs tp=1
    (the committed bit-exactness contract — per-head math is
    head-independent and the single psum per projection reassembles
    identical logits up to ~1e-6 reassociation noise, which greedy
    argmax must not see on these streams), zero steady-state compiles
    in BOTH engines (shard count is a construction-time mode, never a
    shape axis — a tp=N engine owns the same two programs a tp=1
    engine does), clean page audits, every request finished, and the
    /statusz sharding block reporting the expected shard count.
    Sampled requests ride the same stream; their exact-match rate is
    reported (not gated: the Gumbel comparison may flip a near-tie on
    the reassociation noise, by design). vs_baseline is the per-chip
    goodput ratio tp=N / tp=1 (< 1 on CPU by construction)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import Request, ServingEngine

    tp_n = int(os.environ.get("BENCH_TP", 2))
    if len(jax.devices()) < tp_n:
        _emit("gpt2_serving_tp_tokens_per_sec_per_chip", 0.0,
              "tokens/sec/chip", 0.0,
              error=f"need {tp_n} devices, have {len(jax.devices())}; "
                    "set XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count={tp_n}")
        return 1
    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    32 if on_tpu else 20))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 0))  # req/s; 0=open
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 96
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 256, 1024
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 4, 128
        max_len, page = 128, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    def mk_requests(n, id0):
        rng = np.random.default_rng(23)
        out = []
        for i in range(n):
            out.append(Request(
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(p_lo, p_hi + 1))).tolist(),
                int(rng.integers(o_lo, o_hi + 1)),
                do_sample=bool(i % 2), temperature=0.8, top_k=40,
                seed=i, request_id=id0 + i))
        return out

    def run_config(tag, tp):
        # both configs pin the same chunk grid — the comparison varies
        # shard count and nothing else
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, chunk_tokens=page,
                            prefill_chunk_budget=slots * page, tp=tp)
        eng.serve([Request(list(range(1, page + 1)), 2,
                           request_id=f"{tag}-warm-greedy")])
        eng.serve([Request(list(range(1, page + 1)), 2, do_sample=True,
                           seed=0, request_id=f"{tag}-warm-sampled")])
        eng.mark_warm()
        c0 = _engine_compiles(eng._eid)
        eng.reset_stats()

        reqs = mk_requests(n_requests, id0=1000)
        rng = np.random.default_rng(13)
        gaps = rng.exponential(1.0 / rate, n_requests) if rate > 0 \
            else np.zeros(n_requests)
        arrivals = np.cumsum(gaps)
        t0 = time.perf_counter()
        pending = list(zip(arrivals, reqs))
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                eng.submit(pending.pop(0)[1])
            if eng.has_work:
                eng.step()
            elif pending:
                time.sleep(min(pending[0][0] - now, 0.01))
        dt = time.perf_counter() - t0

        fin = [r for r in reqs if r.status == "finished"]
        tokens = sum(len(r.output_tokens) for r in fin)
        goodput = tokens / dt
        return {
            "tp": tp,
            "goodput_tokens_per_sec": round(goodput, 2),
            "tokens_per_sec_per_chip": round(goodput / tp, 2),
            "makespan_s": round(dt, 3),
            "finished": len(fin), "requests": n_requests,
            "steady_state_compiles": _engine_compiles(eng._eid) - c0,
            "audit_leaks": len(eng.audit_pages()),
            "sharding": eng._statusz()["sharding"],
            "tp_shards_gauge": eng.stats["tp_shards"],
            "outputs": {r.id: (bool(r.do_sample), list(r.output_tokens))
                        for r in reqs},
            "device_cost": _device_cost_extras(eng._eid),
        }

    base = run_config("tp1", 1)
    shard = run_config(f"tp{tp_n}", tp_n)

    out_b, out_s = base.pop("outputs"), shard.pop("outputs")
    g_mismatch = g_total = s_exact = s_total = 0
    for rid, (sampled, toks_b) in out_b.items():
        toks_s = out_s[rid][1]
        if sampled:
            s_total += 1
            s_exact += int(toks_b == toks_s)
        else:
            g_total += 1
            g_mismatch += int(toks_b != toks_s)

    per_chip_ratio = round(shard["tokens_per_sec_per_chip"]
                           / max(base["tokens_per_sec_per_chip"],
                                 1e-9), 3)
    extras = {
        "tp": tp_n,
        "greedy_mismatches": g_mismatch,
        "greedy_streams": g_total,
        "sampled_exact": f"{s_exact}/{s_total}",
        "tp1": base, f"tp{tp_n}": shard,
        "slots": slots,
        "prompt_lens": f"U[{p_lo},{p_hi}]",
        "output_lens": f"U[{o_lo},{o_hi}]",
        "arrivals": "open-loop" if rate == 0 else f"poisson({rate}/s)",
        "params": cfg.num_params(),
        "device": str(dev.device_kind),
        "devices": len(jax.devices()),
        "baseline": "the same stream on a tp=1 engine, per-chip "
                    "(CPU shards time-slice one host: correctness "
                    "lane, not a speedup claim)",
    }
    _emit("gpt2_serving_tp_tokens_per_sec_per_chip",
          shard["tokens_per_sec_per_chip"], "tokens/sec/chip",
          per_chip_ratio, extras=extras)
    _emit("gpt2_serving_tp_greedy_mismatches", g_mismatch, "tokens",
          0.0, extras={"greedy_streams": g_total, "tp": tp_n})
    ok = (g_mismatch == 0
          and base["steady_state_compiles"] == 0
          and shard["steady_state_compiles"] == 0
          and not base["audit_leaks"] and not shard["audit_leaks"]
          and base["finished"] == n_requests
          and shard["finished"] == n_requests
          and shard["sharding"]["tp_shards"] == tp_n
          and base["sharding"] is None)
    return 0 if ok else 1


def bench_gpt2_serving_http():
    """HTTP ingress overhead + robustness: the SAME greedy Poisson
    stream served (A) in-process — requests submitted straight into a
    ServingEngine stepped by this thread — and (B) through a live
    ServingFrontend over real sockets, one client thread per request.
    After phase B a burst of seeded disconnect clients hangs up
    mid-stream on the same frontend (the recovery count). Pass
    criteria: ZERO greedy mismatches between the offline reference and
    every stream both phases produced, every disconnect detected and
    cancelled with clean audits, zero steady-state compiles in either
    phase, and ingress overhead in bounds: HTTP makespan within
    BENCH_HTTP_OVERHEAD_MAX (default 5%) of in-process OR added cost
    under BENCH_HTTP_INGRESS_MS_MAX (default 5) milliseconds per
    request. The fractional gate is the meaningful one at paper scale,
    where per-request service runs seconds; the absolute per-request
    bound keeps the CPU smoke config (~4 ms of service per request)
    from failing on fixed socket/GIL costs that are noise at scale.
    Reports client-observable TTFB p50/p99 (request sent -> first
    tokens SSE event) against the engine's own TTFT p50/p99."""
    import json as _json
    import socket
    import threading

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
    from mxnet_tpu.serving import (Request, ServingEngine,
                                   ServingFrontend)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 8))
    n_requests = int(os.environ.get("BENCH_HTTP_REQUESTS",
                                    64 if on_tpu else 48))
    n_disc = int(os.environ.get("BENCH_HTTP_DISCONNECTS", 8))
    overhead_max = float(os.environ.get("BENCH_HTTP_OVERHEAD_MAX", 0.05))
    ingress_ms_max = float(os.environ.get("BENCH_HTTP_INGRESS_MS_MAX",
                                          5.0))
    cfg = gpt2_774m_config(dtype="bfloat16" if on_tpu else "float32",
                           dropout=0.0, attention_dropout=0.0)
    max_len, page = 1024, 64
    p_lo, p_hi, o_lo, o_hi = 16, 128, 32, 128
    if not on_tpu:  # CPU smoke config
        cfg.vocab_size, cfg.units, cfg.hidden_size = 512, 64, 256
        cfg.num_layers, cfg.num_heads, cfg.max_length = 2, 2, 64
        max_len, page = 64, 8
        p_lo, p_hi, o_lo, o_hi = 2, 12, 4, 12
        slots, block = min(slots, 4), min(block, 4)

    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    rng = np.random.default_rng(41)
    bodies = [{"prompt": rng.integers(
                   0, cfg.vocab_size,
                   int(rng.integers(p_lo, p_hi + 1))).tolist(),
               "max_new_tokens": int(rng.integers(o_lo, o_hi + 1))}
              for _ in range(n_requests)]

    def new_engine():
        eng = ServingEngine(net, num_slots=slots, max_length=max_len,
                            page_size=page, decode_block=block)
        # warm every prefill bucket, including the ones only a
        # re-prefill of prompt+emitted can land in
        eng.serve([Request(list(range(1, b + 1)), 2,
                           request_id=f"w{b}")
                   for b in range(page, min(p_hi + o_hi + page, max_len),
                                  page)])
        eng.mark_warm()
        eng.reset_stats()
        return eng

    def ttft_ms(eng, q):
        kid = telemetry.get("serving_ttft_seconds").labels(eng._eid)
        return round(kid.percentile(q) * 1e3, 2) if kid.count else None

    # offline greedy reference + closed-loop capacity probe
    ref_eng = new_engine()
    refs = [Request(b["prompt"], b["max_new_tokens"],
                    request_id=f"ref-{i}")
            for i, b in enumerate(bodies)]
    t0 = time.perf_counter()
    ref_eng.serve(refs)
    capacity_rps = n_requests / (time.perf_counter() - t0)
    assert all(r.status == "finished" for r in refs)
    reference = [list(r.output_tokens) for r in refs]
    rate = 0.8 * capacity_rps       # below the knee: the comparison
                                    # should expose ingress cost, not
                                    # shared queueing delay
    arr = np.cumsum(np.random.default_rng(43).exponential(
        1.0 / rate, n_requests))

    # phase A: the same open-loop stream, in-process
    eng_a = new_engine()
    ca0 = _engine_compiles(eng_a._eid)
    reqs_a = [Request(b["prompt"], b["max_new_tokens"],
                      request_id=f"a-{i}")
              for i, b in enumerate(bodies)]
    t0 = time.perf_counter()
    pending = list(zip(arr, reqs_a))
    while pending or eng_a.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng_a.submit(pending.pop(0)[1])
        if eng_a.has_work:
            eng_a.step()
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.005))
    makespan_a = time.perf_counter() - t0
    mismatch_a = sum(list(r.output_tokens) != reference[i]
                     for i, r in enumerate(reqs_a))

    def sse_tokens(raw):
        toks = []
        body = raw.partition(b"\r\n\r\n")[2].decode(errors="replace")
        for block_ in body.split("\n\n"):
            ev = data = None
            for line in block_.strip().splitlines():
                if line.startswith("event: "):
                    ev = line[7:]
                elif line.startswith("data: "):
                    data = line[6:]
            if ev == "tokens" and data:
                toks.extend(_json.loads(data)["tokens"])
        return toks

    # phase B: the same open-loop stream, over real sockets
    eng_b = new_engine()
    cb0 = _engine_compiles(eng_b._eid)
    fe = ServingFrontend(eng_b, keepalive_s=0.05, step_idle_s=0.002)
    out = {}

    def client(i, body, rid, cutoff_first_token=False):
        payload = _json.dumps(dict(body, request_id=rid)).encode()
        t_send = time.perf_counter()
        raw, ttfb = b"", None
        sock = socket.create_connection((fe.host, fe.port), timeout=600)
        try:
            sock.sendall(b"POST /v1/generate HTTP/1.0\r\n"
                         b"Content-Type: application/json\r\n"
                         b"Content-Length: " + str(len(payload)).encode()
                         + b"\r\n\r\n" + payload)
            while True:
                chunk = sock.recv(65536)
                if not chunk:
                    break
                raw += chunk
                if ttfb is None and b"event: tokens" in raw:
                    ttfb = time.perf_counter() - t_send
                    if cutoff_first_token:
                        break       # hang up mid-stream, no goodbye
        finally:
            sock.close()
        out[rid] = (ttfb, raw, time.perf_counter() - t_send)

    try:
        threads = []
        t0 = time.perf_counter()
        for i, (at, body) in enumerate(zip(arr, bodies)):
            lag = at - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            th = threading.Thread(target=client,
                                  args=(i, body, f"b-{i}"), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        while eng_b.has_work or fe.stats["active_streams"]:
            time.sleep(0.002)
        makespan_b = time.perf_counter() - t0
        mismatch_b = sum(
            sse_tokens(out[f"b-{i}"][1]) != reference[i]
            for i in range(n_requests))
        ttfbs = np.array([out[f"b-{i}"][0] for i in range(n_requests)
                          if out[f"b-{i}"][0] is not None])

        # disconnect burst: the recovery count on the same frontend
        disc0 = eng_b.stats["requests_cancelled"]
        dthreads = []
        for i in range(n_disc):
            body = {"prompt": bodies[i]["prompt"],
                    "max_new_tokens": min(2 * o_hi,
                                          max_len - p_hi - page)}
            th = threading.Thread(
                target=client, args=(i, body, f"d-{i}", True),
                daemon=True)
            th.start()
            dthreads.append(th)
        for th in dthreads:
            th.join(timeout=600)
        deadline = time.time() + 120
        while time.time() < deadline:
            if not eng_b.has_work and fe.stats["active_streams"] == 0 \
                    and fe._cmd_q.empty():
                break
            time.sleep(0.01)
        recovered = eng_b.stats["requests_cancelled"] - disc0
        fstats = fe.stats
    finally:
        fe.close()

    overhead = makespan_b / max(makespan_a, 1e-9) - 1.0
    ingress_ms = (makespan_b - makespan_a) * 1e3 / n_requests
    steady_a = _engine_compiles(eng_a._eid) - ca0
    steady_b = _engine_compiles(eng_b._eid) - cb0
    leaks = (len(eng_a.audit_pages()) + len(eng_b.audit_pages())
             + len(eng_a.audit_adapters()) + len(eng_b.audit_adapters()))
    ttfb_p50 = round(float(np.percentile(ttfbs, 50)) * 1e3, 2) \
        if ttfbs.size else None
    ttfb_p99 = round(float(np.percentile(ttfbs, 99)) * 1e3, 2) \
        if ttfbs.size else None
    _emit("gpt2_serving_http_ttfb_p99_ms", ttfb_p99 or 0.0, "ms",
          round(1.0 + overhead, 4), extras={
              "ttfb_p50_ms": ttfb_p50,
              "ttfb_p99_ms": ttfb_p99,
              "ttft_inproc_p50_ms": ttft_ms(eng_a, 50),
              "ttft_inproc_p99_ms": ttft_ms(eng_a, 99),
              "ttft_http_p50_ms": ttft_ms(eng_b, 50),
              "ttft_http_p99_ms": ttft_ms(eng_b, 99),
              "ingress_overhead": round(overhead, 4),
              "ingress_overhead_max": overhead_max,
              "ingress_ms_per_request": round(ingress_ms, 3),
              "ingress_ms_max": ingress_ms_max,
              "makespan_inproc_s": round(makespan_a, 3),
              "makespan_http_s": round(makespan_b, 3),
              "greedy_mismatches_inproc": mismatch_a,
              "greedy_mismatches_http": mismatch_b,
              "disconnect_clients": n_disc,
              "disconnects_detected": fstats["disconnects"],
              "disconnects_recovered": recovered,
              "cancels_issued": fstats["cancels_issued"],
              "cancels_noop": fstats["cancels_noop"],
              "requests_by_code": fstats["requests_by_code"],
              "steady_state_compiles_inproc": steady_a,
              "steady_state_compiles_http": steady_b,
              "audit_leaks": leaks,
              "requests": n_requests, "slots": slots,
              "decode_block": block,
              "capacity_req_per_sec": round(capacity_rps, 3),
              "offered_req_per_sec": round(rate, 3),
              "prompt_lens": f"U[{p_lo},{p_hi}]",
              "output_lens": f"U[{o_lo},{o_hi}] (greedy)",
              "arrivals": f"poisson({round(rate, 2)}/s)",
              "params": cfg.num_params(),
              "device": str(dev.device_kind),
              "baseline": "phase A above (same stream submitted "
                          "in-process, no HTTP)",
          })
    ok = (mismatch_a == 0 and mismatch_b == 0
          and fstats["disconnects"] == n_disc
          and fstats["cancels_issued"] + fstats["cancels_noop"]
          == fstats["disconnects"]
          and steady_a == 0 and steady_b == 0 and leaks == 0
          and (overhead <= overhead_max or ingress_ms <= ingress_ms_max))
    return 0 if ok else 1


def bench_longcontext():
    """Long-context attention: fwd+bwd through the blockwise flash path
    at sequence lengths whose (T, T) score matrix would not fit
    materialized (SURVEY.md §5.7 — long context is first-class). Emits
    tokens/sec for one attention layer fwd+bwd at BENCH_LONG_T."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import flash_attention_data

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    T = int(os.environ.get("BENCH_LONG_T", 8192 if on_tpu else 1024))
    B, H, D = 1, 12, 64
    steps = int(os.environ.get("BENCH_STEPS", 10)) if on_tpu else 2
    rng = np.random.default_rng(0)
    dt_ = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), dt_)
    k = jnp.asarray(rng.standard_normal((B, H, T, D)), dt_)
    v = jnp.asarray(rng.standard_normal((B, H, T, D)), dt_)

    @jax.jit
    def fwd_bwd(q, k, v):
        def f(q, k, v):
            return flash_attention_data(q, k, v, causal=True).astype(
                jnp.float32).sum()
        l, g = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        return l, g

    def sync(l, g):
        # fetching the loss alone would NOT force the backward (async
        # dispatch; the loss is produced before the cotangents) — fetch a
        # gradient element too
        float(l)
        float(g[0][0, 0, 0, 0])

    out = fwd_bwd(q, k, v)
    sync(*out)  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd_bwd(q, k, v)
    sync(*out)
    dt = (time.perf_counter() - t0) / steps
    # causal attention fwd+bwd ≈ 3.5 * 4 * B*H*T^2*D flops (half masked)
    flops = 3.5 * 2 * B * H * T * T * D
    _emit("longcontext_attention_tokens_per_sec", round(B * T / dt, 1),
          "tokens/sec", 0.0, extras={
              "seq_len": T, "heads": H, "head_dim": D,
              "step_time_ms": round(dt * 1e3, 2),
              "achieved_tflops": round(flops / dt / 1e12, 2),
              "kernel": "flash (blockwise, O(T) memory)",
              "device": str(dev.device_kind),
              "baseline": "reference max practical seq len was 512-1024 "
                          "(SURVEY.md §5.7: it has no long-context path)"})
    return 0


def bench_decode():
    """Data-pipeline decode throughput (img/sec through ImageRecordIter's
    native libjpeg path — the reference's iter_image_recordio_2.cc role,
    SURVEY.md §2.5). Synthesizes a RecordIO pack of JPEGs, then measures
    end-to-end decode+resize+batch throughput."""
    import tempfile
    import cv2
    import mxnet_tpu as mx
    from mxnet_tpu.io import ImageRecordIter, MXRecordIO, IRHeader, pack

    n_images = int(os.environ.get("BENCH_DECODE_IMAGES", 512))
    size = int(os.environ.get("BENCH_DECODE_SIZE", 480))
    out_size = 224
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "bench.rec")
        rec = MXRecordIO(path, "w")
        img = rng.integers(0, 255, (size, size, 3)).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img)
        payload = pack(IRHeader(0, 0.0, 0, 0), bytes(buf.tobytes()))
        for i in range(n_images):
            rec.write(payload)
        rec.close()
        it = ImageRecordIter(path, batch_size=32,
                             data_shape=(3, out_size, out_size),
                             to_device=False)
        for _ in it:  # warmup epoch (thread pool spin-up)
            pass
        t0 = time.perf_counter()
        n = 0
        for data, label in it:
            n += data.shape[0]
        dt = time.perf_counter() - t0
    native = it._decoder.is_native
    _emit("decode_pipeline_img_per_sec", round(n / dt, 1), "img/sec",
          0.0, extras={
              "images": n, "src_size": size, "out_size": out_size,
              "threads": it._threads, "native_decoder": native,
              "baseline": "none recorded (reference pipeline not runnable "
                          "here)"})
    return 0


def bench_gpt2_serving_disagg():
    """Disaggregated prefill/decode vs a mixed fleet, across REAL
    worker subprocesses: the SAME seeded Poisson stream of greedy
    requests served by (a) two mixed workers, (b) one prefill + one
    decode worker shipping the KV page payload at first token, and
    (c) the same disaggregated pair with payload shipping OFF (the
    replay-restart ablation). Every arm crosses the wire format
    through a FleetRouter; TTFT is client-observed (submit -> first
    token out of the stream), and the disaggregated arms report the
    `handoff` TTFT phase (prefill export stamp -> decode adoption
    ack) every request must carry. Pass criteria: ZERO greedy
    mismatches between the disaggregated arms and the mixed arm (and
    vs an offline single engine on CPU hosts), zero lost requests,
    steady_state_compiles == 0 on every worker in every arm, and a
    handoff phase on every disaggregated request. vs_baseline on the
    headline metric is mixed_ttft_p99 / disagg_ttft_p99 — what
    splitting the roles costs (or saves) at the tail.

    A fourth block runs the fleet-observability A/B: one disaggregated
    fleet, a warmup stream then eight rotated streams with the
    FleetCollector (scrape/merge + fleet SLO + trace assembly) off/on
    — the collector must cost the serving path < 2% (best-of
    peak-window basis, robust to shared-host stalls), with zero greedy
    mismatches and zero steady-state compiles, and it contributes the
    `fleet_tokens_per_sec_per_chip` headline at the measured fleet
    TTFT p99."""
    import threading

    import jax
    from mxnet_tpu.serving import Request, TokenStream
    from mxnet_tpu.serving.fleet import (FleetRouter, WorkerClient,
                                         spawn_fleet)
    from mxnet_tpu.serving.fleet.worker import build_engine, warm_engine

    # worker subprocesses run on the CPU (spawn_worker gives them the
    # platform explicitly — this parent may hold the chip) with
    # threefry; the local reference must build the SAME weights (rbg —
    # main()'s TPU dropout choice — draws different init bits)
    prng_before = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    try:
        dev = jax.devices()[0]
        on_cpu = dev.platform == "cpu"
        n_requests = int(os.environ.get("BENCH_DISAGG_REQUESTS", 24))
        slots, block, page, max_len = 2, 4, 8, 64
        spec = {
            "config": dict(vocab_size=97, units=32, num_layers=2,
                           num_heads=2, max_length=max_len,
                           dropout=0.0, attention_dropout=0.0),
            "seed": 3, "init_std": 0.05,
            "engine": dict(num_slots=slots, max_length=max_len,
                           page_size=page, decode_block=block,
                           attn_impl="xla"),
        }
        rng = np.random.default_rng(41)
        reqs_spec = [(rng.integers(1, spec["config"]["vocab_size"],
                                   int(rng.integers(3, 13))).tolist(),
                      int(rng.integers(8, 17)))
                     for _ in range(n_requests)]

        # offline reference + capacity probe (CPU hosts only: the
        # workers run on CPU, so a TPU-built reference would not be
        # bit-comparable; the disagg-vs-mixed cross-check below is
        # device-consistent everywhere)
        reference = None
        rate = float(os.environ.get("BENCH_DISAGG_RATE", 0.0))
        if on_cpu:
            _n, ref_cfg, ref_eng = build_engine(spec)
            warm_engine(ref_eng, ref_cfg)
            refs = [Request(list(p), m, request_id=f"ref-{i}")
                    for i, (p, m) in enumerate(reqs_spec)]
            t0 = time.perf_counter()
            ref_eng.serve(refs)
            capacity_rps = n_requests / (time.perf_counter() - t0)
            assert all(r.status == "finished" for r in refs)
            reference = {i: list(r.output_tokens)
                         for i, r in enumerate(refs)}
            if not rate:
                # below the knee: the tail should expose the handoff
                # hop, not shared queueing delay
                rate = 0.7 * capacity_rps
        rate = rate or 6.0

        def run_arm(tag, roles, ship=True):
            procs = spawn_fleet(spec, roles=roles, ship_payload=ship)
            router = None
            try:
                router = FleetRouter(procs.urls)
                reqs = [Request(list(p), m, request_id=f"{tag}-{i}")
                        for i, (p, m) in enumerate(reqs_spec)]
                t_submit, t_first = {}, {}

                def reader(r):
                    while True:
                        toks, closed = r.stream.take(timeout=10.0)
                        if toks and r.id not in t_first:
                            t_first[r.id] = time.perf_counter()
                        if closed is not None:
                            return

                arr = np.cumsum(np.random.default_rng(43).exponential(
                    1.0 / rate, n_requests))
                threads = []
                t0 = time.perf_counter()
                for a, r in zip(arr, reqs):
                    lag = a - (time.perf_counter() - t0)
                    if lag > 0:
                        time.sleep(lag)
                    r.stream = TokenStream(capacity=2 * max_len)
                    t_submit[r.id] = time.perf_counter()
                    router.submit(r)
                    th = threading.Thread(target=reader, args=(r,),
                                          daemon=True)
                    th.start()
                    threads.append(th)
                for r in reqs:
                    router.result(r, timeout=300)
                makespan = time.perf_counter() - t0
                for th in threads:
                    th.join(timeout=60)
                wstats = [WorkerClient(w.url).stats()
                          for w in procs.workers]
            finally:
                if router is not None:
                    router.close()
                procs.close()
            ttfts = [(t_first[r.id] - t_submit[r.id]) * 1e3
                     for r in reqs if r.id in t_first]
            hand = [float(r.phases["handoff"]) * 1e3 for r in reqs
                    if "handoff" in (r.phases or {})]
            out = {i: list(r.output_tokens)
                   for i, r in enumerate(reqs)}
            tokens = sum(len(v) for v in out.values())
            pct = lambda xs, q: (round(float(np.percentile(xs, q)), 2)
                                 if xs else None)  # noqa: E731
            return out, {
                "roles": list(roles), "ship_payload": ship,
                "finished": sum(r.status == "finished" for r in reqs),
                "ttft_p50_ms": pct(ttfts, 50),
                "ttft_p99_ms": pct(ttfts, 99),
                "handoff_p50_ms": pct(hand, 50),
                "handoff_p99_ms": pct(hand, 99),
                "handoff_phase_requests": len(hand),
                "goodput_tokens_per_sec": round(tokens / makespan, 1),
                "makespan_s": round(makespan, 3),
                "workers": [{
                    "role": s["role"],
                    "handoffs": s["handoffs"],
                    "steady_state_compiles":
                        s["stats"]["steady_state_compiles"],
                } for s in wstats],
            }

        mixed_out, mixed = run_arm("mix", ("mixed", "mixed"))
        dis_out, disagg = run_arm("dis", ("prefill", "decode"))
        rep_out, replay = run_arm("rep", ("prefill", "decode"),
                                  ship=False)

        # -- fleet-collector A/B (rotated order, best-of basis) ----------
        # ONE disaggregated fleet; a discarded warmup stream, then
        # EIGHT rotated streams (FleetCollector off / on, palindrome
        # order so both conditions sit at the same mean position under
        # linear machine drift). Each stream is a CLOSED LOOP with a
        # bounded in-flight window — the fleet stays saturated, so the
        # measurement is throughput capacity (the number the
        # collector's scrape/merge loop would actually perturb), while
        # the window keeps the workers' control plane responsive (an
        # unbounded burst piles blocking prefill RPCs onto a worker
        # until its health probes time out and the watchdog
        # false-positives a death). Per arm the estimator is the PEAK
        # SUSTAINED WINDOW — the best tokens/sec over any ~48
        # consecutive completions — then best-of across each
        # condition's arms: shared-host stalls are one-sided (they
        # only slow you down) and multi-second, so they poison the
        # windows they land in and nothing else, while every ~1 s
        # window still contains a scrape at the default cadence, so
        # the traced condition cannot dodge the collector's cost. The
        # gate proves the whole observability plane (metrics merge +
        # timeline pulls + SLO feed) stays off the serving path within
        # the 2% budget.
        ab_order = (False,                                    # warmup
                    True, False, False, True,
                    True, False, False, True)
        n_ab = 6 * n_requests
        ab_window = 4 * slots
        ab_peak, ab_out, fleet_view = [], {}, None
        ab_procs = spawn_fleet(spec, roles=("prefill", "decode"))
        try:
            ab_router = FleetRouter(ab_procs.urls)
            try:
                for i, instrumented in enumerate(ab_order):
                    coll = (ab_router.observe(interval_s=1.0)
                            if instrumented else None)
                    done, done_t, inflight, idx = [], [], [], 0
                    while idx < n_ab or inflight:
                        while idx < n_ab and len(inflight) < ab_window:
                            p, m = reqs_spec[idx % n_requests]
                            r = Request(list(p), m,
                                        request_id=f"ab{i}-{idx}")
                            r.stream = TokenStream(capacity=2 * max_len)
                            ab_router.submit(r)
                            inflight.append(r)
                            idx += 1
                        r = inflight.pop(0)
                        ab_router.result(r, timeout=300)
                        done.append(r)
                        done_t.append(time.perf_counter())
                    toks = [len(r.output_tokens) for r in done]
                    K = min(48, len(done))
                    peak = 0.0
                    for a in range(len(done) - K + 1):
                        dt = done_t[a + K - 1] - done_t[a]
                        if dt > 0:
                            peak = max(peak,
                                       sum(toks[a + 1:a + K]) / dt)
                    ab_peak.append(peak)
                    ab_out[i] = {j: list(r.output_tokens)
                                 for j, r in enumerate(done)}
                    if coll is not None:
                        coll.scrape()
                        fleet_view = coll.fleetz()
                        coll.close()
                        ab_router._collector = None
                    time.sleep(0.5)     # let a CFS quota bucket refill
                ab_wstats = [WorkerClient(w.url).stats()
                             for w in ab_procs.workers]
            finally:
                ab_router.close()
        finally:
            ab_procs.close()
    finally:
        jax.config.update("jax_default_prng_impl", prng_before)

    mismatches = sum(dis_out[i] != mixed_out[i]
                     for i in range(n_requests))
    mismatches += sum(rep_out[i] != mixed_out[i]
                      for i in range(n_requests))
    ref_mismatches = None
    if reference is not None:
        ref_mismatches = sum(reference[i] != mixed_out[i]
                             for i in range(n_requests))
    steady = sum(w["steady_state_compiles"]
                 for arm in (mixed, disagg, replay)
                 for w in arm["workers"])
    lost = sum(n_requests - arm["finished"]
               for arm in (mixed, disagg, replay))

    ratio = mixed["ttft_p99_ms"] / max(disagg["ttft_p99_ms"], 1e-9)
    extras = {
        "mixed_2workers": mixed,
        "disagg_prefill_decode": disagg,
        "disagg_replay_fallback": replay,
        "greedy_mismatches_vs_mixed": mismatches,
        "greedy_mismatches_vs_offline": ref_mismatches,
        "steady_state_compiles_total": steady,
        "lost_requests": lost,
        "requests": n_requests,
        "arrivals": f"poisson({round(rate, 2)}/s), seed 43",
        "prompt_lens": "U[3,12]", "output_lens": "U[8,16]",
        "slots": slots, "decode_block": block, "page_size": page,
        "device": str(dev.device_kind),
        "workers_on": "cpu subprocesses",
        "baseline": "the 2-worker mixed fleet arm above (same stream, "
                    "same wire, no role split)",
    }
    _emit("gpt2_serving_disagg_ttft_p99_ms", disagg["ttft_p99_ms"],
          "ms", round(ratio, 4), extras=extras)
    _emit("gpt2_serving_disagg_handoff_p99_ms",
          disagg["handoff_p99_ms"], "ms", 0.0,
          extras={"handoff_p50_ms": disagg["handoff_p50_ms"],
                  "replay_fallback_handoff_p99_ms":
                      replay["handoff_p99_ms"],
                  "handoff_phase_requests":
                      disagg["handoff_phase_requests"]})
    _emit("gpt2_serving_disagg_greedy_mismatches", mismatches,
          "tokens", 0.0,
          extras={"vs": "2-worker mixed fleet arm",
                  "vs_offline_engine": ref_mismatches})

    # -- the fleet observability plane's own lanes -----------------------
    # arm 0 is the discarded warmup; best-of peak-window per condition
    g_plain = max(g for en, g in zip(ab_order[1:], ab_peak[1:])
                  if not en)
    g_traced = max(g for en, g in zip(ab_order[1:], ab_peak[1:]) if en)
    obs_overhead = round(float(g_plain) / max(float(g_traced), 1e-9)
                         - 1.0, 4)
    ab_mismatches = sum(ab_out[i][j] != mixed_out[j % n_requests]
                        for i in ab_out for j in ab_out[i])
    ab_steady = sum(s["stats"]["steady_state_compiles"]
                    for s in ab_wstats)
    fv = (fleet_view or {}).get("fleet", {})
    chips = max(int(fv.get("chips") or len(ab_wstats)), 1)
    per_chip = round(float(g_traced) / chips, 1)
    # headline: fleet tokens/sec/chip the collector-on fleet sustained,
    # reported AT the fleet-merged TTFT p99 it was achieved at
    # (higher-better by name for bench_compare); vs_baseline is
    # traced/plain goodput — what observing the fleet costs the number
    # it reports
    _emit("gpt2_serving_disagg_fleet_tokens_per_sec_per_chip", per_chip,
          "tokens/sec/chip",
          round(float(g_traced) / max(float(g_plain), 1e-9), 4),
          extras={"chips": chips,
                  "at_ttft_p99_ms": fv.get("ttft_p99_ms"),
                  "fleet_tokens_per_sec": round(float(g_traced), 1),
                  "collector_gauge_tokens_per_sec_per_chip":
                      fv.get("tokens_per_sec_per_chip"),
                  "workers_stale": fv.get("workers_stale"),
                  "greedy_mismatches_vs_mixed": ab_mismatches,
                  "steady_state_compiles": ab_steady,
                  "arms": [round(float(g), 1) for g in ab_peak],
                  "order": "warmup + collector off/on x4 each, "
                           "palindrome rotation, best-of peak-window"})
    # gate lane: the collector must stay off the serving hot path —
    # additive vs_baseline against the 2% budget
    _emit("gpt2_serving_disagg_obs_overhead", obs_overhead, "fraction",
          round(1.0 + obs_overhead, 4),
          extras={"budget": 0.02,
                  "goodput_traced": round(float(g_traced), 2),
                  "goodput_plain": round(float(g_plain), 2),
                  "scrape_interval_s": 1.0,
                  "order": "warmup + rotated x4 per arm, best-of "
                           "peak-window basis"})
    # every prompt crossed the prefill->decode seam in BOTH disagg
    # arms (the prefill worker's handoff counter); the "handoff" TTFT
    # phase exists only where a KV payload was adopted — the replay
    # fallback restarts from kv_history and records no hop, so its
    # coverage gate is the worker counter, not the phase
    crossed = {tag: sum(w["handoffs"] for w in arm["workers"]
                        if w["role"] == "prefill")
               for tag, arm in (("disagg", disagg), ("replay", replay))}
    ok = (mismatches == 0 and not ref_mismatches and lost == 0
          and steady == 0
          and disagg["handoff_phase_requests"] == n_requests
          and crossed["disagg"] == n_requests
          and crossed["replay"] == n_requests
          and obs_overhead < 0.02
          and ab_mismatches == 0 and ab_steady == 0)
    return 0 if ok else 1


def main():
    workload = os.environ.get("BENCH_WORKLOAD", "both")
    if "--workload" in sys.argv:
        workload = sys.argv[sys.argv.index("--workload") + 1]
    if (workload in ("serving_tp", "tp", "tensor_parallel",
                     "gpt2_serving_tp")
            and "jax" not in sys.modules
            and "host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # the tp A/B needs a multi-device mesh; on a CPU host that
        # means forcing virtual devices BEFORE jax initialises (the
        # flag only affects the host platform — harmless on TPU)
        n = max(int(os.environ.get("BENCH_TP", 2)), 2)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")
    import jax
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    # rbg (hardware RNG) for dropout masks: threefry mask generation costs
    # ~35% of step time on TPU; rbg is the standard TPU training choice
    if os.environ.get("JAX_DEFAULT_PRNG_IMPL") is None:
        try:
            jax.config.update("jax_default_prng_impl", "rbg")
        except Exception:
            pass
    if workload == "both":
        # resnet first, BERT LAST — the driver tail-parses the last line
        # and must keep getting the north-star metric
        try:
            rc_r = bench_resnet50()
        except Exception as e:
            traceback.print_exc()
            _emit("resnet50_v1b_img_per_sec_per_chip", 0.0, "img/sec", 0.0,
                  error=str(e)[:200])
            rc_r = 1
        try:
            rc_b = bench_bert()
        except Exception as e:
            # the LAST line must always be the BERT record — an unhandled
            # crash here would leave the resnet line for the tail-parse
            traceback.print_exc()
            _emit("bert_base_mlm_mfu", 0.0, "fraction", 0.0,
                  error=str(e)[:200])
            rc_b = 1
        return rc_b or rc_r
    if workload in ("bert", "bert_base"):
        return bench_bert()
    if workload in ("bert_large",):
        return bench_bert(large=True)
    if workload in ("resnet", "resnet50", "resnet50_v1b"):
        return bench_resnet50()
    if workload in ("gpt2", "gpt2_decode", "gpt2_774m"):
        return bench_gpt2_decode()
    if workload in ("serving", "gpt2_serving"):
        return bench_gpt2_serving()
    if workload in ("serving_prefix", "prefix_reuse",
                    "gpt2_serving_prefix_reuse"):
        return bench_gpt2_serving_prefix_reuse()
    if workload in ("serving_spec", "speculative",
                    "gpt2_serving_speculative"):
        return bench_gpt2_serving_speculative()
    if workload in ("serving_introspection", "introspection", "trace",
                    "gpt2_serving_introspection"):
        return bench_gpt2_serving_introspection()
    if workload in ("serving_overload", "overload", "shedding",
                    "gpt2_serving_overload"):
        return bench_gpt2_serving_overload()
    if workload in ("serving_router", "router", "failover",
                    "gpt2_serving_router"):
        return bench_gpt2_serving_router()
    if workload in ("serving_multitenant", "multitenant", "lora",
                    "gpt2_serving_multitenant"):
        return bench_gpt2_serving_multitenant()
    if workload in ("serving_chunked", "chunked", "chunked_prefill",
                    "gpt2_serving_chunked"):
        return bench_gpt2_serving_chunked()
    if workload in ("serving_quantkv", "quantkv", "int8_kv",
                    "gpt2_serving_quantkv"):
        return bench_gpt2_serving_quantkv()
    if workload in ("serving_w8", "w8", "weight_quant",
                    "gpt2_serving_w8"):
        return bench_gpt2_serving_w8()
    if workload in ("serving_kvspill", "kvspill", "kv_spill",
                    "gpt2_serving_kvspill"):
        return bench_gpt2_serving_kvspill()
    if workload in ("serving_tp", "tp", "tensor_parallel",
                    "gpt2_serving_tp"):
        return bench_gpt2_serving_tp()
    if workload in ("serving_http", "http", "frontend",
                    "gpt2_serving_http"):
        return bench_gpt2_serving_http()
    if workload in ("serving_disagg", "disagg", "prefill_decode",
                    "fleet", "gpt2_serving_disagg"):
        return bench_gpt2_serving_disagg()
    if workload == "decode":
        return bench_decode()
    if workload in ("longcontext", "long"):
        return bench_longcontext()
    _emit("unknown_workload", 0.0, "none", 0.0, error=workload)
    return 1


if __name__ == "__main__":
    sys.exit(main())
