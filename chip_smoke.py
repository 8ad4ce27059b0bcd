#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of a model each supports, with seeded random weights:

  device   a TPU is attached, the peak table knows it, versions, cache dir
  train    BERT-base MLM (bf16, B=32, T=512) through parallel.TrainStep:
           one step() and two run_steps(steps=3) on a repeated batch
  serve    GPT-2 774M (bf16) in a ServingEngine behind ServingFrontend:
           six POST /v1/generate from threads, greedy and sampled
  kernels  every Pallas entry point, compiled by libtpu, against its own
           dense reference (the page write: the row scatter) on the chip
  four_chips  (only when >= 4 chips are visible) BERT-base TrainStep over a
           dp=2 x tp=2 mesh and a tp=4 ServingEngine, with every sharded
           array checked to span four devices

One process, no children. Any failed check raises, so the exit code is
non-zero and no result line is printed; without a TPU it refuses before
doing any work. On success the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

    python3 chip_smoke.py
"""
import http.client
import json
import sys
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt, parallel as par, telemetry
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.models import (BertForMaskedLM, GPT2ForCausalLM,
                              bert_base_config, gpt2_774m_config)
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.nn import dot_product_attention as dpa
from mxnet_tpu.ops.ssm import ssd_chunk_update
from mxnet_tpu.runtime import enable_compile_cache
from mxnet_tpu.serving import Request, ServingEngine, ServingFrontend

PHASES = ("device", "train", "serve", "kernels", "four_chips")
SEED = 0
# sizes of the run; a CPU dry run of the control flow shrinks these
BERT = dict(batch=32, seq_len=512, n_masked=76, overrides={})
GPT2 = dict(slots=8, max_length=1024, page=64, new_tokens=32,
            prompt_lens=(16, 16, 128, 40, 77, 100), overrides={})
# the four-chip serve cuts depth (the contract allows it; widths are what
# tensor parallelism divides) to keep a 4x-charged compile short
GPT2_TP4_LAYERS = 12
MOSAIC = "tpu_custom_call"
PLATFORM = "tpu"


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# -- compile accounting (jax.monitoring: every backend compile in the process)

class CompileMeter:
    """Per-phase compile accounting: seconds in the backend compiler
    (where a persistent-cache hit shows), seconds tracing and lowering
    (which no cache saves), compile requests, and cache hits."""

    def __init__(self):
        self.compile_s = self.trace_s = 0.0
        self.requests = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event.startswith("/jax/core/compile/"):
            self.trace_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return np.array([self.compile_s, self.trace_s, self.requests,
                         self.cache_hits])


def _metered(wall, delta):
    return {"wall_s": round(wall, 2), "compile_s": round(delta[0], 2),
            "trace_lower_s": round(delta[1], 2),
            "compile_requests": int(delta[2]), "cache_hits": int(delta[3])}


def run_phase(name, fn, meter, report):
    before = meter.snapshot()
    t0 = time.perf_counter()
    detail = fn()
    wall = time.perf_counter() - t0
    report[name] = {**_metered(wall, meter.snapshot() - before),
                    **(detail or {})}
    print(f"[chip_smoke] {name}: {json.dumps(report[name])}", flush=True)


# -- device -----------------------------------------------------------------

def phase_device(cache_dir):
    dev = jax.devices()[0]
    check(dev.platform == PLATFORM, f"not a TPU: {dev.platform}")
    import jaxlib
    import libtpu
    pf, pb, kind = telemetry.cost.peaks()   # raises for an unknown kind
    check(pf and pb, f"no peak for {kind}")
    return {"kind": dev.device_kind, "count": len(jax.devices()),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu.__version__, "compile_cache_dir": cache_dir,
            "peak_tflops": pf / 1e12, "peak_gbps": pb / 1e9}


# -- train ------------------------------------------------------------------

def _bert_batch(cfg, batch, seq_len, n_masked):
    rng = np.random.default_rng(SEED)
    ids = mx.nd.array(rng.integers(0, cfg.vocab_size, (batch, seq_len)),
                      dtype="int32")
    tt = mx.nd.array(np.zeros((batch, seq_len)), dtype="int32")
    vl = mx.nd.array(np.full((batch,), seq_len), dtype="int32")
    perm = np.argsort(rng.random((batch, seq_len)), axis=-1)
    pos = mx.nd.array(np.sort(perm[:, :n_masked], axis=-1), dtype="int32")
    labels = mx.nd.array(rng.integers(0, cfg.vocab_size, (batch, n_masked)),
                         dtype="int32")
    return ids, tt, vl, pos, labels


def _bert(mesh=None):
    cfg = bert_base_config(dtype="bfloat16", dropout=0.1,
                           max_length=BERT["seq_len"])
    for k, v in BERT["overrides"].items():
        setattr(cfg, k, v)
    mx.rng.seed(SEED)
    net = BertForMaskedLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    net.cast("bfloat16")
    if mesh is not None:
        par.apply_sharding_rules(net, par.megatron_dense_rules(tp_axis="tp"))
    step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(),
                         opt.AdamW(learning_rate=1e-4, wd=0.01),
                         mesh=mesh, n_net_inputs=4)
    return cfg, step


def phase_train():
    cfg, step = _bert()
    batch = _bert_batch(cfg, BERT["batch"], BERT["seq_len"],
                        BERT["n_masked"])
    losses = [float(step(*batch).asscalar())]
    for _ in range(2):
        losses += [float(x) for x in
                   step.run_steps(*batch, steps=3).asnumpy()]
    check(len(losses) == 7 and np.isfinite(losses).all(),
          f"losses not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    platforms = {d.platform for a in step._param_arrays
                 for d in a.devices()}
    check(platforms == {PLATFORM}, f"parameters live on {platforms}")
    # the packed fused attention ran, not the einsum path of ops/nn.py
    check(MOSAIC in step._lowered().as_text(),
          "train step holds no Mosaic kernel")
    return {"losses": [round(x, 4) for x in losses],
            "params": cfg.num_params()}


# -- serve ------------------------------------------------------------------

def _gpt2(num_layers=None):
    cfg = gpt2_774m_config(dtype="bfloat16", dropout=0.0,
                           attention_dropout=0.0)
    if num_layers is not None:
        cfg.num_layers = num_layers
    for k, v in GPT2["overrides"].items():
        setattr(cfg, k, v)
    mx.rng.seed(SEED)
    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    net.cast("bfloat16")
    return cfg, net


def _prompts(cfg):
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in GPT2["prompt_lens"]]
    prompts[1] = list(prompts[0])   # same greedy prompt, another slot
    return prompts


def _generate(fe, body, out, i):
    """POST /v1/generate and read the SSE stream to its end."""
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=600)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        events = []
        for block in r.read().decode().split("\n\n"):
            lines = [ln for ln in block.strip().splitlines()
                     if not ln.startswith(":")]
            if len(lines) == 2:
                events.append((lines[0][len("event: "):],
                               json.loads(lines[1][len("data: "):])))
        out[i] = (r.status, events)
    finally:
        conn.close()


def _engine_program_texts(eng):
    return {("greedy" if g else "sampled"): fn._call.as_text()
            for g, fn in eng._programs.items()}


def phase_serve():
    cfg, net = _gpt2()
    page, n_new = GPT2["page"], GPT2["new_tokens"]
    # every argument the issue does not name stays at its default, so
    # attn_impl="auto" must itself resolve to the kernel
    eng = ServingEngine(net, num_slots=GPT2["slots"],
                        max_length=GPT2["max_length"], page_size=page)
    steady = []
    hook = lambda ev: steady.append(ev["program"]) if ev["steady"] else None
    telemetry.cost.add_compile_hook(hook)
    try:
        # warm-up on this thread: a greedy wave compiles one unified
        # variant, a sampled request the other
        warm = [Request(list(range(1, n + 1)), 2, request_id=f"w{n}")
                for n in (page // 2, page, 2 * page)]
        warm.append(Request(list(range(1, page + 1)), 2, do_sample=True,
                            seed=0, request_id="w-sampled"))
        done = eng.serve(warm)
        check(all(r.status == "finished" for r in done),
              f"warm-up: {[(r.id, r.status) for r in done]}")
        eng.mark_warm()
        eng.reset_stats()
        texts = _engine_program_texts(eng)
        check(set(texts) == {"greedy", "sampled"},
              f"unified variants compiled: {sorted(texts)}")
        for name, text in texts.items():
            check(MOSAIC in text, f"unified/{name} holds no Mosaic kernel")

        prompts = _prompts(cfg)
        out = [None] * len(prompts)
        with ServingFrontend(eng) as fe:
            threads = []
            for i, p in enumerate(prompts):
                body = {"prompt": p, "max_new_tokens": n_new,
                        "request_id": f"smoke{i}"}
                if i >= len(prompts) // 2:
                    body.update(do_sample=True, temperature=0.8, top_k=40,
                                seed=i)
                threads.append(threading.Thread(
                    target=_generate, args=(fe, body, out, i)))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            check(not any(t.is_alive() for t in threads),
                  "a client thread is still waiting")
        streams = []
        for i, res in enumerate(out):
            check(res is not None, f"request {i}: no response")
            status, events = res
            check(status == 200, f"request {i}: HTTP {status}")
            toks = [t for ev, p in events if ev == "tokens"
                    for t in p["tokens"]]
            dones = [p for ev, p in events if ev == "done"]
            check(len(dones) == 1 and dones[0]["status"] == "finished"
                  and not any(ev == "error" for ev, _ in events),
                  f"request {i}: stream did not close cleanly: {events[-2:]}")
            check(len(toks) == n_new == dones[0]["emitted"],
                  f"request {i}: {len(toks)} tokens, wanted {n_new}")
            check(all(0 <= t < cfg.vocab_size for t in toks),
                  f"request {i}: token out of range")
            streams.append(toks)
        # the same greedy prompt served in two different slots
        check(streams[0] == streams[1],
              "one greedy prompt, two slots, two different streams")
        st = eng.stats
        check(st["requests_finished"] == len(prompts)
              and st["requests_failed"] == 0
              and st["dispatch_retries"] == 0, f"engine stats: "
              f"{ {k: st[k] for k in ('requests_finished', 'requests_failed', 'dispatch_retries', 'dispatch_errors')} }")
        check(not steady, f"compiles after mark_warm(): {steady}")
        check(len(eng._programs) == 2, "a third unified program appeared")
        # both programs took the Mosaic form of every kernel call: a span
        # kernel and a page write of the new K/V rows a layer
        paths = st["kernel_paths"]
        check(paths == dict.fromkeys(
            ("ragged_span_attention/pallas", "kv_page_write/pallas"),
            2 * cfg.num_layers) or not MOSAIC, f"kernel paths: {paths}")
    finally:
        telemetry.cost.remove_compile_hook(hook)
    return {"params": cfg.num_params(), "layers": cfg.num_layers,
            "kernel_paths": paths,
            "decode_dispatches": st["decode_dispatches"],
            "tokens_emitted": st["tokens_emitted"]}


# -- kernels ----------------------------------------------------------------

def _close(got, want, tol, what):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all(), f"{what}: non-finite values")
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    check(err <= tol, f"{what}: max error {err:.4g} > {tol}")
    return round(err, 5)


def _kernel_fused():
    """Packed fused_attention at BERT-base shape: fwd+bwd against the XLA
    path at p=0, then the dropout contract at p=0.1."""
    rng = np.random.default_rng(SEED)
    B, T, H, D = 8, 512, 12, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.random((B, T)) > 0.1)
    w = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    fused = lambda q, k, v: pa.fused_attention(q, k, v, mask=mask,
                                               layout="BTHD")
    dense = lambda q, k, v: dpa.raw_fn(q, k, v, mask=mask, impl="xla",
                                       layout="BTHD")
    got = jax.jit(jax.value_and_grad(loss(fused), argnums=(0, 1, 2)))
    check(got.lower(q, k, v).as_text().count(MOSAIC) == 2,
          "fused fwd+bwd is not two Mosaic kernels")
    with jax.default_matmul_precision("float32"):
        want = jax.jit(jax.value_and_grad(loss(dense),
                                          argnums=(0, 1, 2)))(q, k, v)
        want_fwd = jax.jit(dense)(q, k, v)
    out = {"fwd": _close(jax.jit(fused)(q, k, v), want_fwd, 2e-2,
                         "fused fwd")}
    g = got(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g[1], want[1]):
        out[name] = _close(a, b, 2e-2, f"fused {name}")
    # dropout: the same key gives the same mask (backward relies on it),
    # another key another, and gradients stay finite
    drop = jax.jit(lambda q, key: pa.fused_attention(
        q, k, v, dropout_p=0.1, key=key, layout="BTHD"))
    o1, o2 = drop(q, jax.random.PRNGKey(42)), drop(q, jax.random.PRNGKey(42))
    check(bool(jnp.all(o1 == o2)), "same dropout key, different masks")
    check(bool(jnp.any(o1 != drop(q, jax.random.PRNGKey(7)))),
          "different dropout keys, same mask")
    gd = jax.jit(jax.grad(lambda q: pa.fused_attention(
        q, k, v, dropout_p=0.1, key=jax.random.PRNGKey(42),
        layout="BTHD").sum()))(q)
    check(bool(jnp.isfinite(gd).all()), "dropout gradient not finite")
    # the per-(batch, head) BHTD layout of the same kernel
    bhtd = lambda x: jnp.swapaxes(x, 1, 2)
    out["bhtd_fwd"] = _close(
        jax.jit(lambda q, k, v: pa.fused_attention(q, k, v, mask=mask))(
            bhtd(q), bhtd(k), bhtd(v)),
        bhtd(want_fwd), 2e-2, "fused BHTD fwd")
    return out


def _kernel_span():
    """ragged_span_attention at GPT-2 774M widths, Sq=64 (prefill chunk)
    and Sq=1 (decode), bf16 and int8 pages, against the dense reference.
    The pools are packed (L, N, S, H*D) as PagedKVCache stores them, with
    the data in layer 1 of 2 so that the kernel's layer index is run."""
    rng = np.random.default_rng(SEED)
    B, H, D, S, P = 8, 20, 64, 64, 16
    N = B * P
    out = {}
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    kf = rng.standard_normal((N, S, H, D)).astype(np.float32)
    vf = rng.standard_normal((N, S, H, D)).astype(np.float32)

    def pool(x, dtype):
        return jnp.asarray(np.stack([0 * x, x]), dtype).reshape(2, N, S, -1)

    for sq in (64, 1):
        q = jnp.asarray(rng.standard_normal((B, sq, H, D)), jnp.bfloat16)
        # mixed work: full, partial, idle and long-context slots
        lengths = jnp.asarray([1, 64, 65, 300, 1024 - sq, 17, 512, 700],
                              jnp.int32)
        counts = jnp.asarray([sq, sq, max(sq // 2, 1), sq, sq, 0, 1, sq],
                             jnp.int32)
        for pages in ("bf16", "int8"):
            if pages == "bf16":
                kp, vp = (pool(x, jnp.bfloat16) for x in (kf, vf))
                scales = {}
            else:
                ks = np.abs(kf).max(axis=(1, 3)) / 127.0      # (N, H)
                vs = np.abs(vf).max(axis=(1, 3)) / 127.0
                kp = pool(np.round(kf / ks[:, None, :, None]), jnp.int8)
                vp = pool(np.round(vf / vs[:, None, :, None]), jnp.int8)
                scales = {"k_scale": jnp.asarray(np.stack([0 * ks, ks])),
                          "v_scale": jnp.asarray(np.stack([0 * vs, vs]))}
            kern = jax.jit(lambda q, kp, vp, **kw: pa.ragged_span_attention(
                q, kp, vp, table, lengths, q_counts=counts, layer=1, **kw))
            check(MOSAIC in kern.lower(q, kp, vp, **scales).as_text(),
                  f"span Sq={sq} {pages}: impl='auto' took the dense path")
            with jax.default_matmul_precision("float32"):
                want = jax.jit(
                    lambda q, kp, vp, **kw: pa.ragged_span_attention(
                        q, kp, vp, table, lengths, q_counts=counts,
                        impl="xla", layer=1, **kw))(q, kp, vp, **scales)
            out[f"sq{sq}_{pages}"] = _close(
                kern(q, kp, vp, **scales), want, 3e-2,
                f"span Sq={sq} {pages}")
    return out


def _kernel_page_write():
    """PagedKVCache.write_decode at GPT-2 774M widths through the Mosaic
    call kv_page_write ('auto' on the chip) against the row scatter
    ('xla'), byte for byte: a 64-row chunk that starts mid-page, one
    decode row at a page's last row, an idle slot, a span shorter than the
    chunk, a slot at capacity and a locked first page, in layer 1 of 2."""
    from mxnet_tpu.models import PagedKVCache
    rng = np.random.default_rng(SEED)
    B, H, D, S, P, t = 8, 20, 64, 64, 16, 64
    N = B * P
    table = rng.permutation(N).reshape(B, P).astype(np.int32)
    lengths = np.asarray([0, 37, 127, 300, 1024, 1000, 512, 70], np.int32)
    spans = np.asarray([t, t, 1, 0, t, t, 17, t], np.int32)
    lock = np.zeros(N, bool)
    lock[table[7, 1]] = True
    kp, vp, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                    for shape in 2 * [(2, N, S, H * D)] + 2 * [(B, H, t, D)])

    def write(impl):
        def fn(kp, vp, k, v):
            cache = PagedKVCache(
                kp, vp, jnp.asarray(table), jnp.asarray(lengths),
                page_lock=jnp.asarray(lock), spans=jnp.asarray(spans),
                attn_impl=impl).write_decode(1, k, v)
            return cache.k_pages, cache.v_pages
        return jax.jit(fn)

    check(MOSAIC in write("auto").lower(kp, vp, k, v).as_text(),
          "page write: impl='auto' kept the row scatter")
    got, want = write("auto")(kp, vp, k, v), write("xla")(kp, vp, k, v)
    same = [bool((np.asarray(a).view(np.uint8)
                  == np.asarray(b).view(np.uint8)).all())
            for a, b in zip(got, want)]
    check(all(same), f"page write: pools equal the scatter's (K, V): {same}")
    written = int((np.asarray(want[0][1].astype(jnp.float32))
                   != np.asarray(kp[1].astype(jnp.float32))).any(-1).sum())
    check(written == 64 + 64 + 1 + 24 + 17 + 6, f"rows written: {written}")
    return {"rows_written": written, "bytes_equal": True}


def _kernel_span_gqa():
    """ragged_span_attention at Falcon-H1-34B's heads: 20 query heads over
    4 KV heads of 128, so the kernel stacks 5 query heads a KV head along
    its rows; Sq=64 and Sq=1, bf16 pages, layer 1 of 2."""
    rng = np.random.default_rng(SEED + 1)
    B, Hq, Hkv, D, S, P = 8, 20, 4, 128, 64, 10
    N = B * P
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    kp, vp = (jnp.asarray(np.stack([np.zeros((N, S, Hkv * D), np.float32),
                                    rng.standard_normal((N, S, Hkv * D))]),
                          jnp.bfloat16) for _ in range(2))
    out = {}
    for sq in (64, 1):
        q = jnp.asarray(rng.standard_normal((B, sq, Hq, D)), jnp.bfloat16)
        lengths = jnp.asarray([1, 64, 65, 300, 640 - sq, 17, 512, 400],
                              jnp.int32)
        counts = jnp.asarray([sq, sq, max(sq // 2, 1), sq, sq, 0, 1, sq],
                             jnp.int32)
        call = lambda impl: jax.jit(
            lambda q, kp, vp: pa.ragged_span_attention(
                q, kp, vp, table, lengths, q_counts=counts, layer=1,
                num_kv_heads=Hkv, impl=impl))
        check(MOSAIC in call("auto").lower(q, kp, vp).as_text(),
              f"GQA span Sq={sq}: impl='auto' took the dense path")
        with jax.default_matmul_precision("float32"):
            want = call("xla")(q, kp, vp)
        out[f"sq{sq}"] = _close(call("auto")(q, kp, vp), want, 3e-2,
                                f"GQA span Sq={sq}")
    return out


def _kernel_ssd():
    """ssd_chunk_update at Falcon-H1-34B's mixer: 32 heads of 128, state
    256, 2 groups; 64 rows (a prefill chunk, a decode row, a dead slot, a
    fresh slot) and 8, against the einsum form; the pool's other layer
    and the dead slot's state must come back untouched."""
    rng = np.random.default_rng(SEED + 2)
    B, H, P, G, N = 8, 32, 128, 2, 256
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    A = -jnp.exp(jnp.asarray(0.3 * normal(H)))
    D = jnp.asarray(1 + 0.1 * normal(H))
    state = jnp.asarray(normal(2, B, H, P, N))
    out = {}
    for w in (64, 8):
        x = jnp.asarray(normal(B, w, H, P), jnp.bfloat16)
        Bm, Cm = (jnp.asarray(0.3 * normal(B, w, G, N), jnp.bfloat16)
                  for _ in range(2))
        dt = jax.nn.softplus(jnp.asarray(normal(B, w, H)))
        counts = jnp.asarray([w, 1, 0, w // 2, 1, w, 3, 1], jnp.int32)
        fresh = jnp.asarray([1, 0, 0, 0, 1, 0, 0, 0], bool)
        call = lambda impl: jax.jit(lambda x, dt, Bm, Cm, st: ssd_chunk_update(
            x, dt, A, Bm, Cm, D, st, counts, 1, impl=impl, fresh=fresh))
        check(MOSAIC in call("auto").lower(x, dt, Bm, Cm, state).as_text(),
              f"ssd W={w}: impl='auto' took the einsum path")
        with jax.default_matmul_precision("float32"):
            want_y, want_s = call("xla")(x, dt, Bm, Cm, state)
        y, new = call("auto")(x, dt, Bm, Cm, state)
        out[f"w{w}_y"] = _close(y, want_y, 3e-2, f"ssd W={w} rows")
        out[f"w{w}_state"] = _close(new, want_s, 3e-2, f"ssd W={w} state")
        check(bool(jnp.all(new[0] == state[0])),
              f"ssd W={w}: the layer not asked for changed")
        check(bool(jnp.all(new[1, 2] == state[1, 2])),
              f"ssd W={w}: a slot with no live row changed its state")
    return out


def _kernel_kda():
    """kda_chunk_update at Kimi Linear's mixer: heads of 128 keys by 128
    values, 64 rows (a prefill chunk, a decode row, a dead slot, a fresh
    slot), one head at the published initialisation's strongest decay (the
    running log-decay falls by ~100 over the chunk), against the dense
    form; the pool's other layer and the dead slot's state must come back
    untouched. Then the rows the triangular inverse fears: every 16-row
    block one repeated unit key, decay weak and beta near one, where the
    entries of a block's inverse are differences of like terms and HIGHEST
    is the MXU's six bfloat16 passes, not a CPU's float32."""
    from mxnet_tpu.ops.kda import kda_chunk_update
    rng = np.random.default_rng(SEED + 3)
    B, W, H, D = 8, 64, 8, 128
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = jnp.asarray(unit(rng.standard_normal((B, W, H, D))) * D ** -0.5,
                    jnp.bfloat16)
    k = jnp.asarray(unit(rng.standard_normal((B, W, H, D))), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, W, H, D)), jnp.bfloat16)
    g = -rng.uniform(1, 16, (1, 1, H, 1)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(0.1), (B, W, H, D)))
    g[:, :, 0, :64] = -1.7
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (B, W, H)), jnp.float32)
    state = jnp.asarray(0.5 * rng.standard_normal((2, B, H, D, D)),
                        jnp.float32)
    counts = jnp.asarray([W, 1, 0, W // 2, 1, W, 3, 1], jnp.int32)
    fresh = jnp.asarray([1, 0, 0, 0, 1, 0, 0, 0], bool)
    call = lambda impl: jax.jit(lambda q, k, v, g, beta, st: kda_chunk_update(
        q, k, v, g, beta, st, counts, 1, impl=impl, fresh=fresh))
    check(MOSAIC in call("auto").lower(q, k, v, g, beta, state).as_text(),
          "kda: impl='auto' took the dense path")
    want_o, want_s = call("xla")(q, k, v, g, beta, state)
    o, new = call("auto")(q, k, v, g, beta, state)
    out = {"rows": _close(o, want_o, 1e-2, "kda rows"),
           "state": _close(new, want_s, 1e-2, "kda state")}
    check(bool(jnp.all(new[0] == state[0])),
          "kda: the layer not asked for changed")
    check(bool(jnp.all(new[1, 2] == state[1, 2])),
          "kda: a slot with no live row changed its state")
    k = np.repeat(unit(rng.standard_normal((B, W // 16, 1, H, D))), 16, 2)
    k = jnp.asarray(k.reshape(B, W, H, D), jnp.bfloat16)
    g = jnp.asarray(-1e-3 * rng.uniform(0.5, 1.5, (B, W, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.9, 0.99, (B, W, H)), jnp.float32)
    want_o, want_s = call("xla")(q, k, v, g, beta, state)
    o, new = call("auto")(q, k, v, g, beta, state)
    out["rows_repeated_keys"] = _close(o, want_o, 1e-2,
                                       "kda rows, repeated keys")
    # the state's product takes U and k in bfloat16 (the rows' dtype), and
    # sixteen equal keys add that rounding in step: 0.016 with the finite
    # product and with the merged blocks alike (my chip runs, PR 36)
    out["state_repeated_keys"] = _close(new, want_s, 3e-2,
                                        "kda state, repeated keys")
    return out


def _kernel_latent():
    """latent_span_attention and the one-pool page write at Kimi Linear's
    latent layer: 32 query heads against ONE row of 640 (512 latent + 64
    shared + padding) a token, values the row's leading 512 columns; a
    64-row chunk across a page boundary, a decode row, an idle slot;
    layer 1 of 2, against the dense form and the row scatter."""
    from mxnet_tpu.models import PagedKVCache
    rng = np.random.default_rng(SEED + 4)
    B, Sq, H, Wd, Vw, S, P = 4, 64, 32, 640, 512, 64, 12
    N = B * P
    table = jnp.asarray(rng.permutation(N).reshape(B, P), jnp.int32)
    pool = jnp.asarray(0.3 * rng.standard_normal((2, N, S, Wd)),
                       jnp.bfloat16)
    rows = jnp.asarray(0.3 * rng.standard_normal((B, 1, Sq, Wd)),
                       jnp.bfloat16)
    q = jnp.asarray(0.3 * rng.standard_normal((B, Sq, H, Wd)), jnp.bfloat16)
    lengths = jnp.asarray([300, 0, 700, 100], jnp.int32)
    spans = jnp.asarray([Sq, 5, 0, 1], jnp.int32)

    def attend(impl):
        def fn(pool, rows, q):
            cache = PagedKVCache(pool, None, table, lengths, spans=spans,
                                 attn_impl=impl).write_decode(1, rows, None)
            return cache.k_pages, pa.latent_span_attention(
                q, cache.k_pages, table, lengths + 1, spans,
                value_width=Vw, scale=192 ** -0.5, impl=impl, layer=1)
        return jax.jit(fn)

    check(attend("auto").lower(pool, rows, q).as_text().count(MOSAIC) == 2,
          "latent: impl='auto' left a kernel on its dense path")
    (got_pool, got), (want_pool, want) = (attend(impl)(pool, rows, q)
                                          for impl in ("auto", "xla"))
    check(bool((np.asarray(got_pool).view(np.uint8)
                == np.asarray(want_pool).view(np.uint8)).all()),
          "latent: the one-pool page write left other bytes than the "
          "scatter")
    check(not np.asarray(got[2].astype(jnp.float32)).any(),
          "latent: an idle slot's rows are not zero")
    return {"rows": _close(got, want, 2e-2, "latent span attention")}


def _kernel_flash():
    """flash_attention_data at T=8192: jax's Pallas kernel (not the
    lax.scan) must have produced it, and it must match dense attention."""
    rng = np.random.default_rng(SEED)
    B, H, T, D = 1, 12, 8192, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
               for _ in range(3))
    check(att.pallas_flash_eligible(q, k, None), "flash kernel not chosen")
    fn = jax.jit(lambda q, k, v: att.flash_attention_data(q, k, v,
                                                          causal=True))
    text = fn.lower(q, k, v).as_text()
    check(MOSAIC in text and "stablehlo.while" not in text,
          "flash_attention_data did not lower to the Pallas kernel")
    got = fn(q, k, v)
    with jax.default_matmul_precision("float32"):
        want = jax.jit(lambda q, k, v: dpa.raw_fn(
            q, k, v, causal=True, impl="xla"))(q[:, :2], k[:, :2], v[:, :2])
    return {"fwd": _close(got[:, :2], want, 3e-2, "flash T=8192")}


def phase_kernels():
    return {"fused": _kernel_fused(), "span": _kernel_span(),
            "page_write": _kernel_page_write(),
            "span_gqa": _kernel_span_gqa(), "ssd": _kernel_ssd(),
            "kda": _kernel_kda(), "latent": _kernel_latent(),
            "flash": _kernel_flash()}


# -- four chips -------------------------------------------------------------

def _spans(arrays, n, what):
    for a in arrays:
        check(len(a.sharding.device_set) == n,
              f"{what}: an array of shape {a.shape} lives on "
              f"{len(a.sharding.device_set)} device(s), not {n}")


def _every_chip_in_use(what, floor=32 << 20):
    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:4]]
    check(min(used) > floor, f"{what}: bytes in use per chip {used}")
    return [round(u / 2 ** 20) for u in used]


def phase_four_chips():
    if len(jax.devices()) < 4:
        return {"ran": False, "chips": len(jax.devices())}
    devices = jax.devices()[:4]
    mesh = par.make_mesh({"dp": 2, "tp": 2}, devices=devices)
    cfg, step = _bert(mesh=mesh)
    batch = _bert_batch(cfg, BERT["batch"], BERT["seq_len"],
                        BERT["n_masked"])
    losses = [float(step(*batch).asscalar()) for _ in range(3)]
    check(np.isfinite(losses).all() and losses[-1] < losses[0],
          f"dp2 x tp2 losses: {losses}")
    _spans(step._param_arrays, 4, "TrainStep parameters")
    _spans(jax.tree_util.tree_leaves(step._opt_states), 4,
           "TrainStep optimizer state")
    train_mib = _every_chip_in_use("dp2 x tp2 TrainStep")
    del step

    cfg, net = _gpt2(num_layers=GPT2_TP4_LAYERS)
    check(cfg.num_heads % 4 == 0 and cfg.hidden_size % 4 == 0,
          "tp=4 does not divide the model")
    eng = ServingEngine(net, num_slots=GPT2["slots"],
                        max_length=GPT2["max_length"],
                        page_size=GPT2["page"], tp=4, tp_devices=devices)
    reqs = [Request(p, GPT2["new_tokens"], request_id=f"tp{i}",
                    do_sample=bool(i % 2), temperature=0.8, top_k=40,
                    seed=i)
            for i, p in enumerate(_prompts(cfg)[:4])]
    done = eng.serve(reqs)
    check(len(done) == 4 and all(
        r.status == "finished" and len(r.output_tokens)
        == GPT2["new_tokens"] for r in done),
        f"tp=4 requests: {[(r.id, r.status) for r in done]}")
    st = eng.stats
    check(st["requests_failed"] == 0 and st["dispatch_retries"] == 0,
          "tp=4 engine retried or failed a dispatch")
    _spans([eng._kp, eng._vp], 4, "tp=4 KV pools")
    _spans([placed for _src, placed in eng._placed.values()], 4,
           "tp=4 weights")
    return {"ran": True, "chips": 4, "dp2_tp2_losses":
            [round(x, 4) for x in losses], "train_mib_per_chip": train_mib,
            "tp4_layers": cfg.num_layers,
            "serve_mib_per_chip": _every_chip_in_use("tp=4 ServingEngine")}


# -- main -------------------------------------------------------------------

def main(phases=PHASES):
    platform = jax.devices()[0].platform
    if platform != PLATFORM:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    report = {}
    t0 = time.perf_counter()
    fns = {"device": lambda: phase_device(cache_dir), "train": phase_train,
           "serve": phase_serve, "kernels": phase_kernels,
           "four_chips": phase_four_chips}
    for name in phases:
        run_phase(name, fns[name], meter, report)
    print("[chip_smoke] total: " + json.dumps({
        **_metered(time.perf_counter() - t0, meter.snapshot()),
        "phases": list(report)}), flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
