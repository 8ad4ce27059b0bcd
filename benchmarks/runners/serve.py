"""A serving cell: one model behind serving.ServingEngine, fed by one thread
that submits each request when it falls due and steps the engine in
between: the engine's own serving loop, with arrivals over time.

The configuration's file names the model, the `engine` block and the check
prompts; the traffic file is the parameters of generators/request_stream.
"""
import bisect
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu import models, parallel as par, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import Request, ServingEngine
from mxnet_tpu.serving.scheduler import TERMINAL_STATUSES

from .. import stats
from ..weights import seed_weights

# the traced slice of a `--trace 1` run: this many dispatches, from this
# share of the window on (or as many as the window still holds)
TRACE_AFTER, TRACE_DISPATCHES = 0.3, 40


def _check_reference(run, net, eng, ref, kwargs, check):
    """Warm-up and comparison in one: the check prompts are served through
    the one of the engine's two unified programs that the traffic uses,
    decoding the argmax (greedy, or through the sampled program with
    top_k=1), and the streams are held to the float32 reference,
    teacher-forced on the emitted tokens. The model's own forward gives the
    logits of the same sequences."""
    rng = np.random.default_rng([run.seed, 0x636b])
    vocab, n_new = kwargs["vocab_size"], int(check["new_tokens"])
    prompts = [rng.integers(0, vocab, n) for n in check["prompt_lens"]]
    tol = ref.TOLERANCE["logit_abs"]
    ok = True
    sampled = bool((run.cell.traffic.get("sampling") or {}).get("do_sample"))
    reqs = [Request(p, n_new, request_id=f"check-{i}", do_sample=sampled,
                    top_k=1, seed=i) for i, p in enumerate(prompts)]
    if sampled:
        # one truly sampled request, twice: one seed, one stream
        reqs += [Request(prompts[0], n_new, request_id=f"check-dup-{i}",
                         do_sample=True, temperature=0.8, top_k=40, seed=7)
                 for i in range(2)]
    done = eng.serve(reqs)
    if any(r.status != "finished" or len(r.output_tokens) != n_new
           for r in done):
        states = [(r.id, r.status, len(r.output_tokens)) for r in done]
        run.say(f"check: requests did not finish: {states}")
        return False
    if sampled and reqs[-1].output_tokens != reqs[-2].output_tokens:
        run.say("check: one sampled request served twice gave two streams")
        ok = False
    streams = [list(r.output_tokens) for r in reqs[:len(prompts)]]
    lens = [len(p) + n_new for p in prompts]
    width = -(-max(lens) // 64) * 64
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        ids[i, :lens[i]] = np.concatenate([p, s])
    ids = jnp.asarray(ids)
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    want = jax.jit(functools.partial(ref.logits, kwargs=kwargs))(params,
                                                                 ids=ids)
    own = par.EvalStep(net)(ids)._data.astype(jnp.float32)
    valid = jnp.arange(width)[None, :] < jnp.asarray(lens)[:, None]
    logit_err = float(jnp.max(jnp.where(valid[:, :, None],
                                        jnp.abs(own - want), 0.0)))
    top2 = jax.lax.top_k(want, 2)[0]
    margin = np.asarray(top2[..., 0] - top2[..., 1])
    best = np.asarray(jnp.argmax(want, -1))
    compared = agreed = 0
    for i, (p, s) in enumerate(zip(prompts, streams)):
        for j, token in enumerate(s):
            at = len(p) - 1 + j
            if margin[i, at] > tol:
                compared += 1
                agreed += int(best[i, at] == token)
    if logit_err > tol or agreed != compared or compared == 0:
        ok = False
    run.say(f"reference: the model's logits differ from the float32 "
            f"reference by at most {logit_err:.4f} (limit {tol}) on "
            f"{len(prompts)} sequences of {lens} tokens; the engine's argmax "
            f"stream matches the reference's in {agreed} of {compared} "
            f"positions whose margin exceeds the limit (of "
            f"{len(prompts) * n_new}): {'ok' if ok else 'WRONG'}")
    return ok


class Window:
    """One measured window over one engine: what was offered, and when
    each thing happened, in seconds from the window's start."""

    def __init__(self, eng, specs, seconds, tracer, trace_after=None,
                 trace_dispatches=0, keep_trace=None):
        self.eng, self.seconds = eng, seconds
        self.specs = specs
        self.requests = [
            Request(s["prompt"], s["max_new_tokens"], request_id=f"r{i}",
                    seed=s["seed"], **s["sampling"])
            for i, s in enumerate(specs)]
        self.submitted = []      # (index, seconds late) in submit order
        self.refused = {}        # index -> the exception's name
        # (t0, t1, queued, active, free pages, tokens the active hold)
        self.steps = []
        self.dispatches = []     # the program's serving.dispatch spans
        self.traced_steps = []   # indices into steps inside the traced slice
        self._tracer, self._keep = tracer, keep_trace
        self._trace_after, self._trace_n = trace_after, trace_dispatches

    def _on_span(self, ev):
        if ev["name"] == "serving.dispatch":
            self.dispatches.append(ev)

    def run(self):
        eng, tracer = self.eng, self._tracer
        span = tracer.span
        pending = list(range(len(self.requests)))[::-1]
        telemetry.add_event_hook(self._on_span)
        self.t0 = t0 = time.perf_counter()
        try:
            with contextlib.ExitStack() as traced:      # the traced slice
                while True:
                    now = time.perf_counter() - t0
                    if now >= self.seconds:
                        break
                    if (self._trace_n and not tracer.running
                            and now >= self._trace_after):
                        traced.enter_context(tracer.slice(self._keep))
                    with span("bench.submit"):
                        while pending and \
                                self.specs[pending[-1]]["due"] <= now:
                            i = pending.pop()
                            try:
                                eng.submit(self.requests[i])
                            except MXNetError as e:     # refused: it counts
                                self.refused[i] = type(e).__name__
                            self.submitted.append(
                                (i, now - self.specs[i]["due"]))
                    if eng.has_work:
                        s0 = time.perf_counter() - t0
                        with span("bench.step"):
                            eng.step()
                        sched = eng.scheduler
                        held = [sched.request_at(slot)
                                for slot in sched.active_slots]
                        self.steps.append((
                            s0, time.perf_counter() - t0, sched.num_queued,
                            len(held), eng.page_pool.num_free,
                            sum(r.prompt_len + len(r.output_tokens)
                                for r in held)))
                        if tracer.running:
                            self.traced_steps.append(len(self.steps) - 1)
                            if len(self.traced_steps) >= self._trace_n:
                                traced.close()
                                self._trace_n = 0       # one slice a run
                    else:
                        # idle until the next request falls due, in short naps
                        nap = self.specs[pending[-1]]["due"] - now \
                            if pending else 0.002
                        with span("bench.sleep"):
                            time.sleep(min(max(nap, 0.0), 0.002))
        finally:
            telemetry.remove_event_hook(self._on_span)
        self.stats = dict(eng.stats)
        return self

    def timelines(self):
        """Per submitted request: when it was due, admitted and gave each
        token, whether it failed, and whether it finished as asked."""
        out = []
        rel = lambda t: None if t is None else t - self.t0
        for i, late in self.submitted:
            r, s = self.requests[i], self.specs[i]
            tokens = [rel(t) for t in r.token_times]
            failed = i in self.refused or (
                r.status in TERMINAL_STATUSES and r.status != "finished")
            out.append({
                "due": s["due"], "late": late,
                "prompt_len": len(s["prompt"]), "asked": s["max_new_tokens"],
                "admit": rel(getattr(r, "t_mark", None)),
                "first": tokens[0] if tokens else None, "tokens": tokens,
                "failed": failed, "status": r.status,
                "short": r.status == "finished"
                and len(r.output_tokens) != s["max_new_tokens"]})
        return out


def rows_of_steps(timelines, steps, indices, width):
    """What the dispatches of the steps `indices` had to attend: for each,
    a list of (context, count) per slot with work, as
    reference/gpt2.py `attention_cost` takes them. Rebuilt from when each
    request was admitted and gave each token: a prompt is fed `width`
    tokens a step from the step that admitted it, and its first token
    comes with its last chunk; every later token is one decode row. None
    if a request's first token did not come when that rule says, which
    means the prefill budget was binding and the rule does not hold."""
    ends = [s[1] for s in steps]
    rows = {i: [] for i in indices}
    for r in timelines:
        if r["admit"] is None:
            continue
        plen = r["prompt_len"]
        k = bisect.bisect_left(ends, r["admit"])
        chunks = -(-plen // width)
        if r["first"] is not None \
                and bisect.bisect_left(ends, r["first"]) != k + chunks - 1:
            return None
        for c in range(chunks):
            if k + c in rows:
                rows[k + c].append((c * width,
                                    min(width, plen - c * width)))
        for j, t in enumerate(r["tokens"][1:], start=1):
            m = bisect.bisect_left(ends, t)
            if m in rows:
                rows[m].append((plen + j - 1, 1))
    return [rows[i] for i in indices]


def summarize(timelines, window_s):
    """The serving numbers of one window, from the timelines alone."""
    ttft = stats.ttft_ms(timelines, window_s)
    gaps = stats.gaps_ms(timelines, window_s)
    started = [r for r in timelines
               if r["first"] is not None and r["first"] <= window_s]
    tokens = sum(r["prompt_len"] for r in started) + sum(
        1 for r in timelines for t in r["tokens"] if t <= window_s)
    return {
        "ttft_ms": ttft, "gaps_ms": gaps,
        "ttft_p50_ms": stats.median(ttft),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "itl_p50_ms": stats.median(gaps),
        "itl_p99_ms": stats.percentile(gaps, 99),
        "tokens_per_s": tokens / window_s,
        "finished": sum(r["status"] == "finished" for r in timelines),
        "late_max_ms": max((r["late"] for r in timelines), default=0) * 1e3,
        "late_p50_ms": (stats.median([r["late"] for r in timelines])
                        or 0) * 1e3,
    }


def _say_window(run, win, tl, s):
    ms = lambda v: "none" if v is None else f"{v:.2f}"
    steps = win.steps
    third = max(1, len(steps) // 3)
    q_first = np.mean([x[2] for x in steps[:third]]) if steps else 0
    q_last = np.mean([x[2] for x in steps[-third:]]) if steps else 0
    run.say(
        f"window: {len(tl)} requests submitted, {s['finished']} finished, "
        f"{len(win.refused)} refused; {len(steps)} steps; "
        f"queue depth {q_first:.1f} over the first third of the steps, "
        f"{q_last:.1f} over the last; slots busy mean "
        f"{np.mean([x[3] for x in steps]) if steps else 0:.1f}, max "
        f"{max((x[3] for x in steps), default=0)}")
    if run.cell.tiny:       # a CPU's times are not said, on any line
        return
    run.say(
        f"TTFT p50 {ms(s['ttft_p50_ms'])} p90 {ms(s['ttft_p90_ms'])} ms over "
        f"{len(s['ttft_ms'])} requests ({stats.beyond(s['ttft_ms'], 90)} "
        f"beyond the p90); gap p50 {ms(s['itl_p50_ms'])} p99 "
        f"{ms(s['itl_p99_ms'])} ms over {len(s['gaps_ms'])} gaps "
        f"({stats.beyond(s['gaps_ms'], 99)} beyond the p99); "
        f"{s['tokens_per_s']:.1f} tokens/s; generator late p50 "
        f"{s['late_p50_ms']:.2f} ms, max {s['late_max_ms']:.2f} ms")


def run(run):
    cell = run.cell
    cfg, traffic = cell.config, cell.traffic
    kwargs = cfg["model"]["kwargs"]
    ref = cell.module("reference", cfg["reference"])
    gen = cell.module("generators", traffic["generator"])

    with run.phase("weights"):
        net = getattr(models, cfg["model"]["class"])(
            getattr(models, cfg["model"]["config_fn"])(**kwargs))
        # a server keeps no gradients: none are allocated
        net.collect_params().setattr("grad_req", "null")
        seed_weights(net, run.seed, kwargs["dtype"])
    with run.phase("warmup"):
        eng = ServingEngine(net, **cfg["engine"])
    with run.phase("reference"):
        # the comparison's requests are also the warm-up of the programs
        correct = _check_reference(run, net, eng, ref, kwargs, cfg["check"])
    eng.mark_warm()
    # what a dispatch allocates for itself while it runs
    temps = [fn._call.memory_analysis() for fn in eng._programs.values()]
    run.facts["program_temp_bytes"] = max(
        m.temp_size_in_bytes for m in temps)
    run.say("the unified program holds "
            f"{run.facts['program_temp_bytes'] / 1e9:.3f} GB of temporaries "
            f"beside {temps[0].argument_size_in_bytes / 1e9:.3f} GB of "
            "arguments")

    specs = gen.generate(traffic, kwargs["vocab_size"], run.seed,
                         run.seconds)
    eng.reset_stats()
    win = Window(eng, specs, run.seconds, run.tracer,
                 trace_after=TRACE_AFTER * run.seconds,
                 trace_dispatches=TRACE_DISPATCHES if run.trace else 0,
                 keep_trace=run.keep_trace)
    run.open_window()
    win.run()
    run.close_window()
    tl = win.timelines()
    s = summarize(tl, run.seconds)
    _say_window(run, win, tl, s)

    st = win.stats
    backlog = traffic["arrivals"]["process"] == "backlog"
    short = [r for r in tl if r["short"]]
    failed = [r for r in tl if r["failed"]]
    ran_dry = backlog and win.steps and win.steps[-1][2] == 0
    if short or failed or st["requests_failed"] or st["dispatch_retries"] \
            or ran_dry:
        run.say(f"window WRONG: {len(short)} finished short, {len(failed)} "
                f"failed, engine requests_failed {st['requests_failed']}, "
                f"dispatch_retries {st['dispatch_retries']}, backlog ran dry "
                f"{ran_dry}")
        correct = False
    run.result.update(correct=bool(correct), attempted=len(tl),
                      failed=len(failed) + len(short))
    run.facts.update(
        kind="serve", chips=1, timelines=tl, steps=win.steps,
        traced_steps=win.traced_steps, dispatches=win.dispatches,
        engine_stats=st, slots=eng.num_slots, width=eng.chunk_tokens,
        total_pages=eng.page_pool.num_pages, page_size=eng.page_size,
        model_kwargs=kwargs, attention_cost=ref.attention_cost)
    run.end_to_end.update(
        ttft_p90_ms=s["ttft_p90_ms"], itl_p99_ms=s["itl_p99_ms"],
        serve_tokens_per_s=s["tokens_per_s"])
