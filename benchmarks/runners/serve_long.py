"""A serving cell (runners/serve.py's window, timelines and counts) for a
model as large as runners/serve_large.py's whose check prompts are too long
for runners/serve.py's comparison, which holds float32 logits of EVERY
position over the whole vocabulary twice (2.6 MB a position at 163840
words: 21 GB for an 8200-token prompt). Nothing of another runner is
replaced for the call: this file's `run` is the runner, built from
serve.py's `Window`, `summarize` and facts. Four things are its own.

The weights. benchmarks/weights_per_parameter.py draws each parameter on
its own; the configuration's `draw` then names parameters (by the end of
their names) that are drawn again at a spread of their own, N(0, std),
under a key folded from the seed and the parameter's index (`seed_weights`
below; the configuration says why it draws what).

The reference phase. The check prompts are served by the ENGINE IN COMPANY:
a first wave of short requests takes every slot, the check requests queue
behind it and so take slots that another request has left (its recurrent
state and its pages behind it), and further requests behind them keep every
other slot busy and turning over until the longest check prompt is done
(`company`; the share of the check's dispatches that ran with every slot
busy is said, and under 0.9 is WRONG). Three comparisons decide `correct`:

  1. the system's logits at the last prompt position and at every emitted
     position against the float32 reference's at the same positions,
     teacher-forced on the emitted tokens, by what reference.TOLERANCE
     names (`errors` below). The system's are the model's own paged path
     over ONE slot (`PagedPath`: `make_cache()`, the engine's chunk width,
     page size and kernels: carried state, pages and page boundaries, never
     the whole-sequence forward); the reference's are
     `reference.logits(..., positions=)`, which heads those positions alone.
  2. the ENGINE's stream, the timed 32-slot program's with its neighbours
     live, against that paged path at EVERY emitted position: the engine's
     token is the paged path's best at reference.STREAM_AGREE of the
     positions or more, and nowhere does the logit the paged path gives it
     fall short of its best by more than reference.STREAM_MARGIN (two
     programs of one model in bfloat16 round apart; a wrong block table, a
     reset of the wrong slot or a shared state leaf does not stay within
     them).
  3. the engine's stream against the reference's argmax wherever the
     reference's two best logits lie further apart than its ARGMAX_MARGIN.

The judged count. A traffic file that says `"judged_by":
"fed_and_emitted_tokens"` has `serve_tokens_per_s` count the prompt rows
FED and the tokens emitted, from the engine's own counters (reset at the
window's start): the numerator of `useful_row_share`, over the seconds
those counters cover (the window, or to the end of the step that overran
it). runners/serve.py books a prompt whole at its first token; with prompts
of ten thousand tokens, a dozen slots' worth always in flight at the
window's end, that count moves in steps of 10,000 and spreads by more than
the bound can tell (PERF.md, PR 27 measured the same at prompts of 128).
Both counts are said on the line before the result.

On the chip a run whose engine traced any kernel call with its dense form
(`stats["kernel_paths"]`, a key ending `/xla`) is not `correct`, as in
runners/serve_large.py.

    python3 -m benchmarks.runners.serve_long --workload <cell>
        --seed <n> [<n> ...] [--only [NAME ...]] [--impl xla] [--check]

prints what the reference's limits lie between, as benchmarks/tightness.py
does for the shorter cells: the system's paged logits against the reference
and against each of its PERTURBATIONS and CONTROLS, over random sequences as
long as the check's, at the compared positions.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu import models
from mxnet_tpu.models.kv_cache import PagedKVCache
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.serving import Request, ServingEngine

from . import serve
from .. import weights_per_parameter

# the least share of the check's dispatches that must have run with every
# slot busy
BUSY_SHARE = 0.9


def seed_weights(net, seed, dtype, draw=None):
    """weights_per_parameter.seed_weights, then every parameter whose name
    ends with a key of `draw` again as N(0, draw[key]), each under its own
    key."""
    weights_per_parameter.seed_weights(net, seed, dtype)
    key = jax.random.key(int(seed))
    for i, (name, p) in enumerate(net.collect_params().items()):
        for tail, std in (draw or {}).items():
            if name.endswith(tail):
                k = jax.random.fold_in(jax.random.fold_in(key, i), 1)
                p.set_data(NDArray(weights_per_parameter._normal(
                    k, tuple(p.shape), dtype, float(std), 0.0)))


def build(cfg, seed):
    """The cell's model with its seeded weights, as a server holds it."""
    kwargs = cfg["model"]["kwargs"]
    net = getattr(models, cfg["model"]["class"])(
        getattr(models, cfg["model"]["config_fn"])(**kwargs))
    # a server keeps no gradients: none are allocated
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, seed, kwargs["dtype"], cfg.get("draw"))
    return net


class PagedPath:
    """The model's own paged path over one slot: sequences fed a chunk at
    a time against carried state and pages, as the engine feeds a prompt.
    One jitted step for every sequence up to `longest` tokens, whatever
    weights the model holds when `logits` is called."""

    def __init__(self, net, engine_kw, attn_impl, longest):
        self.net, self.impl = net, attn_impl
        self.width, page = engine_kw["chunk_tokens"], engine_kw["page_size"]
        self.params = list(net.collect_params().values())
        room = -(-(longest + self.width) // page) * page
        self.fresh = lambda: net.make_cache(1, room, page_size=page,
                                            attn_impl=attn_impl)
        self.step = jax.jit(self._step, donate_argnums=(1,))

    def _step(self, arrays, state, table, toks, length, count):
        saved = [p._data for p in self.params]
        try:
            for p, d in zip(self.params, arrays):
                arr = NDArray(d)
                arr._grad_req = "null"
                p._data = arr
            pools, rec = state
            h, new = self.net.hidden(toks, PagedKVCache(
                *pools, table, length, spans=count, attn_impl=self.impl,
                recurrent=rec))
        finally:
            for p, d in zip(self.params, saved):
                p._data = d
        return h._data[0], ((new.k_pages, new.v_pages), new.recurrent)

    def logits(self, ids, positions):
        """(len(positions), V) float32 logits of the sequence `ids`."""
        arrays = [p.data()._data for p in self.params]
        cache, width, n = self.fresh(), self.width, len(ids)
        state = ((cache.k_pages, cache.v_pages), cache.recurrent)
        want, rows = {int(p) for p in positions}, {}
        for at in range(0, n, width):
            toks = np.zeros((1, width), np.int32)
            count = min(width, n - at)
            toks[0, :count] = ids[at:at + count]
            h, state = self.step(arrays, state, cache.page_table,
                                 jnp.asarray(toks),
                                 jnp.full((1,), at, jnp.int32),
                                 jnp.full((1,), count, jnp.int32))
            for p in want & set(range(at, at + count)):
                rows[p] = h[p - at]
        h = jnp.stack([rows[int(p)] for p in positions])
        return self.net.head(h)._data.astype(jnp.float32)


def reference_forward(ref, kwargs, **reading):
    """The reference's jitted forward (with `reading`, one of its
    PERTURBATIONS): (params, ids (n,), positions (m,)) -> (m, V)."""
    forward = jax.jit(functools.partial(ref.logits, kwargs=kwargs, **reading))
    return lambda params, ids, pos: forward(
        params, ids=jnp.asarray(ids)[None],
        positions=jnp.asarray(pos)[None])[0]


def parameters(net):
    return {k: p.data()._data for k, p in net.collect_params().items()}


def errors(own, want):
    """What the comparison reads, as reference.TOLERANCE names them, over
    lists of (positions, V) logits: `logit_rms`, the root of the mean
    squared difference over all of them; `logit_rms_p50`, the median over
    the positions of each position's own; `logit_abs`, the largest
    difference of any logit. A token routed to another expert than the
    reference's (top-k is discontinuous, and bfloat16 hidden states flip
    close calls) moves ONE position's logits by a few tenths, its own root
    mean square with them, the whole's a little and the median not at all;
    a matrix in the wrong precision moves every position's."""
    diff = jnp.concatenate([a - b for a, b in zip(own, want)])
    by_position = jnp.sqrt(jnp.mean(diff * diff, -1))
    return {"logit_rms": float(jnp.sqrt(jnp.mean(diff * diff))),
            "logit_rms_p50": float(jnp.median(by_position)),
            "logit_abs": float(jnp.max(jnp.abs(diff)))}


def beyond(read, tol):
    """The limits of `tol` that the reading exceeds."""
    return sorted(k for k in tol if read[k] > tol[k])


def company(rng, vocab, slots, width, ticks):
    """(a first wave, the requests behind the check's): `slots` requests
    of one to eight chunks that take every slot and leave it at different
    ticks, and requests of an eighth to a half of the check's longest
    prompt, as many as keep `slots - 1` slots busy for `ticks` dispatches
    and half a longest request more."""
    made = lambda tag, n, new: Request(
        rng.integers(0, vocab, int(n)), int(new), request_id=tag)
    first = [made(f"first-{i}", rng.integers(width, 8 * width + 1), 2)
             for i in range(slots)]
    longest = ticks * width // 2
    behind, owed = [], (slots - 1) * (ticks + longest // (2 * width))
    while owed > 0:
        n, new = rng.integers(longest // 4, longest + 1), rng.integers(2, 9)
        behind.append(made(f"behind-{len(behind)}", n, new))
        owed -= -(-n // width) + new
    return first, behind


def serve_in_company(eng, checks, first, behind):
    """Serve the check requests among the others; per dispatch in which a
    check request held a slot, how many slots were held (those that still
    are after the step, and those that finished in it)."""
    for r in first + checks + behind:
        eng.submit(r)
    busy, sched = [], eng.scheduler
    while eng.has_work:
        held = eng.step() + [sched.request_at(s) for s in sched.active_slots]
        if any(r in held for r in checks):
            busy.append(len(held))
    return busy


def check_reference(run, net, eng, ref, kwargs, check):
    """This file's docstring: the warm-up of the engine's programs and the
    three comparisons in one."""
    rng = np.random.default_rng([run.seed, 0x636b])
    vocab, n_new = kwargs["vocab_size"], int(check["new_tokens"])
    prompts = [rng.integers(0, vocab, n) for n in check["prompt_lens"]]
    checks = [Request(p, n_new, request_id=f"check-{i}", seed=i)
              for i, p in enumerate(prompts)]
    width = eng.chunk_tokens
    ticks = -(-max(map(len, prompts)) // width) + n_new
    first, behind = company(rng, vocab, eng.num_slots, width, ticks)
    busy = serve_in_company(eng, checks, first, behind)
    everyone = first + checks + behind
    if any(r.status != "finished" or len(r.output_tokens) != r.max_new_tokens
           for r in everyone):
        states = [(r.id, r.status, len(r.output_tokens)) for r in everyone
                  if r.status != "finished"
                  or len(r.output_tokens) != r.max_new_tokens]
        run.say(f"check: requests did not finish: {states}")
        return False
    all_busy = float(np.mean(np.asarray(busy) == eng.num_slots))
    streams = [list(r.output_tokens) for r in checks]
    # position len(p) - 1 + j holds the distribution of emitted token j
    sequences = [np.concatenate([p, s[:-1]]).astype(np.int32)
                 for p, s in zip(prompts, streams)]
    positions = [len(p) - 1 + np.arange(n_new) for p in prompts]
    path = PagedPath(net, run.cell.config["engine"], eng.attn_impl,
                     max(map(len, sequences)))
    own = [path.logits(ids, pos) for ids, pos in zip(sequences, positions)]
    forward, params = reference_forward(ref, kwargs), parameters(net)
    wants = [forward(params, ids, pos)
             for ids, pos in zip(sequences, positions)]
    read = errors(own, wants)
    # 2: the engine's token against the paged path's own best, everywhere
    short = np.concatenate([
        np.asarray(lg.max(-1) - lg[np.arange(n_new), np.asarray(s)])
        for lg, s in zip(own, streams)])
    # 3: against the reference's argmax, where it is sure of it
    compared = agreed = 0
    for want, stream in zip(wants, streams):
        top2 = jax.lax.top_k(want, 2)[0]
        sure = np.asarray(top2[:, 0] - top2[:, 1]) > ref.ARGMAX_MARGIN
        best = np.asarray(jnp.argmax(want, -1))
        compared += int(sure.sum())
        agreed += int((best == np.asarray(stream))[sure].sum())
    best_share = float(np.mean(short == 0))
    ok = not beyond(read, ref.TOLERANCE) and agreed == compared \
        and best_share >= ref.STREAM_AGREE \
        and float(short.max()) <= ref.STREAM_MARGIN \
        and all_busy >= BUSY_SHARE
    run.say(f"reference: the system's paged logits differ from the float32 "
            f"reference by root mean square {read['logit_rms']:.6f} "
            f"(a position's median {read['logit_rms_p50']:.6f}, largest "
            f"difference {read['logit_abs']:.6f}; limits {ref.TOLERANCE}) at "
            f"{n_new} positions each of {len(prompts)} sequences of "
            f"{[len(s) + 1 for s in sequences]} tokens; the engine, "
            f"{eng.num_slots} slots busy in {all_busy:.3f} of the check's "
            f"{len(busy)} dispatches among {len(first) + len(behind)} other "
            f"requests, gave the paged path's best token at "
            f"{int((short == 0).sum())} of {len(short)} positions (at least "
            f"{ref.STREAM_AGREE} of them) and fell short of it by at most "
            f"{float(short.max()):.6f} (limit {ref.STREAM_MARGIN}), and "
            f"matches the reference's argmax in {agreed} of {compared} "
            f"positions whose margin exceeds "
            f"{ref.ARGMAX_MARGIN}: {'ok' if ok else 'WRONG'}")
    return ok


def fed_and_emitted_per_s(stats, covered_s):
    """Prompt rows fed and tokens emitted per second of the time the
    engine's counters cover."""
    return (stats["prefill_tokens"] + stats["tokens_emitted"]) / covered_s


def run(run):
    cell = run.cell
    cfg, traffic = cell.config, cell.traffic
    kwargs = cfg["model"]["kwargs"]
    ref = cell.module("reference", cfg["reference"])
    gen = cell.module("generators", traffic["generator"])

    with run.phase("weights"):
        net = build(cfg, run.seed)
    with run.phase("warmup"):
        eng = ServingEngine(net, **cfg["engine"])
    with run.phase("reference"):
        # the comparison's requests are also the warm-up of the programs
        correct = check_reference(run, net, eng, ref, kwargs, cfg["check"])
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
    win = serve.Window(eng, specs, run.seconds, run.tracer,
                       trace_after=serve.TRACE_AFTER * run.seconds,
                       trace_dispatches=serve.TRACE_DISPATCHES
                       if run.trace else 0, keep_trace=run.keep_trace)
    run.open_window()
    win.run()
    run.close_window()
    tl = win.timelines()
    s = serve.summarize(tl, run.seconds)
    serve._say_window(run, win, tl, s)

    st = win.stats
    short = [r for r in tl if r["short"]]
    failed = [r for r in tl if r["failed"]]
    ran_dry = traffic["arrivals"]["process"] == "backlog" and win.steps \
        and win.steps[-1][2] == 0
    paths = st.get("kernel_paths", {})
    dense = [] if cell.tiny else sorted(
        k for k, n in paths.items() if n and k.endswith("/xla"))
    if short or failed or st["requests_failed"] or st["dispatch_retries"] \
            or ran_dry or dense:
        run.say(f"window WRONG: {len(short)} finished short, {len(failed)} "
                f"failed, engine requests_failed {st['requests_failed']}, "
                f"dispatch_retries {st['dispatch_retries']}, backlog ran dry "
                f"{ran_dry}, kernels traced with their dense form {dense}")
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
    if traffic.get("judged_by") == "fed_and_emitted_tokens":
        # the counters run to the end of the step that overran the window
        covered = max([run.seconds] + [x[1] for x in win.steps[-1:]])
        fed = fed_and_emitted_per_s(st, covered)
        run.end_to_end["serve_tokens_per_s"] = fed
        if not cell.tiny:       # a CPU's rates are not said, on any line
            walls = np.asarray([x[1] - x[0] for x in win.steps])
            slow = walls[walls > 2 * np.median(walls)]
            run.say(f"judged by fed and emitted tokens: {fed:.3f} tokens/s "
                    f"({st['prefill_tokens']} prompt rows fed, "
                    f"{st['tokens_emitted']} tokens emitted in "
                    f"{covered:.3f} s); with each prompt booked whole at its "
                    f"first token {s['tokens_per_s']:.3f}; a step took "
                    f"{np.median(walls) * 1e3:.2f} ms (median), the longest "
                    f"{walls.max() * 1e3:.2f} ms, and {len(slow)} took over "
                    f"twice the median, {slow.sum():.3f} s in all")


def main(argv=None):
    """The readings the reference's limits lie between (this file's
    docstring), a line a seed and one JSON object last."""
    import argparse
    import gc
    import json
    import os
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", default=[0])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--only", nargs="*", default=None, metavar="NAME",
                    help="these of the reference's PERTURBATIONS and "
                         "CONTROLS alone (none named: the model's own "
                         "error alone)")
    ap.add_argument("--impl", default="auto",
                    help="the kernels' impl (xla: their dense forms)")
    args = ap.parse_args(argv)
    if args.check:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from .. import cells

    cell = cells.Cell(args.workload, tiny=args.check)
    cfg = cell.config
    kwargs, check = cfg["model"]["kwargs"], cfg["check"]
    ref = cell.module("reference", cfg["reference"])
    readings = {**ref.PERTURBATIONS, **ref.CONTROLS}
    names = [n for n in readings if args.only is None or n in args.only]
    n_new = int(check["new_tokens"])
    positions = [n - 1 + np.arange(n_new) for n in check["prompt_lens"]]
    # one model, one paged program and one reference program a reading for
    # every seed: a seed only draws the weights again
    net = build(cfg, args.seed[0])
    path = PagedPath(net, cfg["engine"], args.impl,
                     max(check["prompt_lens"]) + n_new)
    forwards = {n: reference_forward(ref, kwargs, **readings[n])
                for n in names}
    forwards["reference"] = reference_forward(ref, kwargs)
    def read(seed):
        """One seed's readings (its sequences' logits go with this frame:
        the next seed's weights need the room)."""
        rng = np.random.default_rng([seed, 0x7469])
        sequences = [rng.integers(0, kwargs["vocab_size"], n + n_new - 1)
                     .astype(np.int32) for n in check["prompt_lens"]]
        own = [path.logits(ids, pos)
               for ids, pos in zip(sequences, positions)]
        params, got = parameters(net), {}
        for name, forward in forwards.items():
            wants = [forward(params, ids, pos)
                     for ids, pos in zip(sequences, positions)]
            got[name] = errors(own, wants)
            got[name]["beyond"] = beyond(got[name], ref.TOLERANCE)
            if name == "reference":
                got["spread"] = float(jnp.std(jnp.concatenate(wants)))
        return got

    out = {}
    for seed in args.seed:
        if seed != args.seed[0]:
            seed_weights(net, seed, kwargs["dtype"], cfg.get("draw"))
        out[str(seed)] = read(seed)
        gc.collect()
        print(f"[tightness] {cell.name}, seed {seed}, impl {args.impl}, "
              f"limits {ref.TOLERANCE}: {json.dumps(out[str(seed)])}",
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
