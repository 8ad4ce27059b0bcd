"""runners/serve.py for a model too large for weights.py's one draw (see
weights_per_parameter.py): the same serving cell in every other respect,
with each parameter drawn on its own. Nothing else of the runner differs,
so it is the runner itself that runs, with that one function of its
weights phase replaced for the call. Two things are added after it.

On the chip a run whose engine traced any kernel call with its dense form
(`stats["kernel_paths"]`, a key ending in `/xla`) is not `correct`, since
the times it reports are not the kernels'.

A traffic file that says `"judged_by": "emitted_tokens"` has
`serve_tokens_per_s` count the output tokens emitted in the window and no
prompt tokens. `runners/serve.py` books a prompt whole at the instant of its
request's first token. Under decode-heavy traffic that is a few hundred
bookings of 16 to 448 tokens on 4% of the window's slot-ticks, and how many
of them fall before the window's last instant is the draw's and not the
system's: the same code in the same window then reads 3% apart and the
metric's bound cannot tell a change from a seed (PERF.md, PR 27). Every
tick emits one token a decoding slot, so the emitted tokens carry the time.
Both counts are said on the line before the result."""
from . import serve
from ..weights_per_parameter import seed_weights


def emitted_per_s(timelines, window_s):
    """Output tokens that came within the window, per second of it."""
    return sum(1 for r in timelines for t in r["tokens"]
               if t <= window_s) / window_s


def run(run):
    one_draw, serve.seed_weights = serve.seed_weights, seed_weights
    try:
        serve.run(run)
    finally:
        serve.seed_weights = one_draw
    paths = run.facts["engine_stats"].get("kernel_paths", {})
    dense = sorted(k for k, n in paths.items() if n and k.endswith("/xla"))
    if dense and not run.cell.tiny:
        run.say(f"kernels WRONG: the program was traced with {dense}")
        run.result["correct"] = False
    if run.cell.traffic.get("judged_by") == "emitted_tokens":
        whole = run.end_to_end["serve_tokens_per_s"]
        emitted = emitted_per_s(run.facts["timelines"], run.seconds)
        run.end_to_end["serve_tokens_per_s"] = emitted
        if not run.cell.tiny:   # a CPU's rates are not said, on any line
            run.say(f"judged by emitted tokens: {emitted:.3f} tokens/s; "
                    f"with each prompt booked whole at its first token "
                    f"{whole:.3f}")
