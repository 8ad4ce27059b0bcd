"""Scheduler and unified dispatch: where the host's part of a serving tick
goes, in mean milliseconds per dispatch over the whole window. The label
names the phase. Eight are the program's own: the self time (a span's
duration minus its child spans') of the `serving.<phase>` spans inside
`ServingEngine.step()`, which the engine sums into
`stats["tick_phase_seconds"]`. `wait` is the host blocked on the device,
so most of a tick; the other seven are the host's own work. The ninth,
`rest`, is the benchmark's clock around `eng.step()` minus those eight:
the self time of `serving.step` and `serving.dispatch` (expiry scans,
hooks, policy, gauges) and the call itself. The nine sum to the steps' wall
per dispatch.

A phase the engine did not book reads 0.0, not None: the run made by hand
in tests/benchmarks/test_bench_units.py has counters and no phases, and
its two tests hold the result line to the listed metrics exactly. `rest`
keeps that honest on the chip: if the program's counters go missing, it is
the whole tick, over a hundred milliseconds where about one is expected.
(PERF.md section 7 asks the next benchmark PR to put the phases into that
run and turn absence back into None.)"""

# label -> the engine's phase (the span's name without `serving.`)
PHASES = {"admit": "admit", "sync_slot": "sync_slot", "assemble": "assemble",
          "launch": "dispatch.launch", "wait": "dispatch.wait",
          "fetch": "dispatch.fetch", "fanout": "fanout", "finish": "finish"}


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st or not st["decode_dispatches"]:
        return None
    booked = st.get("tick_phase_seconds", {})
    per_dispatch = 1e3 / st["decode_dispatches"]
    if label != "rest":
        return per_dispatch * booked.get(PHASES[label], 0.0)
    wall = sum(s[1] - s[0] for s in run.facts["steps"])
    return per_dispatch * (wall - sum(booked.get(p, 0.0)
                                      for p in PHASES.values()))
