"""Scheduler: slots that began from zero recurrent state (admissions and
re-prefills with no context: the engine's `stats["state_resets"]`) per
dispatch of the window. The reset itself is in the program and costs the
host nothing; this is how often slots turn over. An engine that counts
none had none: 0.0."""


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st or not st.get("decode_dispatches"):
        return None
    return st.get("state_resets", 0) / st["decode_dispatches"]
