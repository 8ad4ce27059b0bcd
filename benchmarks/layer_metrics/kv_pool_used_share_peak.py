"""KV cache: the largest share of the page pool that was leased at the end
of any step of the window, in per cent (`PagePool.num_free` after each
step; the engine's `pool_free_pages` gauge is not refreshed by a plain
admission, so it is not what is read)."""


def read(run, label=None):
    steps = run.facts.get("steps")
    if not steps or "total_pages" not in run.facts:
        return None
    return 100.0 * (1.0 - min(s[4] for s in steps)
                    / run.facts["total_pages"])
