"""From raw timestamps to the numbers the benchmark reports. Pure functions
of lists of floats, so that the tests can hand them timestamps made by
hand. Times are seconds on one clock; what is reported is milliseconds."""
import math


def percentile(samples, q):
    """The nearest-rank percentile: the smallest sample with at least q% of
    the samples at or below it. No interpolation, so the value is one that
    was measured. None for no samples."""
    if not samples:
        return None
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(samples):
    return percentile(samples, 50)


def beyond(samples, q):
    """How many samples lie beyond the q-th percentile's rank. A tail with
    fewer than ten is close to a maximum; the runners print this count."""
    n = len(samples)
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def ttft_ms(requests, window_s):
    """Time to first token of every request, counted from when it was DUE,
    not from when the generator got round to submitting it, so a stalled
    loop shows in the requests behind it.

    requests: dicts with `due` and `first` (seconds from the window's
    start; `first` None while no token has come) and `failed`. One that was
    refused, shed or failed, or that had no token when the window ended,
    counts as the worst the window can show, its whole length."""
    worst = window_s * 1e3
    return [worst if r.get("failed") or r["first"] is None
            or r["first"] > window_s else (r["first"] - r["due"]) * 1e3
            for r in requests]


def gaps_ms(requests, window_s):
    """Every gap between successive tokens of one request that closed
    inside the window, all requests pooled: what a reader of the stream
    sees as stutter."""
    out = []
    for r in requests:
        times = [t for t in r["tokens"] if t <= window_s]
        out.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
    return out
