"""KV cache: of the token positions in leased pages, the share, in per
cent, that hold a token, as the mean over the steps of the window. A slot
leases every page of its longest possible sequence when it is admitted, so
this is what a reservation by the page in use could give back."""


def read(run, label=None):
    steps = run.facts.get("steps")
    if not steps or "total_pages" not in run.facts:
        return None
    total, size = run.facts["total_pages"], run.facts["page_size"]
    shares = [s[5] / ((total - s[4]) * size) for s in steps if s[4] < total]
    return 100.0 * sum(shares) / len(shares) if shares else None
