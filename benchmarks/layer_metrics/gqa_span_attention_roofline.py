"""Attention kernel under grouped KV heads, serving, in a program with
more than one kind of Mosaic kernel: the least time the chip could take for
the paged attention of the traced dispatches (reference/<model>.py
`attention_cost`: keys and values at the width of the KV heads, FLOPs of
the query heads) over the time of the span kernel ALONE, in per cent.
ragged_span_attention_roofline divides by every Mosaic call's time."""
from . import ssd_chunk_roofline


def read(run, label=None):
    return ssd_chunk_roofline.read(run, label, kernel="span",
                                   cost_fn="attention_cost")
