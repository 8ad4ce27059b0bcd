"""Attention kernel of the full layers of a model whose other layers see a
window, serving: the least time the chip could take for the full layers'
paged attention of the traced dispatches (reference/<model>.py
`attention_cost`: every earlier key a query, each live page's keys and
values read once a slot a layer) over the time of the ragged_span_attention
kernel ALONE, read by its name, in per cent. The window layers' calls carry
a name of their own (window_span_attention_roofline.py). The earlier line
says which bound."""
from . import kda_chunk_roofline


def read(run, label=None):
    return kda_chunk_roofline.read(run, label, kernel="ragged_span_attention",
                                   cost_fn="attention_cost")
