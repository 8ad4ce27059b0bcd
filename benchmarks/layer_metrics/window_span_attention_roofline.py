"""Attention kernel of the window layers, serving: the least time the chip
could take for the window attention of the traced dispatches
(reference/<model>.py `window_attention_cost`: min(context, window) keys a
query over the window layers, the pages the kernel visits read once a slot
a layer) over the time of the window_span_attention kernel ALONE, read by
its name, in per cent. The earlier line says which bound."""
from . import kda_chunk_roofline


def read(run, label=None):
    return kda_chunk_roofline.read(run, label, kernel="window_span_attention",
                                   cost_fn="window_attention_cost")
