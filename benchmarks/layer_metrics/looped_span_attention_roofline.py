"""Attention kernel of a model that runs its stack several times, serving:
the least time the chip could take for the paged attention of the traced
dispatches (reference/<model>.py `attention_cost`: every pass attends its
own cache layers, each live page's keys and values read once a slot a cache
layer) over the time of the ragged_span_attention kernel ALONE, read by its
name, in per cent. The earlier line says which bound."""
from . import kda_chunk_roofline


def read(run, label=None):
    return kda_chunk_roofline.read(run, label, kernel="ragged_span_attention",
                                   cost_fn="attention_cost")
