"""Attention kernel over a cache row with no head axis, serving: the least
time the chip could take for the latent attention of the traced dispatches
(reference/<model>.py `attention_cost`: scores over the whole stored row
and values over its latent part for every query head, each live page's
rows read once a slot a layer) over the time of the latent_span_attention
kernel ALONE, read by its name, in per cent."""
from . import kda_chunk_roofline


def read(run, label=None):
    return kda_chunk_roofline.read(run, label, kernel="latent_span_attention",
                                   cost_fn="attention_cost")
