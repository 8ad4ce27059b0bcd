"""What the TPU's own compiler makes of the serving write-then-attend,
compiled for a DESCRIBED v5e (jax.experimental.topologies): shapes only,
nothing allocated, nothing run. It says what fits and which instructions
exist, never how fast.

The KV pool is stored (L, pages, S, H*D) so that the span kernel reads it
and write_decode scatters into it in place. The counter that says so is
the compiled program itself: no copy or slice as large as a layer of the
pool, next to no temporaries, and both pools aliased to their outputs.
With the pool ending in (H, D) the same program held four copies of a
whole pool and a padded copy of every layer (PERF.md, PR 25).

Every compile for the described chip lives in this one file, behind the
`one_chip` fixture: the worker that runs it loads the TPU's library, and
a second file could land on a worker that cannot.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import PagedKVCache
from mxnet_tpu.ops import pallas_attention as pa

# gpt2_774m.doc_backlog: 16 slots x 16 pages of 64 tokens, 20 heads of 64,
# one 64-row chunk a slot; two layers stand for the 36
LAYERS, SLOTS, PAGES_PER_SLOT, PAGE, H, D, SQ = 2, 16, 16, 64, 20, 64, 64
PAGES = SLOTS * PAGES_PER_SLOT
LAYER_ELEMS = PAGES * PAGE * H * D


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        # a v5e host is described four chips at a time; one is used
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _write_then_attend(kp, vp, ks, vs, table, lengths, spans, lock, q, k, v):
    """What a GPT-2 layer does to the cache in the unified dispatch
    (models/gpt2.py), LAYERS times: write this chunk's K/V, attend."""
    cache = PagedKVCache(kp, vp, table, lengths, page_lock=lock, spans=spans,
                         k_scale=ks, v_scale=vs, attn_impl="pallas")
    for layer in range(LAYERS):
        cache = cache.write_decode(layer, k, v)
        out = pa.ragged_span_attention(
            q.transpose(0, 2, 1, 3), cache.k_pages, cache.v_pages, table,
            lengths + 1, q_counts=spans, impl="pallas",
            k_scale=cache.k_scale, v_scale=cache.v_scale, layer=layer)
        q = k = v = out.transpose(0, 2, 1, 3)
    return cache.k_pages, cache.v_pages, cache.k_scale, cache.v_scale, out


def _big_instructions(hlo):
    """(opcode, shape) of every copy, slice or transpose whose result is
    at least one layer of the pool large."""
    found = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|transpose)\(",
            hlo):
        if np.prod([int(d) for d in m.group(1).split(",")]) >= LAYER_ELEMS:
            found.append((m.group(2), m.group(1)))
    return found


@pytest.mark.parametrize("page_dtype", ["bfloat16", "int8"])
def test_write_then_attend_works_on_the_pool_in_place(one_chip, page_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    quant = page_dtype == "int8"
    pool = sds((LAYERS, PAGES, PAGE, H * D), page_dtype)
    scale = sds((LAYERS, PAGES, H), "float32") if quant else None
    x = sds((SLOTS, H, SQ, D), "bfloat16")
    compiled = jax.jit(
        _write_then_attend,
        donate_argnums=(0, 1, 2, 3) if quant else (0, 1)).lower(
            pool, pool, scale, scale,
            sds((SLOTS, PAGES_PER_SLOT), "int32"), sds((SLOTS,), "int32"),
            sds((SLOTS,), "int32"), sds((PAGES,), "bool"), x, x, x).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == LAYERS
    assert _big_instructions(hlo) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    pools = 2 * LAYER_ELEMS * LAYERS * jnp.dtype(page_dtype).itemsize
    scales = 2 * LAYERS * PAGES * H * 4 if quant else 0
    assert mem.alias_size_in_bytes == pools + scales
