"""What the TPU's own compiler makes of the serving write-then-attend,
compiled for a DESCRIBED v5e (jax.experimental.topologies): shapes only,
nothing allocated, nothing run. It says what fits and which instructions
exist, never how fast.

The KV pool is stored (L, pages, S, H*D) so that the span kernel reads it
and write_decode writes into it in place: float pools a page at a time
through the Mosaic call kv_page_write (the pools aliased to its results),
int8 pools by the row scatter. The counter that says so is the compiled
program itself: no copy or slice as large as a layer of the pool, next to
no temporaries, and both pools aliased to their outputs.
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
from mxnet_tpu.ops import kernel_paths
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


def _since(now, was):
    """What a counter of kernel_paths counted since the copy `was`."""
    return {k: n - was.get(k, 0) for k, n in now.items()
            if n != was.get(k, 0)}


def _tiles_since(before):
    """The blocks the span kernel calls traced since `before` (a copy of
    kernel_paths.TILES) were built with: {tile: calls}."""
    return {tile: n for (kernel, tile), n in
            _since(kernel_paths.TILES, before).items()
            if kernel == "ragged_span_attention"}


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
    tiles, paths = dict(kernel_paths.TILES), dict(kernel_paths.PATHS)
    compiled = jax.jit(
        _write_then_attend,
        donate_argnums=(0, 1, 2, 3) if quant else (0, 1)).lower(
            pool, pool, scale, scale,
            sds((SLOTS, PAGES_PER_SLOT), "int32"), sds((SLOTS,), "int32"),
            sds((SLOTS,), "int32"), sds((PAGES,), "bool"), x, x, x).compile()
    hlo = compiled.as_text()
    # a span kernel a layer and, over float pages, a page write a layer
    # (int8 pages keep the row scatter: their scale leaves are a
    # scatter-max)
    assert hlo.count("tpu_custom_call") == (LAYERS if quant else 2 * LAYERS)
    assert _since(kernel_paths.PATHS, paths) == {
        ("ragged_span_attention", "pallas"): LAYERS,
        ("kv_page_write", "xla" if quant else "pallas"): LAYERS}
    assert _big_instructions(hlo) == []
    # the block the adaptive rule documents for 64 rows a head and 16
    # pages of 64 a slot: all of them, 1024 keys a grid step, float and
    # int8 pages alike
    assert _tiles_since(tiles) == {"pages=16,keys=1024,rows=64": LAYERS}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    pools = 2 * LAYER_ELEMS * LAYERS * jnp.dtype(page_dtype).itemsize
    scales = 2 * LAYERS * PAGES * H * 4 if quant else 0
    assert mem.alias_size_in_bytes == pools + scales


def _expert_ffn_in_vmem(hlo):
    """Of every expert_ffn Mosaic call in a compiled program: the operands
    after the grid's size and the three scalar-prefetch tables (the pairs'
    rows and weights, the float32 rows, the experts' matrices) and the
    result that XLA placed in VMEM, `S(1)` in the layout of what defines
    them. Such buffers can lie inside the kernel's own scoped region, where
    its scratch overwrites them: the model's logits were wrong on the chip
    though the kernel alone was exact (PERF.md, PR 41)."""
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", hlo,
                             re.M))
    found, calls = [], 0
    for m in re.finditer(r"^\s*(?:ROOT )?(%expert_ffn[\w.\-]*) = (\S+) "
                         r"custom-call\((.*?)\), custom_call_target", hlo,
                         re.M):
        calls += 1
        ops = re.sub(r"/\*index=\d+\*/", "", m.group(3)).split(", ")
        for name, shape in [(o, shapes.get(o, "")) for o in ops[4:]] + [
                (m.group(1), m.group(2))]:
            if "S(1)" in shape:
                found.append((m.group(1), name, shape))
    assert calls, "no expert_ffn call found"
    return found


# falcon_h1_34b.chat_backlog: the whole unified greedy program of
# ServingEngine at the cell's own engine block and model kwargs
# (benchmarks/configs/falcon_h1_34b.json), weights described, not made
def _described(shape, dtype):
    """An NDArray that holds a shape and a type and no values."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    arr = NDArray.__new__(NDArray)
    arr._data = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    arr._node = arr._grad = None
    arr._grad_req, arr._version = "null", 0
    return arr


def test_falcon_h1_unified_program_donates_pages_and_state(one_chip):
    """One donated pytree holds both page pools and both recurrent-state
    pools, all aliased to the outputs; the program holds no copy or slice
    as large as a layer of either; and it leaves the 1 GB spare that
    `assumed.num_slots` of the cell's configuration asks for. Prints the
    arguments and temporaries that text cites."""
    import json
    from mxnet_tpu import models
    from mxnet_tpu.serving import ServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "falcon_h1_34b.json")) as f:
        cfg = json.load(f)
    kw, ekw = cfg["model"]["kwargs"], cfg["engine"]
    net = models.FalconH1ForCausalLM(models.falcon_h1_34b_config(**kw))
    for p in net.collect_params().values():
        p._data = _described(p.shape, kw["dtype"])
    eng = ServingEngine(net, attn_impl="pallas", **ekw)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    slots, width = ekw["num_slots"], ekw["chunk_tokens"]
    row = lambda dtype: jax.ShapeDtypeStruct((slots,), jnp.dtype(dtype),
                                             sharding=one_chip)
    state = eng._device_state()
    assert sorted(state) == ["k", "rec", "v"]
    tiles = dict(kernel_paths.TILES)
    compiled = eng._build_unified(greedy_only=True).lower(
        tuple(sds(p.data()._data) for p in eng._params),
        jax.tree_util.tree_map(sds, state), sds(eng._dstate[-1]),
        sds(eng._d_lock), *[sds(a) for a in eng._dstate[:11]],
        jax.ShapeDtypeStruct((slots, width), jnp.int32, sharding=one_chip),
        row("int32"), row("bool"), row("bool")).compile()
    hlo = compiled.as_text()
    layers = kw["num_layers"]
    # a page write, a span kernel and a chunk update a layer
    assert hlo.count("tpu_custom_call") == 3 * layers
    # the head sees the one row a slot samples: no logits of all the rows
    assert f"[{slots},{kw['vocab_size']}]" in hlo
    assert f"[{slots},{width},{kw['vocab_size']}]" not in hlo
    # 5 query heads a KV head stack 320 rows: 512 KiB of float32 scores
    # a head leave room for four pages, 256 keys, a grid step (P = 10)
    assert _tiles_since(tiles) == {"pages=4,keys=256,rows=320": layers}
    leaves = jax.tree_util.tree_leaves(state)
    # a pool, or one layer of one (an activation can be as large as a
    # layer of pages: told apart by the dimensions themselves)
    # (the convolution tails, 1 MB a layer, are read and written whole)
    large = [a for a in leaves if a.nbytes // layers > 8 << 20]
    pools = {a.shape for a in large} | {a.shape[1:] for a in large}
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|transpose)\(", hlo)
        if tuple(int(d) for d in m.group(1).split(",")
                 if d != "1") in pools]
    assert moved == []
    mem = compiled.memory_analysis()
    # every leaf aliased; the 8 bytes of the live-rows counter pad to a tile
    assert 0 <= mem.alias_size_in_bytes - sum(a.nbytes for a in leaves) \
        < 4096
    assert eng.stats["recurrent_state_bytes"] == sum(
        a.nbytes for k, a in state["rec"].items() if k != "live_rows")
    print(f"falcon_h1_34b unified greedy program, {slots} slots, {layers} "
          f"layers: {mem.argument_size_in_bytes / 1e9:.3f} GB of arguments, "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB aliased")
    # the allocator's limit on the chip is 16.91e9 bytes (PERF.md)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + 1e9 \
        < 16.91e9


def test_nemotron_h_unified_program_holds_state_by_layer_kind(one_chip):
    """nemotron3_super_120b.doc_backlog's whole unified greedy program at
    the cell's own engine block and model kwargs, weights described: pages
    for the ONE attention layer, recurrent leaves for the FIVE mixers, a
    row of counters for each of the five expert layers, all one donated
    pytree aliased to the outputs; twelve Mosaic calls (five chunk updates
    at head size 64, five grouped expert feed-forwards, one page write and
    one span kernel at 16 query heads a KV head), none refused by the
    chip's compiler, and no array at the bound of rows x choices around the
    expert kernel; and it leaves the 1 GB spare that `assumed.num_slots`
    asks for. Prints the arguments and temporaries that `PERF.md` cites."""
    import json
    from mxnet_tpu import models
    from mxnet_tpu.serving import ServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "nemotron3_super_120b.json")) as f:
        cfg = json.load(f)
    kw, ekw = cfg["model"]["kwargs"], cfg["engine"]
    net = models.NemotronHForCausalLM(
        models.nemotron3_super_120b_config(**kw))
    for p in net.collect_params().values():
        p._data = _described(p.shape, kw["dtype"])
    eng = ServingEngine(net, attn_impl="pallas", **ekw)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    slots, width = ekw["num_slots"], ekw["chunk_tokens"]
    row = lambda dtype: jax.ShapeDtypeStruct((slots,), jnp.dtype(dtype),
                                             sharding=one_chip)
    state = eng._device_state()
    pages = slots * ekw["max_length"] // ekw["page_size"]
    assert state["k"].shape == (1, pages, 64, 2 * 128)
    assert {k: v.shape for k, v in state["rec"].items()} == {
        "conv": (5, slots, 3, 8192 + 2 * 8 * 128),
        "ssm": (5, slots, 128, 64, 128), "moe": (5, 5), "live_rows": (2,)}
    st = eng.stats
    assert st["recurrent_state_bytes"] == 5 * slots * (
        128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert st["kv_page_bytes"] == 2 * 1 * 64 * 256 * 2
    assert st["expert_weight_bytes"] == 5 * 128 * 2 * 1024 * 2688 * 2
    before = (dict(kernel_paths.PATHS), dict(kernel_paths.TILES))
    compiled = eng._build_unified(greedy_only=True).lower(
        tuple(sds(p.data()._data) for p in eng._params),
        jax.tree_util.tree_map(sds, state), sds(eng._dstate[-1]),
        sds(eng._d_lock), *[sds(a) for a in eng._dstate[:11]],
        jax.ShapeDtypeStruct((slots, width), jnp.int32, sharding=one_chip),
        row("int32"), row("bool"), row("bool")).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 12
    assert _since(kernel_paths.PATHS, before[0]) == {
        ("ssd_chunk_update", "pallas"): 5, ("expert_ffn", "pallas"): 5,
        ("kv_page_write", "pallas"): 1,
        ("ragged_span_attention", "pallas"): 1}
    # an expert's two matrices whole in a grid step (2 x 5.5 MB, double
    # buffered): consecutive visits by one expert fetch them once
    assert _since(kernel_paths.TILES, before[1]) == {
        ("expert_ffn", "rows=128,hidden=2688"): 5,
        ("ragged_span_attention", "pages=2,keys=128,rows=1024"): 1}
    # the kernel gathers each pair's row and hands back each row's weighted
    # sum: no array at the bound of 2048 rows x 22 choices on either side
    for shape in ("[45056,1024]", "[2048,22,1024]"):
        assert shape not in hlo
    assert _expert_ffn_in_vmem(hlo) == []
    assert f"[{slots},{kw['vocab_size']}]" in hlo
    mem = compiled.memory_analysis()
    leaves = jax.tree_util.tree_leaves(state)
    # every leaf aliased; the 108 bytes of the two counters pad to a tile
    # each, of 4 KiB at most
    assert 0 <= mem.alias_size_in_bytes - sum(a.nbytes for a in leaves) \
        < 2 * 4096
    print(f"nemotron3_super_120b unified greedy program, {slots} slots, "
          f"{len(kw['pattern'])} layers: "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB of arguments, "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB aliased")
    assert mem.argument_size_in_bytes > 10e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + 1e9 \
        < 16.91e9


def test_kimi_linear_unified_program_holds_one_pool_and_kda_state(one_chip):
    """kimi_linear_48b.longdoc_backlog's whole unified greedy program at
    the cell's own engine block and model kwargs, weights described: ONE
    page pool 640 wide for the two latent layers (no V pool), recurrent
    leaves for the seven KDA layers, a row of counters for each of the
    eight expert layers, all one donated pytree aliased to the outputs;
    nineteen Mosaic calls (seven chunk updates of the delta rule, eight
    gated expert feed-forwards, two one-pool page writes, two latent span
    kernels at 2048 stacked rows), none refused by the chip's compiler, and
    no array at the bound of rows x choices around the expert kernel; and
    it leaves more than the 1 GB spare. Prints the arguments and
    temporaries that `PERF.md` cites."""
    import json
    from mxnet_tpu import models
    from mxnet_tpu.serving import ServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kimi_linear_48b.json")) as f:
        cfg = json.load(f)
    kw, ekw = cfg["model"]["kwargs"], cfg["engine"]
    net = models.KimiLinearForCausalLM(models.kimi_linear_48b_config(**kw))
    for p in net.collect_params().values():
        p._data = _described(p.shape, kw["dtype"])
    eng = ServingEngine(net, attn_impl="pallas", **ekw)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    slots, width = ekw["num_slots"], ekw["chunk_tokens"]
    row = lambda dtype: jax.ShapeDtypeStruct((slots,), jnp.dtype(dtype),
                                             sharding=one_chip)
    state = eng._device_state()
    pages = slots * ekw["max_length"] // ekw["page_size"]
    assert sorted(state) == ["k", "rec"]                # no "v"
    assert state["k"].shape == (2, pages, 64, 640)
    assert {k: v.shape for k, v in state["rec"].items()} == {
        "conv": (7, slots, 3, 3 * 4096), "kda": (7, slots, 32, 128, 128),
        "moe": (8, 5), "live_rows": (2,)}
    st = eng.stats
    assert st["recurrent_state_bytes"] == 7 * slots * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert st["kv_page_bytes"] == 1 * 2 * 64 * 640 * 2    # one pool
    assert st["kv_pool_bytes"] == 2 * pages * 64 * 640 * 2
    assert st["expert_weight_bytes"] == 8 * 64 * 3 * 2304 * 1024 * 2
    before = (dict(kernel_paths.PATHS), dict(kernel_paths.TILES))
    compiled = eng._build_unified(greedy_only=True).lower(
        tuple(sds(p.data()._data) for p in eng._params),
        jax.tree_util.tree_map(sds, state), sds(eng._dstate[-1]),
        sds(eng._d_lock), *[sds(a) for a in eng._dstate[:11]],
        jax.ShapeDtypeStruct((slots, width), jnp.int32, sharding=one_chip),
        row("int32"), row("bool"), row("bool")).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 19
    assert _since(kernel_paths.PATHS, before[0]) == {
        ("kda_chunk_update", "pallas"): 7, ("expert_ffn", "pallas"): 8,
        ("kv_page_write", "pallas"): 2,
        ("latent_span_attention", "pallas"): 2}
    # an expert's three matrices whole in a grid step (14.2 MB, double
    # buffered); 1024 keys a grid step of the latent kernel (PERF.md, PR 34)
    assert _since(kernel_paths.TILES, before[1]) == {
        ("kda_chunk_update", "heads=16,rows=64,block=16,pass=8"): 7,
        ("expert_ffn", "rows=128,hidden=1024"): 8,
        ("latent_span_attention",
         "pages=16,keys=1024,rows=2048,tile=256"): 2}
    # no array at the bound of 2048 rows x 8 choices on either side of the
    # expert kernel
    for shape in ("[16384,2304]", "[2048,8,2304]"):
        assert shape not in hlo
    assert _expert_ffn_in_vmem(hlo) == []
    assert f"[{slots},{kw['vocab_size']}]" in hlo
    mem = compiled.memory_analysis()
    leaves = jax.tree_util.tree_leaves(state)
    # every leaf aliased; the 168 bytes of the two counters pad to a tile
    # each, of 4 KiB at most
    assert 0 <= mem.alias_size_in_bytes - sum(a.nbytes for a in leaves) \
        < 2 * 4096
    print(f"kimi_linear_48b unified greedy program, {slots} slots, "
          f"{len(kw['pattern'])} layers: "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB of arguments, "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB aliased")
    assert mem.argument_size_in_bytes > 11e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + 1e9 \
        < 16.91e9
    # the cell's own check runs the one-slot paged path: the delta rule's
    # kernel once more at Bt = 1, a grid of (1, 2)
    from mxnet_tpu.ops.kda import kda_chunk_update
    one = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one_chip)
    rows = one((1, width, 32, 128), kw["dtype"])
    alone = jax.jit(lambda q, k, v, g, beta, pool, counts: kda_chunk_update(
        q, k, v, g, beta, pool, counts, 3, impl="pallas")).lower(
        rows, rows, rows, one(rows.shape, "float32"),
        one((1, width, 32), "float32"), one((7, 1, 32, 128, 128), "float32"),
        one((1,), "int32")).compile()
    assert alone.as_text().count("tpu_custom_call") == 1


def test_ouro_unified_program_carries_its_pools_in_place_through_the_loop(
        one_chip):
    """ouro_2_6b.fewshot_backlog's whole unified greedy program at the
    cell's own engine block and model kwargs, weights described: all 48
    blocks traced ONCE as the body of a `while` of four passes (96 Mosaic
    calls: a page write and a span kernel a block, not 384), K and V pools
    of 192 cache layers as one donated pytree aliased to the outputs and
    carried through the loop IN PLACE: no copy, slice or update as large as
    a cache layer, and temporaries a hundredth of a pool. Prints the
    arguments and temporaries that `PERF.md` cites. The engine is BUILT
    with one page a slot (its pools would be 8 GB of this host's memory)
    and the program lowered at the cell's 16: a program's shapes are its
    arguments'."""
    import json
    from mxnet_tpu import models
    from mxnet_tpu.serving import ServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    kw, ekw = cfg["model"]["kwargs"], cfg["engine"]
    net = models.OuroForCausalLM(models.ouro_2_6b_config(**kw))
    for p in net.collect_params().values():
        p._data = _described(p.shape, kw["dtype"])
    slots, width, page = (ekw["num_slots"], ekw["chunk_tokens"],
                          ekw["page_size"])
    per_slot = ekw["max_length"] // page
    eng = ServingEngine(net, attn_impl="pallas",
                        **{**ekw, "max_length": page})
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    one = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one_chip)
    row = lambda dtype: one((slots,), dtype)
    state = eng._device_state()
    assert sorted(state) == ["k", "rec", "v"]
    assert state["k"].shape == (192, slots, page, 16 * 128)
    pool = one((192, slots * per_slot, page, 16 * 128), kw["dtype"])
    described = {"k": pool, "v": pool,
                 "rec": jax.tree_util.tree_map(sds, state["rec"])}
    st = eng.stats
    assert (st["kv_layers"], st["loop_steps"]) == (192, 4)
    assert st["kv_bytes_per_token"] == 1_572_864
    before = (dict(kernel_paths.PATHS), dict(kernel_paths.TILES))
    compiled = eng._build_unified(greedy_only=True).lower(
        tuple(sds(p.data()._data) for p in eng._params), described,
        one((slots, per_slot), "int32"), one((slots * per_slot,), "bool"),
        *[sds(a) for a in eng._dstate[:11]], one((slots, width), "int32"),
        row("int32"), row("bool"), row("bool")).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2 * 48
    assert len(re.findall(r" while\(", hlo)) == 1
    assert _since(kernel_paths.PATHS, before[0]) == {
        ("kv_page_write", "pallas"): 48,
        ("ragged_span_attention", "pallas"): 48}
    # GPT-2 774M's geometry: every head its own KV head, all 16 pages a step
    assert _since(kernel_paths.TILES, before[1]) == {
        ("ragged_span_attention", "pages=16,keys=1024,rows=64"): 48}
    assert f"[{slots},{kw['vocab_size']}]" in hlo
    layer_elems = slots * per_slot * page * 16 * 128
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|transpose|"
        r"dynamic-update-slice)\(", hlo)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= layer_elems]
    assert moved == []
    mem = compiled.memory_analysis()
    pools = 2 * 192 * layer_elems * 2
    # both pools aliased; the 20 bytes of the exit counter pad to a tile
    assert 0 <= mem.alias_size_in_bytes - pools < 4096
    assert mem.temp_size_in_bytes < pools // 50
    print(f"ouro_2_6b unified greedy program, {slots} slots, 48 layers x 4 "
          f"passes: {mem.argument_size_in_bytes / 1e9:.3f} GB of arguments, "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB aliased")
    assert mem.argument_size_in_bytes > 13e9
    # the check's own one-slot cache (1.611 GB) beside it still fits
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + pools // slots + 1e9 < 16.91e9


def test_trinity_unified_program_keeps_pool_and_rings_in_place(one_chip):
    """trinity_mini.longdoc32k_backlog's whole unified greedy program at
    the cell's own engine block and model kwargs, weights described: ONE
    full layer's whole-depth K and V pools and the four window layers'
    rings of 34 pages a slot, all one donated pytree aliased to the outputs
    with the counters; fourteen Mosaic calls (a page write and a span
    kernel for the full layer, a page write into its ring and a
    `window_span_attention` for each window layer, four grouped expert
    feed-forwards), none refused by the chip's compiler; no copy, slice or
    update as large as a ring or a layer of the pool; temporaries under 2%
    of the chip; and it leaves more than 1 GB spare. Prints the arguments
    and temporaries that `PERF.md` cites. The engine is BUILT with one page
    a slot (its pool would be 2 GB of this host's memory) and the program
    lowered at the cell's 513: a program's shapes are its arguments'."""
    import json
    from mxnet_tpu import models
    from mxnet_tpu.serving import ServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "trinity_mini.json")) as f:
        cfg = json.load(f)
    kw, ekw = cfg["model"]["kwargs"], cfg["engine"]
    net = models.TrinityForCausalLM(models.trinity_mini_config(**kw))
    for p in net.collect_params().values():
        p._data = _described(p.shape, kw["dtype"])
    slots, width, page = (ekw["num_slots"], ekw["chunk_tokens"],
                          ekw["page_size"])
    per_slot = ekw["max_length"] // page
    eng = ServingEngine(net, attn_impl="pallas",
                        **{**ekw, "max_length": page})
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    one = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one_chip)
    row = lambda dtype: one((slots,), dtype)
    state = eng._device_state()
    assert sorted(state) == ["k", "rec", "v"]
    ring = (4, slots, 34, page, 4 * 128)
    assert {k: v.shape for k, v in state["rec"].items()} == {
        "ring_k": ring, "ring_v": ring, "moe": (4, 5), "live_rows": (2,),
        "window_keys": (2,)}
    pool = one((1, slots * per_slot, page, 4 * 128), kw["dtype"])
    described = {"k": pool, "v": pool,
                 "rec": jax.tree_util.tree_map(sds, state["rec"])}
    st = eng.stats
    ring_bytes = 2 * int(np.prod(ring)) * 2
    assert st["kv_ring_bytes"] == st["recurrent_state_bytes"] == ring_bytes
    assert round(ring_bytes / 1e9, 3) == 0.570
    assert (st["kv_layers"], st["kv_window_layers"], st["kv_ring_pages"]) \
        == (1, 4, 34)
    before = (dict(kernel_paths.PATHS), dict(kernel_paths.TILES))
    compiled = eng._build_unified(greedy_only=True).lower(
        tuple(sds(p.data()._data) for p in eng._params), described,
        one((slots, per_slot), "int32"), one((slots * per_slot,), "bool"),
        *[sds(a) for a in eng._dstate[:11]], one((slots, width), "int32"),
        row("int32"), row("bool"), row("bool")).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 14
    assert _since(kernel_paths.PATHS, before[0]) == {
        ("kv_page_write", "pallas"): 5, ("expert_ffn", "pallas"): 4,
        ("ragged_span_attention", "pallas"): 1,
        ("window_span_attention", "pallas"): 4}
    # 8 query heads a KV head stack 512 rows: 512 KiB of float32 scores a
    # head leave room for four pages a grid step; the window kernel's grid
    # spans the ring's 34 pages, 9 steps a slot, and not the 513 a slot has
    assert _since(kernel_paths.TILES, before[1]) == {
        ("expert_ffn", "rows=128,hidden=1024"): 4,
        ("ragged_span_attention", "pages=4,keys=256,rows=512"): 1,
        ("window_span_attention", "pages=4,keys=256,rows=512"): 4}
    assert len(re.findall(r"%window_span_attention[\w.\-]* = ", hlo)) == 4
    assert _expert_ffn_in_vmem(hlo) == []
    assert f"[{slots},{kw['vocab_size']}]" in hlo
    ring_elems = int(np.prod(ring[1:]))
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|slice|dynamic-slice|transpose|"
        r"dynamic-update-slice)\(", hlo)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= ring_elems]
    assert moved == []
    mem = compiled.memory_analysis()
    leaves = jax.tree_util.tree_leaves(described)
    # every leaf aliased; the three counters pad to a tile each
    assert 0 <= mem.alias_size_in_bytes - sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) < 3 * 4096
    assert mem.temp_size_in_bytes < 0.02 * 16e9
    print(f"trinity_mini unified greedy program, {slots} slots, 5 layers: "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB of arguments, "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB aliased")
    assert mem.argument_size_in_bytes > 11e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + 1e9 \
        < 16.91e9


# The span kernel with no window, lowered for a described v5e at the cells'
# geometries: sha256 of its Mosaic module printed WITHOUT debug information
# (a location names the source file and line, which move with any edit) and
# of the program around it with the module's bytes taken out, as the commit
# before the window kernel lowered them. A window is a kernel of its own
# (`window_span_attention`); these are every other model's, and must not
# move. A change to the span kernel itself updates them.
SPAN_LOWERED = {
    # (slots, rows, query heads, KV heads, head size, pages a slot, pages)
    (16, 64, 20, 20, 64, 16, "bfloat16"): (
        "0e65351ec13e9c6256285a5db3c1c77f0085b82a18cc7eae6822cf6c99d402b9",
        "02f695fefaccb1d5f9849dcb9857c02081eeb6aea595ca73191a9427ac18bf4d"),
    (5, 64, 16, 16, 128, 16, "bfloat16"): (
        "505949d0c2c29ff61fb89be287079fb5e60a6a757e453a9f8ecb74d3a471e8d7",
        "99517d3e4b5965f9e14398206e04d546ee4188ea35fa6e2e32dbf4db82175fa9"),
    (32, 64, 20, 4, 128, 10, "bfloat16"): (
        "919607d535cc0b60d62d499f5fd635589bd6f7c8d8dd36129b3a8d2806fee4a8",
        "b4ad310367d9e08fa6bb4b1bf2d0b0a7079fa4f9971144cbb8706d71dc6eb05a"),
    (32, 64, 32, 4, 128, 513, "bfloat16"): (
        "62fc228340ddf4875902d7064d2ddffd7b9541967d7b1edad27d5235fe90f656",
        "58c322003ca73b2d13950c84770dd3b3bcd4bc2716f6e807ce5820d48dbe60a3"),
    (16, 64, 20, 20, 64, 16, "int8"): (
        "227f7fd33dbea46b5a9bc3b0e009c4da3ee3d00cc38d3e5df7e966cf7df3be3c",
        "f959c8c6088ea9bbbee5d0db74d468ae82c95f474fd2704a8c686ee1ea4caeb1"),
}


def _mosaic_modules(text):
    """(the Mosaic modules of a lowered program's custom calls, printed
    without debug information; the program's text with their bytes out)."""
    import base64
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    body = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)'
    modules = []
    for blob in re.findall(body, text):
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        tpu.register_dialect(ctx)
        with ctx:
            modules.append(ir.Module.parse(base64.b64decode(blob))
                           .operation.get_asm(enable_debug_info=False))
    return modules, re.sub(body, "", text)


@pytest.mark.parametrize("geometry", sorted(SPAN_LOWERED, key=str),
                         ids=lambda g: "x".join(map(str, g)))
def test_the_span_kernel_without_a_window_lowers_as_before(one_chip,
                                                           geometry):
    import hashlib
    B, Sq, Hq, Hkv, D, P, dtype = geometry
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                                 sharding=one_chip)
    quant = dtype == "int8"
    pool = sds((2, B * P, 64, Hkv * D), dtype)
    scale = sds((2, B * P, Hkv), "float32") if quant else None
    text = jax.jit(lambda q, kp, vp, t, l, c, ks, vs:
                   pa.ragged_span_attention(
                       q, kp, vp, t, l, q_counts=c, impl="pallas", layer=1,
                       num_kv_heads=Hkv, k_scale=ks, v_scale=vs)).lower(
        sds((B, Sq, Hq, D), "float32" if quant else "bfloat16"), pool, pool,
        sds((B, P), "int32"), sds((B,), "int32"), sds((B,), "int32"),
        scale, scale).as_text()
    modules, around = _mosaic_modules(text)
    assert len(modules) == 1 and "module @ragged_span_attention" in \
        modules[0]
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()
    assert (digest(modules[0]), digest(around)) == SPAN_LOWERED[geometry]
