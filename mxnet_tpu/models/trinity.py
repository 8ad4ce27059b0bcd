"""Trinity (Arcee, `model_type: afmoe`; Trinity-Mini 26B-A3B): a decoder
whose attention layers are of two kinds in one model, WINDOW layers that
see the last `window` keys and FULL layers that see every key, and whose
feed-forwards after the first `dense_layers` are a mixture of experts with
a shared expert.

    h_0 = Embed(ids) * sqrt(units)                     (mup_enabled)
    block(h):  h = h + RMSNorm_a2(Attn(RMSNorm_a1(h)))
               h = h + RMSNorm_f2(FFN(RMSNorm_f1(h)))   (the "sandwich")
    Attn(x):   q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk) a head; v = x Wv;
               rotary on q and k in window layers only; GQA; a window
               layer's query at i sees keys i - window < j <= i;
               out = (concat_heads(attn) * sigmoid(x Wg)) Wo
    FFN:       a gated MLP (dense layers) or the routed experts and the
               shared one (models/kimi_linear.py's KimiMoE: sigmoid
               scores over all the experts, the top_k of score + bias,
               weights renormalised and times `routed_scaling_factor`)
    logits = RMSNorm(h) W_head

What a serving slot holds differs by kind (`state_spec()`): a full layer
whole-depth pages in the engine's pool (`kv_layers`), a window layer a
RING of `ring_pages` pages of its own, fixed in size (the recurrent leaves
`ring_k` and `ring_v`, kv_cache.PagedKVCache.ring): logical page n at ring
page n mod R. R covers a window and one dispatch's rows, at most a page a
slot (ops/pallas_attention.window_pages), so a dispatch writes its rows
before it reads and never overwrites a page a window still reads; hidden()
refuses a wider grid.

Reference parity: none in the reference framework;
benchmarks/reference/trinity.py is the same model in plain float32
jax.numpy, which the tests hold this file to. The blocks are
models/hybrid.py's Attention (qk_norm, output_gate, window), GatedMLP and
RMSNorm, and models/kimi_linear.py's KimiMoE.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import MOE_COUNTERS, Dense, Embedding
from ..ndarray.ndarray import NDArray
from .hybrid import (LIVE_ROWS_COUNTER, Attention, GatedMLP, RMSNorm,
                     count_live_rows, kernel_impl, linear, over_live_rows,
                     pick_live_rows, raw, require_recurrent_cache, rms_norm)
from .kimi_linear import KimiMoE
from .kv_cache import RING_LEAVES, PagedKVCache

__all__ = ["TrinityConfig", "TrinityForCausalLM", "trinity_mini_config"]

WINDOW, FULL = "sliding_attention", "full_attention"


class TrinityConfig:
    """`layer_types` gives each layer's attention its kind, as config.json
    does (`sliding_attention` or `full_attention`). `num_heads` query heads
    over `num_kv_heads` KV heads of `head_dim`; the first `dense_layers`
    feed-forwards are gated MLPs `dense_hidden_size` wide, every other
    `num_experts` routed experts, `top_k` a token, `expert_hidden_size`
    wide, and one shared expert `shared_hidden_size` wide, every expert
    held. A window layer's ring is of pages of `ring_page_size` rows and
    holds a window and a dispatch of at most a page of rows a slot."""

    def __init__(self, vocab_size=1024, units=256,
                 layer_types=(WINDOW, WINDOW, WINDOW, FULL), num_heads=4,
                 num_kv_heads=2, head_dim=64, dense_layers=1,
                 dense_hidden_size=512, num_experts=8, top_k=2,
                 expert_hidden_size=128, shared_hidden_size=128,
                 routed_scaling_factor=1.0, window=64, rope_theta=1e4,
                 rms_norm_eps=1e-5, max_length=4096, ring_page_size=64,
                 dtype="float32"):
        layer_types = list(layer_types)
        if not layer_types or set(layer_types) - {WINDOW, FULL}:
            raise MXNetError(f"layer_types {layer_types}: {WINDOW!r} or "
                             f"{FULL!r} a layer")
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads")
        if window < 1 or ring_page_size < 1:
            raise MXNetError(f"window {window} and ring_page_size "
                             f"{ring_page_size} must be >= 1")
        self.vocab_size = vocab_size
        self.units = units
        self.layer_types = layer_types
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dense_layers = dense_layers
        self.dense_hidden_size = dense_hidden_size
        self.num_experts = num_experts
        self.top_k = top_k
        # what models/kimi_linear.py's KimiMoE reads: every expert held
        self.held_experts = (0, num_experts)
        self.expert_hidden_size = expert_hidden_size
        self.shared_hidden_size = shared_hidden_size
        self.routed_scaling_factor = routed_scaling_factor
        self.window = window
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.max_length = max_length
        self.ring_page_size = ring_page_size
        self.dtype = dtype

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def ring_pages(self):
        # the kernels' module loads Pallas: imported where it is asked
        from ..ops.pallas_attention import window_pages
        return window_pages(self.ring_page_size, self.ring_page_size,
                            self.window)


def trinity_mini_config(**kw):
    """arcee-ai/Trinity-Mini, config.json: every published size, all 32
    layers (a full layer every fourth, from the fourth) and all 128
    experts unless told otherwise."""
    for k, v in dict(
            vocab_size=200192, units=2048,
            layer_types=[WINDOW, WINDOW, WINDOW, FULL] * 8, num_heads=32,
            num_kv_heads=4, head_dim=128, dense_layers=2,
            dense_hidden_size=6144, num_experts=128, top_k=8,
            expert_hidden_size=1024, shared_hidden_size=1024,
            routed_scaling_factor=2.826, window=2048, rope_theta=1e4,
            rms_norm_eps=1e-5, max_length=131072).items():
        kw.setdefault(k, v)
    return TrinityConfig(**kw)


class TrinityBlock(HybridBlock):
    """One layer, four norms: each branch's input and its output. `index`
    is the attention's place among the layers of its own kind (its ring
    layer or page layer), `expert_index` the feed-forward's among the
    expert layers (its counter row; None for a dense one)."""

    def __init__(self, c, kind, index, expert_index, **kwargs):
        super().__init__(**kwargs)
        self._c, self.kind, self.index = c, kind, index
        self.expert_index = expert_index
        window = kind == WINDOW
        self.attn_norm = RMSNorm(c.units)
        self.attn = Attention(
            c.units, c.num_heads, c.num_kv_heads, c.head_dim, rotary=window,
            rope_theta=c.rope_theta, qk_norm=True, output_gate=True,
            window=c.window if window else None, norm_eps=c.rms_norm_eps)
        self.attn_out_norm = RMSNorm(c.units)
        self.ffn_norm = RMSNorm(c.units)
        self.ffn = GatedMLP(c.units, c.dense_hidden_size) \
            if expert_index is None else KimiMoE(c)
        self.ffn_out_norm = RMSNorm(c.units)

    def forward(self, h, cache, positions, live, pick):
        eps = self._c.rms_norm_eps
        norm = lambda x, n: rms_norm(x, raw(n.weight), eps)
        scope = "attn.window" if self.kind == WINDOW else "attn.global"
        with jax.named_scope(scope):
            a, cache = self.attn.forward(norm(h, self.attn_norm), cache,
                                         self.index, positions)
        h = h + norm(a, self.attn_out_norm)
        u = norm(h, self.ffn_norm)
        if self.expert_index is None:
            f = over_live_rows(self.ffn.forward, u, live, pick)
        elif cache is None:
            f, _ = self.ffn.forward(u, live)
        else:
            f, counts = self.ffn.forward(u, live, **kernel_impl(cache))
            rec = cache.recurrent
            cache = cache.with_recurrent(dict(
                rec, moe=rec["moe"].at[self.expert_index].add(counts)))
        return h + norm(f, self.ffn_out_norm), cache


class TrinityForCausalLM(HybridBlock):
    """Trinity with its untied LM head, behind the engine's contract:
    `hidden(ids, cache)`, `head(h)`, `state_spec()`, `make_cache()`.
    Nothing model-specific is passed to serving.ServingEngine."""

    def __init__(self, config: TrinityConfig, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        seen, experts = {}, 0
        for i, kind in enumerate(c.layer_types):
            dense = i < c.dense_layers
            self.register_child(
                TrinityBlock(c, kind, seen.setdefault(kind, 0),
                             None if dense else experts),
                name=f"layer{i}")
            seen[kind] += 1
            experts += not dense
        self.final_norm = RMSNorm(c.units)
        # registered like the layers, with no attribute: `head` is the
        # method below, the parameter stays "head.weight"
        self.register_child(Dense(c.vocab_size, use_bias=False,
                                  flatten=False, in_units=c.units),
                            name="head")

    def blocks(self, kind=None):
        return [child for name, child in self._children.items()
                if name.startswith("layer")
                and (kind is None or child.kind == kind)]

    def state_spec(self):
        """What a serving slot holds: whole-depth pages for the `kv_layers`
        full layers; for the `window_layers` window layers a ring of
        `ring_pages` pages of `ring_page_size` rows, K and V, as recurrent
        leaves (RING_LEAVES) of `recurrent_layers` = window_layers layers.
        `counters` are not a slot's: whole cumulative leaves kept with the
        state (a row an expert layer, MOE_COUNTERS; the dense feed-forwards'
        dispatches and compact ones, LIVE_ROWS_COUNTER; `window_keys`,
        [pages the window layers' calls visited, pages of the same rows'
        whole context], summed over the window layers, each a page of
        `ring_page_size` key positions). `refuses`: what a ring or the
        blocks of models/hybrid.py do not carry, with the reason."""
        c = self.config
        windows, full = self.blocks(WINDOW), self.blocks(FULL)
        experts = [b for b in self.blocks() if b.expert_index is not None]
        held = sum(raw(p).size * jnp.dtype(raw(p).dtype).itemsize
                   for b in experts for p in (b.ffn.experts.expert_w1,
                                              b.ffn.experts.expert_w2))
        ring = ((c.ring_pages, c.ring_page_size,
                 c.num_kv_heads * c.head_dim), c.dtype)
        rings = ("a window layer's ring holds the last {} pages of ONE "
                 "sequence and overwrites them as it advances: {}")
        hybrid = "the blocks of models/hybrid.py carry no {}"
        return {"num_layers": c.num_layers,
                "kv_layers": len(full),
                "recurrent_layers": len(windows),
                "num_kv_heads": c.num_kv_heads, "head_dim": c.head_dim,
                "recurrent": dict.fromkeys(RING_LEAVES, ring)
                if windows else {},
                "window": c.window, "window_layers": len(windows),
                "ring_pages": c.ring_pages,
                "counters": {
                    **({"moe": ((len(experts), len(MOE_COUNTERS)), "int32")}
                       if experts else {}),
                    **(LIVE_ROWS_COUNTER if c.dense_layers else {}),
                    "window_keys": ((2,), "int32")},
                "expert_weight_bytes": int(held),
                "refuses": {
                    "prefix_cache": rings.format(
                        c.ring_pages, "a prefix's ring is gone once its "
                        "sequence moves on, and the prefix cache shares "
                        "whole-depth pages only"),
                    "host_kv_bytes": rings.format(
                        c.ring_pages, "the spill tier ships whole-depth "
                        "K and V pages of the pool, which a ring is not"),
                    "speculative": "a verify window through the rings "
                                   "has no test here yet",
                    "kv_dtype": "int8 pages carry scale leaves by page and "
                                "head, which the rings have no room for",
                    "tp": hybrid.format("head split or partial-sum rule"),
                    "weight_dtype": hybrid.format("int8 weight plan"),
                    "adapter_pool": hybrid.format("adapter hook")}}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto"):
        """A ragged paged cache of the full layers' pages, with zeroed rings
        and counters, for `batch` slots (the serving engine builds its own
        from `state_spec`)."""
        c, spec = self.config, self.state_spec()
        rec = {k: jnp.zeros((spec["recurrent_layers"], batch) + shape, dt)
               for k, (shape, dt) in spec["recurrent"].items()}
        rec.update({k: jnp.zeros(shape, dt)
                    for k, (shape, dt) in spec["counters"].items()})
        return PagedKVCache.create(
            spec["kv_layers"], batch, c.num_heads, max_length, c.head_dim,
            dtype=dtype or jnp.dtype(c.dtype), page_size=page_size,
            page_table=page_table,
            lengths=jnp.zeros((batch,), jnp.int32) if lengths is None
            else lengths, attn_impl=attn_impl, num_kv_heads=c.num_kv_heads,
            recurrent=rec)

    def _count_window_keys(self, cache):
        """The cache with this dispatch's window_keys added: per slot with
        work, the pages its window calls visit (from the first query's
        first windowed page to the last query's page) and the pages its
        rows' whole context spans (page 0 to the same), times the window
        layers."""
        c, S = self.config, self.config.ring_page_size
        n_win = len(self.blocks(WINDOW))
        work = cache.spans > 0
        end = jnp.where(work, (cache.length + cache.spans + S - 1) // S, 0)
        first = jnp.where(work,
                          jnp.maximum(cache.length + 1 - c.window, 0) // S, 0)
        rec = cache.recurrent
        return cache.with_recurrent(dict(
            rec, window_keys=rec["window_keys"] + n_win * jnp.stack(
                [jnp.sum(end - first), jnp.sum(end)]).astype(jnp.int32)))

    def hidden(self, inputs, cache=None):
        """Everything up to and including the final norm: (B, T) ids ->
        ((B, T, C) hidden states, advanced cache)."""
        c = self.config
        ids = inputs._data if isinstance(inputs, NDArray) else inputs
        b, t = ids.shape
        steps = jnp.arange(t)[None, :]
        pick = None
        if cache is None:
            positions, live = jnp.broadcast_to(steps, (b, t)), \
                jnp.ones((b, t), bool)
        else:
            require_recurrent_cache(self, cache)
            if t > c.ring_page_size:
                raise MXNetError(
                    f"{t} rows a slot in one dispatch: a window layer's "
                    f"ring of {c.ring_pages} pages holds a window of "
                    f"{c.window} and at most a page of "
                    f"{c.ring_page_size} rows")
            positions = cache.length[:, None] + steps
            live = steps < cache.spans[:, None]
            if c.dense_layers:      # their feed-forwards
                pick = pick_live_rows(live)
                cache = count_live_rows(cache, pick)
            cache = self._count_window_keys(cache)
        h = jnp.take(raw(self.embed.weight), ids, axis=0)
        h = (h.astype(jnp.float32) * math.sqrt(c.units)).astype(h.dtype)
        for block in self.blocks():
            h, cache = block.forward(h, cache, positions, live, pick)
        h = rms_norm(h, raw(self.final_norm.weight), c.rms_norm_eps)
        return NDArray(h), None if cache is None else cache.advance(t)

    def head(self, h):
        """(..., C) final hidden states -> (..., V) logits, row by row."""
        h = h._data if isinstance(h, NDArray) else h
        return NDArray(linear(h, self._children["head"]))

    def forward(self, inputs, cache=None):
        h, cache = self.hidden(inputs, cache)
        logits = self.head(h)
        if cache is None:
            return logits
        return logits, cache
