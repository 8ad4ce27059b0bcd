"""Kimi Linear (Moonshot AI, `model_type: kimi_linear`, arXiv:2510.26692): a
decoder whose layers each pair ONE mixer with ONE feed-forward, both
pre-norm with a residual: h <- h + mixer(RMSNorm(h)), h <- h + ffn(RMSNorm(h));
a final RMSNorm and an untied head follow.

The mixer of a layer is given by a pattern string: `K` a KDA layer (a gated
delta rule with a log-decay per key channel: linear attention whose slot
state is a matrix a head, whatever the context) or `L` latent attention
with no rotary embedding (a token's cache row is its normalised latent and
a key part shared by all heads: one row, no head axis). The first
`dense_layers` feed-forwards are dense and gated, (silu(x Wg) * (x Wu)) Wd;
every other is a mixture of experts: s = sigmoid(W_r x) in float32 over ALL
the experts, the top_k of s + b chosen (b steers the choice only), their
weights their own s, renormalised and times `routed_scaling_factor`; an
expert is the same gated form; one shared expert is added to the routed
sum. The routed sum runs over the experts this chip HOLDS (`held_experts`,
gluon.nn.DroplessMoE): with a share of them a layer's output is that
share's partial sum plus the shared expert, and no code here stands in for
the other holders.

Reference parity: none in the reference framework;
benchmarks/reference/kimi_linear.py is the same model in plain float32
jax.numpy, which the tests hold this file to. The mixers and the gated
feed-forward are models/hybrid.py's.

What a serving slot holds differs by layer kind: a KDA layer its
convolution tail and its state, a latent layer pages of ONE pool
(`state_spec()`: `row_width`, and the value is the row's leading
`value_width` columns), an expert layer nothing but its counters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import MOE_COUNTERS, Dense, DroplessMoE, Embedding
from ..ndarray.ndarray import NDArray
from .hybrid import (LIVE_ROWS_COUNTER, GatedMLP, KDAMixer, LatentAttention,
                     RMSNorm, count_live_rows, kernel_impl, linear,
                     over_live_rows, pick_live_rows, raw,
                     require_recurrent_cache, rms_norm)
from .kv_cache import PagedKVCache

__all__ = ["KimiLinearConfig", "KimiLinearForCausalLM",
           "kimi_linear_48b_config"]


class KimiLinearConfig:
    """`pattern` gives each layer's mixer its kind. KDA: `kda_heads` heads
    whose keys and values are `kda_head_dim` wide, `conv_kernel` taps,
    `kda_low_rank` the width between the two matrices of the decay's and of
    the gate's projection. Latent attention: `num_heads` heads, a latent of
    `kv_lora_rank`, `qk_nope_head_dim` + `qk_rope_head_dim` a query head,
    `v_head_dim` a value head (the stored row is the layer's to lay out:
    hybrid.LatentAttention.row_width). Experts:
    `num_experts` routed, `top_k` a token, `expert_hidden_size` wide, one
    shared expert `shared_hidden_size` wide; `held_experts` = (first, count)
    are the routed experts this model holds, all of them by default."""

    def __init__(self, vocab_size=32768, units=1024, pattern="KKKL",
                 dense_layers=1, dense_hidden_size=4096, num_heads=8,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, kda_heads=8,
                 kda_head_dim=128, kda_low_rank=None, conv_kernel=4,
                 chunk_size=64, num_experts=64, top_k=4, held_experts=None,
                 expert_hidden_size=512, shared_hidden_size=512,
                 routed_scaling_factor=1.0, max_length=16384,
                 rms_norm_eps=1e-5, state_dtype="float32", dtype="float32"):
        if not pattern or set(pattern) - set("KL"):
            raise MXNetError(f"layer pattern {pattern!r}: K or L a layer")
        self.vocab_size = vocab_size
        self.units = units
        self.pattern = pattern
        self.dense_layers = dense_layers
        self.dense_hidden_size = dense_hidden_size
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kda_heads = kda_heads
        self.kda_head_dim = kda_head_dim
        self.kda_low_rank = kda_low_rank or kda_head_dim
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.held_experts = tuple(held_experts) if held_experts is not None \
            else (0, num_experts)
        self.expert_hidden_size = expert_hidden_size
        self.shared_hidden_size = shared_hidden_size
        self.routed_scaling_factor = routed_scaling_factor
        self.max_length = max_length
        self.rms_norm_eps = rms_norm_eps
        self.state_dtype = state_dtype
        self.dtype = dtype

    @property
    def num_layers(self):
        return len(self.pattern)


def kimi_linear_48b_config(**kw):
    """moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json: every
    published size, all 27 layers (latent attention at 4, 8, 12, 16, 20,
    24 and 27, counted from 1) and all 256 experts unless told
    otherwise."""
    for k, v in dict(
            vocab_size=163840, units=2304,
            pattern="KKKLKKKLKKKLKKKLKKKLKKKLKKL", dense_layers=1,
            dense_hidden_size=9216, num_heads=32, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            kda_heads=32, kda_head_dim=128, kda_low_rank=128,
            conv_kernel=4, chunk_size=64, num_experts=256, top_k=8,
            expert_hidden_size=1024, shared_hidden_size=1024,
            routed_scaling_factor=2.446, max_length=1048576,
            rms_norm_eps=1e-5).items():
        kw.setdefault(k, v)
    return KimiLinearConfig(**kw)


class KimiMoE(HybridBlock):
    """An expert layer's feed-forward: the routed gated experts plus the
    shared one."""

    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        self.experts = DroplessMoE(
            c.units, c.expert_hidden_size, c.num_experts, c.top_k,
            held=c.held_experts, scale=c.routed_scaling_factor,
            activation="swiglu")
        self.shared = GatedMLP(c.units, c.shared_hidden_size)

    def forward(self, u, live, **impl):
        """(B, T, C) normalised rows, (B, T) which of them are live ->
        (the layer's f, the expert counters of this call)."""
        b, t, c = u.shape
        rows = u.reshape(b * t, c)
        y, counts = self.experts.forward(rows, live.reshape(-1), **impl)
        # over every row, live or not: a 1024-wide expert over 2048 rows is
        # 0.15 ms at the chip's peak, less than the conditional and the two
        # gathers of over_live_rows cost on either side of its choice
        # (PERF.md, PR 38)
        with jax.named_scope("moe.shared"):
            shared = self.shared.forward(rows)
        return (y + shared).reshape(b, t, c), counts


class KimiLinearBlock(HybridBlock):
    """One layer: norm, mixer, norm, feed-forward. `index` is the mixer's
    place among the layers of its own kind (its state layer or page
    layer), `expert_index` the feed-forward's among the expert layers (its
    counter row; None for a dense one)."""

    def __init__(self, c, kind, index, expert_index, **kwargs):
        super().__init__(**kwargs)
        self._c, self.kind, self.index = c, kind, index
        self.expert_index = expert_index
        self.norm = RMSNorm(c.units)
        if kind == "K":
            self.mixer = KDAMixer(
                c.units, c.kda_heads, c.kda_head_dim,
                conv_kernel=c.conv_kernel, low_rank=c.kda_low_rank,
                chunk_size=c.chunk_size, eps=c.rms_norm_eps,
                state_dtype=c.state_dtype)
        else:
            self.mixer = LatentAttention(
                c.units, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, eps=c.rms_norm_eps)
        self.ffn_norm = RMSNorm(c.units)
        self.ffn = GatedMLP(c.units, c.dense_hidden_size) \
            if expert_index is None else KimiMoE(c)

    def forward(self, h, cache, fresh, live, pick):
        eps = self._c.rms_norm_eps
        u = rms_norm(h, raw(self.norm.weight), eps)
        if self.kind == "K":
            f, cache = self.mixer.forward(u, cache, self.index, fresh)
        else:
            f, cache = self.mixer.forward(u, cache, self.index)
        h = h + f
        u = rms_norm(h, raw(self.ffn_norm.weight), eps)
        if self.expert_index is None:
            f = over_live_rows(self.ffn.forward, u, live, pick)
        elif cache is None:
            f, _ = self.ffn.forward(u, live)
        else:
            f, counts = self.ffn.forward(u, live, **kernel_impl(cache))
            rec = cache.recurrent
            cache = cache.with_recurrent(dict(
                rec, moe=rec["moe"].at[self.expert_index].add(counts)))
        return h + f, cache


class KimiLinearForCausalLM(HybridBlock):
    """Kimi Linear with its untied LM head, behind the engine's contract:
    `hidden(ids, cache)`, `head(h)`, `state_spec()`, `make_cache()`.
    Nothing model-specific is passed to serving.ServingEngine."""

    def __init__(self, config: KimiLinearConfig, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        seen, experts = {}, 0
        for i, kind in enumerate(c.pattern):
            dense = i < c.dense_layers
            self.register_child(
                KimiLinearBlock(c, kind, seen.setdefault(kind, 0),
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
        """What a serving slot holds: for the `kv_layers` latent layers
        pages of ONE pool whose row is `row_width` wide with no head axis
        (the value is the row's leading `value_width` columns; there is no
        V pool), for the `recurrent_layers` KDA layers their fixed-size
        leaves. `counters` are not a slot's: whole cumulative leaves kept
        with the state (a row an expert layer, MOE_COUNTERS; where a layer
        has a dense feed-forward, the dispatches and how many of them ran
        it over their live rows alone, LIVE_ROWS_COUNTER).
        `expert_weight_bytes`: the held routed experts'."""
        c = self.config
        kda, latent = self.blocks("K"), self.blocks("L")
        experts = [b for b in self.blocks() if b.expert_index is not None]
        held = sum(raw(p).size * jnp.dtype(raw(p).dtype).itemsize
                   for b in experts for p in (b.ffn.experts.expert_w1,
                                              b.ffn.experts.expert_w2))
        return {"num_layers": c.num_layers,
                "kv_layers": len(latent),
                "recurrent_layers": len(kda),
                "row_width": latent[0].mixer.row_width if latent
                else c.kv_lora_rank + c.qk_rope_head_dim,
                "value_width": c.kv_lora_rank,
                "recurrent": kda[0].mixer.state_leaves(c.dtype)
                if kda else {},
                "counters": {
                    **({"moe": ((len(experts), len(MOE_COUNTERS)), "int32")}
                       if experts else {}),
                    **(LIVE_ROWS_COUNTER if c.dense_layers else {})},
                "expert_weight_bytes": int(held)}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto"):
        """A ragged one-pool paged cache with zeroed recurrent state for
        `batch` slots (the serving engine builds its own from
        `state_spec`)."""
        c, spec = self.config, self.state_spec()
        rec = {k: jnp.zeros((spec["recurrent_layers"], batch) + shape, dt)
               for k, (shape, dt) in spec["recurrent"].items()}
        rec.update({k: jnp.zeros(shape, dt)
                    for k, (shape, dt) in spec["counters"].items()})
        return PagedKVCache.create(
            spec["kv_layers"], batch, 1, max_length, spec["row_width"],
            dtype=dtype or jnp.dtype(c.dtype), page_size=page_size,
            page_table=page_table,
            lengths=jnp.zeros((batch,), jnp.int32) if lengths is None
            else lengths, attn_impl=attn_impl, row_width=spec["row_width"],
            recurrent=rec)

    def hidden(self, inputs, cache=None):
        """Everything up to and including the final norm: (B, T) ids ->
        ((B, T, C) hidden states, advanced cache)."""
        c = self.config
        ids = inputs._data if isinstance(inputs, NDArray) else inputs
        b, t = ids.shape
        steps = jnp.arange(t)[None, :]
        pick = None
        if cache is None:
            fresh, live = None, jnp.ones((b, t), bool)
        else:
            # (every layer's feed-forward keeps a counter in the state)
            require_recurrent_cache(self, cache)
            # a slot with no context yet starts from zero state, whoever
            # held the slot before
            fresh = cache.length == 0
            live = steps < cache.spans[:, None]
            if c.dense_layers:      # their feed-forwards
                pick = pick_live_rows(live)
                cache = count_live_rows(cache, pick)
        h = jnp.take(raw(self.embed.weight), ids, axis=0)
        for block in self.blocks():
            h, cache = block.forward(h, cache, fresh, live, pick)
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
