"""Falcon-H1 (TII, 2025): a decoder whose every block runs grouped-query
attention and a Mamba-2 mixer side by side on one normalised input, adds
both to the residual, and follows with a SwiGLU MLP.

Reference parity: none in the reference framework (its zoo stops at the
transformer); the equations are those of the family's published config
keys, and benchmarks/reference/falcon_h1.py is the same model in plain
float32 jax.numpy, which the tests hold this file to.

Two forwards:
  * no cache: whole sequences, dense causal attention, the recurrence
    scanned over chunks from zero state (training-shaped; `EvalStep`).
  * a ragged PagedKVCache carrying `recurrent` state (the serving
    dispatch): (slots, W) rows of which `cache.spans[b]` are live. Keys
    go to the pages already rotated, positions come from the cache's
    lengths, the recurrence is one chunk (ops/ssm.ssd_chunk_update) from
    the slot's carried state, and a slot whose context is 0 reads zero
    state and a zero convolution tail: decided here from the lengths, so
    the host never clears a slot's state.

Per-slot state besides the KV pages is declared by `state_spec()`; the
serving engine allocates it and donates it with the pages.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Embedding
from ..ndarray.ndarray import NDArray
from .hybrid import (LIVE_ROWS_COUNTER, Attention, Mixer, RMSNorm,
                     count_live_rows, linear as _linear, over_live_rows,
                     pick_live_rows, raw as _raw, require_recurrent_cache,
                     rms_norm as _rms)
from .kv_cache import PagedKVCache

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_34b_config"]


class FalconH1Config:
    """Sizes and the family's fixed scalar multipliers (muP): one on every
    branch's input and output, five on the segments of the mixer's input
    projection. `num_heads` query heads share `num_kv_heads` KV heads of
    `head_dim`; `hidden_size` is the MLP's width."""

    def __init__(self, vocab_size=32784, units=1024, num_layers=36,
                 num_heads=8, num_kv_heads=2, head_dim=64, hidden_size=2048,
                 ssm_heads=24, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
                 conv_kernel=4, chunk_size=128, max_length=16384,
                 rms_norm_eps=1e-5, rope_theta=1e11,
                 embedding_multiplier=1.0, lm_head_multiplier=1.0,
                 attention_in_multiplier=1.0, attention_out_multiplier=1.0,
                 key_multiplier=1.0, ssm_in_multiplier=1.0,
                 ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 1.0),
                 ssm_out_multiplier=1.0, mlp_multipliers=(1.0, 1.0),
                 state_dtype="float32", dtype="float32"):
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads")
        if ssm_heads % ssm_groups:
            raise MXNetError(f"{ssm_heads} SSM heads over {ssm_groups} "
                             "groups")
        self.vocab_size = vocab_size
        self.units = units
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.hidden_size = hidden_size
        self.ssm_heads = ssm_heads
        self.ssm_head_dim = ssm_head_dim
        self.ssm_state = ssm_state
        self.ssm_groups = ssm_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.max_length = max_length
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.embedding_multiplier = embedding_multiplier
        self.lm_head_multiplier = lm_head_multiplier
        self.attention_in_multiplier = attention_in_multiplier
        self.attention_out_multiplier = attention_out_multiplier
        self.key_multiplier = key_multiplier
        self.ssm_in_multiplier = ssm_in_multiplier
        self.ssm_multipliers = tuple(ssm_multipliers)
        self.ssm_out_multiplier = ssm_out_multiplier
        self.mlp_multipliers = tuple(mlp_multipliers)
        self.state_dtype = state_dtype
        self.dtype = dtype

    @property
    def ssm_width(self):            # mamba_d_ssm
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self):           # x, B and C pass the convolution
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    def num_params(self):
        c = self
        attn = c.units * c.head_dim * 2 * (c.num_heads + c.num_kv_heads)
        mixer = (c.units * (c.ssm_width + c.conv_width + c.ssm_heads)
                 + c.conv_width * (c.conv_kernel + 1) + 3 * c.ssm_heads
                 + c.ssm_width + c.ssm_width * c.units)
        mlp = 3 * c.units * c.hidden_size
        return (2 * c.vocab_size * c.units + c.units
                + c.num_layers * (attn + mixer + mlp + 2 * c.units))


def falcon_h1_34b_config(**kw):
    """tiiuae/Falcon-H1-34B-Instruct, config.json: every width and every
    multiplier as published."""
    for k, v in dict(
            vocab_size=261120, units=5120, num_layers=72, num_heads=20,
            num_kv_heads=4, head_dim=128, hidden_size=21504, ssm_heads=32,
            ssm_head_dim=128, ssm_state=256, ssm_groups=2, conv_kernel=4,
            chunk_size=128, max_length=262144, rms_norm_eps=1e-5,
            rope_theta=1e11, embedding_multiplier=5.656854249492381,
            lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
            attention_out_multiplier=0.0375,
            key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
            ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
            ssm_out_multiplier=0.08838834764831845,
            mlp_multipliers=(0.1767766952966369,
                             0.011160714285714284)).items():
        kw.setdefault(k, v)
    return FalconH1Config(**kw)


class FalconH1Block(HybridBlock):
    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        self._c = c
        self.input_norm = RMSNorm(c.units)
        self.attn = Attention(
            c.units, c.num_heads, c.num_kv_heads, c.head_dim,
            rope_theta=c.rope_theta, in_multiplier=c.attention_in_multiplier,
            key_multiplier=c.key_multiplier,
            out_multiplier=c.attention_out_multiplier)
        self.mamba = Mixer(
            c.units, c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups,
            conv_kernel=c.conv_kernel, chunk_size=c.chunk_size,
            eps=c.rms_norm_eps, state_dtype=c.state_dtype,
            in_multiplier=c.ssm_in_multiplier, multipliers=c.ssm_multipliers,
            out_multiplier=c.ssm_out_multiplier)
        self.ff_norm = RMSNorm(c.units)
        kw = dict(use_bias=False, flatten=False)
        self.gate = Dense(c.hidden_size, in_units=c.units, **kw)
        self.up = Dense(c.hidden_size, in_units=c.units, **kw)
        self.down = Dense(c.units, in_units=c.hidden_size, **kw)

    # both branches live in models/hybrid.py; every layer here has both,
    # so a layer's index is its page layer and its state layer
    def _attention(self, u, cache, layer, positions):
        return self.attn.forward(u, cache, layer, positions)

    def _mixer(self, u, cache, layer, fresh):
        return self.mamba.forward(u, cache, layer, fresh)

    def _feed_forward(self, rows):
        """(rows, C) residual rows -> the MLP's branch, row by row."""
        c = self._c
        v = _rms(rows, _raw(self.ff_norm.weight), c.rms_norm_eps)
        f = jax.nn.silu(_linear(v, self.gate) * c.mlp_multipliers[0]) \
            * _linear(v, self.up)
        return _linear(f, self.down) * c.mlp_multipliers[1]

    def forward(self, h, cache, layer, positions, fresh, live, pick):
        c = self._c
        u = _rms(h, _raw(self.input_norm.weight), c.rms_norm_eps)
        a, cache = self._attention(u, cache, layer, positions)
        m, cache = self._mixer(u, cache, layer, fresh)
        h = h + a + m
        return h + over_live_rows(self._feed_forward, h, live, pick), cache


class FalconH1ForCausalLM(HybridBlock):
    """Falcon-H1 with its untied LM head, behind the GPT-2 cache contract
    (`forward(ids, cache) -> (logits, cache)`), so serving.ServingEngine
    takes it with nothing model-specific passed in. `forward` is
    `head(hidden(ids, cache))`: the engine calls the two apart, so only
    the rows it samples pass the head."""

    def __init__(self, config: FalconH1Config, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        for i in range(c.num_layers):
            self.register_child(FalconH1Block(c), name=f"layer{i}")
        self.final_norm = RMSNorm(c.units)
        # registered like the layers, with no attribute: `head` is the
        # method below, the parameter stays "head.weight"
        self.register_child(Dense(c.vocab_size, use_bias=False,
                                  flatten=False, in_units=c.units),
                            name="head")

    def blocks(self):
        return [child for name, child in self._children.items()
                if name.startswith("layer")]

    def state_spec(self):
        """What a serving slot holds for this model: KV pages of
        `num_kv_heads` x `head_dim` a layer, and per layer and slot the
        fixed-size `recurrent` leaves {name: (shape, dtype)}: they do not
        grow with the sequence, are not paged and cannot be shared by
        prefix."""
        c = self.config
        return {"num_layers": c.num_layers,
                "num_kv_heads": c.num_kv_heads, "head_dim": c.head_dim,
                "recurrent": self.blocks()[0].mamba.state_leaves(c.dtype),
                "counters": dict(LIVE_ROWS_COUNTER)}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto"):
        """A ragged paged cache with zeroed recurrent state for `batch`
        slots (the serving engine builds its own from `state_spec`)."""
        c, spec = self.config, self.state_spec()
        rec = {k: jnp.zeros((c.num_layers, batch) + shape, dt)
               for k, (shape, dt) in spec["recurrent"].items()}
        rec.update({k: jnp.zeros(shape, dt)
                    for k, (shape, dt) in spec["counters"].items()})
        return PagedKVCache.create(
            c.num_layers, batch, c.num_heads, max_length, c.head_dim,
            dtype=dtype or jnp.dtype(c.dtype), page_size=page_size,
            page_table=page_table,
            lengths=jnp.zeros((batch,), jnp.int32) if lengths is None
            else lengths, attn_impl=attn_impl,
            num_kv_heads=c.num_kv_heads, recurrent=rec)

    def hidden(self, inputs, cache=None):
        """Everything up to and including the final norm: (B, T) ids ->
        ((B, T, C) hidden states, advanced cache)."""
        c = self.config
        ids = inputs._data if isinstance(inputs, NDArray) else inputs
        b, t = ids.shape
        steps = jnp.arange(t)[None, :]
        fresh = live = pick = None
        if cache is None:
            positions = jnp.broadcast_to(steps, (b, t))
        else:
            require_recurrent_cache(self, cache)
            positions = cache.length[:, None] + steps
            # a slot with no context yet starts from zero state, whoever
            # held the slot before
            fresh = cache.length == 0
            live = steps < cache.spans[:, None]
            pick = pick_live_rows(live)
            cache = count_live_rows(cache, pick)
        h = jnp.take(_raw(self.embed.weight), ids, axis=0) \
            * c.embedding_multiplier
        for i, block in enumerate(self.blocks()):
            h, cache = block.forward(h, cache, i, positions, fresh, live,
                                     pick)
        h = _rms(h, _raw(self.final_norm.weight), c.rms_norm_eps)
        return NDArray(h), None if cache is None else cache.advance(t)

    def head(self, h):
        """(..., C) final hidden states -> (..., V) logits, row by row."""
        h = h._data if isinstance(h, NDArray) else h
        return NDArray(_linear(h, self._children["head"])
                       * self.config.lm_head_multiplier)

    def forward(self, inputs, cache=None):
        h, cache = self.hidden(inputs, cache)
        logits = self.head(h)
        if cache is None:
            return logits
        return logits, cache
