"""Nemotron-H (NVIDIA, `model_type: nemotron_h`): a decoder whose every
layer is of ONE kind, given by a pattern string: `M` a Mamba-2 mixer, `*`
grouped-query attention (no rotary embedding: the mixers carry the order),
`E` a latent mixture of experts. Every layer is h <- h + f(RMSNorm(h));
a final RMSNorm and an untied head follow.

`E`, for a normalised row u: s = sigmoid(W_r u) in float32 over ALL the
experts; the top_k of s + b are chosen (b steers the choice only);
w_e = scale * s_e / sum of the chosen s; z = W_in u (to the latent width);
y = W_out(sum_e w_e W2_e relu(W1_e z)^2) + W2_s relu(W1_s u)^2, the last
the shared expert at full width. The routed sum runs over the experts
this chip HOLDS (`held_experts`), gluon.nn.DroplessMoE: with a share of
them the layer's output is that share's partial sum plus the shared
expert, and no code here stands in for the other holders.

Reference parity: none in the reference framework;
benchmarks/reference/nemotron_h.py is the same model in plain float32
jax.numpy, which the tests hold this file to. The mixer and the attention
are models/hybrid.py's, shared with models/falcon_h1.py.

What a serving slot holds differs by layer kind: an attention layer has KV
pages, a mixer has its convolution tail and SSM state, an expert layer has
nothing. `state_spec()` says how many layers of each there are, and the
model maps its layer index to its page layer and its state layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import MOE_COUNTERS, Dense, DroplessMoE, Embedding
from ..ndarray.ndarray import NDArray
from .hybrid import (LIVE_ROWS_COUNTER, Attention, Mixer, RMSNorm,
                     count_live_rows, kernel_impl, linear, over_live_rows,
                     pick_live_rows, raw, require_recurrent_cache, rms_norm)
from .kv_cache import PagedKVCache

__all__ = ["NemotronHConfig", "NemotronHForCausalLM",
           "nemotron3_super_120b_config"]


class NemotronHConfig:
    """`pattern` gives each layer its kind. Attention: `num_heads` query
    heads over `num_kv_heads` KV heads of `head_dim`, `rotary` off as the
    family's modelling code applies none. Experts: `num_experts` routed,
    `top_k` a token, `expert_hidden_size` wide in a latent space of
    `latent_size`; one shared expert `shared_hidden_size` wide;
    `held_experts` = (first, count) are the routed experts this model
    holds, all of them by default."""

    def __init__(self, vocab_size=32768, units=1024, pattern="MEM*E",
                 num_heads=8, num_kv_heads=2, head_dim=128, ssm_heads=32,
                 ssm_head_dim=64, ssm_state=128, ssm_groups=8, conv_kernel=4,
                 chunk_size=128, num_experts=64, top_k=4, held_experts=None,
                 latent_size=256, expert_hidden_size=512,
                 shared_hidden_size=1024, routed_scaling_factor=1.0,
                 rotary=False, rope_theta=1e4, max_length=16384,
                 rms_norm_eps=1e-5, state_dtype="float32", dtype="float32"):
        if not pattern or set(pattern) - set("ME*"):
            raise MXNetError(f"layer pattern {pattern!r}: one of M, E, * "
                             "a layer")
        self.vocab_size = vocab_size
        self.units = units
        self.pattern = pattern
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ssm_heads = ssm_heads
        self.ssm_head_dim = ssm_head_dim
        self.ssm_state = ssm_state
        self.ssm_groups = ssm_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.held_experts = tuple(held_experts) if held_experts is not None \
            else (0, num_experts)
        self.latent_size = latent_size
        self.expert_hidden_size = expert_hidden_size
        self.shared_hidden_size = shared_hidden_size
        self.routed_scaling_factor = routed_scaling_factor
        self.rotary = rotary
        self.rope_theta = rope_theta
        self.max_length = max_length
        self.rms_norm_eps = rms_norm_eps
        self.state_dtype = state_dtype
        self.dtype = dtype

    @property
    def num_layers(self):
        return len(self.pattern)


def nemotron3_super_120b_config(**kw):
    """nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, config.json: every
    published size, all 88 layers and all 512 experts unless told
    otherwise."""
    for k, v in dict(
            vocab_size=131072, units=4096,
            pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
            num_heads=32, num_kv_heads=2, head_dim=128, ssm_heads=128,
            ssm_head_dim=64, ssm_state=128, ssm_groups=8, conv_kernel=4,
            chunk_size=128, num_experts=512, top_k=22, latent_size=1024,
            expert_hidden_size=2688, shared_hidden_size=5376,
            routed_scaling_factor=5.0, rotary=False, rope_theta=1e4,
            max_length=262144, rms_norm_eps=1e-5).items():
        kw.setdefault(k, v)
    return NemotronHConfig(**kw)


class LatentMoE(HybridBlock):
    """An `E` layer's f: the routed experts in the latent space between
    `latent_in` and `latent_out`, plus the shared expert at full width."""

    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        kw = dict(use_bias=False, flatten=False)
        self.latent_in = Dense(c.latent_size, in_units=c.units, **kw)
        self.experts = DroplessMoE(
            c.latent_size, c.expert_hidden_size, c.num_experts, c.top_k,
            held=c.held_experts, router_units=c.units,
            scale=c.routed_scaling_factor)
        self.latent_out = Dense(c.units, in_units=c.latent_size, **kw)
        self.shared_up = Dense(c.shared_hidden_size, in_units=c.units, **kw)
        self.shared_down = Dense(c.units, in_units=c.shared_hidden_size,
                                 **kw)

    def forward(self, u, live, pick=None, **impl):
        """(B, T, C) normalised rows, (B, T) which of them are live, what
        pick_live_rows made of that (None: whole sequences) -> (the
        layer's f, the expert counters of this call)."""
        from ..ops.moe import relu2     # loads Pallas: not with the package
        b, t, c = u.shape
        rows = u.reshape(b * t, c)
        y, counts = self.experts.forward(
            linear(rows, self.latent_in), live.reshape(-1), route_on=rows,
            **impl)
        with jax.named_scope("moe.shared"):
            shared = over_live_rows(
                lambda r: linear(relu2(linear(r, self.shared_up)),
                                 self.shared_down), u, live, pick)
        return linear(y, self.latent_out).reshape(b, t, c) + shared, counts


class NemotronHBlock(HybridBlock):
    """One layer: its norm and the one mixer its kind names. `index` is
    the layer's place among the layers of its own kind: its page layer,
    state layer or counter row."""

    def __init__(self, c, kind, index, **kwargs):
        super().__init__(**kwargs)
        self._c, self.kind, self.index = c, kind, index
        self.norm = RMSNorm(c.units)
        if kind == "M":
            self.mixer = Mixer(
                c.units, c.ssm_heads, c.ssm_head_dim, c.ssm_state,
                c.ssm_groups, conv_kernel=c.conv_kernel,
                chunk_size=c.chunk_size, eps=c.rms_norm_eps,
                state_dtype=c.state_dtype)
        elif kind == "*":
            self.mixer = Attention(
                c.units, c.num_heads, c.num_kv_heads, c.head_dim,
                rotary=c.rotary, rope_theta=c.rope_theta)
        else:
            self.mixer = LatentMoE(c)

    def forward(self, h, cache, positions, fresh, live, pick):
        u = rms_norm(h, raw(self.norm.weight), self._c.rms_norm_eps)
        if self.kind == "M":
            f, cache = self.mixer.forward(u, cache, self.index, fresh)
        elif self.kind == "*":
            f, cache = self.mixer.forward(u, cache, self.index, positions)
        elif cache is None:
            f, _ = self.mixer.forward(u, live, pick)
        else:
            f, counts = self.mixer.forward(u, live, pick,
                                           **kernel_impl(cache))
            rec = cache.recurrent
            cache = cache.with_recurrent(dict(
                rec, moe=rec["moe"].at[self.index].add(counts)))
        return h + f, cache


class NemotronHForCausalLM(HybridBlock):
    """Nemotron-H with its untied LM head, behind the engine's contract:
    `hidden(ids, cache)`, `head(h)`, `state_spec()`, `make_cache()`.
    Nothing model-specific is passed to serving.ServingEngine."""

    def __init__(self, config: NemotronHConfig, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        seen = {}
        for i, kind in enumerate(c.pattern):
            self.register_child(
                NemotronHBlock(c, kind, seen.setdefault(kind, 0)),
                name=f"layer{i}")
            seen[kind] += 1
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
        """What a serving slot holds: KV pages for the `kv_layers`
        attention layers, the mixers' fixed-size leaves for the
        `recurrent_layers` mixers, nothing for an expert layer. `counters`
        are not a slot's: whole cumulative leaves kept with the state (a
        row an expert layer, MOE_COUNTERS; the dispatches and how many of
        them ran the shared experts over their live rows alone,
        LIVE_ROWS_COUNTER), which the engine zeroes and fetches.
        `expert_weight_bytes`: the held routed experts'."""
        c = self.config
        mixers, experts = self.blocks("M"), self.blocks("E")
        held = sum(raw(p).size * jnp.dtype(raw(p).dtype).itemsize
                   for b in experts for p in (b.mixer.experts.expert_w1,
                                              b.mixer.experts.expert_w2))
        return {"num_layers": c.num_layers,
                "kv_layers": len(self.blocks("*")),
                "recurrent_layers": len(mixers),
                "num_kv_heads": c.num_kv_heads, "head_dim": c.head_dim,
                "recurrent": mixers[0].mixer.state_leaves(c.dtype)
                if mixers else {},
                "counters": {"moe": ((len(experts), len(MOE_COUNTERS)),
                                     "int32"), **LIVE_ROWS_COUNTER}
                if experts else {},
                "expert_weight_bytes": int(held)}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto"):
        """A ragged paged cache with zeroed recurrent state for `batch`
        slots (the serving engine builds its own from `state_spec`)."""
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
            else lengths, attn_impl=attn_impl,
            num_kv_heads=c.num_kv_heads, recurrent=rec)

    def hidden(self, inputs, cache=None):
        """Everything up to and including the final norm: (B, T) ids ->
        ((B, T, C) hidden states, advanced cache)."""
        c = self.config
        ids = inputs._data if isinstance(inputs, NDArray) else inputs
        b, t = ids.shape
        steps = jnp.arange(t)[None, :]
        pick = None
        if cache is None:
            positions = jnp.broadcast_to(steps, (b, t))
            fresh, live = None, jnp.ones((b, t), bool)
        else:
            require_recurrent_cache(self, cache)
            positions = cache.length[:, None] + steps
            # a slot with no context yet starts from zero state, whoever
            # held the slot before
            fresh = cache.length == 0
            live = steps < cache.spans[:, None]
            if self.blocks("E"):    # the shared experts' feed-forwards
                pick = pick_live_rows(live)
                cache = count_live_rows(cache, pick)
        h = jnp.take(raw(self.embed.weight), ids, axis=0)
        for block in self.blocks():
            h, cache = block.forward(h, cache, positions, fresh, live, pick)
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
