"""Ouro (ByteDance Seed, `model_type: ouro`, "Scaling Latent Reasoning via
Looped Language Models", 2025-10): a decoder whose stack of `num_layers`
blocks runs `total_ut_steps` times over the SAME weights. Each pass ends in
the final RMSNorm, whose output is the next pass's input; an exit gate, a
Linear(units, 1) on that normed output, gives every pass's share of the
published exit distribution; the head reads the last pass.

    block_l(h):  h = h + RMSNorm_a2(Attn_l(RMSNorm_a1(h)))
                 h = h + RMSNorm_f2(MLP_l(RMSNorm_f1(h)))      (the "sandwich")
    h_0 = Embed(ids);  h_t = RMSNorm_final(block_{L-1}(.. block_0(h_{t-1})))
    lambda_t = sigmoid(h_t w_gate + b_gate);  logits = h_T W_head
    p_t = lambda_t prod_{s<t} (1 - lambda_s) for t < T,
    p_T = prod_{s<T} (1 - lambda_s)

A token at pass t attends the keys pass t wrote for earlier tokens, never
another pass's: the cache has `total_ut_steps * num_layers` page layers
for `num_layers` blocks of weights, pass t's layer l at `t * num_layers +
l`. The passes are ONE `lax.scan` whose body is the blocks, the final norm
and the gate: the blocks are traced once and run `total_ut_steps` times,
the cache layer handed to a block is a TRACED integer, and the carry is the
rows and the cache's pools, which the page write and the span kernel work
on in place (kv_page_write aliases them; the `while` carries the donated
buffers). The whole-sequence forward (no cache) is the same scan.

At the published `early_exit_threshold` 1 no token leaves early and the
logits are the last pass's; a threshold under 1 (rows that leave the stack
after fewer passes) is not built and is refused (ROADMAP.md B1).

Reference parity: none in the reference framework;
benchmarks/reference/ouro.py is the same model in plain float32 jax.numpy,
which the tests hold this file to. The block is models/hybrid.py's
Attention (rotary, every head its own KV head), GatedMLP and RMSNorm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Embedding
from ..ndarray.ndarray import NDArray
from .hybrid import Attention, GatedMLP, RMSNorm, linear, raw, rms_norm
from .kv_cache import PagedKVCache

__all__ = ["OuroConfig", "OuroForCausalLM", "ouro_2_6b_config"]


class OuroConfig:
    """Sizes: `num_heads` query heads over `num_kv_heads` KV heads of
    `head_dim`; `hidden_size` is the MLP's width; the stack runs
    `total_ut_steps` times."""

    def __init__(self, vocab_size=49152, units=2048, num_layers=48,
                 num_heads=16, num_kv_heads=16, head_dim=128,
                 hidden_size=5632, total_ut_steps=4, early_exit_threshold=1.0,
                 max_length=65536, rms_norm_eps=1e-6, rope_theta=1e6,
                 dtype="float32"):
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads")
        if total_ut_steps < 1:
            raise MXNetError(f"total_ut_steps {total_ut_steps} < 1")
        if early_exit_threshold < 1:
            raise MXNetError(
                f"early_exit_threshold {early_exit_threshold} < 1 lets rows "
                "leave the stack after fewer passes: a step whose passes "
                "differ by row is not built (ROADMAP.md B1); the published "
                "default is 1")
        self.vocab_size = vocab_size
        self.units = units
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.hidden_size = hidden_size
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = early_exit_threshold
        self.max_length = max_length
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.dtype = dtype

    def num_params(self):
        c = self
        attn = c.units * c.head_dim * 2 * (c.num_heads + c.num_kv_heads)
        return (2 * c.vocab_size * c.units + 2 * c.units + 1
                + c.num_layers * (attn + 3 * c.units * c.hidden_size
                                  + 4 * c.units))


def ouro_2_6b_config(**kw):
    """ByteDance/Ouro-2.6B, config.json: every size as published."""
    for k, v in dict(
            vocab_size=49152, units=2048, num_layers=48, num_heads=16,
            num_kv_heads=16, head_dim=128, hidden_size=5632,
            total_ut_steps=4, early_exit_threshold=1.0, max_length=65536,
            rms_norm_eps=1e-6, rope_theta=1e6).items():
        kw.setdefault(k, v)
    return OuroConfig(**kw)


def exit_pdf(gates):
    """(T, ...) the passes' gates lambda_t -> (T, ...) the exit
    distribution: p_t = lambda_t prod_{s<t} (1 - lambda_s), the last pass
    taking what is left."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([(gates * before)[:-1], before[-1:]], 0)


def _pools(cache):
    """What a pass carries of the cache: the pools and, over int8 pages,
    their scale leaves (None otherwise)."""
    return cache.k_pages, cache.v_pages, cache.k_scale, cache.v_scale


class OuroBlock(HybridBlock):
    """Four norms a block: each branch's input AND its output."""

    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        self._eps = c.rms_norm_eps
        self.attn_norm = RMSNorm(c.units)
        self.attn = Attention(c.units, c.num_heads, c.num_kv_heads,
                              c.head_dim, rotary=True,
                              rope_theta=c.rope_theta)
        self.attn_out_norm = RMSNorm(c.units)
        self.ffn_norm = RMSNorm(c.units)
        self.ffn = GatedMLP(c.units, c.hidden_size)
        self.ffn_out_norm = RMSNorm(c.units)

    def forward(self, h, cache, layer, positions):
        """`layer`: the page layer this pass of this block reads and
        writes."""
        norm = lambda x, n: rms_norm(x, raw(n.weight), self._eps)
        with jax.named_scope("ouro.attention"):
            a, cache = self.attn.forward(norm(h, self.attn_norm), cache,
                                         layer, positions)
            h = h + norm(a, self.attn_out_norm)
        with jax.named_scope("ouro.feed_forward"):
            f = self.ffn.forward(norm(h, self.ffn_norm))
            return h + norm(f, self.ffn_out_norm), cache


class OuroForCausalLM(HybridBlock):
    """Ouro with its untied LM head, behind the engine's contract:
    `hidden(ids, cache)`, `head(h)`, `state_spec()`, `make_cache()`.
    Nothing model-specific is passed to serving.ServingEngine."""

    def __init__(self, config: OuroConfig, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        for i in range(c.num_layers):
            self.register_child(OuroBlock(c), name=f"layer{i}")
        self.final_norm = RMSNorm(c.units)
        self.exit_gate = Dense(1, use_bias=True, flatten=False,
                               in_units=c.units)
        # registered like the layers, with no attribute: `head` is the
        # method below, the parameter stays "head.weight"
        self.register_child(Dense(c.vocab_size, use_bias=False,
                                  flatten=False, in_units=c.units),
                            name="head")

    def blocks(self):
        return [child for name, child in self._children.items()
                if name.startswith("layer")]

    def state_spec(self):
        """What a serving slot holds: pages for `kv_layers` =
        `loop_steps` x `num_layers` cache layers (every pass keeps keys and
        values of its own for every block), no recurrent leaves. `counters`
        is not a slot's: `exit`, cumulative float32 [rows, p_1 .. p_T], the
        exit distribution summed over the rows the head reads (each slot's
        last live row of a dispatch). `refuses`: what the blocks of
        models/hybrid.py do not carry."""
        c = self.config
        why = ("the blocks of models/hybrid.py carry no {} (models/gpt2.py "
               "does)")
        return {"num_layers": c.num_layers,
                "kv_layers": c.total_ut_steps * c.num_layers,
                "loop_steps": c.total_ut_steps,
                "num_kv_heads": c.num_kv_heads, "head_dim": c.head_dim,
                "recurrent": {},
                "counters": {"exit": ((1 + c.total_ut_steps,), "float32")},
                "refuses": {
                    "tp": why.format("head split or partial-sum rule"),
                    "weight_dtype": why.format("int8 weight plan"),
                    "adapter_pool": why.format("adapter hook"),
                    "speculative": "a verify window through a looped stack "
                                   "has no test here yet"}}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto",
                   kv_dtype=None):
        """A ragged paged cache of `kv_layers` page layers for `batch`
        slots (the serving engine builds its own from `state_spec`)."""
        c, spec = self.config, self.state_spec()
        return PagedKVCache.create(
            spec["kv_layers"], batch, c.num_heads, max_length, c.head_dim,
            dtype=dtype or jnp.dtype(c.dtype), page_size=page_size,
            page_table=page_table,
            lengths=jnp.zeros((batch,), jnp.int32) if lengths is None
            else lengths, attn_impl=attn_impl, kv_dtype=kv_dtype,
            num_kv_heads=c.num_kv_heads,
            recurrent={k: jnp.zeros(shape, dt)
                       for k, (shape, dt) in spec["counters"].items()})

    def hidden_and_exit(self, inputs, cache=None):
        """(B, T) ids -> ((B, T, C) the last pass's normed hidden states,
        the advanced cache, (passes, B, T) float32 exit distribution)."""
        c = self.config
        ids = inputs._data if isinstance(inputs, NDArray) else inputs
        b, t = ids.shape
        steps = jnp.arange(t)[None, :]
        if cache is None:
            positions, pools = jnp.broadcast_to(steps, (b, t)), None
        else:
            if not getattr(cache, "ragged", False) or cache.spans is None:
                raise MXNetError(
                    f"{type(self).__name__} decodes through a ragged "
                    "PagedKVCache that carries `spans` "
                    "(serving.ServingEngine, or make_cache())")
            positions = cache.length[:, None] + steps
            pools = _pools(cache)
        blocks = self.blocks()
        final, gate = raw(self.final_norm.weight), self.exit_gate

        def one_pass(carry, step):
            h, pools = carry
            passing = None if pools is None else cache._with_pages(*pools)
            with jax.named_scope("ouro.pass"):
                for l, block in enumerate(blocks):
                    h, passing = block.forward(
                        h, passing, step * len(blocks) + l, positions)
                h = rms_norm(h, final, c.rms_norm_eps)
                lam = jax.nn.sigmoid(
                    linear(h, gate).astype(jnp.float32)[..., 0]
                    + raw(gate.bias).astype(jnp.float32)[0])
            return (h, None if pools is None else _pools(passing)), lam

        h = jnp.take(raw(self.embed.weight), ids, axis=0)
        (h, pools), gates = jax.lax.scan(
            one_pass, (h, pools), jnp.arange(c.total_ut_steps))
        pdf = exit_pdf(gates)
        if cache is None:
            return NDArray(h), None, pdf
        cache = cache._with_pages(*pools)
        rec = cache.recurrent
        if rec is not None:
            # the rows the head reads: each slot's last live row
            last = jnp.maximum(cache.spans - 1, 0)
            read = jnp.take_along_axis(pdf, last[None, :, None],
                                       axis=2)[..., 0]
            work = (cache.spans > 0).astype(jnp.float32)
            cache = cache.with_recurrent(dict(
                rec, exit=rec["exit"] + jnp.concatenate(
                    [work.sum()[None], (read * work).sum(-1)])))
        return NDArray(h), cache.advance(t), pdf

    def hidden(self, inputs, cache=None):
        """Everything up to and including the last pass's final norm:
        (B, T) ids -> ((B, T, C) hidden states, advanced cache)."""
        h, cache, _ = self.hidden_and_exit(inputs, cache)
        return h, cache

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
