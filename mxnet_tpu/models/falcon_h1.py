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

import math

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Embedding
from ..gluon.parameter import Parameter
from ..ndarray.ndarray import NDArray
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


def _raw(p):
    return p.data()._data


def _linear(x, dense):
    return jnp.matmul(x, _raw(dense.weight).T)


def _rms(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Full rotary embedding of (B, T, H, D) at `positions` (B, T), the
    two halves of a head rotated against each other, in float32."""
    d = x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv    # (B, T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def _kernel_impl(cache):
    """Both kernels follow the cache's one `attn_impl` knob."""
    interpret = cache.attn_impl == "pallas_interpret"
    return {"impl": "pallas" if interpret else cache.attn_impl,
            "interpret": interpret}


class RMSNorm(HybridBlock):
    def __init__(self, units, **kwargs):
        super().__init__(**kwargs)
        self.weight = Parameter("weight", shape=(units,), init="ones")


class _Attention(HybridBlock):
    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        kw = dict(use_bias=False, flatten=False, in_units=c.units)
        self.query = Dense(c.num_heads * c.head_dim, **kw)
        self.key = Dense(c.num_kv_heads * c.head_dim, **kw)
        self.value = Dense(c.num_kv_heads * c.head_dim, **kw)
        self.proj = Dense(c.units, use_bias=False, flatten=False,
                          in_units=c.num_heads * c.head_dim)


class _Mixer(HybridBlock):
    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        self.in_proj = Dense(c.ssm_width + c.conv_width + c.ssm_heads,
                             use_bias=False, flatten=False, in_units=c.units)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(c.conv_width, c.conv_kernel))
        self.conv_bias = Parameter("conv_bias", shape=(c.conv_width,),
                                   init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(c.ssm_heads,),
                                 init="zeros")
        self.A_log = Parameter("A_log", shape=(c.ssm_heads,), init="zeros")
        self.D = Parameter("D", shape=(c.ssm_heads,), init="ones")
        self.norm = RMSNorm(c.ssm_width)
        self.out_proj = Dense(c.units, use_bias=False, flatten=False,
                              in_units=c.ssm_width)


class FalconH1Block(HybridBlock):
    def __init__(self, c, **kwargs):
        super().__init__(**kwargs)
        self._c = c
        self.input_norm = RMSNorm(c.units)
        self.attn = _Attention(c)
        self.mamba = _Mixer(c)
        self.ff_norm = RMSNorm(c.units)
        kw = dict(use_bias=False, flatten=False)
        self.gate = Dense(c.hidden_size, in_units=c.units, **kw)
        self.up = Dense(c.hidden_size, in_units=c.units, **kw)
        self.down = Dense(c.units, in_units=c.hidden_size, **kw)

    # -- attention -------------------------------------------------------
    def _attention(self, u, cache, layer, positions):
        c, at = self._c, self.attn
        b, t, _ = u.shape
        u = u * c.attention_in_multiplier
        q = _linear(u, at.query).reshape(b, t, c.num_heads, c.head_dim)
        k = (_linear(u, at.key) * c.key_multiplier).reshape(
            b, t, c.num_kv_heads, c.head_dim)
        v = _linear(u, at.value).reshape(b, t, c.num_kv_heads, c.head_dim)
        q = _rope(q, positions, c.rope_theta)
        k = _rope(k, positions, c.rope_theta)
        if cache is None:
            g = c.num_heads // c.num_kv_heads
            qg = q.reshape(b, t, c.num_kv_heads, g, c.head_dim)
            s = jnp.einsum("bjhgd,bthd->bhgjt", qg, k,
                           preferred_element_type=jnp.float32) \
                / math.sqrt(c.head_dim)
            causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            out = jnp.einsum("bhgjt,bthd->bjhgd", w.astype(v.dtype), v)
        else:
            # the keys go to the pages rotated; the kernel's causal
            # offset and these positions come from the same lengths
            # (the kernels' modules load Pallas: imported where they are
            # first traced, as models/gpt2.py does, not with the package)
            from ..ops.pallas_attention import ragged_span_attention
            cache = cache.write_decode(layer, k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3))
            out = ragged_span_attention(
                q.astype(cache.k_pages.dtype), cache.k_pages,
                cache.v_pages, cache.page_table, cache.length + 1,
                q_counts=cache.spans, layer=layer,
                num_kv_heads=c.num_kv_heads,
                **_kernel_impl(cache)).astype(u.dtype)
        out = out.reshape(b, t, c.num_heads * c.head_dim)
        return _linear(out, at.proj) * c.attention_out_multiplier, cache

    # -- Mamba-2 mixer ---------------------------------------------------
    def _conv(self, xbc, tail):
        """Causal depthwise convolution of (B, T, C) rows whose left
        context is `tail` (B, K-1, C); returns the rows and the two
        joined, from which the next tail is cut."""
        mx = self.mamba
        w = _raw(mx.conv_weight).astype(jnp.float32)            # (C, K)
        full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        t, ff = xbc.shape[1], full.astype(jnp.float32)
        out = _raw(mx.conv_bias).astype(jnp.float32) + sum(
            ff[:, k:k + t] * w[:, k] for k in range(w.shape[1]))
        return jax.nn.silu(out).astype(xbc.dtype), full

    def _mixer(self, u, cache, layer, fresh):
        from ..ops.ssm import ssd_chunk_update
        c, mx = self._c, self.mamba
        b, t, _ = u.shape
        H, P, G, N = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        m = c.ssm_multipliers
        p = _linear(u * c.ssm_in_multiplier, mx.in_proj)
        z, xbc, dt = jnp.split(p, [c.ssm_width, c.ssm_width + c.conv_width],
                               axis=-1)
        # mup_vector: one multiplier a segment, in the order z, x, B, C, dt
        z = z * m[0]
        xbc = xbc * jnp.concatenate([
            jnp.full((c.ssm_width,), m[1], jnp.float32),
            jnp.full((G * N,), m[2], jnp.float32),
            jnp.full((G * N,), m[3], jnp.float32)]).astype(xbc.dtype)
        dt = jax.nn.softplus(dt.astype(jnp.float32) * m[4]
                             + _raw(mx.dt_bias).astype(jnp.float32))
        A = -jnp.exp(_raw(mx.A_log).astype(jnp.float32))
        D = _raw(mx.D).astype(jnp.float32)
        K = c.conv_kernel
        if cache is None:
            xbc, _ = self._conv(xbc, jnp.zeros((b, K - 1, c.conv_width),
                                               xbc.dtype))
        else:
            rec = cache.recurrent
            tail = jnp.where(fresh[:, None, None], 0, rec["conv"][layer])
            xbc, full = self._conv(xbc, tail)
            # the next tail is the last K-1 LIVE rows: rows spans-K+1 ..
            # spans-1 of the chunk, which reach into the old tail while
            # a slot has fed fewer than K-1 rows, and are the old tail
            # itself for a slot with no live row
            at = cache.spans[:, None] + jnp.arange(K - 1)[None, :]
            tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
            rec = dict(rec, conv=rec["conv"].at[layer].set(
                tail.astype(rec["conv"].dtype)))
        x, Bm, Cm = jnp.split(xbc, [c.ssm_width, c.ssm_width + G * N],
                              axis=-1)
        x = x.reshape(b, t, H, P)
        Bm, Cm = Bm.reshape(b, t, G, N), Cm.reshape(b, t, G, N)
        if cache is None:
            y = self._scan_chunks(x, dt, A, Bm, Cm, D)
        else:
            y, ssm = ssd_chunk_update(
                x, dt, A, Bm, Cm, D, rec["ssm"], cache.spans, layer,
                fresh=fresh, **_kernel_impl(cache))
            cache = cache.with_recurrent(dict(rec, ssm=ssm))
        # gated RMSNorm: the gate first (mamba_norm_before_gate false),
        # the variance over each group's channels
        g = y.reshape(b, t, -1).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        gg = g.reshape(b, t, G, -1)
        gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, -1, keepdims=True)
                                + c.rms_norm_eps)
        g = (gg.reshape(b, t, -1)
             * _raw(mx.norm.weight).astype(jnp.float32)).astype(u.dtype)
        return _linear(g, mx.out_proj) * c.ssm_out_multiplier, cache

    def _scan_chunks(self, x, dt, A, Bm, Cm, D):
        """The recurrence over whole sequences: the serving path's chunk
        update scanned over chunks of the sequence from zero state."""
        c = self._c
        b, t, H, P = x.shape
        w = min(c.chunk_size, -(-t // 8) * 8)
        n = -(-t // w)
        pad = lambda a: jnp.pad(
            a, [(0, 0), (0, n * w - t)] + [(0, 0)] * (a.ndim - 2))
        # (n, B, w, ...) chunks; the last may be short of live rows
        cut = lambda a: jnp.moveaxis(
            pad(a).reshape((b, n, w) + a.shape[2:]), 1, 0)
        counts = jnp.clip(t - jnp.arange(n) * w, 0, w)

        from ..ops.ssm import ssd_chunk_update

        def step(state, chunk):
            xs, dts, bs, cs, count = chunk
            y, state = ssd_chunk_update(
                xs, dts, A, bs, cs, D, state,
                jnp.full((b,), count, jnp.int32), 0)
            return state, y

        state = jnp.zeros((1, b, H, P, c.ssm_state), c.state_dtype)
        _, ys = jax.lax.scan(step, state,
                             (cut(x), cut(dt), cut(Bm), cut(Cm), counts))
        return jnp.moveaxis(ys, 0, 1).reshape(b, n * w, H, P)[:, :t]

    def forward(self, h, cache, layer, positions, fresh):
        c = self._c
        u = _rms(h, _raw(self.input_norm.weight), c.rms_norm_eps)
        a, cache = self._attention(u, cache, layer, positions)
        m, cache = self._mixer(u, cache, layer, fresh)
        h = h + a + m
        v = _rms(h, _raw(self.ff_norm.weight), c.rms_norm_eps)
        f = jax.nn.silu(_linear(v, self.gate) * c.mlp_multipliers[0]) \
            * _linear(v, self.up)
        return h + _linear(f, self.down) * c.mlp_multipliers[1], cache


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
                "recurrent": {
                    "conv": ((c.conv_kernel - 1, c.conv_width), c.dtype),
                    "ssm": ((c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                            c.state_dtype)}}

    def make_cache(self, batch, max_length, page_size=64, dtype=None,
                   page_table=None, lengths=None, attn_impl="auto"):
        """A ragged paged cache with zeroed recurrent state for `batch`
        slots (the serving engine builds its own from `state_spec`)."""
        c, spec = self.config, self.state_spec()
        rec = {k: jnp.zeros((c.num_layers, batch) + shape, dt)
               for k, (shape, dt) in spec["recurrent"].items()}
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
        fresh = None
        if cache is None:
            positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        else:
            if not getattr(cache, "ragged", False) \
                    or cache.recurrent is None or cache.spans is None:
                raise MXNetError(
                    "FalconH1ForCausalLM decodes through a ragged "
                    "PagedKVCache that carries `recurrent` state and "
                    "`spans` (serving.ServingEngine, or make_cache())")
            positions = cache.length[:, None] + jnp.arange(t)[None, :]
            # a slot with no context yet starts from zero state, whoever
            # held the slot before
            fresh = cache.length == 0
        h = jnp.take(_raw(self.embed.weight), ids, axis=0) \
            * c.embedding_multiplier
        for i, block in enumerate(self.blocks()):
            h, cache = block.forward(h, cache, i, positions, fresh)
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
