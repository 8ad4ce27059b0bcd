"""What the hybrid decoders (models/falcon_h1.py, models/nemotron_h.py)
are built from: RMSNorm, grouped-query attention over a ragged paged cache
with the rotary embedding on or off, and the Mamba-2 mixer (its
parameters, causal convolution, gated norm, and the recurrence with and
without carried state).

A block here knows sizes, not a family's config: the families pass their
own widths and, where they have them, their scalar multipliers. Each
`forward` takes the index of ITS layer in the cache's pools: the page
layer for attention, the state layer for the mixer. A model whose layers
are of one kind each maps its layer index to those itself.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense
from ..gluon.parameter import Parameter

__all__ = ["RMSNorm", "Attention", "Mixer", "rms_norm", "rope", "linear",
           "raw", "kernel_impl", "require_recurrent_cache"]


def raw(p):
    return p.data()._data


def linear(x, dense):
    return jnp.matmul(x, raw(dense.weight).T)


def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
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


def kernel_impl(cache):
    """Every kernel follows the cache's one `attn_impl` knob."""
    interpret = cache.attn_impl == "pallas_interpret"
    return {"impl": "pallas" if interpret else cache.attn_impl,
            "interpret": interpret}


def require_recurrent_cache(model, cache):
    if not getattr(cache, "ragged", False) \
            or cache.recurrent is None or cache.spans is None:
        raise MXNetError(
            f"{type(model).__name__} decodes through a ragged "
            "PagedKVCache that carries `recurrent` state and "
            "`spans` (serving.ServingEngine, or make_cache())")


class RMSNorm(HybridBlock):
    def __init__(self, units, **kwargs):
        super().__init__(**kwargs)
        self.weight = Parameter("weight", shape=(units,), init="ones")


class Attention(HybridBlock):
    """Causal softmax attention, `num_heads` query heads over
    `num_kv_heads` KV heads of `head_dim`, no bias. With `rotary` the
    queries and keys are rotated at the token's own position and the keys
    go to the pages already rotated; without, nothing here reads a
    position (the order is then some other layer's to carry)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rotary=True, rope_theta=1e4, in_multiplier=1.0,
                 key_multiplier=1.0, out_multiplier=1.0, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads")
        self._heads, self._kv_heads, self._dim = (num_heads, num_kv_heads,
                                                  head_dim)
        self._rotary, self._theta = bool(rotary), rope_theta
        self._in, self._key, self._out = (in_multiplier, key_multiplier,
                                          out_multiplier)
        kw = dict(use_bias=False, flatten=False, in_units=units)
        self.query = Dense(num_heads * head_dim, **kw)
        self.key = Dense(num_kv_heads * head_dim, **kw)
        self.value = Dense(num_kv_heads * head_dim, **kw)
        self.proj = Dense(units, use_bias=False, flatten=False,
                          in_units=num_heads * head_dim)

    def forward(self, u, cache, layer, positions):
        """(B, T, C) normalised rows -> (the branch's output, the cache
        with this layer's keys and values written to page layer
        `layer`)."""
        hq, hkv, d = self._heads, self._kv_heads, self._dim
        b, t, _ = u.shape
        u = u * self._in
        q = linear(u, self.query).reshape(b, t, hq, d)
        k = (linear(u, self.key) * self._key).reshape(b, t, hkv, d)
        v = linear(u, self.value).reshape(b, t, hkv, d)
        if self._rotary:
            q = rope(q, positions, self._theta)
            k = rope(k, positions, self._theta)
        if cache is None:
            g = hq // hkv
            qg = q.reshape(b, t, hkv, g, d)
            s = jnp.einsum("bjhgd,bthd->bhgjt", qg, k,
                           preferred_element_type=jnp.float32) \
                / math.sqrt(d)
            causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            out = jnp.einsum("bhgjt,bthd->bjhgd", w.astype(v.dtype), v)
        else:
            # the kernel's causal offset and the positions above come
            # from the same lengths (the kernels' modules load Pallas:
            # imported where they are first traced, as models/gpt2.py
            # does, not with the package)
            from ..ops.pallas_attention import ragged_span_attention
            cache = cache.write_decode(layer, k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3))
            out = ragged_span_attention(
                q.astype(cache.k_pages.dtype), cache.k_pages,
                cache.v_pages, cache.page_table, cache.length + 1,
                q_counts=cache.spans, layer=layer, num_kv_heads=hkv,
                **kernel_impl(cache)).astype(u.dtype)
        out = out.reshape(b, t, hq * d)
        return linear(out, self.proj) * self._out, cache


class Mixer(HybridBlock):
    """Mamba-2: `in_proj` to z | x, B, C | dt; a causal depthwise
    convolution and SiLU over x, B, C; dt = softplus(dt + dt_bias),
    A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
    y_t = S_t C_t + D x_t (head h reads group h // (heads / groups)); the
    gated RMSNorm (the gate first, the variance over each group's
    channels); `out_proj`. `multipliers` are one scalar a segment of the
    input projection, in the order z, x, B, C, dt (Falcon-H1's
    mup_vector); the other two scale the branch's input and output."""

    def __init__(self, units, heads, head_dim, state, groups, conv_kernel=4,
                 chunk_size=128, eps=1e-5, state_dtype="float32",
                 in_multiplier=1.0, multipliers=None, out_multiplier=1.0,
                 **kwargs):
        super().__init__(**kwargs)
        if heads % groups:
            raise MXNetError(f"{heads} SSM heads over {groups} groups")
        self._H, self._P, self._N, self._G = heads, head_dim, state, groups
        self._K, self._chunk, self._eps = conv_kernel, chunk_size, eps
        self._state_dtype = state_dtype
        self._in, self._m, self._out = (in_multiplier, multipliers,
                                        out_multiplier)
        self.width = heads * head_dim                       # mamba_d_ssm
        # x, B and C pass the convolution
        self.conv_width = self.width + 2 * groups * state
        self.in_proj = Dense(self.width + self.conv_width + heads,
                             use_bias=False, flatten=False, in_units=units)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(self.conv_width, conv_kernel))
        self.conv_bias = Parameter("conv_bias", shape=(self.conv_width,),
                                   init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(heads,), init="zeros")
        self.A_log = Parameter("A_log", shape=(heads,), init="zeros")
        self.D = Parameter("D", shape=(heads,), init="ones")
        self.norm = RMSNorm(self.width)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=self.width)

    def state_leaves(self, dtype):
        """What a slot holds for ONE layer of this mixer, as
        `state_spec()["recurrent"]` lists it."""
        return {"conv": ((self._K - 1, self.conv_width), dtype),
                "ssm": ((self._H, self._P, self._N), self._state_dtype)}

    def _conv(self, xbc, tail):
        """Causal depthwise convolution of (B, T, C) rows whose left
        context is `tail` (B, K-1, C); returns the rows and the two
        joined, from which the next tail is cut."""
        w = raw(self.conv_weight).astype(jnp.float32)          # (C, K)
        full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        t, ff = xbc.shape[1], full.astype(jnp.float32)
        out = raw(self.conv_bias).astype(jnp.float32) + sum(
            ff[:, k:k + t] * w[:, k] for k in range(w.shape[1]))
        return jax.nn.silu(out).astype(xbc.dtype), full

    def forward(self, u, cache, layer, fresh):
        """(B, T, C) normalised rows -> (the branch's output, the cache
        with state layer `layer` advanced). Without a cache: whole
        sequences from zero state."""
        from ..ops.ssm import ssd_chunk_update
        b, t, _ = u.shape
        H, P, G, N, K = self._H, self._P, self._G, self._N, self._K
        p = linear(u * self._in, self.in_proj)
        z, xbc, dt = jnp.split(p, [self.width, self.width + self.conv_width],
                               axis=-1)
        dt = dt.astype(jnp.float32)
        if self._m is not None:
            m = self._m
            z = z * m[0]
            xbc = xbc * jnp.concatenate([
                jnp.full((self.width,), m[1], jnp.float32),
                jnp.full((G * N,), m[2], jnp.float32),
                jnp.full((G * N,), m[3], jnp.float32)]).astype(xbc.dtype)
            dt = dt * m[4]
        dt = jax.nn.softplus(dt + raw(self.dt_bias).astype(jnp.float32))
        A = -jnp.exp(raw(self.A_log).astype(jnp.float32))
        D = raw(self.D).astype(jnp.float32)
        if cache is None:
            xbc, _ = self._conv(xbc, jnp.zeros((b, K - 1, self.conv_width),
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
        x, Bm, Cm = jnp.split(xbc, [self.width, self.width + G * N],
                              axis=-1)
        x = x.reshape(b, t, H, P)
        Bm, Cm = Bm.reshape(b, t, G, N), Cm.reshape(b, t, G, N)
        if cache is None:
            y = self._scan_chunks(x, dt, A, Bm, Cm, D)
        else:
            y, ssm = ssd_chunk_update(
                x, dt, A, Bm, Cm, D, rec["ssm"], cache.spans, layer,
                fresh=fresh, **kernel_impl(cache))
            cache = cache.with_recurrent(dict(rec, ssm=ssm))
        # gated RMSNorm: the gate first, the variance over each group's
        # channels
        g = y.reshape(b, t, -1).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        gg = g.reshape(b, t, G, -1)
        gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, -1, keepdims=True)
                                + self._eps)
        g = (gg.reshape(b, t, -1)
             * raw(self.norm.weight).astype(jnp.float32)).astype(u.dtype)
        return linear(g, self.out_proj) * self._out, cache

    def _scan_chunks(self, x, dt, A, Bm, Cm, D):
        """The recurrence over whole sequences: the serving path's chunk
        update scanned over chunks of the sequence from zero state."""
        from ..ops.ssm import ssd_chunk_update
        b, t, H, P = x.shape
        w = min(self._chunk, -(-t // 8) * 8)
        n = -(-t // w)
        pad = lambda a: jnp.pad(
            a, [(0, 0), (0, n * w - t)] + [(0, 0)] * (a.ndim - 2))
        # (n, B, w, ...) chunks; the last may be short of live rows
        cut = lambda a: jnp.moveaxis(
            pad(a).reshape((b, n, w) + a.shape[2:]), 1, 0)
        counts = jnp.clip(t - jnp.arange(n) * w, 0, w)

        def step(state, chunk):
            xs, dts, bs, cs, count = chunk
            y, state = ssd_chunk_update(
                xs, dts, A, bs, cs, D, state,
                jnp.full((b,), count, jnp.int32), 0)
            return state, y

        state = jnp.zeros((1, b, H, P, self._N), self._state_dtype)
        _, ys = jax.lax.scan(step, state,
                             (cut(x), cut(dt), cut(Bm), cut(Cm), counts))
        return jnp.moveaxis(ys, 0, 1).reshape(b, n * w, H, P)[:, :t]
