"""What the hybrid decoders (models/falcon_h1.py, models/nemotron_h.py,
models/kimi_linear.py) are built from: RMSNorm, grouped-query attention
over a ragged paged cache with the rotary embedding on or off, the Mamba-2
mixer (its parameters, causal convolution, gated norm, and the recurrence
with and without carried state), the gated delta-rule mixer with a
per-channel decay (KDA), latent attention whose cache row has no head
axis, and the gated feed-forward.

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

__all__ = ["RMSNorm", "Attention", "Mixer", "KDAMixer", "LatentAttention",
           "GatedMLP", "rms_norm", "rope", "linear", "raw", "kernel_impl",
           "require_recurrent_cache", "causal_conv", "next_conv_tail",
           "scan_chunks", "pick_live_rows", "over_live_rows",
           "count_live_rows", "LIVE_ROWS_COUNTER"]

# a dispatch whose live rows fit one part in so many of its (B, W) grid
# runs its row-wise feed-forwards over the live rows alone (over_live_rows).
# An eighth by measurement (PERF.md, PR 38): at 2048 rows of which ~105 are
# live a quarter's feed-forward costs 13.1 ms a dispatch, an eighth's 8.0, a
# half's 23.7, and 98% of a decode-heavy backlog's ticks fit an eighth
COMPACT_GRID_SHARE = 8
# state_spec()["counters"] of a model that calls over_live_rows:
# cumulative (dispatches, dispatches that took the compact form)
LIVE_ROWS_COUNTER = {"live_rows": ((2,), "int32")}


def raw(p):
    return p.data()._data


def linear(x, dense):
    return jnp.matmul(x, raw(dense.weight).T)


def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def pick_live_rows(live):
    """What every feed-forward of a dispatch shares, worked out once from
    `live` (B, W) bool: (whether the live rows fit T, the grid indices of
    the first T live rows in row order, each row's rank among the live), T
    an eighth of the grid. Past the live count the indices repeat the
    grid's last row, and a dead row's rank is some live row's: nothing
    reads either."""
    flat = live.reshape(-1)
    t = max(1, flat.size // COMPACT_GRID_SHARE)
    upto = jnp.cumsum(flat, dtype=jnp.int32)
    first = jnp.searchsorted(upto, jnp.arange(1, t + 1, dtype=jnp.int32),
                             method="compare_all")
    return (upto[-1] <= t, jnp.minimum(first, flat.size - 1),
            jnp.clip(upto - 1, 0, t - 1))


def over_live_rows(fn, v, live, pick):
    """`fn`, a row-wise function of (rows, C), over `v` (B, W, C). With
    `pick` (pick_live_rows(live): a dispatch through a cache) the device
    chooses each call: where the live rows fit T it gathers them, applies
    `fn` once to (T, C), brings each live row its result by a gather on
    its rank and hands dead rows exact zeros; where they do not, `fn` over
    the whole grid. Without `pick` (whole sequences: every row is live)
    `fn` is called directly and no conditional is traced."""
    b, w, c = v.shape
    rows = v.reshape(b * w, c)
    if pick is None:
        return fn(rows).reshape(b, w, -1)
    fits, first, rank = pick

    def compact(rows):
        out = jnp.take(fn(jnp.take(rows, first, axis=0, mode="clip")),
                       rank, axis=0, mode="clip")
        return jnp.where(live.reshape(-1, 1), out, 0)

    return jax.lax.cond(fits, compact, fn, rows).reshape(b, w, -1)


def count_live_rows(cache, pick):
    """The cache with this dispatch added to its `live_rows` counter."""
    rec = cache.recurrent
    return cache.with_recurrent(dict(
        rec, live_rows=rec["live_rows"]
        + jnp.stack([jnp.int32(1), pick[0].astype(jnp.int32)])))


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
    if not getattr(cache, "ragged", False) or cache.spans is None \
            or cache.recurrent is None:
        raise MXNetError(
            f"{type(model).__name__} decodes through a ragged "
            "PagedKVCache that carries `recurrent` state and "
            "`spans` (serving.ServingEngine, or make_cache())")


def causal_conv(x, tail, weight, bias=None):
    """Causal depthwise convolution and SiLU of (B, T, C) rows whose left
    context is `tail` (B, K-1, C), `weight` (C, K); returns the rows and
    the two joined, from which the next tail is cut."""
    w = weight.astype(jnp.float32)
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    t, ff = x.shape[1], full.astype(jnp.float32)
    out = sum(ff[:, k:k + t] * w[:, k] for k in range(w.shape[1]))
    if bias is not None:
        out = bias.astype(jnp.float32) + out
    return jax.nn.silu(out).astype(x.dtype), full


def next_conv_tail(full, spans, k):
    """The last k - 1 LIVE rows of `full` (a chunk behind its old tail):
    rows spans-k+1 .. spans-1 of the chunk, which reach into the old tail
    while a slot has fed fewer than k - 1 rows, and are the old tail itself
    for a slot with no live row."""
    at = spans[:, None] + jnp.arange(k - 1)[None, :]
    return jnp.take_along_axis(full, at[:, :, None], axis=1)


def scan_chunks(update, rows, state, width):
    """A recurrence over whole sequences from `state`: the serving path's
    chunk update scanned over chunks of `width` rows. `rows` are (B, T, ...)
    arrays; `update(state, chunk_rows, counts)` -> (state, (B, width, ...)
    output), `counts` the chunk's live rows a sequence (the last chunk may
    be short of them). Returns the (B, T, ...) outputs."""
    b, t = rows[0].shape[:2]
    n = -(-t // width)
    pad = lambda a: jnp.pad(
        a, [(0, 0), (0, n * width - t)] + [(0, 0)] * (a.ndim - 2))
    # (n, B, width, ...) chunks
    cut = lambda a: jnp.moveaxis(
        pad(a).reshape((b, n, width) + a.shape[2:]), 1, 0)
    counts = jnp.clip(t - jnp.arange(n) * width, 0, width)

    def step(state, chunk):
        *chunk_rows, count = chunk
        state, out = update(state, chunk_rows,
                            jnp.full((b,), count, jnp.int32))
        return state, out

    _, out = jax.lax.scan(step, state, (*map(cut, rows), counts))
    return jnp.moveaxis(out, 0, 1).reshape(
        (b, n * width) + out.shape[3:])[:, :t]


class RMSNorm(HybridBlock):
    def __init__(self, units, **kwargs):
        super().__init__(**kwargs)
        self.weight = Parameter("weight", shape=(units,), init="ones")


class Attention(HybridBlock):
    """Causal softmax attention, `num_heads` query heads over
    `num_kv_heads` KV heads of `head_dim`, no bias. With `rotary` the
    queries and keys are rotated at the token's own position and the keys
    go to the pages already rotated; without, nothing here reads a
    position (the order is then some other layer's to carry).

    Off unless asked, each adding its own parameters: `qk_norm`, the
    queries and keys RMS-normalised over each head's `head_dim` columns
    with a learned weight of `head_dim` (q_norm, k_norm; eps `norm_eps`),
    before the rotation; `output_gate`, the heads' concatenated output
    times sigmoid(x W_gate) before the projection; `window` W, a token
    sees the W keys up to and including its own. A window layer keeps its
    keys in the slot's RING of pages (PagedKVCache.ring: the recurrent
    leaves `ring_k` and `ring_v`, its `layer` a ring layer) and attends
    through the window kernel, which visits no page before the window;
    every other layer keeps whole-depth pages (its `layer` a page layer)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rotary=True, rope_theta=1e4, in_multiplier=1.0,
                 key_multiplier=1.0, out_multiplier=1.0, qk_norm=False,
                 output_gate=False, window=None, norm_eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads over "
                             f"{num_kv_heads} KV heads")
        if window is not None and window < 1:
            raise MXNetError(f"window {window} < 1")
        self._heads, self._kv_heads, self._dim = (num_heads, num_kv_heads,
                                                  head_dim)
        self._rotary, self._theta = bool(rotary), rope_theta
        self._in, self._key, self._out = (in_multiplier, key_multiplier,
                                          out_multiplier)
        self._qk_norm, self._gated = bool(qk_norm), bool(output_gate)
        self.window, self._eps = window, norm_eps
        kw = dict(use_bias=False, flatten=False, in_units=units)
        self.query = Dense(num_heads * head_dim, **kw)
        self.key = Dense(num_kv_heads * head_dim, **kw)
        self.value = Dense(num_kv_heads * head_dim, **kw)
        if self._qk_norm:
            self.q_norm = RMSNorm(head_dim)
            self.k_norm = RMSNorm(head_dim)
        if self._gated:
            self.gate = Dense(num_heads * head_dim, **kw)
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
        if self._qk_norm:
            q = rms_norm(q, raw(self.q_norm.weight), self._eps)
            k = rms_norm(k, raw(self.k_norm.weight), self._eps)
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
            if self.window is not None:
                causal &= jnp.arange(t)[None, :] \
                    > jnp.arange(t)[:, None] - self.window
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            out = jnp.einsum("bhgjt,bthd->bjhgd", w.astype(v.dtype), v)
        else:
            # the kernel's causal offset and the positions above come
            # from the same lengths (the kernels' modules load Pallas:
            # imported where they are first traced, as models/gpt2.py
            # does, not with the package)
            from ..ops.pallas_attention import ragged_span_attention
            # a window layer's pages are the slot's ring of them
            pages = cache if self.window is None else cache.ring()
            pages = pages.write_decode(layer, k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3))
            # int8 pages keep the query in its own type and hand the
            # kernel the per-(page, head) scales (both None otherwise)
            out = ragged_span_attention(
                q if pages.quantized else q.astype(pages.k_pages.dtype),
                pages.k_pages, pages.v_pages, pages.page_table,
                cache.length + 1, q_counts=cache.spans, layer=layer,
                num_kv_heads=hkv, k_scale=pages.k_scale,
                v_scale=pages.v_scale, window=self.window,
                **kernel_impl(cache)).astype(u.dtype)
            cache = pages if self.window is None else cache.with_ring(pages)
        out = out.reshape(b, t, hq * d)
        if self._gated:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                linear(u, self.gate).astype(jnp.float32))).astype(u.dtype)
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
            tail = jnp.zeros((b, K - 1, self.conv_width), xbc.dtype)
        else:
            rec = cache.recurrent
            tail = jnp.where(fresh[:, None, None], 0, rec["conv"][layer])
        xbc, full = causal_conv(xbc, tail, raw(self.conv_weight),
                                raw(self.conv_bias))
        if cache is not None:
            tail = next_conv_tail(full, cache.spans, K)
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

        def update(state, rows, counts):
            xs, dts, bs, cs = rows
            y, state = ssd_chunk_update(xs, dts, A, bs, cs, D, state,
                                        counts, 0)
            return state, y

        return scan_chunks(
            update, (x, dt, Bm, Cm),
            jnp.zeros((1, b, H, P, self._N), self._state_dtype),
            min(self._chunk, -(-t // 8) * 8))

class GatedMLP(HybridBlock):
    """(silu(x Wg) * (x Wu)) Wd, no bias; `gate_up` holds [Wg | Wu]."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self.gate_up = Dense(2 * hidden_size, use_bias=False, flatten=False,
                             in_units=units)
        self.down = Dense(units, use_bias=False, flatten=False,
                          in_units=hidden_size)

    def forward(self, u):
        g, up = jnp.split(linear(u, self.gate_up), 2, axis=-1)
        return linear(jax.nn.silu(g) * up, self.down)


class KDAMixer(HybridBlock):
    """Kimi Delta Attention: a gated delta rule with a log-decay PER KEY
    CHANNEL (ops/kda.py has the recurrence). For a normalised row x:
    q, k, v = silu(conv(x W_qkv)) in `heads` heads of `head_dim`, three
    causal depthwise convolutions with no bias (held as ONE, over q | k |
    v); q and k L2-normalised a head, q scaled by head_dim**-0.5;
    g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias) a channel, A_log
    one a head; b = sigmoid(x W_b) a head; the output of the rule
    RMS-normalised a head (a learned weight of head_dim), gated by
    sigmoid((x W_ga) W_gb), and projected back."""

    def __init__(self, units, heads, head_dim, conv_kernel=4, low_rank=None,
                 chunk_size=64, eps=1e-5, state_dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._H, self._D, self._K = heads, head_dim, conv_kernel
        self._chunk, self._eps = chunk_size, eps
        self._state_dtype = state_dtype
        self.width = heads * head_dim
        kw = dict(use_bias=False, flatten=False)
        self.qkv_proj = Dense(3 * self.width, in_units=units, **kw)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(3 * self.width, conv_kernel))
        low_rank = low_rank or head_dim
        self.f_a = Dense(low_rank, in_units=units, **kw)
        self.f_b = Dense(self.width, in_units=low_rank, **kw)
        self.dt_bias = Parameter("dt_bias", shape=(self.width,),
                                 init="zeros")
        self.A_log = Parameter("A_log", shape=(heads,), init="zeros")
        self.b_proj = Dense(heads, in_units=units, **kw)
        self.g_a = Dense(low_rank, in_units=units, **kw)
        self.g_b = Dense(self.width, in_units=low_rank, **kw)
        self.o_norm = RMSNorm(head_dim)
        self.out_proj = Dense(units, in_units=self.width, **kw)

    def state_leaves(self, dtype):
        """What a slot holds for ONE layer of this mixer: the last K - 1
        rows of the convolutions' input, and S of every head, transposed
        (values by keys: ops/kda.py)."""
        return {"conv": ((self._K - 1, 3 * self.width), dtype),
                "kda": ((self._H, self._D, self._D), self._state_dtype)}

    def forward(self, u, cache, layer, fresh):
        """(B, T, C) normalised rows -> (the branch's output, the cache
        with state layer `layer` advanced). Without a cache: whole
        sequences from zero state."""
        from ..ops.kda import kda_chunk_update
        b, t, _ = u.shape
        H, D, K = self._H, self._D, self._K
        f32 = jnp.float32
        with jax.named_scope("kda.mixer"):
            qkv = linear(u, self.qkv_proj)
            if cache is None:
                tail = jnp.zeros((b, K - 1, 3 * self.width), qkv.dtype)
            else:
                rec = cache.recurrent
                tail = jnp.where(fresh[:, None, None], 0, rec["conv"][layer])
            qkv, full = causal_conv(qkv, tail, raw(self.conv_weight))
            if cache is not None:
                rec = dict(rec, conv=rec["conv"].at[layer].set(
                    next_conv_tail(full, cache.spans, K)
                    .astype(rec["conv"].dtype)))
            q, k, v = (a.reshape(b, t, H, D)
                       for a in jnp.split(qkv, 3, axis=-1))
            unit = lambda a: a.astype(f32) * jax.lax.rsqrt(
                jnp.sum(jnp.square(a.astype(f32)), -1, keepdims=True) + 1e-6)
            q = (unit(q) * D ** -0.5).astype(u.dtype)
            k = unit(k).astype(u.dtype)
            g = -jnp.exp(raw(self.A_log).astype(f32))[:, None] \
                * jax.nn.softplus(
                    linear(linear(u, self.f_a), self.f_b).astype(f32)
                    + raw(self.dt_bias).astype(f32)).reshape(b, t, H, D)
            beta = jax.nn.sigmoid(linear(u, self.b_proj).astype(f32))
            if cache is None:
                o = self._scan_chunks(q, k, v, g, beta)
            else:
                o, kda = kda_chunk_update(
                    q, k, v, g, beta, rec["kda"], cache.spans, layer,
                    fresh=fresh, **kernel_impl(cache))
                cache = cache.with_recurrent(dict(rec, kda=kda))
            o = rms_norm(o, raw(self.o_norm.weight), self._eps)
            gate = jax.nn.sigmoid(
                linear(linear(u, self.g_a), self.g_b).astype(f32))
            o = (o.reshape(b, t, -1).astype(f32) * gate).astype(u.dtype)
            return linear(o, self.out_proj), cache

    def _scan_chunks(self, q, k, v, g, beta):
        """The rule over whole sequences: the serving path's chunk update
        scanned over chunks of the sequence from zero state."""
        from ..ops.kda import kda_chunk_update
        b, t, H, D = q.shape

        def update(state, rows, counts):
            o, state = kda_chunk_update(*rows, state, counts, 0)
            return state, o

        return scan_chunks(
            update, (q, k, v, g, beta),
            jnp.zeros((1, b, H, D, D), self._state_dtype),
            min(self._chunk, -(-t // 16) * 16))

class LatentAttention(HybridBlock):
    """Multi-head latent attention with NO rotary embedding: a token's
    cache row is [c | r], `kv_rank` latent numbers RMS-normalised and
    `rope_dim` key numbers shared by every head (named for the rotary part
    they are in the family this comes from; nothing is rotated here), and
    has no head axis. Head h's key is [W_uk_h c | r], its value W_uv_h c,
    its query [qn_h | qr_h]; scores are scaled by
    (nope_dim + rope_dim)**-0.5.

    Served ABSORBED: the query takes the up-projection, q~_h = [qn_h W_uk_h
    | qr_h], scores come from the stored row, the weighted sum of the rows'
    latent parts is projected by W_uv_h after. The stored row is padded
    with zeros to `row_width` columns, the next whole 128-lane tile (a page
    is then a Mosaic block and the page write takes it); its value is its
    leading `kv_rank` columns. ONE pool holds it, and no V pool."""

    def __init__(self, units, num_heads, kv_rank, nope_dim, rope_dim,
                 value_dim, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._H, self._R = num_heads, kv_rank
        self._nope, self._rope, self._V = nope_dim, rope_dim, value_dim
        self._eps = eps
        self.row_width = -(-(kv_rank + rope_dim) // 128) * 128
        kw = dict(use_bias=False, flatten=False)
        self.kv_down = Dense(kv_rank + rope_dim, in_units=units, **kw)
        self.kv_norm = RMSNorm(kv_rank)
        self.query = Dense(num_heads * (nope_dim + rope_dim),
                           in_units=units, **kw)
        # a head's rows: its nope_dim key rows, then its value rows
        self.kv_up = Dense(num_heads * (nope_dim + value_dim),
                           in_units=kv_rank, **kw)
        self.proj = Dense(units, in_units=num_heads * value_dim, **kw)

    def forward(self, u, cache, layer):
        """(B, T, C) normalised rows -> (the branch's output, the cache
        with this layer's rows written to page layer `layer`)."""
        H, R = self._H, self._R
        nope, rope_, V = self._nope, self._rope, self._V
        b, t, _ = u.shape
        scale = (nope + rope_) ** -0.5
        with jax.named_scope("latent.attention"):
            cr = linear(u, self.kv_down)
            c = rms_norm(cr[..., :R], raw(self.kv_norm.weight), self._eps)
            row = jnp.concatenate(
                [c, cr[..., R:],
                 jnp.zeros((b, t, self.row_width - R - rope_), c.dtype)], -1)
            q = linear(u, self.query).reshape(b, t, H, nope + rope_)
            up = raw(self.kv_up.weight).reshape(H, nope + V, R)
            # the query takes the keys' up-projection
            qa = jnp.concatenate(
                [jnp.einsum("bthd,hdc->bthc", q[..., :nope], up[:, :nope]),
                 q[..., nope:],
                 jnp.zeros((b, t, H, self.row_width - R - rope_), q.dtype)],
                -1).astype(u.dtype)
            if cache is None:
                s = jnp.einsum("bjhw,btw->bhjt", qa, row,
                               preferred_element_type=jnp.float32) * scale
                causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
                w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
                o = jnp.einsum("bhjt,btc->bjhc", w.astype(row.dtype), c)
            else:
                from ..ops.pallas_attention import latent_span_attention
                cache = cache.write_decode(layer, row[:, None], None)
                o = latent_span_attention(
                    qa.astype(cache.k_pages.dtype), cache.k_pages,
                    cache.page_table, cache.length + 1,
                    q_counts=cache.spans, value_width=R, scale=scale,
                    layer=layer, **kernel_impl(cache)).astype(u.dtype)
            o = jnp.einsum("bthc,hdc->bthd", o, up[:, nope:])
            return linear(o.reshape(b, t, H * V).astype(u.dtype),
                          self.proj), cache
