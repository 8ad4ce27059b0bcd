"""Falcon-H1 (TII, 2025; https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct)
as plain jax.numpy in float32: the yardstick for `correct` in the serving
cells of this family, and the arithmetic of what a dispatch must compute.
One full forward over whole sequences: no cache, no pages, no kernel, no
chunked form of the recurrence (a `lax.scan` over single tokens).

Every block runs grouped-query attention and a Mamba-2 mixer side by side
on one RMS-normalised input, adds both to the residual, then a SwiGLU MLP.

Read from the published config.json, under the names `kwargs` gives them
(mxnet_tpu.models.FalconH1Config's): hidden_size (`units`),
num_hidden_layers (`num_layers`), num_attention_heads / num_key_value_heads
/ head_dim, intermediate_size (`hidden_size`), mamba_n_heads / mamba_d_head
/ mamba_d_state / mamba_n_groups / mamba_d_conv (`ssm_heads`,
`ssm_head_dim`, `ssm_state`, `ssm_groups`, `conv_kernel`; mamba_d_ssm is
heads x head size), rms_norm_eps, rope_theta (rope_scaling null),
vocab_size, and the fourteen multipliers (embedding, lm_head, attention_in,
attention_out, key, ssm_in, the five ssm_multipliers, ssm_out, the two
mlp_multipliers). attention_bias, mamba_proj_bias, mlp_bias and
projectors_bias are false (no bias but the convolution's, mamba_conv_bias
true); mamba_rms_norm true and mamba_norm_before_gate false (the gate, then
the norm); hidden_act silu; tie_word_embeddings false. mamba_use_mlp,
mlp_expansion_factor, mamba_expand and mamba_chunk_size change no equation.

Taken from the family's public modelling code, not from the config: the
order of `mup_vector`'s segments over the mixer's input projection (z, x,
B, C, dt, as the projection is split); that the gated norm's variance is
over each of the `ssm_groups` groups of channels, not over all of them;
rotary embedding over the two halves of a head (not interleaved pairs);
head h of the mixer reads group h // (heads / groups) of B and C; dt =
softplus(dt_raw + dt_bias), A = -exp(A_log).
"""
import math

import jax
import jax.numpy as jnp

# The program computes in bfloat16 (float32 for the recurrent state, the
# rotary embedding, the norms and the softplus) through blocks whose
# residual stream is never renormalised. lm_head_multiplier is 1/128, so
# with the cell's weights (weights_per_parameter.py: N(0, 0.02), norm scales
# 1 + N(0, 0.02), the mixer's A, dt and convolution as its published
# initialization draws them) the logits have a standard deviation of
# 0.01118, not GPT-2's 0.7. On the chip the model's differed from this
# reference's by 0.000521, 0.000578 and 0.000539 (my chip runs, PR 27:
# three seeds, 2 x 192 positions each; seven runs of the cell read 0.0005
# or 0.0006 at four decimals), and the limit is about twice the largest, a
# tenth of the spread. Two readings lie beyond it. This reference with the
# carried state zeroed every 64 tokens (`reset_every=64`: what a lost
# write-back, a stale slot or a state leaked from another request does to
# a sequence) differs from the program by 0.00385, a third of the spread;
# with N(0, 0.02) for the mixer too it was 0.00001 (on the CPU, two layers),
# and no limit could have seen the layer this configuration exists for. And the forward with every
# matrix rounded to float8_e4m3, the nearest precision below the weights',
# differed by 0.00382 (measured under the earlier draws). The engine's
# argmax stream is held to the reference's argmax wherever the reference's
# two best logits lie further apart than the limit.
# What this limit CANNOT see is the recurrent state's own precision. A
# bfloat16 state, rounded after every token, moves this reference's logits
# by 0.000038 at the published widths (float32 on the CPU, six layers,
# vocabulary cut to 4096): a fifteenth of the bfloat16 model's own error,
# because a read-out sums 256 roundings of either sign. (On the chip the
# same control reads exactly 0: the compiler drops a float32 -> bfloat16 ->
# float32 pair, `xla_allow_excess_precision`.) tests/test_falcon_h1.py
# holds the mixer's own output to the reference, with every multiplier 1
# and weights of standard deviation 0.2, where a bfloat16 state does fail.
TOLERANCE = {"logit_abs": 0.0012}

_HEAD_BLOCKS = 8        # the float32 head alone would be 5.3 GB


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """(B, T, H, D), position = the token's index in its sequence."""
    t, d = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # (T, D/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attention_branch(u, w, kw):
    """a of the module docstring's layer, from the normalised input u."""
    b, t, _ = u.shape
    hq, hkv, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    u = u * kw["attention_in_multiplier"]
    q = _rope((u @ w("attn.query.weight").T).reshape(b, t, hq, d),
              kw["rope_theta"])
    k = _rope((u @ w("attn.key.weight").T * kw["key_multiplier"])
              .reshape(b, t, hkv, d), kw["rope_theta"])
    v = (u @ w("attn.value.weight").T).reshape(b, t, hkv, d)
    # KV head g serves query heads g*(hq/hkv) .. : repeat each KV head
    k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bjhd,bthd->bhjt", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bhjt,bthd->bjhd", p, v).reshape(b, t, hq * d)
    return out @ w("attn.proj.weight").T * kw["attention_out_multiplier"]


def mixer_branch(u, w, kw, state_dtype=jnp.float32, reset_every=None):
    """m of the layer: the Mamba-2 mixer, its recurrence one token at a
    time. `state_dtype` and `reset_every` exist for the tightness tests
    alone: the carried state rounded to another type after every token,
    and zeroed every so many tokens."""
    b, t, _ = u.shape
    H, P = kw["ssm_heads"], kw["ssm_head_dim"]
    G, N, K = kw["ssm_groups"], kw["ssm_state"], kw["conv_kernel"]
    ds, m = H * P, kw["ssm_multipliers"]
    p = (u * kw["ssm_in_multiplier"]) @ w("mamba.in_proj.weight").T
    z, xbc, dt = jnp.split(p, [ds, 2 * ds + 2 * G * N], axis=-1)
    z, dt = z * m[0], dt * m[4]
    xbc = xbc * jnp.concatenate([jnp.full((ds,), m[1]),
                                 jnp.full((G * N,), m[2]),
                                 jnp.full((G * N,), m[3])])
    # causal depthwise convolution: K - 1 zero rows of left context
    cw = w("mamba.conv_weight")                                 # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = _silu(w("mamba.conv_bias")
                + sum(padded[:, k:k + t] * cw[:, k] for k in range(K)))
    x, B, C = jnp.split(xbc, [ds, ds + G * N], axis=-1)
    x = x.reshape(b, t, H, P)
    B = jnp.repeat(B.reshape(b, t, G, N), H // G, axis=2)       # per head
    C = jnp.repeat(C.reshape(b, t, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + w("mamba.dt_bias"))               # (B, T, H)
    A = -jnp.exp(w("mamba.A_log"))

    def token(S, row):
        i, x_t, b_t, c_t, dt_t = row
        if reset_every:
            S = jnp.where(i % reset_every == 0, 0.0, S)
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    rows = (jnp.arange(t),) + tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt))
    _, y = jax.lax.scan(token, jnp.zeros((b, H, P, N), jnp.float32), rows)
    y = jnp.moveaxis(y, 0, 1) + w("mamba.D")[:, None] * x
    g = y.reshape(b, t, ds) * _silu(z)
    gg = g.reshape(b, t, G, ds // G)
    gg = gg / jnp.sqrt(jnp.mean(gg * gg, -1, keepdims=True)
                       + kw["rms_norm_eps"])
    g = gg.reshape(b, t, ds) * w("mamba.norm.weight")
    return g @ w("mamba.out_proj.weight").T * kw["ssm_out_multiplier"]


def logits(params, kwargs, ids, **mixer_kw):
    """Next-token logits at every position, (B, T, V)."""
    kw = kwargs
    eps = kw["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        # rows are gathered in the stored type and upcast; every other
        # matrix is upcast where it is used, one at a time
        h = _f32(jnp.take(params["embed.weight"], ids, axis=0)) \
            * kw["embedding_multiplier"]
        for i in range(kw["num_layers"]):
            w = lambda name: _f32(params[f"layer{i}.{name}"])
            u = _rms(h, w("input_norm.weight"), eps)
            h = h + attention_branch(u, w, kw) \
                + mixer_branch(u, w, kw, **mixer_kw)
            v = _rms(h, w("ff_norm.weight"), eps)
            f = _silu(v @ w("gate.weight").T * kw["mlp_multipliers"][0]) \
                * (v @ w("up.weight").T)
            h = h + f @ w("down.weight").T * kw["mlp_multipliers"][1]
        h = _rms(h, _f32(params["final_norm.weight"]), eps)
        head = params["head.weight"]
        n = _HEAD_BLOCKS if head.shape[0] % _HEAD_BLOCKS == 0 else 1
        rows = head.shape[0] // n
        return jnp.concatenate(
            [h @ _f32(head[j * rows:(j + 1) * rows]).T for j in range(n)],
            -1) * kw["lm_head_multiplier"]


def _matmul_params(kw):
    d, f = kw["units"], kw["hidden_size"]
    hd, ds = kw["head_dim"], kw["ssm_heads"] * kw["ssm_head_dim"]
    gn = kw["ssm_groups"] * kw["ssm_state"]
    attn = 2 * d * hd * (kw["num_heads"] + kw["num_kv_heads"])
    mixer = d * (2 * ds + 2 * gn + kw["ssm_heads"]) + ds * d
    return kw["num_layers"] * (attn + mixer + 3 * d * f) \
        + d * kw["vocab_size"]


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs for one token that
    attends `context` keys: the matrices (the embedding is a gather), q k^T
    and p v over the query heads, and the state's update and read-out."""
    kw = kwargs
    ssm = 2 * kw["ssm_heads"] * kw["ssm_head_dim"] * kw["ssm_state"]
    return 2 * _matmul_params(kw) + kw["num_layers"] * (
        4 * kw["num_heads"] * kw["head_dim"] * context + 2 * ssm)


def attention_cost(kwargs, rows):
    """FLOPs and bytes the paged attention of ONE dispatch needs, over all
    layers. `rows` lists, for each slot with work, (context, count): the
    keys already in its pages and the query rows fed now. Query j attends
    context + j + 1 keys, 4 x (query heads x head size) FLOPs a key. Bytes:
    each slot's live keys and values are read once, at the width of the KV
    heads; its queries are read and outputs written at the query heads'."""
    kw = kwargs
    cq = kw["num_heads"] * kw["head_dim"]
    ckv = kw["num_kv_heads"] * kw["head_dim"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for context, count in rows:
        flops += 4 * cq * (count * context + count * (count + 1) // 2)
        bytes_ += (2 * (context + count) * ckv + 2 * count * cq) * itemsize
    return {"flops": kw["num_layers"] * flops,
            "bytes": kw["num_layers"] * bytes_}


def ssm_cost(kwargs, rows):
    """FLOPs and bytes the state-space chunk update of ONE dispatch needs,
    over all layers; `rows` as for attention_cost (the context costs
    nothing: that is the point of the layer). Per slot with work: the
    float32 state read once and written once; the live rows' x, B, C and y
    in the model's type and dt in float32; and 2 FLOPs a multiply-add of
    the state's read-out and update (heads x head size x state size a row
    each) and of the intra-chunk form over the live rows' lower triangle
    (C B^T per group, then its product with x per head)."""
    kw = kwargs
    h, p, n, g = (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
                  kw["ssm_groups"])
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for _context, count in rows:
        tri = count * (count + 1) // 2
        flops += 2 * (2 * count * h * p * n + tri * (g * n + h * p))
        bytes_ += 2 * h * p * n * 4 \
            + count * ((2 * h * p + 2 * g * n) * itemsize + 4 * h)
    return {"flops": kw["num_layers"] * flops,
            "bytes": kw["num_layers"] * bytes_}
