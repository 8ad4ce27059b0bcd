"""Kimi Linear (Moonshot AI; https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
arXiv:2510.26692) as plain jax.numpy in float32: the yardstick for
`correct` in the serving cells of this family, and the arithmetic of what a
dispatch must compute. One full forward over whole sequences: no cache, no
pages, no kernel, no chunked form of the delta rule (a `lax.scan` over
single tokens), latent attention UNABSORBED (every head's keys and values
are made from the latent; queries in blocks so that the scores fit), no
sort of the routed pairs (a loop over the held experts with a mask), the
head over the chosen positions only.

Every layer is h <- h + mixer(RMSNorm(h)), h <- h + ffn(RMSNorm(h)), eps
`rms_norm_eps`; a final RMSNorm and an untied head. `pattern` gives each
layer's mixer:

  K  KDA, for a normalised row x, heads of `kda_head_dim` (D):
     q, k, v = silu(conv4(x W_qkv)), three causal depthwise convolutions
     with no bias; q_h = q_h / |q_h| * D**-0.5, k_h = k_h / |k_h|;
     g = -exp(A_log_h) * softplus((x W_fa) W_fb + dt_bias)  a KEY CHANNEL
     b_h = sigmoid(x W_b)_h
     S_h <- Diag(exp g_h) S_h;  S_h <- S_h + b_h k_h (v_h - S_h^T k_h)^T
     o_h = S_h^T q_h;  y = (RMSNorm_D(o_h) * sigmoid((x W_ga) W_gb)_h) W_o
  L  latent attention, no rotary embedding (`mla_use_nope`):
     [c | r] = x W_dkv; c = RMSNorm(c); [qn_h | qr_h] = (x W_q)_h;
     [kn_h | v_h] = (c W_ukv)_h;
     s_h(t, j) = (qn_h(t) . kn_h(j) + qr_h(t) . r(j)) * (nope + rope)**-0.5
     for j <= t; p = softmax_j(s); o_h = sum_j p v_h(j); y = concat(o_h) W_o

The first `dense_layers` feed-forwards are (silu(x Wg) * (x Wu)) Wd; every
other is s = sigmoid(W_r x); T = top_k(s + b); w_e = scale * s_e / (sum_{j
in T} s_j + 1e-20); y = sum_{e in T, e held} w_e e(x) + shared(x), e and
shared the same gated form. The routed sum runs over the experts
`held_experts` = (first, count) names: the same share the program is given.

Read from the published config.json under the names `kwargs` gives them
(mxnet_tpu.models.KimiLinearConfig's): hidden_size (`units`),
linear_attn_config's two layer lists (`pattern`, K or L a layer),
linear_attn_config.num_heads / head_dim / short_conv_kernel_size
(`kda_heads`, `kda_head_dim`, `conv_kernel`), num_attention_heads,
kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
first_k_dense_replace (`dense_layers`), intermediate_size
(`dense_hidden_size`), num_experts, num_experts_per_token (`top_k`),
moe_intermediate_size (`expert_hidden_size`), num_shared_experts x
moe_intermediate_size (`shared_hidden_size`), routed_scaling_factor,
rms_norm_eps, vocab_size. q_lora_rank null (no low rank for the query),
mla_use_nope true, moe_router_activation_func sigmoid, moe_renormalize true,
num_expert_group = topk_group = 1 (no grouping of the choice), hidden_act
silu, tie_word_embeddings false.

Taken from the family's public modelling code (fla's KDA layer and the
checkpoint's modeling file), not from the config: the two low-rank widths
(`kda_low_rank`, the head size); A_log one a head and dt_bias one a channel;
no convolution bias; the L2 norm of q and k (x * rsqrt(sum x^2 + 1e-6)) and
the query's scale; the output norm per head with a learned weight of the
head size; the router's choice bias.
"""
import jax
import jax.numpy as jnp

# The program computes in bfloat16 (float32 for the KDA state, the decay's
# running sum, the router's scores, the norms, the softplus and the softmax).
# The cell's weights are weights_per_parameter.py's (N(0, 0.02); the KDA
# layers' A, dt and convolutions as their published initialisation draws
# them) with the latent layers' query, kv_up and kv_down drawn again at 0.03,
# 0.08 and 0.05 (the configuration's `draw`: at 0.02 a latent layer's softmax
# over thousands of keys is nearly uniform and nothing it does wrong reaches
# the logits). The logits' standard deviation is 0.96 (the final norm times
# a head of N(0, 0.02)). runners/serve_long.py reads, over the 2 x 24
# compared positions x 163840 words: `logit_rms`, the root of the mean
# squared difference; `logit_rms_p50`, the median over the positions of
# each position's own; `logit_abs`, the largest difference. All on the chip
# (my chip runs, PR 34; PERF.md section 6 has every reading), logit_rms:
#
#   the system's paged logits against this reference, 26 seeds  0.0642-0.0865
#   CONTROLS: state, router scores, softplus and norms in
#     bfloat16, 11 seeds (NOT told: see below)                  0.0633-0.0887
#   TOLERANCE                                                   0.135
#   every matrix in float8_e4m3, the nearest precision below
#     the weights', 11 seeds                                    0.1891-0.2093
#   routed scaling factor left out, 1 seed                      0.198
#   the shared key columns dropped, 11 seeds                    0.3713-0.4065
#   shared expert left out 0.592, delta rule's correction left out 0.658,
#   the latent's norm dropped (11 seeds) 0.8033-0.8373, state not carried
#   across a chunk 0.832, decay one a head 0.875, no renormalisation 0.985
#
# The limit is 1.56 times the largest of the system's readings and float8's
# smallest is 1.40 times the limit: both groups lie within +-0.011 of their
# means (0.073, 0.198), nine of their own standard deviations from it.
# logit_abs is printed and is no limit: top-8 of 256 is discontinuous, so a
# bfloat16 hidden state sends a token here and there to another expert than
# the reference's, which moves ONE position's logits by a few tenths (the
# largest difference is the tail of 7.9 million): the system read
# 0.469-0.821 (0.908 under the first draw) and float8 1.204-1.500, too
# close for a limit that a sound run must never cross. With the kernels'
# dense forms in place of the kernels the system reads the same (0.0716
# against 0.0718 at one seed, first draw): the difference is the model's
# precision, not the kernels'.
# NOT told, by this or any limit on these logits: what the configuration
# keeps in float32 kept in bfloat16 instead (CONTROLS). It moves the
# reference's logits by about 0.014, a fifth of what the program's own
# bfloat16 activations and matrices do, and the system reads the same
# against either (0.0633-0.0887 against 0.0642-0.0865, seed by seed within
# 0.009 of each other). The KDA kernel's float32 state is held by the CPU
# tests (tests/test_kimi_linear.py, against the token recurrence at 1e-4).
# The ENGINE's stream (32 slots, the timed program) is held to the paged
# path's own logits at every emitted position: two programs of one model
# whose bfloat16 roundings are independent (other shapes, other fusions)
# differ from each other about as the system differs from this reference,
# so the engine's token is the paged path's best at most positions and a
# near-best elsewhere. Fifteen sound runs on the chip: the paged path's best
# token at 39-47 of 48 positions, its logit for the engine's token short of
# its best by at most 0.0625-0.3125. With ONE fault that the one-slot path
# cannot have (a KDA layer's `fresh` reset moved to the next slot): the
# best token at 14 of 48, short by 2.81. STREAM_AGREE (30 of 48) and
# STREAM_MARGIN lie between, about as far from either reading in ratio. The
# stream is also held to the reference's argmax wherever the reference's
# two best logits lie further apart than ARGMAX_MARGIN, which a single
# position's difference (0.908 at most over 42 readings, and that the
# largest of 163840) does not bridge.
TOLERANCE = {"logit_rms": 0.135}
ARGMAX_MARGIN = 0.75
STREAM_MARGIN = 0.75
STREAM_AGREE = 0.625

_HEAD_BLOCKS = 8        # the float32 head alone would be 1.5 GB
_QUERY_BLOCK = 256      # rows of scores at a time: (heads, 256, T) float32

# the readings the limit has to lie under: each is this reference with one
# thing wrong, as keywords of `logits`
PERTURBATIONS = {
    "every_matrix_in_float8": {"matrix_dtype": "float8_e4m3fn"},
    "state_not_carried_across_a_chunk": {"reset_every": 64},
    "decay_one_a_head_not_one_a_channel": {"decay_per_head": True},
    "delta_rule_correction_left_out": {"no_delta": True},
    "shared_key_columns_dropped": {"no_shared_key": True},
    "latent_norm_dropped": {"no_latent_norm": True},
    "no_renormalisation": {"no_renorm": True},
    "routed_scaling_factor_left_out": {"no_scale": True},
    "shared_expert_left_out": {"no_shared": True},
}


# read and printed beside them, and NOT told by any limit (TOLERANCE, above,
# has the readings and the reason): what the configuration keeps in float32
# kept in bfloat16
CONTROLS = {
    "state_router_and_norms_in_bfloat16": {"state_dtype": "bfloat16"},
}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, weight, eps, rnd=_f32):
    """`rnd` rounds what a norm computed in a lower precision would round:
    the reciprocal root and the result (a tightness reading)."""
    return rnd(x * rnd(1 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
               * weight)


def _rounder(dtype):
    """Values rounded to `dtype` and back (a tightness reading: what the
    configuration keeps in float32, kept in the precision below)."""
    if dtype is None:
        return _f32
    return lambda a: a.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gated(x, gate_up, down):
    g, u = jnp.split(x @ gate_up.T, 2, axis=-1)
    return (_silu(g) * u) @ down.T


def kda_layer(u, w, kw, reset_every=None, decay_per_head=False,
              no_delta=False, rnd=_f32):
    """f of a `K` layer's mixer from the normalised input u (B, T, C), the
    rule one token at a time. The keywords are the tightness readings: the
    state zeroed every so many tokens, the decay averaged over a head's
    channels, the rule without its correction (S + b k v^T), the carried
    state, the softplus and the output norm rounded by `rnd`."""
    b, t, _ = u.shape
    H, D, K = kw["kda_heads"], kw["kda_head_dim"], kw["conv_kernel"]
    qkv = u @ w("qkv_proj.weight").T
    conv = w("conv_weight")                                 # (3HD, K)
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = _silu(sum(padded[:, j:j + t] * conv[:, j] for j in range(K)))
    q, k, v = (a.reshape(b, t, H, D) for a in jnp.split(qkv, 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * D ** -0.5, unit(k)
    g = -jnp.exp(w("A_log"))[:, None] * rnd(jax.nn.softplus(
        (u @ w("f_a.weight").T) @ w("f_b.weight").T
        + w("dt_bias"))).reshape(b, t, H, D)
    if decay_per_head:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ w("b_proj.weight").T)         # (B, T, H)

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t, i = row                    # (B, H, ...)
        if reset_every:
            S = jnp.where(i % reset_every == 0, 0.0, S)
        S = jnp.exp(g_t)[..., None] * S                     # (B, H, D, D)
        seen = 0.0 if no_delta else jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = rnd(S + b_t[..., None, None] * k_t[..., None]
                * (v_t - seen)[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)) \
        + (jnp.arange(t),)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, D, D), jnp.float32), rows)
    o = _rms(jnp.moveaxis(o, 0, 1), w("o_norm.weight"), kw["rms_norm_eps"],
             rnd)
    gate = jax.nn.sigmoid((u @ w("g_a.weight").T) @ w("g_b.weight").T)
    return (o.reshape(b, t, H * D) * gate) @ w("out_proj.weight").T


def latent_layer(u, w, kw, no_shared_key=False, no_latent_norm=False,
                 rnd=_f32):
    """f of an `L` layer's mixer, unabsorbed: every head's keys and values
    made from the latent, queries `_QUERY_BLOCK` at a time. The keywords
    are the tightness readings: the shared key part left out of the
    scores, the latent not normalised."""
    b, t, _ = u.shape
    H, R = kw["num_heads"], kw["kv_lora_rank"]
    nope, rope, V = (kw["qk_nope_head_dim"], kw["qk_rope_head_dim"],
                     kw["v_head_dim"])
    cr = u @ w("kv_down.weight").T
    c, r = cr[..., :R], cr[..., R:]
    if not no_latent_norm:
        c = _rms(c, w("kv_norm.weight"), kw["rms_norm_eps"], rnd)
    q = (u @ w("query.weight").T).reshape(b, t, H, nope + rope)
    kv = (c @ w("kv_up.weight").T).reshape(b, t, H, nope + V)
    kn, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
    blocks = -(-t // _QUERY_BLOCK)
    pad = blocks * _QUERY_BLOCK - t
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, blocks, _QUERY_BLOCK, H, nope + rope)

    def block(args):
        qs, first = args                                    # (B, QB, H, .)
        s = jnp.einsum("bjhd,bthd->bhjt", qs[..., :nope], kn)
        if not no_shared_key:
            s = s + jnp.einsum("bjhd,btd->bhjt", qs[..., nope:], r)
        at = first + jnp.arange(_QUERY_BLOCK)
        s = jnp.where(jnp.arange(t)[None, :] <= at[:, None], s * scale,
                      -jnp.inf)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return jnp.einsum("bhjt,bthd->bjhd", p, v)

    o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(blocks) * _QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * _QUERY_BLOCK, H * V)[:, :t]
    return o @ w("proj.weight").T


def routing(u, w, kw, no_renorm=False, no_scale=False, rnd=_f32):
    """(weights (..., k), experts (..., k)) of normalised rows u."""
    s = rnd(jax.nn.sigmoid(rnd(u @ w("experts.gate.weight").T)))
    _, chosen = jax.lax.top_k(s + w("experts.gate_bias"), kw["top_k"])
    vals = jnp.take_along_axis(s, chosen, axis=-1)
    if not no_renorm:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    return vals * (1.0 if no_scale else kw["routed_scaling_factor"]), chosen


def expert_layer(u, w, kw, params, prefix, cast, no_renorm=False,
                 no_scale=False, no_shared=False, rnd=_f32):
    """f of an expert layer's feed-forward. The stacked expert weights
    (the held experts', in order) are read from `params` one expert at a
    time. The keywords are the tightness readings."""
    first, count = kw.get("held_experts") or (0, kw["num_experts"])
    weights, chosen = routing(u, w, kw, no_renorm, no_scale, rnd)
    w1 = params[prefix + "experts.expert_w1"]
    w2 = params[prefix + "experts.expert_w2"]

    def one(total, e):
        # the weight of expert first + e where a row chose it, 0 where it
        # did not: the mask
        share = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1,
                        keepdims=True)
        g, up = jnp.split(u @ cast(w1[e]), 2, axis=-1)
        return total + share * ((_silu(g) * up) @ cast(w2[e])), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))
    if not no_shared:
        y = y + _gated(u, w("shared.gate_up.weight"), w("shared.down.weight"))
    return y


def logits(params, kwargs, ids, positions=None, matrix_dtype=None,
           state_dtype=None, **layer_kw):
    """Next-token logits, (B, T, V), or with `positions` (B, n) the logits
    of those positions alone, (B, n, V): the head over the whole vocabulary
    is 0.66 MB a position. Tightness readings: `matrix_dtype` rounds every
    matrix to that type first; `state_dtype` rounds to that type what the
    configuration keeps in float32 (the KDA state after every token, the
    softplus, the router's scores, every norm); `layer_kw` are the
    layers'."""
    kw = kwargs
    eps = kw["rms_norm_eps"]
    rnd = _rounder(state_dtype)
    pick = lambda names: dict({k: layer_kw[k] for k in names
                               if k in layer_kw}, rnd=rnd)
    kda_kw = pick(("reset_every", "decay_per_head", "no_delta"))
    latent_kw = pick(("no_shared_key", "no_latent_norm"))
    expert_kw = pick(("no_renorm", "no_scale", "no_shared"))
    if matrix_dtype is not None:
        matrix_dtype = jnp.dtype(matrix_dtype)

    def cast(a):
        if matrix_dtype is not None and a.ndim >= 2:
            a = a.astype(matrix_dtype)
        return _f32(a)

    with jax.default_matmul_precision("highest"):
        # rows are gathered in the stored type and upcast; every other
        # matrix is upcast where it is used, one at a time
        h = cast(jnp.take(params["embed.weight"], ids, axis=0))
        for i, kind in enumerate(kw["pattern"]):
            prefix = f"layer{i}."
            w = lambda name: cast(params[prefix + "mixer." + name])
            u = _rms(h, _f32(params[prefix + "norm.weight"]), eps, rnd)
            if kind == "K":
                h = h + kda_layer(u, w, kw, **kda_kw)
            else:
                h = h + latent_layer(u, w, kw, **latent_kw)
            w = lambda name: cast(params[prefix + "ffn." + name])
            u = _rms(h, _f32(params[prefix + "ffn_norm.weight"]), eps, rnd)
            if i < kw["dense_layers"]:
                h = h + _gated(u, w("gate_up.weight"), w("down.weight"))
            else:
                h = h + expert_layer(u, w, kw, params, prefix + "ffn.", cast,
                                     **expert_kw)
        h = _rms(h, _f32(params["final_norm.weight"]), eps, rnd)
        if positions is not None:
            h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        head = params["head.weight"]
        n = _HEAD_BLOCKS if head.shape[0] % _HEAD_BLOCKS == 0 else 1
        rows = head.shape[0] // n
        return jnp.concatenate(
            [h @ cast(head[j * rows:(j + 1) * rows]).T for j in range(n)],
            -1)


def _layers(kw, kind):
    return kw["pattern"].count(kind)


def _expert_layers(kw):
    return len(kw["pattern"]) - kw["dense_layers"]


def _held_share(kw):
    _, count = kw.get("held_experts") or (0, kw["num_experts"])
    return count / kw["num_experts"]


def _matmul_params(kw):
    """Matrix elements a token passes on this chip: the held share of its
    top_k experts on average."""
    d = kw["units"]
    hd = kw["kda_heads"] * kw["kda_head_dim"]
    low = kw.get("kda_low_rank") or kw["kda_head_dim"]
    kda = 4 * d * hd + 2 * (d * low + low * hd) + d * kw["kda_heads"]
    H, R = kw["num_heads"], kw["kv_lora_rank"]
    nope, rope, V = (kw["qk_nope_head_dim"], kw["qk_rope_head_dim"],
                     kw["v_head_dim"])
    latent = d * H * (nope + rope) + d * (R + rope) + R * H * (nope + V) \
        + H * V * d
    gated = lambda f: 3 * d * f
    expert = d * kw["num_experts"] + gated(kw["shared_hidden_size"]) \
        + kw["top_k"] * _held_share(kw) * gated(kw["expert_hidden_size"])
    return _layers(kw, "K") * kda + _layers(kw, "L") * latent \
        + kw["dense_layers"] * gated(kw["dense_hidden_size"]) \
        + _expert_layers(kw) * expert + d * kw["vocab_size"]


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs on this chip for one
    token that attends `context` keys: the matrices (the embedding is a
    gather; of the routed experts the held share), the absorbed scores and
    values of the latent layers (row 576, value 512 a head), and the KDA
    state's decay, update and read-out."""
    kw = kwargs
    row = kw["kv_lora_rank"] + kw["qk_rope_head_dim"]
    kda = 3 * kw["kda_heads"] * kw["kda_head_dim"] ** 2
    return 2 * _matmul_params(kw) \
        + _layers(kw, "L") * 2 * kw["num_heads"] \
        * (row + kw["kv_lora_rank"]) * context \
        + _layers(kw, "K") * 2 * kda


def attention_cost(kwargs, rows):
    """FLOPs and bytes the latent span attention of ONE dispatch needs, over
    the latent layers. `rows` lists, for each slot with work, (context,
    count): the rows already in its pages and the query rows fed now. Query
    j attends context + j + 1 rows, 2 x heads x (576 + 512) FLOPs a row:
    scores from the whole row, values from its latent part. Bytes: each
    slot's live rows are read ONCE, at the STORED width: the program pads
    a row to the next whole 128-lane tile (640 for 576), and the padding is
    read, so it is counted; its absorbed queries are read at that width a
    head and its outputs written at the latent's."""
    kw = kwargs
    H, R = kw["num_heads"], kw["kv_lora_rank"]
    stored = -(-(R + kw["qk_rope_head_dim"]) // 128) * 128
    per_row = 2 * H * (R + kw["qk_rope_head_dim"] + R)
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for context, count in rows:
        flops += per_row * (count * context + count * (count + 1) // 2)
        bytes_ += ((context + count) * stored
                   + count * H * (stored + R)) * itemsize
    return {"flops": _layers(kw, "L") * flops,
            "bytes": _layers(kw, "L") * bytes_}


def kda_cost(kwargs, rows):
    """FLOPs and bytes the KDA chunk update of ONE dispatch needs, over the
    KDA layers; `rows` as for attention_cost (the context costs nothing).
    Per slot with work: the float32 state read once and written once; the
    live rows' q, k, v and output in the model's type, their log-decay in
    float32 and their step; 2 FLOPs a multiply-add of the three products
    with the state (rows x D x D a head: what the rows see of it, what the
    queries read, what the rows add) and of the four products over the live
    rows' lower triangle (A, Aq, the solve against U, Aq U: D a pair). The
    inverse of (I + A) is the chunk form's own cost and is not counted."""
    kw = kwargs
    H, D = kw["kda_heads"], kw["kda_head_dim"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for _context, count in rows:
        tri = count * (count + 1) // 2
        flops += 2 * H * (3 * count * D * D + 4 * tri * D)
        bytes_ += 2 * H * D * D * 4 \
            + count * H * (4 * D * itemsize + 4 * D + 4)
    return {"flops": _layers(kw, "K") * flops,
            "bytes": _layers(kw, "K") * bytes_}


def expert_cost(kwargs, pairs, touched):
    """FLOPs and bytes the grouped expert feed-forward needs for `pairs`
    (row, held expert) pairs that touch `touched` experts, summed over
    whatever layers and dispatches the two counts are summed over: 6 x
    units x hidden FLOPs a pair (three products, 2 FLOPs a multiply-add);
    the three matrices of a touched expert read once; a pair's row read and
    its result written."""
    kw = kwargs
    d, f = kw["units"], kw["expert_hidden_size"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    return {"flops": 6 * d * f * pairs,
            "bytes": (3 * d * f * touched + 2 * d * pairs) * itemsize}
