"""Nemotron-H (NVIDIA; https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16)
as plain jax.numpy in float32: the yardstick for `correct` in the serving
cells of this family, and the arithmetic of what a dispatch must compute.
One full forward over whole sequences: no cache, no pages, no kernel, no
chunked form of the recurrence (a `lax.scan` over single tokens), no sort
of the routed pairs (a loop over the held experts with a mask).

`hybrid_override_pattern` (`pattern`) gives each layer ONE kind, and every
layer is h <- h + f(RMSNorm(h)):

  M  Mamba-2 mixer: in_proj to z | x, B, C | dt; causal depthwise
     convolution + SiLU over x, B, C; dt = softplus(dt + dt_bias),
     A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
     y_t = S_t C_t + D x_t, head h reads group h // (heads / groups);
     gated RMSNorm (the gate first, the variance per group); out_proj.
  *  causal softmax attention, grouped KV heads, NO rotary embedding (the
     family's modelling code applies none; `rotary` is the one keyword).
  E  latent mixture of experts, for a normalised row u:
     s = sigmoid(W_r u) over all the experts; T = top_k(s + b);
     w_e = scale * s_e / (sum_{j in T} s_j + 1e-20); z = W_in u;
     y = W_out(sum_{e in T, e held} w_e W2_e relu(W1_e z)^2)
         + W2_s relu(W1_s u)^2.
     The routed sum runs over the experts `held_experts` = (first, count)
     names: the same share the program is given. The weights are
     normalised over all of T, held or not.

Read from the published config.json under the names `kwargs` gives them
(mxnet_tpu.models.NemotronHConfig's): hidden_size (`units`),
hybrid_override_pattern (`pattern`), num_attention_heads /
num_key_value_heads / head_dim, mamba_num_heads / mamba_head_dim /
ssm_state_size / n_groups / conv_kernel (`ssm_heads`, `ssm_head_dim`,
`ssm_state`, `ssm_groups`, `conv_kernel`), n_routed_experts / num_experts_per_tok
/ moe_latent_size / moe_intermediate_size /
moe_shared_expert_intermediate_size / routed_scaling_factor (`num_experts`,
`top_k`, `latent_size`, `expert_hidden_size`, `shared_hidden_size`),
norm_eps, vocab_size. mlp_hidden_act relu2, norm_topk_prob true, n_group =
topk_group = 1 (no grouping of the choice), use_conv_bias true, no other
bias, tie_word_embeddings false. chunk_size changes no equation.
num_nextn_predict_layers (a drafting module beside the model) is not built.
"""
import math

import jax
import jax.numpy as jnp

from . import falcon_h1
from .falcon_h1 import _f32, _rms, _rope

# The program computes in bfloat16 (float32 for the recurrent state, the
# router's scores, the norms and the softplus). With the cell's weights
# (weights_per_parameter.py: N(0, 0.02); the mixer's A, dt and convolution
# as its published initialization draws them) the logits have a standard
# deviation of 1.28. On the chip the model's differed from this
# reference's by 0.1573 and 0.1450 (tightness.py, 2 x 192 positions) and
# by 0.1500-0.1863 in the cell's own check at eight seeds (my chip runs,
# PR 32; PERF.md has every one): an eighth of the spread, more than bfloat16 alone costs a dense
# model, because top-22 of 512 is discontinuous: the model's own forward
# chose 98.7-99.2% of the reference's experts a layer, and in the last
# expert layer 26% of the rows differ from the reference in at least one
# of their 22 (on the CPU in float32 the chosen sets are the reference's
# exactly: tests/test_nemotron_h.py). The limit is 1.34 times the largest
# of the ten readings. Beyond it lie, each read the same way as this reference with
# ONE thing wrong against the unchanged model (PERTURBATIONS below; my
# chip run, PR 32): every matrix rounded to float8_e4m3, the nearest
# precision below the weights', 0.4629; the carried state zeroed every 64
# tokens 1.3149; routed_scaling_factor left out 0.5341; the shared expert
# left out 9.1811; the held range shifted by one expert 0.6303; the choice
# made without the bias 0.3608 with the bias as the cell draws it, N(0,
# 0.02), and 0.5978 with it redrawn at the scores' own spread, 0.25 (the
# model then differs from the reference by 0.1450). The nearest is 1.4
# times the limit. The engine's argmax stream is held to the reference's
# argmax wherever the reference's two best logits lie further apart than
# the limit.
TOLERANCE = {"logit_abs": 0.25}

_HEAD_BLOCKS = 8        # the float32 head alone would be 2.1 GB

# the readings the limit has to lie under (benchmarks/tightness.py): each
# is this reference with one thing wrong, as keywords of `logits`
PERTURBATIONS = {
    "every_matrix_in_float8": {"matrix_dtype": "float8_e4m3fn"},
    "state_zeroed_every_64_tokens": {"reset_every": 64},
    "routed_scaling_factor_left_out": {"no_scale": True},
    "shared_expert_left_out": {"no_shared": True},
    "held_range_shifted_by_one_expert": {"held_shift": 1},
    # told only where the bias decides choices: tightness.py redraws it at
    # the scores' own spread for this one
    "choice_made_without_the_bias": {"no_bias": True},
}


def attention_layer(u, w, kw):
    """f of a `*` layer, from the normalised input u."""
    b, t, _ = u.shape
    hq, hkv, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    q = (u @ w("query.weight").T).reshape(b, t, hq, d)
    k = (u @ w("key.weight").T).reshape(b, t, hkv, d)
    v = (u @ w("value.weight").T).reshape(b, t, hkv, d)
    if kw.get("rotary"):
        q, k = _rope(q, kw["rope_theta"]), _rope(k, kw["rope_theta"])
    # KV head g serves query heads g*(hq/hkv) .. : repeat each KV head
    k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bjhd,bthd->bhjt", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bhjt,bthd->bjhd", p, v).reshape(b, t, hq * d)
    return out @ w("proj.weight").T


def mixer_layer(u, w, kw, **readings):
    """f of an `M` layer, its recurrence one token at a time: Falcon-H1's
    mixer (reference/falcon_h1.py `mixer_branch`, which has the equations)
    with every multiplier 1. `readings` are its `state_dtype` and
    `reset_every`, for the tightness readings alone."""
    return falcon_h1.mixer_branch(
        u, lambda name: w(name.removeprefix("mamba.")),
        dict(kw, ssm_in_multiplier=1.0, ssm_multipliers=(1.0,) * 5,
             ssm_out_multiplier=1.0), **readings)


def routing(u, w, kw, use_bias=True):
    """(weights (..., k), experts (..., k)) of normalised rows u."""
    s = jax.nn.sigmoid(u @ w("experts.gate.weight").T)
    pick = s + w("experts.gate_bias") if use_bias else s
    _, chosen = jax.lax.top_k(pick, kw["top_k"])
    vals = jnp.take_along_axis(s, chosen, axis=-1)
    scale = kw["routed_scaling_factor"]
    return scale * vals / (vals.sum(-1, keepdims=True) + 1e-20), chosen


def expert_layer(u, w, kw, params, prefix, no_scale=False, no_shared=False,
                 no_bias=False, held_shift=0, chosen_out=None):
    """f of an `E` layer. The stacked expert weights are read from
    `params` one expert at a time (a float32 copy of a layer's would be
    2.8 GB). The keywords are the tightness readings: the scaling factor
    left out, the shared expert left out, the choice made without the
    bias, and the held experts taken to be `held_shift` further on."""
    first, count = kw.get("held_experts") or (0, kw["num_experts"])
    weights, chosen = routing(u, w, kw, use_bias=not no_bias)
    if no_scale:
        weights = weights / kw["routed_scaling_factor"]
    if chosen_out is not None:
        chosen_out.append(chosen)
    z = u @ w("latent_in.weight").T

    def one(total, e_w):
        e, w1, w2 = e_w
        # the weight of expert first + held_shift + e where a row chose
        # it, 0 where it did not: the mask
        share = jnp.sum(jnp.where(chosen == first + held_shift + e,
                                  weights, 0.0), -1, keepdims=True)
        h = jnp.maximum(z @ _f32(w1), 0.0) ** 2
        return total + share * (h @ _f32(w2)), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(z),
        (jnp.arange(count), params[prefix + "experts.expert_w1"],
         params[prefix + "experts.expert_w2"]))
    y = total @ w("latent_out.weight").T
    if not no_shared:
        y = y + (jnp.maximum(u @ w("shared_up.weight").T, 0.0) ** 2) \
            @ w("shared_down.weight").T
    return y


def logits(params, kwargs, ids, matrix_dtype=None, chosen_out=None,
           **layer_kw):
    """Next-token logits at every position, (B, T, V). `matrix_dtype`
    rounds every matrix to that type first (a tightness reading);
    `chosen_out`, a list, gets each expert layer's chosen experts;
    `layer_kw` are mixer_layer's and expert_layer's readings."""
    kw = kwargs
    eps = kw["rms_norm_eps"]
    mixer_kw = {k: layer_kw.pop(k) for k in ("state_dtype", "reset_every")
                if k in layer_kw}

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
            prefix = f"layer{i}.mixer."
            w = lambda name: cast(params[prefix + name])
            u = _rms(h, _f32(params[f"layer{i}.norm.weight"]), eps)
            if kind == "M":
                h = h + mixer_layer(u, w, kw, **mixer_kw)
            elif kind == "*":
                h = h + attention_layer(u, w, kw)
            else:
                stacked = params if matrix_dtype is None else {
                    k: v.astype(matrix_dtype) for k, v in params.items()
                    if k.startswith(prefix + "experts.expert_w")}
                h = h + expert_layer(u, w, kw, stacked, prefix,
                                     chosen_out=chosen_out, **layer_kw)
        h = _rms(h, _f32(params["final_norm.weight"]), eps)
        head = params["head.weight"]
        n = _HEAD_BLOCKS if head.shape[0] % _HEAD_BLOCKS == 0 else 1
        rows = head.shape[0] // n
        return jnp.concatenate(
            [h @ cast(head[j * rows:(j + 1) * rows]).T for j in range(n)],
            -1)


def chosen_experts(params, kwargs, ids):
    """Each expert layer's chosen experts, (B, T, k) a layer."""
    out = []
    logits(params, kwargs, ids, chosen_out=out)
    return out


def _layers(kw, kind):
    return kw["pattern"].count(kind)


def _held_share(kw):
    _, count = kw.get("held_experts") or (0, kw["num_experts"])
    return count / kw["num_experts"]


def _matmul_params(kw):
    """Matrix elements a token passes on this chip: the held share of
    its top_k experts on average."""
    d = kw["units"]
    hd, ds = kw["head_dim"], kw["ssm_heads"] * kw["ssm_head_dim"]
    gn = kw["ssm_groups"] * kw["ssm_state"]
    attn = 2 * d * hd * (kw["num_heads"] + kw["num_kv_heads"])
    mixer = d * (2 * ds + 2 * gn + kw["ssm_heads"]) + ds * d
    expert = d * kw["num_experts"] + 2 * d * kw["latent_size"] \
        + 2 * d * kw["shared_hidden_size"] + kw["top_k"] * _held_share(kw) \
        * 2 * kw["latent_size"] * kw["expert_hidden_size"]
    return _layers(kw, "*") * attn + _layers(kw, "M") * mixer \
        + _layers(kw, "E") * expert + d * kw["vocab_size"]


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs on this chip for one
    token that attends `context` keys: the matrices (the embedding is a
    gather; of the routed experts the held share), q k^T and p v over the
    query heads, and the state's update and read-out."""
    kw = kwargs
    ssm = 2 * kw["ssm_heads"] * kw["ssm_head_dim"] * kw["ssm_state"]
    return 2 * _matmul_params(kw) \
        + _layers(kw, "*") * 4 * kw["num_heads"] * kw["head_dim"] * context \
        + _layers(kw, "M") * 2 * ssm


def attention_cost(kwargs, rows):
    """FLOPs and bytes the paged attention of ONE dispatch needs, over the
    attention layers; `rows` and the count as reference/falcon_h1.py
    `attention_cost` (keys and values at the KV heads' width, FLOPs of the
    query heads)."""
    return falcon_h1.attention_cost(
        dict(kwargs, num_layers=_layers(kwargs, "*")), rows)


def ssm_cost(kwargs, rows):
    """FLOPs and bytes the state-space chunk update of ONE dispatch needs,
    over the mixer layers; as reference/falcon_h1.py `ssm_cost` (each slot
    with work reads and writes its float32 state once; live rows only)."""
    return falcon_h1.ssm_cost(
        dict(kwargs, num_layers=_layers(kwargs, "M")), rows)


def expert_cost(kwargs, pairs, touched):
    """FLOPs and bytes the grouped expert feed-forward needs for `pairs`
    (row, held expert) pairs that touch `touched` experts, summed over
    whatever layers and dispatches the two counts are summed over: 4 x
    latent x hidden FLOPs a pair (two products, 2 FLOPs a multiply-add);
    both matrices of a touched expert read once; a pair's latent row read
    and its result written."""
    kw = kwargs
    d, f = kw["latent_size"], kw["expert_hidden_size"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    return {"flops": 4 * d * f * pairs,
            "bytes": (2 * d * f * touched + 2 * d * pairs) * itemsize}
