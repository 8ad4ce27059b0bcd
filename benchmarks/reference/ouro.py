"""Ouro (ByteDance Seed; https://huggingface.co/ByteDance/Ouro-2.6B, "Scaling
Latent Reasoning via Looped Language Models", 2025-10) as plain jax.numpy in
float32: the yardstick for `correct` in the serving cells of this family,
and the arithmetic of what a dispatch must compute. One full forward over
whole sequences: no cache, no pages, no kernel, the passes over the stack a
plain Python loop, the head over the chosen positions only. It imports
nothing of the program.

    block_l(h):                      l = 0 .. L-1, weights shared by the passes
        a = Attn_l(RMSNorm_a1(h))    q, k, v = x Wq, x Wk, x Wv; rotary on q,
                                     k at the token's position (the two halves
                                     of a head rotated against each other);
                                     softmax(q k^T / sqrt(D)) v over the keys
                                     THIS PASS made for layer l, causal; Wo
        h = h + RMSNorm_a2(a)        the branch's OUTPUT is normed, then added
        f = (silu(u Wg) * (u Wu)) Wd,  u = RMSNorm_f1(h)
        h = h + RMSNorm_f2(f)
    h_0 = Embed(ids)
    for t = 1 .. T:  h_t = RMSNorm_final(block_{L-1}(... block_0(h_{t-1})))
                     lambda_t = sigmoid(h_t w_gate + b_gate)
    logits = h_T W_head              early_exit_threshold 1: the last pass's
    exit pdf: p_t = lambda_t prod_{s<t} (1 - lambda_s) for t < T,
              p_T = prod_{s<T} (1 - lambda_s)

Read from the published config.json under the names `kwargs` gives them
(mxnet_tpu.models.OuroConfig's): hidden_size (`units`), num_hidden_layers
(`num_layers`), num_attention_heads, num_key_value_heads, head_dim,
intermediate_size (`hidden_size`), rms_norm_eps, rope_theta, vocab_size,
total_ut_steps, early_exit_threshold (1: no row leaves early). hidden_act
silu, rope_scaling null, no sliding window, tie_word_embeddings false.

Taken from the family's public modelling code (modeling_ouro.py beside the
checkpoint), not from the config: no bias in any projection (OuroAttention,
OuroMLP: `bias=False`); rotary over the two halves of a head (`rotate_half`);
FOUR norms a block (OuroDecoderLayer: `input_layernorm`,
`input_layernorm_2` on the attention's output, `post_attention_layernorm`,
`post_attention_layernorm_2` on the MLP's output); the final norm applied
at the end of EVERY pass and its output carried into the next (OuroModel's
loop over `total_ut_steps`); the gate a Linear(hidden, 1) WITH bias on that
normed output (`early_exit_gate`); a pass's keys and values kept apart from
every other pass's (cache index `pass * num_hidden_layers + layer`).

Departures: none in the mathematics. Matrices are upcast where they are
used, one layer at a time, behind a barrier a pass, so that the compiler
does not keep one float32 copy of all 48 layers (9.9 GB) alive across the
four passes.
"""
import math

import jax
import jax.numpy as jnp

# The program computes in bfloat16 (float32 for the norms, the rotary
# embedding, the softmax and the gate) through 4 x 48 blocks. The cell's
# weights are weights_per_parameter.py's with the embedding, the output
# norms and the attention's query and key matrices drawn again (the
# configuration's `draw` says what and why: as first drawn the model's logits
# do not depend on its input, and nothing it does wrong to its cache reaches
# them). The logits' standard deviation is 0.905 (a unit-RMS row times a head
# of N(0, 0.02) over 2048 columns). runners/serve_long.py reads, over the
# 2 x 16 compared positions x 49152 words: `logit_rms`, the root of the mean
# squared difference; `logit_rms_p50`, the median of the positions' own;
# `logit_abs`, the largest difference. All on the chip (my chip runs, PR 39;
# PERF.md section 6 has every reading), logit_rms:
#
#   the system's paged logits against this reference, 21 seeds  0.0673-0.0770
#   CONTROLS: norms, rotary, softmax and gate in bfloat16,
#     2 seeds (NOT told: see below)                              0.0690-0.0739
#   TOLERANCE                                                    0.115
#   every matrix in float8_e4m3, the nearest precision below
#     the weights', 2 seeds                                      0.1807-0.1940
#   three passes for four                                        0.3924-0.4025
#   the final norm not applied between passes                    0.5350-0.5452
#   all passes share the last pass's cache                       0.7275-0.7552
#   a pass reads the cache of the pass before it                 0.7815-0.8038
#   rope_theta 1e4                                               0.7972-0.8257
#   the two output norms dropped                                 1.3552-1.3610
#
# The limit is 1.5 times the largest of the system's readings and float8's
# smallest is 1.6 times the limit; the system's readings lie within 7% of
# their mean (0.072; the largest on a model's own greedy continuation, which
# the cell's check compares) and float8's within 4% of theirs. The control
# reads what the system reads (0.0690 against 0.0673, 0.0739 against 0.0721,
# seed by seed): the system's error IS its bfloat16 activations, of which the
# norms' outputs are most, and rounding the reference the same way cannot be
# told from the program by a limit on logits. logit_abs is printed and is no
# limit: the system read 0.345-0.434, float8 0.912-0.974.
# The ENGINE's stream (5 slots, the timed program) is held to the paged
# path's own logits at every emitted position: two programs of one model
# whose bfloat16 roundings are independent (other shapes, other fusions)
# differ about as the system differs from this reference, so with a median
# gap of 0.13-0.19 between the reference's two best logits the engine's token
# is the paged path's best at most positions and a near-best elsewhere: seven
# sound runs on the chip gave the best token at 24-29 of 32 positions and
# fell short of it by 0.125-0.25 at most. A token from the wrong slot's
# pages, or from a pass's cache layers read by another pass, falls short of
# the best by the logits' spread and more.
# STREAM_AGREE (20 of 32) and STREAM_MARGIN lie between. The stream is also
# held to the reference's argmax wherever the reference's two best logits lie
# further apart than ARGMAX_MARGIN, about twice the largest difference read
# (0.434 over 21 readings), which no sound position bridges.
TOLERANCE = {"logit_rms": 0.115}
ARGMAX_MARGIN = 0.8
STREAM_MARGIN = 0.75
STREAM_AGREE = 0.625

_HEAD_BLOCKS = 8        # the float32 head whole would be 0.4 GB

# the readings the limit has to lie under: each is this reference with one
# thing wrong, as keywords of `logits`
PERTURBATIONS = {
    "three_passes_for_four": {"passes": 3},
    "pass_reads_the_pass_before_its_cache": {"kv_from": "previous"},
    "all_passes_share_the_last_pass_cache": {"kv_from": "last"},
    "final_norm_not_applied_between_passes": {"no_norm_between": True},
    "output_norms_dropped": {"no_out_norms": True},
    "rope_theta_1e4": {"rope_theta": 1e4},
    "every_matrix_in_float8": {"matrix_dtype": "float8_e4m3fn"},
}
# read and printed beside them, and NOT told by any limit (TOLERANCE, above,
# has the readings and the reason): what the configuration keeps in float32
# (norms, rotary, softmax, gate) kept in bfloat16
CONTROLS = {
    "norms_rotary_and_softmax_in_bfloat16": {"state_dtype": "bfloat16"},
}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounder(dtype):
    """Values rounded to `dtype` and back (a tightness reading)."""
    if dtype is None:
        return _f32
    return lambda a: a.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _rms(x, weight, eps, rnd=_f32):
    return rnd(x * rnd(1 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
               * weight)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta, rnd=_f32):
    """(B, T, H, D), position = the token's index in its sequence."""
    t, d = x.shape[1], x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # (T, D/2)
    cos = rnd(jnp.concatenate([jnp.cos(ang)] * 2, -1))[None, :, None]
    sin = rnd(jnp.concatenate([jnp.sin(ang)] * 2, -1))[None, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return rnd(x * cos + half * sin)


def attention(u, w, kw, theta, kv=None, rnd=_f32):
    """(a of the block from the normalised input u, this pass's (k, v)).
    With `kv` the scores and values come from THOSE keys and values (a
    tightness reading: another pass's)."""
    b, t, _ = u.shape
    hq, hkv, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    q = _rope((u @ w("attn.query.weight").T).reshape(b, t, hq, d), theta,
              rnd)
    own = (_rope((u @ w("attn.key.weight").T).reshape(b, t, hkv, d), theta,
                 rnd),
           (u @ w("attn.value.weight").T).reshape(b, t, hkv, d))
    # KV head g serves query heads g*(hq/hkv) .. : repeat each KV head
    k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (kv or own))
    s = jnp.einsum("bjhd,bthd->bhjt", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = rnd(jnp.exp(s - s.max(-1, keepdims=True)))
    p = rnd(p / p.sum(-1, keepdims=True))
    out = jnp.einsum("bhjt,bthd->bjhd", p, v).reshape(b, t, hq * d)
    return out @ w("attn.proj.weight").T, own


def exit_pdf(gates):
    """The passes' gates lambda_t, a list of (B, T) -> (T, B, T) pdf."""
    stay, pdf = 1.0, []
    for lam in gates[:-1]:
        pdf.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(pdf + [stay * jnp.ones_like(gates[-1])])


def _stack(params, kw, h, cast, rnd, passes, theta, kv_from, shared,
           no_norm_between, no_out_norms):
    """The passes over the blocks from h_0: (h_T, the gates, the last
    pass's (k, v) a layer)."""
    eps, layers = kw["rms_norm_eps"], kw["num_layers"]
    final = _f32(params["final_norm.weight"])
    norm = lambda x, weight: _rms(x, weight, eps, rnd)
    out_norm = (lambda x, weight: x) if no_out_norms else norm
    gates, before = [], [None] * layers
    for step in range(passes):
        made = []
        for i in range(layers):
            names = [k for k in params if k.startswith(f"layer{i}.")]
            # this pass's own upcast of the layer (module docstring)
            h, held = jax.lax.optimization_barrier(
                (h, {k: params[k] for k in names}))
            w = lambda name: cast(held[f"layer{i}.{name}"])
            kv = {"own": None, "previous": before[i],
                  "last": shared and shared[i]}[kv_from]
            a, own = attention(norm(h, w("attn_norm.weight")), w, kw, theta,
                               kv, rnd)
            made.append(own)
            h = h + out_norm(a, w("attn_out_norm.weight"))
            u = norm(h, w("ffn_norm.weight"))
            g, up = jnp.split(u @ w("ffn.gate_up.weight").T, 2, axis=-1)
            f = (_silu(g) * up) @ w("ffn.down.weight").T
            h = h + out_norm(f, w("ffn_out_norm.weight"))
        before = made
        last = step == passes - 1
        normed = norm(h, final)
        if last or not no_norm_between:
            h = normed
        # the gate reads the normed output whatever goes on to the next pass
        gates.append(rnd(jax.nn.sigmoid(
            (normed @ cast(params["exit_gate.weight"]).T)[..., 0]
            + _f32(params["exit_gate.bias"])[0])))
    return h, gates, before


def logits(params, kwargs, ids, positions=None, with_exit_pdf=False,
           matrix_dtype=None, state_dtype=None, passes=None, kv_from="own",
           no_norm_between=False, no_out_norms=False, rope_theta=None):
    """Next-token logits, (B, T, V), or with `positions` (B, n) the logits
    of those positions alone, (B, n, V); with `with_exit_pdf` the pair
    (logits, the exit distribution (passes, B, T or n)). Tightness readings:
    `matrix_dtype` rounds every matrix to that type first; `state_dtype`
    rounds to that type what the configuration keeps in float32 (norms,
    rotary, softmax, gate); `passes` runs so many passes; `kv_from`
    'previous' has pass t attend the keys and values pass t-1 made for the
    layer (the first its own), 'last' has every pass attend those the LAST
    pass of the sound forward made; `no_norm_between` applies the final norm
    after the last pass only; `no_out_norms` drops the two norms on the
    branches' outputs; `rope_theta` replaces the configuration's."""
    kw = kwargs
    if kw.get("early_exit_threshold", 1) < 1:
        raise ValueError("rows that leave the stack early are not written "
                         "down here: early_exit_threshold must be 1")
    rnd = _rounder(state_dtype)
    if matrix_dtype is not None:
        matrix_dtype = jnp.dtype(matrix_dtype)

    def cast(a):
        if matrix_dtype is not None and a.ndim >= 2:
            a = a.astype(matrix_dtype)
        return _f32(a)

    run = lambda h, kv_from, shared: _stack(
        params, kw, h, cast, rnd, passes or kw["total_ut_steps"],
        rope_theta or kw["rope_theta"], kv_from, shared, no_norm_between,
        no_out_norms)
    with jax.default_matmul_precision("highest"):
        # rows are gathered in the stored type and upcast
        h0 = cast(jnp.take(params["embed.weight"], ids, axis=0))
        shared = run(h0, "own", None)[2] if kv_from == "last" else None
        h, gates, _ = run(h0, kv_from, shared)
        pdf = exit_pdf(gates)
        if positions is not None:
            h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
            pdf = jnp.take_along_axis(pdf, positions[None], axis=2)
        head = params["head.weight"]
        n = _HEAD_BLOCKS if head.shape[0] % _HEAD_BLOCKS == 0 else 1
        rows = head.shape[0] // n
        out = jnp.concatenate(
            [h @ cast(head[j * rows:(j + 1) * rows]).T for j in range(n)],
            -1)
    return (out, pdf) if with_exit_pdf else out


def _layer_matmul_params(kw):
    d, hd = kw["units"], kw["head_dim"]
    return 2 * d * hd * (kw["num_heads"] + kw["num_kv_heads"]) \
        + 3 * d * kw["hidden_size"]


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs for one token that
    attends `context` keys: every pass goes through every block's matrices
    and its gate, and attends `context` keys of its own cache; the head is
    read once (the embedding is a gather)."""
    kw = kwargs
    steps, layers = kw["total_ut_steps"], kw["num_layers"]
    matmul = steps * (layers * _layer_matmul_params(kw) + kw["units"]) \
        + kw["units"] * kw["vocab_size"]
    return 2 * matmul \
        + steps * layers * 4 * kw["num_heads"] * kw["head_dim"] * context


def attention_cost(kwargs, rows):
    """FLOPs and bytes the paged attention of ONE dispatch needs, over all
    `total_ut_steps` x `num_layers` cache layers. `rows` lists, for each
    slot with work, (context, count): the keys already in its pages and the
    query rows fed now. Query j attends context + j + 1 keys, 4 x (query
    heads x head size) FLOPs a key. Bytes: each slot's live keys and values
    are read once a cache layer, at the width of the KV heads; its queries
    are read and outputs written at the query heads'."""
    kw = kwargs
    cq = kw["num_heads"] * kw["head_dim"]
    ckv = kw["num_kv_heads"] * kw["head_dim"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    cache_layers = kw["total_ut_steps"] * kw["num_layers"]
    flops = bytes_ = 0
    for context, count in rows:
        flops += 4 * cq * (count * context + count * (count + 1) // 2)
        bytes_ += (2 * (context + count) * ckv + 2 * count * cq) * itemsize
    return {"flops": cache_layers * flops, "bytes": cache_layers * bytes_}
