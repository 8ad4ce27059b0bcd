"""Trinity (Arcee; https://huggingface.co/arcee-ai/Trinity-Mini,
`model_type: afmoe`) as plain jax.numpy in float32: the yardstick for
`correct` in the serving cells of this family, and the arithmetic of what a
dispatch must compute. One full forward over whole sequences: no cache, no
pages, no ring, no kernel; a window layer's window is a MASK over every key
(queries `_QUERY_BLOCK` at a time, so that the scores fit); no sort of the
routed pairs (reference/kimi_linear.py's loop over the experts with a
mask); the head over the chosen positions only.

    h = E[ids] * sqrt(units)                               (mup_enabled)
    every layer, four RMSNorms (eps `rms_norm_eps`):
      h = h + N_attn_out(Attn(N_attn(h)));  h = h + N_ffn_out(FFN(N_ffn(h)))
    Attn(x): q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk), each over a head's
      `head_dim` columns with a learned weight of `head_dim`; v = x Wv;
      g = x Wgate; rotary (theta `rope_theta`, the whole head, rotate-half)
      on q and k in `sliding_attention` layers ONLY; scores q.k *
      head_dim**-0.5, query head h reading KV head h // (heads / kv_heads);
      a `sliding_attention` query at i sees keys i - window < j <= i, a
      `full_attention` query every j <= i;
      out = (concat_heads(softmax(s) v) * sigmoid(g)) Wo
    FFN: the first `dense_layers` (silu(x Wg) * (x Wu)) Wd; every other
      s = sigmoid(x W_r) over all experts; T = top_k(s + b);
      w_e = scale * s_e / (sum_{j in T} s_j + 1e-20); y = sum_{e in T}
      w_e e(x) + shared(x), each expert the same gated form
    logits = RMSNorm(h) W_head

Read from the published config.json under the names `kwargs` gives them
(mxnet_tpu.models.TrinityConfig's): hidden_size (`units`), layer_types,
num_attention_heads (`num_heads`), num_key_value_heads, head_dim,
num_dense_layers (`dense_layers`), intermediate_size (`dense_hidden_size`),
num_experts, num_experts_per_tok (`top_k`), moe_intermediate_size
(`expert_hidden_size`; one shared expert as wide), route_scale
(`routed_scaling_factor`), sliding_window (`window`), rope_theta,
rms_norm_eps, vocab_size. mup_enabled true (the embedding's scale,
always applied), score_func sigmoid, route_norm true, n_group =
topk_group = 1 (no group limit), hidden_act silu, tie_word_embeddings
false.

Taken from the family's modelling code (transformers `models/afmoe`,
AfmoeDecoderLayer and AfmoeAttention), not from the config: the norms of q
and k, the output gate, the rotary embedding in window layers alone, the
sqrt(units) scale of the embedding under mup_enabled, the four norms a
layer, and the window's edge (a window of W keys counting the query's
own).
"""
import jax
import jax.numpy as jnp

from .kimi_linear import _f32, _gated, _rms, expert_cost, expert_layer

__all__ = ["logits", "flops_per_item", "attention_cost",
           "window_attention_cost", "expert_cost"]

WINDOW, FULL = "sliding_attention", "full_attention"

# The program computes in bfloat16 (float32 for the router's scores, the
# norms, the rotary embedding, the gate's sigmoid and the softmax). The
# cell's weights are weights_per_parameter.py's as drawn (no `draw`: the
# reference's logits of two sequences have a cosine of 0.10-0.19, of
# consecutive positions 0.42-0.51, 22-24 distinct argmax tokens of 24; the
# spread 0.90). runners/serve_long.py reads, over 2 x 24 compared
# positions x 200192 words: `logit_rms`, the root of the mean squared
# difference; `logit_rms_p50`, the median over the positions of each
# position's own; `logit_abs`, the largest difference. Measured on one v5e
# chip, logit_rms_p50 (logit_rms):
#
#   the system's paged logits against this reference, 15 seeds
#                                           0.0115-0.0137 (0.0588-0.1364)
#   CONTROL: the window one key wider, 3 seeds (NOT told)
#                                           0.0129-0.0140 (0.0823-0.0893)
#   TOLERANCE                               0.05
#   RoPE on the full layers too, 3 seeds    0.1731-0.1909 (0.1754-0.1905)
#   every matrix in float8_e4m3, the nearest precision below the
#     weights', 3 seeds                     0.2039-0.2255 (0.2102-0.2294)
#   no QK-norm 0.30-0.31, no output gate 0.49-0.51, no window (every layer
#   full) 0.48-0.52 (0.82-0.85 on the 8224-token sequence alone), shared
#   expert left out 0.58-0.59, no sqrt(C) embedding scale 0.75-0.77
#
# The limit is on the MEDIAN position: the system's error is a few
# positions', a token routed to another expert than the reference's (top-8
# of 128 sigmoid scores is discontinuous, and bfloat16 hidden states flip
# close calls) moving ONE position's logits by up to 0.93-1.65
# (`logit_abs`), so the root mean square over all positions reads
# 0.059-0.136 by seed and overlaps RoPE on the full layers (0.175); a
# wrong model moves every position. The limit is 3.6 times the system's
# largest median and the nearest wrong reference 3.5 times the limit. A
# window one key wider is a different model that no limit on these logits
# can tell (CONTROLS). The ENGINE's stream (32 slots, the timed program)
# against the paged path's own logits: eleven sound runs gave the paged
# path's best token at 39-45 of 48 positions and fell short of it by
# 0.06-0.67; two programs of one model round apart, and a routing flip
# between them moves a position as far as the system moves from the
# reference (up to 1.65), so STREAM_MARGIN is 2.0. With the window kernel
# reading the NEXT slot's ring in the many-slot program alone (a fault the
# one-slot paged path cannot have), the engine gave the paged path's best
# token at 0 of 48 positions (STREAM_AGREE asks 30) and fell 5.86 short.
# ARGMAX_MARGIN is as wide: a single position's difference (1.65 at most
# over fifteen readings) must not bridge it; the reference's two best
# logits lay 0.54 apart at most at three seeds, so it compares few
# positions.
TOLERANCE = {"logit_rms_p50": 0.05}
ARGMAX_MARGIN = 2.0
STREAM_MARGIN = 2.0
STREAM_AGREE = 0.625

_HEAD_BLOCKS = 8        # the float32 head alone would be 1.6 GB
_QUERY_BLOCK = 256      # rows of scores at a time: (heads, 256, T) float32

# the readings the limit has to lie under: each is this reference with one
# thing wrong, as keywords of `logits`
PERTURBATIONS = {
    "every_matrix_in_float8": {"matrix_dtype": "float8_e4m3fn"},
    "no_window_every_layer_full": {"no_window": True},
    "rope_on_full_layers_too": {"rope_on_full": True},
    "no_output_gate": {"no_gate": True},
    "no_qk_norm": {"no_qk_norm": True},
    "no_embedding_scale": {"no_embed_scale": True},
    "shared_expert_left_out": {"no_shared": True},
}

# read and printed beside them, and NOT told by any limit: a window one key
# wider (2049 keys where the family's mask has 2048)
CONTROLS = {
    "window_one_key_wider": {"window_delta": 1},
}


def _rope(x, positions, theta):
    """(B, T, H, D) rotated at `positions` (T,): the two halves of a head
    against each other."""
    d = x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attention(u, w, kw, window, rotary, no_gate=False, no_qk_norm=False):
    """f of a layer's attention from the normalised input u (B, T, C):
    `window` None (every earlier key) or W, `rotary` on or off. The
    keywords are the tightness readings."""
    b, t, _ = u.shape
    hq, hkv, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    eps = kw["rms_norm_eps"]
    q = (u @ w("attn.query.weight").T).reshape(b, t, hq, d)
    k = (u @ w("attn.key.weight").T).reshape(b, t, hkv, d)
    v = (u @ w("attn.value.weight").T).reshape(b, t, hkv, d)
    if not no_qk_norm:
        q = _rms(q, w("attn.q_norm.weight"), eps)
        k = _rms(k, w("attn.k_norm.weight"), eps)
    if rotary:
        at = jnp.arange(t)
        q, k = (_rope(a, at, kw["rope_theta"]) for a in (q, k))
    k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
    blocks = -(-t // _QUERY_BLOCK)
    pad = blocks * _QUERY_BLOCK - t
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, blocks, _QUERY_BLOCK, hq, d)

    def block(args):
        qs, first = args                                    # (B, QB, H, D)
        s = jnp.einsum("bjhd,bthd->bhjt", qs, k) * d ** -0.5
        at = (first + jnp.arange(_QUERY_BLOCK))[:, None]
        keys = jnp.arange(t)[None, :]
        seen = keys <= at
        if window is not None:
            seen &= keys > at - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return jnp.einsum("bhjt,bthd->bjhd", p, v)

    o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(blocks) * _QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * _QUERY_BLOCK, hq * d)[:, :t]
    if not no_gate:
        o = o * jax.nn.sigmoid(u @ w("attn.gate.weight").T)
    return o @ w("attn.proj.weight").T


def logits(params, kwargs, ids, positions=None, matrix_dtype=None,
           no_window=False, rope_on_full=False, no_gate=False,
           no_qk_norm=False, no_embed_scale=False, no_shared=False,
           window_delta=0):
    """Next-token logits, (B, T, V), or with `positions` (B, n) the logits
    of those positions alone, (B, n, V): the head over the whole vocabulary
    is 0.8 MB a position. Tightness readings: `matrix_dtype` rounds every
    matrix to that type first; `no_window` makes every layer a full one;
    `rope_on_full` rotates the full layers' queries and keys too;
    `no_gate`, `no_qk_norm`, `no_embed_scale` and `no_shared` leave out the
    output gate, the norms of q and k, the embedding's scale and the
    shared expert; `window_delta` widens the window by so many keys."""
    kw = kwargs
    eps = kw["rms_norm_eps"]
    if matrix_dtype is not None:
        matrix_dtype = jnp.dtype(matrix_dtype)

    def cast(a):
        if matrix_dtype is not None and a.ndim >= 2:
            a = a.astype(matrix_dtype)
        return _f32(a)

    with jax.default_matmul_precision("highest"):
        # rows are gathered in the stored type and upcast; every other
        # matrix is upcast where it is used, one layer at a time
        h = cast(jnp.take(params["embed.weight"], ids, axis=0))
        if not no_embed_scale:
            h = h * jnp.sqrt(jnp.float32(kw["units"]))
        for i, kind in enumerate(kw["layer_types"]):
            prefix = f"layer{i}."
            names = [n for n in params if n.startswith(prefix)]
            # this layer's own upcast, after the layer before it
            h, held = jax.lax.optimization_barrier(
                (h, {n: params[n] for n in names}))
            w = lambda name: cast(held[prefix + name])
            norm = lambda x, name: _rms(x, _f32(held[prefix + name]), eps)
            windowed = kind == WINDOW and not no_window
            a = attention(norm(h, "attn_norm.weight"), w, kw,
                          kw["window"] + window_delta if windowed else None,
                          kind == WINDOW or rope_on_full, no_gate,
                          no_qk_norm)
            h = h + norm(a, "attn_out_norm.weight")
            u = norm(h, "ffn_norm.weight")
            if i < kw["dense_layers"]:
                f = _gated(u, w("ffn.gate_up.weight"), w("ffn.down.weight"))
            else:
                f = expert_layer(u, lambda name: w("ffn." + name), kw, held,
                                 prefix + "ffn.", cast, no_shared=no_shared)
            h = h + norm(f, "ffn_out_norm.weight")
        h = _rms(h, _f32(params["final_norm.weight"]), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        head = params["head.weight"]
        n = _HEAD_BLOCKS if head.shape[0] % _HEAD_BLOCKS == 0 else 1
        rows = head.shape[0] // n
        return jnp.concatenate(
            [h @ cast(head[j * rows:(j + 1) * rows]).T for j in range(n)],
            -1)


def _layers(kw, kind):
    return kw["layer_types"].count(kind)


def _expert_layers(kw):
    return len(kw["layer_types"]) - kw["dense_layers"]


def _held_share(kw):
    _, count = kw.get("held_experts") or (0, kw["num_experts"])
    return count / kw["num_experts"]


def _matmul_params(kw):
    """Matrix elements a token passes on this chip: the held share of its
    top_k experts on average."""
    d, hd = kw["units"], kw["head_dim"]
    attn = d * hd * (3 * kw["num_heads"] + 2 * kw["num_kv_heads"])
    gated = lambda f: 3 * d * f
    expert = d * kw["num_experts"] + gated(kw["shared_hidden_size"]) \
        + kw["top_k"] * _held_share(kw) * gated(kw["expert_hidden_size"])
    return len(kw["layer_types"]) * attn \
        + kw["dense_layers"] * gated(kw["dense_hidden_size"]) \
        + _expert_layers(kw) * expert + d * kw["vocab_size"]


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs on this chip for one
    token that attends `context` keys: the matrices (the embedding is a
    gather; of the routed experts the held share), and scores and values
    over `context` keys in a full layer and over at most `window` of them
    in a window layer, 4 x query heads x head size FLOPs a key."""
    kw = kwargs
    per_key = 4 * kw["num_heads"] * kw["head_dim"]
    return 2 * _matmul_params(kw) \
        + per_key * (_layers(kw, FULL) * context
                     + _layers(kw, WINDOW) * min(context, kw["window"]))


def _io_bytes(kw, count):
    """A slot's query rows read and outputs written, at the query heads'
    width."""
    return 2 * count * kw["num_heads"] * kw["head_dim"]


def attention_cost(kwargs, rows):
    """FLOPs and bytes the span attention of ONE dispatch needs over the
    FULL layers. `rows` lists, for each slot with work, (context, count):
    the keys already in its pages and the query rows fed now. Query j
    attends context + j + 1 keys, 4 x (query heads x head size) FLOPs a
    key. Bytes: each slot's live keys and values read once a layer, at the
    KV heads' width; its queries and outputs at the query heads'."""
    kw = kwargs
    cq = kw["num_heads"] * kw["head_dim"]
    ckv = kw["num_kv_heads"] * kw["head_dim"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for context, count in rows:
        flops += 4 * cq * (count * context + count * (count + 1) // 2)
        bytes_ += (2 * (context + count) * ckv + _io_bytes(kw, count)) \
            * itemsize
    layers = _layers(kw, FULL)
    return {"flops": layers * flops, "bytes": layers * bytes_}


def window_attention_cost(kwargs, rows):
    """FLOPs and bytes the window attention of ONE dispatch needs over the
    WINDOW layers; `rows` as for attention_cost. Query j sees min(context
    + j + 1, window) keys, 4 x (query heads x head size) FLOPs a key.
    Bytes: the pages the window kernel visits, read once a slot a layer:
    from the page of the first query's first key to the page of the last
    query, `ring_page_size` keys a page, K and V at the KV heads' width;
    its queries and outputs at the query heads'."""
    kw = kwargs
    W, S = kw["window"], kw.get("ring_page_size", 64)
    cq = kw["num_heads"] * kw["head_dim"]
    ckv = kw["num_kv_heads"] * kw["head_dim"]
    itemsize = jnp.dtype(kw["dtype"]).itemsize
    flops = bytes_ = 0
    for context, count in rows:
        keys = sum(min(context + j + 1, W) for j in range(count))
        flops += 4 * cq * keys
        pages = (context + count - 1) // S + 1 \
            - max(context + 1 - W, 0) // S
        bytes_ += (2 * pages * S * ckv + _io_bytes(kw, count)) * itemsize
    layers = _layers(kw, WINDOW)
    return {"flops": layers * flops, "bytes": layers * bytes_}
