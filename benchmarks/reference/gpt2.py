"""GPT-2 (Radford et al., 2019) as plain jax.numpy in float32: the
yardstick for `correct` in the serving cells, and the arithmetic of what a
dispatch must compute. One full forward over whole sequences: no cache, no
pages, no kernel, no batching of unequal work.
"""
import jax
import jax.numpy as jnp

from .plain import attention, dense, gelu_tanh, layer_norm

# The program computes in bfloat16 through 36 pre-LN layers whose residual
# stream is never renormalised, so rounding adds up with depth. Logits have
# a standard deviation of about 0.7 with these weights; on the chip the
# model's differed from this reference's by at most 0.052 to 0.056 (my chip
# runs, PR 22, three seeds), and the limit is about twice that. The engine's
# argmax stream is held to the reference's argmax wherever the reference's
# two best logits lie further apart than the same limit (17 to 24 of 48
# positions). An int8 path, or a page of the cache read from the wrong slot,
# lands outside it.
TOLERANCE = {"logit_abs": 0.12}


def logits(params, kwargs, ids):
    """Next-token logits at every position, (B, T, V)."""
    # upcast at use, so that only one layer is held in float32 at a time
    p = lambda name: jnp.asarray(params[name], jnp.float32)
    eps, heads = kwargs["layer_norm_eps"], kwargs["num_heads"]
    t = ids.shape[1]
    with jax.default_matmul_precision("highest"):
        x = (p("backbone.word_embed.weight")[ids]
             + p("backbone.position_embed.weight")[jnp.arange(t)])
        causal = (jnp.arange(t)[None, :]
                  <= jnp.arange(t)[:, None])[None, None]
        for i in range(kwargs["num_layers"]):
            w = lambda name: p(f"backbone.layer{i}.{name}")
            h = layer_norm(x, w("ln1.gamma"), w("ln1.beta"), eps)
            q, k, v = (dense(h, w(f"attn.{n}.weight"), w(f"attn.{n}.bias"))
                       for n in ("query", "key", "value"))
            x = x + dense(attention(q, k, v, heads, causal),
                          w("attn.proj.weight"), w("attn.proj.bias"))
            h = layer_norm(x, w("ln2.gamma"), w("ln2.beta"), eps)
            h = gelu_tanh(dense(h, w("fc1.weight"), w("fc1.bias")))
            x = x + dense(h, w("fc2.weight"), w("fc2.bias"))
        x = layer_norm(x, p("backbone.ln_f.gamma"), p("backbone.ln_f.beta"),
                       eps)
        return x @ p("backbone.word_embed.weight").T


def flops_per_item(kwargs, context):
    """Multiply-adds (as 2 FLOPs) the forward needs for one token that
    attends `context` keys: the formula of configs/gpt2_774m.json."""
    c, l, v = kwargs["units"], kwargs["num_layers"], kwargs["vocab_size"]
    n_matmul = l * (4 * c * c + 2 * c * 4 * c) + c * v
    return 2 * n_matmul + 4 * l * c * context


def attention_cost(kwargs, rows):
    """FLOPs and bytes the paged attention of ONE dispatch needs, over all
    layers. `rows` lists, for each slot with work, (context, count): the
    keys already in its pages and the query rows fed now (a prompt chunk,
    or 1 for a decode tick). Query j attends context + j + 1 keys, 4 C
    FLOPs a key (q k^T and p v over all heads). Bytes: each slot's live
    keys and values are read once, its queries read and outputs written."""
    c, l = kwargs["units"], kwargs["num_layers"]
    itemsize = jnp.dtype(kwargs["dtype"]).itemsize
    flops = bytes_ = 0
    for context, count in rows:
        flops += 4 * c * (count * context + count * (count + 1) // 2)
        bytes_ += (2 * (context + count) + 2 * count) * c * itemsize
    return {"flops": l * flops, "bytes": l * bytes_}
