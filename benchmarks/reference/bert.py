"""BERT with the masked-language-model head (Devlin et al., arXiv:1810.04805),
as plain jax.numpy in float32: the yardstick for `correct` in the training
cells, and the arithmetic of what a step must compute. No kernel, no
dropout (evaluation mode), no mixed precision.

Departures from the paper, both the program's own and stated in
configs/bert_base_mlm.json: GELU is its tanh approximation, and there is no
next-sentence head.
"""
import jax
import jax.numpy as jnp

from .plain import attention, dense, gelu_tanh, layer_norm

# The program computes in bfloat16 (8 bits of mantissa: a relative step of
# 2^-8 = 0.0039) through 12 post-LN layers; the layer norms stop the error
# from growing with depth. On the chip its evaluation loss differed from
# this reference's by 0.0005 and 0.0001 against a loss of 10.4, and its
# logits by at most 0.031 (my chip runs, PR 22, two seeds); the limits are
# ten and four times that. A model that dropped a bias (0.02 a unit) or
# computed its head in 8 bits lands outside them.
TOLERANCE = {"loss_abs": 0.005, "logit_abs": 0.12}


def masked_logits(params, kwargs, ids, token_types, valid_length, positions):
    """Logits over the vocabulary at the masked positions, (B, M, V).
    `params` maps the program's parameter names to arrays; `kwargs` are the
    model's sizes (configs/*.json, model.kwargs)."""
    # upcast at use, so that only one layer is held in float32 at a time
    p = lambda name: jnp.asarray(params[name], jnp.float32)
    eps, heads = kwargs["layer_norm_eps"], kwargs["num_heads"]
    t = ids.shape[1]
    with jax.default_matmul_precision("highest"):
        x = (p("backbone.word_embed.weight")[ids]
             + p("backbone.position_embed.weight")[jnp.arange(t)]
             + p("backbone.token_type_embed.weight")[token_types])
        x = layer_norm(x, p("backbone.embed_ln.gamma"),
                       p("backbone.embed_ln.beta"), eps)
        allowed = (jnp.arange(t)[None, :]
                   < valid_length[:, None])[:, None, None, :]
        for i in range(kwargs["num_layers"]):
            w = lambda name: p(f"backbone.encoder.layer{i}.{name}")
            q, k, v = (dense(x, w(f"attn.{n}.weight"), w(f"attn.{n}.bias"))
                       for n in ("query", "key", "value"))
            h = dense(attention(q, k, v, heads, allowed),
                      w("attn.proj.weight"), w("attn.proj.bias"))
            x = layer_norm(x + h, w("ln1.gamma"), w("ln1.beta"), eps)
            h = gelu_tanh(dense(x, w("ffn.fc1.weight"), w("ffn.fc1.bias")))
            h = dense(h, w("ffn.fc2.weight"), w("ffn.fc2.bias"))
            x = layer_norm(x + h, w("ln2.gamma"), w("ln2.beta"), eps)
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
        h = gelu_tanh(dense(x, p("mlm.transform.weight"),
                            p("mlm.transform.bias")))
        h = layer_norm(h, p("mlm.transform_ln.gamma"),
                       p("mlm.transform_ln.beta"), eps)
        return dense(h, p("backbone.word_embed.weight"),
                     p("mlm.decoder_bias"))


def mlm_loss(logits, labels):
    """Mean cross-entropy over the masked positions."""
    logp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, :, None], axis=-1).mean()


def flops_per_item(kwargs, traffic):
    """Multiply-adds (as 2 FLOPs) a training step must do for one token,
    forward and backward, recomputation not counted: the formula of
    configs/bert_base_mlm.json."""
    c, f = kwargs["units"], kwargs["hidden_size"]
    l, v = kwargs["num_layers"], kwargs["vocab_size"]
    t, m = traffic["seq_len"], traffic["masked"]
    n_matmul = l * (4 * c * c + 2 * c * f)
    return 6 * n_matmul + 12 * l * c * t + (m / t) * 6 * (c * c + c * v)


def attention_cost(kwargs, traffic, batch):
    """FLOPs and bytes the attention of ONE training step needs on one chip
    holding `batch` sequences, forward and backward over all layers.

    Per layer, sequence and head, forward: q k^T and p v, 4 T^2 D. Backward:
    dv, dp, dq and dk, 8 T^2 D (recomputing the scores, as the kernel does,
    is its choice and is not counted). Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv; each
    B*T*C elements of the model's type."""
    c, l = kwargs["units"], kwargs["num_layers"]
    t = traffic["seq_len"]
    itemsize = jnp.dtype(kwargs["dtype"]).itemsize
    return {"flops": l * batch * 12 * t * t * c,
            "bytes": l * batch * 12 * t * c * itemsize}
