"""Seeded weights for a model whose parameters outnumber what one draw can
hold. weights.py makes ONE normal draw for all parameters and cuts it up;
Falcon-H1-34B's stage has 5.25e9 of them, more than a dimension of a TPU
array may have (2**31), and the chip's compiler refuses the draw. Here each
parameter is its own draw on the device, in the type the model runs in,
under a key folded from the seed and the parameter's index: the same
distributions as weights.py (N(0, std); 1 + N(0, std) where the program
marks a scale with `init="ones"`), another stream.

A Mamba-2 mixer's own parameters (found by the names the program gives
them: `A_log`, `dt_bias`, `conv_weight`, `conv_bias`) are drawn as the
layer's published initialization draws them, because N(0, std) there makes
a recurrence with no memory: A uniform in [1, 16], dt log-uniform in
[1e-3, 1e-1] with `dt_bias` its inverse softplus, and the depthwise
convolution uniform within 1/sqrt(its kernel's length). With all four near
0 a token's state decays by half a token later and B and C are a hundredth
of x, so losing the carried state moves the logits by a thousandth of their
spread and no limit could see it; drawn so, it moves them by a fifth
(reference/falcon_h1.py, TOLERANCE)."""
import functools
import math

import jax
import jax.numpy as jnp

from mxnet_tpu.ndarray.ndarray import NDArray


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape, dtype, std, base):
    return (base + std * jax.random.normal(key, shape, dtype)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _uniform(key, shape, dtype, lo, hi, leaf):
    u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if leaf == "A_log":
        u = jnp.log(u)
    elif leaf == "dt_bias":             # softplus(dt_bias) = dt = exp(u)
        u = jnp.exp(u) + jnp.log(-jnp.expm1(-jnp.exp(u)))
    return u.astype(dtype)


def seed_weights(net, seed, dtype, std=0.02):
    """As weights.seed_weights: every parameter of the uninitialized gluon
    block `net` gets its value; nothing is drawn on the host and nothing is
    cast after."""
    net.cast(dtype)
    key = jax.random.key(int(seed))
    params = net.collect_params()
    for i, (name, p) in enumerate(params.items()):
        stem, _, leaf = name.rpartition(".")
        k, shape = jax.random.fold_in(key, i), tuple(p.shape)
        if leaf in ("conv_weight", "conv_bias"):
            bound = params[f"{stem}.conv_weight"].shape[-1] ** -0.5
            value = _uniform(k, shape, dtype, -bound, bound, leaf)
        elif leaf == "A_log":
            value = _uniform(k, shape, dtype, 1.0, 16.0, leaf)
        elif leaf == "dt_bias":
            value = _uniform(k, shape, dtype, math.log(1e-3), math.log(1e-1),
                             leaf)
        else:
            value = _normal(k, shape, dtype, std,
                            1.0 if p.init == "ones" else 0.0)
        p.set_data(NDArray(value))
