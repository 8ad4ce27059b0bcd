"""Seeded weights, made on the device in one jitted call, in the type the
model runs in. Nothing is drawn on the host and nothing is cast after."""
import math

import jax

from mxnet_tpu.ndarray.ndarray import NDArray


def seed_weights(net, seed, dtype, std=0.02):
    """Give every parameter of the gluon block `net` its value: N(0, std)
    for matrices and embeddings, and, so that a dropped bias or scale
    cannot pass the comparison with the reference, N(0, std) for biases
    and offsets and 1 + N(0, std) for the scales of the layer norms too.
    One normal draw covers all of them and is cut into the parameters'
    shapes. The block must not have been initialized (no host-side init to
    pay for and throw away)."""
    net.cast(dtype)
    params = list(net.collect_params().values())
    sizes = [math.prod(p.shape) for p in params]

    @jax.jit
    def make(key):
        flat = std * jax.random.normal(key, (sum(sizes),), dtype)
        out, at = [], 0
        for p, n in zip(params, sizes):
            # a parameter's own `init` is how the program marks a scale
            base = 1.0 if p.init == "ones" else 0.0
            out.append((base + flat[at:at + n]).astype(dtype)
                       .reshape(p.shape))
            at += n
        return out

    for p, value in zip(params, make(jax.random.key(int(seed)))):
        p.set_data(NDArray(value))
