"""The three equations both references share, written out in jax.numpy."""
import math

import jax.numpy as jnp


def layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def dense(x, weight, bias):
    """A gluon Dense keeps its weight as (out, in)."""
    return x @ weight.T + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v, heads, allowed):
    """softmax(q k^T / sqrt(d)) v over (B, T, C) inputs split into `heads`;
    `allowed` broadcasts to (B, heads, Tq, Tk) and is False where a query
    may not see a key."""
    b, t, c = q.shape
    d = c // heads
    split = lambda x: x.reshape(b, -1, heads, d).transpose(0, 2, 1, 3)
    scores = split(q) @ split(k).transpose(0, 1, 3, 2) / math.sqrt(d)
    scores = jnp.where(allowed, scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / p.sum(-1, keepdims=True)
    return (p @ split(v)).transpose(0, 2, 1, 3).reshape(b, t, c)
