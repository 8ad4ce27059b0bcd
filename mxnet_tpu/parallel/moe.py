"""Mixture-of-Experts FFN: two forms over one choice of experts.

Reference parity: none — the reference has no MoE (SURVEY.md §2.4
presence matrix: EP absent); the brief makes it first-class here.

Both forms pick each token's experts and their weights with
`top_k_weights`. They differ in what happens to a token whose expert is
full:

  * `moe_dispatch_combine` (training; gluon.nn.MoEFFN) DROPS it. Every
    expert has `capacity` slots; a token over capacity gets weight 0 from
    that expert. All shapes are static and the experts shard over "ep".
  * `dropless_moe` (serving; gluon.nn.DroplessMoE) drops NOTHING it holds:
    every (token, expert) pair whose expert this chip holds is computed,
    however unevenly the pairs fall, in one grouped feed-forward over the
    pairs sorted by expert (ops/moe.expert_ffn). Its work a dispatch
    depends on the routing. It is told WHICH experts it holds (`first`,
    and as many as its stacked weights have rows); pairs whose expert lives
    elsewhere are left to whoever holds it, and nothing here stands in for
    that chip or for the exchange with it.

The training form (GShard/Switch formulation): top-k gating with a
capacity-bounded one-hot dispatch, so every shape is static —

    dispatch:  (S, E, Cap) one-hot   tokens → expert slots
    compute:   (E, Cap, C) einsums over the stacked expert weights
    combine:   gate-weighted inverse of dispatch

Expert parallelism is a SHARDING of the stacked expert weights and the
(E, Cap, C) activations over "ep" (PartitionSpec("ep", ...)): under
pjit/TrainStep XLA partitions the expert einsums across devices and
inserts the dispatch/combine all-to-all collectives the math requires —
the idiomatic-TPU equivalent of hand-written NCCL all-to-all. An explicit
`shard_map` + `lax.all_to_all` dispatch (`all_to_all_tokens`) is provided
for token-sharded layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .mesh import AXIS_EP, PartitionSpec, current_mesh, shard_map_compat

__all__ = ["top_k_weights", "top_k_gating", "moe_dispatch_combine",
           "dropless_moe", "all_to_all_tokens"]


def top_k_weights(scores, top_k, bias=None, scale=1.0):
    """Each token's experts and their weights. scores: (S, E) float32,
    non-negative (softmax probabilities or sigmoids). The `top_k` largest
    of `scores + bias` are chosen (`bias` steers the choice only); the
    weights are the chosen experts' own scores, renormalised to sum to
    `scale`. Returns (weights (S, k) float32, experts (S, k) int32)."""
    _, idx = lax.top_k(scores if bias is None else scores + bias, top_k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    return scale * vals / (vals.sum(-1, keepdims=True) + 1e-20), idx


def top_k_gating(logits, top_k, capacity):
    """GShard-style gating. logits: (S, E). Returns
    (dispatch (S, E, Cap) bool, combine (S, E, Cap) float32, aux_loss).

    aux_loss is the Switch/GShard load-balancing loss: E * sum_e
    mean(router_prob_e) * mean(tokens_routed_e)."""
    S, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # the kept gates renormalized (standard top-k MoE)
    gate_vals, gate_idx = top_k_weights(probs, top_k)      # (S, k)

    dispatch = jnp.zeros((S, E, capacity), bool)
    combine = jnp.zeros((S, E, capacity), jnp.float32)
    # running per-expert fill count decides each token's slot; tokens over
    # capacity are DROPPED (their combine weight is 0) — the documented
    # Switch behavior that keeps shapes static
    fill = jnp.zeros((E,), jnp.int32)
    for j in range(top_k):
        e_j = gate_idx[:, j]                               # (S,)
        onehot = jax.nn.one_hot(e_j, E, dtype=jnp.int32)   # (S, E)
        pos = fill[e_j] + jnp.cumsum(onehot, axis=0)[
            jnp.arange(S), e_j] - 1                        # slot per token
        keep = pos < capacity
        disp_j = (jax.nn.one_hot(e_j, E, dtype=bool)[:, :, None]
                  & jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                                   dtype=bool)[:, None, :]
                  & keep[:, None, None])
        dispatch = dispatch | disp_j
        combine = combine + disp_j * gate_vals[:, j][:, None, None]
        fill = fill + onehot.sum(axis=0)

    # load-balance auxiliary loss (Switch eq. 4)
    me = probs.mean(axis=0)                                # (E,)
    ce = dispatch.any(axis=-1).astype(jnp.float32).mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


def moe_dispatch_combine(x, gate_logits, w1, b1, w2, b2, top_k=2,
                         capacity_factor=1.25, activation=jax.nn.gelu):
    """The full MoE FFN on flat tokens. x: (S, C); gate_logits: (S, E);
    stacked expert weights w1 (E, C, H), b1 (E, H), w2 (E, H, C),
    b2 (E, C). Returns (y (S, C), aux_loss)."""
    S, C = x.shape
    E = w1.shape[0]
    capacity = max(1, int(S * top_k * capacity_factor / E))
    dispatch, combine, aux = top_k_gating(gate_logits, top_k, capacity)
    xin = x.astype(jnp.float32)
    # dispatch all-to-all: (S, E, Cap) × (S, C) → (E, Cap, C)
    expert_in = jnp.einsum("sec,sm->ecm", dispatch.astype(xin.dtype), xin)
    h = activation(jnp.einsum("ecm,emh->ech", expert_in, w1.astype(
        jnp.float32)) + b1[:, None, :].astype(jnp.float32))
    expert_out = jnp.einsum("ech,ehm->ecm", h, w2.astype(jnp.float32)) \
        + b2[:, None, :].astype(jnp.float32)
    # combine all-to-all back to tokens
    y = jnp.einsum("sec,ecm->sm", combine, expert_out)
    return y.astype(x.dtype), aux.astype(x.dtype)


def dropless_moe(x, weights, experts, live, w1, w2, first=0, impl="auto",
                 interpret=False, activation="relu2"):
    """The dropless form on flat rows, for the experts this chip holds.

    x:        (R, D) rows.
    weights:  (R, k) float32 and
    experts:  (R, k) int32, from `top_k_weights` over ALL the experts.
    live:     (R,) bool; a dead row (padding of a fixed-shape dispatch)
              costs no expert work and gets zeros.
    w1, w2:   (G, D, F), (G, F, D): experts first .. first + G - 1, stacked
              (w1 (G, D, 2F), gate beside up, under `activation="swiglu"`).
    Pairs whose expert is not held, and every pair of a dead row, are
    dropped BEFORE any expert work; the rest are sorted by expert and go
    through one grouped feed-forward (ops/moe.expert_ffn, `activation`).

    Returns (y (R, D) in x's dtype: the weighted sum over the HELD chosen
    experts, a partial sum where first/G cover a share of the experts;
    counts (5,) int32: 1, live rows, pairs computed, experts touched, the
    largest group)."""
    from ..ops.moe import expert_ffn
    R, K = experts.shape
    G = w2.shape[0]
    with jax.named_scope("moe.sort"):
        local = experts - first
        held = (local >= 0) & (local < G) & live[:, None]
        # dropped pairs sort behind every held expert's
        key = jnp.where(held, local, G).reshape(-1).astype(jnp.int32)
        at = jnp.arange(R * K, dtype=jnp.int32)
        key, order = lax.sort_key_val(key, at)
        sizes = jnp.diff(jnp.searchsorted(
            key, jnp.arange(G + 1, dtype=jnp.int32))).astype(jnp.int32)
        rows = jnp.take(x, order // K, axis=0)
        back = jnp.zeros_like(order).at[order].set(at, unique_indices=True)
    with jax.named_scope("moe.experts"):
        y = expert_ffn(rows, w1, w2, sizes, impl=impl, interpret=interpret,
                       activation=activation)
        # back to (row, choice); what the kernel never visited is undefined
        y = jnp.take(y, back, axis=0).reshape(R, K, -1)
        y = jnp.sum(jnp.where(held[:, :, None],
                              y.astype(jnp.float32) * weights[:, :, None],
                              0.0), axis=1).astype(x.dtype)
    counts = jnp.stack([jnp.int32(1), live.sum(dtype=jnp.int32), sizes.sum(),
                        (sizes > 0).sum(dtype=jnp.int32), sizes.max()])
    return y, counts


def all_to_all_tokens(x, mesh=None, axis=AXIS_EP, split_dim=1, concat_dim=0):
    """Explicit token redistribution over the ep axis (lax.all_to_all in a
    shard_map) — the collective a token-sharded dispatch rides. x: global
    (S, E_local_dim, ...) array; its axis-`concat_dim` shards over `axis`
    in, axis-`split_dim` shards over `axis` out."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise MXNetError(f"all_to_all_tokens needs a mesh with {axis!r}")

    def local(xb):
        return lax.all_to_all(xb, axis, split_dim, concat_dim, tiled=True)

    spec_in = [None] * x.ndim
    spec_in[concat_dim] = axis
    spec_out = [None] * x.ndim
    spec_out[split_dim] = axis
    fn = shard_map_compat(local, mesh=mesh,
                          in_specs=PartitionSpec(*spec_in),
                          out_specs=PartitionSpec(*spec_out),
                          check_rep=False)
    return fn(x)
