"""Mixture-of-Experts FFN layers: the holders of a router and of expert
weights STACKED along a leading (E, ...) axis. The math lives in
parallel/moe.py.

Reference parity: none — SURVEY.md §2.4 records EP as absent from the
reference; first-class here per the brief.

  * `MoEFFN`, the TRAINING form, DROPS tokens: GShard/Switch
    capacity-bounded dispatch with softmax gates, static shapes;
    `ep_rules()` shards dim 0 of the stacked weights over "ep" and XLA
    partitions the expert einsums + inserts the dispatch/combine
    collectives.
  * `DroplessMoE`, the SERVING form, drops none of the pairs it holds: it
    routes over all `num_experts`, is told which of them it holds
    (`held=(first, count)`; its stacked weights have `count` rows), and
    computes exactly the (row, expert) pairs of live rows that fall to
    those, in one grouped feed-forward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...base import MXNetError
from ...ops import nn as _opnn
from ...ops.registry import apply_op
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["MoEFFN", "DroplessMoE", "MOE_COUNTERS"]

# what DroplessMoE.forward counts a call (parallel.moe.dropless_moe), in
# the order of its `counts`; a model keeps them cumulatively, a row a layer
MOE_COUNTERS = ("dispatches", "rows", "pairs", "experts_touched",
                "largest_group")

_ACTS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu,
         "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True)}


class MoEFFN(HybridBlock):
    """Drop-in replacement for a transformer PositionwiseFFN: (B, T, C) →
    (B, T, C) through num_experts expert FFNs with top-k routing.

    forward(x, return_aux=True) returns (y, aux_loss); training code adds
    aux_loss * weight into its objective (the Switch recipe).
    """

    def __init__(self, units, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu", **kwargs):
        super().__init__(**kwargs)
        if top_k > num_experts:
            raise MXNetError(f"top_k {top_k} > num_experts {num_experts}")
        if activation not in _ACTS:
            raise MXNetError(f"unsupported MoE activation {activation!r}")
        self._units = units
        self._hidden = hidden_size
        self._E = num_experts
        self._top_k = top_k
        self._cf = capacity_factor
        self._activation = activation
        self.gate = Dense(num_experts, flatten=False, use_bias=False,
                          in_units=units)
        self.expert_w1 = Parameter("expert_w1",
                                   shape=(num_experts, units, hidden_size))
        self.expert_b1 = Parameter("expert_b1",
                                   shape=(num_experts, hidden_size),
                                   init="zeros")
        self.expert_w2 = Parameter("expert_w2",
                                   shape=(num_experts, hidden_size, units))
        self.expert_b2 = Parameter("expert_b2",
                                   shape=(num_experts, units), init="zeros")

    def forward(self, x, return_aux=False):
        from ...parallel.moe import moe_dispatch_combine

        b, t, c = x.shape
        logits = self.gate(x)
        act = _ACTS[self._activation]
        top_k, cf = self._top_k, self._cf

        def closed(xd, ld, w1, b1, w2, b2):
            y, aux = moe_dispatch_combine(
                xd.reshape(b * t, c), ld.reshape(b * t, self._E),
                w1, b1, w2, b2, top_k=top_k, capacity_factor=cf,
                activation=act)
            return y.reshape(b, t, c), aux

        y, aux = apply_op(
            "MoEFFN", closed,
            [x, logits, self.expert_w1.data(), self.expert_b1.data(),
             self.expert_w2.data(), self.expert_b2.data()])
        if return_aux:
            return y, aux
        return y


class DroplessMoE(HybridBlock):
    """The routed experts of a serving mixture-of-experts layer, (R, units)
    rows -> the weighted sum over each row's HELD chosen experts.

    The router scores all `num_experts` in float32 (independent sigmoids;
    `gate_bias` is added for the choice only, DeepSeek-V3's correction
    bias), the `top_k` best are chosen, and their weights are renormalised
    over ALL the chosen (held or not) to sum to `scale`. Expert e is
    act(x W1[e]) W2[e], no bias: `activation` "relu2" (relu(.)^2) or
    "swiglu" (silu(x Wg) * (x Wu), `expert_w1` then holds [Wg | Wu], twice
    `hidden_size` wide). With `held=(first, count)` only
    experts first .. first + count - 1 live here: a row's other pairs are
    somebody else's, and the output is this holder's share of the sum.

    forward(x, live, route_on=None, impl=, interpret=) -> (y, counts):
    `route_on` (R, units_r) is what the router reads where that is not `x`
    itself (a latent layer routes on the full-width row and feeds the
    experts its projection); `counts` as parallel.moe.dropless_moe."""

    def __init__(self, units, hidden_size, num_experts, top_k, held=None,
                 router_units=None, scale=1.0, activation="relu2", **kwargs):
        super().__init__(**kwargs)
        from ...ops.moe import ACTIVATIONS
        if activation not in ACTIVATIONS:
            raise MXNetError(f"unsupported expert activation {activation!r}:"
                             f" {sorted(ACTIVATIONS)}")
        first, count = held if held is not None else (0, num_experts)
        if top_k > num_experts:
            raise MXNetError(f"top_k {top_k} > num_experts {num_experts}")
        if first < 0 or count < 1 or first + count > num_experts:
            raise MXNetError(f"held experts {first}..{first + count - 1} "
                             f"are not among the {num_experts}")
        self._top_k, self._first, self._scale = top_k, first, scale
        self._activation = activation
        self.gate = Dense(num_experts, flatten=False, use_bias=False,
                          in_units=router_units or units)
        self.gate_bias = Parameter("gate_bias", shape=(num_experts,),
                                   init="zeros")
        self.expert_w1 = Parameter(
            "expert_w1",
            shape=(count, units, ACTIVATIONS[activation][1] * hidden_size))
        self.expert_w2 = Parameter("expert_w2",
                                   shape=(count, hidden_size, units))

    def route(self, u):
        """(weights (R, k) float32, experts (R, k)) of rows `u`."""
        from ...parallel.moe import top_k_weights
        logits = jnp.matmul(
            u, self.gate.weight.data()._data.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        bias = self.gate_bias.data()._data.astype(jnp.float32)
        return top_k_weights(jax.nn.sigmoid(logits), self._top_k, bias=bias,
                             scale=self._scale)

    def forward(self, x, live, route_on=None, impl="auto", interpret=False):
        from ...parallel.moe import dropless_moe
        with jax.named_scope("moe.route"):
            weights, experts = self.route(x if route_on is None
                                          else route_on)
        return dropless_moe(
            x, weights, experts, live, self.expert_w1.data()._data,
            self.expert_w2.data()._data, first=self._first, impl=impl,
            interpret=interpret, activation=self._activation)
