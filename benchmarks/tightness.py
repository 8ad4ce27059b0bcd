#!/usr/bin/env python3
"""How tight a serving cell's comparison is: the readings a reference's
`TOLERANCE` has to lie between, on the device the cell runs on.

    python3 benchmarks/tightness.py --workload <name> --seed <n> [--check]

The cell's model is built and seeded as its runner does; its own
whole-sequence forward gives the logits of two random sequences as long as
the check's. Printed, each as the largest absolute difference of any logit:
the model against the float32 reference (what the limit must exceed), and
the model against the reference with each of its `PERTURBATIONS` (what the
limit must stay under). Where the model routes tokens to experts
(`chosen_experts`), also the share of the reference's chosen experts that
the model's own forward chose, and the `choice_made_without_the_bias`
reading taken again with the routers' bias redrawn at the scores' own
spread (0.25), under which draw the bias decides choices. One JSON object
is the last line. `--check`: the tiny sizes on the CPU, for the tests."""
import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

BIAS_SPREAD = 0.25


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    if args.check:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import cells
    from benchmarks.weights_per_parameter import seed_weights
    from mxnet_tpu import models, parallel as par
    from mxnet_tpu.gluon import nn

    cell = cells.Cell(args.workload, tiny=args.check)
    cfg = cell.config
    kwargs = cfg["model"]["kwargs"]
    ref = cell.module("reference", cfg["reference"])
    net = getattr(models, cfg["model"]["class"])(
        getattr(models, cfg["model"]["config_fn"])(**kwargs))
    net.collect_params().setattr("grad_req", "null")
    seed_weights(net, args.seed, kwargs["dtype"])
    check = cfg["check"]
    width = -(-(max(check["prompt_lens"]) + check["new_tokens"]) // 64) * 64
    rng = np.random.default_rng([args.seed, 0x7469])
    ids = jnp.asarray(rng.integers(0, kwargs["vocab_size"], (2, width)),
                      jnp.int32)
    say = lambda text: print(f"[tightness] {text}", flush=True)
    diff = lambda a, b: float(jnp.max(jnp.abs(a - b)))

    def readings(names):
        params = {k: p.data()._data for k, p in net.collect_params().items()}
        forward = lambda **kw: jax.jit(functools.partial(
            ref.logits, kwargs=kwargs, **kw))(params, ids=ids)
        own = par.EvalStep(net)(ids)._data.astype(jnp.float32)
        want = forward()
        out = {"reference": diff(own, want), "spread": float(jnp.std(want))}
        for name in names:
            got = forward(**ref.PERTURBATIONS[name])
            out[name] = diff(own, got)
            say(f"{name}: the model differs from it by {out[name]:.4f}, "
                f"the reference itself by {diff(want, got):.4f}")
        return out, params

    say(f"{cell.name}, seed {args.seed}, 2 x {width} tokens, limit "
        f"{ref.TOLERANCE['logit_abs']}")
    out, params = readings(list(ref.PERTURBATIONS))
    say(f"the model differs from the float32 reference by "
        f"{out['reference']:.4f}; the logits' spread is {out['spread']:.4f}")
    if hasattr(ref, "chosen_experts"):
        want = jax.jit(functools.partial(ref.chosen_experts, kwargs=kwargs))(
            params, ids=ids)
        seen, route = [], nn.DroplessMoE.route

        def spy(self, u):
            weights, experts = route(self, u)
            seen.append(experts)
            return weights, experts

        nn.DroplessMoE.route = spy
        try:
            net.hidden(ids)         # eagerly: the spy sees values
        finally:
            nn.DroplessMoE.route = route
        k = kwargs["top_k"]
        shares = []
        for got, ref_chosen in zip(seen, want):
            got = np.asarray(got).reshape(-1, k)
            ref_chosen = np.asarray(ref_chosen).reshape(-1, k)
            both = (got[:, :, None] == ref_chosen[:, None, :]).any(1)
            shares.append(float(both.mean()))
        out["chosen_share"] = shares
        say("of the reference's chosen experts the model's own forward "
            f"chose, by expert layer: {shares}; rows with the same "
            f"{k}: {float(both.all(1).mean()):.4f} in the last layer")
        rb = np.random.default_rng([args.seed, 0x6262])
        for name, p in net.collect_params().items():
            if name.endswith("gate_bias"):
                p.set_data(jnp.asarray(
                    BIAS_SPREAD * rb.standard_normal(p.shape),
                    kwargs["dtype"]))
        redrawn, _ = readings(["choice_made_without_the_bias"])
        out["bias_redrawn"] = redrawn
        say(f"with the bias redrawn at {BIAS_SPREAD}: the model differs from "
            f"the reference by {redrawn['reference']:.4f}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
