"""A training cell: one gluon model under parallel.TrainStep, one resident
batch, chains of device-chained steps back to back for the whole window.

The configuration's file names the model (`model`), the optimizer and the
plain reference; the traffic file names the batch generator, the steps per
chain and, for more than one chip, the mesh (`layout`).
"""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models, optimizer as opt, parallel as par
from mxnet_tpu.gluon import loss as gloss

from .. import stats
from ..weights import seed_weights


def _check_reference(run, net, ref, kwargs):
    """The model's own forward, evaluation mode, against the float32
    reference on a few seeded sequences: the loss and the masked logits."""
    cell = run.cell
    n = int(cell.traffic["check_sequences"])
    batch = cell.module("generators", cell.traffic["generator"]).generate(
        cell.traffic, kwargs["vocab_size"], run.seed + 1, n)
    ids, tt, vl, pos, labels = (jnp.asarray(a) for a in batch)
    got = par.EvalStep(net)(ids, tt, vl, pos)._data
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    want = jax.jit(functools.partial(ref.masked_logits, kwargs=kwargs))(
        params, ids=ids, token_types=tt, valid_length=vl, positions=pos)
    loss_got = float(ref.mlm_loss(got, labels))
    loss_want = float(ref.mlm_loss(want, labels))
    logit_err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    tol = ref.TOLERANCE
    ok = (abs(loss_got - loss_want) <= tol["loss_abs"]
          and logit_err <= tol["logit_abs"])
    run.say(f"reference: eval MLM loss {loss_got:.5f} against {loss_want:.5f}"
            f" (limit {tol['loss_abs']}), largest logit difference "
            f"{logit_err:.4f} (limit {tol['logit_abs']}) on {n} sequences: "
            f"{'ok' if ok else 'WRONG'}")
    return ok


def run(run):
    cell = run.cell
    cfg, traffic = cell.config, cell.traffic
    kwargs = cfg["model"]["kwargs"]
    ref = cell.module("reference", cfg["reference"])
    devices = run.devices
    layout = traffic.get("layout")
    if layout and math.prod(layout.values()) != len(devices):
        raise ValueError(f"layout {layout} does not cover {len(devices)} "
                         "chips")
    mesh = par.make_mesh(dict(layout), devices=devices) if layout else None
    if cfg.get("prng_impl"):
        # before the first key is made: the generator behind the dropout
        # masks is part of the configuration, as the optimizer is
        jax.config.update("jax_default_prng_impl", cfg["prng_impl"])

    with run.phase("weights"):
        mx.rng.seed(run.seed)
        net = getattr(models, cfg["model"]["class"])(
            getattr(models, cfg["model"]["config_fn"])(**kwargs))
        seed_weights(net, run.seed, kwargs["dtype"])
    with run.phase("reference"):
        correct = _check_reference(run, net, ref, kwargs)

    with run.phase("warmup"):
        o = cfg["optimizer"]
        optim = getattr(opt, o["name"])(
            **{k: v for k, v in o.items() if k != "name"})
        step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), optim,
                             mesh=mesh, n_net_inputs=4)
        per_chip = int(traffic["batch_per_chip"])
        global_batch = per_chip * len(devices)
        batch = cell.module("generators", traffic["generator"]).generate(
            traffic, kwargs["vocab_size"], run.seed, global_batch)
        batch = tuple(mx.nd.array(a, dtype="int32") for a in batch)
        k = int(traffic["steps_per_chain"])
        # two chains: the first compiles, the second shows that the state
        # the first returned is accepted as it is (no second program)
        losses = [step.run_steps(*batch, steps=k).asnumpy()
                  for _ in range(2)]
        # what the step allocates for itself while it runs, per chip (the
        # lowering and the compile are both served from jax's caches)
        temp = step._lowered().compile().memory_analysis()
        run.facts["program_temp_bytes"] = temp.temp_size_in_bytes
        run.say(f"the step program holds {temp.temp_size_in_bytes / 1e9:.3f}"
                f" GB of temporaries a chip beside "
                f"{temp.argument_size_in_bytes / 1e9:.3f} GB of arguments")

    items_per_step = global_batch * int(traffic["seq_len"])
    tracer = run.tracer
    chains = []          # (wall seconds, losses) of each chain in the window
    traced = []          # indices of the chains inside the traced slice

    def chain():
        t0 = time.perf_counter()
        with tracer.span("bench.chain.dispatch"):
            out = step.run_steps(*batch, steps=k)
        with tracer.span("bench.chain.fetch"):
            out = out.asnumpy()
        chains.append((time.perf_counter() - t0, out))

    run.open_window()
    while run.window_left() > 0:
        # the traced slice: three chains, after two untraced ones
        if run.trace and len(chains) == 2 and tracer.reduction is None:
            with tracer.slice(run.keep_trace):
                for _ in range(3):
                    traced.append(len(chains))
                    chain()
        else:
            chain()
    run.close_window()

    walls = [w for w, _ in chains]
    all_losses = np.concatenate([l for _, l in chains]).astype(np.float64)
    finite = bool(np.isfinite(all_losses).all()
                  and np.isfinite(np.concatenate(losses)).all())
    falling = bool(chains[-1][1].mean() < losses[0].mean())
    enough = len(chains) >= 10 or cell.tiny
    run.say(f"window: {len(chains)} chains of {k} steps; loss "
            f"{losses[0][0]:.4f} at the first warm-up step, "
            f"{chains[-1][1][-1]:.4f} at the last; finite {finite}, "
            f"falling {falling}")
    if not cell.tiny:       # a CPU's times are not said, on any line
        run.say(f"chain wall median {stats.median(walls) * 1e3:.3f} ms, "
                f"quartiles {stats.percentile(walls, 25) * 1e3:.3f} / "
                f"{stats.percentile(walls, 75) * 1e3:.3f} ms")
    if not enough:
        run.say(f"only {len(chains)} chains fit the window; the median "
                "wants ten")
    step_s = stats.median(walls) / k
    run.result.update(
        correct=bool(correct and finite and falling and enough),
        attempted=len(chains) * k,
        failed=int((~np.isfinite(all_losses)).sum()))
    run.facts.update(
        kind="train", chips=len(devices), items_per_step=items_per_step,
        steps_per_chain=k, step_s=step_s, chain_walls=walls,
        traced_chains=traced, batch_per_chip=per_chip,
        flops_per_item=ref.flops_per_item(kwargs, traffic),
        attention_cost=ref.attention_cost(kwargs, traffic, per_chip))
    run.end_to_end["train_items_per_s_per_chip"] = \
        items_per_step / step_s / len(devices)
