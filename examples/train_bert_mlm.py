#!/usr/bin/env python
"""BERT MLM pretraining on a device mesh — the north-star workload.

Usage (CPU smoke):  JAX_PLATFORMS=cpu python examples/train_bert_mlm.py
On a TPU slice, pick a real mesh: --dp 8 --tp 2 ... (sharding choices
only; the model code never changes — SURVEY.md §5.7 design)."""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--units", type=int, default=128)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--vocab", type=int, default=1000)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    args = p.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import BertConfig, BertForMaskedLM
    from mxnet_tpu.parallel import PartitionSpec as P

    mesh = None
    n_mesh = args.dp * args.tp * args.sp
    if n_mesh > 1:
        mesh = par.make_mesh({"dp": args.dp, "sp": args.sp, "tp": args.tp},
                             devices=jax.devices()[:n_mesh])

    cfg = BertConfig(vocab_size=args.vocab, units=args.units,
                     hidden_size=4 * args.units, num_layers=args.layers,
                     num_heads=args.heads, max_length=args.seq_len,
                     attention_dropout=0.0 if args.sp > 1 else 0.1)
    net = BertForMaskedLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    if mesh is not None and args.tp > 1:
        par.apply_sharding_rules(net, par.megatron_dense_rules(tp_axis="tp"))

    seq = P("dp", "sp")
    step = par.TrainStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        opt.AdamW(learning_rate=1e-4, wd=0.01), mesh=mesh, n_net_inputs=4,
        batch_specs=(seq, seq, P("dp"), P("dp"), P("dp")))

    ckpt = None
    if args.ckpt_dir:
        from mxnet_tpu.checkpoint import TrainCheckpoint
        ckpt = TrainCheckpoint(args.ckpt_dir)
        if ckpt.latest_step() is not None:
            cursor = ckpt.restore(step)
            print(f"resumed from step {step.step_count}, cursor {cursor}")

    rng = np.random.default_rng(0)
    B, T, M = args.batch, args.seq_len, max(1, args.seq_len // 8)
    ids = mx.nd.array(rng.integers(0, args.vocab, (B, T)), dtype="int32")
    tt = mx.nd.array(np.zeros((B, T)), dtype="int32")
    vl = mx.nd.array(np.full((B,), T), dtype="int32")
    pos = mx.nd.array(np.sort(np.argsort(
        rng.random((B, T)))[:, :M]), dtype="int32")
    lab = mx.nd.array(rng.integers(0, args.vocab, (B, M)), dtype="int32")

    tic = time.time()
    for i in range(step.step_count, args.steps):
        loss = step(ids, tt, vl, pos, lab)
        if (i + 1) % 10 == 0:
            # Speedometer-format line (reference callback.Speedometer)
            speed = 10 * B / (time.time() - tic)
            print(f"Batch[{i + 1}]\tSpeed: {speed:.2f} samples/sec"
                  f"\tloss={float(loss.asscalar()):.4f}")
            tic = time.time()
            if ckpt is not None:
                ckpt.save(i + 1, step, data_cursor={"step": i + 1})
    if ckpt is not None:
        ckpt.wait_until_finished()
    print("final loss:", float(loss.asscalar()))


if __name__ == "__main__":
    main()
