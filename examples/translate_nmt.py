#!/usr/bin/env python
"""Transformer NMT demo: train on a toy reversal corpus, then beam-decode.

Usage: JAX_PLATFORMS=cpu python examples/translate_nmt.py --steps 80"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np

BOS, EOS = 2, 3


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=80)
    p.add_argument("--beam", type=int, default=4)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer, loss as gloss
    from mxnet_tpu.models import NMTConfig, TransformerNMT

    cfg = NMTConfig(src_vocab_size=32, tgt_vocab_size=32, units=32,
                    hidden_size=64, enc_layers=2, dec_layers=2,
                    num_heads=2, max_length=32, dropout=0.0,
                    bos_id=BOS, eos_id=EOS)
    net = TransformerNMT(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.05))

    # toy task: target = reversed source
    rng = np.random.default_rng(0)
    B, T = 8, 6
    src = rng.integers(4, 32, (B, T)).astype(np.int32)
    body = src[:, ::-1]
    tgt_in = np.concatenate([np.full((B, 1), BOS, np.int32), body], axis=1)
    tgt_out = np.concatenate([body, np.full((B, 1), EOS, np.int32)], axis=1)

    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 3e-3},
                 kvstore=None)
    lfn = gloss.SoftmaxCrossEntropyLoss()
    s_nd = mx.nd.array(src, dtype="int32")
    for i in range(args.steps):
        with mx.autograd.record():
            logits = net(s_nd, mx.nd.array(tgt_in, dtype="int32"))
            loss = lfn(logits.reshape((-1, 32)),
                       mx.nd.array(tgt_out.reshape(-1), dtype="int32")
                       ).mean()
        loss.backward()
        tr.step(1)
        if (i + 1) % 20 == 0:
            print(f"step {i + 1}: loss {float(loss.asscalar()):.4f}")

    toks, scores = net.translate(s_nd, beam_size=args.beam,
                                 max_length=T + 1)
    toks = toks.asnumpy()
    exact = (toks[:, 0, :] == tgt_out).all(axis=1).mean()
    print(f"beam-{args.beam} exact-match on the toy corpus: {exact:.2f}")
    print("src   :", src[0].tolist())
    print("best  :", toks[0, 0].tolist(), " (want reversed + EOS)")


if __name__ == "__main__":
    main()
