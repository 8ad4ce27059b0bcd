#!/usr/bin/env python
"""BERT-base MLM pretraining on REAL local text, end to end:

    tools/make_text_corpus.py  (Python stdlib + site-packages sources +
                                /usr/share/doc — real code/English text;
                                zero-egress environment, no downloads)
      -> dynamic-masking batch sampler (BERT 15% / 80-10-10 recipe)
      -> TrainStep.run_steps (device-chained steps, AdamW, linear
         warmup->decay applied between chunks)
      -> TrainCheckpoint (async, orbax) every --ckpt-every chunks
      -> held-out masked-token loss/accuracy via EvalStep
      -> docs/runs/bert_mlm_real.csv (+ .png curve)

Usage:
    python examples/train_bert_mlm_real.py --steps 3000
    JAX_PLATFORMS=cpu python examples/train_bert_mlm_real.py \
        --steps 40 --layers 2 --units 128 --heads 2 --batch 4 \
        --seq-len 128   # smoke
"""
import argparse
import csv
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def sample_batch(rng, stream, vocab_size, batch, seq_len, n_masked,
                 mask_id=4, n_special=5):
    """Random windows + BERT dynamic masking. Returns the 5-tuple
    BertForMaskedLM consumes: ids, token_types, valid_len, positions,
    labels."""
    starts = rng.integers(0, len(stream) - seq_len - 1, batch)
    ids = np.stack([stream[s:s + seq_len] for s in starts]).astype(np.int32)
    perm = np.argsort(rng.random((batch, seq_len)), axis=-1)
    pos = np.sort(perm[:, :n_masked], axis=-1).astype(np.int32)
    labels = np.take_along_axis(ids, pos, axis=1).astype(np.int32)
    r = rng.random((batch, n_masked))
    replace = np.where(
        r < 0.8, mask_id,
        np.where(r < 0.9,
                 rng.integers(n_special, vocab_size, (batch, n_masked)),
                 labels)).astype(np.int32)
    np.put_along_axis(ids, pos, replace, axis=1)
    tt = np.zeros((batch, seq_len), np.int32)
    vl = np.full((batch,), seq_len, np.int32)
    return ids, tt, vl, pos, labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--corpus", default="", help="dir from "
                   "make_text_corpus.py (auto-built if empty)")
    p.add_argument("--steps", type=int, default=3000)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--chunk", type=int, default=25,
                   help="steps per device dispatch (run_steps)")
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--warmup", type=int, default=300)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--units", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--ckpt-every", type=int, default=20)
    p.add_argument("--eval-every", type=int, default=4)
    p.add_argument("--out", default="docs/runs")
    args = p.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.checkpoint import TrainCheckpoint
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import BertConfig, BertForMaskedLM
    from mxnet_tpu.parallel import EvalStep

    on_tpu = jax.devices()[0].platform == "tpu"

    corpus_dir = args.corpus
    if not corpus_dir:
        corpus_dir = os.path.join(tempfile.gettempdir(), "textcorpus")
        if not os.path.exists(os.path.join(corpus_dir, "corpus.npz")):
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), "tools"))
            sys.argv = ["make_text_corpus", "--out", corpus_dir]
            import make_text_corpus
            make_text_corpus.main()
    blob = np.load(os.path.join(corpus_dir, "corpus.npz"))
    train_stream, val_stream = blob["train"], blob["val"]
    vocab_size = len(json.load(open(os.path.join(corpus_dir,
                                                 "vocab.json"))))
    n_masked = max(1, int(args.seq_len * 0.15))

    cfg = BertConfig(vocab_size=vocab_size, units=args.units,
                     hidden_size=4 * args.units, num_layers=args.layers,
                     num_heads=args.heads, max_length=args.seq_len,
                     dropout=0.1, attention_dropout=0.1,
                     dtype="bfloat16" if on_tpu else "float32")
    net = BertForMaskedLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")

    o = opt.AdamW(learning_rate=args.lr, wd=0.01)
    step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), o,
                         mesh=None, n_net_inputs=4)
    ckpt = TrainCheckpoint(os.path.join(tempfile.gettempdir(),
                                        "bert_mlm_real_ckpt"))

    rng = np.random.default_rng(1)
    eval_rng = np.random.default_rng(99)
    eval_batches = [sample_batch(eval_rng, val_stream, vocab_size,
                                 args.batch, args.seq_len, n_masked)
                    for _ in range(4)]
    eval_step = EvalStep(net, mesh=None)

    def evaluate():
        step.sync_params()
        tot_loss = tot_correct = tot = 0
        for ids, tt, vl, pos, labels in eval_batches:
            logits = eval_step(mx.nd.array(ids), mx.nd.array(tt),
                               mx.nd.array(vl), mx.nd.array(pos))
            lg = np.asarray(logits.asnumpy(), np.float32)
            lg = lg - lg.max(-1, keepdims=True)
            lp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
            nll = -np.take_along_axis(lp, labels[..., None], -1)[..., 0]
            tot_loss += float(nll.sum())
            tot_correct += int((lg.argmax(-1) == labels).sum())
            tot += labels.size
        return tot_loss / tot, tot_correct / tot

    def lr_at(t):
        if t < args.warmup:
            return args.lr * (t + 1) / args.warmup
        frac = (t - args.warmup) / max(1, args.steps - args.warmup)
        return args.lr * max(0.05, 1.0 - frac)

    rows = []
    tokens_per_step = args.batch * args.seq_len
    done = 0
    t0 = time.perf_counter()
    while done < args.steps:
        k = min(args.chunk, args.steps - done)
        o.learning_rate = lr_at(done)
        batches = [sample_batch(rng, train_stream, vocab_size, args.batch,
                                args.seq_len, n_masked) for _ in range(k)]
        stacked = [mx.nd.array(np.stack([b[i] for b in batches]))
                   for i in range(5)]
        losses = step.run_steps(*stacked).asnumpy()
        done += k
        elapsed = time.perf_counter() - t0
        row = {"step": done, "train_loss": float(losses.mean()),
               "lr": round(lr_at(done), 7),
               "tokens_per_sec": round(done * tokens_per_step / elapsed, 1),
               "wall_sec": round(elapsed, 1)}
        if (done // args.chunk) % args.eval_every == 0 or done >= args.steps:
            vl_, va = evaluate()
            row["val_loss"], row["val_masked_acc"] = round(vl_, 4), \
                round(va, 4)
            print(f"step {done}: train {row['train_loss']:.4f} "
                  f"val {vl_:.4f} masked-acc {va:.4f} "
                  f"({row['tokens_per_sec']:.0f} tok/s)")
        else:
            print(f"step {done}: train {row['train_loss']:.4f} "
                  f"({row['tokens_per_sec']:.0f} tok/s)")
        rows.append(row)
        if (done // args.chunk) % args.ckpt_every == 0:
            ckpt.save(done, step)

    ckpt.save(args.steps, step, wait=True)
    os.makedirs(args.out, exist_ok=True)
    csv_path = os.path.join(args.out, "bert_mlm_real.csv")
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["step", "train_loss", "val_loss",
                                          "val_masked_acc", "lr",
                                          "tokens_per_sec", "wall_sec"])
        w.writeheader()
        w.writerows(rows)
    print(f"wrote {csv_path}")

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, ax1 = plt.subplots(figsize=(7, 4))
        ax1.plot([r["step"] for r in rows],
                 [r["train_loss"] for r in rows], "C0-",
                 label="train loss")
        ev = [r for r in rows if "val_loss" in r]
        ax1.plot([r["step"] for r in ev], [r["val_loss"] for r in ev],
                 "C2--o", ms=3, label="val loss")
        ax1.set_xlabel("step")
        ax1.set_ylabel("MLM loss")
        ax2 = ax1.twinx()
        ax2.plot([r["step"] for r in ev],
                 [r["val_masked_acc"] for r in ev], "C1-o", ms=3,
                 label="val masked acc")
        ax2.set_ylabel("masked-token accuracy")
        fig.legend(loc="upper right")
        ax1.set_title("BERT-base MLM on real local text "
                      f"(B={args.batch}, T={args.seq_len})")
        fig.tight_layout()
        png = os.path.join(args.out, "bert_mlm_real.png")
        fig.savefig(png, dpi=110)
        print(f"wrote {png}")
    except Exception as e:
        print("plot skipped:", e)

    last_ev = [r for r in rows if "val_loss" in r][-1]
    print(f"FINAL: step {last_ev['step']} val_loss {last_ev['val_loss']} "
          f"masked_acc {last_ev['val_masked_acc']} "
          f"{rows[-1]['tokens_per_sec']:.0f} tok/s sustained")


if __name__ == "__main__":
    main()
