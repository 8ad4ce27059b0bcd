#!/usr/bin/env python
"""ResNet-50 v1b on REAL image data end-to-end through the native stack:

    sklearn digits (1,797 real handwritten images)
      -> tools/make_digits_rec.py RecordIO pack
      -> ImageRecordIter (native libjpeg decode, thread pool, prefetch)
      -> Estimator(fused=True)  [TrainStep: one XLA program/step]
      -> CheckpointHandler + held-out evaluation each epoch
      -> docs/runs/resnet50_digits.csv (+ .png curve)

This is the "small end-to-end train" evidence tier (SURVEY.md §4): a real
model, real data, the real input pipeline, to a real held-out accuracy.
It also measures sustained img/sec WITH the pipeline feeding (not
synthetic resident tensors), closing the input-path measurement gap.

Usage:
    python examples/train_resnet50_digits.py --epochs 40
    JAX_PLATFORMS=cpu python examples/train_resnet50_digits.py \
        --epochs 2 --size 64 --batch 32 --model resnet18_v1b   # smoke
"""
import argparse
import csv
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data", default="", help="dir with train.rec/val.rec "
                   "(made by tools/make_digits_rec.py; auto-built if empty)")
    p.add_argument("--model", default="resnet50_v1b")
    p.add_argument("--size", type=int, default=224)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--epochs", type=int, default=40)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--warmup-epochs", type=int, default=3)
    p.add_argument("--ckpt-epochs", type=int, default=10)
    p.add_argument("--out", default="docs/runs")
    p.add_argument("--ckpt-dir", default="")
    args = p.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer, loss as gloss
    from mxnet_tpu.gluon.contrib.estimator import (
        CheckpointHandler, Estimator, LoggingHandler)
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        EpochEnd, TrainBegin)
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.metric import Accuracy
    from mxnet_tpu.models.vision import get_model
    from mxnet_tpu.parallel import EvalStep

    on_tpu = jax.devices()[0].platform == "tpu"

    data_dir = args.data
    if not data_dir:
        data_dir = os.path.join(tempfile.gettempdir(),
                                f"digits_rec_{args.size}")
        if not (os.path.exists(os.path.join(data_dir, "train.rec"))
                and os.path.exists(os.path.join(data_dir, "val.rec"))):
            sys.argv = ["make_digits_rec", "--out", data_dir,
                        "--size", str(args.size)]
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), "tools"))
            import make_digits_rec
            make_digits_rec.main()

    class ShiftJitterAug:
        """Random +-shift translation (zero-fill) — the one geometric
        augmentation that matters for centered digit glyphs."""

        def __init__(self, max_frac=0.08):
            self.max_frac = max_frac

        def __call__(self, src):
            img = src.asnumpy() if hasattr(src, "asnumpy") else src
            h, w = img.shape[:2]
            m = int(h * self.max_frac)
            dy, dx = np.random.randint(-m, m + 1, 2)
            out = np.zeros_like(img)
            ys = slice(max(dy, 0), h + min(dy, 0))
            xs = slice(max(dx, 0), w + min(dx, 0))
            ys_src = slice(max(-dy, 0), h + min(-dy, 0))
            xs_src = slice(max(-dx, 0), w + min(-dx, 0))
            out[ys, xs] = img[ys_src, xs_src]
            return out

    train_it = ImageRecordIter(
        os.path.join(data_dir, "train.rec"), batch_size=args.batch,
        data_shape=(3, args.size, args.size), shuffle=True,
        aug_list=[ShiftJitterAug()])
    # the iterator drops partial batches (reference batching contract);
    # evaluation must cover EVERY held-out image, so the val iterator
    # uses one full-set batch
    from mxnet_tpu.io import MXRecordIO
    _vr = MXRecordIO(os.path.join(data_dir, "val.rec"), "r")
    n_val = 0
    while _vr.read() is not None:
        n_val += 1
    _vr.close()
    val_it = ImageRecordIter(
        os.path.join(data_dir, "val.rec"), batch_size=n_val,
        data_shape=(3, args.size, args.size), shuffle=False)

    net = get_model(args.model, classes=10)
    net.initialize(mx.init.Xavier())
    dtype = "bfloat16" if on_tpu else "float32"
    if on_tpu:
        net.cast("bfloat16")

    def batch_fn(b):
        data, label = b
        x = (data / 255.0 - 0.5) * 4.0  # digits are near-binary; wide range
        return mx.nd.cast(x, dtype), mx.nd.cast(label, "int32")

    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(),
                    train_metrics=Accuracy(),
                    trainer=Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": args.lr,
                                     "momentum": 0.9, "wd": 1e-4},
                                    kvstore=None),
                    fused=True)

    # held-out eval through a single compiled forward program (EvalStep),
    # not per-op eager dispatch
    eval_step = {"step": None}

    def evaluate():
        if eval_step["step"] is None:
            eval_step["step"] = EvalStep(net, mesh=None)
        correct = total = 0
        for b in val_it:
            data, label = batch_fn(b)
            logits = eval_step["step"](data)
            pred = np.asarray(logits.asnumpy()).argmax(1)
            correct += int((pred == label.asnumpy()).sum())
            total += len(pred)
        return correct / max(total, 1)

    rows = []
    t_train = {"tic": None, "images": 0}

    class CurveHandler(TrainBegin, EpochEnd):
        def train_begin(self, estimator, **kw):
            t_train["tic"] = time.perf_counter()
            if args.warmup_epochs:
                estimator.trainer.optimizer.learning_rate = \
                    args.lr / (args.warmup_epochs + 1)

        def epoch_end(self, estimator, epoch=None, **kw):
            # linear LR warmup over the first epochs (bf16 ResNet with a
            # cold head diverges at full lr on this tiny dataset)
            if epoch is not None and epoch < args.warmup_epochs:
                estimator.trainer.optimizer.learning_rate = \
                    args.lr * (epoch + 2) / (args.warmup_epochs + 1)
            # sync the step's weights into the net for EvalStep
            if estimator._train_step is not None:
                estimator._train_step.sync_params()
            metrics = {m.get()[0]: m.get()[1]
                       for m in estimator.train_metrics}
            acc = evaluate()
            dt = time.perf_counter() - t_train["tic"]
            # note: train accuracy is not available on the fused path
            # (the one-program step returns only the loss)
            rows.append({"epoch": epoch, "train_loss": metrics["loss"],
                         "val_acc": acc, "wall_sec": round(dt, 2)})
            print(f"epoch {epoch}: loss {metrics['loss']:.4f} "
                  f"VAL_ACC {acc:.4f}")

    handlers = [LoggingHandler(log_interval="epoch"), CurveHandler()]
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "resnet50_digits_ckpt")

    class PeriodicCheckpoint(CheckpointHandler):
        # every N epochs: a full-param host fetch per save is expensive
        # over a remote device link
        def epoch_end(self, estimator, epoch=None, **kw):
            if epoch is not None and (epoch + 1) % args.ckpt_epochs == 0:
                super().epoch_end(estimator, epoch=epoch, **kw)

    handlers.append(PeriodicCheckpoint(ckpt_dir, model_prefix=args.model,
                                       monitor=None))

    est.fit(train_it, epochs=args.epochs, batch_fn=batch_fn,
            event_handlers=handlers)

    # sustained throughput WITH the pipeline feeding (post-warmup epochs)
    step = est._train_step
    n = 0
    t0 = time.perf_counter()
    for b in train_it:
        data, label = batch_fn(b)
        step(data, label)
        n += data.shape[0]
    loss = step(data, label)
    float(loss.asscalar())
    pipeline_img_sec = n / (time.perf_counter() - t0)

    os.makedirs(args.out, exist_ok=True)
    csv_path = os.path.join(args.out, "resnet50_digits.csv")
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
        w.writeheader()
        w.writerows(rows)
    print(f"wrote {csv_path}")
    print(f"pipeline-fed throughput: {pipeline_img_sec:.1f} img/sec "
          f"(decode+augment+H2D+train, batch {args.batch})")

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, ax1 = plt.subplots(figsize=(7, 4))
        ep = [r["epoch"] for r in rows]
        ax1.plot(ep, [r["train_loss"] for r in rows], "C0-",
                 label="train loss")
        ax1.set_xlabel("epoch")
        ax1.set_ylabel("loss")
        ax2 = ax1.twinx()
        ax2.plot(ep, [r["val_acc"] for r in rows], "C1-o", ms=3,
                 label="held-out accuracy")
        ax2.set_ylabel("val accuracy")
        ax2.set_ylim(0, 1.02)
        fig.legend(loc="center right")
        ax1.set_title(f"{args.model} on sklearn digits (real data, "
                      f"native pipeline)")
        fig.tight_layout()
        png = os.path.join(args.out, "resnet50_digits.png")
        fig.savefig(png, dpi=110)
        print(f"wrote {png}")
    except Exception as e:  # plotting is best-effort
        print("plot skipped:", e)

    final = rows[-1]
    print(f"FINAL: val_acc={final['val_acc']:.4f} after "
          f"{args.epochs} epochs; {pipeline_img_sec:.1f} img/sec sustained")
    return final


if __name__ == "__main__":
    main()
