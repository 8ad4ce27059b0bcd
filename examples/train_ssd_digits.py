#!/usr/bin/env python
"""SSD trained END TO END on a real-image detection set through the
box-aware native pipeline:

    sklearn digits (real handwritten images) composited with boxes
      -> tools/make_digits_det_rec.py RecordIO pack
      -> ImageDetIter + CreateDetAugmenter (box-aware crop/pad jitter)
      -> jitted multibox_target -> TrainStep (fused step)
      -> held-out mAP (VOCMApMetric over SSD.detect) each eval period
      -> docs/runs/ssd_digits.csv (+ .png curve)

Usage:
    python examples/train_ssd_digits.py --epochs 30
    JAX_PLATFORMS=cpu python examples/train_ssd_digits.py \
        --epochs 1 --train 48 --val 16 --size 128 --batch 8   # smoke
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
    p.add_argument("--data", default="", help="dir with train.rec/val.rec")
    p.add_argument("--size", type=int, default=256)
    p.add_argument("--train", type=int, default=1600)
    p.add_argument("--val", type=int, default=400)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--epochs", type=int, default=30)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--eval-every", type=int, default=5)
    p.add_argument("--out", default="docs/runs")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.image import CreateDetAugmenter, ImageDetIter
    from mxnet_tpu.metric import VOCMApMetric
    from mxnet_tpu.models.vision.ssd import SSD, SSDMultiBoxLoss
    from mxnet_tpu.ops import detection as det_ops, nn as opnn

    on_tpu = jax.devices()[0].platform == "tpu"

    data_dir = args.data
    if not data_dir:
        data_dir = os.path.join(
            tempfile.gettempdir(),
            f"digits_det_{args.size}_{args.train}_{args.val}")
        if not os.path.exists(os.path.join(data_dir, "train.rec")):
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), "tools"))
            sys.argv = ["make_digits_det_rec", "--out", data_dir,
                        "--size", str(args.size),
                        "--train", str(args.train), "--val", str(args.val)]
            import make_digits_det_rec
            make_digits_det_rec.main()

    det_augs = CreateDetAugmenter((3, args.size, args.size),
                                  rand_crop=0.3, rand_pad=0.3,
                                  rand_mirror=False,  # digits are chiral
                                  brightness=0.2, contrast=0.2)
    train_it = ImageDetIter(os.path.join(data_dir, "train.rec"),
                            batch_size=args.batch,
                            data_shape=(3, args.size, args.size),
                            max_objs=4, shuffle=True,
                            det_aug_list=det_augs)
    val_it = ImageDetIter(os.path.join(data_dir, "val.rec"),
                          batch_size=args.batch,
                          data_shape=(3, args.size, args.size),
                          max_objs=4, shuffle=False)

    net = SSD(classes=10, image_size=args.size)
    mx.rng.seed(0)
    net.initialize(mx.init.Xavier())
    dtype = "bfloat16" if on_tpu else "float32"
    if on_tpu:
        net.cast("bfloat16")

    def norm(data):
        return mx.nd.cast((data / 255.0 - 0.25) * 2.0, dtype)

    # static anchors: one tiny forward
    probe = mx.nd.array(np.zeros((1, 3, args.size, args.size), np.float32))
    _, _, anchors = net(mx.nd.cast(probe, dtype))
    anchors_j = anchors._data.astype(jnp.float32)
    n_anchors = anchors.shape[1]
    print(f"SSD-{args.size}: {n_anchors} anchors")

    # one compiled program for the anchor->gt matching per batch
    tgt_raw = det_ops.multibox_target.raw_fn

    @jax.jit
    def make_targets(labels):
        dummy = jnp.zeros((labels.shape[0], 11, n_anchors), jnp.float32)
        return tgt_raw(anchors_j, labels, dummy)

    class _Loss(SSDMultiBoxLoss):
        def forward(self, cls_p, box_p, anc, ctt, btt, bmm):
            return super().forward(cls_p, box_p, ctt, btt, bmm)

    step = par.TrainStep(net, _Loss(),
                         opt.SGD(learning_rate=args.lr, momentum=0.9,
                                 wd=5e-4),
                         mesh=None, n_net_inputs=1)

    def evaluate():
        step.sync_params()
        metric = VOCMApMetric(iou_thresh=0.5,
                              class_names=[str(i) for i in range(10)])
        n_eval = 0
        for data, label in val_it:
            out = net.detect(norm(data), threshold=0.05)  # (B, N, 6)
            metric.update(label, out)
            n_eval += data.shape[0]
        if n_eval == 0:
            raise RuntimeError(
                "validation iterator yielded no batches (batch size "
                "larger than the val set? partial batches are dropped)")
        names, vals = metric.get()
        return vals[-1] if isinstance(vals, list) else vals

    rows = []
    t0 = time.perf_counter()
    for epoch in range(args.epochs):
        losses = []
        for data, label in train_it:
            x = norm(data)
            bt, bm, ct = make_targets(label._data.astype(jnp.float32))
            loss = step(x, mx.nd.NDArray(ct), mx.nd.NDArray(bt),
                        mx.nd.NDArray(bm))
            losses.append(loss)
        mean_loss = float(np.mean([float(l.asscalar()) for l in losses]))
        row = {"epoch": epoch, "train_loss": round(mean_loss, 4),
               "wall_sec": round(time.perf_counter() - t0, 1)}
        if (epoch + 1) % args.eval_every == 0 or epoch == args.epochs - 1:
            row["val_map"] = round(float(evaluate()), 4)
            print(f"epoch {epoch}: loss {mean_loss:.4f} "
                  f"VAL_mAP {row['val_map']:.4f}")
        else:
            print(f"epoch {epoch}: loss {mean_loss:.4f}")
        rows.append(row)

    os.makedirs(args.out, exist_ok=True)
    csv_path = os.path.join(args.out, "ssd_digits.csv")
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["epoch", "train_loss",
                                          "val_map", "wall_sec"])
        w.writeheader()
        w.writerows(rows)
    print(f"wrote {csv_path}")

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, ax1 = plt.subplots(figsize=(7, 4))
        ax1.plot([r["epoch"] for r in rows],
                 [r["train_loss"] for r in rows], "C0-",
                 label="train multibox loss")
        ax1.set_xlabel("epoch")
        ax1.set_ylabel("loss")
        ev = [r for r in rows if "val_map" in r]
        ax2 = ax1.twinx()
        ax2.plot([r["epoch"] for r in ev], [r["val_map"] for r in ev],
                 "C1-o", ms=4, label="held-out mAP@0.5")
        ax2.set_ylabel("mAP")
        ax2.set_ylim(0, 1.02)
        fig.legend(loc="center right")
        ax1.set_title(f"SSD-{args.size} on digit-detection composites "
                      "(real digit images)")
        fig.tight_layout()
        png = os.path.join(args.out, "ssd_digits.png")
        fig.savefig(png, dpi=110)
        print(f"wrote {png}")
    except Exception as e:
        print("plot skipped:", e)

    last = [r for r in rows if "val_map" in r][-1]
    print(f"FINAL: held-out mAP@0.5 = {last['val_map']:.4f}")


if __name__ == "__main__":
    main()
