#!/usr/bin/env python
"""Image classification through the full native data path:
folder → im2rec pack → ImageRecordIter (libjpeg decode) → Estimator.fit.

Usage (synthesizes a toy dataset when --rec is omitted):
    JAX_PLATFORMS=cpu python examples/train_image_classifier.py"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def synth_pack(td, classes=2, per_class=8, size=32):
    import cv2
    from mxnet_tpu.io import IRHeader, MXRecordIO, pack

    rng = np.random.default_rng(0)
    path = os.path.join(td, "toy.rec")
    rec = MXRecordIO(path, "w")
    i = 0
    for c in range(classes):
        base = rng.integers(0, 255, (size, size, 3)).astype(np.uint8)
        for _ in range(per_class):
            noisy = np.clip(base.astype(int) +
                            rng.integers(-20, 20, base.shape), 0,
                            255).astype(np.uint8)
            ok, buf = cv2.imencode(".jpg", noisy)
            rec.write(pack(IRHeader(0, float(c), i, 0),
                           bytes(buf.tobytes())))
            i += 1
    rec.close()
    return path


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rec", default="", help="RecordIO pack (im2rec.py)")
    p.add_argument("--model", default="resnet18_v1")
    p.add_argument("--classes", type=int, default=2)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=3)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer, loss as gloss
    from mxnet_tpu.gluon.contrib.estimator import Estimator, LoggingHandler
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.metric import Accuracy
    from mxnet_tpu.models.vision import get_model

    rec_path = args.rec or synth_pack(tempfile.mkdtemp(),
                                      classes=args.classes,
                                      size=args.size)
    it = ImageRecordIter(rec_path, batch_size=args.batch,
                         data_shape=(3, args.size, args.size),
                         shuffle=True)
    net = get_model(args.model, classes=args.classes, thumbnail=True)
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(),
                    train_metrics=Accuracy(),
                    trainer=Trainer(net.collect_params(), "adam",
                                    {"learning_rate": 1e-3},
                                    kvstore=None))

    def batch_fn(b):
        data, label = b
        return data / 255.0, mx.nd.cast(label, "int32")

    est.fit(it, epochs=args.epochs, batch_fn=batch_fn,
            event_handlers=[LoggingHandler(log_interval=2)])
    for m in est.train_metrics:
        name, val = m.get()
        print(f"final train {name}: {val:.4f}")


if __name__ == "__main__":
    main()
