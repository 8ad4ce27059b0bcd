#!/usr/bin/env python
"""SSD detection end to end: anchors → targets → multibox loss → fused
train step → decode+NMS → VOC mAP, on a synthetic two-box dataset.

Usage: JAX_PLATFORMS=cpu python examples/train_ssd.py"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--size", type=int, default=128)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt, parallel as par
    from mxnet_tpu.metric import VOCMApMetric
    from mxnet_tpu.models.vision import ssd_512_resnet50_v1_voc
    from mxnet_tpu.models.vision.ssd import SSDMultiBoxLoss

    net = ssd_512_resnet50_v1_voc()
    mx.rng.seed(0)
    net.initialize(mx.init.Xavier())

    rng = np.random.default_rng(0)
    x = mx.nd.array(rng.standard_normal((2, 3, args.size, args.size)),
                    dtype="float32")
    labels = np.full((2, 2, 5), -1.0, np.float32)
    labels[0, 0] = [5, 0.2, 0.3, 0.6, 0.8]
    labels[1, 0] = [2, 0.5, 0.5, 0.9, 0.85]
    labels[1, 1] = [7, 0.05, 0.05, 0.3, 0.3]

    # targets are a pure function of the (static) anchors + labels
    cls_pred, _, anchors = net(x)
    bt, bm, ct = mx.nd.multibox_target(
        anchors, mx.nd.array(labels), cls_pred.transpose((0, 2, 1)))
    print(f"{anchors.shape[1]} anchors, "
          f"{int((ct.asnumpy() > 0).sum())} matched positives")

    class _Loss(SSDMultiBoxLoss):
        def forward(self, cls_p, box_p, anc, ctt, btt, bmm):
            return super().forward(cls_p, box_p, ctt, btt, bmm)

    step = par.TrainStep(net, _Loss(),
                         opt.SGD(learning_rate=5e-4, momentum=0.9),
                         mesh=None, n_net_inputs=1)
    for i in range(args.steps):
        loss = step(x, ct, bt, bm)
        if (i + 1) % 5 == 0:
            print(f"step {i + 1}: multibox loss "
                  f"{float(loss.asscalar()):.3f}")
    step.sync_params()

    det = net.detect(x, threshold=0.01)
    metric = VOCMApMetric(iou_thresh=0.5)
    metric.update(mx.nd.array(labels), det)
    name, value = metric.get()
    print(f"{name} on the training images: {value:.3f} "
          "(overfit sanity — rises with --steps)")


if __name__ == "__main__":
    main()
