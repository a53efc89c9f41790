"""ResNet ImageNet-style training with Gluon hybridize — BASELINE workload 2.

Counterpart of the reference's ResNet-50 training path
(``example/image-classification/train_imagenet.py`` + Gluon model_zoo
``resnet.py``), re-engineered TPU-first: the whole step — forward + loss +
backward + gradient allreduce + SGD-momentum update — compiles into ONE XLA
module via ``mxnet_tpu.parallel.TrainStep`` over a ``dp`` device mesh.
With a real ImageRecordIter ``.rec`` file pass ``--rec``; otherwise
synthetic ImageNet-shaped data keeps it runnable with zero egress.

Usage::

    python train_resnet.py --model resnet18_v1 --batch-size 32 --devices 8
    python train_resnet.py --model resnet50_v1 --rec train.rec
"""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)) + "/../..")

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel
from mxnet_tpu.gluon.model_zoo import vision


def parse_args():
    p = argparse.ArgumentParser(
        description="Gluon hybridized ResNet trainer (fused SPMD step)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--model", type=str, default="resnet50_v1",
                   help="any mxnet_tpu.gluon.model_zoo.vision model name")
    p.add_argument("--batch-size", type=int, default=32,
                   help="GLOBAL batch size (sharded over devices)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--devices", type=int, default=0,
                   help="devices in the dp mesh; 0 = all visible")
    p.add_argument("--num-batches", type=int, default=50,
                   help="batches per epoch for synthetic data")
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--mom", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=1e-4)
    p.add_argument("--disp-batches", type=int, default=10)
    p.add_argument("--rec", type=str, default=None,
                   help="path to an ImageRecord .rec file")
    p.add_argument("--save-prefix", type=str, default=None,
                   help="export symbol+params here after training")
    return p.parse_args()


def data_iter(args):
    if args.rec:
        return mx.io.ImageRecordIter(
            path_imgrec=args.rec, batch_size=args.batch_size,
            data_shape=(3, args.image_size, args.image_size), shuffle=True)
    rng = np.random.RandomState(0)
    x = rng.rand(args.batch_size * args.num_batches, 3,
                 args.image_size, args.image_size).astype(np.float32)
    y = rng.randint(0, args.num_classes,
                    args.batch_size * args.num_batches).astype(np.float32)
    return mx.io.NDArrayIter(x, y, args.batch_size, shuffle=False,
                             last_batch_handle="discard")


def main():
    args = parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")
    import jax

    n_dev = args.devices or len(jax.devices())
    mesh = parallel.device_mesh(n_dev)
    logging.info("training %s on %d device(s): %s", args.model, n_dev,
                 [str(d) for d in mesh.devices.flat])

    net = getattr(vision, args.model)(classes=args.num_classes)
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian",
                                         factor_type="in", magnitude=2))
    net.hybridize()
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd", mesh,
        optimizer_params={"learning_rate": args.lr, "momentum": args.mom,
                          "wd": args.wd})

    metric = mx.metric.Loss()
    for epoch in range(args.num_epochs):
        it = data_iter(args)
        tic = time.time()
        n_seen = 0
        for i, batch in enumerate(it):
            loss = step(batch.data[0], batch.label[0])
            metric.update(None, [loss])
            n_seen += args.batch_size
            if (i + 1) % args.disp_batches == 0:
                loss.wait_to_read()  # bound the async queue at the log point
                speed = n_seen / (time.time() - tic)
                logging.info("Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                             "\tloss=%.4f", epoch, i + 1, speed,
                             metric.get()[1])
                metric.reset()
                tic, n_seen = time.time(), 0
        logging.info("Epoch[%d] done", epoch)

    step.copy_to_net()
    if args.save_prefix:
        net.export(args.save_prefix)
        logging.info("exported to %s-symbol.json / %s-0000.params",
                     args.save_prefix, args.save_prefix)


if __name__ == "__main__":
    main()
