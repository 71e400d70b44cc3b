"""Minimal end-to-end training example: MNIST MLP through the Fluid-style
static-graph API on one chip (TPU when attached; CPU otherwise).

Run:  python examples/train_mnist.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import dataset, models


def main():
    img, label, pred, loss, acc = models.mnist.build(arch="mlp")
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    train_reader = fluid.batch(dataset.mnist.train(), batch_size=128)
    for epoch in range(3):
        losses, accs = [], []
        for batch in train_reader():
            xs = np.stack([s[0] for s in batch])
            ys = np.array([[s[1]] for s in batch], np.int64)
            lv, av = exe.run(feed={"img": xs, "label": ys},
                             fetch_list=[loss, acc])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
            accs.append(float(np.asarray(av).reshape(-1)[0]))
        print("epoch %d: loss %.4f acc %.3f" %
              (epoch, np.mean(losses), np.mean(accs)))

    fluid.io.save_inference_model("./mnist_model", ["img"], [pred], exe)
    print("saved inference model to ./mnist_model")


if __name__ == "__main__":
    main()
