"""Any-program model parallelism through the descriptor path: the SAME
Fluid program shards over a dp x tp mesh with ZeRO-1 optimizer-state
sharding — no model rewrite, just BuildStrategy knobs (+ optional
per-param ParamAttr(shard_spec=...) annotations).

The sharding planner (parallel/planner.py) assigns every parameter a
PartitionSpec (auto Megatron column/row derivation for fc/embedding
chains unless annotated) and XLA GSPMD inserts the collectives — the
TPU-native equivalent of the reference's multi-device graph builder
(multi_devices_graph_pass.cc), which only did data parallelism.

Run (a dry run on 8 virtual CPU devices; it never touches the chip):
  python examples/train_tensor_parallel.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _bootstrap  # noqa: E402

_bootstrap.ensure_devices(8)

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers



def main():
    # an ordinary fluid.layers model — nothing parallel-aware in it
    ids = layers.data(name="ids", shape=[16], dtype="int64")
    label = layers.data(name="label", shape=[1], dtype="int64")
    emb = layers.embedding(ids, size=[1024, 64])          # auto: vocab-row
    h = layers.reduce_mean(emb, dim=1)
    h = layers.fc(h, 256, act="relu")                     # auto: column
    h = layers.fc(h, 256, act="relu")                     # auto: row
    # explicit annotation always wins over the auto walk:
    logits = layers.fc(h, 16, param_attr=fluid.ParamAttr(
        name="head_w", shard_spec=(None, "tp")))
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    bs = fluid.BuildStrategy()
    bs.tensor_parallel_degree = 2                  # mesh = (dp=n/2, tp=2)
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce  # ZeRO-1
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)

    rng = np.random.RandomState(0)
    for step in range(20):
        feed = {"ids": rng.randint(0, 1024, (64, 16)).astype(np.int64),
                "label": rng.randint(0, 16, (64, 1)).astype(np.int64)}
        (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
        if step % 5 == 0:
            print("step %2d  loss %.4f" % (step,
                                           float(np.asarray(lv).mean())))

    plan = next(iter(compiled._compiled_steps.values()))._plan.summary()
    print("\nsharding plan (param -> PartitionSpec dims):")
    for name, spec in sorted(plan.items()):
        print("  %-28s %s" % (name, spec))


if __name__ == "__main__":
    main()
