"""Expert parallelism through the Fluid API: nets.switch_moe builds a
top-1 switch mixture-of-experts FFN inside an ordinary program; under
CompiledProgram the sharding planner places one expert group per dp rank
(the expert weights carry shard_spec=("dp", None, None)) and GSPMD routes
tokens between ranks — expert parallelism without writing any collective.

Run (a dry run on 8 virtual CPU devices; it never touches the chip):
  python examples/train_moe.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _bootstrap  # noqa: E402

_bootstrap.ensure_devices(8)

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, nets



def main():
    x = layers.data(name="x", shape=[8, 64], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="int64")
    h = layers.fc(x, 64, num_flatten_dims=2, act="relu")
    h, aux = nets.switch_moe(h, num_experts=8, d_ff=256,
                             capacity_factor=1.25, name="moe")
    h = layers.reduce_mean(h, dim=1)
    logits = layers.fc(h, 16)
    ce = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    # the switch load-balance aux loss keeps experts evenly used
    loss = layers.elementwise_add(ce, layers.scale(aux, scale=0.01))
    fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=loss.name)

    rng = np.random.RandomState(0)
    for step in range(20):
        feed = {"x": rng.randn(32, 8, 64).astype(np.float32),
                "y": rng.randint(0, 16, (32, 1)).astype(np.int64)}
        lv, av = exe.run(compiled, feed=feed, fetch_list=[loss, aux])
        if step % 5 == 0:
            print("step %2d  loss %.4f  aux %.4f" % (
                step, float(np.asarray(lv).mean()),
                float(np.asarray(av).mean())))

    import jax

    w1 = fluid.global_scope().get("moe_w1")
    if isinstance(w1, jax.Array):
        print("\nexpert weight shards per device:",
              sorted({s.data.shape for s in w1.addressable_shards}))


if __name__ == "__main__":
    main()
