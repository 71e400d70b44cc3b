"""Any-program SEQUENCE parallelism: a long-context fluid.layers model
whose self-attention runs as RING attention over the `sp` mesh axis —
K/V blocks rotate between chips via ppermute while each chip accumulates
its query shard with the online-softmax recurrence, so the [T, T] score
matrix never exists on any chip and per-chip activation memory is
O(T/sp). Just a BuildStrategy knob on an ordinary model (SURVEY §5.7's
scale-sequence-length axis; `ops/compat_ops.py flash_attention` routes
onto `parallel/ring_attention.py` when the mesh has an sp axis).

Run (a dry run on 8 virtual CPU devices; it never touches the chip):
  python examples/train_long_context.py
"""

import _bootstrap

_bootstrap.ensure_devices(8)

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models import transformer_fluid


def main():
    SEQ = 1024  # long context; feeds shard batch x seq over (dp, sp)
    tokens, labels, loss = transformer_fluid.build(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        seq_len=SEQ, remat=True)
    fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    bs = fluid.BuildStrategy()
    bs.sequence_parallel_degree = 2   # mesh = (dp=4, sp=2)
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)

    rng = np.random.RandomState(0)
    for step in range(8):
        feed = {"tokens": rng.randint(0, 256, (8, SEQ)).astype(np.int32),
                "labels": rng.randint(0, 256, (8, SEQ)).astype(np.int32)}
        (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
        if step % 2 == 0:
            print("step %2d  loss %.4f" % (step,
                                           float(np.asarray(lv).mean())))
    step_obj = next(iter(compiled._compiled_steps.values()))
    print("\nmesh:", dict(step_obj.mesh.shape),
          "(ring attention engaged on the sp axis)")


if __name__ == "__main__":
    main()
