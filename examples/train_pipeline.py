"""Any-program PIPELINE parallelism through the descriptor path: the SAME
plain fluid.layers transformer trains on a dp x pp x tp mesh with a 1F1B
microbatch schedule — no model rewrite, just BuildStrategy knobs (plus
optional `with fluid.pipeline_stage(i):` placement; the default is a
FLOP-balanced auto-split of the forward section).

Under the hood (parallel/pipeline_program.py): stage bodies become
lax.switch branches selected by the pp rank, activations cross stage cuts
as packed wire buffers on a ppermute ring, stage gradients come from
jax.vjp of the lowered forwards, and the program's own optimizer ops run
on the accumulated gradients. Tensor parallelism (GSPMD, planner specs)
keeps working inside every stage body.

Run (a dry run on 8 virtual CPU devices; it never touches the chip):
  python examples/train_pipeline.py
"""

import _bootstrap

_bootstrap.ensure_devices(8)

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models import transformer_fluid


def main():
    # an ordinary fluid.layers transformer (recompute + flash attention +
    # chunked vocab head) — nothing pipeline-aware in the model code
    tokens, labels, loss = transformer_fluid.build(
        vocab_size=256, d_model=64, n_heads=4, n_layers=4, d_ff=128,
        seq_len=64, remat=True)
    fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    bs = fluid.BuildStrategy()
    bs.pipeline_stages = 2          # pp axis; forward auto-splits by FLOPs
    bs.pipeline_microbatches = 4    # 1F1B fill/drain depth
    bs.tensor_parallel_degree = 2   # composes: mesh = (dp=2, pp=2, tp=2)
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)

    rng = np.random.RandomState(0)
    B = 16  # must be a multiple of dp * pipeline_microbatches (= 8 here)
    for step in range(12):
        feed = {"tokens": rng.randint(0, 256, (B, 64)).astype(np.int32),
                "labels": rng.randint(0, 256, (B, 64)).astype(np.int32)}
        (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
        if step % 3 == 0:
            print("step %2d  loss %.4f" % (step,
                                           float(np.asarray(lv).mean())))

    step_obj = next(iter(compiled._compiled_steps.values()))
    sizes = [step_obj.stage_of.count(s) for s in range(step_obj.pp)]
    print("\nmesh:", dict(step_obj.mesh.shape),
          "| ops per stage:", sizes,
          "| activation vars crossing each cut:",
          [len(c) for c in step_obj.crossing])


if __name__ == "__main__":
    main()
