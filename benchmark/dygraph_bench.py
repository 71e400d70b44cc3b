"""Eager vs TracedLayer vs static-graph step benchmark (the BASELINE.md
dygraph row). Methodology: device-resident input; every variant reduces
its output to a SCALAR in-graph, steps are dispatched back-to-back with
conversion DEFERRED past the timed loop (the flagship bench's async
cadence — per-step blocking fetches would measure the host round trip,
not the framework), and the median of 3 repeats is reported. What this row isolates is host-side dispatch cost: per-op
launches for eager, the executor path for static, the pre-bound plan
for traced."""

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np

import jax

import paddle_tpu as fluid
from paddle_tpu import dygraph, layers
from paddle_tpu.core import device as ptpu_device


def _median_time(fn, repeats=3):
    fn()  # warm (compile)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def measure(width, batch, steps):
    x_dev = jax.device_put(
        np.random.RandomState(0).randn(batch, width).astype(np.float32))

    with dygraph.guard():
        class M(dygraph.Layer):
            def __init__(self):
                super().__init__("m")
                self.l1 = dygraph.nn.Linear(width, width, act="relu")
                self.l2 = dygraph.nn.Linear(width, width, act="relu")
                self.l3 = dygraph.nn.Linear(width, width)

            def forward(self, v):
                out = self.l3(self.l2(self.l1(v)))
                from paddle_tpu.dygraph.nn import _trace
                return _trace("reduce_mean", {"X": [out]}, ["Out"],
                              {"dim": None, "keep_dim": False,
                               "reduce_all": True})["Out"][0]

        m = M()
        xv = dygraph.to_variable(x_dev)

        def run_eager():
            # inference comparison: no tape (recording every step's
            # intermediates would hold steps x activations in HBM)
            with dygraph.no_grad():
                outs = [m(xv).value for _ in range(steps)]
            import jax as _jax

            _jax.block_until_ready(outs)

        _, traced = dygraph.TracedLayer.trace(m, [xv])
        step_plan = None

        def run_traced():
            # defer conversion: drive the pre-bound step directly and
            # block once at the end (TracedLayer.__call__ itself returns
            # numpy, which would serialize a host round trip per step)
            nonlocal step_plan
            outs = []
            feed = {traced._feed_vars[0].name: x_dev}
            for _ in range(steps):
                traced._refresh_params()
                if step_plan is None:
                    traced([x_dev])
                    step_plan = next(iter(traced._steps.values()))
                outs.append(step_plan.run(traced._scope, feed)[0])
            import jax as _jax

            _jax.block_until_ready(outs)

        t_eager = _median_time(run_eager) / steps
        t_traced = _median_time(run_traced) / steps

    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    xs = layers.data(name="x", shape=[width], dtype="float32")
    h = layers.fc(xs, width, act="relu")
    h = layers.fc(h, width, act="relu")
    h = layers.fc(h, width)
    h = layers.reduce_mean(h)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    def run_static():
        outs = [exe.run(feed={"x": x_dev}, fetch_list=[h],
                        return_numpy=False)[0] for _ in range(steps)]
        jax.block_until_ready(outs)

    t_static = _median_time(run_static) / steps

    print("width=%d B=%d: eager %.0f | traced %.0f | static %.0f ex/s"
          "  (traced = %.2fx static)  [%s (%s) x%d]"
          % ((width, batch, batch / t_eager, batch / t_traced,
              batch / t_static, t_static / t_traced)
             + tuple(ptpu_device.identity())))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    ptpu_device.require_tpu("dygraph_bench.py")
    measure(1024, 1024, args.steps)
    measure(4096, 4096, max(args.steps // 2, 10))
