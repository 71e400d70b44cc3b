"""Dygraph (imperative) test tier (parity: tests/unittests/
test_imperative_*.py — eager training loops with fluid.optimizer.minimize,
eager-vs-static equivalence, and state_dict checkpointing)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import dygraph


class SmallConvNet(dygraph.Layer):
    def __init__(self):
        super().__init__("convnet")
        self.conv = dygraph.Conv2D("c", num_filters=4, filter_size=3,
                                   padding=1)
        self.pool = dygraph.Pool2D(pool_size=2, pool_type="max",
                                   pool_stride=2)
        self.fc = dygraph.Linear(4 * 4 * 4, 10)
        self.add_sublayer("conv", self.conv)
        self.add_sublayer("pool", self.pool)
        self.add_sublayer("fc", self.fc)

    def forward(self, x):
        h = self.conv(x)
        h = self.pool(h)
        # flatten via the traced reshape op so grads flow through the tape
        t = fluid.dygraph.base._current_tracer()
        flat = t.trace_op("reshape2", {"X": [h]}, ["Out", "XShape"],
                          {"shape": [0, -1]})["Out"][0]
        return self.fc(flat)


@pytest.mark.parametrize("opt_name", ["SGD", "Momentum", "Adam", "Adagrad",
                                      "RMSProp"])
def test_imperative_training_loss_decreases(opt_name):
    rng = np.random.RandomState(0)
    xs = rng.rand(16, 1, 8, 8).astype(np.float32)
    ys = (xs.mean(axis=(1, 2, 3)) * 10).astype(np.int64) % 10

    with dygraph.guard():
        net = SmallConvNet()
        kwargs = {"learning_rate": 0.05}
        if opt_name == "Momentum":
            kwargs["momentum"] = 0.9
        opt = getattr(fluid.optimizer, opt_name)(**kwargs)
        losses = []
        for step in range(10):
            logits = net(dygraph.to_variable(xs))
            t = fluid.dygraph.base._current_tracer()
            loss = t.trace_op(
                "softmax_with_cross_entropy",
                {"Logits": [logits],
                 "Label": [dygraph.to_variable(ys[:, None])]},
                ["Loss"], {})["Loss"][0]
            avg = t.trace_op("mean", {"X": [loss]}, ["Out"], {})["Out"][0]
            avg.backward()
            opt.minimize(avg)
            net.clear_gradients()
            losses.append(float(np.asarray(avg.value).reshape(-1)[0]))
        assert losses[-1] < losses[0], (opt_name, losses)


def test_imperative_matches_static_forward():
    """Same weights -> same forward output in eager and static modes
    (reference pattern: test_imperative_resnet.py comparisons)."""
    rng = np.random.RandomState(1)
    x = rng.rand(4, 6).astype(np.float32)

    with dygraph.guard():
        lin = dygraph.Linear(6, 3)
        eager_out = np.asarray(lin(dygraph.to_variable(x)).value)
        w = np.asarray(lin._w.value)
        b = np.asarray(lin._b.value)

    xv = fluid.layers.data(name="x", shape=[6], dtype="float32")
    out = fluid.layers.fc(input=xv, size=3,
                          param_attr=fluid.ParamAttr(name="sw"),
                          bias_attr=fluid.ParamAttr(name="sb"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    from paddle_tpu.core.scope import global_scope

    global_scope().set("sw", w)
    global_scope().set("sb", b)
    static_out, = exe.run(feed={"x": x}, fetch_list=[out])
    np.testing.assert_allclose(eager_out, np.asarray(static_out),
                               rtol=1e-5, atol=1e-6)


def test_imperative_state_dict_roundtrip(tmp_path):
    with dygraph.guard():
        net = SmallConvNet()
        x = np.random.RandomState(2).rand(2, 1, 8, 8).astype(np.float32)
        net(dygraph.to_variable(x))  # materialize lazy params
        state = net.state_dict()
        path = str(tmp_path / "model")
        dygraph.save_dygraph(state, path)

        net2 = SmallConvNet()
        net2(dygraph.to_variable(x))
        loaded, _ = dygraph.load_dygraph(path)
        net2.set_dict(loaded)
        o1 = np.asarray(net(dygraph.to_variable(x)).value)
        o2 = np.asarray(net2(dygraph.to_variable(x)).value)
        np.testing.assert_allclose(o1, o2, rtol=1e-6)


def test_imperative_weight_decay_applied():
    """regularization= must decay weights in dygraph too (the static path
    adds decay ops; the eager path folds decay into the gradient)."""
    x = np.ones((2, 4), np.float32)
    with dygraph.guard():
        def run(reg):
            lin = dygraph.Linear(4, 3)
            w0 = np.asarray(lin._w.value).copy()
            opt = fluid.optimizer.SGD(learning_rate=0.1, regularization=reg)
            out = lin(dygraph.to_variable(x))
            t = fluid.dygraph.base._current_tracer()
            loss = t.trace_op("mean", {"X": [out]}, ["Out"], {})["Out"][0]
            loss.backward()
            opt.minimize(loss, parameter_list=[lin._w, lin._b])
            return w0, np.asarray(lin._w.value)

        from paddle_tpu.regularizer import L2Decay

        w0_plain, w1_plain = run(None)
        w0_reg, w1_reg = run(L2Decay(0.5))
        # same loss-gradient (weights differ per-instance, so compare the
        # update DELTA): with decay the step includes -lr*coeff*w extra
        delta_plain = w1_plain - w0_plain
        delta_reg = w1_reg - w0_reg
        expected_extra = -0.1 * 0.5 * w0_reg
        np.testing.assert_allclose(delta_reg - delta_plain, expected_extra,
                                   rtol=1e-4, atol=1e-6)


def test_traced_layer_matches_eager_and_serves(tmp_path):
    """TracedLayer captures an eager forward into a Program: outputs match
    eager on the trace batch AND a fresh batch, the Program runs as one
    executor step, and save_inference_model produces a loadable artifact
    with identical predictions (round-3 review, dygraph-to-jit item)."""
    rng = np.random.RandomState(0)
    x1 = rng.rand(4, 1, 8, 8).astype(np.float32)
    x2 = rng.rand(4, 1, 8, 8).astype(np.float32)
    with dygraph.guard():
        model = SmallConvNet()
        model.eval()
        out_eager, traced = dygraph.TracedLayer.trace(
            model, [dygraph.to_variable(x1)])
        # ops are in the program; one fc, one conv
        types = [op.type for op in traced.program.global_block().ops]
        assert "conv2d" in types and ("mul" in types or "matmul" in types)
        got1, = traced([x1])
        np.testing.assert_allclose(np.asarray(got1), out_eager.numpy(),
                                   rtol=1e-5, atol=1e-6)
        # fresh batch: traced program == eager module
        eager2 = model(dygraph.to_variable(x2)).numpy()
        got2, = traced([x2])
        np.testing.assert_allclose(np.asarray(got2), eager2, rtol=1e-5,
                                   atol=1e-6)
        traced.save_inference_model(str(tmp_path / "traced_sd"))

    # load the artifact the standard static way, outside dygraph
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        prog, feeds, fetches = fluid.io.load_inference_model(
            str(tmp_path / "traced_sd"), exe)
        pred, = exe.run(prog, feed={feeds[0]: x2}, fetch_list=fetches)
    np.testing.assert_allclose(np.asarray(pred), eager2, rtol=1e-5,
                               atol=1e-6)


def test_traced_layer_requires_guard_and_varbase():
    with pytest.raises(RuntimeError, match="dygraph.guard"):
        dygraph.TracedLayer.trace(lambda x: x, [np.zeros(3)])
    with dygraph.guard():
        model = SmallConvNet()
        with pytest.raises(TypeError, match="VarBase"):
            dygraph.TracedLayer.trace(
                model, [np.zeros((1, 1, 8, 8), np.float32)])


def test_traced_layer_tracks_continued_eager_training():
    """The traced program SHARES the dygraph parameter storage (reference
    TracedLayer semantics; round-4 advisor): eager updates to the layer
    after tracing are visible to later traced calls, not frozen at the
    trace-time snapshot."""
    rng = np.random.RandomState(3)
    x = rng.rand(4, 1, 8, 8).astype(np.float32)
    with dygraph.guard():
        model = SmallConvNet()
        model.eval()
        _, traced = dygraph.TracedLayer.trace(
            model, [dygraph.to_variable(x)])
        before, = traced([x])
        # continued "training": shift every parameter in place
        for p in model.parameters():
            p.set_value(p.numpy() + 0.05)
        eager_after = model(dygraph.to_variable(x)).numpy()
        after, = traced([x])
    assert not np.allclose(np.asarray(after), np.asarray(before))
    np.testing.assert_allclose(np.asarray(after), eager_after,
                               rtol=1e-5, atol=1e-6)
