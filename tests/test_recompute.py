"""layers.recompute (remat segments) + the lean softmax_with_cross_entropy
custom vjp — the descriptor-path TPU knobs behind the Fluid-API transformer
(models/transformer_fluid.py; round-1 review item 1).

Parity anchor: the reference's later RecomputeOptimizer plays the remat
role on GPU; here segments lower onto jax.checkpoint through the
`recompute` op (ops/controlflow.py)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers


def _fixed_params():
    rng = np.random.RandomState(42)
    return {
        "rw1": (rng.randn(4, 8).astype(np.float32) * 0.3),
        "rb1": (rng.randn(8).astype(np.float32) * 0.1),
        "rw2": (rng.randn(8, 4).astype(np.float32) * 0.3),
        "rb2": (rng.randn(4).astype(np.float32) * 0.1),
    }


def _run(remat, steps=5):
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = layers.data(name="x", shape=[4], dtype="float32")

        def seg(h):
            h = layers.fc(h, 8, act="gelu",
                          param_attr=fluid.ParamAttr(name="rw1"),
                          bias_attr=fluid.ParamAttr(name="rb1"))
            return layers.fc(h, 4,
                             param_attr=fluid.ParamAttr(name="rw2"),
                             bias_attr=fluid.ParamAttr(name="rb2"))

        y = layers.recompute(seg, x) if remat else seg(x)
        loss = layers.mean(y)
        fluid.optimizer.SGD(0.5).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.core.scope.Scope()
    with fluid.scope_guard(sc):
        exe.run(sprog)
        for n, v in _fixed_params().items():
            sc.set(n, v.copy())
        feed = {"x": np.arange(8, dtype=np.float32).reshape(2, 4)}
        return [
            float(np.asarray(
                exe.run(prog, feed=feed, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(steps)
        ]


def test_recompute_training_matches_plain():
    """Same params, same feeds: the remat segment must reproduce the plain
    build's loss trajectory exactly (grads flow through jax.checkpoint)."""
    plain = _run(remat=False)
    remat = _run(remat=True)
    np.testing.assert_allclose(plain, remat, rtol=1e-5)
    assert plain[0] != plain[-1]  # actually trained


def test_recompute_rejects_inplace_outer_writes():
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = layers.data(name="x", shape=[4], dtype="float32")
        side = layers.fc(x, 4)

        def seg(h):
            layers.assign(h, side)  # writes an outer var in place
            return layers.fc(h, 4)

        with pytest.raises(ValueError, match="in place"):
            layers.recompute(seg, x)


def test_recompute_multi_output():
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = layers.data(name="x", shape=[4], dtype="float32")

        def seg(h):
            a = layers.fc(h, 4, param_attr=fluid.ParamAttr(name="mw1"))
            b = layers.fc(h, 3, param_attr=fluid.ParamAttr(name="mw2"))
            return a, b

        a, b = layers.recompute(seg, x)
        loss = layers.mean(a) + layers.mean(b)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.core.scope.Scope()
    with fluid.scope_guard(sc):
        exe.run(sprog)
        out_a, out_b = exe.run(
            prog, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[a, b])
    assert np.asarray(out_a).shape == (2, 4)
    assert np.asarray(out_b).shape == (2, 3)


def test_sce_custom_vjp_numeric_grad():
    """The memory-lean hard-label CE vjp (residual = logits, backward
    recomputes softmax) against a numeric gradient."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.loss_ops import _hard_label_ce

    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(3, 7), jnp.float32)
    lab = jnp.asarray(rng.randint(0, 7, (3,)), jnp.int32)

    def f(lg):
        return _hard_label_ce(lg, lab, -100).sum()

    g = jax.grad(f)(logits)
    eps = 1e-3
    for (i, j) in [(0, 2), (1, 5), (2, 0)]:
        lp = np.asarray(logits).copy()
        lp[i, j] += eps
        num = (float(f(jnp.asarray(lp))) - float(f(logits))) / eps
        assert abs(float(g[i, j]) - num) < 5e-3


def test_sce_ignore_index_masks_grad():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.loss_ops import _hard_label_ce

    logits = jnp.asarray(np.random.RandomState(1).randn(4, 5), jnp.float32)
    lab = jnp.asarray([1, -100, 3, -100], jnp.int32)

    loss = _hard_label_ce(logits, lab, -100)
    assert float(loss[1, 0]) == 0.0 and float(loss[3, 0]) == 0.0
    g = jax.grad(lambda lg: _hard_label_ce(lg, lab, -100).sum())(logits)
    assert np.allclose(np.asarray(g)[1], 0.0)
    assert np.allclose(np.asarray(g)[3], 0.0)
    assert not np.allclose(np.asarray(g)[0], 0.0)


def test_static_rnn_remat_matches_plain():
    """StaticRNN(remat=True) rematerializes the scan body in backward;
    the training trajectory must be identical to remat=False."""
    def run(remat):
        prog, sprog = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, sprog):
            x = layers.data(name="x", shape=[3, 4], dtype="float32")
            xt = layers.transpose(x, perm=[1, 0, 2])
            m0 = layers.scale(layers.squeeze(
                layers.slice(x, axes=[1], starts=[0], ends=[1]), axes=[1]),
                scale=0.0)
            rnn = layers.StaticRNN(remat=remat)
            with rnn.step():
                xi = rnn.step_input(xt)
                m = rnn.memory(init=m0)
                nm = layers.fc(layers.concat([xi, m], axis=1), 4,
                               act="tanh",
                               param_attr=fluid.ParamAttr(name="sr_w"),
                               bias_attr=False)
                rnn.update_memory(m, nm)
                rnn.step_output(nm)
            out = rnn()
            loss = layers.mean(out)
            fluid.optimizer.SGD(0.5).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.core.scope.Scope()
        with fluid.scope_guard(sc):
            exe.run(sprog)
            sc.set("sr_w", np.random.RandomState(7).randn(8, 4)
                   .astype(np.float32) * 0.3)
            feed = {"x": np.random.RandomState(1).rand(2, 3, 4)
                    .astype(np.float32)}
            return [float(np.asarray(exe.run(prog, feed=feed,
                    fetch_list=[loss])[0]).ravel()[0]) for _ in range(4)]

    np.testing.assert_allclose(run(False), run(True), rtol=1e-5)


def test_fluid_transformer_stacked_trains():
    """build_stacked: the layer stack as ONE StaticRNN(remat=True) over
    stacked per-layer weights (the native lax.scan structure through the
    Fluid API); loss must drop."""
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        toks, labs, loss = transformer_fluid.build_stacked(
            vocab_size=64, d_model=16, n_heads=2, n_layers=3, d_ff=32,
            seq_len=8, dtype="float32")
        fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.core.scope.Scope()
    with fluid.scope_guard(sc):
        exe.run(sprog)
        rng = np.random.RandomState(0)
        t = rng.randint(0, 64, (4, 8)).astype(np.int32)
        l = np.roll(t, -1, 1).astype(np.int32)
        losses = []
        for _ in range(12):
            out, = exe.run(prog, feed={"tokens": t, "labels": l},
                           fetch_list=[loss])
            losses.append(float(np.asarray(out).ravel()[0]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_fluid_transformer_tiny_trains_with_amp_and_remat():
    """End-to-end: the Fluid-API transformer (flagship architecture at toy
    scale) through AMP decorate + per-layer recompute; loss must drop."""
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        toks, labs, loss = transformer_fluid.build(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=32,
            seq_len=8, remat=True)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(1e-2), init_loss_scaling=1.0,
            use_dynamic_loss_scaling=False)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.core.scope.Scope()
    with fluid.scope_guard(sc):
        exe.run(sprog)
        rng = np.random.RandomState(0)
        t = rng.randint(0, 64, (4, 8)).astype(np.int32)
        l = np.roll(t, -1, 1).astype(np.int32)
        losses = []
        for _ in range(12):
            out, = exe.run(prog, feed={"tokens": t, "labels": l},
                           fetch_list=[loss])
            losses.append(float(np.asarray(out).ravel()[0]))
    assert losses[-1] < losses[0] - 0.3, losses
