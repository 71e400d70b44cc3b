"""Inference-engine tests (parity: inference/api tests — load, optimize,
repeated run, isolated scope; SURVEY §3.5 call stack)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference import (AnalysisConfig, PaddleTensor,
                                  create_paddle_predictor)


def _export_model(tmp_path):
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    y = fluid.layers.fc(input=h, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [y], exe)
    # reference output for parity check
    xd = np.random.RandomState(0).rand(4, 8).astype(np.float32)
    want, = exe.run(fluid.default_main_program(), feed={"x": xd},
                    fetch_list=[y])
    return d, xd, want


def test_predictor_runs_and_matches_training_graph(tmp_path):
    d, xd, want = _export_model(tmp_path)
    cfg = AnalysisConfig(d)
    cfg.disable_gpu()
    pred = create_paddle_predictor(cfg)
    assert pred.get_input_names() == ["x"]
    outs = pred.run([PaddleTensor(xd, name="x")])
    np.testing.assert_allclose(outs[0].as_ndarray(), want, rtol=1e-5,
                               atol=1e-6)
    # repeated run, same executable (program cache path)
    outs2 = pred.run([PaddleTensor(xd)])
    np.testing.assert_allclose(outs2[0].as_ndarray(), want, rtol=1e-5,
                               atol=1e-6)


def test_predictor_aot_warmup(tmp_path):
    d, xd, want = _export_model(tmp_path)
    cfg = AnalysisConfig(d)
    cfg.disable_gpu()
    cfg.set_aot_shapes({"x": (4, 8)})
    pred = create_paddle_predictor(cfg)
    outs = pred.run([PaddleTensor(xd, name="x")])
    np.testing.assert_allclose(outs[0].as_ndarray(), want, rtol=1e-5,
                               atol=1e-6)


def test_predictor_scope_isolated(tmp_path):
    d, xd, _ = _export_model(tmp_path)
    cfg = AnalysisConfig(d)
    cfg.disable_gpu()
    pred = create_paddle_predictor(cfg)
    # global scope must not see the predictor's params
    pnames = [v.name for v in pred._program.global_block().all_parameters()]
    global_vals = [fluid.global_scope().get(n) for n in pnames]
    # predictor works regardless of global scope contents
    pred.run([PaddleTensor(xd)])
    assert pred._scope.get(pnames[0]) is not None


def test_export_and_serve_stablehlo_artifact(tmp_path):
    """AOT serving: export a StableHLO artifact with baked-in weights and
    serve it from a FRESH process with no program/op-registry involvement
    (jax.export parity with TRT engine files, SURVEY §7 design mapping)."""
    import json
    import os
    import subprocess
    import sys

    from paddle_tpu.inference import export_serving_model, load_serving_model

    d, xd, want = _export_model(tmp_path)
    cfg = AnalysisConfig(d)
    cfg.disable_gpu()
    pred = create_paddle_predictor(cfg)
    path = export_serving_model(d, pred, {"x": (4, 8)})
    assert os.path.exists(path)

    # same-process load + run matches the training graph
    sp = load_serving_model(d)
    assert sp.get_input_names() == ["x"]
    outs = sp.run([PaddleTensor(xd, name="x")])
    np.testing.assert_allclose(outs[0].as_ndarray(), want, rtol=1e-5,
                               atol=1e-6)

    # fresh-process serve: only the artifact + numpy + jax are touched
    script = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from paddle_tpu.inference import load_serving_model\n"
        "sp = load_serving_model(%r)\n"
        "x = np.array(json.loads(sys.argv[1]), np.float32)\n"
        "out = sp.run_dict({'x': x})[0]\n"
        "print(json.dumps(np.asarray(out).tolist()))\n"
        % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))), d))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script, json.dumps(xd.tolist())],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    got = np.array(json.loads(r.stdout.strip().splitlines()[-1]), np.float32)
    # a fresh-process serving check, not bit-exactness
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)


def test_batch_norm_inference_through_save_predict_serve(tmp_path):
    """BN must use running stats (not batch stats) identically across
    clone(for_test), AnalysisPredictor, the StableHLO serving artifact,
    and any batch size."""
    x = fluid.layers.data(name="x", shape=[3, 8, 8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.conv2d(input=x, num_filters=4, filter_size=3,
                            padding=1)
    h = fluid.layers.batch_norm(input=h, act="relu")
    pool = fluid.layers.pool2d(input=h, global_pooling=True,
                               pool_type="avg")
    pred = fluid.layers.fc(input=pool, size=3, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(input=pred,
                                                        label=y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    for _ in range(5):
        exe.run(feed={"x": rng.rand(8, 3, 8, 8).astype(np.float32) * 3,
                      "y": rng.randint(0, 3, (8, 1)).astype(np.int64)},
                fetch_list=[loss])

    xd = rng.rand(4, 3, 8, 8).astype(np.float32)
    test_prog = fluid.default_main_program().clone(for_test=True)
    want, = exe.run(test_prog,
                    feed={"x": xd, "y": np.zeros((4, 1), np.int64)},
                    fetch_list=[pred])
    want = np.asarray(want)

    d = str(tmp_path / "bn_model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)

    from paddle_tpu.inference import (export_serving_model,
                                      load_serving_model)

    cfg = AnalysisConfig(d)
    cfg.disable_gpu()
    p = create_paddle_predictor(cfg)
    got = p.run([PaddleTensor(xd, name="x")])[0].as_ndarray()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    export_serving_model(d, p, {"x": (4, 3, 8, 8)})
    sp = load_serving_model(d)
    got2 = np.asarray(sp.run_dict({"x": xd})[0])
    np.testing.assert_allclose(got2, want, rtol=1e-4, atol=1e-5)

    # batch-size independence: a single sample equals its batch-run row
    got3 = p.run([PaddleTensor(xd[:1], name="x")])[0].as_ndarray()
    np.testing.assert_allclose(got3[0], want[0], rtol=1e-4, atol=1e-5)
