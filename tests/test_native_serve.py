"""Python-free PJRT serving (native/serve.cc — round-4 VERDICT missing
#4; reference: analysis_predictor.cc:884 C++ deployment).

What CAN be verified in this image: the export side (raw per-platform
StableHLO modules + line manifest), the C++ npy/npz codec numerically
against numpy, and the PJRT plugin handshake (dlopen -> GetPjrtApi ->
version negotiation -> PJRT_Plugin_Initialize) against the real libtpu
plugin. What CANNOT: end-to-end execution — the test sandbox has no
chip and no PJRT CPU plugin .so ships in any wheel here (verified by
scanning every .so for GetPjrtApi), so client-create correctly reports
'no device'. On a TPU host (libtpu sees /dev/accel*) the same binary
is meant to run the artifact end to end; that has not been done yet
(ROADMAP D7).
"""

import os
import subprocess

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BIN = os.path.join(_ROOT, "native", "native_serve")
_LIBTPU = "/opt/venv/lib/python3.12/site-packages/libtpu/libtpu.so"


def _need_bin():
    # the binary is a build artifact (no longer committed): build it
    # from source so the tests can never exercise a stale ELF
    r = subprocess.run(["make", "-C", os.path.dirname(_BIN), "-s",
                        "native_serve"], capture_output=True, text=True)
    if r.returncode != 0 or not os.path.exists(_BIN):
        pytest.skip("native_serve build failed (make -C native "
                    "native_serve): %s" % (r.stderr or r.stdout)[-400:])


def test_npz_roundtrip_matches_numpy(tmp_path):
    """The C++ npy/npz codec round-trips numpy's own output bit-exactly
    across dtypes, ranks, and the empty-shape/1-tuple header cases."""
    _need_bin()
    rng = np.random.RandomState(0)
    arrays = {
        "f32": rng.randn(3, 4).astype(np.float32),
        "f64": rng.randn(5).astype(np.float64),
        "i64": rng.randint(-5, 5, (2, 2, 2)).astype(np.int64),
        "i32": rng.randint(0, 9, (7,)).astype(np.int32),
        "u8": rng.randint(0, 255, (4, 1)).astype(np.uint8),
        "pred": (rng.rand(6) > 0.5),
        "scalar": np.float32(3.25).reshape(()),
    }
    src = str(tmp_path / "in.npz")
    dst = str(tmp_path / "out.npz")
    np.savez(src, **arrays)
    rc = subprocess.run([_BIN, "--npz-roundtrip", src, dst],
                        capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    with np.load(dst) as got:
        assert sorted(got.files) == sorted(arrays)
        for k, v in arrays.items():
            np.testing.assert_array_equal(got[k], v)
            assert got[k].dtype == v.dtype


def test_pjrt_plugin_handshake():
    """dlopen -> GetPjrtApi -> cross-version negotiation (plugin 0.8x vs
    the vendored 0.72 header rides the struct_size convention) ->
    PJRT_Plugin_Initialize, against the REAL libtpu plugin."""
    _need_bin()
    if not os.path.exists(_LIBTPU):
        pytest.skip("no libtpu.so in image")
    rc = subprocess.run([_BIN, "--probe", "--plugin", _LIBTPU],
                        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stderr[-2000:]
    assert "probe ok" in rc.stderr
    assert "plugin api" in rc.stderr


def test_export_writes_native_artifact(tmp_path):
    """export_serving_model writes the Python-free companion: one RAW
    StableHLO bytecode module per platform (MLIR magic) + the line
    manifest in jax dict-flatten argument order."""
    import paddle_tpu as fluid
    from paddle_tpu import inference, layers

    x = layers.data(name="x", shape=[4])
    b = layers.data(name="a_second", shape=[4])
    y = layers.fc(input=fluid.layers.elementwise_add(x, b), size=3,
                  act="relu")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    model_dir = str(tmp_path / "m")
    fluid.io.save_inference_model(model_dir, ["x", "a_second"], [y], exe)
    pred = inference.create_paddle_predictor(
        inference.AnalysisConfig(model_dir))
    art = str(tmp_path / "art")
    inference.export_serving_model(art, pred,
                                   {"x": (2, 4), "a_second": (2, 4)},
                                   platforms=("cpu",))
    manifest = open(os.path.join(art, "__serving_native__.txt")).read()
    lines = manifest.strip().splitlines()
    assert lines[0] == "module cpu __serving__.cpu.mlirbc"
    # inputs listed in sorted (jax dict-flatten) order
    assert lines[1].startswith("input a_second <f4")
    assert lines[2].startswith("input x <f4")
    assert lines[3].startswith("output ")
    blob = open(os.path.join(art, "__serving__.cpu.mlirbc"), "rb").read()
    assert blob[:4] == b"ML\xefR" and len(blob) > 200  # MLIR bytecode


def test_full_serve_reaches_device_boundary(tmp_path):
    """The complete flow (manifest parse, module load, compile request)
    proceeds until PJRT client creation, which must fail with the
    no-local-TPU error — proving every layer of the binary up to the
    hardware boundary. On a TPU host this same invocation serves."""
    _need_bin()
    if not os.path.exists(_LIBTPU):
        pytest.skip("no libtpu.so in image")
    import paddle_tpu as fluid
    from paddle_tpu import inference, layers

    x = layers.data(name="x", shape=[4])
    y = layers.fc(input=x, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    model_dir = str(tmp_path / "m")
    fluid.io.save_inference_model(model_dir, ["x"], [y], exe)
    pred = inference.create_paddle_predictor(
        inference.AnalysisConfig(model_dir))
    art = str(tmp_path / "art")
    inference.export_serving_model(art, pred, {"x": (2, 4)},
                                   platforms=("cpu",))
    np.savez(str(tmp_path / "in.npz"),
             x=np.ones((2, 4), dtype=np.float32))
    rc = subprocess.run(
        [_BIN, "--artifact", art, "--input", str(tmp_path / "in.npz"),
         "--output", str(tmp_path / "out.npz"), "--plugin", _LIBTPU,
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=300)
    assert rc.returncode == 1
    assert "client create" in rc.stderr  # died AT the device boundary,
    # not in manifest/module/npz handling


def test_native_train_artifact_semantics(tmp_path):
    """export_native_train_step: the exported module's loop-carried
    semantics (state out -> state in, counter as a state slot) must
    reproduce the Executor's training trajectory EXACTLY — validated by
    deserializing the jax.export blob and iterating it the same way the
    C++ --train-loop does."""
    import jax
    from jax import export as jexport

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers

    x = layers.data(name="x", shape=[8])
    y = layers.data(name="y", shape=[1])
    pred = layers.fc(input=x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    xb = rng.randn(16, 8).astype(np.float32)
    yb = rng.randn(16, 1).astype(np.float32)

    from paddle_tpu.core import scope as scope_mod

    sc = scope_mod.global_scope()
    init = {n: np.asarray(sc.get(n)).copy() for n in sc.local_var_names()
            if sc.get(n) is not None and not n.startswith("__")}

    golden = []
    for _ in range(4):
        (lv,) = exe.run(fluid.default_main_program(),
                        feed={"x": xb, "y": yb}, fetch_list=[loss])
        golden.append(float(np.asarray(lv).reshape(-1)[0]))
    for n, v in init.items():
        sc.set(n, v.copy())
    sc.set("__step_counter__", 0)

    art = str(tmp_path / "train_art")
    state_names = inference.export_native_train_step(
        art, fluid.default_main_program(), {"x": (16, 8), "y": (16, 1)},
        fetch_names=[loss.name], platforms=("cpu",))
    manifest = open(os.path.join(art, "__train_native__.txt")).read()
    assert "module cpu __train__.cpu.mlirbc" in manifest
    blob = open(os.path.join(art, "__train__.cpu.mlirbc"), "rb").read()
    assert blob[:4] == b"ML\xefR"

    with open(os.path.join(art, "__train__.jaxexport"), "rb") as f:
        exported = jexport.deserialize(bytearray(f.read()))
    with np.load(os.path.join(art, "state0.npz")) as data:
        state = [data[n] for n in state_names]
    counter = np.uint32(0)
    feeds = [xb, yb]  # sorted feed names: x < y
    losses = []
    for _ in range(4):  # exactly what the C++ loop does
        outs = exported.call(*state, counter, *feeds)
        k = len(state)
        state, counter = list(outs[:k]), outs[k]
        losses.append(float(np.asarray(outs[k + 1]).reshape(-1)[0]))
    np.testing.assert_allclose(losses, golden, rtol=1e-6, atol=1e-7)


def test_native_train_loop_reaches_device_boundary(tmp_path):
    """--train-loop proceeds through manifest/module/state/npz handling
    to PJRT client creation (no local chip here; on a TPU host the same
    invocation trains)."""
    _need_bin()
    if not os.path.exists(_LIBTPU):
        pytest.skip("no libtpu.so in image")
    import paddle_tpu as fluid
    from paddle_tpu import inference, layers

    x = layers.data(name="x", shape=[4])
    y = layers.data(name="y", shape=[1])
    loss = layers.mean(layers.square_error_cost(
        layers.fc(input=x, size=1), y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    art = str(tmp_path / "art")
    inference.export_native_train_step(
        art, fluid.default_main_program(), {"x": (8, 4), "y": (8, 1)},
        fetch_names=[loss.name], platforms=("cpu",))
    np.savez(str(tmp_path / "in.npz"),
             x=np.ones((8, 4), np.float32), y=np.ones((8, 1), np.float32))
    rc = subprocess.run(
        [_BIN, "--artifact", art, "--train-loop", "3",
         "--input", str(tmp_path / "in.npz"),
         "--output", str(tmp_path / "out.npz"), "--plugin", _LIBTPU,
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=300)
    assert rc.returncode == 1
    assert "client create" in rc.stderr


def test_train_loop_stats_selftest(tmp_path):
    """The step-latency stats accumulator behind --metrics-out (profiler.cc
    shared with the train loop) records and dumps JSON without needing a
    PJRT device; the schema is the one tools/ptpu_stats.py renders."""
    _need_bin()
    import json

    out = str(tmp_path / "stats.json")
    rc = subprocess.run([_BIN, "--stats-selftest", out],
                        capture_output=True, text=True, timeout=60)
    assert rc.returncode == 0, rc.stderr
    with open(out) as f:
        doc = json.load(f)
    s = doc["stats"]["train_loop/step_time_us"]
    assert s["count"] == 3
    assert s["min"] == 80.0 and s["max"] == 120.0
    assert abs(s["avg"] - 100.0) < 1e-9
    import sys

    cli = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "ptpu_stats.py"), out],
        capture_output=True, text=True, timeout=120)
    assert cli.returncode == 0, cli.stderr
    assert "train_loop/step_time_us" in cli.stdout
