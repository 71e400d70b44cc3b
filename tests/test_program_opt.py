"""Compile-time program-optimization pipeline (docs/COMPILER_PASSES.md):
per-pass equivalence against the PTPU_NO_PROGRAM_OPT=1 lowering path
(bitwise — the passes change what is traced, never the math), fetch-dead
branches vanishing from the lowered module text, constant folding baking
scope parameters, BuildStrategy knob honoring (fuse_elewise_add_act_ops,
enable_inplace donation policy incl. write-before-read promotion), and
the opt-out restoring the exact pre-pipeline identity."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import ir, layers, unique_name
from paddle_tpu.compiler import classify_persistable_state
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.ir_passes import InplaceInfo


def _fresh_scope():
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    return scope_mod.global_scope()


def _reset_build_state():
    """Two builds of the same model must be IDENTICAL (names, init
    seeds) for the bitwise equivalence runs: reset the global name and
    op-seed counters the layer stack draws from."""
    from paddle_tpu import initializer, layer_helper

    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    unique_name.switch()
    initializer._global_seed_counter[0] = 0
    layer_helper._op_seed_counter[0] = 0
    return _fresh_scope()


def _run_both(monkeypatch, build, feed, steps=1):
    """Run `build()`'s program optimized and under PTPU_NO_PROGRAM_OPT=1
    (fresh scope + startup each, same seeds) and return the optimized
    trajectory plus the optimized compiled-step program."""
    results = []
    opt_programs = []
    for noopt in (False, True):
        if noopt:
            monkeypatch.setenv("PTPU_NO_PROGRAM_OPT", "1")
        else:
            monkeypatch.delenv("PTPU_NO_PROGRAM_OPT", raising=False)
        _reset_build_state()
        fetch_var = build()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        traj = []
        for _ in range(steps):
            out, = exe.run(feed=feed(), fetch_list=[fetch_var])
            traj.append(np.asarray(out))
        results.append(traj)
        if not noopt:
            # skip the startup program's cached step (empty fetch list)
            opt_programs.extend(s.program for s in exe._cache.values()
                                if s.fetch_names)
    monkeypatch.delenv("PTPU_NO_PROGRAM_OPT", raising=False)
    opt, unopt = results
    for a, b in zip(opt, unopt):
        assert a.dtype == b.dtype and np.array_equal(a, b), (a, b)
    return opt, opt_programs


# ---------------------------------------------------------------------------
# fetch-driven DCE
# ---------------------------------------------------------------------------


def test_dce_removes_fetch_dead_branch_bitwise(monkeypatch):
    def build():
        x = layers.data(name="dc_x", shape=[5], dtype="float32")
        live = layers.reduce_sum(layers.relu(x))
        # fetch-unreachable branch with a distinctively-shaped weight
        dead = layers.fc(input=x, size=41)
        layers.tanh(dead)
        return live

    def feed():
        return {"dc_x": np.arange(20, dtype=np.float32).reshape(4, 5)}

    _, progs = _run_both(monkeypatch, build, feed)
    (prog,) = progs
    types = [op.type for op in prog.global_block().ops]
    assert "tanh" not in types and "mul" not in types, types


def test_dce_branch_vanishes_from_lowered_module_text(monkeypatch):
    """The receipt the issue asks for: the fetch-dead branch's ops are
    absent from the optimized step's StableHLO, present in the
    PTPU_NO_PROGRAM_OPT=1 step's. FLAGS_check_nan_inf keeps every op
    output alive through jax's own jaxpr-level DCE (each contributes an
    isfinite flag to the step's returns), so the module text shows
    exactly what program-level DCE removed BEFORE tracing."""
    fluid.flags.set_flags({"check_nan_inf": True})
    try:
        texts = {}
        for noopt in (False, True):
            if noopt:
                monkeypatch.setenv("PTPU_NO_PROGRAM_OPT", "1")
            else:
                monkeypatch.delenv("PTPU_NO_PROGRAM_OPT", raising=False)
            scope = _reset_build_state()
            x = layers.data(name="mt_x", shape=[5], dtype="float32")
            out = layers.reduce_sum(layers.relu(x))
            dead = layers.fc(input=x, size=41)  # weight [5,41], fetch-dead
            layers.tanh(dead)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            feed = {"mt_x": np.ones((4, 5), np.float32)}
            exe.run(feed=feed, fetch_list=[out])
            (step,) = [s for s in exe._cache.values() if s.fetch_names]
            mut = {n: scope.get(n) for n in step.mut_names}
            const = {n: scope.get(n) for n in step.const_names}
            texts[noopt] = step._jitted.lower(
                mut, const, feed, np.uint32(0)).as_text()
    finally:
        fluid.flags.set_flags({"check_nan_inf": False})
        monkeypatch.delenv("PTPU_NO_PROGRAM_OPT", raising=False)
    assert "5x41" in texts[True]       # the dead fc weight is traced
    assert "5x41" not in texts[False]  # ...and eliminated by fetch_dce
    assert "tanh" in texts[True] and "tanh" not in texts[False]


# ---------------------------------------------------------------------------
# CSE
# ---------------------------------------------------------------------------


def test_cse_dedups_duplicate_subgraph_bitwise(monkeypatch):
    def build():
        x = layers.data(name="cs_x", shape=[6], dtype="float32")
        a = layers.sigmoid(layers.scale(x, scale=1.7))
        b = layers.sigmoid(layers.scale(x, scale=1.7))  # duplicate chain
        return layers.reduce_sum(layers.elementwise_add(a, b))

    def feed():
        rng = np.random.RandomState(7)
        return {"cs_x": rng.randn(3, 6).astype(np.float32)}

    _, progs = _run_both(monkeypatch, build, feed)
    (prog,) = progs
    types = [op.type for op in prog.global_block().ops]
    assert types.count("sigmoid") == 1 and types.count("scale") == 1, types


def test_cse_skips_rebound_kept_output():
    """If the FIRST occurrence's output name is later rebound in place,
    the duplicate must NOT be eliminated — rewired readers would observe
    the rebound value, not the common subexpression."""
    x = layers.data(name="rb_x", shape=[4], dtype="float32")
    a = layers.scale(x, scale=2.0)          # kept candidate: A = 2x
    layers.assign(layers.scale(x, scale=9.0), output=a)  # rebinds A = 9x
    b = layers.scale(x, scale=2.0)          # duplicate of the kept op
    out = layers.reduce_sum(b)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res, = exe.run(feed={"rb_x": np.ones((1, 4), np.float32)},
                   fetch_list=[out])
    assert np.asarray(res).item() == pytest.approx(8.0)  # 2x, never 9x


def test_cse_keeps_fetched_and_multiply_written_vars(monkeypatch):
    """A duplicate whose output is itself fetched must survive."""
    def build():
        x = layers.data(name="cp_x", shape=[4], dtype="float32")
        a = layers.scale(x, scale=2.0)
        build.aux = layers.scale(x, scale=2.0)  # duplicate, but fetched
        return layers.reduce_sum(layers.elementwise_add(a, build.aux))

    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    _fresh_scope()
    out = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"cp_x": np.ones((2, 4), np.float32)}
    o, aux = exe.run(feed=feed, fetch_list=[out, build.aux])
    assert np.asarray(o).item() == pytest.approx(32.0)
    assert np.asarray(aux).shape == (2, 4)


# ---------------------------------------------------------------------------
# constant folding
# ---------------------------------------------------------------------------


def test_constant_fold_inlines_small_consts_bitwise(monkeypatch):
    def build():
        x = layers.data(name="cf_x", shape=[3], dtype="float32")
        c = layers.fill_constant([3], "float32", 1.5)
        c = layers.scale(c, scale=0.5)
        c = layers.elementwise_add(c, layers.fill_constant(
            [3], "float32", 0.25))  # const subgraph: 1.5*0.5 + 0.25 = 1.0
        return layers.reduce_sum(layers.elementwise_add(x, c))

    def feed():
        return {"cf_x": np.full((2, 3), 2.0, np.float32)}

    _run_both(monkeypatch, build, feed)
    # re-run structurally to inspect the folded program
    _reset_build_state()
    out = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res, = exe.run(feed=feed(), fetch_list=[out])
    assert np.asarray(res).item() == pytest.approx(18.0)  # 2*3*(2+1)
    (step,) = [s for s in exe._cache.values() if s.fetch_names]
    types = [op.type for op in step.program.global_block().ops]
    # the whole const chain collapsed into one inline assign_value
    assert "fill_constant" not in types and "scale" not in types, types
    assert types.count("assign_value") == 1
    av = [op for op in step.program.global_block().ops
          if op.type == "assign_value"][0]
    np.testing.assert_array_equal(np.asarray(av.attrs["values"]),
                                  np.ones(3, np.float32))
    # the user's original program is untouched
    orig_types = [op.type
                  for op in fluid.default_main_program().global_block().ops]
    assert orig_types.count("fill_constant") == 2


def test_constant_fold_bakes_large_consts_as_scope_params(monkeypatch):
    """Above the inline threshold the folded value becomes an
    initialized persistable parameter (content-addressed scope entry),
    keeping big constants out of the StableHLO module."""
    def build():
        x = layers.data(name="cb_x", shape=[70000], dtype="float32")
        c = layers.fill_constant([70000], "float32", 2.0)
        c = layers.scale(c, scale=0.5)   # 70000 elems > inline threshold
        return layers.reduce_sum(layers.elementwise_add(x, c))

    def feed():
        return {"cb_x": np.full((1, 70000), 3.0, np.float32)}

    _run_both(monkeypatch, build, feed)
    scope = _reset_build_state()
    out = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res, = exe.run(feed=feed(), fetch_list=[out])
    assert np.asarray(res).item() == pytest.approx(4.0 * 70000)
    (step,) = [s for s in exe._cache.values() if s.fetch_names]
    types = [op.type for op in step.program.global_block().ops]
    assert "fill_constant" not in types and "scale" not in types, types
    baked = [n for n in step.program.global_block().vars
             if n.startswith("__folded__.")]
    assert baked, "no baked const param"
    for n in baked:
        val = np.asarray(scope.get(n))
        assert val.shape == (70000,) and val[0] == 1.0
    # baked params ride in as read-only state, not module constants
    assert set(baked) <= set(step.const_names)


# ---------------------------------------------------------------------------
# elementwise_add + activation fusion (BuildStrategy knob)
# ---------------------------------------------------------------------------


def test_fuse_elewise_add_act_knob_bitwise():
    x = layers.data(name="fu_x", shape=[16], dtype="float32")
    h = layers.fc(input=x, size=32, act="relu")  # bias add + relu
    out = layers.reduce_mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"fu_x": np.random.RandomState(3).randn(8, 16).astype(np.float32)}

    prog = fluid.default_main_program()
    results = {}
    steps = {}
    for knob in (False, True):
        bs = fluid.compiler.BuildStrategy()
        bs.fuse_elewise_add_act_ops = knob
        cp = fluid.compiler.CompiledProgram(prog).with_data_parallel(
            build_strategy=bs)
        r, = exe.run(cp, feed=feed, fetch_list=[out])
        results[knob] = np.asarray(r)
        (steps[knob],) = cp._compiled_steps.values()

    assert np.array_equal(results[False], results[True])
    types = [op.type
             for op in steps[True].program.global_block().ops]
    assert "fused_elemwise_activation" in types, types
    assert "relu" not in types
    assert "fused_elemwise_activation" not in [
        op.type for op in steps[False].program.global_block().ops]


def test_fusion_skips_grad_referenced_ops():
    """In a train program the forward add/act are re-run by their grad
    ops — fusing them would orphan the __fwd_op__ references, so the
    pass must leave them."""
    x = layers.data(name="fg_x", shape=[8], dtype="float32")
    h = layers.fc(input=x, size=4, act="relu")
    loss = layers.reduce_mean(h)
    fluid.optimizer.SGD(0.1).minimize(loss)
    prog = fluid.default_main_program()

    bs = fluid.compiler.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    cp = fluid.compiler.CompiledProgram(prog).with_data_parallel(
        loss_name=loss.name, build_strategy=bs)
    feed = {"fg_x": np.ones((8, 8), np.float32)}
    l0, = exe.run(cp, feed=feed, fetch_list=[loss])
    l1, = exe.run(cp, feed=feed, fetch_list=[loss])
    assert float(np.asarray(l1).ravel()[0]) < \
        float(np.asarray(l0).ravel()[0])  # still trains
    (step,) = cp._compiled_steps.values()
    types = [op.type for op in step.program.global_block().ops]
    assert "fused_elemwise_activation" not in types


# ---------------------------------------------------------------------------
# enable_inplace: donation policy (the donation-sensitive equivalence)
# ---------------------------------------------------------------------------


def _train_once(enable_inplace, steps=4):
    _reset_build_state()
    x = layers.data(name="ip_x", shape=[8], dtype="float32")
    y = layers.data(name="ip_y", shape=[1], dtype="float32")
    pred = layers.fc(input=layers.fc(input=x, size=16, act="relu"), size=1)
    loss = layers.reduce_mean(layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    prog = fluid.default_main_program()
    prog.random_seed = 11
    fluid.default_startup_program().random_seed = 11
    bs = fluid.compiler.BuildStrategy()
    bs.enable_inplace = enable_inplace
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    cp = fluid.compiler.CompiledProgram(prog).with_data_parallel(
        loss_name=loss.name, build_strategy=bs)
    rng = np.random.RandomState(5)
    xs = rng.randn(8, 8).astype(np.float32)
    ys = rng.randn(8, 1).astype(np.float32)
    traj = []
    for _ in range(steps):
        lv, = exe.run(cp, feed={"ip_x": xs, "ip_y": ys}, fetch_list=[loss])
        traj.append(np.asarray(lv).copy())
    (step,) = cp._compiled_steps.values()
    return traj, step


def test_enable_inplace_donation_sensitive_equivalence():
    on_traj, on_step = _train_once(True)
    off_traj, off_step = _train_once(False)
    for a, b in zip(on_traj, off_traj):
        assert np.array_equal(a, b), (a, b)
    # the knob is real: inplace off moves every read+written persistable
    # out of the donated set; on keeps them donated
    assert on_step.mut_names and not off_step.mut_names
    assert sorted(on_step.state_out) == sorted(off_step.state_out)
    assert set(on_step.mut_names) <= set(off_step.const_names)


def test_write_before_read_promotion_into_donated_state():
    """A large persistable that the step overwrites before any read is
    promoted into the donated inputs (its stale scope buffer frees into
    XLA's arena) — and the step still computes/writes back correctly."""
    scope = _fresh_scope()
    prog = fluid.default_main_program()
    block = prog.global_block()
    x = layers.data(name="wp_x", shape=[4], dtype="float32")
    acc = block.create_var(name="wp_acc", shape=(512, 512),
                           dtype="float32", persistable=True)
    layers.fill_constant([512, 512], "float32", 3.0, out=acc)
    out = layers.reduce_sum(x)

    info = InplaceInfo(scope=scope)
    # un-initialized scope slot: nothing to donate, no promotion
    mut, const, state_out = classify_persistable_state(
        block, [out.name], inplace=info)
    assert "wp_acc" not in mut and "wp_acc" in state_out
    # initialized + >= 1 MiB: promoted into the donated set
    scope.set("wp_acc", np.zeros((512, 512), np.float32))
    mut, const, _ = classify_persistable_state(
        block, [out.name], inplace=info)
    assert "wp_acc" in mut and "wp_acc" not in const
    # disabled policy: nothing donated at all
    mut_off, const_off, _ = classify_persistable_state(
        block, [out.name], inplace=InplaceInfo(enabled=False, scope=scope))
    assert mut_off == []

    exe = fluid.Executor(fluid.CPUPlace())
    res, = exe.run(prog, feed={"wp_x": np.ones((2, 4), np.float32)},
                   fetch_list=[out])
    assert np.asarray(res).item() == pytest.approx(8.0)
    assert np.asarray(scope.get("wp_acc"))[0, 0] == 3.0


def test_cached_step_survives_scope_switch():
    """A compiled step can depend on the compile-time scope (baked
    __folded__.* params) — running the same program under a DIFFERENT
    scope must keep working: the baked values self-heal into the new
    scope (state_fallback), reusing the cached step."""
    x = layers.data(name="sk_x", shape=[70000], dtype="float32")
    c = layers.scale(layers.fill_constant([70000], "float32", 2.0),
                     scale=0.5)  # baked as a scope param (> inline max)
    out = layers.reduce_sum(layers.elementwise_add(x, c))
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"sk_x": np.zeros((1, 70000), np.float32)}
    exe.run(fluid.default_startup_program())
    r1, = exe.run(prog, feed=feed, fetch_list=[out])
    n_cached = len(exe._cache)
    scope_b = scope_mod.Scope()
    with scope_mod.scope_guard(scope_b):
        exe.run(fluid.default_startup_program(), scope=scope_b)
        r2, = exe.run(prog, feed=feed, fetch_list=[out], scope=scope_b)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert len(exe._cache) == n_cached  # same step served both scopes
    assert any(n.startswith("__folded__.") and scope_b.get(n) is not None
               for n in [v for v in prog.global_block().vars] +
               [v for s in exe._cache.values()
                for v in s.program.global_block().vars])


def test_enable_inplace_flip_recompiles():
    """Flipping BuildStrategy.enable_inplace between runs changes the
    donation classification — the compile cache must not serve the
    stale step."""
    x = layers.data(name="ik_x", shape=[4], dtype="float32")
    h = layers.fc(input=x, size=4)
    loss = layers.reduce_mean(h)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    bs = fluid.compiler.BuildStrategy()
    cp = fluid.compiler.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
        loss_name=loss.name, build_strategy=bs)
    feed = {"ik_x": np.ones((8, 4), np.float32)}
    exe.run(cp, feed=feed, fetch_list=[loss])
    bs.enable_inplace = False
    exe.run(cp, feed=feed, fetch_list=[loss])
    assert len(cp._compiled_steps) == 2
    donating = [bool(s.mut_names) for s in cp._compiled_steps.values()]
    assert sorted(donating) == [False, True]


# ---------------------------------------------------------------------------
# train-program equivalence through the whole default pipeline
# ---------------------------------------------------------------------------


def test_train_program_optimized_bitwise(monkeypatch):
    """The sharpest end-to-end case: a cloned+optimized TRAIN program
    (grad ops with __fwd_op__ references, optimizer state donation, a
    dead branch and a const chain riding along) reproduces the
    unoptimized loss trajectory bitwise."""
    def build():
        x = layers.data(name="tr_x", shape=[8], dtype="float32")
        y = layers.data(name="tr_y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=16, act="relu")
        pred = layers.fc(input=h, size=1)
        loss = layers.reduce_mean(
            layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        # appendix the pipeline should clean up
        c = layers.scale(layers.fill_constant([1], "float32", 2.0),
                         scale=0.5)
        layers.elementwise_add(layers.scale(loss, scale=3.0), c)
        fluid.default_main_program().random_seed = 9
        fluid.default_startup_program().random_seed = 9
        return loss

    rng = np.random.RandomState(0)
    xs = rng.randn(4, 8).astype(np.float32)
    ys = rng.randn(4, 1).astype(np.float32)

    def feed():
        return {"tr_x": xs, "tr_y": ys}

    traj, progs = _run_both(monkeypatch, build, feed, steps=5)
    assert float(traj[-1].ravel()[0]) < float(traj[0].ravel()[0])
    (prog,) = progs
    # the fetch-dead appendix is gone from the compiled program (the
    # default main program left by the noopt leg has the full op list)
    assert len(prog.global_block().ops) < len(
        fluid.default_main_program().global_block().ops)


# ---------------------------------------------------------------------------
# opt-out + cache identity + registry surface
# ---------------------------------------------------------------------------


def test_opt_out_restores_pre_pipeline_identity(monkeypatch):
    monkeypatch.setenv("PTPU_NO_PROGRAM_OPT", "1")
    x = layers.data(name="oo_x", shape=[4], dtype="float32")
    out = layers.reduce_sum(layers.relu(x))
    layers.tanh(layers.scale(x, scale=2.0))  # dead, but must stay
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(prog, feed={"oo_x": np.ones((2, 4), np.float32)},
            fetch_list=[out])
    (step,) = [s for s in exe._cache.values()
               if s.program.global_block().ops]
    assert step.program is prog  # no clone, no transforms


def test_pipeline_passes_registered():
    names = ir.registered_passes()
    for p in ("fetch_dce", "cse", "constant_fold", "fuse_elewise_add_act",
              "conv_bn_fold_baked"):
        assert p in names, names


def test_inference_pipeline_through_with_inference_optimize():
    """with_inference_optimize routes the inference builtins: the
    baked conv+bn fold fires on an is_test program without touching the
    user's parameters."""
    img = layers.data(name="io_img", shape=[3, 8, 8], dtype="float32")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    bn = layers.batch_norm(conv)
    out = layers.reduce_mean(bn)
    test_prog = fluid.default_main_program().clone(for_test=True)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"io_img": rng.rand(2, 3, 8, 8).astype(np.float32)}
    before, = exe.run(test_prog, feed=feed, fetch_list=[out])

    scope = scope_mod.global_scope()
    w_name = [op for op in test_prog.global_block().ops
              if op.type == "conv2d"][0].input_names("Filter")[0]
    w_before = np.asarray(scope.get(w_name)).copy()

    cp = fluid.compiler.CompiledProgram(test_prog).with_data_parallel() \
        .with_inference_optimize(None)
    after, = exe.run(cp, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               rtol=1e-4, atol=1e-5)
    (step,) = cp._compiled_steps.values()
    types = [op.type for op in step.program.global_block().ops]
    assert "batch_norm" not in types, types
    # non-destructive: the ORIGINAL weights are untouched
    np.testing.assert_array_equal(np.asarray(scope.get(w_name)), w_before)
    assert "batch_norm" in [op.type
                            for op in test_prog.global_block().ops]


def test_fetched_dropout_output_survives_inference_pipeline():
    """Fetching an upscale_in_train dropout's output on an is_test
    program: the auto dropout_remove must keep a producer (identity
    scale) for the fetched name instead of renaming it away."""
    x = layers.data(name="fd_x", shape=[4], dtype="float32")
    d = layers.dropout(x, dropout_prob=0.4,
                       dropout_implementation="upscale_in_train")
    out = layers.reduce_sum(d)
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.arange(8, dtype=np.float32).reshape(2, 4)
    o, dv = exe.run(test_prog, feed={"fd_x": xv}, fetch_list=[out, d])
    np.testing.assert_array_equal(np.asarray(dv), xv)  # test-mode identity
    assert np.asarray(o).item() == pytest.approx(xv.sum())


def test_fetched_residual_add_survives_conv_fuse():
    """with_inference_optimize + fetching the residual add's output:
    conv_elementwise_add_fuse must skip the match instead of orphaning
    the fetched interior name."""
    img = layers.data(name="fr2_img", shape=[3, 8, 8], dtype="float32")
    skip = layers.data(name="fr2_skip", shape=[4, 8, 8], dtype="float32")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    added = layers.elementwise_add(conv, skip)
    out = layers.reduce_mean(layers.relu(added))
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = {"fr2_img": rng.rand(2, 3, 8, 8).astype(np.float32),
            "fr2_skip": rng.rand(2, 4, 8, 8).astype(np.float32)}
    want, want_add = exe.run(test_prog, feed=feed,
                             fetch_list=[out, added])
    cp = fluid.compiler.CompiledProgram(test_prog).with_data_parallel() \
        .with_inference_optimize(None)
    got, got_add = exe.run(cp, feed=feed, fetch_list=[out, added])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_add), np.asarray(want_add),
                               rtol=1e-5, atol=1e-6)


def test_with_inference_optimize_without_data_parallel():
    """The inference pipeline must fire on the plain (non-data-parallel)
    CompiledProgram run path too."""
    img = layers.data(name="ni_img", shape=[3, 8, 8], dtype="float32")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    bn = layers.batch_norm(conv)
    out = layers.reduce_mean(bn)
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"ni_img": rng.rand(2, 3, 8, 8).astype(np.float32)}
    want, = exe.run(test_prog, feed=feed, fetch_list=[out])
    cp = fluid.compiler.CompiledProgram(test_prog) \
        .with_inference_optimize(None)
    got, = exe.run(cp, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    (run_prog,) = cp._infer_programs.values()
    assert "batch_norm" not in [op.type
                                for op in run_prog.global_block().ops]
    assert "batch_norm" in [op.type
                            for op in test_prog.global_block().ops]


def test_conv_bn_fold_then_residual_fuse_keeps_bias():
    """ResNet-style conv -> bn -> residual add -> relu through the
    inference pipeline: the residual fuse must carry the conv+bn fold's
    FoldedBias into conv2d_fusion's Bias (silently dropping it skews
    every output)."""
    img = layers.data(name="bf_img", shape=[3, 8, 8], dtype="float32")
    skip = layers.data(name="bf_skip", shape=[4, 8, 8], dtype="float32")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    bn = layers.batch_norm(conv)
    out = layers.reduce_mean(layers.relu(layers.elementwise_add(bn, skip)))
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(2)
    feed = {"bf_img": rng.rand(2, 3, 8, 8).astype(np.float32),
            "bf_skip": rng.rand(2, 4, 8, 8).astype(np.float32)}
    want, = exe.run(test_prog, feed=feed, fetch_list=[out])
    cp = fluid.compiler.CompiledProgram(test_prog).with_data_parallel() \
        .with_inference_optimize(None)
    got, = exe.run(cp, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    (step,) = cp._compiled_steps.values()
    fusion = [op for op in step.program.global_block().ops
              if op.type == "conv2d_fusion"]
    assert fusion and fusion[0].inputs.get("Bias"), \
        [op.type for op in step.program.global_block().ops]


def test_predictor_fetches_dropout_output(tmp_path):
    """AnalysisPredictor pins fetch targets before its load-time passes:
    a saved model whose output IS a dropout's output must survive
    dropout_remove."""
    from paddle_tpu.inference import (AnalysisConfig, PaddleTensor,
                                      create_paddle_predictor)

    x = layers.data(name="pd_x", shape=[4], dtype="float32")
    d = layers.dropout(x, dropout_prob=0.3,
                       dropout_implementation="upscale_in_train")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mdir = str(tmp_path / "m")
    fluid.io.save_inference_model(mdir, ["pd_x"], [d], exe)
    cfg = AnalysisConfig(mdir)
    cfg.disable_gpu()
    pred = create_paddle_predictor(cfg)
    xv = np.arange(8, dtype=np.float32).reshape(2, 4)
    outs = pred.run([PaddleTensor(xv, name="pd_x")])
    np.testing.assert_array_equal(outs[0].as_ndarray(), xv)


def test_optimize_is_idempotent(monkeypatch):
    """Re-optimizing an already-optimized program is a no-op: the same
    object comes back (and keeps its _baked_values), so chained
    optimization (with_inference_optimize -> Executor.run) neither
    re-clones per compile nor loses the state_fallback entries."""
    from paddle_tpu import ir_passes

    x = layers.data(name="id_x", shape=[3], dtype="float32")
    c = layers.scale(layers.fill_constant([3], "float32", 2.0), scale=0.5)
    out = layers.reduce_sum(layers.elementwise_add(x, c))
    layers.tanh(layers.scale(x, scale=2.0))  # dead branch
    prog = fluid.default_main_program()
    scope = scope_mod.global_scope()
    opt1 = ir_passes.optimize_for_execution(prog, [out.name], scope)
    assert opt1 is not prog
    opt2 = ir_passes.optimize_for_execution(opt1, [out.name], scope)
    assert opt2 is opt1


def test_dropout_remove_respects_rebinding():
    """dropout_remove's rename is only sound under single assignment:
    a later in-place rebinding of the dropout's out name must fall back
    to the identity-producer form, not rewire readers to the source."""
    x = layers.data(name="dr_x", shape=[4], dtype="float32")
    y = layers.dropout(x, dropout_prob=0.5,
                       dropout_implementation="upscale_in_train")
    a = layers.scale(y, scale=2.0)
    layers.assign(layers.scale(x, scale=10.0), output=y)  # rebind y
    b = layers.scale(y, scale=1.0)
    out = layers.reduce_sum(layers.elementwise_add(a, b))
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res, = exe.run(test_prog, feed={"dr_x": np.ones((1, 4), np.float32)},
                   fetch_list=[out])
    # a = 2*x = 2 each; b = 10*x = 10 each -> sum = 4*(2+10)
    assert np.asarray(res).item() == pytest.approx(48.0)
