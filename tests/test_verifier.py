"""Program IR verifier + static-analysis harness tests (ISSUE 9): one
known-bad program per verifier rule, pass-blame attribution, the
PTPU_VERIFY_PASSES=1 clean-run and env-unset identity pins, the
flags-registry semantics, the repo linter's rules, and the ptpu_stats
NaN regression."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import analysis, flags, ir, ir_passes, layers  # noqa: E402
from paddle_tpu.analysis import VerifyError, verify  # noqa: E402
from paddle_tpu.framework import Operator, Program  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(violations):
    return {v.rule for v in violations}


def _train_program():
    x = layers.data(name="vx", shape=[13], dtype="float32")
    y = layers.data(name="vy", shape=[1], dtype="float32")
    pred = layers.fc(input=x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    return fluid.default_main_program(), loss


# ---------------------------------------------------------------------------
# clean programs verify clean
# ---------------------------------------------------------------------------


def test_clean_train_program_verifies():
    prog, loss = _train_program()
    assert verify(prog, fetch_names=[loss.name]) == []
    assert verify(fluid.default_startup_program(), fetch_names=[]) == []


def test_verify_levels_and_bad_level():
    prog, loss = _train_program()
    assert verify(prog, level="basic", fetch_names=[loss.name]) == []
    with pytest.raises(ValueError, match="level"):
        verify(prog, level="pedantic")


# ---------------------------------------------------------------------------
# one known-bad program per rule
# ---------------------------------------------------------------------------


def test_unknown_op_type_flagged():
    prog = Program()
    blk = prog.global_block()
    v = blk.create_var(name="u_out", shape=(4,), dtype="float32")
    blk.append_op("definitely_not_an_op", inputs={}, outputs={"Out": [v]})
    violations = verify(prog)
    assert _rules(violations) == {"unknown-op"}
    assert violations[0].op_type == "definitely_not_an_op"
    assert violations[0].block_idx == 0 and violations[0].op_idx == 0


def test_dangling_fwd_op_ref_flagged():
    # grad op whose __fwd_op__ points at an op of a DIFFERENT program —
    # the clone invariant Program.clone() exists to preserve
    other = Program()
    ov = other.global_block().create_var(name="o", shape=(4,),
                                         dtype="float32")
    foreign = other.global_block().append_op(
        "relu", inputs={"X": [ov]}, outputs={"Out": [ov]})

    prog = Program()
    blk = prog.global_block()
    a = blk.create_var(name="a", shape=(4,), dtype="float32",
                       is_data=True)
    g = blk.create_var(name="a@GRAD", shape=(4,), dtype="float32")
    blk.append_op("relu", inputs={"X": [a]}, outputs={"Out": [g]},
                  attrs={"__fwd_op__": foreign})
    violations = verify(prog)
    assert "dangling-ref" in _rules(violations)
    assert any("not in this program" in v.message for v in violations)


def test_foreign_var_ref_flagged():
    other = Program()
    foreign_v = other.global_block().create_var(
        name="f", shape=(4,), dtype="float32", is_data=True)
    prog = Program()
    blk = prog.global_block()
    out = blk.create_var(name="fo", shape=(4,), dtype="float32")
    blk.append_op("relu", inputs={"X": [foreign_v]},
                  outputs={"Out": [out]})
    violations = verify(prog)
    assert "dangling-ref" in _rules(violations)
    assert any(v.var == "f" for v in violations)


def test_dtype_mismatch_flagged_with_location():
    prog = Program()
    blk = prog.global_block()
    x = blk.create_var(name="dx", shape=(4,), dtype="float32",
                       is_data=True)
    out = blk.create_var(name="dout", shape=(4,), dtype="float32")
    blk.append_op("relu", inputs={"X": [x]}, outputs={"Out": [x]})  # warm
    blk.append_op("cast", inputs={"X": [x]}, outputs={"Out": [out]},
                  attrs={"in_dtype": "float32", "out_dtype": "bfloat16"})
    violations = verify(prog)
    assert "dtype-mismatch" in _rules(violations)
    v = next(v for v in violations if v.rule == "dtype-mismatch")
    # the diagnostic pins op index, var name, expected vs found
    assert v.op_idx == 1 and v.var == "dout"
    assert "bfloat16" in v.message and "float32" in v.message
    # basic level skips meta propagation
    assert "dtype-mismatch" not in _rules(verify(prog, level="basic"))


def test_shape_mismatch_flagged():
    prog = Program()
    blk = prog.global_block()
    out = blk.create_var(name="sc", shape=(3, 3), dtype="float32")
    blk.append_op("fill_constant", inputs={},
                  outputs={"Out": [out]},
                  attrs={"shape": [2, 2], "dtype": "float32",
                         "value": 0.0})
    violations = verify(prog)
    assert "shape-mismatch" in _rules(violations)
    # statically incompatible matmul contraction dims
    prog2 = Program()
    blk2 = prog2.global_block()
    a = blk2.create_var(name="ma", shape=(4, 8), dtype="float32",
                        is_data=True)
    b = blk2.create_var(name="mb", shape=(9, 2), dtype="float32",
                        is_data=True)
    o = blk2.create_var(name="mo", shape=(4, 2), dtype="float32")
    blk2.append_op("matmul", inputs={"X": [a], "Y": [b]},
                   outputs={"Out": [o]})
    assert "shape-mismatch" in _rules(verify(prog2))


def test_op_signature_missing_slot_and_attr():
    prog = Program()
    blk = prog.global_block()
    x = blk.create_var(name="gx", shape=(4,), dtype="float32",
                       is_data=True)
    out = blk.create_var(name="go", shape=(4,), dtype="float32")
    # elementwise_add without its Y operand
    blk.append_op("elementwise_add", inputs={"X": [x]},
                  outputs={"Out": [out]})
    # cast without the required out_dtype attr
    blk.append_op("cast", inputs={"X": [x]}, outputs={"Out": [out]})
    violations = verify(prog)
    msgs = [v.message for v in violations
            if v.rule == "op-signature"]
    assert any("'Y'" in m for m in msgs)
    assert any("out_dtype" in m for m in msgs)
    assert not [v for v in verify(prog, level="basic")
                if v.rule == "op-signature"]


def test_use_before_def_in_sub_block():
    prog = Program()
    gb = prog.global_block()
    gx = gb.create_var(name="sb_x", shape=(4,), dtype="float32",
                       is_data=True)
    sub = prog._create_block()
    tmp = sub.create_var(name="sb_tmp", shape=(4,), dtype="float32")
    o = sub.create_var(name="sb_o", shape=(4,), dtype="float32")
    # reads sb_tmp BEFORE the op that defines it, inside the sub-block
    sub.append_op("relu", inputs={"X": [tmp]}, outputs={"Out": [o]})
    sub.append_op("relu", inputs={"X": [gx]}, outputs={"Out": [tmp]})
    prog._rollback()
    violations = verify(prog)
    assert "use-before-def" in _rules(violations)
    v = next(v for v in violations if v.rule == "use-before-def")
    assert v.block_idx == 1 and v.var == "sb_tmp" and v.op_idx == 0


def test_use_before_def_anchors_are_honored():
    """Persistables, feeds, tensor arrays and cross-block writes are NOT
    use-before-def, whatever the op order."""
    prog = Program()
    blk = prog.global_block()
    p = blk.create_var(name="anchor_p", shape=(4,), dtype="float32",
                       persistable=True)
    o = blk.create_var(name="anchor_o", shape=(4,), dtype="float32")
    blk.append_op("relu", inputs={"X": [p]}, outputs={"Out": [o]})
    blk.append_op("relu", inputs={"X": [o]}, outputs={"Out": [p]})
    assert verify(prog) == []


def test_donated_and_fetched_var_flagged():
    prog = Program()
    blk = prog.global_block()
    # >= 1 MiB write-before-read persistable: an inplace-promotion
    # candidate, so fetching it breaks the donation-safety convention
    acc = blk.create_var(name="df_acc", shape=(512, 1024),
                         dtype="float32", persistable=True)
    blk.append_op("fill_constant", inputs={}, outputs={"Out": [acc]},
                  attrs={"shape": [512, 1024], "dtype": "float32",
                         "value": 1.0})
    violations = verify(prog, fetch_names=["df_acc"])
    assert _rules(violations) == {"donated-fetch"}
    assert violations[0].var == "df_acc"
    # not fetched -> clean; fetch set unknown -> rule skipped
    assert verify(prog, fetch_names=[]) == []
    assert verify(prog) == []
    # small buffers never promote, so fetching them is fine
    prog2 = Program()
    blk2 = prog2.global_block()
    small = blk2.create_var(name="df_small", shape=(4,),
                            dtype="float32", persistable=True)
    blk2.append_op("fill_constant", inputs={}, outputs={"Out": [small]},
                   attrs={"shape": [4], "dtype": "float32", "value": 0.0})
    assert verify(prog2, fetch_names=["df_small"]) == []


def test_verify_error_structured_fields():
    prog = Program()
    blk = prog.global_block()
    v = blk.create_var(name="e_out", shape=(4,), dtype="float32")
    blk.append_op("definitely_not_an_op", inputs={}, outputs={"Out": [v]})
    with pytest.raises(VerifyError) as ei:
        analysis.verify_or_raise(prog)
    err = ei.value
    assert err.rule == "unknown-op"
    assert err.program_version == prog.version
    assert err.block_idx == 0 and err.op_idx == 0
    assert err.pass_name is None
    assert err.violations and "definitely_not_an_op" in str(err)


# ---------------------------------------------------------------------------
# pass-blame attribution (PTPU_VERIFY_PASSES=1)
# ---------------------------------------------------------------------------


@pytest.fixture
def corrupting_pass():
    name = "corrupt_for_verifier_test"

    @ir.register_pass(name)
    def _corrupt(program, scope):
        blk = program.global_block()
        out = blk.create_var(name="corrupt_out", shape=(1,),
                             dtype="float32")
        blk.append_op("not_a_registered_op", inputs={},
                      outputs={"Out": [out]})
        return program

    yield name
    ir.unregister_pass(name)


def test_apply_passes_blames_corrupting_pass(monkeypatch,
                                             corrupting_pass):
    monkeypatch.setenv("PTPU_VERIFY_PASSES", "1")
    prog = Program()
    blk = prog.global_block()
    a = blk.create_var(name="bp_a", shape=(4,), dtype="float32",
                       is_data=True)
    o = blk.create_var(name="bp_o", shape=(4,), dtype="float32")
    blk.append_op("relu", inputs={"X": [a]}, outputs={"Out": [o]})
    with pytest.raises(VerifyError) as ei:
        ir.apply_passes(prog, [corrupting_pass])
    assert ei.value.pass_name == corrupting_pass
    assert corrupting_pass in str(ei.value)
    assert ei.value.rule == "unknown-op"


def test_optimize_for_execution_blames_pipeline_pass(monkeypatch,
                                                     corrupting_pass):
    monkeypatch.setenv("PTPU_VERIFY_PASSES", "1")
    prog, loss = _train_program()
    real = ir_passes.build_pipeline

    def pipeline_with_corruption(*args, **kwargs):
        return real(*args, **kwargs) + [corrupting_pass]

    monkeypatch.setattr(ir_passes, "build_pipeline",
                        pipeline_with_corruption)
    with pytest.raises(VerifyError) as ei:
        ir_passes.optimize_for_execution(prog, [loss.name],
                                         fluid.global_scope())
    assert ei.value.pass_name == corrupting_pass


def test_preexisting_violation_not_reblamed(monkeypatch):
    """A violation already present in the INPUT program raises at input
    verification (pass_name None), never blamed on a pass."""
    monkeypatch.setenv("PTPU_VERIFY_PASSES", "1")
    prog = Program()
    blk = prog.global_block()
    v = blk.create_var(name="pre_out", shape=(4,), dtype="float32")
    blk.append_op("definitely_not_an_op", inputs={},
                  outputs={"Out": [v]})
    with pytest.raises(VerifyError) as ei:
        ir.apply_passes(prog, ["cse"])
    assert ei.value.pass_name is None


# ---------------------------------------------------------------------------
# end-to-end: clean run under the env flag, identity with it unset
# ---------------------------------------------------------------------------


def _run_fit_a_line(steps=3):
    prog, loss = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out = None
    rng = np.random.RandomState(0)
    for _ in range(steps):
        out, = exe.run(prog, feed={
            "vx": rng.uniform(-1, 1, (8, 13)).astype(np.float32),
            "vy": rng.uniform(-1, 1, (8, 1)).astype(np.float32)},
            fetch_list=[loss])
    return np.asarray(out)


def test_verify_passes_clean_run_and_telemetry(monkeypatch):
    from paddle_tpu.observability import metrics

    monkeypatch.setenv("PTPU_VERIFY_PASSES", "1")
    reg = metrics.registry()
    metrics.reset()
    metrics.enable()
    try:
        loss = _run_fit_a_line()
    finally:
        metrics.disable()
    assert np.isfinite(loss).all()
    checked = reg.counter("verify/programs_checked").value
    assert checked >= 1
    assert reg.counter("verify/violations").value == 0


def test_verify_passes_covers_noopt_path(monkeypatch):
    monkeypatch.setenv("PTPU_VERIFY_PASSES", "1")
    monkeypatch.setenv("PTPU_NO_PROGRAM_OPT", "1")
    calls = []
    real = analysis.verifier.ProgramVerifier.verify

    def counting(self, program, fetch_names=None):
        calls.append(1)
        return real(self, program, fetch_names)

    monkeypatch.setattr(analysis.verifier.ProgramVerifier, "verify",
                        counting)
    loss = _run_fit_a_line()
    assert np.isfinite(loss).all()
    assert calls  # the no-opt compile path still verified


def test_env_unset_means_no_verifier_in_compile_path(monkeypatch):
    """ISSUE 9 acceptance: with PTPU_VERIFY_PASSES unset the compile
    path never touches the verifier — behaviorally unchanged."""
    monkeypatch.delenv("PTPU_VERIFY_PASSES", raising=False)

    def boom(*a, **k):
        raise AssertionError("verifier invoked with the env flag unset")

    monkeypatch.setattr(analysis.verifier.PassPipelineVerifier,
                        "__init__", boom)
    monkeypatch.setattr(analysis.verifier.ProgramVerifier, "verify",
                        boom)
    loss = _run_fit_a_line()
    assert np.isfinite(loss).all()


# ---------------------------------------------------------------------------
# flags registry
# ---------------------------------------------------------------------------


def test_flags_registry_describe_lists_every_flag():
    table = flags.describe()
    declared = flags.declared_flags()
    assert len(declared) >= 20
    for name in declared:
        assert name in table, name
    # docstrings ride along
    assert "verifier" in table


def test_flags_env_semantics(monkeypatch):
    # unset -> declared default
    monkeypatch.delenv("PTPU_ASYNC_STEPS", raising=False)
    assert flags.env("PTPU_ASYNC_STEPS") == 12
    monkeypatch.setenv("PTPU_ASYNC_STEPS", "7")
    assert flags.env("PTPU_ASYNC_STEPS") == 7
    monkeypatch.setenv("PTPU_ASYNC_STEPS", "seven")
    with pytest.raises(ValueError, match="PTPU_ASYNC_STEPS"):
        flags.env("PTPU_ASYNC_STEPS")
    # bool spellings (the zero.py _env_flag semantics, now shared)
    for raw, want in (("1", True), ("true", True), ("YES", True),
                      ("on", True), ("0", False), ("No", False),
                      ("off", False)):
        monkeypatch.setenv("PTPU_VERIFY_PASSES", raw)
        assert flags.env("PTPU_VERIFY_PASSES") is want, raw
    monkeypatch.setenv("PTPU_VERIFY_PASSES", "banana")
    with pytest.raises(ValueError, match="PTPU_VERIFY_PASSES"):
        flags.env("PTPU_VERIFY_PASSES")
    # undeclared names fail loudly — the runtime analogue of the linter
    with pytest.raises(KeyError, match="PTPU_NOT_A_FLAG"):
        flags.env("PTPU_NOT_A_FLAG")


def test_flags_path_type_accepts_off_spellings(monkeypatch):
    """PTPU_TRACE_DIR=0 must DISABLE tracing (the pre-registry _env_on
    semantics), not name a directory literally '0' — path-typed flags
    share the boolean off spellings."""
    for off in ("0", "false", "OFF", "no"):
        monkeypatch.setenv("PTPU_TRACE_DIR", off)
        assert flags.env("PTPU_TRACE_DIR") is None, off
        monkeypatch.setenv("PTPU_BLACKBOX_DIR", off)
        assert flags.env("PTPU_BLACKBOX_DIR") is None, off
    monkeypatch.setenv("PTPU_TRACE_DIR", "/tmp/traces")
    assert flags.env("PTPU_TRACE_DIR") == "/tmp/traces"


def test_elementwise_declared_shape_matches_verifier_rule():
    """The builder's declared Out shape and the verifier's inferred one
    come from ONE shared rule (analysis.meta.elementwise_out_dims) — the
    reversed-scalar `1 - v` case that drifted pre-PR stays pinned."""
    v = layers.data(name="ew_v", shape=[2], dtype="float32")
    out = 1.0 - layers.softmax(v)  # __rsub__: X is the promoted (1,)
    assert out.shape == v.shape
    assert verify(fluid.default_main_program(),
                  fetch_names=[out.name]) == []


def test_flags_env_reads_at_call_time(monkeypatch):
    monkeypatch.delenv("PTPU_SPIKE_FACTOR", raising=False)
    assert flags.env("PTPU_SPIKE_FACTOR") is None
    monkeypatch.setenv("PTPU_SPIKE_FACTOR", "2.5")
    assert flags.env("PTPU_SPIKE_FACTOR") == 2.5


# ---------------------------------------------------------------------------
# infer_meta registration surface
# ---------------------------------------------------------------------------


def test_register_infer_meta_via_registry():
    from paddle_tpu.ops import registry

    assert registry.get("cast").infer_meta is not None
    assert analysis.meta_of("cast").attrs == ("out_dtype",)
    # a bare infer fn is accepted and wrapped
    @registry.register("verifier_test_op", infer_meta=lambda op, m: {})
    def _impl(ctx, ins, attrs):
        return {"Out": [ins["X"][0]]}

    try:
        m = analysis.meta_of("verifier_test_op")
        assert isinstance(m, analysis.OpMeta) and m.infer is not None
    finally:
        registry._REGISTRY.pop("verifier_test_op", None)
