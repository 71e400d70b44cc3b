"""CPU tests of the benchmark harness (toy widths, no chip)."""

import copy
import json
import os
import time

import numpy as np
import pytest

from perfbench import loadgen, run, spec, trace_reduce
from perfbench.flops import xglm as flops
from perfbench.reference import xglm as ref

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_ROOT = os.path.join(HERE, "root")
XGLM_564M = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "xglm-564m-train.json"))
XGLM_1_7B = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "xglm-1.7b-serve.json"))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


def rehearse(cell, trace=0, seed=2147483659, seconds=1.5, hooks=None):
    return run.run_cell(cell, seed, seconds, trace, require_chip=False,
                        root=TEST_ROOT, hooks=hooks)


def well_formed(line, trace):
    assert set(line) == RESULT_KEYS | ({"breakdown"} if trace else set())
    json.dumps(line)
    # every number compared, beside its limit, comes last in the line
    assert list(line)[-1] == "checks" and line["checks"]
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "ok"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    for name, m in line["metrics"].items():
        spec.check_name(name)
        spec.check_unit(m["unit"])
        assert np.isfinite(m["value"])


# -- the command end to end, one case per runner and traffic kind --------

@pytest.mark.parametrize("cell,trace,expect", [
    ("tiny.train", 0, {"train_tokens_per_s", "setup_s"}),
    ("tiny.train", 1, {"step_ms.train", "mfu_pct.train",
                       "step_ms_max.added"}),
    ("tiny.open", 0, {"ttft_p50_ms", "itl_p50_ms", "itl_p99_ms", "setup_s"}),
    ("tiny.open", 1, {"queue_wait_p90_ms", "kv_pool_used_pct",
                      "ttft_p90_ms.steady", "batch_occupancy_mean",
                      "engine_step_ms.serve", "slow_gap_share_pct.serve"}),
    ("tiny.closed", 0, {"serve_tokens_per_s", "setup_s"}),
    ("tiny.closed", 1, {"engine_step_ms.batch", "ttft_p90_ms.batch",
                        "itl_p95_ms.batch", "batch_occupancy_mean.batch"}),
])
def test_rehearsal_ends_in_a_well_formed_result(cell, trace, expect):
    line = rehearse(cell, trace)
    well_formed(line, trace)
    assert line["correct"] is True
    # off the chip there is no device trace: its readers return nothing
    # and the harness leaves those metrics out
    assert set(line["metrics"]) == expect


def test_a_run_without_a_chip_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.run_cell("tiny.train", 1, 1.0, 0, require_chip=True,
                     root=TEST_ROOT)
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


# -- the timed path broken underneath, and the lower-precision controls --

def _unchanged_state(step, scope, leaves):
    """A step that returns its state unchanged: the parameters and the
    moments are put back after every call."""
    def broken(tokens, labels):
        keep = {n: scope.get(n) + 0 for n in scope.local_var_names()}
        out = step(tokens, labels)
        for n, v in keep.items():
            scope.set(n, v)
        return out
    return broken


def _half_batch(step, scope, leaves):
    """A step that leaves out a part of the batch."""
    def broken(tokens, labels):
        tokens = np.concatenate([tokens[:1]] * len(tokens))
        labels = np.concatenate([labels[:1]] * len(labels))
        return step(tokens, labels)
    return broken


def _failed(line):
    assert line["correct"] is False
    return line


def _bad_checks(lines):
    notes = [json.loads(x) for x in lines if x.startswith('{"check"')]
    return {n["check"] for n in notes if not n["ok"]}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys):
    _failed(rehearse("tiny.train", hooks={"break_step": _unchanged_state}))
    assert "param_change_gap_worst_leaf" in _bad_checks(
        capsys.readouterr().out.splitlines())


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(capsys):
    _failed(rehearse("tiny.train", hooks={"break_step": _half_batch}))
    assert "loss_gap_max" in _bad_checks(
        capsys.readouterr().out.splitlines())


@pytest.mark.parametrize("cell,name,fails", [
    ("tiny.train", "bf16_params", "param_change_gap_worst_leaf"),
    ("tiny.train", "lr_x1.1", "param_change_gap_worst_leaf"),
    ("tiny.open", "int8_weights", "served_logit_gap_mean")])
def test_a_control_is_not_correct(cell, name, fails, capsys):
    """perfbench/control.py at toy size: the program's own bf16-stored
    parameters and an Adam step 10 % too long (training) and the
    weight-only int8 store (serving) come out as not correct under the
    same checks, and the tool says so."""
    from perfbench import control

    rc = control.main(["--workload", cell, "--seed", "2147483659",
                       "--seconds", "1.5", "--control", name],
                      require_chip=False, root=TEST_ROOT)
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is False
    assert line["control"]["name"] == name
    assert fails in _bad_checks(out)


def test_the_kv_pool_control_gives_the_engine_a_bf16_pool():
    """The engine has no switch for the pool's type: the control wraps
    the class the engine builds its pool from, and takes the wrap off.
    (Whether ``correct`` tells a bf16 pool from the fp32 one is a chip
    reading: PERF.md, section 2.)"""
    from paddle_tpu.serving import engine
    from perfbench import control

    pool = engine.KVBlockPool
    served = spec.cell(spec.load_benchmark(), "xglm-1.7b.doc-steady")[1]
    with control.switched_on(control.pick(served, "bf16_kv_pool")) as hooks:
        assert hooks == {}
        made = engine.KVBlockPool(2, 2, 8, 8, 4)
        assert made.k.dtype == "bfloat16" and made.num_blocks == 4
    with control.switched_on(control.pick(served, "bf16_kv_pool_320")):
        assert engine.KVBlockPool(2, 2, 8, 8, 896).num_blocks == 320
    assert engine.KVBlockPool is pool
    with pytest.raises(spec.SpecError):
        control.pick(served, "no_such_control")


def _alter_tokens(model):
    """A token altered where it is produced: the head's columns are
    rotated by one, so every served token is its neighbour."""
    import jax.numpy as jnp

    w = dict(model.weights)
    w["lm_head"] = jnp.roll(w["lm_head"], 1, axis=1)
    return type(model)(model.config, w)


def test_a_server_whose_tokens_are_altered_is_not_correct():
    _failed(rehearse("tiny.open", hooks={"tamper": _alter_tokens}))


def test_a_request_that_is_refused_makes_the_run_not_correct(
        monkeypatch, capsys):
    """A refused request drops out of the times to first token and the
    token gaps, so it may not pass: ``failed`` counts it and
    ``correct`` is false."""
    from perfbench.runners import serve

    real = serve.submit

    def shed(engine, rec):
        if rec.spec.index == 3:
            rec.submitted, rec.refused = time.perf_counter(), True
        else:
            real(engine, rec)

    monkeypatch.setattr(serve, "submit", shed)
    line = _failed(rehearse("tiny.open"))
    assert line["failed"] == 1
    assert _bad_checks(capsys.readouterr().out.splitlines()) \
        == {"failed_requests"}


# -- the reference against the program, directly -------------------------

TOY = {"vocab_size": 97, "d_model": 32, "attention_heads": 4,
       "num_layers": 3, "ffn_dim": 80}


def test_reference_matches_generation_model_through_the_pool():
    """Prefill in chunks and decode through the KV pool (the model's
    own step builders, return_logits=True) against the reference's
    full forward pass: logits, every position."""
    import jax

    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool)
    from perfbench.runners.serve import seeded_weights

    g = GenerationConfig(97, 32, 4, 3, 80, max_seq_len=64)
    model = GenerationModel(g, seeded_weights(ref, TOY, 11))
    B, bs, Mb, C = 2, 8, 8, 8
    pool = KVBlockPool(3, 4, 8, bs, B * Mb)
    toks = np.random.default_rng(0).integers(0, 97, (B, 21)).astype(np.int32)
    tables = np.arange(1, B * Mb + 1, dtype=np.int32).reshape(B, Mb)
    on, prev = np.ones(B, bool), np.zeros(B, np.int32)
    chunk = model.make_prefill_step(B, Mb, C, return_logits=True)
    decode = model.make_decode_step(B, Mb, return_logits=True)
    k, v, got = pool.k, pool.v, {}
    for s in (0, 8):       # two prompt chunks of 8, then 5 decode steps
        k, v, _t, z = chunk(model.weights, k, v, toks[:, s:s + C], on, prev,
                            np.full(B, s, np.int32), np.full(B, C, np.int32),
                            tables, on)
        got[s + C - 1] = np.asarray(z)
    for t in range(16, 21):
        k, v, _t, z = decode(model.weights, k, v, toks[:, t], on, prev,
                             np.full(B, t, np.int32), tables, on)
        got[t] = np.asarray(z)
    params = ref.make_params(11, TOY)
    for b in range(B):
        want = np.asarray(ref.logits_at(params, toks[b], np.arange(21), TOY))
        for t, z in got.items():
            np.testing.assert_allclose(z[b], want[t], atol=2e-4, rtol=0)


def test_reference_matches_transformer_fluid_loss_and_one_adam_step():
    """Loss at step 0 and after one optimizer step, fp32 program (no
    AMP) against the reference's loss, jax.grad and plain Adam."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer_fluid
    from perfbench.runners import train as T

    cfg = dict(TOY, family="xglm", train={
        "seq_len": 16, "batch": 3, "remat": False, "param_dtype": "float32",
        "head_chunk": 8, "lr": 1e-2, "beta1": 0.9, "beta2": 0.98,
        "epsilon": 1e-8})
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        _a, _b, loss = transformer_fluid.build(
            vocab_size=97, d_model=32, n_heads=4, n_layers=3, d_ff=80,
            seq_len=16, remat=False, dtype="float32", head_chunk=8)
        fluid.optimizer.Adam(1e-2, beta1=0.9, beta2=0.98).minimize(loss)
    leaves = T.leaf_map(prog, ref, cfg)
    tokens, labels = loadgen.token_batch(5, 0, 3, 16, 97)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog)
        for n, a in T.seeded_weights(ref, cfg, 5, leaves, "float32").items():
            scope.set(n, a)
        got = [float(np.asarray(exe.run(
            prog, feed={"tokens": tokens, "labels": labels},
            fetch_list=[loss])[0]).ravel()[0]) for _ in range(2)]
        exe.close()
    params = ref.make_params(5, cfg)
    l0, g = ref.loss_and_grad(params, jnp.asarray(tokens),
                              jnp.asarray(labels), cfg)
    for k in list(params):
        params[k], _m, _v = ref.adam_leaf(
            params[k], jnp.zeros_like(params[k]), jnp.zeros_like(params[k]),
            g[k], 1.0, 1e-2, 0.9, 0.98, 1e-8)
    l1, _ = ref.loss_and_grad(params, jnp.asarray(tokens),
                              jnp.asarray(labels), cfg)
    assert abs(got[0] - float(l0)) < 2e-5
    assert abs(got[1] - float(l1)) < 2e-4
    assert got[1] < got[0] - 0.05      # the step moved the loss


# -- counts from shapes, and the table of peaks --------------------------

def test_flops_and_bytes_pin_the_hand_counts():
    matmul, attention = flops.train_flops_per_token(XGLM_564M, 2048)
    assert matmul == 6 * (24 * (4 * 1024 ** 2 + 2 * 1024 * 4096)
                          + 1024 * 256008)
    assert round(matmul / 1e9, 2) == 3.38
    assert round(attention / 1e9, 2) == 0.30
    weights, kv = flops.decode_step_bytes(XGLM_1_7B, 4, 4, 1000)
    assert round(weights / 1e9, 1) == 6.9
    assert kv == 1000 * 393216 == 1000 * flops.kv_bytes_per_token(XGLM_1_7B, 4)
    assert ref.n_params(XGLM_564M) == 826615808
    assert ref.n_params(XGLM_1_7B) == 2257211392


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_raises():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_mfu_reader_is_tokens_times_flops_over_peak():
    _meta, read = spec.layer_metric("mfu_pct.train")
    obs = {"train_tokens_per_s": 10000.0, "seq_len": 2048, "chips": 1,
           "config": XGLM_564M, "peaks": spec.peaks("TPU v5 lite")}
    assert abs(read(obs) - 100 * 10000 * 3.68684e9 / 197e12) < 1e-3
    assert read({"config": XGLM_564M}) is None


# -- the reduction on the recorded trace ---------------------------------

def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(spec.HERE, "testdata", "probe_step.xplane.pb")
    out = trace_reduce.reduce_file(path, window_s=0.0706)
    # six steps of one fused matmul+tanh (15.8 us each) and two copies
    assert abs(out["busy_s"] - 111.9e-6) < 1e-6
    assert out["window_s"] == 0.0706 and out["devices"] == 1
    assert out["device_ops"][0][0] == "convolution_tanh_fusion"
    assert abs(out["device_ops"][0][1] - 95.28e-6) < 1e-6
    # the gaps between steps lie under the benchmark's own sleep span
    assert out["idle_gaps"][0][0] == "sleep"
    assert 0.05 < out["idle_gaps"][0][1] < 0.0706
    _meta, read = spec.layer_metric("device_idle_pct.train")
    assert abs(read({"trace": out}) - 100 * (1 - 111.9e-6 / 0.0706)) < 1e-6
    assert read({"trace": None}) is None


def test_operations_are_clipped_to_the_tracers_own_window_span():
    """The profiler also records what runs while it starts and stops:
    busy time and the window are taken inside ``bench/traced_window``."""
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    profile = NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            ev("%a.1 = f32[] x()", 0, 10_000), ev("%a.2 = f32[] x()", 20_000, 10_000),
            ev("%b = f32[] y()", 50_000, 10_000)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("bench/traced_window", 5_000, 50_000),
            ev("bench/exe.run", 28_000, 24_000)])])])
    out = trace_reduce.reduce_profile(profile, window_s=123.0)
    assert abs(out["window_s"] - 50e-6) < 1e-12
    assert abs(out["busy_s"] - 20e-6) < 1e-12
    assert out["device_ops"] == [["a", 15e-6], ["b", 5e-6]]
    assert out["idle_gaps"] == [["exe.run", 20e-6]]


@pytest.mark.parametrize("gap,engine_span,names", [
    # the worker held the gap: its own phase names it, prefix and all
    ((10_000, 30_000), ("ptpu/engine.wait", 12_000, 17_000),
     "ptpu/engine.wait"),
    # the worker covered under half of it: it had nothing to do, and
    # the gap is the generator's sleep between arrivals
    ((10_000, 30_000), ("ptpu/engine.plan", 12_000, 4_000),
     "generator.sleep"),
    # the trainer's dispatch inside the benchmark's exe.run
    ((10_000, 30_000), ("ptpu/exe.dispatch", 9_000, 25_000),
     "ptpu/exe.dispatch")])
def test_an_idle_gap_goes_to_the_programs_span_where_it_held_the_gap(
        gap, engine_span, names):
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    profile = NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            ev("%a = f32[] x()", 0, gap[0]),
            ev("%a.2 = f32[] x()", gap[1], 10_000)])]),
        NS(name="/host:CPU", lines=[
            NS(name="generator", events=[
                ev("bench/generator.sleep", 0, 40_000)]),
            NS(name="worker", events=[ev(*engine_span)])])])
    out = trace_reduce.reduce_profile(profile)
    assert out["idle_gaps"] == [[names, (gap[1] - gap[0]) * 1e-9]]


@pytest.mark.parametrize("name,kind", [
    ("%fusion.123 = f32[8]{0} fusion(f32[8]{0} %p)", "fusion"),
    ("%copy-done = bf16[2]", "copy-done"),
    # the compiler's rematerialised copies fold into their kind
    ("%fusion.25.remat = f32[8]{0} fusion(f32[8]{0} %p)", "fusion"),
    ("%fusion.25.remat2.1 = f32[8]", "fusion"),
    ("%slice-done", "slice-done")])
def test_op_kind_strips_the_instruction_to_its_kind(name, kind):
    assert trace_reduce.op_kind(name) == kind


# -- the gap percentile's rule, and the set-up line ----------------------

def test_slow_gap_share_is_the_share_of_gaps_over_twice_the_median():
    # 900 decode steps of 5-6 ms, 100 gaps that held a 25-30 ms chunk
    gaps = [5.0 + 0.001 * i for i in range(900)] \
        + [25.0 + 0.05 * i for i in range(100)]
    assert loadgen.slow_gap_share(gaps) == pytest.approx(0.1)
    assert loadgen.slow_gap_share([5.0] * 10) == 0.0
    # a gap of exactly twice the median is not slow
    assert loadgen.slow_gap_share([5.0, 5.0, 5.0, 10.0]) == 0.0
    assert loadgen.slow_gap_share([]) is None
    assert loadgen.samples_beyond(20000, 99) == 200
    assert loadgen.samples_beyond(120, 95) == 6


@pytest.mark.parametrize("shares,q,clear", [
    ([0.10, 0.12, 0.15], 95, True),       # all at 2 x 5 % or more
    ([0.10, 0.12, 0.15], 99, True),       # ten times the 1 % beyond p99
    ([0.10, 0.12, 0.15], 50, True),       # under half of the 50 % beyond
    ([0.020, 0.024, 0.025], 95, True),    # all at half of 5 % or less
    ([0.06, 0.12], 95, False),            # one run inside the band
    ([0.02, 0.12], 95, False),            # clear, but on either side
    ([0.012, 0.2], 99, False),
    ([0.26, 0.30], 50, False),            # over a quarter: p50 in the band
    ([], 95, False)])
def test_a_percentile_is_judged_only_clear_of_the_cliff(shares, q, clear):
    assert loadgen.percentile_is_clear(shares, q) is clear


def test_the_committed_cells_judge_only_percentiles_their_runs_cleared():
    """The steady cells' twelve runs a cell (PERF.md section 2) keep
    every judged gap percentile a factor of two from the cliff."""
    bench = spec.load_benchmark()
    shares = spec.read_json(os.path.join(
        spec.HERE, "testdata", "slow_gap_shares.json"))
    for m in bench["end_to_end"]:
        if not m["name"].startswith("itl_p"):
            continue
        q = float(m["name"][len("itl_p"):-len("_ms")])
        for cell in m["workloads"]:
            assert len(shares[cell]) >= 12, cell
            assert loadgen.percentile_is_clear(shares[cell], q), \
                (m["name"], cell)
    assert not any(m["moves"] == "itl_p95_ms" for m in bench["per_layer"]) \
        or any(m["name"] == "itl_p95_ms" for m in bench["end_to_end"])


def test_the_set_up_line_counts_the_compile_caches_hits_and_misses(capsys):
    counter = run.CompileCounter()
    counter._on_event(run.CompileCounter.HIT)
    counter._on_event(run.CompileCounter.HIT)
    counter._on_event(run.CompileCounter.MISS)
    counter._on_event("/jax/compilation_cache/tasks_using_cache")
    counter._on(run.CompileCounter.EVENT, 1.5)
    assert counter.setup_line() == {
        "compile_seconds_total": 1.5, "compilations": 1,
        "cache_hits": 2, "cache_misses": 1}
    rehearse("tiny.open")
    notes = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    (setup,) = [n for n in notes if n["phase"] == "window_open"]
    assert setup["setup_s"] > 0 and setup["compilations"] > 0
    assert {"compile_seconds_total", "cache_hits", "cache_misses"} \
        <= set(setup)
    (closed,) = [n for n in notes if n["phase"] == "window_closed"]
    assert 0.0 <= closed["slow_gap_share"] <= 1.0
    assert closed["token_gaps"] > 0
    # reaching the chip, the phase of set-up that is the machine's own,
    # is on the first line and read per layer in every committed cell
    (start,) = [n for n in notes if n["phase"] == "start"]
    assert 0 < start["reached_chip_s"] < setup["setup_s"]
    args, read = spec.layer_metric("reach_chip_s")
    assert read({"reached_chip_s": [start["reached_chip_s"]]}, **args) \
        == start["reached_chip_s"]
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "reach_chip_s"]
    assert entry["moves"] == "setup_s" and set(entry["workloads"]) \
        == {w["name"] for w in bench["workloads"]}


def test_the_compile_cache_is_a_directory_of_the_checkouts_own(
        monkeypatch):
    import jax

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_compilation_cache_max_size", 201326592)
        assert run.place_compile_cache() \
            == os.path.join(spec.ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_max_size == -1
        jax.config.update("jax_compilation_cache_dir", "/shared/jax")
        own = run.place_compile_cache()
        assert os.path.dirname(own) == "/shared/jax"
        monkeypatch.setattr(run, "ROOT", "/another/checkout")
        jax.config.update("jax_compilation_cache_dir", "/shared/jax")
        other = run.place_compile_cache()
        assert os.path.dirname(other) == "/shared/jax" and other != own
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)


# -- data discovery and the rules on names -------------------------------

def test_a_cell_made_only_of_added_files_is_found():
    bench = spec.load_benchmark(TEST_ROOT)
    w, config, traffic = spec.cell(bench, "tiny.open", TEST_ROOT)
    assert config["name"] == "tiny-serve" and traffic["kind"] == "open_loop"
    assert spec.runner(config).__name__ == "perfbench.runners.serve"
    assert spec.family(config, "reference") is ref
    args, read = spec.layer_metric("step_ms_max.added", TEST_ROOT)
    assert read({"step_s": [0.1, 0.3]}, **args) == 300.0
    # a quantity split by cell kind is read by one file
    assert spec.layer_metric("engine_step_ms.serve") \
        == spec.layer_metric("engine_step_ms.batch")
    with pytest.raises(spec.SpecError):
        spec.layer_metric("no_such_metric.serve", TEST_ROOT)
    with pytest.raises(spec.SpecError):
        spec.cell(bench, "no.such.cell", TEST_ROOT)


def test_the_committed_benchmark_and_its_files_agree():
    bench = spec.load_benchmark()
    assert bench["paths"] == ["perfbench"]
    for w in bench["workloads"]:
        _w, config, traffic = spec.cell(bench, w["name"])
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert spec.runner(config) and traffic["kind"]
        e2e = [m["name"] for m in
               spec.metrics_of(bench, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics_of(bench, "per_layer", w["name"], e2e)
    for m in bench["per_layer"]:
        assert callable(spec.layer_metric(m["name"])[1])


@pytest.mark.parametrize("field,bad", [
    ("name", "ttft p90"), ("name", "a,b"), ("name", "a/b"), ("name", ""),
    ("name", "x" * 65), ("name", "µs_per_step"),
    ("unit", "tokens per second"), ("unit", "µs"), ("unit", ""),
    ("unit", "x" * 17)])
def test_a_name_or_unit_outside_the_allowed_characters_is_rejected(field, bad):
    bench = copy.deepcopy(spec.load_benchmark(TEST_ROOT))
    bench["per_layer"][0][field] = bad
    with pytest.raises(spec.SpecError):
        spec.validate(bench)
    with pytest.raises(spec.SpecError):
        (spec.check_name if field == "name" else spec.check_unit)(bad)


# -- the load generator --------------------------------------------------

CHAT = spec.read_json(os.path.join(spec.ROOT, "perfbench", "traffic",
                                   "chat-steady.json"))


def test_a_seed_reproduces_the_request_list_and_every_seed_gets_the_same_work():
    a = loadgen.open_loop(2 ** 31 + 5, CHAT, 20.0, 256008)
    b = loadgen.open_loop(2 ** 31 + 5, CHAT, 20.0, 256008)
    c = loadgen.open_loop(7, CHAT, 20.0, 256008)
    assert len(a) == len(b) == len(c) == round(CHAT["rate_per_s"] * 20)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    # another seed: the same schedule and sizes, other token ids
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] \
        == [(r.due_s, len(r.prompt), r.max_new_tokens) for r in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert all(0 < r.due_s < 20.0 for r in a + c)
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    lens = [len(r.prompt) for r in a]
    assert min(lens) >= 32 and max(lens) <= 1024
    assert 150 < np.median(lens) < 240
    ta, la = loadgen.token_batch(9, 3, 4, 32, 1000)
    tb, _ = loadgen.token_batch(9, 3, 4, 32, 1000)
    assert np.array_equal(ta, tb) and np.array_equal(ta[:, 1:], la[:, :-1])
    assert len({r.tobytes() for r in ta}) == 4          # rows all differ


def test_the_program_receives_only_the_generated_inputs(monkeypatch):
    """What reaches ``engine.submit`` is the generator's list: prompts,
    token budgets, nothing else (no seed, no lengths to come)."""
    from perfbench.runners import serve

    seen = []
    real = serve.submit

    def spy(engine, rec):
        seen.append((rec.spec.index, rec.spec.prompt.tolist(),
                     rec.spec.max_new_tokens))
        return real(engine, rec)

    monkeypatch.setattr(serve, "submit", spy)
    line = rehearse("tiny.open", seed=31, seconds=1.0)
    _w, config, mix = spec.cell(spec.load_benchmark(TEST_ROOT), "tiny.open",
                                TEST_ROOT)
    want = loadgen.open_loop(31, mix, 1.0, config["vocab_size"])
    timed = [s for s in seen if s[0] >= 0]
    assert timed == [(r.index, r.prompt.tolist(), r.max_new_tokens)
                     for r in want]
    assert line["attempted"] == len(want)
