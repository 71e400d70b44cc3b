"""Python-level profiler tests (parity: fluid.profiler — SURVEY §5.1):
record_event aggregation, start/stop summary, chrome-trace export, the
context-manager API, and reset."""

import json
import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import profiler


def test_record_event_aggregates_and_dumps_chrome_trace(tmp_path, capsys):
    profiler.reset_profiler()
    profiler.start_profiler("All")
    for _ in range(3):
        with profiler.record_event("my_span"):
            np.dot(np.ones((64, 64)), np.ones((64, 64)))
    profiler.stop_profiler(sorted_key="calls")
    out = capsys.readouterr().out
    assert "my_span" in out and "Calls" in out
    # per-event stats: 3 calls recorded
    line = [l for l in out.splitlines() if l.startswith("my_span")][0]
    assert line.split()[1] == "3"

    path = str(tmp_path / "trace.json")
    n = profiler.dump_chrome_trace(path)
    with open(path) as f:
        trace = json.load(f)
    assert "traceEvents" in trace
    if n:  # native collector present: spans must be in the trace
        names = {e.get("name") for e in trace["traceEvents"]}
        assert "my_span" in names


def test_profiler_context_trains_and_writes_trace(tmp_path):
    x = fluid.layers.data(name="px", shape=[4], dtype="float32")
    loss = fluid.layers.mean(fluid.layers.fc(input=x, size=2))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    trace_dir = str(tmp_path / "jax_trace")
    with profiler.profiler("All", "total", trace_dir):
        for _ in range(2):
            exe.run(feed={"px": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
    # the jax trace dir gets XPlane artifacts (plugins/profile/...)
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found.extend(files)
    assert found, "jax.profiler produced no trace artifacts"


def test_reset_clears_stats(capsys):
    profiler.reset_profiler()
    profiler.start_profiler("All")
    with profiler.record_event("gone"):
        pass
    profiler.reset_profiler()
    profiler.stop_profiler()
    out = capsys.readouterr().out
    assert "gone" not in out


def test_named_scopes_reach_lowered_hlo():
    """Every descriptor op's identity must appear in the lowered module
    (jax.named_scope threading — the attribution the trace table keys on)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = layers.data(name="ns_x", shape=[8], dtype="float32")
    h = layers.fc(x, 16, act="relu",
                  param_attr=fluid.ParamAttr(name="ns_w"))
    loss = layers.reduce_mean(h)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    import numpy as np

    exe.run(feed={"ns_x": np.ones((4, 8), np.float32)}, fetch_list=[loss])
    from paddle_tpu.core.scope import global_scope

    step = next(s for s in exe._cache.values()
                if "ns_x" in s.feed_names)
    sc = global_scope()
    mut = {n: np.asarray(sc.get(n)) for n in step.mut_names}
    const = {n: np.asarray(sc.get(n)) for n in step.const_names}
    feeds = {"ns_x": np.ones((4, 8), np.float32)}
    lowered = step._jitted.lower(mut, const, feeds, np.uint32(1))
    txt = lowered.as_text(debug_info=True)
    for frag in ("fluid/mul__", "fluid/relu__", "fluid/sgd__"):
        assert frag in txt, frag


# ---------------------------------------------------------------------------
# observability layer: metrics registry, tracing spans, hot-path telemetry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_semantics():
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("t/c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("t/c") is c  # get-or-create
    import pytest as _pytest

    with _pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic
    with _pytest.raises(TypeError):
        reg.gauge("t/c")  # kind conflict

    g = reg.gauge("t/g")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert abs(g.value - 3.0) < 1e-12

    h = reg.histogram("t/h", buckets=(0.1, 1.0, 10.0))
    assert h.count == 0 and h.min == float("inf")  # empty sentinels
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4
    assert abs(h.sum - 55.55) < 1e-9
    assert h.min == 0.05 and h.max == 50.0
    assert h.bucket_counts == [1, 1, 1, 1]  # one per bucket + +Inf tail

    d = reg.to_dict()
    assert d["counters"]["t/c"] == 5
    assert d["histograms"]["t/h"]["count"] == 4
    # zero-observation histograms must not leak the inf sentinel
    reg.histogram("t/empty", buckets=(1.0,))
    d = reg.to_dict()
    assert "min" not in d["histograms"]["t/empty"]


def test_registry_prometheus_text_format():
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("exec/steps").inc(7)
    reg.gauge("reader/queue_depth").set(3)
    h = reg.histogram("exec/step_time", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    assert "# TYPE ptpu_exec_steps_total counter" in lines
    assert "ptpu_exec_steps_total 7" in lines
    assert "# TYPE ptpu_reader_queue_depth gauge" in lines
    assert "ptpu_reader_queue_depth 3" in lines
    # histogram buckets are CUMULATIVE and end at +Inf == count
    assert 'ptpu_exec_step_time_bucket{le="0.1"} 1' in lines
    assert 'ptpu_exec_step_time_bucket{le="1"} 2' in lines
    assert 'ptpu_exec_step_time_bucket{le="+Inf"} 3' in lines
    assert "ptpu_exec_step_time_count 3" in lines


def test_tracing_spans_nest_and_export_chrome_schema(tmp_path):
    from paddle_tpu.observability import tracing

    tracing.reset()
    tracing.enable()
    try:
        with tracing.span("outer", tag="a"):
            with tracing.span("inner"):
                pass
    finally:
        tracing.disable()
    path = str(tmp_path / "trace.json")
    n = tracing.dump_chrome_trace(path)
    assert n == 2
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    outer, inner = by_name["outer"], by_name["inner"]
    for e in evs:  # chrome-trace complete-event schema
        assert e["ph"] == "X"
        for k in ("pid", "tid", "ts", "dur"):
            assert isinstance(e[k], int), (k, e)
    assert outer["args"] == {"tag": "a"}
    # inner nests inside outer on the same thread
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    tracing.reset()


def test_telemetry_disabled_is_noop_fast_path():
    """With the switches off, instrumented call sites get shared null
    singletons — no per-step allocation. Force-disables around the body
    so the test holds even under a PTPU_METRICS=1 workflow env."""
    from paddle_tpu import observability as obs

    was_metrics = obs.metrics.enabled()
    was_tracing = obs.tracing.enabled()
    obs.disable()
    try:
        assert not obs.metrics.enabled()
        assert not obs.tracing.enabled()
        assert obs.counter("x") is obs.metrics.NULL_METRIC
        assert obs.histogram("y") is obs.counter("x")
        assert obs.span("z") is obs.tracing.NULL_SPAN
        obs.span("z").set(a=1)  # null span swallows everything
        with obs.span("z"):
            pass
        # and nothing above registered into the real registry
        assert "x" not in obs.registry().metrics()
    finally:
        if was_metrics:
            obs.metrics.enable()
        if was_tracing:
            obs.tracing.enable()


def test_executor_run_records_step_and_cache_metrics(tmp_path):
    """Acceptance: a 3-step toy program under metrics+tracing produces
    executor/step_time count==3, compile_cache hit>=1 and miss>=1, and a
    chrome trace whose events nest step > execute."""
    from paddle_tpu import observability as obs

    x = fluid.layers.data(name="obs_x", shape=[4], dtype="float32")
    loss = fluid.layers.mean(fluid.layers.fc(input=x, size=2))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    obs.registry().reset()
    obs.tracing.reset()
    obs.enable()
    try:
        for _ in range(3):
            exe.run(feed={"obs_x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
    finally:
        obs.disable()

    dump = str(tmp_path / "metrics.json")
    obs.dump_metrics(dump)
    with open(dump) as f:
        doc = json.load(f)
    assert doc["histograms"]["executor/step_time"]["count"] == 3
    assert doc["counters"]["compile_cache/hit"] >= 1
    assert doc["counters"]["compile_cache/miss"] >= 1
    assert doc["counters"]["executor/steps"] == 3
    assert doc["counters"]["executor/feed_bytes"] == 3 * 2 * 4 * 4
    assert doc["histograms"]["compile_cache/compile_time"]["count"] == 1
    assert doc["histograms"][
        "compile_cache/stablehlo_module_bytes"]["count"] == 1
    assert doc["counters"]["lowering/ops_traced"] > 0

    trace_path = str(tmp_path / "trace.json")
    obs.dump_chrome_trace(trace_path)
    with open(trace_path) as f:
        evs = json.load(f)["traceEvents"]
    steps = [e for e in evs if e["name"] == "step"]
    execs = [e for e in evs if e["name"] == "execute"]
    assert len(steps) == 3 and len(execs) == 3
    assert any(s["ts"] <= e["ts"]
               and e["ts"] + e["dur"] <= s["ts"] + s["dur"]
               for s in steps for e in execs), "execute must nest in step"
    obs.registry().reset()
    obs.tracing.reset()


def test_legacy_table_zero_call_event_prints_dash(capsys):
    """A registered-but-never-called event must render '-' (not inf)."""
    from paddle_tpu import profiler as prof

    prof.reset_profiler()
    prof._legacy.histogram("never_called")
    with prof.record_event("called_once"):
        pass
    prof.start_profiler("All")
    prof.stop_profiler()
    out = capsys.readouterr().out
    line = [l for l in out.splitlines() if l.startswith("never_called")][0]
    assert "inf" not in line
    assert line.split()[1] == "0"
    assert line.split()[3] == "-"
    stats = prof.event_stats()
    assert stats["never_called"]["calls"] == 0
    assert stats["never_called"]["min"] is None
    assert stats["called_once"]["calls"] == 1
    prof.reset_profiler()


def test_native_stats_accumulator_roundtrip(tmp_path):
    """profiler.cc value-stats: record behind ptpu_prof_enable, dump as
    JSON the Python telemetry tooling parses."""
    from paddle_tpu.core import native

    l = native.lib()
    if l is None:
        import pytest

        pytest.skip("native library unavailable")
    l.ptpu_prof_reset()
    l.ptpu_prof_stat_record(b"gated", 1.0)  # disabled: must not record
    assert l.ptpu_prof_stat_count(b"gated") == 0
    l.ptpu_prof_enable(1)
    try:
        for v in (100.0, 300.0, 200.0):
            l.ptpu_prof_stat_record(b"step_us", v)
    finally:
        l.ptpu_prof_enable(0)
    assert l.ptpu_prof_stat_count(b"step_us") == 3
    path = str(tmp_path / "stats.json")
    assert l.ptpu_prof_stats_dump_json(path.encode()) == 1
    with open(path) as f:
        doc = json.load(f)
    s = doc["stats"]["step_us"]
    assert s["count"] == 3 and s["min"] == 100.0 and s["max"] == 300.0
    assert abs(s["avg"] - 200.0) < 1e-9
    # the stats CLI renders this schema
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ptpu_stats.py"),
         path], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "step_us" in out.stdout
    l.ptpu_prof_reset()
