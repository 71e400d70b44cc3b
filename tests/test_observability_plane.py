"""Production observability plane (docs/OBSERVABILITY.md): per-request
trace ids through the tracer, the crash-safe flight recorder, the live
/metrics + /healthz + /varz endpoint, Histogram quantiles, cost
analysis of compiled steps, and the ptpu_stats --diff/--url sources.

Everything here is host-side (one tiny jit for the cost-analysis leg);
each test restores the global tracer/recorder/registry state it touches
so the rest of the suite keeps its defaults-off identity.
"""

import json
import math
import os
import sys
import threading
import urllib.error
import urllib.request

import pytest

from paddle_tpu.observability import (flight_recorder, metrics,
                                      tracing)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))
import ptpu_stats  # noqa: E402


# ---------------------------------------------------------------------------
# Histogram quantiles
# ---------------------------------------------------------------------------


def test_histogram_quantile_interpolates_within_buckets():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("q/lat")
    for i in range(1, 101):
        h.observe(i / 1000.0)  # uniform 1..100 ms
    assert abs(h.quantile(0.50) - 0.050) < 0.005
    assert abs(h.quantile(0.95) - 0.095) < 0.005
    assert abs(h.quantile(0.99) - 0.099) < 0.005
    # clamped to the observed range at the extremes
    assert h.quantile(0.0) >= h.min
    assert h.quantile(1.0) <= h.max
    with pytest.raises(ValueError):
        h.quantile(1.5)
    d = h.to_dict()
    for k in ("p50", "p95", "p99"):
        assert k in d, d


def test_histogram_quantile_empty_and_overflow_tail():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("q/empty")
    assert h.quantile(0.5) == 0.0
    assert "p50" not in h.to_dict()
    # all mass past the largest bound lands in +Inf: the quantile
    # answers max, not inf
    h2 = reg.histogram("q/tail", buckets=(0.001,))
    for _ in range(10):
        h2.observe(5.0)
    assert h2.quantile(0.99) == 5.0


def test_engine_latency_percentiles_come_from_histograms():
    """The deque(1024) windows are gone: the ttft/latency p50/p99 gauges
    are now Histogram.quantile over the full-run histograms."""
    import paddle_tpu.serving.engine as engine_mod

    assert not hasattr(engine_mod, "_percentile")
    src = open(engine_mod.__file__).read()
    assert "deque(maxlen=1024)" not in src


# ---------------------------------------------------------------------------
# Prometheus exposition hardening (satellite 3)
# ---------------------------------------------------------------------------


def test_prom_name_collision_raises_instead_of_silently_merging():
    reg = metrics.MetricsRegistry()
    reg.counter("a/b").inc()
    reg.counter("a.b").inc()  # both mangle to ptpu_a_b
    with pytest.raises(ValueError, match="collision"):
        reg.to_prometheus()


def test_nan_and_inf_gauges_roundtrip_through_scrape(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.gauge("w/nan").set(float("nan"))
    reg.gauge("w/pinf").set(float("inf"))
    reg.gauge("w/ninf").set(float("-inf"))
    text = reg.to_prometheus()
    assert "ptpu_w_nan NaN" in text
    assert "ptpu_w_pinf +Inf" in text
    assert "ptpu_w_ninf -Inf" in text
    # and through the dump -> ptpu_stats --prometheus path
    path = str(tmp_path / "m.json")
    reg.dump_json(path)
    doc = json.load(open(path))
    assert math.isnan(doc["gauges"]["w/nan"])
    text2 = ptpu_stats._to_prometheus(doc)
    assert "ptpu_w_nan NaN" in text2
    assert "ptpu_w_pinf +Inf" in text2


def test_concurrent_observe_during_scrape_is_lock_clean(monkeypatch):
    """Hammer observe() from N threads while another scrapes
    to_prometheus()/to_dict(), under the lock tracker with switch-
    interval jitter: no tracker violations, no torn exposition."""
    monkeypatch.setenv("PTPU_LOCK_CHECK", "1")
    from paddle_tpu.analysis import concurrency

    reg = metrics.MetricsRegistry()
    h = reg.histogram("race/obs")
    c = reg.counter("race/n")
    stop = threading.Event()
    errors = []
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def writer():
            i = 0
            while not stop.is_set():
                h.observe((i % 100) / 1000.0)
                c.inc()
                i += 1

        threads = [threading.Thread(target=writer, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(50):
                try:
                    text = reg.to_prometheus()
                    assert "ptpu_race_obs_count" in text
                    d = reg.to_dict()
                    hd = d["histograms"]["race/obs"]
                    # bucket mass never exceeds the count read later
                    assert sum(hd["buckets"].values()) <= reg.histogram(
                        "race/obs").count
                except Exception as e:  # pragma: no cover - fail loud
                    errors.append(e)
                    break
        finally:
            stop.set()
            for t in threads:
                t.join(10)
    finally:
        sys.setswitchinterval(old_interval)
    assert not errors, errors
    concurrency.assert_clean()


# ---------------------------------------------------------------------------
# per-request tracing (the tentpole's trace-id layer)
# ---------------------------------------------------------------------------


def _traced_events():
    return tracing.events()


def test_trace_ids_stamp_span_events_and_anonymous_spans_stay_bare():
    tracing.reset()
    tracing.enable()
    try:
        tid = tracing.new_trace_id()
        assert isinstance(tid, str) and "." in tid
        assert tracing.new_trace_id() != tid
        with tracing.span("traced_op", trace_id=tid, request=7):
            pass
        with tracing.span("anon_op", tag="x"):
            pass
        sid = tracing.complete("post_hoc", 1000, 3000, trace_id=tid)
        tracing.instant("marker", trace_id=tid, parent_id=sid)
    finally:
        tracing.disable()
    evs = {e["name"]: e for e in _traced_events()}
    traced = evs["traced_op"]["args"]
    assert traced["trace_id"] == tid
    assert isinstance(traced["span_id"], int)
    assert traced["request"] == 7
    # anonymous spans keep the exact pre-trace_id event shape
    assert evs["anon_op"]["args"] == {"tag": "x"}
    post = evs["post_hoc"]
    assert post["ts"] == 1 and post["dur"] == 2
    assert evs["marker"]["args"]["parent_id"] == sid
    assert evs["marker"]["dur"] == 0
    tracing.reset()


def test_ring_eviction_bumps_dropped_spans_counter(monkeypatch):
    import collections

    tracing.reset()
    monkeypatch.setattr(tracing, "MAX_EVENTS", 4)
    monkeypatch.setattr(tracing, "_events",
                        collections.deque(maxlen=4))
    was_metrics = metrics.enabled()
    metrics.enable()
    reg = metrics.registry()
    before = reg.counter("trace/dropped_spans").value
    tracing.enable()
    try:
        for i in range(7):
            tracing.instant("spam", i=i)
    finally:
        tracing.disable()
        if not was_metrics:
            metrics.disable()
    assert len(tracing.events()) == 4
    assert reg.counter("trace/dropped_spans").value - before == 3


def test_generation_request_trace_id_defaults_off():
    """Tracing off => no trace_id minted anywhere (the defaults-off
    identity the acceptance gate checks)."""
    from paddle_tpu.serving.scheduler import GenerationRequest

    was = tracing.enabled()
    tracing.disable()
    try:
        req = GenerationRequest([1, 2, 3], max_new_tokens=4)
        assert req.trace_id is None
    finally:
        if was:
            tracing.enable()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


@pytest.fixture
def recorder(tmp_path):
    """Fresh enabled recorder writing under tmp_path; restores the
    defaults-off global state afterwards."""
    flight_recorder.reset()
    flight_recorder.enable(str(tmp_path), capacity=8)
    yield flight_recorder
    flight_recorder.reset()
    flight_recorder.disable()


def test_recorder_off_by_default_records_nothing():
    """Force-disabled body (the test must hold even under a
    PTPU_BLACKBOX_DIR workflow env, mirroring the telemetry
    defaults-off test)."""
    was = flight_recorder.enabled()
    flight_recorder.disable()
    try:
        before = len(flight_recorder.events())
        flight_recorder.record_event("worker_dead", model="x")
        assert len(flight_recorder.events()) == before
        assert flight_recorder.dump("worker_dead") is None
    finally:
        if was:
            flight_recorder.enable()


def test_recorder_ring_bounds_and_drop_accounting(recorder):
    for i in range(12):
        recorder.record_event("rollback", step=i)
    evs = recorder.events()
    assert len(evs) == 8
    assert [e["step"] for e in evs] == list(range(4, 12))
    assert recorder.dropped() == 4
    for e in evs:
        assert e["type"] == "rollback"
        assert isinstance(e["ts"], float)
        assert e["thread"]


def test_recorder_dump_is_atomic_and_structured(recorder, tmp_path):
    recorder.record_event("replica_dead", replica=0, error="boom")
    recorder.record_event("readmit", request=3, replica=1)
    path = recorder.dump("replica_dead")
    assert path and os.path.exists(path)
    assert os.path.basename(path).startswith("ptpu_blackbox_")
    assert path.endswith("_replica_dead.json")
    # no torn tmp file left behind
    assert not [f for f in os.listdir(str(tmp_path))
                if f.startswith(".ptpu_tmp_")]
    doc = json.load(open(path))
    assert doc["reason"] == "replica_dead"
    assert doc["pid"] == os.getpid()
    assert [e["type"] for e in doc["events"]] == ["replica_dead",
                                                  "readmit"]


def test_lock_check_failure_dumps_before_raising(recorder, monkeypatch):
    """concurrency.assert_clean's passive hook: a LockCheckError ships a
    lock_check_failed dump."""
    from paddle_tpu.analysis import concurrency

    monkeypatch.setattr(
        concurrency, "violations",
        lambda: [concurrency.LockViolation("order",
                                           "synthetic violation")])
    with pytest.raises(concurrency.LockCheckError):
        concurrency.assert_clean()
    types = [e["type"] for e in recorder.events()]
    assert "lock_check_failed" in types
    dumps = [f for f in os.listdir(recorder._DIR)
             if f.endswith("_lock_check_failed.json")]
    assert dumps


def test_engine_worker_death_dumps_worker_dead(recorder):
    """An uncaught worker death records worker_dead and dumps — driven
    through a real (tiny) engine via the fault injector."""
    from paddle_tpu import resilience, serving

    model = serving.GenerationModel.random(
        serving.GenerationConfig(vocab_size=32, d_model=16, n_heads=2,
                                 n_layers=1, d_ff=32, max_seq_len=32),
        seed=0, name="bbox")
    prev = resilience.set_global_injector(
        resilience.FaultInjector("serve_die_at_step:2"))
    try:
        import warnings

        from paddle_tpu import serving

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            with serving.ServingEngine(model, max_batch=2,
                                       max_seq_len=32,
                                       block_size=4) as eng:
                req = eng.submit([1, 2, 3], max_new_tokens=8)
                with pytest.raises(Exception):
                    req.wait(120)
    finally:
        resilience.set_global_injector(prev)
    types = [e["type"] for e in recorder.events()]
    assert "worker_dead" in types
    assert any(f.endswith("_worker_dead.json")
               for f in os.listdir(recorder._DIR))


# ---------------------------------------------------------------------------
# live endpoint
# ---------------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode("utf-8")


@pytest.fixture
def live_endpoint():
    from paddle_tpu.observability import endpoint

    was_metrics = metrics.enabled()
    metrics.enable()
    endpoint.start(0)
    yield endpoint
    endpoint.stop()
    if not was_metrics:
        metrics.disable()


def test_endpoint_off_by_default_no_thread():
    from paddle_tpu.observability import endpoint

    assert endpoint.port() is None
    assert not any(t.name == "ptpu-metrics-endpoint"
                   for t in threading.enumerate())


def test_endpoint_metrics_and_varz_match_registry(live_endpoint):
    reg = metrics.registry()
    reg.counter("live/scrapes").inc(2)
    status, text = _get(live_endpoint.url("/metrics"))
    assert status == 200
    assert text == reg.to_prometheus()
    status, body = _get(live_endpoint.url("/varz"))
    assert status == 200
    assert json.loads(body) == json.loads(
        json.dumps(reg.to_dict(), sort_keys=True))
    # unknown route: 404, server stays up
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(live_endpoint.url("/nope"))
    assert err.value.code == 404
    assert _get(live_endpoint.url("/metrics"))[0] == 200


def test_endpoint_healthz_aggregates_providers(live_endpoint):
    live_endpoint.register_health_provider(
        "unit", lambda: {"alive": True})
    try:
        status, body = _get(live_endpoint.url("/healthz"))
        doc = json.loads(body)
        assert status == 200 and doc["status"] == "ok"
        assert doc["providers"]["unit"] == {"alive": True}

        def broken():
            raise RuntimeError("wedged")

        live_endpoint.register_health_provider("bad", broken)
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(live_endpoint.url("/healthz"))
        assert err.value.code == 503
        doc = json.loads(err.value.read().decode("utf-8"))
        assert doc["status"] == "degraded"
        assert "wedged" in doc["providers"]["bad"]["error"]
    finally:
        live_endpoint.unregister_health_provider("unit")
        live_endpoint.unregister_health_provider("bad")


# ---------------------------------------------------------------------------
# compiled-step cost analysis
# ---------------------------------------------------------------------------


def test_cost_publish_and_mfu():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import cost

    was_metrics = metrics.enabled()
    metrics.enable()
    try:
        compiled = jax.jit(
            lambda a, b: jnp.dot(a, b)).lower(
                jnp.ones((16, 16)), jnp.ones((16, 16))).compile()
        out = cost.publish(compiled)
        assert out["step_flops"] > 0
        g = metrics.registry().to_dict()["gauges"]
        assert g["exec/step_flops"] == out["step_flops"]
        assert g["exec/step_bytes_accessed"] > 0
        assert g["exec/peak_hbm_bytes"] > 0
    finally:
        if not was_metrics:
            metrics.disable()
    # the peak table is keyed by device_kind; a device it does not hold
    # (this CPU) has no peak and publishes no MFU — never a default
    assert cost.peak_flops("TPU v5 lite") == 197e12
    assert abs(cost.mfu_pct(197e12, 1.0, "TPU v5 lite") - 100.0) < 1e-9
    with pytest.raises(KeyError, match="no peak"):
        cost.peak_flops()
    assert cost.mfu_pct(1e11, 1.0) is None


# ---------------------------------------------------------------------------
# ptpu_stats --diff / --url
# ---------------------------------------------------------------------------


def test_ptpu_stats_diff_subtracts_counters(tmp_path, capfd):
    a = {"counters": {"d/c": 2}, "gauges": {"d/g": 1.0},
         "histograms": {"d/h": {"count": 3, "sum": 0.3}}}
    b = {"counters": {"d/c": 7}, "gauges": {"d/g": 4.0},
         "histograms": {"d/h": {"count": 10, "sum": 1.0}}}
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(a, open(pa, "w"))
    json.dump(b, open(pb, "w"))
    rc = ptpu_stats.main(["--diff", pa, pb])
    out = capfd.readouterr().out
    assert rc == 0
    row = [ln for ln in out.splitlines() if ln.startswith("d/c")][0]
    assert row.split() == ["d/c", "2", "7", "5"]
    hrow = [ln for ln in out.splitlines() if ln.startswith("d/h")][0]
    assert hrow.split()[-1] == "7"
    # --diff wants exactly two sources
    with pytest.raises(SystemExit):
        ptpu_stats.main(["--diff", pa])


def test_ptpu_stats_url_scrapes_varz_and_metrics(live_endpoint,
                                                capfd):
    reg = metrics.registry()
    reg.counter("scrape/hits").inc(5)
    rc = ptpu_stats.main(["--url", live_endpoint.url("/varz"),
                          "--assert-min", "scrape/hits=5"])
    assert rc == 0
    assert "scrape/hits" in capfd.readouterr().out
    # the Prometheus route parses best-effort under mangled names
    rc = ptpu_stats.main(["--url", live_endpoint.url("/metrics")])
    out = capfd.readouterr().out
    assert rc == 0
    assert "ptpu_scrape_hits_total" in out


def test_ptpu_stats_parse_prometheus_histograms():
    text = ("# TYPE ptpu_x_lat histogram\n"
            'ptpu_x_lat_bucket{le="0.01"} 2\n'
            'ptpu_x_lat_bucket{le="+Inf"} 3\n'
            "ptpu_x_lat_sum 0.05\n"
            "ptpu_x_lat_count 3\n"
            "# TYPE ptpu_x_n_total counter\n"
            "ptpu_x_n_total 9\n"
            "# TYPE ptpu_x_g gauge\n"
            "ptpu_x_g NaN\n")
    doc = ptpu_stats._parse_prometheus(text)
    assert doc["histograms"]["ptpu_x_lat"] == {"count": 3, "sum": 0.05}
    assert doc["counters"]["ptpu_x_n_total"] == 9
    assert math.isnan(doc["gauges"]["ptpu_x_g"])
