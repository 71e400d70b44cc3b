"""Unified telemetry layer: metrics registry + span tracing.

The measurement substrate for the whole framework (see
docs/OBSERVABILITY.md). Two sub-facilities, individually switchable:

  metrics  — process-wide counters/gauges/histograms/raw samples with
             JSON and Prometheus exposition. Enable with PTPU_METRICS=1; set
             PTPU_METRICS_OUT=<path> to dump JSON at process exit.
  tracing  — nestable host spans exported as Chrome-trace/Perfetto
             JSON, forwarded to jax.profiler.TraceAnnotation (device
             XPlane alignment) and the native C++ collector. Enable
             with PTPU_TRACE=1, or PTPU_TRACE_DIR=<dir> to also write
             <dir>/ptpu_trace.json at process exit.

Instrumented hot paths (Executor.run per-step wall time + feed/fetch
bytes, the compiled-program cache, program lowering, PyReader's feed
queue) check one module-level bool and touch shared null objects when
telemetry is off — the disabled path allocates nothing per step.

The legacy `paddle_tpu.profiler` event table is a facade over this
registry since the telemetry PR; prefer these APIs in new code.
"""

import atexit
import os
import time

from . import flight_recorder, metrics, tracing
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, Samples, counter, gauge, histogram,
                      registry, samples)
from .tracing import span  # noqa: F401

__all__ = ["metrics", "tracing", "flight_recorder", "span", "counter",
           "gauge", "histogram", "samples", "registry", "enabled",
           "enable", "disable", "dump_metrics", "dump_chrome_trace",
           "Counter", "Gauge", "Histogram", "Samples", "MetricsRegistry"]


def enabled():
    """True when any telemetry facility is on."""
    return metrics.enabled() or tracing.enabled()


def enable():
    """Turn on both metrics and tracing (programmatic alternative to
    PTPU_METRICS=1 PTPU_TRACE=1)."""
    metrics.enable()
    tracing.enable()


def disable():
    metrics.disable()
    tracing.disable()


def dump_metrics(path):
    """Write the process-wide registry as JSON (tools/ptpu_stats.py
    renders it)."""
    return metrics.dump_json(path)


def dump_chrome_trace(path):
    """Write collected spans as Chrome-trace JSON (open in Perfetto)."""
    return tracing.dump_chrome_trace(path)


class _StepScope:
    """One executor step's shared instrumentation: a `step` span plus the
    executor/step_time histogram and executor/steps counter — used by
    both Executor.run and CompiledProgram._run so the two paths cannot
    drift. step_time is recorded only on clean exit (an op raising
    mid-step would otherwise pollute the latency distribution)."""

    __slots__ = ("_rec", "_span", "_t0")

    def __enter__(self):
        self._rec = metrics.enabled()
        self._span = tracing.span("step")
        self._span.__enter__()
        self._t0 = time.perf_counter() if self._rec else 0.0
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        if self._rec and exc[0] is None:
            reg = metrics.registry()
            reg.histogram("executor/step_time").observe(
                time.perf_counter() - self._t0)
            reg.counter("executor/steps").inc()
        return False


def step_scope():
    """Context manager instrumenting one executor step; the shared
    no-op singleton when telemetry is fully disabled (no allocation)."""
    if not (metrics.enabled() or tracing.enabled()):
        return tracing.NULL_SPAN
    return _StepScope()


def _exit_dumps():
    from .. import flags as _flags

    out = _flags.env("PTPU_METRICS_OUT")
    if out:
        try:
            metrics.dump_json(out)
        except OSError:
            pass
    tdir = _flags.env("PTPU_TRACE_DIR")
    if tdir:
        try:
            os.makedirs(tdir, exist_ok=True)
            tracing.dump_chrome_trace(os.path.join(tdir, "ptpu_trace.json"))
        except OSError:
            pass


from .. import flags as _flags  # noqa: E402  (stdlib-only, cycle-free)

if _flags.env("PTPU_METRICS_OUT") or _flags.env("PTPU_TRACE_DIR"):
    atexit.register(_exit_dumps)

if _flags.env("PTPU_METRICS_PORT") is not None:
    # live scrape surface, same conditional-startup pattern as the exit
    # dumps: no flag, no import, no thread
    from . import endpoint as _endpoint  # noqa: E402

    _endpoint.start()
