"""Profiler (parity: python/paddle/fluid/profiler.py + platform/profiler.cc
+ tools/timeline.py).

TPU-native: wraps jax.profiler (XPlane) for device traces — the replacement
for the CUPTI DeviceTracer (SURVEY §5.1) — plus a lightweight host-side
event aggregator with the reference's calls/avg/max/min table output.
Traces are viewable in TensorBoard/Perfetto (the chrome://tracing shape the
reference's timeline.py produced).
"""

import contextlib
import os
import time

from .observability import metrics as _obs_metrics
from .observability import tracing as _obs_tracing

__all__ = ["cuda_profiler", "reset_profiler", "profiler", "start_profiler",
           "stop_profiler", "record_event", "dump_chrome_trace",
           "event_stats"]

# Legacy aggregator, rebuilt on the observability registry: each
# record_event name is one histogram in this dedicated always-on registry
# (the fluid profiler API predates the PTPU_METRICS switch and must
# aggregate whenever used, so it does not share the global gate).
_legacy = _obs_metrics.MetricsRegistry()
_active = [False]
_trace_dir = [None]


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Accelerator passthrough profiler (nvprof parity shim): emits a JAX
    device trace instead."""
    with profiler("All", "total", output_file):
        yield


def reset_profiler():
    _legacy.reset()


def event_stats():
    """{event name: {'calls', 'total', 'avg', 'max', 'min'}} in seconds —
    the table _print_summary renders, as data."""
    out = {}
    for name, h in _legacy.metrics().items():
        out[name] = {"calls": h.count, "total": h.sum, "avg": h.avg,
                     "max": h.max if h.count else None,
                     "min": h.min if h.count else None}
    return out


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    if _active[0]:
        return
    _active[0] = True
    from .core import native

    l = native.lib()
    if l is not None:
        l.ptpu_prof_enable(1)
    if trace_dir:
        import jax

        _trace_dir[0] = trace_dir
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    if not _active[0]:
        return
    _active[0] = False
    from .core import native

    l = native.lib()
    if l is not None:
        l.ptpu_prof_enable(0)
    if _trace_dir[0]:
        import jax

        jax.profiler.stop_trace()
        _trace_dir[0] = None
    _print_summary(sorted_key)


def _print_summary(sorted_key=None):
    hists = _legacy.metrics()
    if not hists:
        return
    rows = []
    for name, h in hists.items():
        # zero-call events (registered but never observed) carry the
        # histogram's +/-inf sentinels; keep them sortable here and
        # render them as '-' below instead of leaking inf into the table
        rows.append((name, h.count, h.sum, h.avg,
                     h.max if h.count else 0.0,
                     h.min if h.count else 0.0))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "max": 4, "min": 5}.get(
        sorted_key, 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    print("%-40s %8s %12s %12s %12s %12s" % (
        "Event", "Calls", "Total(ms)", "Avg(ms)", "Max(ms)", "Min(ms)"))
    for name, calls, total, avg, mx, mn in rows:
        if calls == 0:
            print("%-40s %8d %12.4f %12s %12s %12s" % (
                name, 0, 0.0, "-", "-", "-"))
            continue
        print("%-40s %8d %12.4f %12.4f %12.4f %12.4f" % (
            name, calls, total * 1e3, avg * 1e3, mx * 1e3, mn * 1e3))


@contextlib.contextmanager
def record_event(name):
    """Host-side RAII event marker (parity: platform/profiler.h RecordEvent).
    When the native library is present, spans also land in the C++ collector
    (platform/profiler.cc parity) for chrome-trace export; when span tracing
    is on (PTPU_TRACE), they land in the observability chrome trace too."""
    from .core import native

    l = native.lib()
    span = _obs_tracing.span(name)
    # when span tracing is on, Span.__exit__ already forwards the interval
    # to the native collector (ptpu_prof_mark) — pushing here too would
    # record every event twice in the chrome-trace dump
    use_native = (l is not None and _active[0]
                  and not _obs_tracing.enabled())
    t0 = time.perf_counter()
    if use_native:
        l.ptpu_prof_push(name.encode())
    span.__enter__()
    try:
        yield
    finally:
        span.__exit__(None, None, None)
        if use_native:
            l.ptpu_prof_pop()
        _legacy.histogram(name).observe(time.perf_counter() - t0)


def dump_chrome_trace(path):
    """Export collected host events as chrome://tracing JSON (parity:
    tools/timeline.py). Returns the number of events written."""
    from .core import native

    l = native.lib()
    if l is None:
        import json as _json

        with open(path, "w") as f:
            _json.dump({"traceEvents": []}, f)
        return 0
    return l.ptpu_prof_dump_chrome(path.encode())


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option=None):
    """Context profiler (parity: fluid.profiler.profiler). Starts a JAX
    device trace when profile_path is a directory-like path."""
    trace_dir = None
    if profile_path and not profile_path.endswith((".txt", ".pb")):
        trace_dir = profile_path
        os.makedirs(trace_dir, exist_ok=True)
    start_profiler(state, tracer_option, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
