"""Span-based host tracing with Chrome-trace/Perfetto JSON export.

`span("compile")` is a nestable, thread-safe context manager. Each
completed span is recorded as one chrome://tracing complete ("X") event
(the format tools/timeline.py merges and Perfetto/chrome://tracing open
directly). Device-side alignment: while a `jax.profiler` trace is
active, every span also enters a `jax.profiler.TraceAnnotation`, so the
host spans show up on the XPlane timeline next to the XLA device rows —
the CUPTI DeviceTracer correlation the reference had (SURVEY §5.1).
Spans are additionally forwarded to the native C++ collector
(native/profiler.cc ptpu_prof_mark) when it is loaded and enabled, so
one chrome-trace dump can carry Python, C++, and device work. A span
never loads (let alone builds) the library itself: only a caller that
already loaded it (`profiler.start_profiler`) can have enabled its
collector.

Enablement mirrors metrics.py: OFF unless `PTPU_TRACE=1` or
`PTPU_TRACE_DIR=<dir>` is set (or `enable()` is called); when off,
`span()` returns a shared null context manager — no per-call
allocation. Buffering is a bounded ring (`MAX_EVENTS`): the newest
spans win, and the dump carries a `ptpuDroppedSpans` eviction count.
"""

import collections
import itertools
import json
import os
import threading
import time

__all__ = ["span", "complete", "instant", "annotation", "new_trace_id",
           "enabled", "enable", "disable", "events", "dump_chrome_trace",
           "reset", "MAX_EVENTS"]

MAX_EVENTS = 200000

# ring buffer: the NEWEST spans win (the tail of a long run is what gets
# debugged); evictions are counted into the dump's ptpuDroppedSpans note
_events = collections.deque(maxlen=MAX_EVENTS)
_dropped = 0
# deliberately a PLAIN lock, not a tracked one (docs/STATIC_ANALYSIS.md):
# this module executes during package bootstrap, before
# paddle_tpu.analysis exists, and the ring-buffer append it guards is the
# tracing hot path — it nests no other lock, so there is no order to
# observe
_lock = threading.Lock()
_pid = os.getpid()

# request-scoped tracing identity: trace ids are minted once per request
# (ServingEngine.submit / RouterRequest) and survive failover re-dispatch;
# span ids are minted per recorded span. itertools.count is a single
# C-level op, safe to share across threads without the ring lock.
_trace_seq = itertools.count(1)
_span_seq = itertools.count(1)


def new_trace_id():
    """Process-unique request trace id ("<pid>.<seq>" hex)."""
    return "%x.%x" % (_pid, next(_trace_seq))

_jax_profiler = None  # resolved lazily; False = unavailable


def _annotation(name):
    """jax.profiler.TraceAnnotation if jax is importable, else None."""
    global _jax_profiler
    if _jax_profiler is None:
        try:
            from jax import profiler as jp
            _jax_profiler = jp
        except Exception:
            _jax_profiler = False
    if _jax_profiler:
        try:
            return _jax_profiler.TraceAnnotation(name)
        except Exception:
            return None
    return None


def annotation(name):
    """A host span on the PROFILER's clock only
    (`jax.profiler.TraceAnnotation`: it lands on the `/host:CPU` plane
    of a `jax.profiler` capture, beside the device rows, and costs about
    a microsecond when no capture is running), or the null span where
    jax is missing. Not gated on `enabled()`: the step logs
    (`ptpu/engine.*`, `ptpu/exe.*`, docs/OBSERVABILITY.md) gate on their
    own switch."""
    return _annotation(name) or NULL_SPAN


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        return self  # chains like Span.set: `with span(...).set(...)`


NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("name", "args", "trace_id", "span_id", "parent_id",
                 "_t0", "_ann")

    def __init__(self, name, args=None, trace_id=None, parent_id=None):
        self.name = name
        self.args = args
        self.trace_id = trace_id
        self.parent_id = parent_id
        # span ids only exist on request-scoped spans: anonymous spans
        # keep the exact pre-trace_id event shape (defaults-off identity)
        self.span_id = next(_span_seq) if trace_id is not None else None
        self._t0 = 0
        self._ann = None

    def set(self, **args):
        """Attach key/values rendered in the trace viewer's args pane."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        return self

    def __enter__(self):
        ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self._ann = ann
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        ts = self._t0 // 1000
        dur = (t1 - self._t0) // 1000
        ev = {"name": self.name, "ph": "X", "pid": _pid,
              "tid": threading.get_ident() % 100000, "ts": ts, "dur": dur,
              "cat": "host"}
        args = self.args
        if self.trace_id is not None:
            args = dict(args) if args else {}
            args["trace_id"] = self.trace_id
            args["span_id"] = self.span_id
            if self.parent_id is not None:
                args["parent_id"] = self.parent_id
        if args:
            ev["args"] = args
        _record(ev)
        _forward_native(self.name, ts, ts + dur)
        return False


def _record(ev):
    global _dropped
    evicted = False
    with _lock:
        if len(_events) == MAX_EVENTS:
            _dropped += 1  # deque evicts the oldest on append
            evicted = True
        _events.append(ev)
    if evicted:
        # promoted to a first-class counter so CI can gate on trace loss
        # without parsing the chrome dump; incremented OUTSIDE the plain
        # ring lock — the metric's tracked lock must not nest under it
        _metrics.counter("trace/dropped_spans").inc()


def _forward_native(name, us_start, us_end):
    """Mirror the span into the C++ collector when it is live+enabled,
    so ptpu_prof_dump_chrome sees host spans too. `native.loaded()`,
    never `native.lib()`: a span's exit must not run `make` (it did, on
    the first traced step, inside the serving worker)."""
    l = _native.loaded()
    if l is not None and l.ptpu_prof_enabled():
        l.ptpu_prof_mark(name.encode(), us_start, us_end)


from ..core import native as _native  # stdlib-only, loads nothing
from . import metrics as _metrics
from .metrics import _env_on  # central flags-registry check

_ENABLED = _env_on("PTPU_TRACE") or _env_on("PTPU_TRACE_DIR")


def enabled():
    return _ENABLED


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def span(name, trace_id=None, parent_id=None, **args):
    """A context manager timing one named region; nested spans nest in
    the exported trace. No-op singleton (zero allocation) when disabled.
    Pass `trace_id` (from `new_trace_id()`) to stamp the span with a
    request identity — it gets a span id, and `trace_id`/`span_id`/
    `parent_id` land in the event's args pane."""
    if not _ENABLED:
        return NULL_SPAN
    return Span(name, args or None, trace_id, parent_id)


def complete(name, t0_ns, t1_ns, trace_id=None, parent_id=None, **args):
    """Record an already-timed region as one complete event with explicit
    `perf_counter_ns` bounds — for retroactive request-scoped spans such
    as queue_wait, whose start predates the emit site. Returns the span
    id (None when tracing is off or no trace_id was given)."""
    if not _ENABLED:
        return None
    span_id = next(_span_seq) if trace_id is not None else None
    ts = t0_ns // 1000
    dur = max(0, (t1_ns - t0_ns) // 1000)
    ev = {"name": name, "ph": "X", "pid": _pid,
          "tid": threading.get_ident() % 100000, "ts": ts, "dur": dur,
          "cat": "host"}
    if trace_id is not None:
        args["trace_id"] = trace_id
        args["span_id"] = span_id
        if parent_id is not None:
            args["parent_id"] = parent_id
    if args:
        ev["args"] = args
    _record(ev)
    _forward_native(name, ts, ts + dur)
    return span_id


def instant(name, trace_id=None, parent_id=None, **args):
    """Zero-duration marker event at now (readmit, deadline_expired)."""
    t = time.perf_counter_ns()
    return complete(name, t, t, trace_id=trace_id, parent_id=parent_id,
                    **args)


def events():
    """Snapshot of the recorded chrome-trace events."""
    with _lock:
        return list(_events)


def reset():
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def dump_chrome_trace(path):
    """Write {"traceEvents": [...]} Chrome-trace JSON (open in Perfetto:
    ui.perfetto.dev > Open trace file). Returns the event count."""
    with _lock:
        evs = list(_events)
        dropped = _dropped
    doc = {"traceEvents": evs, "displayTimeUnit": "ms"}
    if dropped:
        doc["ptpuDroppedSpans"] = dropped
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(evs)
