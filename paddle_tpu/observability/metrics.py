"""Process-wide metrics registry (counters, gauges, histograms, raw
samples).

The measurement substrate every perf PR reports through (ROADMAP north
star: before/after numbers come from the framework itself, not ad-hoc
timers). Exposition formats: a JSON dump (`MetricsRegistry.to_dict` /
`dump_json`, rendered by tools/ptpu_stats.py) and Prometheus text
(`to_prometheus`) for scrape-style deployments of native_serve hosts.

Enablement contract: telemetry is OFF unless `PTPU_METRICS` is set (or
`enable()` is called), and the disabled path is a no-op fast path — the
module-level `counter()/gauge()/histogram()` helpers hand back shared
null singletons, so instrumented hot loops allocate nothing per step.
Registry objects themselves are always live: going through
`registry()` directly (bench.py --metrics-out does, so its results
share the dump with any executor telemetry) or constructing a private
`MetricsRegistry()` bypasses the global switch — explicit use IS the
opt-in.

Threading: one lock per registry guards name->metric creation, and each
metric guards its own mutation — `x += n` is a load/add/store sequence
the GIL can interleave, so counters shared across threads (the reader
thread and the main loop both live in one process) would drop updates
without it.
"""

import collections
import json
import math
import sys
import threading

__all__ = ["Counter", "Gauge", "Histogram", "Samples", "MetricsRegistry",
           "registry", "counter", "gauge", "histogram", "samples",
           "enabled", "enable", "disable", "dump_json", "to_prometheus",
           "reset"]

# default histogram bucket upper bounds, in seconds: 100us .. ~100s
# exponential — wide enough for step times on one chip and compile times
DEFAULT_BUCKETS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1,
                   1.0, 3.0, 10.0, 30.0, 100.0)


def _make_lock(name):
    """Named lock site (docs/STATIC_ANALYSIS.md): tracked under
    PTPU_LOCK_CHECK=1. STRICTLY passive about the import: this module
    executes during package bootstrap, and importing
    `paddle_tpu.analysis` from here would run `analysis.meta`'s
    kernel-conditional `declare(...)` calls against a half-registered op
    corpus (their registrations silently no-op — a measured breakage).
    Locks created before the analysis package exists (the global
    registry's own lock) stay plain; every metric lock created at
    runtime goes through the tracker."""
    conc = sys.modules.get("paddle_tpu.analysis.concurrency")
    if conc is None:
        return threading.Lock()
    return conc.make_lock(name)


class Counter:
    """Monotonically increasing count (Prometheus counter semantics)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = 0
        self._lock = _make_lock("obs.metric")

    def inc(self, n=1):
        if n < 0:
            raise ValueError("counter %r cannot decrease" % self.name)
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def to_dict(self):
        return self._value


class Gauge:
    """Last-set value (queue depth, module bytes, throughput)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = 0.0
        self._lock = _make_lock("obs.metric")

    def set(self, v):
        self._value = float(v)

    def inc(self, n=1):
        with self._lock:
            self._value += n

    def dec(self, n=1):
        with self._lock:
            self._value -= n

    @property
    def value(self):
        return self._value

    def to_dict(self):
        return self._value


class Histogram:
    """Cumulative-bucket histogram with count/sum/min/max.

    min is float('inf') while empty — renderers must print a placeholder
    for zero-observation histograms rather than leak the sentinel (the
    legacy profiler table bug this layer fixes)."""

    __slots__ = ("name", "buckets", "bucket_counts", "count", "sum",
                 "min", "max", "_lock")

    def __init__(self, name, buckets=None):
        self.name = name
        # float-normalized so the JSON bucket keys (repr of each bound)
        # round-trip through tools/ptpu_stats.py --prometheus even when
        # the caller passed integer bounds
        self.buckets = tuple(sorted(float(b)
                                    for b in (buckets or DEFAULT_BUCKETS)))
        self.bucket_counts = [0] * (len(self.buckets) + 1)  # +Inf tail
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._lock = _make_lock("obs.metric")

    def observe(self, v):
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            for i, le in enumerate(self.buckets):
                if v <= le:
                    self.bucket_counts[i] += 1
                    return
            self.bucket_counts[-1] += 1

    @property
    def avg(self):
        return self.sum / self.count if self.count else 0.0

    def quantile(self, q):
        """Bucket-interpolated quantile estimate (Prometheus
        histogram_quantile semantics: linear interpolation inside the
        bucket holding the q-th observation), clamped to the observed
        min/max so a wide first/last bucket cannot report a value outside
        the real range. The one shared percentile implementation — the
        serving engine's p50/p99 gauges read this, replacing its retired
        ad-hoc deque(1024) windows."""
        if not 0.0 <= q <= 1.0:
            raise ValueError("quantile %r outside [0, 1]" % (q,))
        if not self.count:
            return 0.0
        target = q * self.count
        cum = 0
        lo = 0.0
        for i, le in enumerate(self.buckets):
            n = self.bucket_counts[i]
            if n and cum + n >= target:
                v = lo + (le - lo) * (max(target - cum, 0.0) / n)
                return min(max(v, self.min), self.max)
            cum += n
            lo = le
        return self.max  # mass in the +Inf tail: best estimate is max

    def to_dict(self):
        d = {"count": self.count, "sum": self.sum, "avg": self.avg}
        if self.count:
            d["min"] = self.min
            d["max"] = self.max
            d["p50"] = self.quantile(0.50)
            d["p95"] = self.quantile(0.95)
            d["p99"] = self.quantile(0.99)
        return d | {"buckets": {
            ("+Inf" if i == len(self.buckets) else repr(self.buckets[i])): n
            for i, n in enumerate(self.bucket_counts)}}


def _quantile_of_sorted(v, q):
    at = q * (len(v) - 1)
    lo = int(at)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (at - lo)


def exact_quantile(values, q):
    """The q-quantile of raw values, linear between the two nearest
    order statistics (``numpy.percentile``'s default), or None of
    none."""
    if not 0.0 <= q <= 1.0:
        raise ValueError("quantile %r outside [0, 1]" % (q,))
    return _quantile_of_sorted(sorted(values), q) if values else None


SUMMARY_QUANTILES = (("p50", 0.5), ("p95", 0.95), ("p99", 0.99))


def _summary(values):
    d = {"count": len(values), "sum": float(sum(values))}
    if values:
        v = sorted(values)
        for key, q in SUMMARY_QUANTILES:
            d[key] = _quantile_of_sorted(v, q)
        d["max"] = v[-1]
    return d


class Samples:
    """Raw samples in a bounded ring: the newest ``maxlen`` win and the
    evictions are counted. A sample is a number, or a record (a dict,
    one per event: the serving step log) whose numeric ``fields`` are
    the ones ``to_dict()`` and the Prometheus text summarise. Quantiles
    are exact over what the ring holds, which is what a tail needs and
    a bucketed :class:`Histogram` cannot give."""

    __slots__ = ("name", "fields", "_ring", "added", "_restored", "_lock")

    def __init__(self, name, maxlen=4096, fields=None):
        self.name = name
        self.fields = tuple(fields) if fields else None
        self._ring = collections.deque(maxlen=int(maxlen))
        self.added = 0
        self._restored = None
        self._lock = _make_lock("obs.metric")

    @property
    def maxlen(self):
        return self._ring.maxlen

    @property
    def evicted(self):
        return self.added - len(self._ring)

    def add(self, sample):
        with self._lock:
            self._ring.append(sample)
            self.added += 1

    def records(self):
        """What the ring holds, oldest first."""
        with self._lock:
            return list(self._ring)

    def values(self, field=None):
        """The samples themselves, or the records' ``field`` where a
        record has one (None counts as absent)."""
        recs = self.records()
        if field is None:
            return recs
        return [r[field] for r in recs if r.get(field) is not None]

    def quantile(self, q, field=None):
        return exact_quantile(self.values(field), q)

    def max(self, field=None):
        return max(self.values(field), default=None)

    def to_dict(self):
        if self._restored is not None:
            return self._restored
        d = {"added": self.added, "evicted": self.evicted}
        if self.fields is None:
            return d | _summary(self.values())
        return d | {"fields": {f: _summary(self.values(f))
                               for f in self.fields}}

    def restore(self, doc):
        """Serve a dumped summary as it was dumped (tools/ptpu_stats.py
        --prometheus): the raw samples did not travel with it."""
        self._restored = dict(doc)
        self.fields = tuple(doc["fields"]) if "fields" in doc else None


class _NullMetric:
    """Shared no-op stand-in for every metric kind when telemetry is off:
    the instrumented call sites stay branch-free and allocation-free."""

    __slots__ = ()

    def inc(self, n=1):
        pass

    def add(self, sample):
        pass

    def dec(self, n=1):
        pass

    def set(self, v):
        pass

    def observe(self, v):
        pass

    @property
    def value(self):
        return 0


NULL_METRIC = _NullMetric()


class MetricsRegistry:
    """Name -> metric store. Names are slash-scoped ('executor/step_time');
    get-or-create, with a kind-conflict check so 'executor/step_time' can't
    be a counter in one file and a histogram in another."""

    def __init__(self):
        self._metrics = {}
        self._lock = _make_lock("obs.registry")

    def _get(self, name, cls, *args):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name, *args)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise TypeError("metric %r is a %s, not a %s"
                            % (name, type(m).__name__, cls.__name__))
        return m

    def counter(self, name):
        return self._get(name, Counter)

    def gauge(self, name):
        return self._get(name, Gauge)

    def histogram(self, name, buckets=None):
        if buckets is None:
            return self._get(name, Histogram)
        h = self._get(name, Histogram, buckets)
        if h.buckets != tuple(sorted(float(b) for b in buckets)):
            # a silent first-creation-wins would park every observation
            # in one bucket of the wrong scale; fail like kind conflicts
            raise ValueError(
                "histogram %r already exists with buckets %r"
                % (name, h.buckets))
        return h

    def samples(self, name, maxlen=4096, fields=None):
        return self._get(name, Samples, maxlen, fields)

    def metrics(self):
        with self._lock:
            return dict(self._metrics)

    def reset(self):
        with self._lock:
            self._metrics.clear()

    def to_dict(self):
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, m in sorted(self.metrics().items()):
            kind = ("counters" if isinstance(m, Counter) else
                    "gauges" if isinstance(m, Gauge) else
                    "samples" if isinstance(m, Samples) else "histograms")
            out.setdefault(kind, {})[name] = m.to_dict()
        return out

    def dump_json(self, path):
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
        return path

    def to_prometheus(self, prefix="ptpu_"):
        """Prometheus text exposition format 0.0.4."""
        lines = []
        seen = {}  # mangled family name -> original metric name
        for name, m in sorted(self.metrics().items()):
            pn = prefix + _prom_name(name)
            other = seen.setdefault(pn, name)
            if other != name:
                # 'a/b' and 'a_b' both mangle to ptpu_a_b — merging them
                # silently would corrupt both series; fail like the
                # registry's kind-conflict check does
                raise ValueError(
                    "prometheus name collision: metrics %r and %r both "
                    "expose as %r" % (other, name, pn))
            if isinstance(m, Counter):
                lines.append("# TYPE %s_total counter" % pn)
                lines.append("%s_total %s" % (pn, _prom_num(m.value)))
            elif isinstance(m, Gauge):
                lines.append("# TYPE %s gauge" % pn)
                lines.append("%s %s" % (pn, _prom_num(m.value)))
            elif isinstance(m, Samples):
                d = m.to_dict()
                for field, summary in (d["fields"].items()
                                       if "fields" in d else [(None, d)]):
                    fam = pn if field is None else pn + "_" + field
                    lines.append("# TYPE %s summary" % fam)
                    for key, q in SUMMARY_QUANTILES:
                        if key in summary:
                            lines.append('%s{quantile="%s"} %s' % (
                                fam, q, _prom_num(float(summary[key]))))
                    lines.append("%s_sum %s" % (
                        fam, _prom_num(float(summary["sum"]))))
                    lines.append("%s_count %d" % (fam, summary["count"]))
            else:
                lines.append("# TYPE %s histogram" % pn)
                cum = 0
                for le, n in zip(m.buckets, m.bucket_counts):
                    cum += n
                    lines.append('%s_bucket{le="%s"} %d'
                                 % (pn, _prom_num(le), cum))
                lines.append('%s_bucket{le="+Inf"} %d' % (pn, m.count))
                lines.append("%s_sum %s" % (pn, _prom_num(m.sum)))
                lines.append("%s_count %d" % (pn, m.count))
        return "\n".join(lines) + ("\n" if lines else "")


def _prom_name(name):
    return "".join(c if (c.isalnum() or c == "_") else "_" for c in name)


def _prom_num(v):
    if isinstance(v, float):
        if math.isnan(v):
            return "NaN"  # int(nan) raises — a poisoned gauge must not
            # crash the scrape; ptpu_stats' NaN-hardened asserts catch it
        if math.isinf(v):
            return "+Inf" if v > 0 else "-Inf"
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
    return repr(v)


# ---------------------------------------------------------------------------
# process-wide default registry + enablement switch
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry()


def _env_on(name):
    """PTPU_* switch check through the central flags registry (bool flags
    parse with the shared spellings; path-valued flags count as on when
    set non-empty)."""
    from .. import flags as _flags

    return bool(_flags.env(name))


_ENABLED = _env_on("PTPU_METRICS")


def enabled():
    """One-branch check instrumented hot paths gate on."""
    return _ENABLED


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def registry():
    """The process-wide registry (live even when disabled — explicit use
    is an opt-in, the switch only mutes the instrumented hot paths)."""
    return _REGISTRY


def counter(name):
    return _REGISTRY.counter(name) if _ENABLED else NULL_METRIC


def gauge(name):
    return _REGISTRY.gauge(name) if _ENABLED else NULL_METRIC


def histogram(name, buckets=None):
    return _REGISTRY.histogram(name, buckets) if _ENABLED else NULL_METRIC


def samples(name, maxlen=4096, fields=None):
    return (_REGISTRY.samples(name, maxlen, fields) if _ENABLED
            else NULL_METRIC)


def dump_json(path):
    return _REGISTRY.dump_json(path)


def to_prometheus():
    return _REGISTRY.to_prometheus()


def reset():
    _REGISTRY.reset()
