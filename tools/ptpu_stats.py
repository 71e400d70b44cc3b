"""Pretty-print a paddle_tpu metrics dump (parity: the reference's
profiler PrintProfiler tables, now fed from files instead of process
state).

Accepts either exposition schema the framework writes:
  - a registry dump ({"counters": ..., "gauges": ..., "histograms": ...,
    and "samples" where the run took raw samples) from PTPU_METRICS_OUT /
    MetricsRegistry.dump_json / bench.py --metrics-out
  - a native stats dump ({"stats": {name: {count,sum,min,max,avg}}})
    from native_serve --train-loop --metrics-out (profiler.cc)

Usage:
  python tools/ptpu_stats.py dump.json [more.json ...]
  python tools/ptpu_stats.py --prometheus dump.json   # re-expose as text
  python tools/ptpu_stats.py --selftest               # CI smoke hook
  python tools/ptpu_stats.py dump.json \
      --assert-has exec/inflight_steps \
      --assert-min exec/inflight_steps=2   # CI gating on metric presence
  python tools/ptpu_stats.py --diff before.json after.json  # activity delta
  python tools/ptpu_stats.py --url http://127.0.0.1:9100/varz  # live scrape

--url accepts both endpoint schemas: /varz (JSON registry dump — exact
metric names, preferred) and /metrics (Prometheus text, parsed back
best-effort under the mangled ptpu_* names).
"""

import argparse
import json
import os
import sys


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        if v and (abs(v) < 1e-3 or abs(v) >= 1e6):
            return "%.3e" % v
        return "%.6g" % v
    return str(v)


def render(doc, out=None):
    """Render one parsed metrics document as aligned tables."""
    out = out if out is not None else sys.stdout  # late-bound: respects
    # a caller's redirected stdout (an import-time default would not)
    wrote = False
    if "stats" in doc:  # native profiler.cc schema
        doc = {"histograms": {
            name: {"count": s.get("count", 0), "sum": s.get("sum", 0.0),
                   "avg": s.get("avg"), "min": s.get("min"),
                   "max": s.get("max")}
            for name, s in doc["stats"].items()}}
    counters = doc.get("counters", {})
    gauges = doc.get("gauges", {})
    hists = doc.get("histograms", {})
    if counters:
        out.write("%-44s %14s\n" % ("Counter", "Value"))
        for name in sorted(counters):
            out.write("%-44s %14s\n" % (name, _fmt(counters[name])))
        wrote = True
    if gauges:
        if wrote:
            out.write("\n")
        out.write("%-44s %14s\n" % ("Gauge", "Value"))
        for name in sorted(gauges):
            out.write("%-44s %14s\n" % (name, _fmt(gauges[name])))
        wrote = True
    if hists:
        if wrote:
            out.write("\n")
        out.write("%-44s %8s %12s %12s %12s %12s\n" % (
            "Histogram", "Count", "Sum", "Avg", "Min", "Max"))
        for name in sorted(hists):
            h = hists[name]
            count = h.get("count", 0)
            # zero-observation histograms have no min/max — render '-'
            out.write("%-44s %8d %12s %12s %12s %12s\n" % (
                name, count, _fmt(h.get("sum", 0.0)),
                _fmt(h.get("avg") if count else None),
                _fmt(h.get("min") if count else None),
                _fmt(h.get("max") if count else None)))
        wrote = True
    samples = doc.get("samples", {})
    if samples:
        if wrote:
            out.write("\n")
        # raw samples: exact quantiles over what the ring still held
        out.write("%-44s %8s %12s %12s %12s %12s\n" % (
            "Samples", "Count", "P50", "P95", "P99", "Max"))
        for name in sorted(samples):
            s = samples[name]
            for field, row in (s["fields"].items() if "fields" in s
                               else [(None, s)]):
                out.write("%-44s %8d %12s %12s %12s %12s\n" % (
                    name if field is None else "%s.%s" % (name, field),
                    row.get("count", 0), _fmt(row.get("p50")),
                    _fmt(row.get("p95")), _fmt(row.get("p99")),
                    _fmt(row.get("max"))))
        wrote = True
    if not wrote:
        out.write("(no metrics)\n")


def _to_prometheus(doc):
    """Rebuild a registry from a JSON dump and re-expose as Prometheus
    text. Registry dumps carry their bucket bounds/counts and round-trip
    exactly; the native profiler.cc schema has no buckets (count/sum/
    min/max only), so its histograms expose all mass at +Inf."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()

    def _fill(name, h):
        bucket_doc = h.get("buckets") or {}
        bounds = tuple(sorted(float(k) for k in bucket_doc if k != "+Inf"))
        hist = reg.histogram(name, buckets=bounds or None)
        if bucket_doc:
            hist.bucket_counts = [int(bucket_doc.get(repr(b), 0))
                                  for b in hist.buckets]
            hist.bucket_counts.append(int(bucket_doc.get("+Inf", 0)))
        else:
            hist.bucket_counts[-1] = int(h.get("count", 0))
        hist.count = int(h.get("count", 0))
        hist.sum = float(h.get("sum", 0.0))
        if hist.count:
            hist.min = float(h.get("min", 0.0))
            hist.max = float(h.get("max", 0.0))

    if "stats" in doc:
        for name, s in doc["stats"].items():
            _fill(name, s)
    for name, v in doc.get("counters", {}).items():
        reg.counter(name).inc(v)
    for name, v in doc.get("gauges", {}).items():
        reg.gauge(name).set(v)
    for name, h in doc.get("histograms", {}).items():
        _fill(name, h)
    for name, summary in doc.get("samples", {}).items():
        reg.samples(name).restore(summary)
    return reg.to_prometheus()


def _selftest():
    """Build a registry in-process, dump it, re-read and render — the CI
    smoke that the full JSON round trip stays parseable."""
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("selftest/count").inc(3)
    reg.gauge("selftest/gauge").set(1.5)
    h = reg.histogram("selftest/hist")
    for v in (0.01, 0.02, 0.04):
        h.observe(v)
    reg.histogram("selftest/empty")  # zero-call rendering path
    with tempfile.NamedTemporaryFile("r", suffix=".json") as f:
        reg.dump_json(f.name)
        doc = json.load(open(f.name))
    render(doc)
    assert doc["counters"]["selftest/count"] == 3
    assert doc["histograms"]["selftest/hist"]["count"] == 3
    assert "min" not in doc["histograms"]["selftest/empty"]
    print("ptpu_stats selftest ok")
    return 0


def _parse_prometheus(text):
    """Best-effort inverse of the exposition format: counters/gauges by
    their ``# TYPE`` lines, histograms from ``_count``/``_sum`` suffix
    samples (bucket lines are cumulative and lossy — skipped). Names
    come back in their mangled ``ptpu_*`` form; point ``--url`` at
    ``/varz`` when the exact registry names matter."""
    counters, gauges, hists = {}, {}, {}
    types = {}
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) == 4:
                types[parts[2]] = parts[3]
            continue
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            val = float(value)
        except ValueError:
            continue
        base = name.partition("{")[0]
        if base.endswith("_bucket"):
            continue
        for suffix, field in (("_count", "count"), ("_sum", "sum")):
            if base.endswith(suffix) \
                    and types.get(base[:-len(suffix)]) == "histogram":
                h = hists.setdefault(base[:-len(suffix)], {})
                h[field] = int(val) if field == "count" else val
                break
        else:
            if types.get(base) == "counter":
                counters[base] = val
            else:
                gauges[base] = val
    doc = {}
    if counters:
        doc["counters"] = counters
    if gauges:
        doc["gauges"] = gauges
    if hists:
        doc["histograms"] = hists
    return doc


def _fetch_doc(url):
    """Scrape a live endpoint: JSON (``/varz``) parses as a registry
    dump verbatim; anything else is treated as Prometheus text."""
    from urllib.request import urlopen

    with urlopen(url, timeout=10) as resp:
        body = resp.read().decode("utf-8")
    try:
        return json.loads(body)
    except ValueError:
        return _parse_prometheus(body)


def render_diff(a, b, out=None):
    """Activity between two dumps of the same process: counters and
    histogram observation counts are monotone, so ``B - A`` is what
    happened in between; gauges are instantaneous levels and render
    side-by-side instead of as a (meaningless) delta."""
    out = out if out is not None else sys.stdout
    wrote = False
    ca, cb = a.get("counters", {}), b.get("counters", {})
    if ca or cb:
        out.write("%-44s %12s %12s %12s\n"
                  % ("Counter", "Before", "After", "Delta"))
        for name in sorted(set(ca) | set(cb)):
            va, vb = ca.get(name, 0), cb.get(name, 0)
            out.write("%-44s %12s %12s %12s\n"
                      % (name, _fmt(va), _fmt(vb), _fmt(vb - va)))
        wrote = True
    ga, gb = a.get("gauges", {}), b.get("gauges", {})
    if ga or gb:
        if wrote:
            out.write("\n")
        out.write("%-44s %12s %12s\n" % ("Gauge", "Before", "After"))
        for name in sorted(set(ga) | set(gb)):
            out.write("%-44s %12s %12s\n"
                      % (name, _fmt(ga.get(name)), _fmt(gb.get(name))))
        wrote = True
    ha, hb = a.get("histograms", {}), b.get("histograms", {})
    if ha or hb:
        if wrote:
            out.write("\n")
        out.write("%-44s %12s %12s %12s\n"
                  % ("Histogram", "Count A", "Count B", "Delta"))
        for name in sorted(set(ha) | set(hb)):
            na = int(ha.get(name, {}).get("count", 0))
            nb = int(hb.get(name, {}).get("count", 0))
            out.write("%-44s %12d %12d %12d\n" % (name, na, nb, nb - na))
        wrote = True
    if not wrote:
        out.write("(no metrics)\n")


def _lookup(doc, name):
    """(found, numeric value-or-None) for a metric of any kind."""
    for kind in ("counters", "gauges"):
        if name in doc.get(kind, {}):
            return True, float(doc[kind][name])
    for kind in ("histograms", "stats"):
        if name in doc.get(kind, {}):
            return True, float(doc[kind][name].get("count", 0))
    if name in doc.get("samples", {}):
        return True, float(doc["samples"][name].get("added", 0))
    return False, None


def check_assertions(doc, has, mins, maxs=None):
    """CI gating: every `has` name must exist in the dump; every
    `mins`/`maxs` "name=value" must exist with numeric value >=/<= the
    bound (histograms compare their observation count, raw samples the
    number added). A NaN value
    fails ANY bound comparison loudly — NaN compares false against
    everything, so without the explicit check a poisoned metric would
    sail through `--assert-max` (and a NaN bound would never fire).
    Returns a list of failure messages."""
    import math

    failures = []
    for name in has or ():
        if not _lookup(doc, name)[0]:
            failures.append("missing metric: %s" % name)

    def _bound_check(specs, flag, bad):
        for spec in specs or ():
            name, _, bound = spec.partition("=")
            if not bound:
                failures.append("%s wants NAME=VALUE, got %r"
                                % (flag, spec))
                continue
            found, val = _lookup(doc, name)
            try:
                bound_val = float(bound)
            except ValueError:
                failures.append("%s wants NAME=VALUE with a numeric "
                                "value, got %r" % (flag, spec))
                continue
            if not found:
                failures.append("missing metric: %s" % name)
            elif math.isnan(val) or math.isnan(bound_val):
                failures.append(
                    "metric %s = %s vs bound %s: NaN fails every "
                    "%s comparison" % (name, val, bound, flag))
            elif bad(val, bound_val):
                failures.append("metric %s = %s, want %s %s"
                                % (name, val,
                                   ">=" if flag == "--assert-min"
                                   else "<=", bound))

    _bound_check(mins, "--assert-min", lambda v, b: v < b)
    _bound_check(maxs, "--assert-max", lambda v, b: v > b)
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("files", nargs="*", help="metrics JSON dump(s)")
    ap.add_argument("--prometheus", action="store_true",
                    help="emit Prometheus text instead of tables")
    ap.add_argument("--selftest", action="store_true",
                    help="run the in-process round-trip smoke and exit")
    ap.add_argument("--assert-has", nargs="+", default=None,
                    metavar="NAME",
                    help="fail unless every named metric is in the dump")
    ap.add_argument("--assert-min", nargs="+", default=None,
                    metavar="NAME=VALUE",
                    help="fail unless metric >= value (histograms "
                         "compare their observation count)")
    ap.add_argument("--assert-max", nargs="+", default=None,
                    metavar="NAME=VALUE",
                    help="fail unless metric <= value (the chaos stage "
                         "gates final loss this way)")
    ap.add_argument("--diff", action="store_true",
                    help="render the activity delta between exactly two "
                         "sources (counters/histogram counts subtract; "
                         "gauges show side-by-side)")
    ap.add_argument("--url", action="append", default=[],
                    metavar="URL",
                    help="scrape a live endpoint as a source: /varz "
                         "(JSON, exact names) or /metrics (Prometheus "
                         "text, mangled ptpu_* names)")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    sources = [(p, "file") for p in args.files] \
        + [(u, "url") for u in args.url]
    if not sources:
        ap.error("no metrics files or --url given (or use --selftest)")
    docs = []
    for src, kind in sources:
        if kind == "url":
            docs.append((src, _fetch_doc(src)))
        else:
            with open(src) as f:
                docs.append((src, json.load(f)))
    if args.diff:
        if len(docs) != 2:
            ap.error("--diff wants exactly two sources, got %d"
                     % len(docs))
        render_diff(docs[0][1], docs[1][1])
        # assertions gate the AFTER document — the state being shipped
        docs = docs[1:]
        rc = 0
        for src, doc in docs:
            failures = check_assertions(doc, args.assert_has,
                                        args.assert_min, args.assert_max)
            for msg in failures:
                sys.stderr.write("%s: %s\n" % (src, msg))
            if failures:
                rc = 1
        return rc
    rc = 0
    for i, (src, doc) in enumerate(docs):
        if len(docs) > 1:
            sys.stdout.write("%s== %s ==\n" % ("\n" if i else "", src))
        if args.prometheus:
            sys.stdout.write(_to_prometheus(doc))
        else:
            render(doc)
        failures = check_assertions(doc, args.assert_has, args.assert_min,
                                    args.assert_max)
        for msg in failures:
            sys.stderr.write("%s: %s\n" % (src, msg))
        if failures:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
