"""One run of one cell: load, warm up, measure, check, print the line.

    python3 perfbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One new process, one cell, once. Earlier lines of standard output are
JSON notes (set-up phases, the generator's lateness, every number the
output check compared beside its limit); the LAST line is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     (+ "breakdown" in a traced run) "device": {...}, "checks": {...}}

``checks`` holds every number the output check compared, beside its
limit; the same lines end standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""

import time

T_PROCESS_START = time.perf_counter()   # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402


def note(**fields):
    """An earlier line: anything worth a number that is not the result."""
    print(json.dumps(fields), flush=True)


def place_compile_cache():
    """jax's persistent cache, one directory a checkout and nothing
    ever evicted: ``<checkout>/.jax_cache``, or, where
    ``JAX_COMPILATION_CACHE_DIR`` is set, a directory of this
    checkout's own under it, named from the checkout's path (fixed, as
    the path is part of the cache key). Two checkouts that share one
    directory hold a copy each of every step program, because a Mosaic
    kernel's body carries its checkout's paths; under a size limit
    (``JAX_COMPILATION_CACHE_MAX_SIZE``: the chip tool states 192 MiB,
    two sides of one XGLM cell need 290) each side's run pushed the
    other's programs out and the next run compiled them again inside
    ``setup_s`` (PERF.md section 6, PR 36). So the limit is stated
    here, as none; every program is kept, however quick its compile;
    and a cell's second run compiles nothing, whichever cells or
    checkouts ran between the two."""
    import hashlib

    import jax

    shared = jax.config.jax_compilation_cache_dir
    if shared is None:
        own = os.path.join(ROOT, ".jax_cache")
    else:
        own = os.path.join(shared, "perfbench-" + hashlib.sha1(
            ROOT.encode()).hexdigest()[:16])
    jax.config.update("jax_compilation_cache_dir", own)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return own


def find_device(chips, require_chip=True):
    """(devices, {"platform", "kind", "count"}) as jax reports them.
    Raises SystemExit(3) when the cell's chips are not there."""
    import jax

    devices = jax.devices()
    ident = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if require_chip and (ident["platform"] != "tpu"
                         or ident["count"] < chips):
        sys.stderr.write("perfbench: the cell needs %d TPU chip(s); jax "
                         "found %r\n" % (chips, ident))
        raise SystemExit(3)
    return devices, ident


class CompileCounter:
    """Counts jax's backend compilations, so a window can show none,
    and the persistent cache's hits and misses, so a set-up can show
    that every program was found."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def setup_line(self):
        """What the set-up line says of compilation: a backend
        "compilation" is also counted where the program was read back
        from the cache, so the seconds are small on a hit."""
        return dict(compile_seconds_total=self.seconds,
                    compilations=self.count, cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses)


def layer_metrics(bench, cell_name, reported, obs, root=ROOT):
    """Each per-layer metric's reader over the run's observations. A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in spec.metrics_of(bench, "per_layer", cell_name, reported):
        args, read = spec.layer_metric(m["name"], root)
        value = read(obs, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload, seed, seconds, trace, require_chip=True,
             root=ROOT, hooks=None):
    """Drive one run and return the result line as a dict. ``root``
    is where BENCHMARK.json and the data files are looked up; ``hooks``
    are keyword arguments for the runner (the harness's own tests and
    the lower-precision controls break or swap the timed path)."""
    bench = spec.load_benchmark(root)
    w, config, traffic = spec.cell(bench, workload, root)
    # a CPU rehearsal keeps no cache: it compiles toys, and XLA:CPU logs
    # an error line for every entry it reads back
    cache_dir = place_compile_cache() if require_chip else None
    devices, ident = find_device(w["chips"], require_chip)
    # a CPU rehearsal borrows the v5e's row: its numbers are never read
    peaks = spec.peaks(ident["kind"] if require_chip else "TPU v5 lite")
    compiles = CompileCounter()
    reached_chip_s = time.perf_counter() - T_PROCESS_START
    note(phase="start", cell=workload, seed=seed, seconds=seconds,
         trace=trace, device=ident, compile_cache=cache_dir,
         reached_chip_s=reached_chip_s)

    def note_setup(**fields):
        """The runners' notes; the set-up line (``window_open``) also
        says what set-up compiled and what it found in the cache."""
        if fields.get("phase") == "window_open":
            fields.update(compiles.setup_line())
        note(**fields)

    from perfbench import trace_reduce

    tracer = trace_reduce.Tracer(
        os.path.join(ROOT, ".perfbench_trace", workload),
        n_devices=w["chips"]) if trace else None
    run = spec.runner(config).run(dict(
        cell=w, config=config, traffic=traffic, seed=int(seed),
        seconds=float(seconds), tracer=tracer, devices=devices,
        peaks=peaks, compiles=compiles, t_start=T_PROCESS_START,
        note=note_setup, require_chip=require_chip), **(hooks or {}))

    for c in run["checks"]:
        note(check=c["name"], **{k: v for k, v in c.items()
                                 if k != "name"})
    correct = all(c["ok"] for c in run["checks"])
    e2e = dict(run["end_to_end"])
    device = dict(ident, memory_peak_bytes=int(run["memory_peak_bytes"]))
    line = {"correct": bool(correct), "attempted": int(run["attempted"]),
            "failed": int(run["failed"])}
    if trace:
        obs = dict(run["observations"], config=config, peaks=peaks,
                   chips=w["chips"], traffic=traffic,
                   reached_chip_s=[reached_chip_s])
        reported = [m["name"] for m in
                    spec.metrics_of(bench, "end_to_end", workload)]
        line["metrics"] = layer_metrics(bench, workload, reported, obs,
                                        root)
        reduced = obs.get("trace") or {}
        device["busy_s"] = reduced.get("busy_s")
        device["window_s"] = reduced.get("window_s")
        line["breakdown"] = {
            "device_ops": reduced.get("device_ops", [])[:10],
            "idle_gaps": reduced.get("idle_gaps", [])[:10]}
    else:
        line["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in spec.metrics_of(bench, "end_to_end", workload)
            if e2e.get(m["name"]) is not None}
    line["device"] = device
    # every number compared, beside its limit: last in the line
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"],
                                  "ok": c["ok"]} for c in run["checks"]}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, args.trace)
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():    # and last on standard error
        sys.stderr.write("perfbench check %s: %r (limit %r) %s\n"
                         % (name, c["value"], c["limit"],
                            "ok" if c["ok"] else "NOT OK"))
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
