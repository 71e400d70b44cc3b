"""From a profiler trace to device busy time, top operations and gaps.

The reduction is the benchmark's own, kept here so that every PR
computes the same numbers the same way. It reads the ``.xplane.pb`` the
jax profiler writes with ``jax.profiler.ProfileData`` and nothing else.

What a v5e trace holds (looked at by hand, PR 23; a recorded one is in
``perfbench/testdata/probe_step.xplane.pb``): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one per operation, back to back inside a
module) and ``Async XLA Ops`` (copies in flight, overlapping the ops);
and a ``/host:CPU`` plane with one line per host thread, on the same
clock, where ``jax.profiler.TraceAnnotation`` spans appear by name.

* busy: the union of the ``XLA Ops`` intervals of a chip, averaged over
  the chips used. ``Async XLA Ops`` are left out (they overlap).
* device_ops: seconds per operation kind (HLO name without its ``%``
  and trailing ``.<n>``), largest first.
* idle_gaps: the stretches in which no operation ran, each attributed
  to the host span that covers most of it, summed by that name. The
  program's own spans (``ptpu/engine.plan|dispatch|wait|stream``,
  ``ptpu/exe.prepare|dispatch``; named with their prefix) come first:
  the benchmark's generator sleeps or waits under a ``bench/`` span
  whenever no request is due, whatever the server is doing, so its
  span covers every gap, and what the engine's worker was doing says
  why the device stood still. Where no span of the program covers
  half of a gap (the worker had nothing to do), the benchmark's own
  span (``bench/``, named without the prefix) takes it; a gap under no
  span is ``host, unattributed``.
"""

import bisect
import collections
import glob
import os
import re
import shutil
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
PROGRAM_PREFIX = "ptpu/"         # the program's own spans, named whole
WINDOW_SPAN = "traced_window"    # the Tracer's own span: the window
UNATTRIBUTED = "host, unattributed"
_SUFFIX = re.compile(r"(\.\d+|\.remat\d*)+$")


def op_kind(name):
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion``; so is
    ``fusion.25.remat``, the compiler's rematerialised copy."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head) or head


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_profile(profile, window_s=None, n_devices=None, min_gap_s=2e-5):
    """The reduction over a ``ProfileData``. The window is the
    Tracer's ``bench/traced_window`` span where the trace has one
    (operations are clipped to it: the profiler also records what runs
    while it starts and stops); else ``window_s`` by the host's clock;
    else the span from the first to the last device operation."""
    per_device, ops, spans = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ivs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    ivs.append((s, s + d))
                    ops.append((s, s + d, op_kind(ev.name)))
            per_device.append(_union(ivs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        name = name[len(SPAN_PREFIX):]
                    elif not name.startswith(PROGRAM_PREFIX):
                        continue
                    spans.append((ev.start_ns,
                                  ev.start_ns + ev.duration_ns, name))
    window = next(((a, b) for a, b, name in spans if name == WINDOW_SPAN),
                  None)
    ops_total = collections.Counter()
    for a, b, kind in ops:
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        ops_total[kind] += max(0, b - a)
    if window is not None:
        per_device = [_union(_clip(u, *window)) for u in per_device]
        window_s = (window[1] - window[0]) * 1e-9
        spans = [x for x in spans if x[2] != WINDOW_SPAN]
    per_device = [u for u in per_device if u]
    if not per_device:
        return None
    if n_devices:
        per_device = per_device[:n_devices]
    n = len(per_device)
    busy_ns = sum(e - s for u in per_device for s, e in u) / n
    first = min(u[0][0] for u in per_device)
    last = max(u[-1][1] for u in per_device)
    if window_s is None:
        window_s = (last - first) * 1e-9
    # gaps of the first device, inside the span its operations cover;
    # only the spans that can overlap a gap are looked at
    spans.sort()
    starts = [s for s, _e, _n in spans]
    longest = max((e - s for s, e, _n in spans), default=0)
    gaps = collections.Counter()
    u = per_device[0]
    for (_s0, e0), (s1, _e1) in zip(u, u[1:]):
        if (s1 - e0) * 1e-9 < min_gap_s:
            continue
        # the span that covers most of the gap, of the benchmark's own
        # (False) and of the program's (True)
        best = {False: (0, UNATTRIBUTED), True: (0, UNATTRIBUTED)}
        for s, e, name in spans[bisect.bisect_left(starts, e0 - longest):
                                bisect.bisect_right(starts, s1)]:
            own = name.startswith(PROGRAM_PREFIX)
            best[own] = max(best[own], (min(e, s1) - max(s, e0), name))
        cover, name = best[True]
        if 2 * cover < s1 - e0:      # the worker had nothing to do
            name = best[False][1]
        gaps[name] += (s1 - e0)
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": float(window_s),
        "devices": n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in
                       ops_total.most_common(10)],
        "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(10)],
    }


def reduce_file(path, **kw):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), **kw)


class Tracer:
    """One traced stretch of a run: ``start()``, ``stop()``, then
    ``reduce()``. The directory is inside the checkout and emptied
    first."""

    def __init__(self, directory, n_devices=1):
        self.directory = directory
        self.n_devices = n_devices
        self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory)
        jax.profiler.start_trace(self.directory)
        self.t0 = time.perf_counter()
        self._window = span(WINDOW_SPAN)
        self._window.__enter__()

    def stop(self):
        import jax

        self._window.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def started(self):
        return self.t0 is not None

    def reduce(self):
        if self.t0 is None:
            return None
        if self.t1 is None:
            self.stop()
        files = glob.glob(os.path.join(self.directory, "**",
                                       "*.xplane.pb"), recursive=True)
        if not files:
            return None
        out = reduce_file(files[0], window_s=self.t1 - self.t0,
                          n_devices=self.n_devices)
        shutil.rmtree(self.directory, ignore_errors=True)
        return out


def span(name, **kw):
    """A host span of the benchmark's own, on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **kw)
