"""Device time of named kernels in the traced window, and a kernel's
share of the window's busy time.

``collect(directory, kernels, module)`` is called by a runner while the
profile is still on disk (``trace_reduce.Tracer.reduce`` deletes it and
keeps ten operation kinds only). It returns, inside the Tracer's
``bench/traced_window`` span, for each kernel (an ``XLA Ops`` event
whose kind, ``trace_reduce.op_kind``, is the kernel's name) its seconds
over the whole window (``all_s``) and inside the executions of the
program ``module`` (``in_module_s``: ``jit_decode_step``, so that a
roofline share of decode steps is not diluted by the mixed steps' calls
of the same kernel), and how many executions of that program lay wholly
inside the window (``modules``). None where there is no profile.

``read(obs, kernel)``: 100 x the kernel's ``all_s`` over the trace
reduction's ``busy_s``; nothing where the run kept no such numbers.
"""

import bisect
import glob
import os

from perfbench import trace_reduce

MODULES_LINE = "XLA Modules"


def collect(directory, kernels, module):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    profile = ProfileData.from_file(files[0])
    window, ops, runs = None, [], []
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            if ops or runs:
                continue            # the first chip's plane is enough
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    for ev in line.events:
                        kind = trace_reduce.op_kind(ev.name)
                        if kind in kernels:
                            ops.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns, kind))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        if ev.name.startswith(module):
                            runs.append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == (trace_reduce.SPAN_PREFIX
                                   + trace_reduce.WINDOW_SPAN):
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        return None
    lo, hi = window
    runs = sorted(r for r in runs if r[0] >= lo and r[1] <= hi)
    out = {k: {"all_s": 0.0, "in_module_s": 0.0} for k in kernels}
    starts = [r[0] for r in runs]
    for s, e, kind in ops:
        out[kind]["all_s"] += max(0, min(e, hi) - max(s, lo)) * 1e-9
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= runs[i][1]:
            out[kind]["in_module_s"] += (e - s) * 1e-9
    return {"kernels": out, "modules": len(runs), "module": module}


def read(obs, kernel):
    seen = (obs.get("kernel_trace") or {}).get("kernels", {}).get(kernel)
    busy = (obs.get("trace") or {}).get("busy_s")
    if not seen or not busy:
        return None
    return 100.0 * seen["all_s"] / busy
