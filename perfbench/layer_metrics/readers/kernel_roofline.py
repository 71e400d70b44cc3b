"""A kernel's share of its memory roofline over the traced decode
steps: the bytes its calls had to move, by the configuration family's
own function in ``perfbench/flops/<family>.py`` fed with the step log's
counters, over the kernel's device time inside the traced executions of
the decode program (``kernel_share.collect``), over the chip's peak
``hbm_bytes_per_s``.

The step log has no device clock, so the counters are taken as a mean
over the decode steps dispatched during the traced stretch (host
stamps) and multiplied by the number of decode executions the trace
holds. ``bytes_fn`` names the function; ``fields`` the record fields it
is fed, in order, each as that per-step mean times the executions
(``rows`` is the step's occupied rows). Nothing is returned where the
program keeps no such counters or the trace no such kernel.
"""

from perfbench import spec
from perfbench.layer_metrics.readers import step_log


def read(obs, kernel, bytes_fn, fields, series="serving/step",
         kind="decode"):
    seen = obs.get("kernel_trace") or {}
    k = seen.get("kernels", {}).get(kernel)
    span = obs.get("traced_span")
    recs = step_log.warm_records(series, kind)
    if not k or not k["in_module_s"] or not seen.get("modules") \
            or not span or not recs:
        return None
    recs = [r for r in recs
            if span[0] <= r.get("t_dispatched", -1.0) <= span[1]
            and all(r.get(f) is not None for f in fields)]
    if not recs:
        return None
    totals = [sum(r[f] for r in recs) / len(recs) * seen["modules"]
              for f in fields]
    need = getattr(spec.family(obs["config"], "flops"), bytes_fn)(
        obs["config"], *totals)
    return 100.0 * need / k["in_module_s"] / obs["peaks"]["hbm_bytes_per_s"]
