"""A kernel's share of its roofline, the LARGER of its memory and its
compute bound, over the traced steps of one program.

The time the kernel had to take is ``max(bytes / hbm_bytes_per_s, flops
/ bf16_flops_per_s)``, the bytes and FLOPs by the configuration
family's own functions in ``perfbench/flops/<family>.py`` fed with the
step log's fields; it is divided by the kernel's device seconds inside
the traced executions of the program (``kernel_share.collect``, kept by
the runner under ``trace``: ``kernel_trace`` for the decode program,
``kernel_trace_chunk`` for the chunk program).

As ``kernel_roofline``: the step log has no device clock, so each field
is a mean over the steps of ``kind`` dispatched during the traced
stretch (host stamps) times the program executions the trace holds.
``bytes_fn`` / ``flops_fn`` name the functions, ``bytes_fields`` /
``flops_fields`` the record fields each is fed, in order (``rows`` is
the step's occupied rows; ``tokens`` its prefill plus decode tokens).
Nothing is returned where the program keeps no such fields or the trace
no such kernel.
"""

from perfbench import spec
from perfbench.layer_metrics.readers import step_log


def field(rec, name):
    if name == "tokens":
        return rec.get("prefill_tokens", 0) + rec.get("decode_tokens", 0)
    return rec.get(name)


def traced_totals(obs, trace, kind, fields, series="serving/step"):
    """Each field's mean over the traced steps of ``kind`` times the
    program executions the trace holds, or None."""
    seen = obs.get(trace) or {}
    span = obs.get("traced_span")
    recs = step_log.warm_records(series, kind)
    if not seen.get("modules") or not span or not recs:
        return None
    recs = [r for r in recs
            if span[0] <= r.get("t_dispatched", -1.0) <= span[1]
            and all(field(r, f) is not None for f in fields)]
    if not recs:
        return None
    return {f: sum(field(r, f) for r in recs) / len(recs) * seen["modules"]
            for f in fields}


def read(obs, kernel, trace, kind, bytes_fn, bytes_fields, flops_fn,
         flops_fields):
    k = (obs.get(trace) or {}).get("kernels", {}).get(kernel)
    totals = traced_totals(obs, trace, kind,
                           list(bytes_fields) + list(flops_fields))
    if not k or not k["in_module_s"] or totals is None:
        return None
    flops = spec.family(obs["config"], "flops")
    need_bytes = getattr(flops, bytes_fn)(
        obs["config"], *[totals[f] for f in bytes_fields])
    need_flops = getattr(flops, flops_fn)(
        obs["config"], *[totals[f] for f in flops_fields])
    bound_s = max(need_bytes / obs["peaks"]["hbm_bytes_per_s"],
                  need_flops / obs["peaks"]["bf16_flops_per_s"])
    return 100.0 * bound_s / k["in_module_s"]
