"""Model FLOP/s utilization of SERVING over the traced stretch: the
forward FLOPs of every step dispatched in it (decode and mixed), by the
family's ``step_flops`` from the step log's fields (tokens, rows, the
pairs placed on held experts, the keys attended), over the stretch's
seconds times the chips' published bf16 peak. Nothing where the program
keeps no such fields."""

from perfbench import spec
from perfbench.layer_metrics.readers import step_log
from perfbench.layer_metrics.readers.kernel_bound import field


def read(obs, fields, series="serving/step", flops_fn="step_flops"):
    span = obs.get("traced_span")
    recs = step_log.warm_records(series)
    if not span or not recs:
        return None
    recs = [r for r in recs
            if span[0] <= r.get("t_dispatched", -1.0) <= span[1]]
    if not recs or any(field(r, f) is None for r in recs for f in fields):
        return None
    totals = [sum(field(r, f) for r in recs) for f in fields]
    flops = getattr(spec.family(obs["config"], "flops"), flops_fn)(
        obs["config"], *totals)
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / ((span[1] - span[0]) * peak)
