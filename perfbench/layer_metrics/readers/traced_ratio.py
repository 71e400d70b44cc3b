"""A ratio of sums over the step log's records of the TRACED stretch:
``sum(ratio[0]) / sum(ratio[1]) * scale`` over the warm records of one
``kind`` whose dispatch stamp lies inside ``obs["traced_span"]`` (the
host stamps of the Tracer's start and stop, which runner
``serve_latent`` hands over).

``readers/step_log.py`` takes every warm step of the process, the
drain too; in a closed loop that drains 160 requests the batch shrinks
from 128 rows to none over as many steps as the window holds, and a
quantity that follows the number of rows (how many experts a step
touches) would read the drain, not the full batch. Nothing is returned
where the run was not traced or the program keeps no such fields."""

from perfbench.layer_metrics.readers import step_log


def read(obs, series, ratio, kind=None, scale=1.0):
    span = obs.get("traced_span")
    recs = step_log.warm_records(series, kind)
    if not span or not recs:
        return None
    recs = [r for r in recs
            if span[0] <= r.get("t_dispatched", -1.0) <= span[1]
            and all(r.get(f) is not None for f in ratio)]
    above, below = (sum(r[f] for r in recs) for f in ratio)
    return float(above) / float(below) * scale if below else None
