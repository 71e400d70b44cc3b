"""``readers/step_log.py``'s ``ratio`` for fields a program may lack:
``sum(ratio[0]) / sum(ratio[1]) * scale`` over the warm records of one
``kind`` (the same population: every step the process ran warm), taken
over the records that carry BOTH fields.

``step_log.read`` indexes a ratio's fields without asking
(``r[field]``), so on a program whose records lack one (the parent
commit of the PR that adds it) it raises and the traced run fails.
This reader applies that module's own rule for a single field to the
pair: nothing is returned, and the metric is left out, where the
program keeps no such samples or where fewer than half of the steps of
the kind carry both fields."""

from perfbench.layer_metrics.readers import step_log


def read(obs, series, ratio, kind=None, scale=1.0):
    recs = step_log.warm_records(series, kind)
    if not recs:
        return None
    have = [r for r in recs if all(r.get(f) is not None for f in ratio)]
    if not have or 2 * len(have) < len(recs):
        return None
    above, below = (sum(r[f] for r in have) for f in ratio)
    return float(above) / float(below) * scale if below else None
