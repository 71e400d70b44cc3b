"""A statistic over the program's own step log: the raw samples that
``paddle_tpu.observability.metrics.registry()`` keeps while metrics are
on (``serving/step``: one record per engine step, written by the
serving worker; ``executor/run_host_ms``: one number per
``Executor.run``). The registry is process-wide because the runner
closes and deletes the engine before the readers run.

``series`` names the samples. Of a record series, ``kind`` keeps the
steps of one kind, and either ``field`` with ``stat`` (``median``,
``mean``, ``max``, ``p<q>``) gives a statistic of one field, or
``ratio`` (two fields) the sum of the first over the sum of the second.
Times ``scale``.

The population is every step the process ran warm (``cold`` false: the
dispatch neither traced nor compiled), so the tail of the warm-up, the
ramp, the window and the drain: the runner hands readers no absolute
window to clip to. Nothing is returned, and the metric is left out,
where the program keeps no such samples (a parent commit without the
log), or where fewer than half of the steps of the kind carry the
field."""

from perfbench.layer_metrics.readers import stat as _stat


def warm_records(series, kind=None):
    """The series' records, or None where the program has none."""
    from paddle_tpu.observability import metrics

    samples = metrics.registry().metrics().get(series)
    if samples is None or not hasattr(samples, "records"):
        return None
    recs = samples.records()
    if recs and isinstance(recs[0], dict):
        recs = [r for r in recs if not r.get("cold")
                and (kind is None or r.get("kind") == kind)]
    return recs


def read(obs, series, field=None, ratio=None, kind=None, stat="median",
         scale=1.0):
    recs = warm_records(series, kind)
    if not recs:
        return None
    if ratio is not None:
        above, below = (sum(r[f] for r in recs) for f in ratio)
        return float(above) / float(below) * scale if below else None
    values = recs if field is None else \
        [r[field] for r in recs if r.get(field) is not None]
    if not values or 2 * len(values) < len(recs):
        return None
    return _stat.read({"values": values}, "values", stat, scale)
