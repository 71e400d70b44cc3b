"""``readers/step_log_ratio.py`` and the metric it serves,
``chunk_rows_fill_pct``: over a synthetic registry, with and without
the field a parent commit's records lack."""

import pytest

from paddle_tpu.observability import metrics
from perfbench import spec
from perfbench.layer_metrics.readers import step_log_ratio


@pytest.fixture
def log():
    metrics.disable()
    metrics.reset()
    try:
        yield metrics.registry().samples("serving/step")
    finally:
        metrics.disable()
        metrics.reset()


def mixed(used, rows=None, cold=False):
    rec = {"kind": "mixed", "slots_used": used, "slots_total": 4096,
           "cold": cold}
    if rows is not None:
        rec["rows_computed"] = rows
    return rec


@pytest.mark.parametrize("metric", ["chunk_rows_fill_pct.serve",
                                    "chunk_rows_fill_pct.batch"])
def test_the_ratio_and_the_parent_without_the_field(log, metric):
    args, reader = spec.layer_metric(metric)
    assert reader is step_log_ratio.read
    assert reader({}, **args) is None                 # no log at all
    log.add({"kind": "decode", "slots_used": 16, "slots_total": 16,
             "cold": False})
    assert reader({}, **args) is None                 # no mixed step
    log.add(mixed(700, 1040, cold=True))              # a compile's step
    for used in (190, 230, 290):
        log.add(mixed(used))                          # the parent's records
    assert reader({}, **args) is None                 # and it does not raise
    for used in (182, 219, 204):
        log.add(mixed(used, 1040))
    # half of the warm mixed steps carry the field: read over those
    assert reader({}, **args) == pytest.approx(
        100.0 * (182 + 219 + 204) / (3 * 1040))
