"""The ten per-layer metrics that read the request log
(``serving/request``; ``rows_deferred_per_mixed_step`` reads
``serving/step``): every file is data on the ``step_log`` /
``step_log_ratio`` readers, reads the number it names from a synthetic
log, is left out on a program without the log, is listed once in
``BENCHMARK.json`` for the two steady cells, and comes out of a traced
CPU rehearsal of the toy open-loop cell."""

import json
import os
import shutil

import pytest

from paddle_tpu.observability import metrics
from perfbench import run, spec
from perfbench.layer_metrics.readers import step_log, step_log_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_ROOT = os.path.join(HERE, "root")
STEADY = ["xglm-1.7b.chat-steady", "xglm-1.7b.doc-steady"]
# metric -> (layer, moves, source)
ENGINE, STEPS = "serving engine", "serving steps"
NEW = {
    "ttft_log_ms.serve": (ENGINE, "ttft_p50_ms", "program_span"),
    "ttft_queue_ms.serve": (ENGINE, "ttft_p50_ms", "program_span"),
    "ttft_prefill_ms.serve": (STEPS, "ttft_p50_ms", "program_span"),
    "ttft_inflight_ms.serve": (ENGINE, "ttft_p50_ms", "program_span"),
    "ttft_ahead_ms.serve": (ENGINE, "ttft_p50_ms", "program_span"),
    "ttft_deliver_ms.serve": (ENGINE, "ttft_p50_ms", "program_span"),
    "prefill_steps_per_request.serve": (STEPS, "ttft_p50_ms",
                                        "program_counter"),
    "prefill_deferred_steps_p90.serve": (ENGINE, "ttft_p50_ms",
                                         "program_counter"),
    "rows_deferred_per_mixed_step.serve": (ENGINE, "ttft_p50_ms",
                                           "program_counter"),
    "gaps_behind_mixed_pct.serve": (ENGINE, "itl_p99_ms",
                                    "program_counter"),
}


@pytest.fixture
def registry():
    metrics.disable()
    metrics.reset()
    try:
        yield metrics.registry()
    finally:
        metrics.disable()
        metrics.reset()


def read(metric):
    args, reader = spec.layer_metric(metric)
    assert reader in (step_log.read, step_log_ratio.read)
    return reader({}, **args)


def request(ttft, queue=1.0, prefill=0.0, inflight=20.0, ahead=8.0,
            deliver=0.5, steps=1, deferred=0, gaps=10, mixed=1,
            cold=False):
    return {"ttft_ms": ttft, "queue_ms": queue, "prefill_ms": prefill,
            "inflight_ms": inflight, "ahead_ms": ahead,
            "deliver_ms": deliver, "prefill_steps": steps,
            "deferred_steps": deferred, "gaps": gaps, "gaps_mixed": mixed,
            "cold": cold}


def test_each_file_parses_and_names_a_reader_that_is_there():
    for name in NEW:
        path = os.path.join(spec.ROOT, "perfbench", "layer_metrics",
                            name.rpartition(".")[0] + ".json")
        meta = spec.read_json(path)
        assert set(meta) == {"reader", "args", "source"}
        assert meta["reader"] in ("step_log", "step_log_ratio")
        assert "warm" in meta["source"] and "Left out" in meta["source"]
        args, reader = spec.layer_metric(name)
        assert args == meta["args"] and callable(reader)


def test_the_readings_over_a_synthetic_log(registry):
    log = registry.samples("serving/request")
    # a request a cold step carried: never in the population
    log.add(request(9000.0, queue=4000.0, steps=9, deferred=9, gaps=1,
                    mixed=1, cold=True))
    log.add(request(30.0, queue=1.0, inflight=22.0, ahead=9.0))
    log.add(request(40.0, queue=3.0, inflight=28.0, ahead=None,
                    deliver=0.7, gaps=20, mixed=3))
    log.add(request(110.0, queue=2.0, prefill=60.0, inflight=30.0,
                    ahead=15.0, deliver=0.6, steps=6, deferred=4, gaps=30,
                    mixed=8))
    # a request that failed before its first token: counts, no times
    log.add(dict(request(None, queue=5.0, prefill=None, inflight=None,
                         ahead=None, deliver=None, steps=2, gaps=0,
                         mixed=0)))
    steps = registry.samples("serving/step")
    for kind, deferred in (("mixed", 0), ("mixed", 3), ("decode", None),
                           ("mixed", 0), ("mixed", 1)):
        rec = {"kind": kind, "cold": False}
        if deferred is not None:
            rec["rows_deferred"] = deferred
        steps.add(rec)
    assert read("ttft_log_ms.serve") == 40.0
    assert read("ttft_queue_ms.serve") == 2.5          # of four
    assert read("ttft_prefill_ms.serve") == 0.0
    assert read("ttft_inflight_ms.serve") == 28.0
    assert read("ttft_ahead_ms.serve") == 12.0         # of two: half
    assert read("ttft_deliver_ms.serve") == 0.6
    assert read("prefill_steps_per_request.serve") == 2.5
    assert read("prefill_deferred_steps_p90.serve") \
        == pytest.approx(2.8)                          # 0, 0, 0, 4
    assert read("rows_deferred_per_mixed_step.serve") == 1.0
    assert read("gaps_behind_mixed_pct.serve") \
        == pytest.approx(100.0 * 12 / 60)


def test_a_program_without_the_request_log_reads_nothing(registry):
    """The parent commit writes `serving/step` and no `serving/request`:
    nine metrics are left out and nothing raises; the tenth reads the
    step log's `rows_deferred`, which the parent has."""
    registry.samples("serving/step").add(
        {"kind": "mixed", "cold": False, "rows_deferred": 2})
    for name in NEW:
        got = read(name)
        assert got == (2.0 if name.startswith("rows_deferred") else None)
    # records that lack the fields (an older log under the same name)
    registry.samples("serving/request").add({"cold": False, "gaps": 3})
    for name in NEW:
        if not name.startswith("rows_deferred"):
            assert read(name) is None, name


def test_benchmark_json_lists_each_once_for_the_steady_cells():
    bench = spec.load_benchmark()
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name, (layer, moves, source) in NEW.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == STEADY
        assert (m["layer"], m["moves"], m["source"]) \
            == (layer, moves, source)
        assert m["layer"] in layers and m["better"] == "lower"
    # appended: the entries that were there keep their places
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] \
        == list(NEW)
    for cell in STEADY:
        reported = [m["name"] for m in
                    spec.metrics_of(bench, "end_to_end", cell)]
        names = [m["name"] for m in
                 spec.metrics_of(bench, "per_layer", cell, reported)]
        assert set(NEW) <= set(names)
        assert {"ttft_p50_ms", "itl_p99_ms"} <= set(reported)
    for cell in ("xglm-1.7b.batch-closed", "xglm-564m.pretrain-2k",
                 "kanana-2-30b-a3b.decode-closed",
                 "trinity-large-preview.long-closed"):
        names = [m["name"] for m in
                 spec.metrics_of(bench, "per_layer", cell, ())]
        assert not set(NEW) & set(names)


@pytest.fixture
def toy_root(tmp_path):
    """The toy benchmark of perfbench/tests/root with the committed
    benchmark's ten entries appended for its open-loop cell."""
    root = str(tmp_path / "root")
    shutil.copytree(TEST_ROOT, root)
    path = os.path.join(root, "BENCHMARK.json")
    toy = spec.read_json(path)
    for m in spec.load_benchmark()["per_layer"]:
        if m["name"] in NEW:
            toy["per_layer"].append(dict(m, workloads=["tiny.open"]))
    with open(path, "w") as f:
        json.dump(toy, f)
    return root


def test_a_traced_rehearsal_reads_a_number_from_every_file(
        toy_root, registry):
    line = run.run_cell("tiny.open", 2147483661, 1.5, 1,
                        require_chip=False, root=toy_root)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items() if k in NEW}
    print(json.dumps(got))
    # on the CPU a toy step is done before the host asks for it, so the
    # first-token step may have no device time and `ahead_ms` with it:
    # that one is read below from the records that do carry it
    assert set(NEW) - {"ttft_ahead_ms.serve"} <= set(got) <= set(NEW)
    recs = step_log.warm_records("serving/request")
    assert recs and all(r["outcome"] == "finished" for r in recs)
    phases = ("ttft_queue_ms.serve", "ttft_prefill_ms.serve",
              "ttft_inflight_ms.serve", "ttft_deliver_ms.serve")
    assert all(got[p] >= 0 for p in phases)
    # medians of the phases of one population: under its median sum
    assert sum(got[p] for p in phases) <= 2 * got["ttft_log_ms.serve"]
    assert 0 < got["ttft_log_ms.serve"] \
        <= max(r["ttft_ms"] for r in recs)
    # the toy's prompts are 4-48 tokens over chunks of 16
    assert 1 <= got["prefill_steps_per_request.serve"] <= 3
    assert got["prefill_deferred_steps_p90.serve"] >= 0
    assert got["rows_deferred_per_mixed_step.serve"] >= 0
    assert 0 <= got["gaps_behind_mixed_pct.serve"] <= 100
    have = [r["ahead_ms"] for r in recs if r["ahead_ms"] is not None]
    if "ttft_ahead_ms.serve" in got:
        assert 2 * len(have) >= len(recs)
        assert min(have) - 1e-6 <= got["ttft_ahead_ms.serve"] <= max(have)
    else:
        assert 2 * len(have) < len(recs)
    # the record's identity holds in the benchmark's own run
    for r in recs:
        assert r["queue_ms"] + r["plan_ms"] + r["prefill_ms"] \
            + r["inflight_ms"] + r["deliver_ms"] == r["ttft_ms"]
