"""The four per-layer metrics of PR 43 that say how deep the serving
worker's queue ran and what a shallow one cost (``dry_dispatch_pct.*``,
``steps_queued_ahead.*``): two data files on the ``step_log`` reader,
each reading the field it names from a synthetic log and left out on a
program whose records lack it, listed in ``BENCHMARK.json`` for the
cells whose runner writes ``serving/step`` (the six that serve through
a ``ServingEngine``) and no other, and coming out of a traced CPU
rehearsal of the toy serving cells."""

import json
import os
import shutil

import pytest

from paddle_tpu.observability import metrics
from perfbench import run, spec
from perfbench.layer_metrics.readers import step_log

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_ROOT = os.path.join(HERE, "root")
STEADY = ["xglm-1.7b.chat-steady", "xglm-1.7b.doc-steady"]
CLOSED = ["xglm-1.7b.batch-closed", "kanana-2-30b-a3b.decode-closed",
          "trinity-large-preview.long-closed", "zaya1-8b.reason-closed"]
# metric -> (field, moves, better, cells)
NEW = {
    "dry_dispatch_pct.serve": ("ran_dry", "ttft_p50_ms", "lower", STEADY),
    "dry_dispatch_pct.batch": ("ran_dry", "serve_tokens_per_s", "lower",
                               CLOSED),
    "steps_queued_ahead.serve": ("queued", "ttft_p50_ms", "lower", STEADY),
    "steps_queued_ahead.batch": ("queued", "serve_tokens_per_s", "higher",
                                 CLOSED),
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
    assert reader is step_log.read
    return reader({}, **args)


def test_each_file_parses_and_names_its_field():
    # that the worker's records carry the fields: the rehearsal below
    for name, (field, _moves, _better, _cells) in NEW.items():
        path = os.path.join(spec.ROOT, "perfbench", "layer_metrics",
                            name.rpartition(".")[0] + ".json")
        meta = spec.read_json(path)
        assert set(meta) == {"reader", "args", "source"}
        assert meta["reader"] == "step_log"
        assert meta["args"]["series"] == "serving/step"
        assert meta["args"]["field"] == field
        assert "warm" in meta["source"] and "Left out" in meta["source"]
        args, reader = spec.layer_metric(name)
        assert args == meta["args"] and callable(reader)


def test_the_readings_over_a_synthetic_log(registry):
    log = registry.samples("serving/step")
    # a cold step: never in the population
    log.add({"kind": "decode", "cold": True, "queued": 0, "ran_dry": True})
    for kind, queued, dry in (("mixed", 0, False), ("decode", 1, False),
                              ("decode", 1, True), ("mixed", 1, False),
                              ("decode", 1, False)):
        log.add({"kind": kind, "cold": False, "queued": queued,
                 "ran_dry": dry})
    for suffix in ("serve", "batch"):
        assert read("dry_dispatch_pct." + suffix) == pytest.approx(20.0)
        assert read("steps_queued_ahead." + suffix) == pytest.approx(0.8)


def test_a_program_without_ran_dry_leaves_one_out_and_reads_the_other(
        registry):
    """The parent commit's step records carry `queued` and no `ran_dry`:
    `dry_dispatch_pct.*` is left out and nothing raises; a program with
    no step log reads nothing at all."""
    for name in NEW:
        assert read(name) is None
    log = registry.samples("serving/step")
    for queued in (0, 3, 3, 3):
        log.add({"kind": "decode", "cold": False, "queued": queued})
    for suffix in ("serve", "batch"):
        assert read("dry_dispatch_pct." + suffix) is None
        assert read("steps_queued_ahead." + suffix) == 2.25


def test_benchmark_json_lists_them_for_the_serving_cells_alone():
    bench = spec.load_benchmark()
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name, (_field, moves, better, cells) in NEW.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == cells
        assert (m["layer"], m["moves"], m["better"], m["source"]) \
            == ("serving engine", moves, better, "program_counter")
        assert m["layer"] in layers
    # appended together behind the entries that were there (a later PR
    # appends behind these: they need not stay last)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(NEW)))
    assert names[at:at + len(NEW)] == list(NEW) and at >= 63
    # the cells whose runner writes `serving/step`: those an accepted
    # metric already reads that series in. Every one of them is listed,
    # no other is, and each reports the end-to-end metric the entry moves
    logged = set()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            continue
        args, _reader = spec.layer_metric(m["name"])
        if args.get("series") == "serving/step":
            logged.update(m["workloads"])
    assert logged == set(STEADY + CLOSED)
    for w in bench["workloads"]:
        cell = w["name"]
        reported = [m["name"] for m in
                    spec.metrics_of(bench, "end_to_end", cell)]
        names = [m["name"] for m in
                 spec.metrics_of(bench, "per_layer", cell, reported)]
        want = {n for n, (_f, _m, _b, cells) in NEW.items()
                if cell in cells}
        assert set(NEW) & set(names) == want
        assert len(want) == (2 if cell in logged else 0)
        assert all(NEW[n][1] in reported for n in want)


@pytest.mark.parametrize("cell,suffix", [("tiny.open", "serve"),
                                         ("tiny.closed", "batch")])
def test_a_traced_rehearsal_reads_both_from_the_toy_cells(
        tmp_path, registry, cell, suffix):
    """The committed entries laid over the toy benchmark for its
    open-loop and its closed-loop cell: the line of a traced CPU
    rehearsal carries both numbers, and they are the step log's."""
    root = str(tmp_path / "root")
    shutil.copytree(TEST_ROOT, root)
    path = os.path.join(root, "BENCHMARK.json")
    toy = spec.read_json(path)
    names = [n for n in NEW if n.endswith("." + suffix)]
    moves = {m["name"] for m in spec.metrics_of(toy, "end_to_end", cell)}
    for m in spec.load_benchmark()["per_layer"]:
        if m["name"] in names:
            assert m["moves"] in moves
            toy["per_layer"].append(dict(m, workloads=[cell]))
    with open(path, "w") as f:
        json.dump(toy, f)
    line = run.run_cell(cell, 2147483677, 1.5, 1, require_chip=False,
                        root=root)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items() if k in NEW}
    print(json.dumps(got))
    assert set(got) == set(names)
    recs = step_log.warm_records("serving/step")
    assert recs and all(type(r["ran_dry"]) is bool for r in recs)
    assert got["dry_dispatch_pct." + suffix] == pytest.approx(
        100.0 * sum(r["ran_dry"] for r in recs) / len(recs))
    assert 0 <= got["dry_dispatch_pct." + suffix] <= 100
    # the toy engine states no depth: one step queued at most
    assert max(r["queued"] for r in recs) == 1
    assert 0 < got["steps_queued_ahead." + suffix] <= 1
