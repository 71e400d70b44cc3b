"""The step log's reader and the seven metrics it serves: over a
synthetic registry, and in a traced CPU rehearsal of the toy cells with
the committed benchmark's new entries laid over the toy benchmark."""

import json
import os
import shutil

import pytest

from paddle_tpu.observability import metrics
from perfbench import run, spec
from perfbench.layer_metrics.readers import step_log

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_ROOT = os.path.join(HERE, "root")
NEW = {
    "serve": ["decode_step_ms", "mixed_step_ms", "chunk_window_fill_pct",
              "engine_host_ms_per_step", "engine_host_max_ms",
              "engine_wait_max_ms"],
    "train": ["run_host_ms"]}


@pytest.fixture
def registry():
    metrics.disable()
    metrics.reset()
    try:
        yield metrics.registry()
    finally:
        metrics.disable()
        metrics.reset()


def step(kind, device_ms, host_ms=1.0, wait_ms=2.0, used=4, total=16,
         cold=False):
    return {"kind": kind, "device_ms": device_ms, "host_ms": host_ms,
            "wait_ms": wait_ms, "slots_used": used, "slots_total": total,
            "cold": cold}


def read(metric, obs=None):
    args, reader = spec.layer_metric(metric)
    assert reader is step_log.read
    return reader(obs or {}, **args)


def test_the_readers_over_a_synthetic_log(registry):
    log = registry.samples("serving/step")
    # a cold step's times are a compile's: never in the population
    log.add(step("decode", 900.0, host_ms=5000.0, wait_ms=700.0,
                 cold=True))
    for ms in (50.0, 54.0, 56.0, None):
        log.add(step("decode", ms))
    for ms, used in ((270.0, 190), (280.0, 700)):
        log.add(step("mixed", ms, host_ms=3.0, wait_ms=250.0, used=used,
                     total=4096))
    log.add(step("mixed", None, host_ms=40.0, used=134, total=4096))
    assert read("decode_step_ms.serve") == 54.0
    assert read("decode_step_ms.batch") == 54.0
    assert read("mixed_step_ms.serve") == 275.0
    assert read("chunk_window_fill_pct.serve") \
        == pytest.approx(100.0 * 1024 / (3 * 4096))
    assert read("engine_host_ms_per_step.batch") == 1.0
    assert read("engine_host_max_ms.serve") == 40.0
    assert read("engine_wait_max_ms.serve") == 250.0
    runs = registry.samples("executor/run_host_ms")
    for ms in (900.0, 7.0, 6.0, 8.0, 7.5):
        runs.add(ms)
    assert read("run_host_ms.train") == 7.5


def test_a_field_on_under_half_of_the_steps_reads_nothing(registry):
    log = registry.samples("serving/step")
    for ms in (55.0, None, None):
        log.add(step("decode", ms))
    log.add(step("mixed", 275.0))
    assert read("decode_step_ms.serve") is None      # 1 of 3
    assert read("mixed_step_ms.serve") == 275.0
    log.add(step("decode", 57.0))                    # 2 of 4: half
    assert read("decode_step_ms.serve") == 56.0


def test_a_program_without_the_log_reads_nothing(registry):
    """The parent commit's registry has no such samples (and no
    `samples` kind): every new metric is left out, nothing raises."""
    for names in NEW.values():
        for name in names:
            suffix = ".train" if name == "run_host_ms" else ".serve"
            assert read(name + suffix) is None
    registry.counter("serving/step")     # a name taken by another kind
    assert read("engine_host_max_ms.serve") is None
    assert step_log.warm_records("serving/step") is None


def step_log_entries(bench):
    """PR 24's thirteen entries of the committed benchmark, by name."""
    return [m for m in bench["per_layer"]
            if m["name"].rpartition(".")[0] in NEW["serve"] + NEW["train"]]


def test_the_committed_entries_name_the_reader_and_the_layers():
    bench = spec.load_benchmark()
    added = step_log_entries(bench)
    assert len(added) == 13
    layers = {m["layer"] for m in bench["per_layer"] if m not in added}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in added:
        assert m["layer"] in layers
        stem, _, suffix = m["name"].rpartition(".")
        # a step time moves the gap percentile that sits on that kind
        # of step; the closed loops' and the trainer's move the rate
        assert m["moves"] in {"serve": ("itl_p50_ms", "itl_p99_ms",
                                        "itl_p95_ms"),
                              "batch": ("serve_tokens_per_s",),
                              "train": ("train_tokens_per_s",)}[suffix]
        # and is read in cells that report what it moves
        assert m["workloads"] and set(m["workloads"]) \
            <= set(e2e[m["moves"]]["workloads"])
        meta = spec.read_json(os.path.join(
            spec.ROOT, "perfbench", "layer_metrics", stem + ".json"))
        assert meta["reader"] == "step_log" and "warm" in meta["source"]


@pytest.fixture
def toy_root(tmp_path):
    """The toy benchmark of perfbench/tests/root with the committed
    benchmark's thirteen step-log entries appended to it."""
    root = str(tmp_path / "root")
    shutil.copytree(TEST_ROOT, root)
    path = os.path.join(root, "BENCHMARK.json")
    toy = spec.read_json(path)
    cells = {"serve": "tiny.open", "batch": "tiny.closed",
             "train": "tiny.train"}
    for m in step_log_entries(spec.load_benchmark()):
        # the committed entry, in the toy cell of its suffix's kind
        toy["per_layer"].append(dict(
            m, workloads=[cells[m["name"].rpartition(".")[2]]]))
    with open(path, "w") as f:
        json.dump(toy, f)
    return root


@pytest.mark.parametrize("cell,suffix,kind", [
    ("tiny.closed", ".batch", "serve"), ("tiny.open", ".serve", "serve"),
    ("tiny.train", ".train", "train")])
def test_a_traced_rehearsal_prints_the_cells_new_metrics(
        toy_root, registry, cell, suffix, kind, capsys):
    line = run.run_cell(cell, 2147483659, 1.5, 1, require_chip=False,
                        root=toy_root)
    assert line["correct"] is True
    got = {k: v for k, v in line["metrics"].items()
           if k.rpartition(".")[0] in NEW[kind]}
    print(json.dumps(got))
    # on the CPU a toy step is done before the host asks for it, so the
    # two device times may be left out; the rest are always there
    optional = {"decode_step_ms" + suffix, "mixed_step_ms" + suffix}
    assert {n + suffix for n in NEW[kind]} - optional <= set(got) \
        <= {n + suffix for n in NEW[kind]}
    for name, m in got.items():
        assert m["value"] >= 0.0, name
    if kind == "serve":
        assert 0 < got["chunk_window_fill_pct" + suffix]["value"] <= 100
        assert got["engine_host_max_ms" + suffix]["value"] \
            >= got["engine_host_ms_per_step" + suffix]["value"]
        recs = step_log.warm_records("serving/step")
        assert {r["kind"] for r in recs} == {"decode", "mixed"}
    else:
        assert got["run_host_ms.train"]["value"] \
            < line["metrics"]["step_ms.train"]["value"]
