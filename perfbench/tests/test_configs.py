"""The committed serving configurations fill the chip, and the
committed traffic gives a window enough requests. CPU arithmetic on
shapes only: nothing is built and nothing runs.

(ISSUE 36 asked for this file as ``tests/test_perfbench_configs.py``,
collected by tier-1; a benchmark PR may add files under ``perfbench/``
only, so it stands here until a PR that may touch ``tests/`` moves or
collects it: PERF.md section 7.)"""

import glob
import os

import numpy as np
import pytest

from perfbench import loadgen, spec

USABLE_HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler hands out
CONFIGS = sorted(glob.glob(os.path.join(spec.ROOT, "perfbench", "configs",
                                        "*.json")))
SERVING = [p for p in CONFIGS if "engine" in spec.read_json(p)]


def generation_config(config):
    from paddle_tpu.serving import GenerationConfig

    runner = spec.runner(config)
    e = config["engine"]
    if hasattr(runner, "generation_config"):
        return runner.generation_config(config, e["max_seq_len"])
    return GenerationConfig(
        vocab_size=config["vocab_size"], d_model=config["d_model"],
        n_heads=config["attention_heads"], n_layers=config["num_layers"],
        d_ff=config["ffn_dim"], max_seq_len=e["max_seq_len"])


def held_bytes(config, monkeypatch):
    """(weights, pool) bytes as the TPU's store holds them: the dot
    operands in the dtype a default-precision dot consumes there."""
    from paddle_tpu.serving import GenerationModel, model

    monkeypatch.setattr(model, "default_dot_rounds_to_bf16", lambda: True)
    cfg = generation_config(config)
    weights = sum(int(np.prod(shape)) * np.dtype(
        "uint16" if dtype == "bfloat16" else dtype).itemsize
        for shape, dtype in model.leaf_shapes(cfg).values())
    shell = GenerationModel.__new__(GenerationModel)
    shell.config = cfg
    entry = shell.cache_entry()
    token = sum(int(np.prod(shape)) for _n, shape in entry.parts) \
        * (2 if entry.dtype == "bfloat16" else np.dtype(entry.dtype).itemsize)
    e = config["engine"]
    pool = cfg.n_layers * token * e["block_size"] * e["num_blocks"]
    return weights, pool


def test_there_are_serving_configurations_to_look_at():
    assert len(SERVING) >= 2


@pytest.mark.parametrize("path", SERVING, ids=os.path.basename)
def test_a_serving_configuration_fills_the_chip(path, monkeypatch):
    config = spec.read_json(path)
    weights, pool = held_bytes(config, monkeypatch)
    share = (weights + pool) / USABLE_HBM_BYTES
    # ISSUE 36 said 80 %, reckoning kanana's 13.50 GB (decimal) against
    # 15.75 as if both were one unit; of the 15.75 GiB it is 79.8 %
    assert 0.75 <= share <= 0.995, (weights, pool, share)
    # every control that resizes the pool is a size the engine can hold
    for c in config.get("controls", ()):
        blocks = (c.get("value") or {}).get("num_blocks")
        assert blocks is None or 0 < blocks <= config["engine"]["num_blocks"]


def test_the_xglm_store_is_what_the_configuration_says(monkeypatch):
    config = spec.read_json(os.path.join(
        spec.ROOT, "perfbench", "configs", "xglm-1.7b-serve.json"))
    weights, pool = held_bytes(config, monkeypatch)
    assert abs(weights - 5.56e9) < 0.01e9          # "5.56 GB held"
    assert pool == 6291456 * config["engine"]["num_blocks"]
    assert "bf16" in config["precision"] and "5.56 GB" in config["deployment"]
    assert "fp32 weights" not in config["precision"]


def cells():
    bench = spec.load_benchmark()
    return [(w["name"], bench["run_seconds"]) for w in bench["workloads"]]


@pytest.mark.parametrize("cell,seconds", cells())
def test_a_cells_traffic_parses_and_fills_a_window(cell, seconds):
    _w, config, mix = spec.cell(spec.load_benchmark(), cell)
    vocab = config["vocab_size"]
    if mix["kind"] == "open_loop":
        requests = loadgen.open_loop(1, mix, float(seconds), vocab)
        assert len(requests) >= 100
        assert len(requests) == round(mix["rate_per_s"] * seconds)
        assert all(0 < r.due_s < seconds for r in requests)
        longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
        assert longest <= config["engine"]["max_seq_len"]
        # ten gaps or more beyond every judged gap percentile
        gaps = sum(r.max_new_tokens - 1 for r in requests)
        bench = spec.load_benchmark()
        for m in spec.metrics_of(bench, "end_to_end", cell):
            if m["name"].startswith("itl_p"):
                q = float(m["name"][len("itl_p"):-len("_ms")])
                assert loadgen.samples_beyond(gaps, q) >= 10
    elif mix["kind"] == "closed_loop":
        requests = loadgen.closed_loop(1, mix, vocab)
        assert len(requests) == loadgen.CLOSED_LOOP_CYCLE
        assert mix["clients"] > config["engine"]["max_batch"]
    else:
        assert mix["kind"] == "token_stream"
