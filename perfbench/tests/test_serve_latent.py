"""CPU tests of runner ``serve_latent`` and the readers PR 27 added
(toy widths, no chip; ``perfbench/tests/root_latent`` is a benchmark of
added files that leans on the committed per-layer metric files)."""

import json
import os

import pytest

from perfbench import control_block, run, spec
from perfbench.flops import kanana as flops
from perfbench.layer_metrics.readers import (kernel_roofline, kernel_share,
                                             step_log)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "root_latent")
CELL = "tiny-latent.closed"
SERVED = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "kanana-2-30b-a3b-serve.json"))


def rehearse(trace=0, hooks=None):
    return run.run_cell(CELL, 2147483659, 1.5, trace, require_chip=False,
                        root=ROOT, hooks=hooks)


def bad_checks(lines):
    notes = [json.loads(x) for x in lines if x.startswith('{"check"')]
    return {n["check"] for n in notes if not n["ok"]}


@pytest.mark.parametrize("trace,expect", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    (1, {"engine_step_ms.batch", "batch_occupancy_mean.batch",
         "ttft_p90_ms.batch", "itl_p95_ms.batch", "decode_step_ms.batch",
         "mixed_step_ms.batch", "chunk_window_fill_pct.batch",
         "engine_host_ms_per_step.batch", "engine_host_max_ms.batch",
         "engine_wait_max_ms.batch", "experts_touched_pct.decode",
         "expert_load_max_over_mean.decode", "kv_pool_used_pct.batch"})])
def test_rehearsal_ends_in_a_well_formed_correct_result(trace, expect):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # off the chip there is no device trace: the four device_trace
    # metrics' readers return nothing and the line leaves them out
    assert set(line["metrics"]) == expect
    if trace:
        assert 0 < line["metrics"]["experts_touched_pct.decode"]["value"] \
            <= 100


@pytest.mark.parametrize("name", ["int8_expert_weights", "bf16_router"])
def test_a_control_is_not_correct(name, capsys):
    """The two lower-precision controls at toy size, through the control
    tool: expert weights on the int8 grid, the router's scores in
    bfloat16."""
    # a window long enough for the requests whose tokens the int8 grid
    # moves (a handful in a thousand at these widths) on a slow machine
    rc = control_block.main(["--workload", CELL, "--seed", "2147483659",
                             "--seconds", "4", "--control", name],
                            require_chip=False, root=ROOT)
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is False, out[-8:]
    assert line["control"]["name"] == name
    assert {"served_logit_gap_mean", "decided_logit_gap_mean"} \
        <= bad_checks(out)


def _rotated_head(model):
    """A token altered where it is produced: every served token is its
    neighbour in the vocabulary."""
    import jax.numpy as jnp

    w = dict(model.weights)
    w["lm_head"] = jnp.roll(w["lm_head"], 1, axis=1)
    return type(model)(model.config, w)


def _dropped_expert(model):
    """An expert that computes nothing: its down projection is zero in
    every expert layer, so the tokens routed to it lose a sixth... here a
    third of their routed result."""
    w = dict(model.weights)
    for k in list(w):
        if k.endswith("we_down"):
            w[k] = w[k].at[0].set(0)
    return type(model)(model.config, w)


@pytest.mark.parametrize("tamper", [_rotated_head, _dropped_expert])
def test_a_broken_path_is_not_correct(tamper, capsys):
    line = rehearse(hooks={"tamper": tamper})
    assert line["correct"] is False
    assert bad_checks(capsys.readouterr().out.splitlines()) \
        >= {"served_logit_gap_mean", "decided_logit_gap_mean"}


def test_the_gap_is_judged_over_the_decided_tokens(capsys, monkeypatch):
    """``correct.router_margin`` takes the tokens whose router choice is
    nearly tied out of the second mean; where it leaves none (sigmoid
    scores lie within 1 of each other), the run is not correct by
    ``undecided_token_share`` and by an empty mean."""
    line = rehearse()
    note = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith('{"phase": "reference_done"')][-1]
    rows = {r["margin"]: r for r in note["by_margin"]}
    assert line["correct"] and set(rows) == {0.0, 0.01}
    assert 0 < rows[0.01]["tokens"] < rows[0.0]["tokens"] \
        == note["served_tokens"]
    assert note["decided"] == rows[0.0]       # the toy's margin is 0

    real = spec.read_json

    def wide_margin(path):
        d = real(path)
        if path.endswith("tiny-latent.json"):
            d["correct"]["router_margin"] = 1.0
        return d

    monkeypatch.setattr(spec, "read_json", wide_margin)
    assert rehearse()["correct"] is False
    assert bad_checks(capsys.readouterr().out.splitlines()) == {
        "decided_logit_gap_mean", "undecided_token_share"}


def test_the_configuration_keeps_the_published_keys():
    row = [json.loads(x) for x in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "kanana-2-30b-a3b-instruct-2601" in x] \
        if os.path.exists("/opt/skills/guides/model-configs") else []
    for r in row:
        changed = {k for k, v in r["config"].items() if SERVED.get(k) != v}
        assert changed == set(SERVED["reduced"]) == {"num_hidden_layers"}
        assert SERVED["source"] == r["source_url"]
    e = SERVED["engine"]
    assert e["max_batch"] * e["max_seq_len"] == e["num_blocks"] \
        * e["block_size"]
    bench = spec.load_benchmark()
    w, config, mix = spec.cell(bench, "kanana-2-30b-a3b.decode-closed")
    assert config == SERVED and w["chips"] == 1
    assert mix["clients"] == 160 and mix["ramp_s"] == 12
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.8, "min": 32, "max": 512}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 256, "max": 2048}


# -- the readers on hand-made records ----------------------------------------

def _records(monkeypatch, recs):
    monkeypatch.setattr(step_log, "warm_records",
                        lambda series, kind=None: [
                            r for r in recs
                            if kind is None or r["kind"] == kind])


def test_counter_ratios_read_the_traced_stretch_of_the_step_log(
        monkeypatch):
    recs = [dict(kind="decode", t_dispatched=10.0, experts_touched=890,
                 expert_slots=896, expert_rows_max=98, expert_pairs=5376),
            dict(kind="decode", t_dispatched=11.0, experts_touched=896,
                 expert_slots=896, expert_rows_max=112, expert_pairs=5376),
            # a mixed step, and a decode step of the drain: left out
            dict(kind="mixed", t_dispatched=11.5, experts_touched=1,
                 expert_slots=896, expert_rows_max=999, expert_pairs=1),
            dict(kind="decode", t_dispatched=70.0, experts_touched=40,
                 expert_slots=896, expert_rows_max=7, expert_pairs=42)]
    _records(monkeypatch, recs)
    obs = {"traced_span": (9.5, 14.5)}
    touched_args, read = spec.layer_metric("experts_touched_pct.decode")
    assert read(obs, **touched_args) == pytest.approx(100 * 1786 / 1792)
    load_args, read = spec.layer_metric("expert_load_max_over_mean.decode")
    # sum of the busiest experts' rows over the mean rows of an expert
    assert read(obs, **load_args) == pytest.approx(210 / (10752 / 128))
    assert read({}, **load_args) is None          # not traced: nothing


def test_roofline_and_share_readers(monkeypatch):
    recs = [dict(kind="decode", t_dispatched=10.0 + i, rows=128,
                 experts_touched=890, expert_pairs=5376,
                 cached_tokens=100_000) for i in range(4)]
    recs.append(dict(kind="decode", t_dispatched=99.0, rows=1,
                     experts_touched=1, expert_pairs=1, cached_tokens=1))
    _records(monkeypatch, recs)
    obs = {"config": SERVED, "peaks": {"hbm_bytes_per_s": 819e9},
           "traced_span": (9.5, 14.5), "trace": {"busy_s": 4.0},
           "kernel_trace": {"modules": 200, "module": "jit_decode_step",
                            "kernels": {
                                "gmm": {"all_s": 2.8, "in_module_s": 2.4},
                                "latent_paged_attention": {
                                    "all_s": 0.6, "in_module_s": 0.4}}}}
    args, read = spec.layer_metric("gmm_roofline_pct.decode")
    need = flops.gmm_bytes(SERVED, 890 * 200, 5376 * 200)
    assert read(obs, **args) == pytest.approx(100 * need / 2.4 / 819e9)
    assert 0 < read(obs, **args) < 100
    args, read = spec.layer_metric("latent_attn_roofline_pct.decode")
    need = flops.latent_attention_bytes(SERVED, 100_000 * 200, 128 * 200)
    assert read(obs, **args) == pytest.approx(100 * need / 0.4 / 819e9)
    args, read = spec.layer_metric("experts_device_share_pct.decode")
    assert read(obs, **args) == pytest.approx(70.0)
    args, read = spec.layer_metric("latent_attn_device_share_pct.decode")
    assert read(obs, **args) == pytest.approx(15.0)
    # a program without the counters or a run without a trace: nothing
    for missing in ({}, dict(obs, kernel_trace=None),
                    dict(obs, traced_span=None)):
        for name in ("gmm_roofline_pct.decode",
                     "experts_device_share_pct.decode"):
            args, read = spec.layer_metric(name)
            if name.startswith("experts") and missing.get("kernel_trace"):
                continue
            assert read(missing, **args) is None
    assert kernel_share.collect(os.path.join(HERE, "no-such-dir"),
                                ("gmm",), "jit_decode_step") is None
    assert kernel_roofline.read(dict(obs, traced_span=(0.0, 1.0)),
                                "gmm", "gmm_bytes",
                                ["experts_touched", "expert_pairs"]) is None


def test_bytes_functions_count_a_weight_once_and_touched_experts_only():
    d = flops.dims(SERVED)
    one = 3 * d["D"] * d["Fe"] * 2
    assert flops.gmm_bytes(SERVED, 1, 0) == one
    assert flops.gmm_bytes(SERVED, 896, 0) == 896 * one == 8_455_716_864
    rows = flops.gmm_bytes(SERVED, 0, 768)
    assert rows == 768 * ((2 * 2048 + 768) * 2 + (2 * 768 + 2048) * 4)
    assert flops.cache_bytes_per_token(SERVED) == 9216
    assert flops.latent_attention_bytes(SERVED, 1000, 0) == 1000 * 9216
    assert flops.expert_layers(SERVED) == 7
