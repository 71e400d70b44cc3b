"""CPU tests of runner ``serve_zaya``, family ``zaya``'s FLOPs and the
three per-layer metrics PR 42 added (toy widths, no chip;
``perfbench/tests/root_zaya`` is a benchmark of added files that leans
on the committed per-layer metric files)."""

import json
import os

import pytest

from perfbench import control_block, run, spec
from perfbench.flops import zaya as flops
from perfbench.layer_metrics.readers import step_log, traced_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "root_zaya")
CELL = "tiny-zaya.closed"
SERVED = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "zaya1-8b-serve.json"))


def rehearse(trace=0, hooks=None):
    return run.run_cell(CELL, 2147483659, 2.0, trace, require_chip=False,
                        root=ROOT, hooks=hooks)


@pytest.mark.parametrize("trace,expect", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    (1, {"engine_step_ms.batch", "batch_occupancy_mean.batch",
         "ttft_p90_ms.batch", "itl_p95_ms.batch", "decode_step_ms.batch",
         "mixed_step_ms.batch", "chunk_window_fill_pct.batch",
         "chunk_rows_fill_pct.batch", "engine_host_ms_per_step.batch",
         "engine_host_max_ms.batch", "engine_wait_max_ms.batch",
         "experts_touched_pct.decode", "kv_pool_used_pct.batch",
         "decode_weight_bytes_per_param.batch", "mfu_pct.batch",
         "expert_top_load_pct.decode", "carry_rows_pct.batch"})])
def test_rehearsal_ends_in_a_well_formed_correct_result(trace, expect):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0
    # off the chip there is no device trace: the device_trace metrics'
    # readers return nothing and the line leaves them out
    assert set(line["metrics"]) == expect
    if trace:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # decode steps read the carry for every token, chunks for a
        # row's first alone
        assert 0 < m["carry_rows_pct.batch"] < 100
        # the busiest of 4 experts holds at least a fair share
        assert 25 <= m["expert_top_load_pct.decode"] <= 100
        assert m["mfu_pct.batch"] > 0


@pytest.mark.parametrize("name", ["int8_expert_weights", "bf16_router",
                                  "carry_ignored"])
def test_a_control_is_not_correct(name, capsys):
    rc = control_block.main(["--workload", CELL, "--seed", "2147483659",
                             "--seconds", "3", "--control", name],
                            require_chip=False, root=ROOT)
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is False, out[-8:]
    assert line["control"]["name"] == name


def test_flops_of_the_served_configuration():
    assert flops.token_cache_bytes(SERVED) == 1024
    assert flops.cache_bytes_per_token(SERVED) == 20 * 1024
    # an expert is 12.58 M parameters: three 2048 x 2048 matrices
    assert flops.gmm_bytes(SERVED, 1, 0) == 3 * 2048 * 2048 * 2
    assert flops.gmm_flops(SERVED, 2) == 2 * 6 * 2048 * 2048
    # a layer outside its experts: W_q and W_o 2.10 M each, W_k 0.52 M,
    # the two value projections 0.26 M each, the grouped taps 0.33 M,
    # the router 0.66 M
    assert flops.matmul_params_per_token(SERVED) == 20 * (
        2 * 2097152 + 524288 + 2 * 262144 + 327680
        + 2048 * 256 + 2 * 256 * 256 + 256 * 16)
    assert flops.attention_flops(SERVED, 10, 0) == 10 * 8 * 128 * 4
    # one decode step of 96 rows: every expert of every layer once
    step = flops.gmm_bytes(SERVED, 16 * 20, 96 * 20)
    assert 8.05e9 < step < 8.2e9
    # the head over the whole tied vocabulary, once a row
    assert flops.step_flops(SERVED, 0, 1, 0, 0, 0) == 2 * 2048 * 262272


def test_the_carry_metric_reads_the_steps_own_counter(monkeypatch):
    recs = [{"kind": "decode", "cold": False, "t_dispatched": 1.0,
             "carry_rows": 90, "slots_used": 90},
            {"kind": "mixed", "cold": False, "t_dispatched": 2.0,
             "carry_rows": 80, "slots_used": 1110},
            {"kind": "mixed", "cold": False, "t_dispatched": 20.0,
             "carry_rows": 1, "slots_used": 1}]
    monkeypatch.setattr(step_log, "warm_records",
                        lambda series, kind=None: [
                            r for r in recs if kind in (None, r["kind"])])
    args, read = spec.layer_metric("carry_rows_pct.batch", ROOT)
    got = read({"traced_span": (0.0, 10.0)}, **args)
    assert got == pytest.approx(100.0 * 170 / 1200)
    # a program without the counter (the parent): nothing, and no raise
    for r in recs:
        del r["carry_rows"]
    assert read({"traced_span": (0.0, 10.0)}, **args) is None
    assert traced_ratio.read({}, "serving/step", ["a", "b"]) is None
