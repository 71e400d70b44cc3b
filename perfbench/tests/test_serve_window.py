"""CPU tests of runner ``serve_window`` and the readers PR 37 added (toy
widths, no chip; ``perfbench/tests/root_window`` is a benchmark of added
files that leans on the committed per-layer metric files)."""

import json
import os

import pytest

from perfbench import control_block, run, spec
from perfbench.flops import trinity as flops
from perfbench.layer_metrics.readers import kernel_bound, serve_mfu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "root_window")
CELL = "tiny-window.closed"
SERVED = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "trinity-large-preview-serve.json"))


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
         "experts_touched_pct.decode",
         "kv_pool_used_pct.batch", "decode_weight_bytes_per_param.batch",
         "mfu_pct.batch", "window_pages_walked_pct.decode",
         "global_pool_used_pct.batch", "window_pool_used_pct.batch"})])
def test_rehearsal_ends_in_a_well_formed_correct_result(trace, expect):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0
    # off the chip there is no device trace: the device_trace metrics'
    # readers return nothing and the line leaves them out
    assert set(line["metrics"]) == expect
    if trace:
        walked = line["metrics"]["window_pages_walked_pct.decode"]["value"]
        assert 0 < walked < 100          # the window engages
        assert 0 < line["metrics"]["window_pool_used_pct.batch"]["value"] \
            <= 100


@pytest.mark.parametrize("name", ["int8_expert_weights", "bf16_router",
                                  "window_ignored"])
def test_a_control_is_not_correct(name, capsys):
    rc = control_block.main(["--workload", CELL, "--seed", "2147483659",
                             "--seconds", "3", "--control", name],
                            require_chip=False, root=ROOT)
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is False, out[-8:]
    assert line["control"]["name"] == name


def test_flops_of_the_served_configuration():
    d = flops.dims(SERVED)
    assert (d["global_layers"], d["window_layers"]) == (1, 4)
    assert flops.token_cache_bytes(SERVED) == 4096
    assert flops.cache_bytes_per_token(SERVED) == 5 * 4096
    # an expert is 28.31 M parameters: three 3072 x 3072 matrices
    assert flops.gmm_bytes(SERVED, 1, 0) == 3 * 3072 * 3072 * 2
    assert flops.gmm_flops(SERVED, 2) == 2 * 6 * 3072 * 3072
    # attention 62.91 M a layer, dense 113.25 M, shared 28.31 M, router
    assert flops.matmul_params_per_token(SERVED) == (
        5 * 62914560 + 113246208 + 4 * (28311552 + 3072 * 256))
    assert flops.attention_flops(SERVED, 10, 5) == 15 * 48 * 128 * 4


def test_kernel_bound_takes_the_larger_bound():
    obs = {"config": SERVED,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "traced_span": (0.0, 10.0),
           "kernel_trace": {"modules": 2, "kernels": {
               "gqa_paged_decode_attention": {"in_module_s": 0.004,
                                              "all_s": 0.004}}}}
    recs = [{"kind": "decode", "cold": False, "t_dispatched": 1.0,
             "rows": 48, "global_keys_attended": 400000,
             "window_keys_attended": 600000}]
    import perfbench.layer_metrics.readers.step_log as step_log
    orig = step_log.warm_records
    step_log.warm_records = lambda series, kind=None: [
        r for r in recs if kind in (None, r["kind"])]
    try:
        got = kernel_bound.read(
            obs, "gqa_paged_decode_attention", "kernel_trace", "decode",
            "attention_bytes", ["global_keys_attended",
                                "window_keys_attended", "rows"],
            "attention_flops", ["global_keys_attended",
                                "window_keys_attended"])
        need = 2 * flops.attention_bytes(SERVED, 400000, 600000, 48)
        assert got == pytest.approx(100 * need / 819e9 / 0.004)
        mfu = serve_mfu.read(
            dict(obs, chips=1), ["tokens", "rows", "expert_pairs",
                                 "global_keys_attended",
                                 "window_keys_attended"])
        assert mfu is None               # a record lacks expert_pairs
    finally:
        step_log.warm_records = orig
