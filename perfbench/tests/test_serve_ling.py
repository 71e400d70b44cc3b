"""CPU tests of runner ``serve_ling``, family ``ling``'s FLOPs and bytes
and the per-layer metrics PR 44 added (toy widths, no chip;
``perfbench/tests/root_ling`` is a benchmark of added files that leans
on the committed per-layer metric files)."""

import json
import os

import pytest

from perfbench import control_block, loadgen, run, spec
from perfbench.flops import ling as flops
from perfbench.layer_metrics.readers import step_log

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "root_ling")
CELL = "tiny-ling.closed"
SERVED = spec.read_json(os.path.join(
    spec.ROOT, "perfbench", "configs", "ling-3.0-flash-serve.json"))


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
         "expert_top_load_pct.decode", "dry_dispatch_pct.batch",
         "steps_queued_ahead.batch"})])
def test_rehearsal_ends_in_a_well_formed_correct_result(trace, expect):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0
    # off the chip there is no device trace: the device_trace metrics'
    # readers return nothing and the line leaves them out
    assert set(line["metrics"]) == expect
    if trace:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # three expert layers hold 8 of the router's 16 experts
        assert 12.5 <= m["expert_top_load_pct.decode"] <= 100
        assert m["mfu_pct.batch"] > 0


@pytest.mark.parametrize("stalled_s,failed", [(5.0, False), (-1.0, True)])
def test_requests_the_drain_cuts_are_not_failed_unless_they_stalled(
        stalled_s, failed, monkeypatch, capsys):
    """The drain ends once ``correct`` has its sample and the runner
    cuts what still runs (here at once: four rows busy, two requests
    queued). A cut request is no failure, but one whose last token is
    older than ``STALLED_S`` at the cut is."""
    from perfbench.runners import serve_ling

    import gc

    import jax

    live, checks = [], serve_ling.output_checks

    def reference(*a):
        gc.collect()
        live.append(sum(x.nbytes for x in jax.live_arrays()))
        return checks(*a)

    monkeypatch.setattr(serve_ling, "output_checks", reference)
    monkeypatch.setattr(serve_ling, "sample_ready", lambda *a: True)
    monkeypatch.setattr(serve_ling, "STALLED_S", stalled_s)
    line = run.run_cell(CELL, 2147483661, 0.3, 0, require_chip=False,
                        root=ROOT)
    closed = next(json.loads(n) for n in capsys.readouterr().out.splitlines()
                  if '"window_closed"' in n)
    assert closed["unfinished_at_close"] >= 4
    assert closed["finished"] + closed["cut_at_close"] + line["failed"] \
        == line["attempted"]
    assert (line["failed"] > 0) is failed
    assert line["checks"]["failed_requests"]["ok"] is not failed
    if not failed:
        assert closed["cut_at_close"] >= 4
    # the reference gets the device: the error the worker raised does
    # not keep the engine's weights and row state (6.7 MB of state here)
    assert live[0] < closed["row_state_bytes"] / 2


class _Request:
    def __init__(self, slot, start, done, tokens):
        self.slot, self.start_time, self.finished = slot, start, done
        self.error, self.tokens = None, [0] * tokens


class _Record:
    def __init__(self, prompt, output, slot, start, done=True):
        self.spec = loadgen.Request(0, 0.0, [0] * prompt, output)
        self.request = _Request(slot, start, done, output if done else 1)
        self.submitted, self.stamps = start, [start + 1.0]
    finished_ok = property(lambda self: self.request.finished)


def test_the_drain_ends_when_correct_can_draw_one_request_of_each_class():
    from perfbench.runners import serve_ling

    config = {"correct": {"sample_requests": 8, "state_rows": 2,
                          "long_prompt": 8192, "long_output": 4096,
                          "short_total": 4096, "longest_sampled": 24576}}
    ready = lambda recs: serve_ling.sample_ready(config, 7, 100.0, recs)
    # rows 0-9 hold finished requests nobody displaced: short, and rest
    recs = [_Record(500, 1500, i, float(i)) for i in range(5)] \
        + [_Record(3000, 3000, i, float(i)) for i in range(5, 10)]
    running = _Record(1000, 8000, 10, 10.0, done=False)
    assert not ready(recs + [running])          # no long prompt or output
    recs += [_Record(9000, 2000, 11, 11.0), _Record(30000, 2000, 12, 12.0)]
    assert not ready(recs + [running])          # still no long output
    recs.append(_Record(1000, 5000, 13, 13.0))
    assert ready(recs + [running])
    # a request that waits for a row may yet displace a finished one
    queued = _Record(1000, 2000, None, 14.0, done=False)
    assert not ready(recs + [running, queued])
    held = serve_ling.state_row_records(config, 7, recs + [running])
    sample = serve_ling.pick_sample(
        config, 7, 8, [], [r for r in recs if r not in held])
    assert len(sample) == len(set(sample)) == 8
    assert not serve_ling.classes_missing(config, sample)
    # the 30,000-token prompt is beyond what the reference is given
    assert all(len(r.spec.prompt) < 30000 for r in sample + held)
    # nothing left to wait for: the drain ends whatever was drawn
    assert ready(recs[:3])


@pytest.mark.parametrize("name", [
    "int8_expert_weights", "bf16_router", "bf16_scan_state",
    "scan_restarts_each_chunk"])
def test_a_control_is_not_correct(name, capsys):
    rc = control_block.main(["--workload", CELL, "--seed", "2147483659",
                             "--seconds", "3", "--control", name],
                            require_chip=False, root=ROOT)
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is False, out[-8:]
    assert line["control"]["name"] == name


def test_flops_and_bytes_of_the_served_configuration():
    d = flops.dims(SERVED)
    assert (d["kda"], d["mla"], d["dense"]) == (6, 1, 1)
    # one MLA layer: 512 + 64 bf16 values a token
    assert flops.cache_bytes_per_token(SERVED) == 1152
    # six KDA layers: 32 x 128 x 128 float32 and 3 x 12,288 bf16 a layer
    assert flops.row_state_bytes(SERVED) == 6 * (2097152 + 73728)
    # a decode step of 256 rows reads and writes 6.4 GB of scan state
    assert 6.4e9 < flops.kda_state_bytes(SERVED, 256) < 6.6e9
    # an expert is 5.90 M parameters: three 2560 x 768 matrices
    assert flops.gmm_bytes(SERVED, 1, 0) == 3 * 2560 * 768 * 2
    # one decode step streams every held expert of six layers: 9.06 GB
    step = flops.gmm_bytes(SERVED, 128 * 6, 0)
    assert 9.0e9 < step < 9.1e9
    assert flops.gmm_flops(SERVED, 2) == 2 * 6 * 2560 * 768
    assert flops.kda_chunk_flops(SERVED, 1) == 6 * 32 * 7 * 128 * 128
    assert flops.latent_attention_bytes(SERVED, 10, 0) == 10 * 576 * 2
    assert flops.latent_attention_flops(SERVED, 1) \
        == 32 * 2 * (512 + 64 + 512)
    # the head over this chip's quarter of the vocabulary, once a row
    assert flops.step_flops(SERVED, 0, 1, 0, 0, 0) == 2 * 2560 * 39296
    # a token outside experts, scan and head: 438 M parameters
    assert 4.3e8 < flops.matmul_params_per_token(SERVED) < 4.5e8


def test_the_scan_rooflines_read_the_steps_own_counters(monkeypatch):
    recs = [{"kind": "decode", "cold": False, "t_dispatched": 1.0,
             "rows": 256, "scan_tokens": 0, "scan_fresh_rows": 0},
            {"kind": "mixed", "cold": False, "t_dispatched": 2.0,
             "rows": 256, "scan_tokens": 1024, "scan_fresh_rows": 1,
             "prefill_tokens": 1024, "decode_tokens": 255}]
    monkeypatch.setattr(step_log, "warm_records",
                        lambda series, kind=None: [
                            r for r in recs if kind in (None, r["kind"])])
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    obs = {"traced_span": (0.0, 10.0), "config": SERVED, "peaks": peaks,
           "kernel_trace": {"modules": 2, "kernels": {
               "kda_decode": {"all_s": 0.02, "in_module_s": 0.02}}},
           "kernel_trace_chunk": {"modules": 1, "kernels": {
               "kda_chunk": {"all_s": 0.004, "in_module_s": 0.004}}},
           "trace": {"busy_s": 0.1}}
    args, read = spec.layer_metric("kda_decode_roofline_pct.decode", ROOT)
    want = 100 * flops.kda_state_bytes(SERVED, 512) / 0.02 / 819e9
    assert read(obs, **args) == pytest.approx(want) and 70 < want < 90
    args, read = spec.layer_metric("kda_chunk_roofline_pct.batch", ROOT)
    need = max(flops.kda_chunk_bytes(SERVED, 1024, 1) / 819e9,
               flops.kda_chunk_flops(SERVED, 1024) / 197e12)
    assert read(obs, **args) == pytest.approx(100 * need / 0.004)
    args, read = spec.layer_metric("kda_device_share_pct.batch", ROOT)
    assert read(dict(obs, kernel_trace={"kernels": {
        "kda_decode": {"all_s": 0.02}, "kda_chunk": {"all_s": 0.004}}}),
        **args) == pytest.approx(24.0)
    # a program without the kernels or the counters (the parent):
    # nothing, and no raise
    for name in ("kda_decode_roofline_pct.decode",
                 "kda_chunk_roofline_pct.batch",
                 "kda_device_share_pct.batch",
                 "scan_kernels_device_share_pct.batch"):
        args, read = spec.layer_metric(name, ROOT)
        assert read({"traced_span": (0.0, 10.0), "config": SERVED,
                     "peaks": peaks, "kernel_trace": {"kernels": {}},
                     "kernel_trace_chunk": {"kernels": {}},
                     "trace": {"busy_s": 0.1}}, **args) is None
