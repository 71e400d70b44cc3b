"""The benchmark's serving configurations fill the chip, by their own
numbers, and every cell's traffic parses. CPU arithmetic on shapes only:
nothing is built and nothing runs. (What ``perfbench/tests/
test_configs.py`` checks, which tier-1 never collects; that file
stays.)"""

import numpy as np
import pytest

from perfbench import loadgen, spec

USABLE_HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler hands out
BENCH = spec.load_benchmark()


def _read(entry):
    return spec.read_json(spec.os.path.join(spec.ROOT, entry["file"]))


SERVING = [c["name"] for c in BENCH["configs"] if "engine" in _read(c)]
CELLS = [w["name"] for w in BENCH["workloads"]]


def configuration(name):
    return _read(next(c for c in BENCH["configs"] if c["name"] == name))


def generation_config(config):
    from paddle_tpu.serving import GenerationConfig

    runner, e = spec.runner(config), config["engine"]
    if hasattr(runner, "generation_config"):
        return runner.generation_config(config, e["max_seq_len"])
    return GenerationConfig(
        vocab_size=config["vocab_size"], d_model=config["d_model"],
        n_heads=config["attention_heads"], n_layers=config["num_layers"],
        d_ff=config["ffn_dim"], max_seq_len=e["max_seq_len"])


def itemsize(dtype):
    return 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize


def held_bytes(config, monkeypatch):
    """(weights, pools) bytes as the TPU's store holds them: the leaves
    in the dtypes ``leaf_shapes`` states there, and every page kind's
    arrays, the null block among them."""
    from paddle_tpu.serving import GenerationModel, model

    monkeypatch.setattr(model, "default_dot_rounds_to_bf16", lambda: True)
    cfg = generation_config(config)
    weights = sum(int(np.prod(shape)) * itemsize(dtype)
                  for shape, dtype in model.leaf_shapes(cfg).values())
    shell = GenerationModel.__new__(GenerationModel)
    shell.config = cfg
    entry, e = shell.cache_entry(), config["engine"]
    token = sum(int(np.prod(shape)) for _n, shape in entry.parts) \
        * itemsize(entry.dtype)
    kinds = shell.page_kinds()
    if kinds and len(kinds) > 1:
        pools = sum(len(k.layers) * token * e["block_size"]
                    * (e[k.name + "_blocks"] + 1) for k in kinds)
        # the one number perfbench/tests/test_configs.py reckons with
        assert cfg.n_layers * e["num_blocks"] == sum(
            len(k.layers) * e[k.name + "_blocks"] for k in kinds)
    else:
        # one kind of page: every layer's, or the layers it names
        # (where they are fewer the file states their blocks beside
        # the number `perfbench/tests/test_configs.py` reckons with)
        paged = len(kinds[0].layers) if kinds else cfg.n_layers
        pools = paged * token * e["block_size"] \
            * (e.get("latent_blocks", e["num_blocks"]) + 1)
    state = shell.row_state()
    if state is not None:          # a row's state beside its pages
        from paddle_tpu.serving.kv_cache import row_state_parts

        pools += e["max_batch"] * sum(
            int(np.prod(shape)) * itemsize(dtype)
            for _n, shape, dtype in row_state_parts(*state))
    return weights, pools


def test_the_benchmark_has_the_serving_configurations():
    assert set(SERVING) >= {"xglm-1.7b-serve", "kanana-2-30b-a3b-serve",
                            "trinity-large-preview-serve", "zaya1-8b-serve",
                            "ling-3.0-flash-serve"}


@pytest.mark.parametrize("name", SERVING)
def test_a_serving_configuration_fills_the_chip(name, monkeypatch):
    config = configuration(name)
    weights, pools = held_bytes(config, monkeypatch)
    share = (weights + pools) / USABLE_HBM_BYTES
    assert 0.75 <= share <= 0.995, (weights, pools, share)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_every_reduced_key_states_its_published_value(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    config = _read(entry)
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    if "engine" in config:
        for key in config["reduced"]:
            assert key in config.get("published", {}), key
            assert config["published"][key] != config[key], key


def test_trinity_states_its_cut_and_its_assumed_equations():
    config = configuration("trinity-large-preview-serve")
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert config["published"]["num_experts"] == 256 \
        == config["router_experts"]
    assert len(config["layer_types"]) == config["num_hidden_layers"] == 5
    assert config["layer_types"].count("full_attention") == 1
    for key in ("rotary_on_window_layers_only", "output_gate",
                "qk_norm_over_head_dim", "mup_embedding_scale",
                "sandwich_norms", "engine"):
        assert key in config["assumed"], key
    assert "eight chips" in config["deployment"]
    assert [c["name"] for c in config["controls"]] == [
        "bf16_router", "int8_expert_weights", "window_ignored"]
    e = config["engine"]
    assert config["controls"][2]["value"]["sliding_window"] \
        > e["max_seq_len"]
    # no width is cut
    for key, want in (("hidden_size", 3072), ("head_dim", 128),
                      ("num_attention_heads", 48),
                      ("num_key_value_heads", 8),
                      ("intermediate_size", 12288),
                      ("moe_intermediate_size", 3072),
                      ("num_experts_per_tok", 4), ("sliding_window", 4096)):
        assert config[key] == want, key


def test_zaya_states_its_cut_and_its_assumed_equations():
    config = configuration("zaya1-8b-serve")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "layers_held": [0, 19]}
    assert config["num_hidden_layers"] == 20
    # copied whole from the source: the held layers are its first 20
    assert config["layer_types"] == ["hybrid"] * 40
    for key in ("sources", "layers_held", "embedding", "cca_latent",
                "conv_taps", "qk_mean", "value_shift",
                "qk_norm_and_temperature", "partial_rotary",
                "residual_scaling", "router", "init", "engine"):
        assert key in config["assumed"], key
    assert "two pipeline stages" in config["deployment"]
    assert any("skip expert" in d for d in config["departures"])
    assert [c["name"] for c in config["controls"]] == [
        "bf16_router", "int8_expert_weights", "carry_ignored"]
    # no width, no expert and no vocabulary row is cut
    for key, want in (("hidden_size", 2048), ("head_dim", 128),
                      ("num_attention_heads", 8),
                      ("num_key_value_heads", 2),
                      ("moe_intermediate_size", 2048),
                      ("num_experts", 16), ("num_experts_per_tok", 1),
                      ("router_hidden_size", 256), ("vocab_size", 262272),
                      ("cca_time0", 2), ("cca_time1", 2),
                      ("partial_rotary_factor", 0.5),
                      ("tie_word_embeddings", True)):
        assert config[key] == want, key
    e = config["engine"]
    assert (e["max_batch"], e["max_seq_len"], e["block_size"]) \
        == (96, 12288, 64)
    assert e["prefill_chunk"] == e["prefill_token_budget"] == 1024
    from perfbench.flops import zaya as flops
    from perfbench.reference import zaya as ref

    # a cached token is 512 values a layer, 20,480 B over the 20 layers
    assert flops.cache_bytes_per_token(config) == 20480
    assert ref.n_params(config) == 4688800104       # 9.38 GB in bf16


def test_ling_states_its_cut_and_its_assumed_equations(monkeypatch):
    config = configuration("ling-3.0-flash-serve")
    assert config["reduced"] == ["num_hidden_layers",
                                 "first_k_dense_replace", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184, "layers_held": [1, 7]}
    assert config["router_experts"] == 512
    for key in ("sources", "layer_kinds", "kda_inputs", "kda_safe_gate",
                "kda_seeds", "kda_beta_and_gate", "group_norm_size", "mla",
                "use_qk_norm", "router", "init", "conv_state_dtype",
                "engine"):
        assert key in config["assumed"], key
    assert "four chips share each layer" in config["deployment"]
    assert any("MTP" in d for d in config["departures"])
    assert [c["name"] for c in config["controls"]] == [
        "bf16_router", "int8_expert_weights", "bf16_scan_state",
        "scan_restarts_each_chunk"]
    # no width is cut
    for key, want in (("hidden_size", 2560), ("head_dim", 128),
                      ("num_attention_heads", 32),
                      ("short_conv_kernel_size", 4),
                      ("kv_lora_rank", 512), ("qk_rope_head_dim", 64),
                      ("qk_nope_head_dim", 128), ("v_head_dim", 128),
                      ("moe_intermediate_size", 768),
                      ("moe_shared_expert_intermediate_size", 768),
                      ("intermediate_size", 6144),
                      ("num_experts_per_tok", 8), ("n_group", 8),
                      ("topk_group", 4), ("layer_group_size", 6),
                      ("kda_lower_bound", -5)):
        assert config[key] == want, key
    # the floors: a whole period and four layers after the dense one,
    # eight experts, an eighth of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (7, 128, 39296)
    from perfbench.flops import ling as flops
    from perfbench.reference import ling as ref

    assert ref.layer_types(config) == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert ref.held(config) == list(range(128))
    assert ref.n_params(config) == 5169367232        # 10.34 GB in bf16
    e = config["engine"]
    assert (e["max_batch"], e["max_seq_len"], e["block_size"],
            e["async_depth"]) == (256, 40960, 64, 2)
    assert e["prefill_chunk"] == e["prefill_token_budget"] == 1024
    assert e["max_queue"] >= 384
    assert config["dtypes"]["state"] == "float32"
    # the two kinds of state: the row state is the larger, and with the
    # weights they are 88-96 % of what the runtime gives
    weights, pools = held_bytes(config, monkeypatch)
    state = e["max_batch"] * flops.row_state_bytes(config)
    pages = pools - state
    assert pages == (e["latent_blocks"] + 1) * 64 * 640 * 2 < state
    # the one number the harness's own test multiplies by every layer
    assert abs(7 * 64 * 1280 * e["num_blocks"]
               - (pages + state)) < 7 * 64 * 1280
    assert 0.88 <= (weights + pools) / USABLE_HBM_BYTES <= 0.96


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_traffic_parses(cell):
    _w, config, mix = spec.cell(BENCH, cell)
    vocab = config["vocab_size"]
    if mix["kind"] == "open_loop":
        requests = loadgen.open_loop(1, mix, float(BENCH["run_seconds"]),
                                     vocab)
        assert len(requests) >= 100
    elif mix["kind"] == "closed_loop":
        requests = loadgen.closed_loop(1, mix, vocab)
        assert len(requests) == loadgen.CLOSED_LOOP_CYCLE
        assert mix["clients"] > config["engine"]["max_batch"]
    else:
        assert mix["kind"] == "token_stream"
        return
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert longest <= config["engine"]["max_seq_len"]
    assert all(0 <= int(t) < vocab for r in requests[:8] for t in r.prompt)


@pytest.mark.parametrize("cell,want", [
    ("trinity-large-preview.long-closed", {
        "prompt_len": {"dist": "lognormal", "median": 8192, "sigma": 0.9,
                       "min": 512, "max": 32768},
        "output_len": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                       "min": 256, "max": 2048},
        "kind": "closed_loop", "clients": 64, "ramp_s": 30.0,
        "drain_s": 240.0, "trace_seconds": 5.0}),
    ("zaya1-8b.reason-closed", {
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                       "min": 128, "max": 8192},
        "output_len": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                       "min": 512, "max": 4096},
        "kind": "closed_loop", "clients": 120, "ramp_s": 45.0,
        "drain_s": 240.0, "trace_seconds": 5.0}),
    ("ling-3.0-flash.reason-long-closed", {
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                       "min": 128, "max": 32768},
        "output_len": {"dist": "lognormal", "median": 4096, "sigma": 0.5,
                       "min": 1024, "max": 8192},
        "kind": "closed_loop", "clients": 320, "ramp_s": 60.0,
        "drain_s": 300.0, "trace_seconds": 5.0})])
def test_the_new_cells_traffic_is_what_the_issue_gave(cell, want):
    w, _c, mix = spec.cell(BENCH, cell)
    assert mix == want and w["chips"] == 1


def test_the_zaya_cell_reports_the_kernels_it_shares():
    """Appended to the readings of the kernels it reuses, not to the
    window ones; its own three metrics are its alone."""
    cell = "zaya1-8b.reason-closed"
    mine = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", cell)}
    assert {"gmm_roofline_pct.decode", "gqa_decode_attn_roofline_pct.decode",
            "gqa_chunk_attn_roofline_pct.batch", "mfu_pct.batch",
            "kernels_device_share_pct.batch", "expert_top_load_pct.decode",
            "carry_rows_pct.batch", "reach_chip_s"} <= mine
    assert not mine & {"window_pages_walked_pct.decode",
                       "global_pool_used_pct.batch",
                       "window_pool_used_pct.batch",
                       "expert_load_max_over_mean.decode"}
    for name in mine:
        spec.layer_metric(name)              # every one has its file
    e2e = {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", cell)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


def test_the_ling_cell_reports_the_kernels_it_shares():
    """Appended to the readings of the kernels it reuses (the expert
    layer's and the latent attention's), not to the grouped-query ones;
    the scan's four metrics are its alone."""
    cell = "ling-3.0-flash.reason-long-closed"
    mine = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", cell)}
    own = {"kda_decode_roofline_pct.decode", "kda_chunk_roofline_pct.batch",
           "kda_device_share_pct.batch",
           "scan_kernels_device_share_pct.batch"}
    assert own | {"gmm_roofline_pct.decode", "mfu_pct.batch",
                  "latent_attn_roofline_pct.decode",
                  "latent_attn_device_share_pct.decode",
                  "latent_runs_per_row.decode",
                  "latent_rows_opened_warm_pct.decode",
                  "experts_touched_pct.decode", "expert_top_load_pct.decode",
                  "kv_pool_used_pct.batch", "dry_dispatch_pct.batch",
                  "steps_queued_ahead.batch", "reach_chip_s"} <= mine
    assert not mine & {"gqa_decode_attn_roofline_pct.decode",
                       "gqa_chunk_attn_roofline_pct.batch",
                       "carry_rows_pct.batch",
                       "kernels_device_share_pct.batch"}
    for name in own:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [cell]
        assert (entry["unit"], entry["moves"]) == ("%",
                                                   "serve_tokens_per_s")
    for name in mine:
        spec.layer_metric(name)              # every one has its file
    e2e = {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", cell)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


LATENT_CELLS = ["kanana-2-30b-a3b.decode-closed",
                "ling-3.0-flash.reason-long-closed"]


@pytest.mark.parametrize("metric,want", [
    ("latent_runs_per_row.decode", (300 + 260) / (128 + 128)),
    ("latent_rows_opened_warm_pct.decode", 100.0 * (127 + 127) / 256)])
def test_the_latent_pipes_two_metrics_read_the_traced_decode_steps(metric,
                                                                   want):
    """The counters of the latent kernel's page pipe (PR 46), in both
    cells whose programs call `latent_paged_attention`, beside its
    roofline: data files on `traced_ratio`, which returns nothing (and
    does not raise) on a parent whose step log has no such fields."""
    from paddle_tpu.observability import metrics
    from perfbench.layer_metrics.readers import traced_ratio

    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == LATENT_CELLS
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels", "serve_tokens_per_s", "program_counter")
    roofline = next(m for m in BENCH["per_layer"]
                    if m["name"] == "latent_attn_roofline_pct.decode")
    assert set(LATENT_CELLS) <= set(roofline["workloads"])
    for cell in LATENT_CELLS:
        assert metric in {m["name"] for m in
                          spec.metrics_of(BENCH, "per_layer", cell)}
    args, reader = spec.layer_metric(metric)
    assert reader is traced_ratio.read and args["kind"] == "decode"
    metrics.disable()
    metrics.reset()
    try:
        log = metrics.registry().samples("serving/step")
        obs = {"traced_span": (10.0, 15.0)}
        assert reader(obs, **args) is None            # no log at all
        step = {"kind": "decode", "cold": False, "rows": 128}
        log.add(dict(step, t_dispatched=11.0))        # the parent's record
        assert reader(obs, **args) is None
        pipe = dict(decode_rows_walked=128, decode_rows_opened_warm=127)
        log.add(dict(step, t_dispatched=12.0, decode_runs_walked=300, **pipe))
        log.add(dict(step, t_dispatched=14.5, decode_runs_walked=260, **pipe))
        # outside the traced stretch, and a mixed step: not read
        log.add(dict(step, t_dispatched=20.0, decode_runs_walked=999, **pipe))
        log.add(dict(step, kind="mixed", t_dispatched=13.0,
                     decode_runs_walked=999, **pipe))
        assert reader(obs, **args) == pytest.approx(want)
        assert reader({}, **args) is None             # a run not traced
    finally:
        metrics.disable()
        metrics.reset()
