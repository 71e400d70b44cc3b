"""Runner ``serve_latent``: a latent-attention / routed-expert
configuration (family ``kanana``) served through the normal path.

The same engine, load generators and sampling as runner ``serve``
(imported from it: ``Record``, ``OpenLoop``, ``ClosedLoop``, ``submit``,
``sample_stats``, ``build_engine``); what differs is how the model is
made, and over which tokens the output is judged. The reference's
``init_layer`` and ``init_top`` run on the device a layer at a time
(bf16 leaves, 10 GB in all: no pass through the host) and are handed to
``GenerationModel`` in the serving layout, the same arrays except for
``W_kvb``, which the block wants split per head into ``w_uk`` and
``w_uv``.

``correct`` is runner ``serve``'s comparison (a sample of the requests
the window finished goes through ``perfbench/reference/kanana.py`` once
the engine and its weights are freed; compared: the gap by which a
served token's reference logit lies below the reference's best), its
mean taken twice: over every served token, and over the DECIDED tokens,
those whose router choice, in the reference, stands clear of the first
expert left out by more than ``correct.router_margin`` in every expert
layer. With 128 near-tied seeded experts a served path that rounds its
matmul operands to bfloat16 takes another sixth expert than the float32
reference on about a third of its tokens (a tie falling the other way,
not an error): the first mean measures those ties and what moves them
(a router in lower precision), the second the arithmetic (weights on a
coarser grid). ``output_checks`` below; PERF.md section 2 has the
readings behind each limit.

A traced run also keeps, for the per-layer readers, the device seconds
of the block's two kernels (``kernel_share.collect``, read while the
profile is still on disk) and the host stamps of the traced stretch
(the step log's records carry host stamps only).
"""

import gc
import time

import numpy as np

from perfbench import loadgen, spec
from perfbench.layer_metrics.readers import kernel_share
from perfbench.runners import check, counter_value, memory_peak_bytes
from perfbench.runners.serve import (ClosedLoop, OpenLoop, Record,
                                     build_engine, engine_steps,
                                     sample_stats, submit)

KERNELS = ("gmm", "latent_paged_attention")
DECODE_PROGRAM = "jit_decode_step"
# the reference's leaf names that the serving layout spells otherwise
RENAMED = {"e_gate": "we_gate", "e_up": "we_up", "e_down": "we_down",
           "s_gate": "ws_gate", "s_up": "ws_up", "s_down": "ws_down"}


def generation_config(config, max_seq_len):
    from paddle_tpu.serving import GenerationConfig
    from paddle_tpu.serving.latent_moe import LatentMoEBlock

    types = config.get("dtypes", {})
    block = LatentMoEBlock(
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        first_k_dense=config["first_k_dense_replace"],
        n_routed_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        moe_d_ff=config["moe_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        weight_dtype=types.get("weights", "bfloat16"),
        activation_dtype=types.get("activations", "bfloat16"),
        router_dtype=types.get("router", "float32"),
        cache_dtype=types.get("cache", "bfloat16"))
    return GenerationConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        block=block)


def serving_layer(leaves, config):
    """One layer of the reference's leaves in the serving layout."""
    H = config["num_attention_heads"]
    dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    kvb = leaves["wkv_b"]
    kvb = kvb.reshape(kvb.shape[0], H, dn + dv)            # [r, H, dn+dv]
    out = {RENAMED.get(k, k): v for k, v in leaves.items() if k != "wkv_b"}
    out["w_uk"] = kvb[:, :, :dn].transpose(1, 2, 0)        # [H, dn, r]
    out["w_uv"] = kvb[:, :, dn:].transpose(1, 0, 2)        # [H, r, dv]
    return out


def seeded_weights(ref, config, seed):
    """The reference's weights in the serving layout, made on the
    default device one jitted call a layer, the seed an argument."""
    import jax

    words = ref.seed_words(seed)
    top = jax.jit(lambda w: ref.init_top(w, config))(words)
    weights = {"embedding": top["embed"], "lm_head": top["head"],
               "final_norm": top["norm_f"]}
    layer = jax.jit(
        lambda w, i: serving_layer(ref.init_layer(w, config, i), config),
        static_argnums=1)
    for i in range(config["num_hidden_layers"]):
        for k, v in layer(words, i).items():
            weights["l%d/%s" % (i, k)] = v
    return weights


def served_token_gaps(ref, config, seed, sample, t_max, r_max):
    """Runner ``serve``'s ``served_token_gaps`` with, beside each served
    token's gap, the least router margin the reference met at the
    position that produced it: ``[(gaps, margins)]`` a sampled request."""
    import jax
    import jax.numpy as jnp

    params = ref.make_params(seed, config)

    @jax.jit
    def gaps(params, tokens, rows, served):
        z, margin = ref.logits_and_margin_at(params, tokens, rows, config)
        picked = jnp.take_along_axis(z, served[:, None], axis=1)[:, 0]
        return jnp.max(z, axis=-1) - picked, margin

    out = []
    for rec in sample:
        prompt = np.asarray(rec.spec.prompt, np.int32)
        served = np.asarray(rec.request.tokens, np.int32)
        n, m = len(prompt), len(served)
        tokens = np.zeros(t_max, np.int32)
        tokens[:n], tokens[n:n + m - 1] = prompt, served[:-1]
        rows = np.zeros(r_max, np.int32)
        rows[:m] = n - 1 + np.arange(m)
        tok = np.zeros(r_max, np.int32)
        tok[:m] = served
        g, least = gaps(params, tokens, rows, tok)
        out.append((np.asarray(g)[:m], np.asarray(least)[:m]))
    del params
    return out


def output_checks(ref, config, mix, seed, finished, window, note):
    """Runner ``serve``'s ``output_checks`` (the same sample: drawn from
    the seed, the longest request in it, one padded length) with the
    gap's mean judged twice: over every served token (what a fault that
    moves the router's choices shows in) and over the decided tokens
    (what a fault in the arithmetic shows in, the ties taken out).
    ``undecided_token_share`` keeps the second from resting on a
    handful of tokens. ``by_margin`` prints the same numbers at other
    margins, so that a reading can be placed without another run."""
    c = config["correct"]
    by_len = sorted(finished, key=lambda r: len(r.spec.prompt)
                    + r.spec.max_new_tokens)
    sample, rest = by_len[-1:], by_len[:-1]
    pick = loadgen.rng_for(seed, 5).permutation(len(rest))
    sample += [rest[i] for i in pick[:c["sample_requests"] - 1]]
    t0 = time.perf_counter()
    t_max = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
              // 128) * 128
    pairs = served_token_gaps(ref, config, seed, sample, t_max,
                              mix["output_len"]["max"]) if sample else []
    gap = np.concatenate([g for g, _m in pairs]) if pairs \
        else np.array([np.inf])
    margin = np.concatenate([m for _g, m in pairs]) if pairs \
        else np.array([np.inf])

    def over(least):
        g = gap[margin > least] if least > 0 else gap
        return {"margin": least, "tokens": int(g.size),
                "gap_mean": float(g.mean()) if g.size else float("inf"),
                "gap_max": float(g.max()) if g.size else float("inf"),
                "first_choice_share": float(np.mean(g <= 0.0))
                if g.size else None}

    served, decided = over(0.0), over(c["router_margin"])
    note(phase="reference_done", seconds=time.perf_counter() - t0,
         sampled_requests=len(sample), served_tokens=served["tokens"],
         first_choice_share=served["first_choice_share"],
         gap_mean=served["gap_mean"], gap_max=served["gap_max"],
         decided=decided,
         by_margin=[over(m) for m in c.get("margins_printed", [])])
    checks = [check("served_logit_gap_mean", served["gap_mean"],
                    c["served_logit_gap_mean"]),
              check("decided_logit_gap_mean", decided["gap_mean"],
                    c["decided_logit_gap_mean"]),
              check("undecided_token_share",
                    1.0 - decided["tokens"] / served["tokens"],
                    c["undecided_token_share"]),
              check("failed_requests", window["failed"], 0),
              check("window_compilations", window["compilations"], 0),
              check("window_step_traces", window["traces"], 0)]
    if window["kernel_fallbacks"] is not None:
        checks.append(check("kernel_fallbacks",
                            window["kernel_fallbacks"], 0))
    return checks


def run(ctx, tamper=None):
    """``tamper(model)`` is for the harness's own tests and the
    lower-precision controls: it returns the model the engine serves."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import GenerationModel

    config, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    note, tracer, seconds = ctx["note"], ctx["tracer"], ctx["seconds"]
    ref = spec.family(config, "reference")
    vocab, e = config["vocab_size"], config["engine"]
    if tracer:
        metrics.enable()   # kernel dispatch counters and the step log

    model = GenerationModel(generation_config(config, e["max_seq_len"]),
                            seeded_weights(ref, config, seed))
    if tamper is not None:
        model = tamper(model)
    note(phase="model_ready", seconds=time.perf_counter() - ctx["t_start"],
         parameters=ref.n_params(config))
    engine = build_engine(config, model)
    try:
        warm = [Record(s, 0.0) for s in loadgen.warmup_requests(mix, vocab)]
        for rec in warm:
            submit(engine, rec)
        for rec in warm:
            rec.request.wait(1200)
        note(phase="warm", seconds=time.perf_counter() - ctx["t_start"],
             compile_seconds_total=ctx["compiles"].seconds)

        closed = mix["kind"] == "closed_loop"
        if closed:
            gen = ClosedLoop(engine, loadgen.closed_loop(seed, mix, vocab),
                             mix["clients"])
            gen.start()
            time.sleep(mix["ramp_s"])   # to a full, mixed batch
            t0 = time.perf_counter()
        elif mix["kind"] == "open_loop":
            t0 = time.perf_counter() + 0.05
            gen = OpenLoop(engine, [
                Record(s, t0 + s.due_s)
                for s in loadgen.open_loop(seed, mix, seconds, vocab)])
            time.sleep(max(0.0, t0 - time.perf_counter()))
            gen.start()
        else:
            raise spec.SpecError("runner serve_latent needs open_loop or "
                                 "closed_loop traffic")
        note(phase="window_open", setup_s=t0 - ctx["t_start"])
        compiles0, traces0 = ctx["compiles"].count, model.trace_count
        steps0 = engine_steps(engine)
        fallbacks0 = counter_value("kernels/fallbacks") if tracer else None
        occupancy, pool_used = [], []
        t_end = t0 + seconds
        if tracer:
            sample_stats(engine, t_end - mix["trace_seconds"], occupancy,
                         pool_used)
            tracer.start()
        sample_stats(engine, t_end, occupancy, pool_used)
        steps = engine_steps(engine) - steps0
        if tracer:
            tracer.stop()
        memory_peak = memory_peak_bytes(ctx["devices"][:1])

        # the drain is outside the window
        unfinished = sum(1 for r in gen.records if r.request is not None
                         and not r.request.finished)
        if closed:
            gen.stopping.set()
        gen.join(mix["drain_s"])
        records = [r for r in gen.records
                   if r.submitted is not None and r.submitted < t_end]
        deadline = time.perf_counter() + mix["drain_s"]
        for rec in records:
            if rec.request is not None:
                try:
                    rec.request.wait(max(0.0, deadline
                                         - time.perf_counter()))
                except Exception as err:   # counted below as failed
                    note(phase="request_failed", error=repr(err))
        window = {
            "compilations": ctx["compiles"].count - compiles0,
            "traces": model.trace_count - traces0,
            "kernel_fallbacks": (counter_value("kernels/fallbacks")
                                 - fallbacks0) if tracer else None}
        pool_stats = next(iter(engine.stats().values()))
        drain_s = time.perf_counter() - t_end
    finally:
        engine.close()

    ok = [r for r in records if r.finished_ok]
    failed = window["failed"] = len(records) - len(ok)
    late = [(r.submitted - r.due) * 1e3 for r in records] if not closed \
        else [0.0]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in ok]
    queue_wait = [(r.request.start_time - r.due) * 1e3 for r in ok]
    itl = [d * 1e3 for r in ok for d in np.diff(r.stamps)]
    in_window = sum(1 for r in records for s in r.stamps if t0 <= s < t_end)
    note(phase="window_closed", requests=len(records), finished=len(ok),
         failed=failed, engine_steps=steps, tokens_in_window=in_window,
         unfinished_at_close=unfinished, drain_s=drain_s,
         generator_late_ms_max=max(late),
         ttft_ms_p50=loadgen.percentile(ttft, 50),
         itl_ms_p50=loadgen.percentile(itl, 50),
         occupancy_mean=float(np.mean(occupancy)) if occupancy else None,
         blocks_total=pool_stats["blocks_total"])

    del engine, model, gen
    gc.collect()
    kernel_trace = traced_span = reduced = None
    if tracer:
        kernel_trace = kernel_share.collect(tracer.directory, KERNELS,
                                            DECODE_PROGRAM)
        traced_span = (tracer.t0, tracer.t1)
        reduced = tracer.reduce()
        note(phase="kernel_trace", **(kernel_trace or {}))
    checks = output_checks(ref, config, mix, seed, ok, window, note)
    p = loadgen.percentile
    return {
        "end_to_end": {
            "ttft_p50_ms": p(ttft, 50), "itl_p95_ms": p(itl, 95),
            "serve_tokens_per_s": in_window / seconds,
            "setup_s": t0 - ctx["t_start"]},
        "observations": {
            "ttft_ms": ttft, "itl_ms": itl, "queue_wait_ms": queue_wait,
            "occupancy": occupancy, "pool_used_pct": pool_used,
            "window_s": seconds, "engine_steps": steps,
            "trace": reduced, "kernel_trace": kernel_trace,
            "traced_span": traced_span},
        "attempted": len(records), "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak,
    }
