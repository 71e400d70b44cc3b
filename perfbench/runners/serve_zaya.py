"""Runner ``serve_zaya``: a compressed-convolutional-attention / top-1
routed configuration (family ``zaya``) served through the normal path.

The same engine, load generators, records and sampling as runner
``serve`` and the same shape of run, kernels and ``correct`` as runner
``serve_window`` (imported from them: ``Record``, ``OpenLoop``,
``ClosedLoop``, ``submit``, ``engine_steps``, ``sample_stats``;
``KERNELS``, the two program names, ``RENAMED``, ``bucket``). What
differs, and why ``run`` and ``output_checks`` are copies of
``serve_window``'s and not calls of them (they reach their model, engine
and sample through their own module's names; PERF.md section 7 asks a
``benchmark`` issue to give them one seam):

* the model is the fourth serving block (``ZayaBlock``), its weights the
  reference's ``init_layer`` / ``init_top`` handed over leaf by leaf,
  the experts' leaves renamed; the head is the embedding;
* the pool keeps pages of one kind, and the engine a row state beside
  them;
* the traced stretch's step records are kept past the drain
  (``keep_in_step_log``): the drain alone outruns the log's ring;
* the sample of ``correct`` is as many finished requests of each class
  of length (prompt plus output: under 2,048, to 4,096, to 8,192, beyond,
  in the cell), each padded to its own bucket, and the reference's head
  runs in blocks
  of rows (``served_gaps_at``: at 262,272 columns the logits of a
  request's 4,096 served tokens are 4.3 GB).
"""

import gc
import time

import numpy as np

from perfbench import loadgen, spec
from perfbench.layer_metrics.readers import kernel_share
from perfbench.runners import check, counter_value, memory_peak_bytes
from perfbench.runners.serve import (ClosedLoop, OpenLoop, Record,
                                     engine_steps, sample_stats, submit)
from perfbench.runners.serve_window import (CHUNK_PROGRAM, DECODE_PROGRAM,
                                            KERNELS, RENAMED, bucket)

# the sample holds one request of each class of length (prompt plus
# output): up to these shares of the mix's longest, and beyond
CLASS_SHARES = (1 / 6, 1 / 3, 2 / 3)


def generation_config(config, max_seq_len):
    from paddle_tpu.serving import GenerationConfig
    from paddle_tpu.serving.zaya import ZayaBlock

    types = config.get("dtypes", {})
    block = ZayaBlock(
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        conv_taps=(config["cca_time0"], config["cca_time1"]),
        partial_rotary=config["partial_rotary_factor"],
        rope_theta=config["rope_parameters"]["hybrid"]["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        router_hidden=config["router_hidden_size"],
        n_routed_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        weight_dtype=types.get("weights", "bfloat16"),
        activation_dtype=types.get("activations", "bfloat16"),
        router_dtype=types.get("router", "float32"),
        cache_dtype=types.get("cache", "bfloat16"))
    return GenerationConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"], max_seq_len=max_seq_len,
        block=block)


def seeded_weights(ref, config, seed):
    """The reference's weights in the serving layout, made on the
    default device one jitted call a layer (one program: the layer is
    an argument, as the seed is)."""
    import jax

    words = ref.seed_words(seed)
    top = jax.jit(lambda w: ref.init_top(w, config))(words)
    weights = {"embedding": top["embed"], "final_norm": top["norm_f"]}
    layer = jax.jit(lambda w, i: ref.init_layer(w, config, i))
    for i in range(config["num_hidden_layers"]):
        for k, v in layer(words, np.int32(i)).items():
            weights["l%d/%s" % (i, RENAMED.get(k, k))] = v
    return weights


def step_records(t0, t1):
    """The step log's records dispatched between two host stamps."""
    from paddle_tpu.observability import metrics

    return [r for r in metrics.registry().samples("serving/step").records()
            if t0 <= r.get("t_dispatched", -1.0) <= t1]


def keep_in_step_log(records):
    """Put ``records`` back where the ring has dropped them. The step
    log keeps its newest 4,096 records; this cell drains a batch of
    streams a few thousand tokens long, more steps than that, so by the
    time the readers run the traced stretch's records would be gone."""
    from paddle_tpu.observability import metrics

    log = metrics.registry().samples("serving/step")
    held = {id(r) for r in log.records()}
    for r in records:
        if id(r) not in held:
            log.add(r)


def build_engine(config, model):
    from paddle_tpu.serving import ServingEngine

    e = config["engine"]
    return ServingEngine(
        model, max_batch=e["max_batch"], max_seq_len=e["max_seq_len"],
        block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_queue=e["max_queue"], prefill_chunk=e["prefill_chunk"],
        prefill_token_budget=e["prefill_token_budget"],
        async_depth=e["async_depth"], prefix_cache=False, spec_k=0,
        spec_tree="")


def pick_sample(mix, seed, n, in_window, finished):
    """``n`` of the requests the window finished (``in_window``; where
    it finished too few of a class, of those the drain finished too), as
    many of each class of length (:data:`CLASS_SHARES` of the mix's
    longest prompt plus output: under 2,048, to 4,096, to 8,192 and
    beyond in the cell), drawn from the seed within the class."""
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    edges = [0] + [s * longest for s in CLASS_SHARES] + [float("inf")]
    rng = loadgen.rng_for(seed, 5)
    sample = []
    for lo, hi in zip(edges, edges[1:]):
        def of(records):
            mine = [r for r in records if r not in sample and lo <= len(
                r.spec.prompt) + r.spec.max_new_tokens < hi]
            return [mine[i] for i in rng.permutation(len(mine))]
        sample += (of(in_window) + of(finished))[:n // (len(edges) - 1)]
    return sample


def served_token_gaps(ref, config, seed, sample, t_max, r_max):
    """``[(gaps, margins, errors)]`` a sampled request: each served
    token's gap below the reference's best logit, the least router
    margin the reference met at the position that produced it, and the
    served logit of the token less the reference's logit of it."""
    import jax

    params = ref.make_params(seed, config)
    gaps = jax.jit(lambda params, tokens, rows, served: ref.served_gaps_at(
        params, tokens, rows, served, config))
    out = []
    for rec in sample:
        prompt = np.asarray(rec.spec.prompt, np.int32)
        served = np.asarray(rec.request.tokens, np.int32)
        n, m = len(prompt), len(served)
        tokens = np.zeros(bucket(n + m, t_max), np.int32)
        tokens[:n], tokens[n:n + m - 1] = prompt, served[:-1]
        rows = np.zeros(r_max, np.int32)
        rows[:m] = n - 1 + np.arange(m)
        tok = np.zeros(r_max, np.int32)
        tok[:m] = served
        g, least, picked = gaps(params, tokens, rows, tok)
        top = np.asarray(rec.request.top_logits, np.float32)
        out.append((np.asarray(g)[:m], np.asarray(least)[:m],
                    top - np.asarray(picked)[:m]))
    del params
    return out


def output_checks(ref, config, mix, seed, in_window, finished, window,
                  note):
    """Runner ``serve_window``'s three numbers over this runner's
    sample: the mean logit gap of every served token, of the decided
    ones (those whose router choice, top-1 here, stands clear of the
    runner-up by more than ``correct.router_margin`` in every layer of
    the reference), and the root mean square distance of the decided
    tokens' served logits from the reference's."""
    c = config["correct"]
    sample = pick_sample(mix, seed, c["sample_requests"], in_window,
                         finished)
    t0 = time.perf_counter()
    t_max = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
              // 2048) * 2048
    pairs = served_token_gaps(ref, config, seed, sample, t_max,
                              mix["output_len"]["max"]) if sample else []
    gap, margin, err = (np.concatenate([p[i] for p in pairs]) if pairs
                        else np.array([np.inf]) for i in range(3))

    def over(least):
        keep = margin > least if least > 0 else np.ones(gap.shape, bool)
        g, e = gap[keep], np.abs(err[keep])
        return {"margin": least, "tokens": int(g.size),
                "gap_mean": float(g.mean()) if g.size else float("inf"),
                "gap_max": float(g.max()) if g.size else float("inf"),
                "first_choice_share": float(np.mean(g <= 0.0))
                if g.size else None,
                "err_median": float(np.median(e)) if e.size
                else float("inf"),
                "err_rms": float(np.sqrt(np.mean(e * e))) if e.size
                else float("inf")}

    served, decided = over(0.0), over(c["router_margin"])
    note(phase="reference_done", seconds=time.perf_counter() - t0,
         sampled_requests=len(sample),
         sampled_lengths=[len(r.spec.prompt) + r.spec.max_new_tokens
                          for r in sample],
         served_tokens=served["tokens"],
         first_choice_share=served["first_choice_share"],
         gap_mean=served["gap_mean"], gap_max=served["gap_max"],
         err_median=served["err_median"], err_rms=served["err_rms"],
         decided=decided,
         by_margin=[over(m) for m in c.get("margins_printed", [])])
    checks = [check("served_logit_gap_mean", served["gap_mean"],
                    c["served_logit_gap_mean"]),
              check("decided_logit_gap_mean", decided["gap_mean"],
                    c["decided_logit_gap_mean"]),
              check("decided_logit_err_rms", decided["err_rms"],
                    c["decided_logit_err_rms"]),
              check("undecided_token_share",
                    1.0 - decided["tokens"] / served["tokens"],
                    c["undecided_token_share"]),
              check("sampled_requests_missing",
                    c["sample_requests"] - len(sample), 0),
              check("failed_requests", window["failed"], 0),
              check("window_compilations", window["compilations"], 0),
              check("window_step_traces", window["traces"], 0)]
    if window["kernel_fallbacks"] is not None:
        checks.append(check("kernel_fallbacks",
                            window["kernel_fallbacks"], 0))
    return checks


def run(ctx, tamper=None):
    """``tamper(model)`` is for the harness's own tests and the
    controls: it returns the model the engine serves."""
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
            raise spec.SpecError("runner serve_zaya needs open_loop or "
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
        traced = []
        if tracer:
            tracer.stop()
            traced = step_records(tracer.t0, tracer.t1)
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
    keep_in_step_log(traced)

    ok = [r for r in records if r.finished_ok]
    failed = window["failed"] = len(records) - len(ok)
    late = [(r.submitted - r.due) * 1e3 for r in records] if not closed \
        else [0.0]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in ok]
    queue_wait = [(r.request.start_time - r.due) * 1e3 for r in ok]
    itl = [d * 1e3 for r in ok for d in np.diff(r.stamps)]
    in_window = sum(1 for r in records for s in r.stamps if t0 <= s < t_end)
    # requests whose last token fell inside the window
    done_in_window = [r for r in ok if r.stamps and r.stamps[-1] < t_end]
    note(phase="window_closed", requests=len(records), finished=len(ok),
         finished_in_window=len(done_in_window),
         failed=failed, engine_steps=steps, tokens_in_window=in_window,
         unfinished_at_close=unfinished, drain_s=drain_s,
         generator_late_ms_max=max(late),
         ttft_ms_p50=loadgen.percentile(ttft, 50),
         itl_ms_p50=loadgen.percentile(itl, 50),
         slow_gap_share=loadgen.slow_gap_share(itl),
         occupancy_mean=float(np.mean(occupancy)) if occupancy else None,
         blocks_total=pool_stats["blocks_total"])

    del engine, model, gen
    gc.collect()
    kernel_trace = chunk_trace = traced_span = reduced = None
    if tracer:
        kernel_trace = kernel_share.collect(tracer.directory, KERNELS,
                                            DECODE_PROGRAM)
        chunk_trace = kernel_share.collect(tracer.directory, KERNELS,
                                           CHUNK_PROGRAM)
        traced_span = (tracer.t0, tracer.t1)
        reduced = tracer.reduce()
        note(phase="kernel_trace", decode=kernel_trace, chunk=chunk_trace)
    checks = output_checks(ref, config, mix, seed, done_in_window, ok,
                           window, note)
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
            "kernel_trace_chunk": chunk_trace,
            "traced_span": traced_span},
        "attempted": len(records), "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak,
    }
