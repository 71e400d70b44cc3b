"""Runner ``serve_ling``: a delta-rule linear-attention / latent-attention
/ routed-expert configuration (family ``ling``) served through the normal
path.

The same engine, load generators, records and sampling as runner
``serve`` and the same shape of run and ``correct`` as runners
``serve_window`` and ``serve_zaya`` (imported from them: ``Record``,
``OpenLoop``, ``ClosedLoop``, ``submit``, ``engine_steps``,
``sample_stats``; the two program names, ``RENAMED``; ``step_records``,
``keep_in_step_log``). What differs, and why
``run`` and ``output_checks`` are copies of theirs and not calls of
them (they reach their model, engine and sample through their own
module's names; PERF.md section 7 asks a ``benchmark`` issue to give
them one seam):

* the model is the fifth serving block (``LingBlock``), its weights the
  reference's ``init_layer`` / ``init_top`` handed over leaf by leaf (a
  layer's kind decides its leaves), the experts' leaves renamed;
* the pool keeps the latent pages of the MLA layers alone
  (``engine.latent_blocks``; ``engine.num_blocks`` is the one number
  whose product with layers x bytes x block is the pages and the row
  state together, which is what ``perfbench/tests/test_configs.py``
  reckons with), and the engine a row state of two parts beside them
  (the scan's matrices, the convolutions' inputs): the run notes the
  bytes of each;
* a traced run keeps the device seconds of the block's five kernels;
* ``correct`` also holds the scan state itself against the reference:
  once the drain is over a batch row still carries the state of the
  last sequence it held, and of ``correct.state_rows`` such rows
  (``pick_state_rows``) that state is compared with the reference's
  after the same tokens (``scan_state_err_rel``), because no logit can
  tell a narrower scan from the served path's own rounding;
* the sample of ``correct`` holds requests of four classes
  (``pick_sample``): a prompt longer than ``correct.long_prompt``, an
  output longer than ``correct.long_output``, prompt plus output under
  ``correct.short_total``, and the rest; each padded to its own bucket
  of positions (runner ``serve_window``'s, then
  ``correct.longest_sampled``), the reference's head in blocks of rows;
* the drain does not wait for every request (one admitted as the window
  closes has up to 8,192 tokens to make, four to five minutes, and the
  driver stops a run at six): it lasts until ``correct`` can draw its
  sample (``sample_ready``: every request admitted, a finished request
  of each class, ``correct.state_rows`` rows that keep a finished
  sequence's state), ``drain_s`` at most, and the runner then cuts what
  still runs (``ServingEngine.kill``). A request it cut is no failure
  unless its row had stopped making tokens (``STALLED_S``); one that
  failed, was refused or ended short by itself is, as in the other
  runners. Times to first token and gaps are over the requests that
  finished.
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
                                            RENAMED, bucket)
from perfbench.runners.serve_zaya import keep_in_step_log, step_records

STALLED_S = 5.0   # a row cut this long after its last token was stuck
KERNELS = ("gmm", "latent_paged_attention", "latent_write", "kda_decode",
           "kda_chunk")


def generation_config(config, max_seq_len):
    from paddle_tpu.serving import GenerationConfig
    from paddle_tpu.serving.ling import LingBlock

    ref = spec.family(config, "reference")
    types = config.get("dtypes", {})
    if config["moe_shared_expert_intermediate_size"] \
            != config["moe_intermediate_size"]:
        raise spec.SpecError("the shared expert is as wide as a routed one")
    block = LingBlock(
        head_dim=config["head_dim"], layer_types=ref.layer_types(config),
        conv_kernel=config["short_conv_kernel_size"],
        kda_lower_bound=config["kda_lower_bound"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        first_k_dense=config["first_k_dense_replace"],
        n_routed_experts=ref.router_experts(config),
        experts_per_token=config["num_experts_per_tok"],
        n_shared_experts=config["num_shared_experts"],
        moe_d_ff=config["moe_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        experts_held=ref.held(config),
        weight_dtype=types.get("weights", "bfloat16"),
        activation_dtype=types.get("activations", "bfloat16"),
        router_dtype=types.get("router", "float32"),
        cache_dtype=types.get("cache", "bfloat16"),
        state_dtype=types.get("state", "float32"))
    return GenerationConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        block=block)


def seeded_weights(ref, config, seed):
    """The reference's weights in the serving layout, made on the
    default device one jitted call a layer (``ref.layer_maker``: a
    program a kind of layer)."""
    import jax

    words = ref.seed_words(seed)
    top = jax.jit(lambda w: ref.init_top(w, config))(words)
    weights = {"embedding": top["embed"], "final_norm": top["norm_f"],
               "lm_head": top["head"]}
    layer = ref.layer_maker(config)
    for i in range(config["num_hidden_layers"]):
        for k, v in layer(words, i).items():
            weights["l%d/%s" % (i, RENAMED.get(k, k))] = v
    return weights


def build_engine(config, model):
    from paddle_tpu.serving import ServingEngine

    e = config["engine"]
    return ServingEngine(
        model, max_batch=e["max_batch"], max_seq_len=e["max_seq_len"],
        block_size=e["block_size"], num_blocks=e["latent_blocks"],
        max_queue=e["max_queue"], prefill_chunk=e["prefill_chunk"],
        prefill_token_budget=e["prefill_token_budget"],
        async_depth=e["async_depth"], prefix_cache=False, spec_k=0,
        spec_tree="")


def state_row_records(config, seed, records):
    """``correct.state_rows`` finished requests, drawn from the seed
    among those whose batch row no later sequence took (a row keeps its
    last occupant's state: a step leaves the rows it does not compute
    alone) and that are no longer than ``correct.longest_sampled``
    positions; of classes 2 and 3 (:func:`request_class`) where there
    are as many."""
    c = config["correct"]
    last = {}
    for r in records:
        q = r.request
        if q is not None and q.slot is not None and (
                q.slot not in last
                or q.start_time > last[q.slot].request.start_time):
            last[q.slot] = r
    mine = [r for _slot, r in sorted(last.items()) if r.finished_ok
            and len(r.spec.prompt) + r.spec.max_new_tokens
            <= c["longest_sampled"]]
    # the few long ones that finish are left to the sample
    plain = [r for r in mine if request_class(c, r) >= 2]
    if len(plain) >= c["state_rows"]:
        mine = plain
    rng = loadgen.rng_for(seed, 6)
    return [mine[i] for i in rng.permutation(len(mine))[:c["state_rows"]]]


def pick_state_rows(config, seed, engine, records):
    """``[(record, scan state)]`` of :func:`state_row_records`; the
    state ``[KDA layers, H, dk, dv]`` as the engine holds it, widened to
    float32 on the host. Call it once the worker has stopped: no step
    in flight holds the arrays then."""
    scan = engine.row_state().part("scan")
    return [(r, np.asarray(scan[r.request.slot], np.float32))
            for r in state_row_records(config, seed, records)]


def request_class(c, r):
    """0: a prompt longer than ``correct.long_prompt`` (a scan carried
    through many chunks), else 1: an output longer than
    ``correct.long_output`` (thousands of one-token steps on one state),
    else 2: prompt plus output under ``correct.short_total``, else 3."""
    p, m = len(r.spec.prompt), r.spec.max_new_tokens
    return (0 if p > c["long_prompt"] else 1 if m > c["long_output"]
            else 2 if p + m < c["short_total"] else 3)


def pick_sample(config, seed, n, in_window, finished):
    """``n`` of the requests the window finished (``in_window``; where
    it finished too few of a class, of those the drain finished too):
    ``n // 4`` of each class (:func:`request_class`) and, where the
    drain's end left a class fewer (it waits for one of each), more of
    the others; drawn from the seed within the class, among those no
    longer than ``correct.longest_sampled`` positions (the reference's
    full forward beside the weights)."""
    c = config["correct"]
    rng = loadgen.rng_for(seed, 5)
    sample = []

    def of(classes, share):
        def drawn(records):
            mine = [r for r in records if r not in sample
                    and request_class(c, r) in classes
                    and len(r.spec.prompt) + r.spec.max_new_tokens
                    <= c["longest_sampled"]]
            return [mine[i] for i in rng.permutation(len(mine))]
        first = drawn(in_window)
        return (first + [r for r in drawn(finished)
                         if r not in first])[:share]

    for k in range(4):
        sample += of((k,), n // 4)
    return sample + of(range(4), n - len(sample))


def classes_missing(config, sample):
    return 4 - len({request_class(config["correct"], r) for r in sample})


def sample_ready(config, seed, t_end, records):
    """Has the drain given ``correct`` what it compares? Every request
    has been admitted (so no later sequence takes a finished one's row)
    and, of those that finished, ``correct.state_rows`` rows and
    ``correct.sample_requests`` requests of the four classes can be
    drawn; or nothing is left to wait for."""
    c = config["correct"]
    if all(r.request is None or r.request.finished for r in records):
        return True
    if any(r.request is not None and r.request.slot is None
           and not r.request.finished for r in records):
        return False
    held = state_row_records(config, seed, records)
    ok = [r for r in records if r.finished_ok and r not in held
          and r.submitted < t_end]
    sample = pick_sample(config, seed, c["sample_requests"],
                         [r for r in ok if r.stamps[-1] < t_end], ok)
    return (len(held) == c["state_rows"]
            and len(sample) == c["sample_requests"]
            and not classes_missing(config, sample))


def served_token_gaps(ref, config, seed, sample, states, t_max, r_max):
    """``[(gaps, margins, errors, state_err)]`` a sampled request:
    runner ``serve_zaya``'s three (each served token's gap below the
    reference's best logit, the least router margin the reference met at
    the position that produced it, the served logit of the token less
    the reference's), and, of a request in ``states`` (``[(record,
    scan state)]``), a KDA layer each: the distance of the
    row's scan state from the reference's after the tokens the row was
    fed (the prompt and every served token but the last), as a share of
    the reference's norm; None of the others."""
    import jax
    import jax.numpy as jnp

    params = ref.make_params(seed, config)

    @jax.jit
    def gaps(params, tokens, rows, served, stop, state):
        g, least, picked, want = ref.served_gaps_at(
            params, tokens, rows, served, config, stop)
        d = state - want
        return g, least, picked, jnp.sqrt(
            jnp.sum(d * d, axis=(1, 2, 3))
            / jnp.sum(want * want, axis=(1, 2, 3)))

    held = {id(rec): state for rec, state in states}
    blank = jnp.zeros(ref.scan_state_shape(config), jnp.float32)
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
        # a request outside ``states`` is compared all the same (one
        # program), and the result dropped
        state = held.get(id(rec), blank)
        g, least, picked, state_err = gaps(params, tokens, rows, tok,
                                           np.int32(n + m - 1), state)
        top = np.asarray(rec.request.top_logits, np.float32)
        out.append((np.asarray(g)[:m], np.asarray(least)[:m],
                    top - np.asarray(picked)[:m],
                    np.asarray(state_err) if id(rec) in held else None))
    del params
    return out


def output_checks(ref, config, mix, seed, in_window, finished, states,
                  window, note):
    """Runner ``serve_window``'s three numbers over this runner's
    sample: the mean logit gap of every served token, of the decided
    ones (those whose router choice, of experts and of groups, stands
    clear of the runner-up by more than ``correct.router_margin`` in
    every expert layer of the reference), and the root mean square
    distance of the decided tokens' served logits from the
    reference's. Then ``scan_state_err_rel``: how far the scan state of
    the first KDA layer (the one whose inputs have passed through the
    fewest roundings of the served path) lies from the reference's, the
    mean over the rows of ``states``, which join the sample."""
    c = config["correct"]
    held = [rec for rec, _state in states]
    sample = pick_sample(
        config, seed, c["sample_requests"],
        [r for r in in_window if r not in held],
        [r for r in finished if r not in held])
    absent = classes_missing(config, sample)
    sample += held
    t0 = time.perf_counter()
    pairs = served_token_gaps(ref, config, seed, sample, states,
                              c["longest_sampled"],
                              mix["output_len"]["max"]) if sample else []
    gap, margin, err = (np.concatenate([p[i] for p in pairs]) if pairs
                        else np.array([np.inf]) for i in range(3))
    state_err = [p[3] for p in pairs if p[3] is not None]

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
         sampled_classes=[request_class(c, r) for r in sample],
         served_tokens=served["tokens"],
         first_choice_share=served["first_choice_share"],
         gap_mean=served["gap_mean"], gap_max=served["gap_max"],
         err_median=served["err_median"], err_rms=served["err_rms"],
         decided=decided,
         by_margin=[over(m) for m in c.get("margins_printed", [])],
         state_rows_lengths=[len(r.spec.prompt) + r.spec.max_new_tokens
                             for r in held],
         scan_state_err_by_layer=[[float(x) for x in e]
                                  for e in state_err])
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
                    c["sample_requests"] + c["state_rows"] - len(sample)
                    + absent, 0),
              check("failed_requests", window["failed"], 0),
              check("window_compilations", window["compilations"], 0),
              check("window_step_traces", window["traces"], 0),
              check("scan_state_err_rel",
                    float(np.mean([e[0] for e in state_err]))
                    if state_err else float("inf"),
                    c["scan_state_err_rel"])]
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
            raise spec.SpecError("runner serve_ling needs open_loop or "
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

        # the drain is outside the window, and lasts until ``correct``
        # has its sample (``drain_s`` at most): what still runs then is
        # cut, a request of thousands of tokens being minutes from its
        # end (PERF.md section 4)
        unfinished = sum(1 for r in gen.records if r.request is not None
                         and not r.request.finished)
        if closed:
            gen.stopping.set()
        gen.join(30.0)
        records = [r for r in gen.records
                   if r.submitted is not None and r.submitted < t_end]
        while time.perf_counter() < t_end + mix["drain_s"] \
                and not sample_ready(config, seed, t_end, gen.records):
            time.sleep(0.25)
        window = {
            "compilations": ctx["compiles"].count - compiles0,
            "traces": model.trace_count - traces0,
            "kernel_fallbacks": (counter_value("kernels/fallbacks")
                                 - fallbacks0) if tracer else None}
        pool_stats = next(iter(engine.stats().values()))
        t_cut = time.perf_counter()
        cut = engine.kill(RuntimeError("perfbench: the drain is over"))
        while time.perf_counter() < t_cut + 60.0 and any(
                h["error"] is None for h in engine.health().values()):
            time.sleep(0.01)   # the worker dies at its next step's end
        for rec in gen.records:
            if rec.request is not None:
                try:
                    rec.request.wait(60.0)
                except Exception as err:   # counted below as failed
                    if err is not cut:
                        note(phase="request_failed", error=repr(err))
        drain_s = time.perf_counter() - t_end
        # raised in the worker, the error holds the worker's frames, and
        # they the weights and the state; every cut request carries it
        cut.__traceback__ = None
    finally:
        engine.close()
    # the worker has stopped: no step in flight holds the state
    states = pick_state_rows(config, seed, engine, gen.records)
    keep_in_step_log(traced)

    ok = [r for r in records if r.finished_ok]
    # cut by the runner, and was being served: its last token is recent
    cut_short = [r for r in records if r.request is not None
                 and r.request.error is cut
                 and not (r.stamps and t_cut - r.stamps[-1] > STALLED_S)]
    failed = window["failed"] = len(records) - len(ok) - len(cut_short)
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
         failed=failed, cut_at_close=len(cut_short), engine_steps=steps,
         tokens_in_window=in_window,
         unfinished_at_close=unfinished, drain_s=drain_s,
         generator_late_ms_max=max(late),
         ttft_ms_p50=loadgen.percentile(ttft, 50),
         itl_ms_p50=loadgen.percentile(itl, 50),
         slow_gap_share=loadgen.slow_gap_share(itl),
         occupancy_mean=float(np.mean(occupancy)) if occupancy else None,
         blocks_total=pool_stats["blocks_total"],
         page_bytes=pool_stats.get("page_bytes"),
         row_state_bytes=pool_stats.get("row_state_bytes"),
         row_state_parts=pool_stats.get("row_state_parts"))

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
                           states, window, note)
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
