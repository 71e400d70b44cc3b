"""Runner ``serve``: a configuration served through the normal path.

``GenerationModel`` -> ``ServingEngine`` (scheduler, ``KVBlockPool``,
chunked prefill, ``paged_decode``), driven by the benchmark's own load
generator: an open loop that times every request from the moment it was
due, or a closed loop of callers. Every token is stamped in the
engine's ``stream`` callback; percentiles come from the raw samples.

A token gap is one engine step: a decode step (5-8 ms) or, while any
request prefills, a mixed step (25-45 ms). A percentile of the gaps is
a step time only while it sits clear of the cliff between the two, so
every run prints ``slow_gap_share`` (``loadgen.slow_gap_share``), and
``BENCHMARK.json`` judges a gap percentile only in the cells whose
runs kept that share a factor of two from the percentile
(``loadgen.percentile_is_clear``; PERF.md section 2): ``itl_p50_ms``,
how fast text streams, and ``itl_p99_ms``, the chunk a user waits
behind. ``itl_p95_ms`` is computed too and read per layer.

``correct`` (builder's contract, a served model): once the window has
closed, a sample of the requests it finished, drawn from the seed and
with the longest in it, goes through the plain reference once, prompt
and served tokens together. Compared: the gap by which a served
token's reference logit lies below the reference's best at that
position, its mean over the sample (steady from seed to seed; the
number the lower-precision control fails) and its widest (swings by its
nature; held against a token altered where it is produced). The
traffic is greedy (no sampling, no EOS), so a sound server only ever
differs from the reference at near-ties. The engine
and its weights are freed before the reference makes its own.

Times to first token and gaps between tokens are taken over the
requests that finished whole; one that is refused, fails or is not
drained would drop out of them, so any such request makes the run not
correct (``failed_requests``, limit 0): shedding slow requests cannot
improve a metric.
"""

import gc
import queue
import threading
import time

import numpy as np

from perfbench import loadgen, spec
from perfbench.runners import check, counter_value, memory_peak_bytes
from perfbench.trace_reduce import span

SERVING_LAYER_KEYS = {"ln1_scale": "ln1_g", "ln1_bias": "ln1_b",
                      "wproj": "wo", "bproj": "bo", "ln2_scale": "ln2_g",
                      "ln2_bias": "ln2_b", "wff1": "w1", "bff1": "b1",
                      "wff2": "w2", "bff2": "b2"}


def seeded_weights(ref, config, seed):
    """The reference's ``init_params`` in the serving layout (fused
    ``wqkv``/``bqkv``), made on the device in one jitted call, then
    taken to the host leaf by leaf: ``GenerationModel.__init__`` pulls
    every weight through numpy anyway, and handing it device arrays
    would hold two copies of the model on the chip."""
    import jax
    import jax.numpy as jnp

    def make(words):
        p = ref.init_params(words, config)
        w = {"embedding": p["embed"], "lm_head": p["head"],
             "final_ln_scale": p["lnf_g"], "final_ln_bias": p["lnf_b"]}
        for i in range(config["num_layers"]):
            pre = "l%d/" % i
            for k, leaf in SERVING_LAYER_KEYS.items():
                w[pre + k] = p[leaf][i]
            w[pre + "wqkv"] = jnp.concatenate(
                [p["wq"][i], p["wk"][i], p["wv"][i]], axis=1)
            w[pre + "bqkv"] = jnp.concatenate(
                [p["bq"][i], p["bk"][i], p["bv"][i]])
        return w

    on_device = jax.jit(make)(ref.seed_words(seed))
    return {k: np.asarray(on_device.pop(k)) for k in list(on_device)}


class Record:
    """One request as the benchmark saw it (host clock)."""

    __slots__ = ("spec", "due", "submitted", "request", "stamps",
                 "refused", "on_final")

    def __init__(self, spec_, due, on_final=None):
        self.spec, self.due, self.on_final = spec_, due, on_final
        self.submitted = self.request = None
        self.stamps, self.refused = [], False

    def stream(self, _request, _token, final):
        self.stamps.append(time.perf_counter())
        if final and self.on_final is not None:
            self.on_final(self)

    @property
    def finished_ok(self):
        r = self.request
        return (r is not None and r.finished and r.error is None
                and len(r.tokens) == self.spec.max_new_tokens)


def submit(engine, rec):
    from paddle_tpu.serving.scheduler import AdmissionError

    with span("engine.submit"):
        rec.submitted = time.perf_counter()
        try:
            rec.request = engine.submit(
                rec.spec.prompt.tolist(),
                max_new_tokens=rec.spec.max_new_tokens, eos_id=None,
                stream=rec.stream)
        except AdmissionError:
            rec.refused = True


class OpenLoop(threading.Thread):
    """Sends each request when it is due, whatever the server is doing."""

    def __init__(self, engine, records):
        super().__init__(name="perfbench-open-loop", daemon=True)
        self.engine, self.records = engine, records

    def run(self):
        for rec in self.records:
            with span("generator.sleep"):
                while True:
                    wait = rec.due - time.perf_counter()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05) if wait > 2e-3 else 0)
            submit(self.engine, rec)


class ClosedLoop(threading.Thread):
    """``clients`` callers: a finished request frees its caller, which
    sends the next one of the list. Stops sending when told to."""

    def __init__(self, engine, specs, clients):
        super().__init__(name="perfbench-closed-loop", daemon=True)
        self.engine, self.specs, self.clients = engine, specs, clients
        self.records, self.done = [], queue.Queue()
        self.stopping = threading.Event()

    def _send(self):
        s = self.specs[len(self.records) % len(self.specs)]
        rec = Record(s, None, on_final=self.done.put)
        self.records.append(rec)
        rec.due = time.perf_counter()
        submit(self.engine, rec)

    def run(self):
        for _ in range(self.clients):
            self._send()
        while not self.stopping.is_set():
            try:
                with span("generator.wait"):
                    self.done.get(timeout=0.05)
            except queue.Empty:
                continue
            if not self.stopping.is_set():
                self._send()


def sample_stats(engine, until, occupancy, pool_used, period=0.05):
    """The harness's own sampling of ``stats()`` until the window ends."""
    while True:
        now = time.perf_counter()
        if now >= until:
            return
        s = next(iter(engine.stats().values()))
        occupancy.append(s["batch_occupancy"])
        pool_used.append(100.0 * s["blocks_in_use"] / s["blocks_total"])
        time.sleep(min(period, max(0.0, until - now)))


def engine_steps(engine):
    return next(iter(engine.stats().values()))["steps"]


def served_token_gaps(ref, config, seed, sample, t_max, r_max):
    """For each sampled request, the gaps (reference's best logit minus
    the served token's logit) at every served position, and whether the
    served token was the reference's first choice."""
    import jax
    import jax.numpy as jnp

    params = ref.make_params(seed, config)

    @jax.jit
    def gaps(params, tokens, rows, served):
        z = ref.logits_at(params, tokens, rows, config)
        picked = jnp.take_along_axis(z, served[:, None], axis=1)[:, 0]
        return jnp.max(z, axis=-1) - picked

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
        out.append(np.asarray(gaps(params, tokens, rows, tok))[:m])
    del params
    return out


def output_checks(ref, config, mix, seed, finished, window, note):
    """The numbers compared, each beside its limit. The sample is drawn
    from the seed, with the longest request in it; every sequence is
    padded to one length so the reference compiles once per mix."""
    c = config["correct"]
    by_len = sorted(finished, key=lambda r: len(r.spec.prompt)
                    + r.spec.max_new_tokens)
    sample, rest = by_len[-1:], by_len[:-1]
    pick = loadgen.rng_for(seed, 5).permutation(len(rest))
    sample += [rest[i] for i in pick[:c["sample_requests"] - 1]]
    t0 = time.perf_counter()
    t_max = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
              // 128) * 128
    gaps = served_token_gaps(ref, config, seed, sample, t_max,
                             mix["output_len"]["max"]) if sample else []
    flat = np.concatenate(gaps) if gaps else np.array([np.inf])
    note(phase="reference_done", seconds=time.perf_counter() - t0,
         sampled_requests=len(sample), served_tokens=int(flat.size),
         first_choice_share=float(np.mean(flat <= 0.0)),
         gap_mean=float(flat.mean()), gap_max=float(flat.max()))

    checks = [check("served_logit_gap_mean", float(flat.mean()),
                    c["served_logit_gap_mean"]),
              check("served_logit_gap_max", float(flat.max()),
                    c["served_logit_gap_max"]),
              check("failed_requests", window["failed"], 0),
              check("window_compilations", window["compilations"], 0),
              check("window_step_traces", window["traces"], 0)]
    if window["kernel_fallbacks"] is not None:
        checks.append(check("kernel_fallbacks",
                            window["kernel_fallbacks"], 0))
    return checks


def build_engine(config, model):
    from paddle_tpu.serving import ServingEngine

    e = config["engine"]
    return ServingEngine(
        model, max_batch=e["max_batch"], max_seq_len=e["max_seq_len"],
        block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_queue=e["max_queue"], prefill_chunk=e["prefill_chunk"],
        prefix_cache=False, spec_k=0, spec_tree="")


def run(ctx, tamper=None):
    """``tamper(model)`` is for the harness's own tests and the
    lower-precision controls: it returns the model the engine serves
    (quantized, or broken), to show ``correct`` come out false."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import GenerationConfig, GenerationModel

    config, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    note, tracer, seconds = ctx["note"], ctx["tracer"], ctx["seconds"]
    ref = spec.family(config, "reference")
    vocab, e = config["vocab_size"], config["engine"]
    if tracer:
        metrics.enable()   # kernel dispatch counters; off when timing

    gcfg = GenerationConfig(
        vocab_size=vocab, d_model=config["d_model"],
        n_heads=config["attention_heads"], n_layers=config["num_layers"],
        d_ff=config["ffn_dim"], max_seq_len=e["max_seq_len"])
    model = GenerationModel(gcfg, seeded_weights(ref, config, seed))
    if tamper is not None:
        model = tamper(model)
    note(phase="model_ready", seconds=time.perf_counter() - ctx["t_start"],
         parameters=ref.n_params(config))
    engine = build_engine(config, model)
    try:
        # set-up: every step shape this mix uses, through the engine
        warm = [Record(s, 0.0)
                for s in loadgen.warmup_requests(mix, vocab)]
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
            raise spec.SpecError("runner serve needs open_loop or "
                                 "closed_loop traffic")
        note(phase="window_open", setup_s=t0 - ctx["t_start"])
        compiles0, traces0 = ctx["compiles"].count, model.trace_count
        steps0 = engine_steps(engine)
        fallbacks0 = counter_value("kernels/fallbacks") if tracer else None
        occupancy, pool_used = [], []
        t_end = t0 + seconds
        trace_s = mix["trace_seconds"]
        if tracer:
            sample_stats(engine, t_end - trace_s, occupancy, pool_used)
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

    # a request that was refused, failed or did not finish in the drain
    # drops out of the statistics below, so it makes the run not correct
    ok = [r for r in records if r.finished_ok]
    failed = window["failed"] = len(records) - len(ok)
    late = [(r.submitted - r.due) * 1e3 for r in records] if not closed \
        else [0.0]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in ok]
    queue_wait = [(r.request.start_time - r.due) * 1e3 for r in ok]
    itl = [d * 1e3 for r in ok for d in np.diff(r.stamps)]
    in_window = sum(1 for r in records for s in r.stamps if t0 <= s < t_end)
    p = loadgen.percentile
    slow_share = loadgen.slow_gap_share(itl)
    ttft_p50, itl_p50, itl_p95, itl_p99 = (
        p(ttft, 50), p(itl, 50), p(itl, 95), p(itl, 99))
    note(phase="window_closed", requests=len(records), finished=len(ok),
         failed=failed, engine_steps=steps, tokens_in_window=in_window,
         token_gaps=len(itl), slow_gap_share=slow_share,
         unfinished_at_close=unfinished, drain_s=drain_s,
         generator_late_ms_p50=p(late, 50),
         generator_late_ms_max=max(late),
         ttft_ms_p50=ttft_p50, ttft_ms_p90=p(ttft, 90),
         itl_ms_p50=itl_p50, itl_ms_p95=itl_p95, itl_ms_p99=itl_p99,
         queue_wait_ms_p90=p(queue_wait, 90),
         # a queue that grows over the window shows in the second half
         ttft_ms_p50_by_half=[p(ttft[:len(ttft) // 2], 50),
                              p(ttft[len(ttft) // 2:], 50)],
         pool_used_pct_max=max(pool_used) if pool_used else None,
         occupancy_mean=float(np.mean(occupancy)) if occupancy else None,
         blocks_total=pool_stats["blocks_total"],
         weight_store=pool_stats["weight_store"])

    del engine, model, gen
    gc.collect()
    reduced = tracer.reduce() if tracer else None
    checks = output_checks(ref, config, mix, seed, ok, window, note)
    return {
        "end_to_end": {
            "ttft_p50_ms": ttft_p50, "itl_p50_ms": itl_p50,
            "itl_p95_ms": itl_p95, "itl_p99_ms": itl_p99,
            "serve_tokens_per_s": in_window / seconds,
            "setup_s": t0 - ctx["t_start"]},
        "observations": {
            "ttft_ms": ttft, "itl_ms": itl, "queue_wait_ms": queue_wait,
            "occupancy": occupancy, "pool_used_pct": pool_used,
            # one sample, so that reader ``stat`` serves it
            "slow_gap_share_pct": None if slow_share is None
            else [100.0 * slow_share],
            "window_s": seconds, "engine_steps": steps,
            "trace": reduced},
        "attempted": len(records), "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak,
    }
