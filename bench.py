"""Benchmark harness (parity: benchmark/fluid/fluid_benchmark.py — prints
throughput the same way, normalized per chip).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Default benchmark: Transformer-base LM training throughput, tokens/sec/chip
on the attached accelerator (BASELINE.json north-star metric). The
vs_baseline denominator is 90% of a published A100 transformer-base
training figure (~55k tokens/s/GPU for a 65M-param model in bf16) per the
BASELINE.md note that the reference repo publishes no numbers of its own.
"""

import json
import sys
import time

import numpy as np

# 90% of A100 transformer-base tokens/sec (north star: >= 90% of A100)
BASELINE_TOKENS_PER_SEC = 0.9 * 55000.0


def bench_transformer(steps=24, warmup=3, batch=192, seq=512, remat=None):
    """Full Adam training step (fp32 moments + bias correction — the same
    optimizer the harness-faithful rows use; measured free vs SGD at this
    scale, 276.7k vs 275.3k tok/s, because the update stream overlaps the
    backward's matmuls). batch=192 with rematerialization is the measured
    single-chip optimum on v5e-1 (16G HBM): 238k tok/s @128, 245.6k @160,
    ~276k @192 (flat to 256; 320 OOMs). The chunked memory-lean CE head
    (single_chip_loss: custom-vjp CE keeps only bf16 logits as residuals)
    is what admits batches past 128 — the full-seq fp32 logits +
    log-softmax residual previously pinned ~16G. remat defaults on for
    batch >= 64 (smaller batches fit activations and run faster without).
    Throughput-per-chip at the best operating point is the metric,
    matching how the A100 baseline figure is itself quoted."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.transformer import (
        TransformerConfig, init_params, single_chip_loss)

    if remat is None:
        remat = batch >= 64
    cfg = TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=6, d_ff=2048,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=remat)
    params = init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                          if x.dtype == jnp.float32 and x.ndim >= 2 else x,
                          params)
    m0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    lr, b1, b2, eps = 1e-4, 0.9, 0.999, 1e-8

    def train_step(params, m, v, t, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: single_chip_loss(p, tokens, labels, cfg))(params)
        t = t + 1
        tf = t.astype(jnp.float32)

        def upd(p, g, mm, vv):
            gf = g.astype(jnp.float32)
            m2 = b1 * mm + (1 - b1) * gf
            v2 = b2 * vv + (1 - b2) * gf * gf
            p2 = (p.astype(jnp.float32)
                  - lr * (m2 / (1 - b1 ** tf))
                  / (jnp.sqrt(v2 / (1 - b2 ** tf)) + eps))
            return p2.astype(p.dtype), m2, v2

        flat_p, tdef = jax.tree.flatten(params)
        out = [upd(p, g, mm, vv) for p, g, mm, vv in zip(
            flat_p, tdef.flatten_up_to(grads),
            tdef.flatten_up_to(m), tdef.flatten_up_to(v))]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]),
                tdef.unflatten([o[2] for o in out]), t, loss)

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    labs = np.roll(toks, -1, axis=1).astype(np.int32)

    # one host sync every SYNC_EVERY steps: a deep in-flight queue
    # amortizes the host round trip
    SYNC_EVERY = 12
    state = (params, m0, v0, jnp.zeros((), jnp.int32))
    for _ in range(warmup):
        *state, loss = step(*state, toks, labs)
        float(loss)

    t0 = time.perf_counter()
    for i in range(steps):
        *state, loss = step(*state, toks, labs)
        if (i + 1) % SYNC_EVERY == 0:
            float(loss)
    float(loss)
    dt = time.perf_counter() - t0

    n_chips = 1  # single-chip bench; per-chip normalization
    tokens_per_sec = steps * batch * seq / dt / n_chips
    return tokens_per_sec, float(loss)


def bench_transformer_fluid(steps=24, warmup=3, batch=160, seq=512,
                            async_exec=True, feed_mode="device",
                            model_kwargs=None, program_opt=True,
                            dtype="bfloat16", amp="legacy"):
    """The SAME flagship trained through the Fluid-equivalent Python API
    (fluid.layers program -> descriptor lowering -> one donated jitted
    step). This is the HEADLINE path (BASELINE.json north star: "via the
    Fluid-equivalent Python API") and, since round 5, also the fastest:
    the fused multihead-attention op keeps the flash kernel's operand
    layout inside the projection dots, the chunked CE head bounds the
    fp32 log-softmax transient, and with both in place batch 160 fits
    16G HBM WITHOUT remat — skipping the backward recompute that the
    bespoke-jax step (bench_transformer) still needs at its operating
    point. Measured 286.4k vs 278.5k tok/s same-day (round 5).

    async_exec=True is the steady-state async pipeline: every run() is
    return_numpy=False and the executor's bounded in-flight window
    (async_steps=12) provides the only
    backpressure — no explicit per-K-steps host sync in the loop body.
    async_exec=False is the fully synchronous baseline row (materialize
    every step), measured for the with/without-async comparison.

    feed_mode="device" pins the (fixed) batch in HBM once — the headline
    configuration. "host" re-feeds host numpy each step through
    Executor.prefetch, exercising the background H2D staging path (the
    --tiny smoke uses it so feed/h2d_bytes telemetry has traffic).

    program_opt=False runs the leg under PTPU_NO_PROGRAM_OPT=1 — the
    exact pre-pass-pipeline lowering path, measured so the compile-time
    optimization win (compile_time_s, StableHLO module size, tokens/s)
    is visible in BENCH_*.json.

    dtype/amp select the precision scheme for the AMP-vs-fp32 pair of
    legs (docs/MIXED_PRECISION.md): amp="legacy" keeps the historical
    headline configuration (bf16-stored params + the contrib attr-mark
    decorator); amp=False is the pure-fp32 baseline leg; amp=True runs
    the same fp32-stored model through paddle_tpu.amp.decorate — the
    compile-time bf16 dtype-rewrite pass — so the two legs isolate
    exactly what automatic mixed precision buys."""
    import os

    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        _t, _l, loss = transformer_fluid.build(
            seq_len=seq, remat=False, dtype=dtype,
            **(model_kwargs or {}))
        if amp == "legacy":
            opt = fluid.contrib.mixed_precision.decorate(
                fluid.optimizer.SGD(0.01), init_loss_scaling=1.0,
                use_dynamic_loss_scaling=False)
        elif amp:
            opt = fluid.amp.decorate(fluid.optimizer.SGD(0.01))
        else:
            opt = fluid.optimizer.SGD(0.01)
        opt.minimize(loss)
        # compile-pipeline receipt (docs/COMPILER_PASSES.md): a foldable
        # const chain, a CSE-able duplicate pair, and a fetch-dead branch
        # — the optimized leg's compiler/* counters and the noopt leg's
        # larger module size come from these
        _c = fluid.layers.scale(
            fluid.layers.fill_constant([1], "float32", 1.5), scale=0.5)
        _d1 = fluid.layers.scale(loss, scale=3.0)
        _d2 = fluid.layers.scale(loss, scale=3.0)
        fluid.layers.elementwise_add(
            fluid.layers.elementwise_add(_d1, _d2), _c)
    # the default place: main() has already required a TPU unless this
    # is a --tiny rehearsal
    exe = fluid.Executor(async_steps=12)
    prev_opt = os.environ.get("PTPU_NO_PROGRAM_OPT")
    if not program_opt:
        os.environ["PTPU_NO_PROGRAM_OPT"] = "1"
    exe.run(sprog)
    vocab = (model_kwargs or {}).get("vocab_size", 32000)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    labs = np.roll(toks, -1, axis=1).astype(np.int32)
    if feed_mode == "device":
        feed = {"tokens": jax.device_put(toks), "labels": jax.device_put(labs)}
    else:
        feed = {"tokens": toks, "labels": labs}

    def one_step():
        if feed_mode != "device":
            exe.prefetch(feed)
        out, = exe.run(prog, feed=feed, fetch_list=[loss],
                       return_numpy=not async_exec)
        return out

    try:
        out = None
        compile_time_s = None
        for i in range(warmup):
            t0 = time.perf_counter()
            out = one_step()
            float(np.asarray(out).ravel()[0])
            if i == 0:
                # cold call: program optimization + trace + XLA compile
                # (the steady-state step time is measured separately)
                compile_time_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            out = one_step()
            if not async_exec:
                float(np.asarray(out).ravel()[0])
        last = float(np.asarray(out).ravel()[0])  # the one sync point
        dt = time.perf_counter() - t0
        exe.close()
    finally:
        if not program_opt:
            if prev_opt is None:
                os.environ.pop("PTPU_NO_PROGRAM_OPT", None)
            else:
                os.environ["PTPU_NO_PROGRAM_OPT"] = prev_opt
    return steps * batch * seq / dt, last, dt / steps, compile_time_s


# tiny configuration for the CI bench-smoke stage: exercises the whole
# async pipeline (window, prefetch H2D, compile cache) in seconds on CPU
TINY = dict(
    model_kwargs=dict(vocab_size=512, d_model=64, n_heads=2, n_layers=2,
                      d_ff=128),
    batch=8, seq=32, steps=6, warmup=1,
)


def _stablehlo_bytes():
    """Cumulative lowered-module bytes from the compile-cache telemetry
    (None when metrics are off — the AOT instrumentation is what records
    module sizes). Callers diff before/after a leg."""
    from paddle_tpu.observability import metrics as obs_metrics

    if not obs_metrics.enabled():
        return None
    h = obs_metrics.registry().histogram(
        "compile_cache/stablehlo_module_bytes")
    return h.sum


def bench_resilience_overhead(steps=48, warmup=8, batch=64,
                              guard_every=8):
    """Guarded vs unguarded steady-state step time on a small train
    program, so the resilience guard's cost is measured, not assumed
    (acceptance: < 5% on the tiny config). Both legs share ONE program +
    executor (identical compiled step) and the SAME sync cadence — the
    unguarded loop also materializes every `guard_every` steps — so the
    delta isolates exactly what the guard adds: the host-side
    isfinite/EMA scan plus one scope snapshot per validated boundary.
    Returns (unguarded_step_s, guarded_step_s)."""
    import paddle_tpu as fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = fluid.layers.data(name="rx", shape=[64], dtype="float32")
        y = fluid.layers.data(name="ry", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=64, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    # private scope: the guard snapshots the whole training scope, so
    # sharing the global one would bill earlier bench legs' params to
    # this measurement
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog, scope=scope)
    rng = np.random.RandomState(0)
    feed = {"rx": rng.uniform(-1, 1, (batch, 64)).astype(np.float32),
            "ry": rng.uniform(-1, 1, (batch, 1)).astype(np.float32)}

    def unguarded(n):
        pending = []
        for _ in range(n):
            out, = exe.run(prog, feed=feed, fetch_list=[loss],
                           scope=scope, return_numpy=False)
            pending.append(out)
            if len(pending) >= guard_every:
                for f in pending:
                    np.asarray(f)
                pending = []
        for f in pending:
            np.asarray(f)

    from paddle_tpu.resilience import ResilientTrainer

    trainer = ResilientTrainer(exe, prog, fetch_list=[loss], scope=scope,
                               guard_every=guard_every)

    def guarded(n):
        trainer.run({"rx": feed["rx"], "ry": feed["ry"]}
                    for _ in range(n))

    unguarded(warmup)
    guarded(warmup)
    t0 = time.perf_counter()
    unguarded(steps)
    t1 = time.perf_counter()
    guarded(steps)
    t2 = time.perf_counter()
    exe.close()
    return (t1 - t0) / steps, (t2 - t1) / steps


def bench_data_ingestion(n_shards=8, records_per_shard=2048, width=32,
                         batch_size=256, repeats=3):
    """Streaming-ingestion receipt (docs/DATA_PLANE.md): records/s
    through the fault-tolerant QueueDataset reader, healthy vs degraded
    (one shard corrupted on disk and QUARANTINED by the containment
    policy). The degraded leg reads fewer records, so the honest
    receipt is throughput on the SURVIVING stream:
    `bench/data_degraded_throughput_ratio` = degraded / healthy
    records-per-second — containment must cost detection overhead, not
    collapse the pipeline. Returns a result dict."""
    import shutil
    import tempfile
    import warnings

    import paddle_tpu as fluid
    from paddle_tpu import data_plane

    class _Var:
        def __init__(self, name):
            self.name = name

    tmp = tempfile.mkdtemp(prefix="ptpu_bench_data_")
    try:
        paths = []
        payload = np.arange(width, dtype=np.float32)
        for i in range(n_shards):
            p = "%s/shard%02d.rec" % (tmp, i)

            def gen(i=i):
                for j in range(records_per_shard):
                    yield (payload + i * records_per_shard + j,
                           np.int64(i * records_per_shard + j))

            fluid.convert_reader_to_recordio_file(p, gen)
            paths.append(p)

        def make_ds():
            ds = fluid.DatasetFactory().create_dataset("QueueDataset")
            ds.set_filelist(paths)
            ds.set_batch_size(batch_size)
            ds.set_use_var([_Var("x"), _Var("y")])
            ds.set_thread(2)
            return ds

        def run_leg():
            best = None
            n_records = 0
            for _ in range(repeats):
                t0 = time.perf_counter()
                n_records = 0
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    for feed in make_ds()._batches_prefetched():
                        n_records += feed["y"].shape[0]
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
                data_plane.reset_quarantine()  # re-detect per repeat
            return n_records / best, n_records

        healthy_rps, healthy_records = run_leg()

        # damage one mid-list shard on disk (a real torn byte, not an
        # injector hook — the bench measures the production path)
        raw = bytearray(open(paths[n_shards // 2], "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        with open(paths[n_shards // 2], "wb") as f:
            f.write(bytes(raw))
        import os as _os

        _os.environ["PTPU_DATA_ANOMALY_POLICY"] = "quarantine_shard"
        try:
            degraded_rps, degraded_records = run_leg()
        finally:
            _os.environ.pop("PTPU_DATA_ANOMALY_POLICY", None)
            data_plane.reset_quarantine()
        return {
            "healthy_records_per_sec": healthy_rps,
            "degraded_records_per_sec": degraded_rps,
            "degraded_throughput_ratio": degraded_rps / healthy_rps,
            "healthy_records": healthy_records,
            "degraded_records": degraded_records,
            "records_lost": healthy_records - degraded_records,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_recommender(n_shards=4, records_per_shard=320, batch_size=32,
                      epochs=3, vocab=512, fields=6, embed_dim=16,
                      cache_rows=128):
    """Recommender fast-path receipt (docs/RECOMMENDER.md): the SAME
    recordio CTR stream and the SAME parameter init through three legs
    of a host-table DeepFM —

      sync           legacy in-step `pure_callback` embedding pull
      overlap        PTPU_EMBED_PREFETCH=1: batch t+1's unique rows
                     gathered on a host worker while the device runs t
      overlap_cache  + PTPU_EMBED_CACHE_ROWS: frequency-admitted hot
                     rows served from a device-resident cache

    The receipt is honest only because the three legs are REQUIRED to
    be bitwise identical (per-epoch losses and final table shards +
    accumulators) — the fast path may only move work, never change
    numerics. Throughput excludes epoch 0 (compile). Returns a result
    dict; `rec_bitwise_identical` gates the CI rec stage."""
    import hashlib
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu import initializer as _init
    from paddle_tpu import unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.models import deepfm
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.parallel import host_embedding
    from paddle_tpu.parallel.host_embedding import HostEmbeddingTable

    obs_metrics.enable()
    tmp = tempfile.mkdtemp(prefix="ptpu_bench_rec_")

    class _Var:
        def __init__(self, name):
            self.name = name

    def write_shards():
        paths = []
        for s in range(n_shards):
            p = "%s/ctr%02d.rec" % (tmp, s)
            rng = np.random.RandomState(7000 + s)

            def gen(rng=rng):
                for _ in range(records_per_shard):
                    # Zipf-ish skew: half the lookups land in a 32-row
                    # hot set so frequency admission has a signal
                    hot = rng.rand(fields) < 0.5
                    ids = np.where(hot, rng.randint(0, 32, fields),
                                   rng.randint(0, vocab, fields))
                    yield (ids.astype(np.int64),
                           np.array([rng.randint(0, 2)], np.float32))

            fluid.convert_reader_to_recordio_file(p, gen)
            paths.append(p)
        return paths

    def fresh():
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        scope_mod._scope_stack[:] = [scope_mod.Scope()]
        HostEmbeddingTable.reset_registry()
        _init._global_seed_counter[0] = 0
        np.random.seed(42)

    def table_digest():
        h = hashlib.sha256()
        state = host_embedding.tables_state_dict()
        for tab in sorted(state):
            for key in sorted(state[tab]):
                h.update(np.ascontiguousarray(state[tab][key]).tobytes())
        return h.hexdigest()

    knobs = ("PTPU_EMBED_PREFETCH", "PTPU_EMBED_CACHE_ROWS",
             "PTPU_EMBED_CACHE_ADMIT")

    def run_leg(env):
        import os as _os

        for k in knobs:
            _os.environ.pop(k, None)
        _os.environ.update(env)
        fresh()
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_batch_size(batch_size)
        ds.set_filelist(paths)
        main_p, startup = framework.Program(), framework.Program()
        with framework.program_guard(main_p, startup):
            (ids, label), _pred, avg_cost = deepfm.build_distributed(
                vocab_size=vocab, num_fields=fields, embed_dim=embed_dim,
                mlp_dims=(32, 16), num_shards=2, learning_rate=0.05)
            fluid.optimizer.SGD(learning_rate=0.05).minimize(avg_cost)
        ds.set_use_var([_Var("ids"), _Var("label")])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        reg = obs_metrics.registry()
        c0 = {m: reg.counter("embed/" + m).value
              for m in ("cache_hits", "prefetch_hits", "pull_rows")}
        losses, times = [], []
        try:
            for _ in range(epochs):
                t0 = time.perf_counter()
                out = exe.train_from_dataset(program=main_p, dataset=ds,
                                             fetch_list=[avg_cost])
                times.append(time.perf_counter() - t0)
                losses.append(np.asarray(out[0]).copy())
        finally:
            for k in knobs:
                _os.environ.pop(k, None)
        counters = {m: reg.counter("embed/" + m).value - c0[m]
                    for m in c0}
        timed = sum(times[1:]) if epochs > 1 else times[0]
        n_examples = n_shards * records_per_shard * max(epochs - 1, 1)
        return {"examples_per_sec": n_examples / max(timed, 1e-9),
                "losses": losses, "digest": table_digest(),
                "counters": counters}

    try:
        paths = write_shards()
        sync = run_leg({})
        overlap = run_leg({"PTPU_EMBED_PREFETCH": "1"})
        cached = run_leg({"PTPU_EMBED_PREFETCH": "1",
                          "PTPU_EMBED_CACHE_ROWS": str(cache_rows),
                          "PTPU_EMBED_CACHE_ADMIT": "2"})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    bitwise = (sync["digest"] == overlap["digest"] == cached["digest"]
               and all(a.tobytes() == b.tobytes() == c.tobytes()
                       for a, b, c in zip(sync["losses"],
                                          overlap["losses"],
                                          cached["losses"])))
    hits = cached["counters"]["cache_hits"]
    served = hits + cached["counters"]["pull_rows"]
    return {
        "sync_examples_per_sec": sync["examples_per_sec"],
        "overlap_examples_per_sec": overlap["examples_per_sec"],
        "cache_examples_per_sec": cached["examples_per_sec"],
        "overlap_speedup": (overlap["examples_per_sec"]
                            / sync["examples_per_sec"]),
        "cache_hit_rate": hits / served if served else 0.0,
        "prefetch_hits": overlap["counters"]["prefetch_hits"],
        "cache_hits": hits,
        "bitwise_identical": bitwise,
        "final_loss": float(np.asarray(sync["losses"][-1]).ravel()[0]),
        "table_digest": sync["digest"],
    }


def bench_serving(n_requests=32, max_new_tokens=24, rate=100000.0,
                  max_batch=16, vocab=256, d_model=64, n_heads=2,
                  n_layers=2, d_ff=128, max_seq_len=128):
    """Continuous-batching serving throughput (docs/SERVING.md): the
    SAME deterministic Poisson request stream served twice on one tiny
    decoder-only model — (a) through an 8-slot continuously-batched
    ServingEngine, (b) serially, one request at a time through a 1-slot
    engine (the pre-serving "loop over AnalysisPredictor calls" shape).
    Aggregate generated tokens/s is the metric; the acceptance gate is
    batched >= 2x serial with >= 8 concurrent requests, and the two
    legs' outputs must be token-identical (greedy decode is
    deterministic — batching may never change what a request gets).

    Returns (batched_tps, serial_tps, outputs_match, p50_s, p99_s,
    total_tokens, batched_steps_per_sec, batched_step_flops) — the last
    two feed the MFU receipt (step_flops is None with metrics off)."""
    from paddle_tpu import serving

    cfg = serving.GenerationConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=max_seq_len)
    model = serving.GenerationModel.random(cfg, seed=0)
    gen = serving.PoissonLoadGenerator(
        rate, n_requests, prompt_len=(4, 12),
        max_new_tokens=max_new_tokens, vocab_size=vocab, seed=0)

    # batched leg: open-loop Poisson arrivals into the shared batch.
    # One warmup request first: the decode step's XLA compile is a
    # one-time cost, not steady-state serving throughput (the same
    # reason every other leg here runs warmup steps).
    eng = serving.ServingEngine(model, max_batch=max_batch,
                                max_seq_len=max_seq_len, block_size=16)
    t0 = time.perf_counter()
    eng.generate([1, 2], max_new_tokens=2, timeout=600)
    compile_batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    accepted, rejected = gen.run(eng)
    batched_outs = [r.wait(600) for r in accepted]
    dt_batched = time.perf_counter() - t0
    lats = sorted(r.latency for r in accepted)
    steps_batched = sum(s["steps"] for s in eng.stats().values())
    eng.close()
    total_tokens = sum(len(o) for o in batched_outs)
    # snapshot the batched engine's compiled-step flops BEFORE the
    # serial engine compiles (the exec/step_flops gauge is
    # last-writer-wins)
    batched_step_flops = _current_step_flops()

    # serial leg: the identical stream, one request at a time (no
    # arrival sleeps — this measures pure serial decode capacity)
    eng1 = serving.ServingEngine(model, max_batch=1,
                                 max_seq_len=max_seq_len, block_size=16)
    eng1.generate([1, 2], max_new_tokens=2, timeout=600)
    t0 = time.perf_counter()
    serial_outs = [
        eng1.generate(spec["prompt"],
                      max_new_tokens=spec["max_new_tokens"], timeout=600)
        for spec in gen.make_requests()]
    dt_serial = time.perf_counter() - t0
    eng1.close()
    from paddle_tpu.observability import metrics as obs_metrics

    obs_metrics.registry().gauge(
        "bench/serving_compile_time_s").set(compile_batched_s)

    if rejected:
        raise RuntimeError("serving bench rejected %d requests — grow "
                           "max_queue" % len(rejected))

    def pct(q):
        return lats[min(len(lats) - 1, int(round(q * (len(lats) - 1))))]

    return (total_tokens / dt_batched,
            sum(len(o) for o in serial_outs) / dt_serial,
            batched_outs == serial_outs, pct(0.5), pct(0.99),
            total_tokens, steps_batched / dt_batched,
            batched_step_flops)


def _current_step_flops():
    """The most recently compiled program's per-step flops
    (``exec/step_flops``, published at compile time when metrics are
    on; None with metrics off — the cost-analysis read never runs)."""
    from paddle_tpu.observability import metrics as obs_metrics

    if not obs_metrics.enabled():
        return None
    return obs_metrics.registry().to_dict().get(
        "gauges", {}).get("exec/step_flops")


def _mfu_extra(step_flops, steps_per_sec):
    """MFU receipt for one leg: compiled-step flops against the
    device_kind-keyed peak table (observability.cost). Returns the
    --legs-out fields and publishes ``bench/mfu_pct``; {} when metrics
    are off or the leg has no step cadence. A device with no peak on
    record (the CPU of a --tiny run) reports its flops and no MFU."""
    if not step_flops or not steps_per_sec:
        return {}
    from paddle_tpu.observability import cost as obs_cost
    from paddle_tpu.observability import metrics as obs_metrics

    pct = obs_cost.mfu_pct(step_flops, steps_per_sec)
    if pct is None:
        return {"step_flops": step_flops}
    obs_metrics.registry().gauge("bench/mfu_pct").set(pct)
    return {"step_flops": step_flops, "mfu_pct": round(pct, 4)}


def bench_serving_fastpath(n_requests=10, max_new_tokens=8,
                           prefix_len=64, max_batch=8, vocab=256,
                           d_model=64, n_heads=2, n_layers=2, d_ff=128,
                           max_seq_len=160, block_size=16, chunk=16):
    """Serving fast-path receipt (docs/SERVING.md): one
    shared-system-prompt request set — every prompt is one long shared
    prefix plus a short unique tail, the dominant traffic shape at
    millions-of-users scale — served with radix prefix caching on:
    the chunked step takes ``ceil(prefix_len/chunk)`` calls for a
    prompt, and once the first request seals the shared blocks, later
    requests skip even those. The outputs must stay token-identical to
    ``reference_decode`` (the functional gate).

    Returns a dict with the leg's ttft_p50/tokens_per_sec, the prefix
    hit rate and the identity flag."""
    from paddle_tpu import serving

    cfg = serving.GenerationConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=max_seq_len)
    model = serving.GenerationModel.random(cfg, seed=0)
    rng = np.random.RandomState(11)
    shared = rng.randint(0, vocab, size=prefix_len).tolist()
    prompts = [shared + rng.randint(
        0, vocab, size=int(rng.randint(2, 9))).tolist()
        for _ in range(n_requests)]
    refs = [serving.reference_decode(model, p, max_new_tokens)
            for p in prompts]
    shared_blocks = prefix_len // block_size

    def run_leg(**kw):
        eng = serving.ServingEngine(model, max_batch=max_batch,
                                    max_seq_len=max_seq_len,
                                    block_size=block_size, **kw)
        # priming request: pays the one-time XLA compile for both step
        # shapes AND prefills + seals the shared prefix blocks, the
        # steady-state cache-warm serving condition
        eng.generate(shared + [7], max_new_tokens=2, timeout=600)
        primed_reuse = eng.stats()["default"]["prefix_blocks_reused"]
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        outs = [r.wait(600) for r in reqs]
        wall = time.perf_counter() - t0
        ttfts = sorted(r.ttft for r in reqs)
        stats = eng.stats()["default"]
        eng.close()
        return {
            "outputs_match": outs == refs,
            "ttft_p50": ttfts[len(ttfts) // 2],
            "tokens_per_sec": sum(len(o) for o in outs) / wall,
            "prefix_blocks_reused":
                stats["prefix_blocks_reused"] - primed_reuse,
        }

    fast = run_leg(prefill_chunk=chunk, prefix_cache=True)
    possible = n_requests * shared_blocks
    return {
        "fast": fast,
        "prefix_hit_rate": fast["prefix_blocks_reused"] / possible,
        "outputs_match": fast["outputs_match"],
    }


def bench_serving_spec(n_requests=6, max_new_tokens=48, spec_k=6,
                       max_batch=2, vocab=64, d_model=64, n_heads=2,
                       n_layers=2, d_ff=128, max_seq_len=256,
                       block_size=16, chunk=8, pattern_len=4, reps=3):
    """Speculative-decoding receipt (docs/SERVING.md): one
    repetitive/structured generation set — each prompt is a short
    random pattern repeated several times, and the tiny model's greedy
    continuation settles into near-periodic runs: templated/structured
    output, the traffic shape n-gram/prompt-lookup drafting shines on —
    served with ``spec_k`` on and off. Requests run one at a time (low
    concurrency is where the one-compiled-step-per-token bound actually
    binds; a full batch hides it behind row parallelism).

    The headline is **emitted tokens per compiled step**: legacy decode
    is exactly 1 per sequence per step, speculation emits the accepted
    run + 1 correction token per verify window. That ratio is the
    TPU-relevant receipt — a decode step is memory-bandwidth-bound on
    real hardware, so streaming the weights once per WINDOW instead of
    once per token is the win; the CPU CI box is compute-bound and
    pays the full window FLOPs, so wall-clock tokens/s is recorded as
    context but the gate rides the step-count ratio. Both legs must
    stay token-identical to ``reference_decode`` (the functional gate)
    with a positive accept rate.

    The compounded legs (ISSUE 18) ride the same prompt set:
    ``tree`` serves a width x ``spec_k`` token TREE verified in one
    compiled step, drafted by the jitted on-device ``ModelDrafter``;
    ``int8`` compounds the tree leg onto int8 weight stores for BOTH
    drafter and target (gated token-identical to the dequantized
    reference). Every leg's ``tokens_per_step`` counts compiled TARGET
    steps only — draft-side dispatches are accounted separately as
    ``draft_steps`` (and tree commit dispatches increment neither), so
    the ratio stays the weights-streamed-once-per-window receipt.

    Returns a dict with per-leg tokens_per_sec/tokens_per_step/steps/
    draft_steps/accept_rate, the tokens-per-step speedups (spec vs
    legacy, tree vs the linear-k leg) and identity."""
    from paddle_tpu import serving

    cfg = serving.GenerationConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=max_seq_len)
    model = serving.GenerationModel.random(cfg, seed=0)
    rng = np.random.RandomState(11)
    prompts = [(rng.randint(0, vocab, size=pattern_len).tolist()) * reps
               for _ in range(n_requests)]
    refs = [serving.reference_decode(model, p, max_new_tokens)
            for p in prompts]

    def run_leg(k, tree=None, mdl=model, rf=refs, drafter=None):
        eng = serving.ServingEngine(mdl, max_batch=max_batch,
                                    max_seq_len=max_seq_len,
                                    block_size=block_size,
                                    prefill_chunk=chunk, spec_k=k,
                                    spec_tree=tree, drafter=drafter)
        # priming request: pays the one-time XLA compile for every
        # step shape this leg dispatches
        eng.generate(prompts[0][:3], max_new_tokens=2, timeout=600)
        base = eng.stats()["default"]
        t0 = time.perf_counter()
        outs = [eng.generate(p, max_new_tokens=max_new_tokens,
                             timeout=600) for p in prompts]
        wall = time.perf_counter() - t0
        st = eng.stats()["default"]
        eng.close()
        gen = st["generated_tokens"] - base["generated_tokens"]
        steps = st["steps"] - base["steps"]
        return {
            "outputs_match": outs == rf,
            "tokens_per_sec": sum(len(o) for o in outs) / wall,
            "tokens_per_step": gen / max(1, steps),
            "steps": steps,
            "draft_steps": (st["spec_draft_steps"]
                            - base["spec_draft_steps"]),
            "accept_rate": st["spec_accept_rate"],
        }

    legacy = run_leg(0)
    spec = run_leg(spec_k)
    tree_shape = "2x%d" % spec_k
    tree = run_leg(0, tree=tree_shape,
                   drafter=serving.ModelDrafter(model))
    qmodel = model.quantized()
    qrefs = [serving.reference_decode(qmodel, p, max_new_tokens)
             for p in prompts]
    int8 = run_leg(0, tree=tree_shape, mdl=qmodel, rf=qrefs,
                   drafter=serving.ModelDrafter(qmodel))
    return {
        "legacy": legacy,
        "spec": spec,
        "tree": tree,
        "int8": int8,
        "tree_shape": tree_shape,
        "tokens_per_step_speedup": (spec["tokens_per_step"]
                                    / legacy["tokens_per_step"]),
        "tree_speedup_vs_linear": (tree["tokens_per_step"]
                                   / spec["tokens_per_step"]),
        "accept_rate": spec["accept_rate"],
        "outputs_match": (legacy["outputs_match"]
                          and spec["outputs_match"]
                          and tree["outputs_match"]
                          and int8["outputs_match"]),
    }


def bench_serving_fleet(n_requests=16, max_new_tokens=16, max_batch=4,
                        vocab=256, d_model=64, n_heads=2, n_layers=2,
                        d_ff=128, max_seq_len=128, block_size=16):
    """Fleet scaling receipt (docs/SERVING.md "Fleet & failover"): one
    deterministic request set through a 1-replica and a 2-replica
    ``ServingRouter`` on the same model (the replicas share the jitted
    step, so the pair pays one compile). ``max_batch`` is sized so the
    single replica is batch-capacity-bound — the fleet's win is
    aggregate batch slots plus a second worker thread. On a multi-core
    box the 2-replica leg approaches 2x (two engine threads release
    the GIL into XLA concurrently); a 1-core box serializes the two
    step streams and parity is the honest expectation — ci.sh's gate
    floor is core-aware for exactly that reason, and on real TPU pods
    each replica owns its own chip so the scaling is the product
    number. Outputs must stay token-identical to ``reference_decode``
    on BOTH legs (routing may never change what a request gets).

    Returns a dict with per-leg tokens_per_sec/outputs_match/
    replicas_used and the 1->2 scaling ratio."""
    from paddle_tpu import serving

    cfg = serving.GenerationConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=max_seq_len)
    model = serving.GenerationModel.random(cfg, seed=0)
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, vocab,
                           size=int(rng.randint(4, 12))).tolist()
               for _ in range(n_requests)]
    refs = [serving.reference_decode(model, p, max_new_tokens)
            for p in prompts]

    def run_leg(n_replicas):
        router = serving.ServingRouter(
            model, replicas=n_replicas, max_batch=max_batch,
            max_seq_len=max_seq_len, block_size=block_size)
        # one primer per replica, submitted concurrently so the
        # least-loaded dispatch lands one on each: pays the one-time
        # XLA compile outside the measured window
        primers = [router.submit([1, 2], max_new_tokens=2)
                   for _ in range(n_replicas)]
        for p in primers:
            p.wait(600)
        t0 = time.perf_counter()
        reqs = [router.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        outs = [r.wait(600) for r in reqs]
        wall = time.perf_counter() - t0
        st = router.stats()
        router.close()
        return {
            "tokens_per_sec": sum(len(o) for o in outs) / wall,
            "outputs_match": outs == refs,
            "replicas_used": sum(
                1 for r in st["replicas"]
                if r["model:default"]["steps"] > 0),
            "failovers": st["failovers"],
            "shed_requests": st["shed_requests"],
        }

    one = run_leg(1)
    two = run_leg(2)
    return {
        "one": one,
        "two": two,
        "scaling": two["tokens_per_sec"] / one["tokens_per_sec"],
        "outputs_match": one["outputs_match"] and two["outputs_match"],
    }


def bench_serving_online(n_requests=24, max_new_tokens=12, vocab=64,
                         max_seq_len=32, max_batch=4, block_size=4):
    """Online hot-swap receipt (docs/SERVING.md "Online updates"): one
    deterministic request set through a 2-replica fleet twice — once
    steady-state, once with an ``OnlineUpdater`` publishing and rolling
    a new weight version across the fleet mid-stream (drain -> swap ->
    undrain, one replica at a time). The rollout leg's throughput ratio
    is the measured cost of a live weight push; the functional gates are
    absolute: zero requests lost, and every output token-identical to
    ``reference_decode`` under the weight version that actually served
    it (the router latches ``weight_version`` at dispatch, so the
    mid-stream swap may never mix versions inside one request).

    Returns per-leg tokens/s, the rollout/steady ratio, the version
    ledger receipts, and the identity/loss gates."""
    import os
    import shutil
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu import checkpoint as _ckpt
    from paddle_tpu import inference, serving
    from paddle_tpu.models import transformer_fluid

    base = tempfile.mkdtemp(prefix="ptpu_bench_online_")
    try:
        prog, sprog = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, sprog):
            transformer_fluid.build(vocab_size=vocab, d_model=16,
                                    n_heads=2, n_layers=1, d_ff=32,
                                    seq_len=8, remat=False)
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(sprog, scope=scope)
        v0_dir = os.path.join(base, "v0")
        inference.export_generation_model(v0_dir, prog, scope,
                                          max_seq_len=max_seq_len)
        ckpt_dir = os.path.join(base, "ckpts")
        pub_dir = os.path.join(base, "pub")
        os.makedirs(ckpt_dir)
        rng = np.random.RandomState(31)
        prompts = [rng.randint(0, vocab,
                               size=int(rng.randint(3, 8))).tolist()
                   for _ in range(n_requests)]
        state = {}
        for name, value in scope.items():
            v = np.asarray(value)
            if np.issubdtype(v.dtype, np.floating):
                v = v + rng.normal(0, 0.02, v.shape).astype(v.dtype)
            state[name] = v
        with serving.ServingRouter(v0_dir, replicas=2,
                                   max_batch=max_batch,
                                   max_seq_len=max_seq_len,
                                   block_size=block_size,
                                   backoff_base=0.0,
                                   health_interval_s=0.02) as router:
            # canary_pct=None: unconditional rollout — the canary gate
            # has its own receipt in ci.sh's online stage; this leg
            # measures the swap machinery's throughput cost
            upd = serving.OnlineUpdater(router, ckpt_dir, pub_dir, prog,
                                        max_seq_len=max_seq_len,
                                        canary_pct=None)
            # primers: one per replica, concurrently, so the one-time
            # XLA compile lands outside both measured windows
            for p in [router.submit([1, 2], max_new_tokens=2)
                      for _ in range(2)]:
                p.wait(600)

            def run_leg(rollout_mid_stream):
                t0 = time.perf_counter()
                reqs = [router.submit(p, max_new_tokens=max_new_tokens)
                        for p in prompts]
                roll = None
                if rollout_mid_stream:
                    roll = threading.Thread(target=upd.poll_once,
                                            name="bench-online-rollout")
                    roll.start()
                outs = [r.wait(600) for r in reqs]
                wall = time.perf_counter() - t0
                if roll is not None:
                    roll.join()
                return (outs, [r.weight_version for r in reqs], wall)

            steady_outs, steady_vers, steady_wall = run_leg(False)
            _ckpt.save_checkpoint(ckpt_dir, state, 1)
            roll_outs, roll_vers, roll_wall = run_leg(True)
            st = router.stats()
        models = {0: inference.load_generation_model(v0_dir),
                  1: inference.load_generation_model(
                      os.path.join(pub_dir, "v1"))}
        match = all(
            o == serving.reference_decode(models[v], p, max_new_tokens)
            for o, v, p in zip(steady_outs + roll_outs,
                               steady_vers + roll_vers,
                               prompts + prompts))
        steady_tps = sum(len(o) for o in steady_outs) / steady_wall
        roll_tps = sum(len(o) for o in roll_outs) / roll_wall
        return {
            "steady_tokens_per_sec": steady_tps,
            "rollout_tokens_per_sec": roll_tps,
            "rollout_throughput_ratio": roll_tps / steady_tps,
            "outputs_match": match,
            "requests_lost": (st["requests_submitted"]
                              - st["requests_completed"]
                              - st["requests_failed"]),
            "versions_published": upd.versions_published,
            "swaps": upd.swaps,
            "final_versions": sorted(
                r["weight_version"] for r in st["replicas"]),
        }
    finally:
        shutil.rmtree(base, ignore_errors=True)


def bench_zero(steps=16, warmup=4, repeats=3, depth=4, width=256,
               batch=64, bucket_mb=0.5):
    """ZeRO ladder + comm/compute overlap receipt (docs/ZERO.md) on the
    8-device CPU mesh: ONE 4-layer tanh MLP trained through every rung —
    per-leaf ZeRO-1 (the trajectory anchor), bucketed ZeRO-1 with overlap
    OFF (the exact PR-5 path), ZeRO-2 with overlap ON, ZeRO-3, and
    host-offloaded m/v. The headline gate is the STEP-TIME overlap
    receipt: overlapped bucketed step <= the non-overlapped PR-5 step.
    The two legs are measured INTERLEAVED (overlap/no-overlap rounds
    alternate) with the best-of-`repeats` round kept per leg, so a load
    spike on a shared box hits both legs, not one.

    Numerics gates ride along: every rung's trained parameters must
    match the bucketed ZeRO-1 leg within float tolerance and every
    leg's loss must be finite and decreasing. (The BITWISE pins live in
    tests/test_zero.py on fusion-stable problems — on a deep model the
    per-rung module shapes fuse the backward dots differently, ~1 ulp
    per step, which Adam's normalization then amplifies; a bitwise gate
    here would pin XLA's fusion choices, not the ZeRO math.)

    Returns a dict of per-leg step times/losses + the receipt fields."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.parallel import ShardedAdam

    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError("bench_zero needs 8 devices (run under "
                           "xla_force_host_platform_device_count=8)")
    mesh = Mesh(np.array(devs[:8]).reshape(8), ["dp"])
    rng = np.random.RandomState(0)
    layers = [((rng.normal(size=(width, width)) * 0.05).astype(np.float32),
               np.zeros((width,), np.float32)) for _ in range(depth)]
    x = np.asarray(rng.normal(size=(batch, width)), np.float32)
    y = np.asarray(rng.normal(size=(batch, width)), np.float32)

    def fresh():
        import jax.numpy as jnp

        return [(jnp.asarray(w), jnp.asarray(b)) for w, b in layers]

    def loss_fn(p, x, y):
        h = x
        for w, b in p:
            h = jnp.tanh(h @ w + b)
        return jnp.mean((h - y) ** 2)

    class Leg:
        def __init__(self, name, opt):
            self.name, self.opt = name, opt
            self.p = fresh()
            self.st = opt.init_state(self.p, mesh)
            if (opt._plan or {}).get("stage") == 3:
                self.p = opt.shard_params(self.p, mesh)
            self.step = opt.make_step(mesh, loss_fn)
            self.losses = []
            self.times = []

        def run(self, n, timed=True):
            t0 = _time.perf_counter()
            for _ in range(n):
                self.p, self.st, l = self.step(self.p, self.st, x, y)
            self.losses.append(float(l))  # the leg's one sync point
            if timed:
                self.times.append((_time.perf_counter() - t0) / n)

        def params(self):
            if (self.opt._plan or {}).get("stage") == 3:
                return self.opt.gather_params(self.p)
            return self.p

    kw = dict(learning_rate=1e-3, axis_name="dp", bucket_mb=bucket_mb)
    legs = {
        "zero1_per_leaf": Leg("zero1_per_leaf", ShardedAdam(
            learning_rate=1e-3, axis_name="dp")),
        "zero1_bucketed": Leg("zero1_bucketed", ShardedAdam(**kw)),
        "zero2_overlap": Leg("zero2_overlap", ShardedAdam(
            zero_stage=2, overlap=True, **kw)),
        "zero3": Leg("zero3", ShardedAdam(
            zero_stage=3, overlap=True, **kw)),
        "zero_offload": Leg("zero_offload", ShardedAdam(
            offload=True, **kw)),
    }
    for leg in legs.values():
        leg.run(warmup, timed=False)
    # every leg runs the same schedule (the numeric comparisons need
    # identical step counts), interleaved so a load spike on a shared
    # box hits all legs, best-of-`repeats` kept per leg
    for _ in range(repeats):
        for leg in legs.values():
            leg.run(steps)

    t_no = min(legs["zero1_bucketed"].times)
    t_ov = min(legs["zero2_overlap"].times)

    def flat(leg):
        return np.concatenate([np.ravel(np.asarray(a))
                               for pair in leg.params() for a in pair])

    anchor = flat(legs["zero1_bucketed"])

    def close(name):
        return bool(np.allclose(flat(legs[name]), anchor,
                                rtol=5e-2, atol=5e-3))

    legs["zero_offload"].step.close()  # release the stager worker
    return {
        "step_time_no_overlap_s": t_no,
        "step_time_overlap_s": t_ov,
        "overlap_speedup": t_no / t_ov,
        "step_time_per_leaf_s": min(legs["zero1_per_leaf"].times),
        "step_time_zero3_s": min(legs["zero3"].times),
        "step_time_offload_s": min(legs["zero_offload"].times),
        "zero2_close": close("zero2_overlap"),
        "zero3_close": close("zero3"),
        "offload_close": close("zero_offload"),
        "losses": {name: leg.losses[-1] for name, leg in legs.items()},
        "loss_decreasing": all(leg.losses[-1] < leg.losses[0]
                               for leg in legs.values()),
    }


def bench_quant_predictor(batches=24, batch=64, in_dim=64, hidden=256,
                          n_classes=16, warmup=3):
    """fp32-vs-int8 predictor receipt (docs/QUANTIZATION.md): one MLP
    classifier exported through save_inference_model, served three ways
    — plain fp32 AnalysisPredictor, full_int8 (calibrate -> quant_rewrite
    int8 execution), and weight_only (convert_to_int8's int8 store).
    Reported: examples/s fp32 vs int8, the numerics receipt
    (max-abs-err of the logits + top-1 agreement vs fp32 — the
    documented CI bound), and the weight-store receipt
    (bytes saved / fp32 bytes >= 0.4 is the acceptance gate; int8 twins
    plus per-channel fp32 scales land ~0.74 on this model).

    Returns a dict of per-leg numbers."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import inference, quant

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = fluid.layers.data(name="qb_x", shape=[in_dim],
                              dtype="float32")
        h = fluid.layers.fc(input=x, size=hidden, act="relu")
        h = fluid.layers.fc(input=h, size=hidden, act="relu")
        logits = fluid.layers.fc(input=h, size=n_classes)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog)
    outdir = tempfile.mkdtemp(prefix="ptpu_quant_bench_")
    try:
        fluid.io.save_inference_model(outdir, ["qb_x"], [logits], exe,
                                      main_program=prog)
        exe.close()
        rng = np.random.RandomState(0)
        eval_feeds = [rng.uniform(-1, 1, (batch, in_dim))
                      .astype(np.float32) for _ in range(batches)]

        cfg = inference.AnalysisConfig(outdir)
        cfg.disable_gpu()
        p_fp32 = inference.AnalysisPredictor(cfg)
        table = quant.calibrate(
            p_fp32._program, ({"qb_x": f} for f in eval_feeds[:4]),
            scope=p_fp32._scope)

        cfg8 = inference.AnalysisConfig(outdir)
        cfg8.disable_gpu()
        cfg8.enable_quantize("full_int8",
                             calibration_table=table)
        p_int8 = inference.AnalysisPredictor(cfg8)

        # weight-store receipt from the weight_only predictor: its
        # private scope holds the int8 twins INSTEAD of the fp32 copies
        cfgw = inference.AnalysisConfig(outdir)
        cfgw.disable_gpu()
        cfgw.enable_quantize("weight_only")
        p_wo = inference.AnalysisPredictor(cfgw)
        fp32_bytes = saved_bytes = 0
        for name in table.weights:
            w = np.asarray(p_fp32._scope.get(name))
            q = p_wo._scope.get(name + ".int8")
            if q is None:
                continue
            fp32_bytes += w.nbytes
            saved_bytes += w.nbytes - np.asarray(q).nbytes
        saved_ratio = saved_bytes / fp32_bytes if fp32_bytes else 0.0

        def run_leg(pred):
            for f in eval_feeds[:warmup]:
                pred.run_dict({"qb_x": f})
            outs = []
            t0 = time.perf_counter()
            for f in eval_feeds:
                out, = pred.run_dict({"qb_x": f})
                outs.append(np.asarray(out))
            dt = time.perf_counter() - t0
            return batches * batch / dt, outs

        fp32_eps, fp32_outs = run_leg(p_fp32)
        int8_eps, int8_outs = run_leg(p_int8)
        max_err = max(float(np.abs(a - b).max())
                      for a, b in zip(fp32_outs, int8_outs))
        agree = float(np.mean([
            np.argmax(a, axis=1) == np.argmax(b, axis=1)
            for a, b in zip(fp32_outs, int8_outs)]))
        return {
            "fp32_examples_per_sec": fp32_eps,
            "int8_examples_per_sec": int8_eps,
            "speedup_vs_fp32": int8_eps / fp32_eps,
            "max_abs_err": max_err,
            "top1_agreement": agree,
            "weight_bytes_saved_ratio": saved_ratio,
        }
    finally:
        shutil.rmtree(outdir, ignore_errors=True)


def bench_serving_quant(n_requests=16, max_new_tokens=16, max_batch=8,
                        vocab=256, d_model=64, n_heads=2, n_layers=2,
                        d_ff=128, max_seq_len=128):
    """Quantized serving receipt (docs/QUANTIZATION.md): the SAME
    deterministic request set decoded through a continuously-batched
    engine twice — fp32 weights vs the weight-only-int8 store
    (`GenerationModel.quantized()`). Gates: the int8 leg must be
    token-identical to `reference_decode` over its own dequantized
    weights (its fp32 reference — greedy decode is deterministic, the
    int8 store may never change what the STEP computes), and the
    per-token agreement vs the plain-fp32 leg is reported as the
    quantization-noise receipt. Aggregate tokens/s per leg is the
    throughput receipt (`bench/serving_tokens_per_sec_int8`).

    Returns (int8_tps, fp32_tps, int8_matches_reference,
    token_agreement_vs_fp32, total_tokens)."""
    from paddle_tpu import serving

    cfg = serving.GenerationConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=max_seq_len)
    model = serving.GenerationModel.random(cfg, seed=0)
    qmodel = model.quantized()
    specs = serving.PoissonLoadGenerator(
        1e9, n_requests, prompt_len=(4, 12),
        max_new_tokens=max_new_tokens, vocab_size=vocab,
        seed=0).make_requests()

    def run_leg(m):
        eng = serving.ServingEngine(m, max_batch=max_batch,
                                    max_seq_len=max_seq_len,
                                    block_size=16)
        eng.generate([1, 2], max_new_tokens=2, timeout=600)  # compile
        t0 = time.perf_counter()
        reqs = [eng.submit(s["prompt"],
                           max_new_tokens=s["max_new_tokens"])
                for s in specs]
        outs = [r.wait(600) for r in reqs]
        dt = time.perf_counter() - t0
        eng.close()
        return sum(len(o) for o in outs) / dt, outs

    fp32_tps, fp32_outs = run_leg(model)
    int8_tps, int8_outs = run_leg(qmodel)
    refs = [serving.reference_decode(qmodel, s["prompt"],
                                     s["max_new_tokens"])
            for s in specs]
    matches_ref = int8_outs == refs
    agree_n = agree_d = 0
    for a, b in zip(int8_outs, fp32_outs):
        for ta, tb in zip(a, b):
            agree_n += int(ta == tb)
            agree_d += 1
    agreement = agree_n / max(agree_d, 1)
    return (int8_tps, fp32_tps, matches_ref, agreement,
            sum(len(o) for o in int8_outs))


def bench_kernels(repeats=30, warmup=3):
    """Per-kernel dispatch receipts (docs/KERNELS.md): each Pallas
    kernel timed against its own lax fallback on the SAME inputs —
    paged flash-decode vs the contiguous block-table gather, the spec
    verify window (C=4) vs the same gathered reference, and the fused
    int8 matmul vs the unfused quantize->dot->dequantize chain. Off-TPU
    the kernels run in the Pallas interpreter: the run checks parity
    only and reports no time, because an interpreter timing says nothing
    about the kernel.

    Returns {kernel: {max_err}} plus {pallas_s, lax_s, speedup} on TPU."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import device as ptpu_device
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    on_tpu = ptpu_device.on_tpu()

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))  # compile + result
        if not on_tpu:
            return None, out
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / repeats, out

    def receipt(t_pallas, t_lax, got, want):
        out = {"max_err": float(jnp.max(jnp.abs(got - want)))}
        if on_tpu:
            out.update(pallas_s=t_pallas, lax_s=t_lax,
                       speedup=t_lax / max(t_pallas, 1e-12))
        return out

    results = {}

    # paged attention: decode window (C=1) and spec verify window (C=4)
    NB, bs, H, Dh, B, Mb = 64, 16, 4, 64, 8, 8
    # a one-layer pool, [L, NB + 1, bs, H, Dh]: the kernels take the
    # pool whole
    k_pool = jnp.asarray(rng.randn(1, NB + 1, bs, H, Dh)
                         .astype(np.float32))
    v_pool = jnp.asarray(rng.randn(1, NB + 1, bs, H, Dh)
                         .astype(np.float32))
    tables = jnp.asarray(
        rng.permutation(NB)[:B * Mb].reshape(B, Mb).astype(np.int32) + 1)
    pallas_fn = jax.jit(functools.partial(pk.paged_attention, layer=0))
    lax_fn = jax.jit(functools.partial(pk.paged_attention_reference,
                                       layer=0))
    for name, C in (("paged_decode", 1), ("spec_window", 4)):
        q = jnp.asarray(rng.randn(B, C, H, Dh).astype(np.float32))
        pos = jnp.asarray(
            np.tile(np.arange(Mb * bs - C, Mb * bs, dtype=np.int32),
                    (B, 1)))
        t_pallas, got = timed(pallas_fn, k_pool, v_pool, q, tables, pos)
        t_lax, want = timed(lax_fn, k_pool, v_pool, q, tables, pos)
        results[name] = receipt(t_pallas, t_lax, got, want)

    # fused int8 matmul vs the unfused chain (bitwise-identical)
    M, K, N = 256, 512, 512
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray(rng.randint(-128, 128, (K, N)).astype(np.int8))
    dq = jnp.asarray((rng.rand(N).astype(np.float32) + 0.1) / 127.0)
    act = float(127.0 / 3.0)
    t_pallas, got = timed(
        jax.jit(pk.int8_matmul, static_argnums=3), x, w, dq, act)
    t_lax, want = timed(
        jax.jit(pk.int8_matmul_reference, static_argnums=3),
        x, w, dq, act)
    results["int8_matmul"] = receipt(t_pallas, t_lax, got, want)
    return results


def _fusion_receipt():
    """One forward-only fc+relu program through CompiledProgram with
    fuse_elewise_add_act_ops on: the bias add + relu collapse into a
    fused_elemwise_activation, putting traffic on compiler/ops_fused
    (the CI bench-smoke asserts the counter)."""
    import paddle_tpu as fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = fluid.layers.data(name="fr_x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        out = fluid.layers.reduce_mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog)
    bs = fluid.compiler.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    cp = fluid.compiler.CompiledProgram(prog).with_data_parallel(
        build_strategy=bs)
    exe.run(cp, feed={"fr_x": np.ones((4, 16), np.float32)},
            fetch_list=[out])
    exe.close()


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--metrics-out", metavar="bench_metrics.json",
                    default=None,
                    help="also write the result through the observability "
                         "metrics registry as a JSON dump (the BENCH_*.json "
                         "trajectory becomes reproducible from the "
                         "framework's own telemetry)")
    ap.add_argument("--legs-out", metavar="bench_legs.json", default=None,
                    help="write a machine-readable per-leg JSON array "
                         "(leg name, tokens/s, step time, loss) so "
                         "fp32 and AMP legs are recorded separately")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--tiny", action="store_true",
                    help="toy-size rehearsal: toy model + host feeds "
                         "through the background prefetcher, on whatever "
                         "device jax finds (the CI bench-smoke "
                         "configuration). Without it every leg except "
                         "the --zero-only CPU-mesh dry run needs a TPU")
    ap.add_argument("--sync-only", action="store_true",
                    help="skip the async leg (debug aid)")
    ap.add_argument("--amp-only", action="store_true",
                    help="run only the fp32-vs-AMP leg pair (the CI amp "
                         "stage configuration)")
    ap.add_argument("--serving-only", action="store_true",
                    help="run only the continuous-batching serving leg "
                         "pair (the CI serve stage configuration)")
    ap.add_argument("--spec-only", action="store_true",
                    help="run only the speculative-decoding serving "
                         "pair (spec_k on vs off on the repetitive-"
                         "generation set)")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run only the serving-fleet scaling pair "
                         "(1-replica vs 2-replica ServingRouter, the "
                         "CI fleet stage configuration)")
    ap.add_argument("--online-only", action="store_true",
                    help="run only the online weight-hot-swap leg pair "
                         "(steady-state vs mid-stream rollout through "
                         "an OnlineUpdater, the CI online stage "
                         "configuration)")
    ap.add_argument("--zero-only", action="store_true",
                    help="run only the ZeRO/overlap ladder on the "
                         "8-device CPU mesh (the CI zero stage "
                         "configuration)")
    ap.add_argument("--quant-only", action="store_true",
                    help="run only the int8 quantization legs — the "
                         "fp32-vs-int8 predictor pair and the "
                         "weight-only-int8 serving pair (the CI quant "
                         "stage configuration)")
    ap.add_argument("--data-only", action="store_true",
                    help="run only the streaming-ingestion leg pair "
                         "(healthy vs one-quarantined-shard records/s "
                         "— the CI data-chaos stage configuration)")
    ap.add_argument("--rec-only", action="store_true",
                    help="run only the recommender fast-path legs "
                         "(sync vs overlapped prefetch vs prefetch + "
                         "hot-row cache on a host-table DeepFM, gated "
                         "bitwise-identical — the CI rec stage "
                         "configuration)")
    ap.add_argument("--kernels-only", action="store_true",
                    help="run only the Pallas kernel receipts — each "
                         "kernel vs its own lax fallback (paged "
                         "decode, spec verify window, fused int8 "
                         "matmul; CPU floor gates, TPU real margins)")
    ap.add_argument("--resilience", action="store_true",
                    help="also measure guarded vs unguarded step time "
                         "(always on under --tiny)")
    args = ap.parse_args(argv)

    from paddle_tpu.core import device as ptpu_device

    if args.zero_only:
        # the ZeRO ladder is a dry run on an 8-device virtual CPU mesh,
        # set up BEFORE the first device query: it never touches the chip
        from xla_env import use_host_mesh

        use_host_mesh(8)
    if args.zero_only or args.tiny:
        # a dry run or toy-size rehearsal: runs wherever jax runs and
        # says where
        device = ptpu_device.identity()
    else:
        device = ptpu_device.require_tpu("bench.py without --tiny")

    def emit(result):
        """Print one result line; every result names its device."""
        result["device"] = device._asdict()
        print(json.dumps(result))

    if args.kernels_only:
        res = bench_kernels()
        # timed on the chip only; off-TPU the receipts hold max_err alone
        speedups = {name: round(r["speedup"], 4)
                    for name, r in res.items() if "speedup" in r}
        if args.metrics_out:
            from paddle_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            for name, r in res.items():
                reg.gauge("bench/kernel_%s_max_err" % name).set(
                    r["max_err"])
            for name, x in speedups.items():
                reg.gauge("bench/kernel_%s_speedup" % name).set(x)
            reg.dump_json(args.metrics_out)
        if args.legs_out:
            legs = []
            for name, r in res.items():
                leg = {"leg": "kernel_" + name, "max_err": r["max_err"]}
                if name in speedups:
                    leg.update({"pallas_s": round(r["pallas_s"], 6),
                                "lax_s": round(r["lax_s"], 6),
                                "kernel_%s_speedup" % name: speedups[name]})
                legs.append(leg)
            with open(args.legs_out, "w") as f:
                json.dump(legs, f, indent=2)
        out = {"metric": "kernel_parity",
               "unit": "max abs error, pallas kernel vs its lax fallback",
               "value": {name: r["max_err"] for name, r in res.items()}}
        if speedups:
            out["kernel_speedups"] = speedups
            out["kernel_speedups_unit"] = \
                "x (lax fallback time / pallas kernel time)"
        emit(out)
        return

    if args.data_only:
        res = bench_data_ingestion()
        if args.metrics_out:
            from paddle_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.gauge("bench/data_records_per_sec_healthy").set(
                res["healthy_records_per_sec"])
            reg.gauge("bench/data_records_per_sec_degraded").set(
                res["degraded_records_per_sec"])
            reg.gauge("bench/data_degraded_throughput_ratio").set(
                res["degraded_throughput_ratio"])
            reg.gauge("bench/data_records_lost").set(
                res["records_lost"])
            reg.dump_json(args.metrics_out)
        if args.legs_out:
            with open(args.legs_out, "w") as f:
                json.dump([
                    {"leg": "data_healthy",
                     "records_per_sec": round(
                         res["healthy_records_per_sec"], 1),
                     "records": res["healthy_records"]},
                    {"leg": "data_degraded",
                     "records_per_sec": round(
                         res["degraded_records_per_sec"], 1),
                     "records": res["degraded_records"],
                     "data_degraded_throughput_ratio": round(
                         res["degraded_throughput_ratio"], 4)},
                ], f, indent=2)
        emit({
            "metric": "data_degraded_throughput_ratio",
            "value": round(res["degraded_throughput_ratio"], 4),
            "unit": "x (degraded / healthy records-per-sec)",
            "records_per_sec_healthy": round(
                res["healthy_records_per_sec"], 1),
            "records_per_sec_degraded": round(
                res["degraded_records_per_sec"], 1),
            "records_lost": res["records_lost"],
        })
        return

    if args.rec_only:
        res = bench_recommender()
        if args.metrics_out:
            from paddle_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.gauge("bench/rec_examples_per_sec_sync").set(
                res["sync_examples_per_sec"])
            reg.gauge("bench/rec_examples_per_sec_overlap").set(
                res["overlap_examples_per_sec"])
            reg.gauge("bench/rec_examples_per_sec_cache").set(
                res["cache_examples_per_sec"])
            reg.gauge("bench/rec_overlap_speedup").set(
                res["overlap_speedup"])
            reg.gauge("bench/rec_cache_hit_rate").set(
                res["cache_hit_rate"])
            reg.gauge("bench/rec_bitwise_identical").set(
                1.0 if res["bitwise_identical"] else 0.0)
            reg.dump_json(args.metrics_out)
        if args.legs_out:
            with open(args.legs_out, "w") as f:
                json.dump([
                    {"leg": "rec_sync",
                     "examples_per_sec": round(
                         res["sync_examples_per_sec"], 1)},
                    {"leg": "rec_overlap",
                     "examples_per_sec": round(
                         res["overlap_examples_per_sec"], 1),
                     "rec_overlap_speedup": round(
                         res["overlap_speedup"], 4),
                     "prefetch_hits": res["prefetch_hits"]},
                    {"leg": "rec_overlap_cache",
                     "examples_per_sec": round(
                         res["cache_examples_per_sec"], 1),
                     "rec_cache_hit_rate": round(
                         res["cache_hit_rate"], 4),
                     "cache_hits": res["cache_hits"],
                     "bitwise_identical": bool(
                         res["bitwise_identical"])},
                ], f, indent=2)
        emit({
            "metric": "rec_overlap_speedup",
            "value": round(res["overlap_speedup"], 4),
            "unit": "x (overlapped-prefetch / synchronous examples-"
                    "per-sec, bitwise-identical numerics)",
            "examples_per_sec_sync": round(
                res["sync_examples_per_sec"], 1),
            "examples_per_sec_overlap": round(
                res["overlap_examples_per_sec"], 1),
            "examples_per_sec_cache": round(
                res["cache_examples_per_sec"], 1),
            "cache_hit_rate": round(res["cache_hit_rate"], 4),
            "bitwise_identical": res["bitwise_identical"],
            "final_loss": res["final_loss"],
        })
        return

    if args.online_only:
        res = bench_serving_online()
        if args.metrics_out:
            from paddle_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.gauge("bench/online_tokens_per_sec_steady").set(
                res["steady_tokens_per_sec"])
            reg.gauge("bench/online_tokens_per_sec_rollout").set(
                res["rollout_tokens_per_sec"])
            reg.gauge("bench/online_rollout_throughput_ratio").set(
                res["rollout_throughput_ratio"])
            reg.gauge("bench/online_outputs_match").set(
                1.0 if res["outputs_match"] else 0.0)
            reg.gauge("bench/online_requests_lost").set(
                res["requests_lost"])
            reg.gauge("bench/online_versions_published").set(
                res["versions_published"])
            reg.gauge("bench/online_swaps").set(res["swaps"])
            reg.dump_json(args.metrics_out)
        if args.legs_out:
            with open(args.legs_out, "w") as f:
                json.dump([
                    {"leg": "online_steady",
                     "tokens_per_sec": round(
                         res["steady_tokens_per_sec"], 1),
                     "outputs_match": bool(res["outputs_match"])},
                    {"leg": "online_rollout",
                     "tokens_per_sec": round(
                         res["rollout_tokens_per_sec"], 1),
                     "outputs_match": bool(res["outputs_match"]),
                     "online_rollout_throughput_ratio": round(
                         res["rollout_throughput_ratio"], 4),
                     "requests_lost": res["requests_lost"],
                     "swaps": res["swaps"],
                     "final_versions": res["final_versions"]},
                ], f, indent=2)
        emit({
            "metric": "online_rollout_throughput_ratio",
            "value": round(res["rollout_throughput_ratio"], 4),
            "unit": "x (mid-rollout / steady-state serving tokens/s)",
            "tokens_per_sec_steady": round(
                res["steady_tokens_per_sec"], 1),
            "tokens_per_sec_rollout": round(
                res["rollout_tokens_per_sec"], 1),
            "outputs_match": res["outputs_match"],
            "requests_lost": res["requests_lost"],
            "versions_published": res["versions_published"],
        })
        return

    if args.zero_only:
        res = bench_zero()
        if args.metrics_out:
            from paddle_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.gauge("bench/zero_step_time_no_overlap").set(
                res["step_time_no_overlap_s"])
            reg.gauge("bench/zero_step_time_overlap").set(
                res["step_time_overlap_s"])
            reg.gauge("bench/zero_overlap_speedup").set(
                res["overlap_speedup"])
            reg.gauge("bench/zero_step_time_per_leaf").set(
                res["step_time_per_leaf_s"])
            reg.gauge("bench/zero_step_time_zero3").set(
                res["step_time_zero3_s"])
            reg.gauge("bench/zero_step_time_offload").set(
                res["step_time_offload_s"])
            reg.gauge("bench/zero2_close").set(
                1.0 if res["zero2_close"] else 0.0)
            reg.gauge("bench/zero3_close").set(
                1.0 if res["zero3_close"] else 0.0)
            reg.gauge("bench/zero_offload_close").set(
                1.0 if res["offload_close"] else 0.0)
            reg.gauge("bench/zero_losses_decreasing").set(
                1.0 if res["loss_decreasing"] else 0.0)
            for name, loss in res["losses"].items():
                reg.gauge("bench/%s_last_loss" % name).set(loss)
            reg.dump_json(args.metrics_out)
        if args.legs_out:
            zlegs = [{"leg": name,
                      "step_time_s": round(res["step_time_%s_s"
                                           % key], 6),
                      "last_loss": res["losses"][name]}
                     for name, key in
                     (("zero1_per_leaf", "per_leaf"),
                      ("zero1_bucketed", "no_overlap"),
                      ("zero2_overlap", "overlap"),
                      ("zero3", "zero3"),
                      ("zero_offload", "offload"))]
            zlegs[2]["overlap_speedup"] = round(
                res["overlap_speedup"], 4)
            with open(args.legs_out, "w") as f:
                json.dump(zlegs, f, indent=2)
        emit({
            "metric": "zero_overlap_speedup",
            "value": round(res["overlap_speedup"], 4),
            "unit": "x (non-overlapped / overlapped step time)",
            "step_time_overlap_s": round(res["step_time_overlap_s"], 6),
            "step_time_no_overlap_s": round(
                res["step_time_no_overlap_s"], 6),
            "zero2_close": res["zero2_close"],
            "zero3_close": res["zero3_close"],
            "offload_close": res["offload_close"],
        })
        return

    if args.tiny:
        kw = dict(TINY)
        kw["feed_mode"] = "host"
    else:
        kw = dict(steps=args.steps, warmup=args.warmup)

    legs = []

    def _leg(name, tps, step_s, loss=None, **extra):
        entry = {"leg": name, "tokens_per_sec": round(tps, 1),
                 "step_time_s": round(step_s, 6)}
        if loss is not None:
            entry["last_loss"] = float(loss)
        entry.update(extra)
        legs.append(entry)
        return entry

    sync_tps = sync_step = None
    async_tps = async_step = None
    noopt_tps = noopt_step = None
    compile_opt = compile_noopt = None
    hlo_opt = hlo_noopt = None
    last_loss = None
    if args.serving_only or args.quant_only or args.spec_only \
            or args.fleet_only:
        args.amp_only = False  # dedicated leg: skip everything else
    if not args.amp_only and not args.serving_only \
            and not args.quant_only and not args.spec_only \
            and not args.fleet_only:
        if not args.sync_only:
            async_tps, last_loss, async_step, _ = bench_transformer_fluid(
                async_exec=True, **kw)
            _leg("async", async_tps, async_step, last_loss,
                 **_mfu_extra(_current_step_flops(),
                              1.0 / async_step if async_step else 0))
        hlo0 = _stablehlo_bytes()
        sync_tps, last_loss_sync, sync_step, compile_opt = \
            bench_transformer_fluid(async_exec=False, **kw)
        _leg("sync", sync_tps, sync_step, last_loss_sync,
             **_mfu_extra(_current_step_flops(),
                          1.0 / sync_step if sync_step else 0))
        hlo1 = _stablehlo_bytes()
        # the PTPU_NO_PROGRAM_OPT=1 leg: identical program through the
        # exact pre-pass-pipeline lowering path — its compile time, module
        # size and throughput are the optimization pipeline's
        # before/after receipt
        noopt_tps, _, noopt_step, compile_noopt = bench_transformer_fluid(
            async_exec=False, program_opt=False, **kw)
        _leg("noopt", noopt_tps, noopt_step)
        hlo2 = _stablehlo_bytes()
        hlo_opt = (hlo1 - hlo0) if hlo0 is not None else None
        hlo_noopt = (hlo2 - hlo1) if hlo0 is not None else None
        if hlo0 is not None:
            # metrics are on: pay the extra compile only when its counter
            # (compiler/ops_fused) actually lands in a dump
            _fusion_receipt()
        if last_loss is None:
            last_loss = last_loss_sync

    # AMP receipt (docs/MIXED_PRECISION.md): the SAME fp32 transformer
    # config trained plain and through paddle_tpu.amp.decorate — the
    # bf16 dtype-rewrite's tokens/s/chip win is recorded per leg, fp32 and
    # AMP separately. The tiny
    # bench-smoke run skips the pair (ci.sh's dedicated `amp` stage
    # already pays the identical tiny pair via --amp-only).
    fp32_tps = amp_tps = fp32_step = amp_step = None
    fp32_loss = amp_loss = None
    if args.amp_only or not (args.tiny or args.serving_only
                             or args.quant_only or args.spec_only
                             or args.fleet_only):
        fp32_tps, fp32_loss, fp32_step, _ = bench_transformer_fluid(
            async_exec=False, dtype="float32", amp=False, **kw)
        _leg("fp32", fp32_tps, fp32_step, fp32_loss,
             **_mfu_extra(_current_step_flops(),
                          1.0 / fp32_step if fp32_step else 0))
        amp_tps, amp_loss, amp_step, _ = bench_transformer_fluid(
            async_exec=False, dtype="float32", amp=True, **kw)
        _leg("amp", amp_tps, amp_step, amp_loss,
             speedup_vs_fp32=round(amp_tps / fp32_tps, 4),
             **_mfu_extra(_current_step_flops(),
                          1.0 / amp_step if amp_step else 0))

    # continuous-batching serving receipt (docs/SERVING.md): batched vs
    # serial aggregate tokens/s on the same Poisson stream + identity
    serve_batched = serve_serial = serve_match = None
    serve_p50 = serve_p99 = serve_tokens = None
    if args.serving_only or not (args.tiny or args.amp_only
                                 or args.quant_only or args.spec_only
                                 or args.fleet_only):
        (serve_batched, serve_serial, serve_match, serve_p50,
         serve_p99, serve_tokens, serve_sps,
         serve_flops) = bench_serving()
        _leg("serving_batched", serve_batched, 0.0,
             p50_latency_s=round(serve_p50, 4),
             p99_latency_s=round(serve_p99, 4),
             outputs_match=bool(serve_match),
             **_mfu_extra(serve_flops, serve_sps))
        _leg("serving_serial", serve_serial, 0.0,
             speedup_batched_vs_serial=round(
                 serve_batched / serve_serial, 4))

    # serving fast-path receipt (docs/SERVING.md): chunked prefill +
    # radix prefix caching on one shared-system-prompt stream
    fastpath_res = None
    if args.serving_only or not (args.tiny or args.amp_only
                                 or args.quant_only or args.spec_only
                                 or args.fleet_only):
        fastpath_res = bench_serving_fastpath()
        _leg("serving_fastpath", fastpath_res["fast"]["tokens_per_sec"],
             0.0,
             ttft_p50_s=round(fastpath_res["fast"]["ttft_p50"], 4),
             prefix_hit_rate=round(fastpath_res["prefix_hit_rate"], 4),
             outputs_match=bool(fastpath_res["outputs_match"]))

    # speculative-decoding receipt (docs/SERVING.md): draft-k verified
    # in one step vs legacy one-token decode on the repetitive set —
    # emitted tokens per compiled step is the headline
    spec_res = None
    if args.spec_only or args.serving_only \
            or not (args.tiny or args.amp_only or args.quant_only
                    or args.fleet_only):
        spec_res = bench_serving_spec()
        _leg("serving_spec", spec_res["spec"]["tokens_per_sec"], 0.0,
             tokens_per_step=round(spec_res["spec"]["tokens_per_step"],
                                   4),
             accept_rate=round(spec_res["accept_rate"], 4),
             outputs_match=bool(spec_res["outputs_match"]))
        _leg("serving_spec_baseline",
             spec_res["legacy"]["tokens_per_sec"], 0.0,
             tokens_per_step=round(
                 spec_res["legacy"]["tokens_per_step"], 4),
             spec_tokens_per_step_speedup=round(
                 spec_res["tokens_per_step_speedup"], 4))
        _leg("serving_spec_tree", spec_res["tree"]["tokens_per_sec"],
             0.0,
             tokens_per_step=round(
                 spec_res["tree"]["tokens_per_step"], 4),
             draft_steps=spec_res["tree"]["draft_steps"],
             accept_rate=round(spec_res["tree"]["accept_rate"], 4),
             tree_shape=spec_res["tree_shape"],
             tree_speedup_vs_linear=round(
                 spec_res["tree_speedup_vs_linear"], 4),
             outputs_match=bool(spec_res["tree"]["outputs_match"]))
        _leg("serving_spec_int8", spec_res["int8"]["tokens_per_sec"],
             0.0,
             tokens_per_step=round(
                 spec_res["int8"]["tokens_per_step"], 4),
             draft_steps=spec_res["int8"]["draft_steps"],
             accept_rate=round(spec_res["int8"]["accept_rate"], 4),
             outputs_match=bool(spec_res["int8"]["outputs_match"]))

    # int8 quantization receipt (docs/QUANTIZATION.md): fp32-vs-int8
    # predictor numerics + throughput + weight-store shrink, and the
    # weight-only-int8 serving leg gated token-identical against its
    # fp32 reference
    quant_res = None
    qserve_int8 = qserve_fp32 = qserve_match = None
    qserve_agree = qserve_tokens = None
    if args.quant_only or not (args.tiny or args.amp_only
                               or args.serving_only or args.spec_only
                               or args.fleet_only):
        quant_res = bench_quant_predictor()
        _leg("quant_fp32_predictor",
             quant_res["fp32_examples_per_sec"], 0.0)
        _leg("quant_int8_predictor",
             quant_res["int8_examples_per_sec"], 0.0,
             speedup_vs_fp32=round(quant_res["speedup_vs_fp32"], 4),
             max_abs_err=round(quant_res["max_abs_err"], 6),
             top1_agreement=round(quant_res["top1_agreement"], 4),
             weight_bytes_saved_ratio=round(
                 quant_res["weight_bytes_saved_ratio"], 4))
        (qserve_int8, qserve_fp32, qserve_match, qserve_agree,
         qserve_tokens) = bench_serving_quant()
        _leg("serving_fp32_ref", qserve_fp32, 0.0)
        _leg("serving_int8", qserve_int8, 0.0,
             speedup_vs_fp32=round(qserve_int8 / qserve_fp32, 4),
             outputs_match=bool(qserve_match),
             token_agreement=round(qserve_agree, 4))

    # serving-fleet receipt (docs/SERVING.md "Fleet & failover"):
    # 1-replica vs 2-replica router on one request set — aggregate
    # tokens/s scaling plus routed-output identity
    fleet_res = None
    if args.fleet_only or not (args.tiny or args.amp_only
                               or args.serving_only or args.quant_only
                               or args.spec_only):
        fleet_res = bench_serving_fleet()
        _leg("serving_fleet_1r", fleet_res["one"]["tokens_per_sec"], 0.0,
             outputs_match=bool(fleet_res["one"]["outputs_match"]),
             replicas_used=fleet_res["one"]["replicas_used"])
        _leg("serving_fleet_2r", fleet_res["two"]["tokens_per_sec"], 0.0,
             outputs_match=bool(fleet_res["two"]["outputs_match"]),
             replicas_used=fleet_res["two"]["replicas_used"],
             fleet_scaling=round(fleet_res["scaling"], 4))

    headline = async_tps if async_tps is not None else \
        (sync_tps if sync_tps is not None else
         (amp_tps if amp_tps is not None else
          (serve_batched if serve_batched is not None else
           (qserve_int8 if qserve_int8 is not None else
            (spec_res["spec"]["tokens_per_sec"]
             if spec_res is not None else
             fleet_res["two"]["tokens_per_sec"])))))
    if last_loss is None:
        last_loss = amp_loss

    # resilience-overhead leg (docs/RESILIENCE.md): the guard's cost is
    # measured, not assumed — acceptance is < 5% on the tiny config
    guarded = unguarded = overhead_pct = None
    if (args.resilience or args.tiny) and not (args.amp_only
                                               or args.serving_only
                                               or args.quant_only
                                               or args.spec_only
                                               or args.fleet_only):
        unguarded, guarded = bench_resilience_overhead()
        overhead_pct = 100.0 * (guarded - unguarded) / unguarded

    if args.metrics_out:
        # explicit registry use is an opt-in — no PTPU_METRICS needed;
        # the executor's own step/compile telemetry (when enabled) shares
        # the same process-wide registry and lands in the same dump
        from paddle_tpu.observability import metrics as obs_metrics

        reg = obs_metrics.registry()
        reg.gauge("bench/tokens_per_sec_per_chip").set(headline)
        reg.gauge("bench/vs_baseline").set(
            headline / BASELINE_TOKENS_PER_SEC)
        if last_loss is not None:  # --serving-only trains nothing
            reg.gauge("bench/last_loss").set(last_loss)
        reg.counter("bench/steps").inc(kw.get("steps", args.steps))
        if sync_tps is not None:  # --amp-only skips the headline legs
            reg.gauge("bench/step_time_sync").set(sync_step)
            reg.gauge("bench/tokens_per_sec_sync").set(sync_tps)
        if async_tps is not None:
            reg.gauge("bench/step_time_async").set(async_step)
            reg.gauge("bench/tokens_per_sec_async").set(async_tps)
        if compile_opt is not None:  # --warmup 0: no cold call measured
            reg.gauge("bench/compile_time_s_opt").set(compile_opt)
        if compile_noopt is not None:
            reg.gauge("bench/compile_time_s_noopt").set(compile_noopt)
        if noopt_tps is not None:
            reg.gauge("bench/tokens_per_sec_noopt").set(noopt_tps)
        if amp_tps is not None:  # pair skipped on the tiny smoke run
            reg.gauge("bench/tokens_per_sec_fp32").set(fp32_tps)
            reg.gauge("bench/tokens_per_sec_amp").set(amp_tps)
            reg.gauge("bench/amp_speedup_vs_fp32").set(amp_tps / fp32_tps)
            reg.gauge("bench/amp_last_loss").set(amp_loss)
            reg.gauge("bench/fp32_last_loss").set(fp32_loss)
        if hlo_opt is not None:
            reg.gauge("bench/stablehlo_bytes_opt").set(hlo_opt)
            reg.gauge("bench/stablehlo_bytes_noopt").set(hlo_noopt)
        if guarded is not None:
            reg.gauge("bench/step_time_guarded").set(guarded)
            reg.gauge("bench/step_time_unguarded").set(unguarded)
            reg.gauge("bench/guard_overhead_pct").set(overhead_pct)
        if quant_res is not None:
            reg.gauge("bench/quant_examples_per_sec_fp32").set(
                quant_res["fp32_examples_per_sec"])
            reg.gauge("bench/quant_examples_per_sec_int8").set(
                quant_res["int8_examples_per_sec"])
            reg.gauge("bench/quant_speedup_vs_fp32").set(
                quant_res["speedup_vs_fp32"])
            reg.gauge("bench/quant_max_abs_err").set(
                quant_res["max_abs_err"])
            reg.gauge("bench/quant_top1_agreement").set(
                quant_res["top1_agreement"])
            reg.gauge("bench/quant_weight_bytes_saved_ratio").set(
                quant_res["weight_bytes_saved_ratio"])
        if qserve_int8 is not None:
            reg.gauge("bench/serving_tokens_per_sec_int8").set(
                qserve_int8)
            reg.gauge("bench/serving_tokens_per_sec_fp32_ref").set(
                qserve_fp32)
            reg.gauge("bench/serving_int8_speedup_vs_fp32").set(
                qserve_int8 / qserve_fp32)
            reg.gauge("bench/serving_int8_outputs_match").set(
                1.0 if qserve_match else 0.0)
            reg.gauge("bench/serving_int8_token_agreement").set(
                qserve_agree)
            reg.gauge("bench/serving_int8_total_tokens").set(
                qserve_tokens)
        if serve_batched is not None:
            reg.gauge("bench/serving_tokens_per_sec_batched").set(
                serve_batched)
            reg.gauge("bench/serving_tokens_per_sec_serial").set(
                serve_serial)
            reg.gauge("bench/serving_speedup_vs_serial").set(
                serve_batched / serve_serial)
            reg.gauge("bench/serving_outputs_match").set(
                1.0 if serve_match else 0.0)
            reg.gauge("bench/serving_p50_latency_s").set(serve_p50)
            reg.gauge("bench/serving_p99_latency_s").set(serve_p99)
            reg.gauge("bench/serving_total_tokens").set(serve_tokens)
        if fastpath_res is not None:
            reg.gauge("bench/serving_ttft_chunked_s").set(
                fastpath_res["fast"]["ttft_p50"])
            reg.gauge("bench/serving_prefix_hit_rate").set(
                fastpath_res["prefix_hit_rate"])
            reg.gauge("bench/serving_fastpath_outputs_match").set(
                1.0 if fastpath_res["outputs_match"] else 0.0)
        if fleet_res is not None:
            reg.gauge("bench/serving_fleet_tokens_per_sec_1r").set(
                fleet_res["one"]["tokens_per_sec"])
            reg.gauge("bench/serving_fleet_tokens_per_sec_2r").set(
                fleet_res["two"]["tokens_per_sec"])
            reg.gauge("bench/serving_fleet_scaling").set(
                fleet_res["scaling"])
            reg.gauge("bench/serving_fleet_outputs_match").set(
                1.0 if fleet_res["outputs_match"] else 0.0)
            reg.gauge("bench/serving_fleet_replicas_used").set(
                fleet_res["two"]["replicas_used"])
        if spec_res is not None:
            reg.gauge("bench/serving_spec_tokens_per_step").set(
                spec_res["spec"]["tokens_per_step"])
            reg.gauge("bench/serving_spec_speedup").set(
                spec_res["tokens_per_step_speedup"])
            reg.gauge("bench/serving_spec_accept_rate").set(
                spec_res["accept_rate"])
            reg.gauge("bench/serving_spec_outputs_match").set(
                1.0 if spec_res["outputs_match"] else 0.0)
            reg.gauge("bench/serving_spec_tokens_per_sec").set(
                spec_res["spec"]["tokens_per_sec"])
            reg.gauge("bench/serving_spec_baseline_tokens_per_sec").set(
                spec_res["legacy"]["tokens_per_sec"])
            reg.gauge("bench/serving_spec_tree_tokens_per_step").set(
                spec_res["tree"]["tokens_per_step"])
            reg.gauge("bench/serving_spec_tree_speedup").set(
                spec_res["tree_speedup_vs_linear"])
            reg.gauge("bench/serving_spec_tree_accept_rate").set(
                spec_res["tree"]["accept_rate"])
            reg.gauge("bench/serving_spec_int8_outputs_match").set(
                1.0 if spec_res["int8"]["outputs_match"] else 0.0)
        reg.dump_json(args.metrics_out)
    if args.legs_out:
        # machine-readable per-leg record (ISSUE 5): the fp32 and AMP
        # legs separately from the headline
        with open(args.legs_out, "w") as f:
            json.dump(legs, f, indent=2)
    result = {
        "metric": "transformer_base_tokens_per_sec_per_chip",
        "value": round(headline, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(headline / BASELINE_TOKENS_PER_SEC, 4),
    }
    if amp_tps is not None:
        result["fp32_tokens_per_sec"] = round(fp32_tps, 1)
        result["amp_tokens_per_sec"] = round(amp_tps, 1)
        result["amp_speedup_vs_fp32"] = round(amp_tps / fp32_tps, 4)
    if sync_tps is not None:
        result["sync_tokens_per_sec"] = round(sync_tps, 1)
        result["step_time_sync_s"] = round(sync_step, 6)
    if noopt_tps is not None:
        result["noopt_tokens_per_sec"] = round(noopt_tps, 1)
    if compile_opt is not None:  # --warmup 0: no cold call measured
        result["compile_time_s_opt"] = round(compile_opt, 3)
    if compile_noopt is not None:
        result["compile_time_s_noopt"] = round(compile_noopt, 3)
    if hlo_opt is not None:
        result["stablehlo_bytes_opt"] = int(hlo_opt)
        result["stablehlo_bytes_noopt"] = int(hlo_noopt)
    if async_tps is not None:
        result["async_tokens_per_sec"] = round(async_tps, 1)
        result["step_time_async_s"] = round(async_step, 6)
    if guarded is not None:
        result["step_time_guarded_s"] = round(guarded, 6)
        result["step_time_unguarded_s"] = round(unguarded, 6)
        result["guard_overhead_pct"] = round(overhead_pct, 2)
    if quant_res is not None:
        result["quant_int8_examples_per_sec"] = round(
            quant_res["int8_examples_per_sec"], 1)
        result["quant_speedup_vs_fp32"] = round(
            quant_res["speedup_vs_fp32"], 4)
        result["quant_max_abs_err"] = round(quant_res["max_abs_err"], 6)
        result["quant_top1_agreement"] = round(
            quant_res["top1_agreement"], 4)
        result["quant_weight_bytes_saved_ratio"] = round(
            quant_res["weight_bytes_saved_ratio"], 4)
    if qserve_int8 is not None:
        result["serving_tokens_per_sec_int8"] = round(qserve_int8, 1)
        result["serving_int8_speedup_vs_fp32"] = round(
            qserve_int8 / qserve_fp32, 4)
        result["serving_int8_outputs_match"] = bool(qserve_match)
    if serve_batched is not None:
        result["serving_tokens_per_sec_batched"] = round(serve_batched, 1)
        result["serving_tokens_per_sec_serial"] = round(serve_serial, 1)
        result["serving_speedup_vs_serial"] = round(
            serve_batched / serve_serial, 4)
        result["serving_p99_latency_s"] = round(serve_p99, 4)
        result["serving_outputs_match"] = bool(serve_match)
    if fastpath_res is not None:
        result["serving_ttft_chunked_s"] = round(
            fastpath_res["fast"]["ttft_p50"], 4)
        result["serving_prefix_hit_rate"] = round(
            fastpath_res["prefix_hit_rate"], 4)
        result["serving_fastpath_outputs_match"] = bool(
            fastpath_res["outputs_match"])
    if fleet_res is not None:
        result["serving_fleet_tokens_per_sec_1r"] = round(
            fleet_res["one"]["tokens_per_sec"], 1)
        result["serving_fleet_tokens_per_sec_2r"] = round(
            fleet_res["two"]["tokens_per_sec"], 1)
        result["serving_fleet_scaling"] = round(fleet_res["scaling"], 4)
        result["serving_fleet_outputs_match"] = bool(
            fleet_res["outputs_match"])
    if spec_res is not None:
        result["serving_spec_tokens_per_step"] = round(
            spec_res["spec"]["tokens_per_step"], 4)
        result["serving_spec_speedup"] = round(
            spec_res["tokens_per_step_speedup"], 4)
        result["serving_spec_accept_rate"] = round(
            spec_res["accept_rate"], 4)
        result["serving_spec_outputs_match"] = bool(
            spec_res["outputs_match"])
        result["serving_spec_tree_tokens_per_step"] = round(
            spec_res["tree"]["tokens_per_step"], 4)
        result["serving_spec_tree_speedup"] = round(
            spec_res["tree_speedup_vs_linear"], 4)
        result["serving_spec_tree_draft_steps"] = int(
            spec_res["tree"]["draft_steps"])
        result["serving_spec_int8_outputs_match"] = bool(
            spec_res["int8"]["outputs_match"])
    emit(result)


if __name__ == "__main__":
    main()
