"""Continuous-batching generation serving runtime (docs/SERVING.md):
KV block pool, iteration-level scheduler, multi-model ServingEngine,
artifact export — plus the round-5 satellite regressions
(_ResidLayout float64 refusal, global_shuffle failed-exchange restore).
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.serving import (AdmissionError, GenerationConfig,
                                GenerationModel, KVBlockPool,
                                PoissonLoadGenerator, RequestQueue,
                                blocks_needed, reference_decode)

CFG = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
           max_seq_len=64)


def tiny_model(seed=0, name="model", **overrides):
    cfg = dict(CFG, **overrides)
    return GenerationModel.random(GenerationConfig(**cfg), seed=seed,
                                  name=name)


# one shared model for the engine tests that don't care about trace
# accounting — every ServingEngine over it reuses the compiled step
_SHARED = {}


def shared_model():
    if "m" not in _SHARED:
        _SHARED["m"] = tiny_model()
    return _SHARED["m"]


# ---------------------------------------------------------------------------
# KV block pool
# ---------------------------------------------------------------------------


def test_blocks_needed():
    assert blocks_needed(0, 16) == 0
    assert blocks_needed(1, 16) == 1
    assert blocks_needed(16, 16) == 1
    assert blocks_needed(17, 16) == 2


def test_pool_alloc_free_reuse_and_null_block():
    pool = KVBlockPool(n_layers=1, n_heads=1, head_dim=4, block_size=4,
                       num_blocks=6)
    assert pool.k.shape == (1, 7, 4, 1, 4)  # +1 null block
    assert pool.reserve("a", 2) and pool.reserve("b", 3)
    ids_a = [pool.alloc_block("a"), pool.alloc_block("a")]
    ids_b = [pool.alloc_block("b") for _ in range(3)]
    all_ids = ids_a + ids_b
    assert len(set(all_ids)) == 5
    assert KVBlockPool.NULL_BLOCK not in all_ids  # never handed out
    assert pool.block_table("a") == ids_a  # table preserves alloc order
    assert pool.blocks_in_use == 5
    # reservation exhausted -> loud failure, not silent overdraw
    with pytest.raises(RuntimeError):
        pool.alloc_block("a")
    # pool nearly full: a 2-block reservation must be refused
    assert not pool.reserve("c", 2)
    assert pool.reserve("c", 1)
    pool.free_owner("c")
    # free returns blocks for reuse
    assert pool.free_owner("a") == 2
    assert pool.blocks_in_use == 3
    assert pool.reserve("d", 3)
    got = {pool.alloc_block("d") for _ in range(3)}
    assert got & set(ids_a)  # freed blocks recycle
    stats = pool.stats()
    assert stats["blocks_total"] == 6
    assert stats["blocks_in_use"] == 6
    assert stats["utilization"] == 1.0


def test_pool_reservation_counts_against_free():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=4)
    assert pool.reserve("a", 3)
    # 3 reserved but unallocated: only 1 block is really available
    assert pool.blocks_free == 1
    assert not pool.reserve("b", 2)
    assert pool.reserve("b", 1)


# ---------------------------------------------------------------------------
# engine: correctness (the acceptance pin)
# ---------------------------------------------------------------------------


def _prompts(n, vocab, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=rng.randint(2, 9)).tolist()
            for _ in range(n)]


def test_batched_decode_token_identical_to_unbatched():
    """8 concurrent requests through a 4-slot continuously-batched
    engine produce EXACTLY the tokens of (a) the unpaged unbatched
    numpy reference decoder and (b) a serial max_batch=1 engine."""
    model = shared_model()
    prompts = _prompts(8, model.config.vocab_size)
    max_new = 12

    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4) as eng:
        reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        batched = [eng.result(r, timeout=120) for r in reqs]

    refs = [reference_decode(model, p, max_new) for p in prompts]
    assert batched == refs

    with serving.ServingEngine(model, max_batch=1, max_seq_len=64,
                               block_size=4) as eng1:
        serial = [eng1.generate(p, max_new_tokens=max_new, timeout=120)
                  for p in prompts]
    assert serial == refs


def test_block_tables_vs_contiguous_reference():
    """Paged-gather correctness at the step level: drive the raw decode
    step with a hand-built scattered block table and compare per-step
    logits against the contiguous-cache reference forward."""
    import jax.numpy as jnp

    model = tiny_model(seed=3)
    cfg = model.config
    bs, mb = 4, 4  # block_size, blocks per seq -> ctx 16
    step = model.make_decode_step(1, mb, return_logits=True)
    nb = 8
    kv_shape = (cfg.n_layers, nb + 1, bs, cfg.n_heads, cfg.head_dim)
    kv_k = jnp.zeros(kv_shape, jnp.float32)
    kv_v = jnp.zeros(kv_shape, jnp.float32)
    # deliberately non-contiguous, non-monotone physical blocks
    table = np.array([[5, 2, 7, 3]], np.int32)

    tokens = [9, 33, 2, 41, 17, 8, 60, 5, 11, 30]
    got_logits = []
    prev = jnp.zeros((1,), jnp.int32)
    for pos, tok in enumerate(tokens):
        kv_k, kv_v, prev, logits = step(
            model.weights, kv_k, kv_v,
            np.array([tok], np.int32), np.array([True]),
            prev, np.array([pos], np.int32), table, np.array([True]))
        got_logits.append(np.asarray(logits)[0])

    # reference: teacher-force the same tokens through the numpy
    # contiguous-cache decoder, capturing argmax tokens per position
    ref_next = reference_decode(model, tokens, 1)
    # the decode path's prediction after the full prompt must agree
    assert int(np.argmax(got_logits[-1])) == ref_next[0]
    # and every intermediate step must be finite and vocab-shaped
    assert all(l.shape == (cfg.vocab_size,) and np.isfinite(l).all()
               for l in got_logits)


def test_eos_stops_early_and_truncates():
    model = shared_model()
    prompt = [3, 7, 11, 2]
    ref = reference_decode(model, prompt, 16)
    eos = ref[5]  # force an early stop at the 6th generated token
    ref_eos = reference_decode(model, prompt, 16, eos_id=eos)
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        got = eng.generate(prompt, max_new_tokens=16, eos_id=eos,
                           timeout=120)
    assert got == ref_eos
    assert got[-1] == eos and len(got) <= 16
    assert eos not in got[:-1]


# ---------------------------------------------------------------------------
# scheduler: shape stability + admission control
# ---------------------------------------------------------------------------


def test_no_retrace_across_join_and_retire():
    """Sequences joining and retiring at step boundaries never change
    the compiled steps' shapes: exactly TWO traces (the mixed window
    and the decode step) for the whole staggered workload."""
    model = tiny_model(seed=5)
    assert model.trace_count == 0
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4) as eng:
        # staggered: different prompt lengths, different max_new, new
        # requests arriving while earlier ones are mid-decode
        first = [eng.submit([1, 2, 3], max_new_tokens=10),
                 eng.submit([4] * 7, max_new_tokens=3)]
        first[1].wait(120)
        late = [eng.submit([9, 8], max_new_tokens=6),
                eng.submit([5, 6, 7, 8, 9], max_new_tokens=8)]
        for r in first + late:
            r.wait(120)
    assert model.trace_count == 2


def test_queue_admission_control():
    q = RequestQueue(max_queue=2)
    q.submit(serving.GenerationRequest([1]))
    q.submit(serving.GenerationRequest([2]))
    with pytest.raises(AdmissionError):
        q.submit(serving.GenerationRequest([3]))
    assert len(q) == 2


def test_oversized_request_rejected_up_front():
    model = shared_model()
    with serving.ServingEngine(model, max_batch=1, max_seq_len=32,
                               block_size=4, num_blocks=4) as eng:
        # needs ceil(24/4)=6 blocks but the pool holds 4 total
        with pytest.raises(AdmissionError):
            eng.submit([1] * 8, max_new_tokens=16)
        # a fitting request still serves
        assert eng.generate([1, 2], max_new_tokens=4, timeout=120)


def test_too_long_prompt_fails_the_request():
    from paddle_tpu.observability import metrics as obs

    model = shared_model()
    was_enabled = obs.enabled()
    obs.enable()
    before = obs.registry().counter("serving/requests_failed").value
    try:
        with serving.ServingEngine(model, max_batch=1, max_seq_len=16,
                                   block_size=4) as eng:
            req = eng.submit(list(range(2, 20)), max_new_tokens=2)
            with pytest.raises(ValueError):
                req.wait(120)
    finally:
        if not was_enabled:
            obs.disable()
    # accepted-then-errored requests are accounted (submitted =
    # completed + failed once the engine drains)
    assert obs.registry().counter("serving/requests_failed").value \
        == before + 1


def test_head_of_line_blocking_preserves_order():
    """A big head request that doesn't fit the pool must NOT be jumped
    by a small one behind it (no starvation)."""
    model = shared_model()
    pool = KVBlockPool(model.config.n_layers, model.config.n_heads,
                       model.config.head_dim, block_size=4, num_blocks=7)
    sched = serving.StepScheduler(2, pool, max_seq_len=24)
    q = RequestQueue(8)
    big = serving.GenerationRequest([1] * 8, max_new_tokens=16)  # 6 blocks
    small = serving.GenerationRequest([1, 2], max_new_tokens=2)  # 1 block
    # a live sequence holds 3 of the 6 blocks
    assert pool.reserve("live", 3)
    q.submit(big)
    q.submit(small)
    assert sched.admit(q) == []  # big doesn't fit; small must wait
    assert q.peek() is big
    pool.free_owner("live")
    admitted = sched.admit(q)
    assert [s.request for s in admitted] == [big, small]


# ---------------------------------------------------------------------------
# streaming + load generator
# ---------------------------------------------------------------------------


def test_streaming_callbacks_in_order():
    model = shared_model()
    seen = []
    done_flags = []

    def cb(request, token, finished):
        seen.append(token)
        done_flags.append(finished)

    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        req = eng.submit([2, 4, 6], max_new_tokens=7, stream=cb)
        tokens = eng.result(req, timeout=120)
    assert seen == tokens
    assert done_flags == [False] * (len(tokens) - 1) + [True]


def test_poisson_loadgen_deterministic_and_serves():
    gen = PoissonLoadGenerator(rate=500.0, n_requests=5,
                               prompt_len=(2, 5), max_new_tokens=(3, 6),
                               vocab_size=CFG["vocab_size"], seed=11)
    a = gen.make_requests()
    b = PoissonLoadGenerator(rate=500.0, n_requests=5, prompt_len=(2, 5),
                             max_new_tokens=(3, 6),
                             vocab_size=CFG["vocab_size"],
                             seed=11).make_requests()
    assert a == b  # reproducible stream
    model = shared_model()
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4) as eng:
        accepted, rejected = gen.run(eng)
        outs = [r.wait(120) for r in accepted]
    assert not rejected
    assert [len(o) for o in outs] == [s["max_new_tokens"] for s in a]


# ---------------------------------------------------------------------------
# multi-model isolation
# ---------------------------------------------------------------------------


def test_multi_model_isolated_scopes():
    ma = tiny_model(seed=0, name="a")
    mb = tiny_model(seed=1, name="b")
    prompt = [5, 9, 2]
    ref_a = reference_decode(ma, prompt, 6)
    ref_b = reference_decode(mb, prompt, 6)
    assert ref_a != ref_b  # different weights, different generations
    with serving.ServingEngine({"a": ma, "b": mb}, max_batch=2,
                               max_seq_len=64, block_size=4) as eng:
        assert sorted(eng.model_names) == ["a", "b"]
        got_a = eng.generate(prompt, max_new_tokens=6, model="a",
                             timeout=120)
        got_b = eng.generate(prompt, max_new_tokens=6, model="b",
                             timeout=120)
        assert got_a == ref_a and got_b == ref_b
        # the scopes are distinct stores, one per model
        sa, sb = eng.model_scope("a"), eng.model_scope("b")
        assert sa is not sb
        assert not np.array_equal(np.asarray(sa.get("embedding")),
                                  np.asarray(sb.get("embedding")))
        # hot-swap through the scope surface: pointing b's scope at a's
        # weights must change what b serves (the step reads the scope
        # at every dispatch — weights are state, not baked constants)
        for name in list(ma.weights):
            sb.set(name, sa.get(name))
        assert eng.generate(prompt, max_new_tokens=6, model="b",
                            timeout=120) == ref_a


def test_unknown_model_rejected():
    with serving.ServingEngine(shared_model(), max_batch=1,
                               max_seq_len=32, block_size=4) as eng:
        with pytest.raises(KeyError):
            eng.submit([1, 2], model="nope")


# ---------------------------------------------------------------------------
# artifact export (inference.py -> serving)
# ---------------------------------------------------------------------------


def _build_fluid_program(vocab=96, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, seq_len=8):
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        toks, labs, loss = transformer_fluid.build(
            vocab_size=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, d_ff=d_ff, seq_len=seq_len, remat=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog, scope=scope)
    return prog, scope, exe, loss


def test_export_roundtrip_and_serve(tmp_path):
    from paddle_tpu import inference

    prog, scope, exe, _ = _build_fluid_program()
    cfg = inference.export_generation_model(str(tmp_path), prog, scope,
                                            max_seq_len=48)
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layers) == (96, 32, 2)
    model = inference.load_generation_model(str(tmp_path))
    ref = reference_decode(model, [5, 9, 2], 5)
    with serving.ServingEngine(str(tmp_path), max_batch=2,
                               max_seq_len=48, block_size=4) as eng:
        assert eng.generate([5, 9, 2], max_new_tokens=5,
                            timeout=120) == ref


def test_exported_weights_match_training_graph_numerics(tmp_path):
    """Teacher-forced cross-entropy computed from the serving decode
    path's logits must match the loss the TRAINING program computes for
    the same token row — pinning the weight extraction (layout, fused
    qkv repack, layer order) against the real Fluid graph."""
    import jax.numpy as jnp

    from paddle_tpu import inference

    seq_len, vocab = 8, 96
    prog, scope, exe, loss = _build_fluid_program(seq_len=seq_len,
                                                  vocab=vocab)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, vocab, (1, seq_len)).astype(np.int32)
    labs = np.roll(toks, -1, axis=1).astype(np.int32)
    (train_loss,) = exe.run(prog, feed={"tokens": toks, "labels": labs},
                            fetch_list=[loss], scope=scope)

    cfg = inference.export_generation_model(str(tmp_path), prog, scope,
                                            max_seq_len=32)
    model = inference.load_generation_model(str(tmp_path))
    step = model.make_decode_step(1, 8, return_logits=True)
    nb = 8
    kv_shape = (cfg.n_layers, nb + 1, 4, cfg.n_heads, cfg.head_dim)
    kv_k = jnp.zeros(kv_shape, jnp.float32)
    kv_v = jnp.zeros(kv_shape, jnp.float32)
    table = np.arange(1, 9, dtype=np.int32).reshape(1, 8)
    prev = jnp.zeros((1,), jnp.int32)
    ces = []
    for pos in range(seq_len):
        kv_k, kv_v, prev, logits = step(
            model.weights, kv_k, kv_v,
            np.array([toks[0, pos]], np.int32), np.array([True]), prev,
            np.array([pos], np.int32), table, np.array([True]))
        lg = np.asarray(logits, np.float64)[0]
        lse = np.log(np.sum(np.exp(lg - lg.max()))) + lg.max()
        ces.append(lse - lg[labs[0, pos]])
    assert np.isclose(float(np.mean(ces)),
                      float(np.asarray(train_loss).ravel()[0]),
                      rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# telemetry (the autoscaling surface)
# ---------------------------------------------------------------------------


def test_serving_metrics_surface():
    from paddle_tpu.observability import metrics as obs

    model = shared_model()
    was_enabled = obs.enabled()
    obs.enable()
    reg = obs.registry()
    done0 = reg.counter("serving/requests_completed").value
    lat0 = reg.histogram("serving/request_latency").count
    dec0 = reg.counter("serving/decode_tokens").value
    pre0 = reg.counter("serving/prefill_tokens").value
    prompts = _prompts(8, model.config.vocab_size)
    try:
        with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                                   block_size=4) as eng:
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            for r in reqs:
                r.wait(120)
    finally:
        if not was_enabled:
            obs.disable()
    assert reg.counter("serving/requests_completed").value - done0 == 8
    assert reg.gauge("serving/peak_batch_occupancy").value >= 2
    assert reg.histogram("serving/request_latency").count - lat0 == 8
    assert reg.gauge("serving/request_latency_p99").value > 0
    assert np.isfinite(reg.gauge("serving/request_latency_p99").value)
    assert reg.gauge("serving/tokens_per_sec").value > 0
    # a request's first token comes out of the window that consumed
    # the end of its prompt; the other seven are decode tokens
    assert reg.counter("serving/decode_tokens").value - dec0 == 8 * 7
    assert reg.counter("serving/prefill_tokens").value - pre0 \
        == sum(len(p) for p in prompts)


# ---------------------------------------------------------------------------
# satellite regressions (ADVICE round 5)
# ---------------------------------------------------------------------------


def test_resid_layout_rejects_float64():
    from paddle_tpu.parallel.pipeline_program import _ResidLayout

    with pytest.raises(NotImplementedError, match="float64"):
        _ResidLayout(treedef=None, avals=[((2, 2), np.float64)],
                     rebind=[None])
    # fp32 still packs
    layout = _ResidLayout(treedef=None, avals=[((2, 2), np.float32)],
                          rebind=[None])
    assert layout.nf == 4


def test_global_shuffle_restores_samples_on_failed_exchange(monkeypatch):
    from paddle_tpu import dataset_api, distributed_runtime

    class FakeFleet:
        def worker_index(self):
            return 0

        def worker_num(self):
            return 2

        def worker_endpoints(self):
            return ["127.0.0.1:1", "127.0.0.1:2"]

    ds = dataset_api.InMemoryDataset()
    samples = [[np.arange(3, dtype=np.int64) + i,
                np.float32(i)] for i in range(6)]
    ds._samples = [list(s) for s in samples]

    def boom(*a, **k):
        raise ConnectionError("peer died mid-exchange")

    monkeypatch.setattr(distributed_runtime, "exchange_samples", boom)
    with pytest.raises(ConnectionError):
        ds.global_shuffle(FakeFleet(), seed=3)
    # the dataset must still hold every pre-exchange sample (any order)
    assert ds._samples is not None and len(ds._samples) == 6
    got = sorted(float(s[1]) for s in ds._samples)
    assert got == [float(i) for i in range(6)]
    for s in ds._samples:
        i = int(s[1])
        np.testing.assert_array_equal(np.asarray(s[0]),
                                      np.arange(3, dtype=np.int64) + i)
