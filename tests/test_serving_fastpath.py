"""Serving fast path (ISSUE 11, docs/SERVING.md): chunked prefill +
radix prefix caching in the continuous-batching engine.

Covers the two tentpole legs and their satellites:
  * refcounted content-addressed KVBlockPool — sharing, LRU caching,
    eviction, the reservation-conservation invariant under sharing
    (``free + reserved + owned + shared == total``), and the
    shared-block-never-freed-while-referenced pin;
  * the chunked [max_batch, chunk] prefill step — staggered-arrival
    torture across chunk boundaries pinned token-identical to
    ``reference_decode`` with exactly TWO traces (one per step shape),
    and the per-step prefill token budget (decode-latency bound): what
    it is where nobody states it (four chunks up to the matmuls' ridge),
    the token rows the chunk step is compiled for at the benchmark's
    three geometries, and who gets it (the row admitted first);
  * the one planner — its plan sequence (mixed windows, then decode
    windows of one) and pool accounting pinned against an in-test
    oracle; what a default engine builds; a default engine prefills a
    long prompt in ceil(len / chunk) mixed steps;
  * TTFT telemetry (histogram + p50/p99 gauges).
"""

import json
import os
import threading

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                GenerationRequest, KVBlockPool,
                                RequestQueue, StepScheduler,
                                prefix_chain_keys, reference_decode)
from paddle_tpu.serving.scheduler import (PREFILL_BUDGET_RIDGE,
                                          default_prefill_token_budget)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
           max_seq_len=64)


def tiny_model(seed=0, name="model", **overrides):
    cfg = dict(CFG, **overrides)
    return GenerationModel.random(GenerationConfig(**cfg), seed=seed,
                                  name=name)


_SHARED = {}


def shared_model():
    if "m" not in _SHARED:
        _SHARED["m"] = tiny_model()
    return _SHARED["m"]


def _prompts(n, vocab, seed=7, lo=2, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _conserved(pool):
    """The two-phase no-deadlock invariant, refcount-sharing edition:
    every usable block is exactly one of free-or-cached (folded into
    ``blocks_free`` net of reservations), owned, or shared — and
    reservations never overdraw what is reclaimable."""
    st = pool.stats()
    assert (st["blocks_free"] + st["blocks_reserved"]
            + st["blocks_owned"] + st["blocks_shared"]
            == st["blocks_total"]), st
    assert st["blocks_free"] >= 0, st
    assert st["blocks_in_use"] == st["blocks_owned"] + st["blocks_shared"]
    assert st["blocks_cached"] >= 0
    return st


# ---------------------------------------------------------------------------
# prefix chain keys
# ---------------------------------------------------------------------------


def test_prefix_chain_keys_commit_to_content_and_chain():
    toks = list(range(1, 13))
    a = prefix_chain_keys(toks, 4)
    assert len(a) == 3  # only FULL blocks are keyed
    assert prefix_chain_keys(toks + [99], 4) == a  # partial tail ignored
    assert prefix_chain_keys(toks, 4) == a  # deterministic
    # same middle block behind a different first block -> different key
    b = prefix_chain_keys([7] + toks[1:], 4)
    assert b[0] != a[0] and b[1] != a[1] and b[2] != a[2]
    # the namespace (model) partitions the key space
    assert prefix_chain_keys(toks, 4, namespace="other") != a
    assert prefix_chain_keys(toks[:3], 4) == []  # no full block


# ---------------------------------------------------------------------------
# pool: refcounted sharing + conservation
# ---------------------------------------------------------------------------


def test_pool_shared_block_freed_only_at_refcount_zero():
    """Satellite pin: a shared block is never freed (or handed out)
    while a second owner's table still references it."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=4)
    keys = prefix_chain_keys(list(range(8)), 4)
    assert pool.reserve("a", 3)
    b1, b2 = pool.alloc_block("a"), pool.alloc_block("a")
    assert pool.seal_block(b1, keys[0]) and pool.seal_block(b2, keys[1])
    _conserved(pool)
    assert pool.reserve("b", 3, prefix_keys=keys)
    assert pool.block_table("b") == [b1, b2]  # adopted, table order
    st = _conserved(pool)
    assert st["blocks_shared"] == 2
    pool.free_owner("a")
    # b still references both: neither freed nor cached nor evictable
    st = _conserved(pool)
    assert st["blocks_shared"] == 0 and st["blocks_owned"] == 2
    assert st["blocks_cached"] == 0
    n_alloc = pool.blocks_free
    assert pool.reserve("c", n_alloc)
    got = [pool.alloc_block("c") for _ in range(n_alloc)]
    assert b1 not in got and b2 not in got
    pool.free_owner("b")
    st = _conserved(pool)
    assert st["blocks_cached"] == 2  # sealed blocks park on the LRU


def test_pool_cached_blocks_revive_and_evict_lru():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=4)
    keys = prefix_chain_keys(list(range(8)), 4)
    assert pool.reserve("a", 2)
    b1, b2 = pool.alloc_block("a"), pool.alloc_block("a")
    pool.seal_block(b1, keys[0])
    pool.seal_block(b2, keys[1])
    pool.free_owner("a")
    assert pool.blocks_cached == 2
    assert pool.blocks_free == 4  # cached blocks stay reclaimable
    # an identical prefix revives the cached blocks without compute
    assert pool.reserve("b", 3, prefix_keys=keys)
    assert pool.block_table("b") == [b1, b2]
    assert pool.blocks_cached == 0
    _conserved(pool)
    pool.free_owner("b")
    # allocation pressure evicts the LRU copies and drops the index
    assert pool.reserve("c", 4)
    got = [pool.alloc_block("c") for _ in range(4)]
    assert len(set(got)) == 4 and b1 in got and b2 in got
    assert pool.lookup_prefix(keys) == []  # index entries evicted
    _conserved(pool)


def test_pool_eviction_consumes_chains_tail_first():
    """LRU eviction must drop the DEEPEST cached chain block first: the
    longest-prefix-match walks head-first, so evicting the head would
    strand every still-cached successor as unmatchable dead entries
    (found in review, reproduced, fixed in free_owner)."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=6)
    keys = prefix_chain_keys(list(range(12)), 4)  # a 3-block chain
    assert pool.reserve("a", 3)
    bids = [pool.alloc_block("a") for _ in range(3)]
    for bid, key in zip(bids, keys):
        assert pool.seal_block(bid, key)
    pool.free_owner("a")
    assert pool.blocks_cached == 3
    # pressure for 4 blocks: 3 free + the chain's TAIL, not its head
    assert pool.reserve("b", 4)
    got = [pool.alloc_block("b") for _ in range(4)]
    assert bids[2] in got and bids[0] not in got and bids[1] not in got
    # the 2-block prefix stays matchable at the same memory cost
    assert pool.lookup_prefix(keys) == bids[:2]
    _conserved(pool)


def test_pool_adoption_revival_cannot_unback_reservations():
    """Reviving a cached block during adoption is charged against
    availability: an outstanding worst-case reservation can never be
    left unbacked (the no-deadlock invariant survives sharing)."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=4)
    keys = prefix_chain_keys(list(range(8)), 4)
    assert pool.reserve("a", 2)
    b1, b2 = pool.alloc_block("a"), pool.alloc_block("a")
    pool.seal_block(b1, keys[0])
    pool.seal_block(b2, keys[1])
    pool.free_owner("a")
    assert pool.reserve("B", 4)  # worst case: 2 free + 2 cached
    # adopting both cached blocks now would strand B's reservation
    assert not pool.reserve("C", 2, prefix_keys=keys)
    got = [pool.alloc_block("B") for _ in range(4)]
    assert len(set(got)) == 4  # B draws its whole reservation
    _conserved(pool)


def test_pool_seal_rules_and_flush():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=4)
    keys = prefix_chain_keys(list(range(8)), 4)
    assert not pool.seal_block(pool.NULL_BLOCK, keys[0])  # never null
    assert not pool.seal_block(3, keys[0])  # not live -> refused
    assert pool.reserve("a", 2)
    b1, b2 = pool.alloc_block("a"), pool.alloc_block("a")
    assert pool.seal_block(b1, keys[0])
    assert pool.seal_block(b1, keys[0])  # idempotent
    assert not pool.seal_block(b2, keys[0])  # first sealer wins
    assert pool.seal_block(b2, keys[1])
    pool.free_owner("a")
    assert pool.blocks_cached == 2
    # weight hot-swap invalidates cached KV: flush drops the index
    assert pool.flush_prefix_cache() == 2
    assert pool.blocks_cached == 0 and pool.lookup_prefix(keys) == []
    assert pool.blocks_free == 4
    _conserved(pool)


# ---------------------------------------------------------------------------
# chunked prefill: the staggered-arrival torture pin
# ---------------------------------------------------------------------------


def test_chunked_staggered_torture_token_identical():
    """Chunked-prefill rows join and retire around in-flight decode
    rows across chunk boundaries; every request stays token-identical
    to reference_decode and the engine compiles exactly TWO step
    shapes (the [B, chunk] window and the one-token decode step)."""
    model = tiny_model(seed=5)
    assert model.trace_count == 0
    rng = np.random.RandomState(3)
    p1 = rng.randint(0, 64, size=9).tolist()    # 4+4+1 chunks
    p2 = rng.randint(0, 64, size=11).tolist()   # 4+4+3
    p3 = rng.randint(0, 64, size=2).tolist()    # sub-chunk prompt
    p4 = rng.randint(0, 64, size=13).tolist()   # joins after retires
    first_tok = threading.Event()

    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, prefill_chunk=4) as eng:
        r1 = eng.submit(p1, max_new_tokens=12,
                        stream=lambda *_: first_tok.set())
        assert first_tok.wait(120)  # r1 is decoding now
        r2 = eng.submit(p2, max_new_tokens=6)   # prefills vs r1's decode
        r3 = eng.submit(p3, max_new_tokens=9)
        outs = [r.wait(120) for r in (r1, r2, r3)]
        r4 = eng.submit(p4, max_new_tokens=5)
        out4 = r4.wait(120)

    refs = [reference_decode(model, p, n) for p, n in
            ((p1, 12), (p2, 6), (p3, 9), (p4, 5))]
    assert outs + [out4] == refs
    assert model.trace_count == 2


def test_chunked_serves_poisson_stream_identically():
    model = shared_model()
    prompts = _prompts(8, model.config.vocab_size, seed=19)
    refs = [reference_decode(model, p, 7) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, prefill_chunk=8) as eng:
        reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs


def test_chunked_eos_truncates_like_reference():
    model = shared_model()
    prompt = [3, 7, 11, 2, 9]
    ref = reference_decode(model, prompt, 16)
    eos = ref[4]
    ref_eos = reference_decode(model, prompt, 16, eos_id=eos)
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4, prefill_chunk=4) as eng:
        got = eng.generate(prompt, max_new_tokens=16, eos_id=eos,
                           timeout=120)
    assert got == ref_eos and got[-1] == eos


def test_chunk_budget_bounds_prefill_per_step():
    """The engine's decode-latency bound: prefill rows past the
    per-step token budget sit the step out (the row admitted first is
    fed first) and resume next step; decode rows always ride."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=32)
    sched = StepScheduler(2, pool, 32, prefill_chunk=4,
                          prefill_token_budget=4)
    q = RequestQueue(8)
    r1 = GenerationRequest(list(range(1, 9)), max_new_tokens=2)
    r2 = GenerationRequest(list(range(11, 17)), max_new_tokens=2)
    q.submit(r1)
    q.submit(r2)
    assert len(sched.admit(q)) == 2
    plan, kind = sched.plan_step()
    assert kind == "mixed"
    # slot0 burns the whole budget; slot1 is deferred, not starved
    assert sched.chunk_lens.tolist() == [4, 0]
    assert sched.active.tolist() == [True, False]
    assert [g for _, g in plan] == [None]
    assert sched.rows_deferred == 1
    for seq, g in plan:
        sched.record_token(seq, g, 1)
    plan, kind = sched.plan_step()
    assert kind == "mixed"
    assert sched.chunk_lens.tolist() == [4, 0]  # r1 finishes its prompt
    assert [g for _, g in plan] == [0]
    for seq, g in plan:
        sched.record_token(seq, g, 1)
    # mixed step: r1 decodes (1-token window, budget-exempt), r2 gets
    # the whole replenished budget
    plan, kind = sched.plan_step()
    assert kind == "mixed"
    assert sched.chunk_lens.tolist() == [1, 4]
    assert sched.use_prompt.tolist() == [False, True]
    assert sched.active.tolist() == [True, True]
    assert sched.rows_deferred == 0


@pytest.mark.parametrize("chunk,max_batch,stated,want", [
    (256, 16, None, 256),       # xglm-1.7b-serve: one chunk, the ridge
    (16, 128, None, 64),        # kanana-2-30b-a3b-serve: four chunks
    (1024, 48, 1024, 1024),     # trinity-large-preview-serve states it
    (512, 16, None, 512),       # a chunk past the ridge: never under it
    (256, 16, 1024, 1024),      # a stated budget beats the rule
    (64, 2, None, 256),         # four chunks where they reach the ridge
    (100, 4, None, 256),        # ... and are cut to it where they pass
], ids=["xglm", "kanana", "trinity", "chunk_past_the_ridge",
        "stated_beats_rule", "four_chunks_at_the_ridge",
        "cut_to_the_ridge"])
def test_default_budget_by_geometry(chunk, max_batch, stated, want):
    """Where nobody states `prefill_token_budget` a mixed step holds
    four chunks of prompt, but no more than the token rows at which a
    weight matmul turns compute-bound, and never under one chunk: the
    rule reads the chunk alone."""
    assert PREFILL_BUDGET_RIDGE == 256
    assert default_prefill_token_budget(chunk) == max(
        chunk, min(4 * chunk, PREFILL_BUDGET_RIDGE))
    pool = KVBlockPool(1, 1, 4, 16, num_blocks=8)
    sched = StepScheduler(max_batch, pool, 4096, prefill_chunk=chunk,
                          prefill_token_budget=stated)
    assert sched.prefill_chunk == chunk
    assert sched.prefill_token_budget == want


def _served_geometry(config):
    with open(os.path.join(REPO, "perfbench/configs", config)) as f:
        return json.load(f)["engine"]


def _xglm_toy(max_seq_len):
    return tiny_model(max_seq_len=max_seq_len)


def _latent_toy(max_seq_len):
    return GenerationModel.random(GenerationConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=max_seq_len, block=serving.LatentMoEBlock(
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=128, n_routed_experts=8, experts_per_token=2,
            n_shared_experts=2, moe_d_ff=32)), seed=7)


def _window_toy(max_seq_len):
    return GenerationModel.random(GenerationConfig(
        vocab_size=96, d_model=64, n_heads=4, n_layers=4, d_ff=96,
        max_seq_len=max_seq_len, block=serving.AfmoeBlock(
            n_kv_heads=2, head_dim=128,
            layer_types=["sliding_attention"] * 2
            + ["full_attention", "sliding_attention"],
            sliding_window=16, n_routed_experts=8, experts_per_token=2,
            n_shared_experts=1, moe_d_ff=32)), seed=7)


@pytest.mark.parametrize("config,toy,num_blocks,rows,parent_rows", [
    ("xglm-1.7b-serve.json", _xglm_toy, 8, 272, 1040),
    ("kanana-2-30b-a3b-serve.json", _latent_toy, 8, 192, 192),
    ("trinity-large-preview-serve.json", _window_toy,
     {"global": 8, "window": 8}, 1072, 1072),
], ids=["xglm", "kanana", "trinity"])
def test_chunk_rows_of_the_served_geometries(config, toy, num_blocks,
                                             rows, parent_rows):
    """The token rows the ONE chunk program is compiled for at the
    benchmark's three serving geometries (a toy model of the
    configuration's block in the configuration's engine): XGLM's fall
    from 1,040 to 272; kanana's and trinity's, and with them their
    step-cache keys, are what they were under a default of four chunks
    (`max_batch + 4 * chunk`, and trinity's stated 1,024)."""
    e = _served_geometry(config)
    assert parent_rows == e["max_batch"] + e.get(
        "prefill_token_budget", 4 * e["prefill_chunk"])
    model = toy(e["max_seq_len"])
    with serving.ServingEngine(
            model, max_batch=e["max_batch"], max_seq_len=e["max_seq_len"],
            block_size=e["block_size"], num_blocks=num_blocks,
            prefill_chunk=e["prefill_chunk"],
            prefill_token_budget=e.get("prefill_token_budget")) as eng:
        w = eng._workers["default"]
        assert w._chunk_rows == rows
        blocks_a_row = w.scheduler.max_blocks_per_seq
    chunk_keys = [k for k in model._steps if k[0] == "chunk"]
    assert len(chunk_keys) == 1 and len(model._steps) == 2
    geometry = ("chunk", e["max_batch"], blocks_a_row,
                e["prefill_chunk"], False)
    if model.config.block is None:
        # the XGLM step states its rows where they are fewer than slots
        assert chunk_keys[0][:5] == geometry
        assert chunk_keys[0][-1] == "rows:%d" % rows
    else:
        assert chunk_keys[0][:6] == geometry + (rows,)
    assert model.trace_count == 0           # built, never traced


def test_chunked_budgeted_engine_token_identical():
    model = shared_model()
    prompts = _prompts(5, model.config.vocab_size, seed=23, lo=6, hi=20)
    refs = [reference_decode(model, p, 6) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, prefill_chunk=4,
                               prefill_token_budget=4) as eng:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs


def test_long_prompts_under_a_one_chunk_budget_in_admission_order():
    """Three prompts several chunks long admitted together, one chunk
    of budget a step (what a default engine gives a chunk of 256): the
    tokens are `reference_decode`'s, the first tokens come in the order
    the requests were admitted, and the engine traced two shapes."""
    model = tiny_model(seed=11)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 64, size=n).tolist() for n in (26, 19, 23)]
    refs = [reference_decode(model, p, 6) for p in prompts]
    first, lock = [], threading.Lock()

    def stream_of(i):
        def on_token(*_):
            with lock:
                if i not in first:
                    first.append(i)
        return on_token

    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, prefill_chunk=4,
                               prefill_token_budget=4) as eng:
        gate = threading.Event()
        # hold the worker on a request's stream until all three wait in
        # the queue, so that one tick admits them together
        held = eng.submit([1, 2], max_new_tokens=2,
                          stream=lambda *_: gate.wait(60))
        reqs = [eng.submit(p, max_new_tokens=6, stream=stream_of(i))
                for i, p in enumerate(prompts)]
        gate.set()
        assert held.wait(120) == reference_decode(model, [1, 2], 2)
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert first == [0, 1, 2]
    # four prompt tokens a step at most: 68 of them took 17 mixed steps
    assert st["steps"] >= (26 + 19 + 23) // 4
    assert model.trace_count == 2


def test_late_arrival_in_a_lower_slot_does_not_overtake():
    """The budget goes to the prefilling row admitted FIRST, not to the
    lowest slot: a request that lands in a freed lower slot waits until
    the half-prefilled row above it is through its prompt."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=64)
    sched = StepScheduler(2, pool, 64, prefill_chunk=4,
                          prefill_token_budget=4)
    q = RequestQueue(8)

    def step():
        plan, kind = sched.plan_step()
        for seq, g in plan:
            sched.record_token(seq, g, 1)
        sched.reap()
        return kind, sched.chunk_lens.tolist(), sched.rows_deferred

    short = GenerationRequest([1, 2], max_new_tokens=1)
    long_ = GenerationRequest(list(range(1, 15)), max_new_tokens=2)
    q.submit(short)
    q.submit(long_)
    assert [s.slot for s in sched.admit(q)] == [0, 1]
    assert step() == ("mixed", [2, 2], 0)   # slot 0 done, slot 1 at 2/14
    assert sched.slots[0] is None and sched.slots[1].pos == 2
    late = GenerationRequest(list(range(21, 31)), max_new_tokens=2)
    q.submit(late)
    assert [s.slot for s in sched.admit(q)] == [0]   # the lower slot
    # three more chunks of the older prompt first; the late arrival
    # sits them out however low its slot
    for fed in (4, 4, 4):
        assert step() == ("mixed", [0, fed], 1)
    assert not sched.slots[1].in_prefill and sched.slots[0].pos == 0
    # then the late arrival gets the budget while the older row decodes
    assert step() == ("mixed", [4, 1], 0)


# ---------------------------------------------------------------------------
# radix prefix caching through the engine
# ---------------------------------------------------------------------------


def test_prefix_cache_skips_shared_span_token_identical():
    model = shared_model()
    rng = np.random.RandomState(31)
    shared = rng.randint(0, 64, size=12).tolist()
    prompts = [shared + rng.randint(0, 64, size=3).tolist()
               for _ in range(3)]
    refs = [reference_decode(model, p, 8) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, prefix_cache=True) as eng:
        first = eng.generate(prompts[0], max_new_tokens=8, timeout=120)
        rest = [eng.generate(p, max_new_tokens=8, timeout=120)
                for p in prompts[1:]]
        st = eng.stats()["default"]
    assert [first] + rest == refs
    # 3 full shared blocks sealed by the first request, adopted twice
    assert st["prefix_blocks_reused"] == 6
    assert st["prefix_tokens_skipped"] == 24
    assert st["prefix_cache"] is True


def test_prefix_cache_eviction_recomputes_correctly():
    """A pool sized for ONE full-length sequence: request B's worst-case
    reservation evicts A's cached prefix blocks; replaying A's prefix
    afterwards gets no match and recomputes — still token-identical."""
    model = shared_model()
    rng = np.random.RandomState(37)
    pa = rng.randint(0, 64, size=13).tolist()
    pb = rng.randint(0, 64, size=26).tolist()
    ref_a = reference_decode(model, pa, 4)
    ref_b = reference_decode(model, pb, 4)
    with serving.ServingEngine(model, max_batch=1, max_seq_len=32,
                               block_size=4, num_blocks=8,
                               prefix_cache=True) as eng:
        assert eng.generate(pa, max_new_tokens=4, timeout=120) == ref_a
        worker = eng._workers["default"]
        assert worker.pool.blocks_cached == 3  # A's sealed prefix
        assert eng.generate(pb, max_new_tokens=4, timeout=120) == ref_b
        reused_before = worker.scheduler.prefix_blocks_reused
        # B needed the whole pool: A's cached blocks were evicted
        assert eng.generate(pa, max_new_tokens=4, timeout=120) == ref_a
        assert worker.scheduler.prefix_blocks_reused == reused_before
        _conserved(worker.pool)


def test_prefix_cache_with_chunked_prefill_combined():
    model = shared_model()
    rng = np.random.RandomState(41)
    shared = rng.randint(0, 64, size=16).tolist()
    prompts = [shared + rng.randint(0, 64, size=int(n)).tolist()
               for n in rng.randint(2, 7, size=4)]
    refs = [reference_decode(model, p, 6) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, prefill_chunk=4,
                               prefix_cache=True) as eng:
        first = eng.generate(prompts[0], max_new_tokens=6, timeout=120)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        rest = [r.wait(120) for r in reqs]
        st = eng.stats()["default"]
    assert [first] + rest == refs
    # 4 shared full blocks adopted by each of the 3 follow-ups
    assert st["prefix_blocks_reused"] == 12
    assert st["prefix_tokens_skipped"] == 48


def test_prefix_cache_multi_model_namespaced():
    """Two models with identical prompts must never share KV blocks:
    the chain keys are namespaced per model (and the pools are
    per-model anyway)."""
    ma = tiny_model(seed=0, name="a")
    mb = tiny_model(seed=1, name="b")
    prompt = list(range(2, 15))
    ref_a = reference_decode(ma, prompt, 5)
    ref_b = reference_decode(mb, prompt, 5)
    assert ref_a != ref_b
    with serving.ServingEngine({"a": ma, "b": mb}, max_batch=2,
                               max_seq_len=64, block_size=4,
                               prefix_cache=True) as eng:
        assert eng.generate(prompt, max_new_tokens=5, model="a",
                            timeout=120) == ref_a
        assert eng.generate(prompt, max_new_tokens=5, model="b",
                            timeout=120) == ref_b
        assert eng.generate(prompt, max_new_tokens=5, model="a",
                            timeout=120) == ref_a


# ---------------------------------------------------------------------------
# the one planner, and what the defaults build
# ---------------------------------------------------------------------------


def test_plan_sequence_pinned_against_oracle():
    """The planner's observable trace (step kind, positions, window
    lengths, use_prompt, active, the prompt tokens fed, gen indices and
    the lazily-built block tables), pinned literally: mixed windows
    while a row is mid-prompt (decode rows riding as windows of one),
    then decode windows of one that feed no prompt token."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=16)
    sched = StepScheduler(2, pool, max_seq_len=16, prefill_chunk=2)
    q = RequestQueue(8)
    r1 = GenerationRequest([5, 6, 7], max_new_tokens=3)
    r2 = GenerationRequest([9, 8], max_new_tokens=2)
    q.submit(r1)
    q.submit(r2)
    assert len(sched.admit(q)) == 2
    trace = []
    for _ in range(6):
        plan, kind = sched.plan_step()
        lens = sched.chunk_lens.tolist()
        trace.append((kind, sched.positions.tolist(), lens,
                      sched.use_prompt.tolist(), sched.active.tolist(),
                      [sched.chunk_feed[i, :n].tolist() if u else []
                       for i, (n, u) in enumerate(
                           zip(lens, sched.use_prompt))],
                      [g for _, g in plan]))
        for seq, g in plan:
            sched.record_token(seq, g, 1)
        sched.reap()
    T, F = True, False
    assert trace == [
        ("mixed", [0, 0], [2, 2], [T, T], [T, T], [[5, 6], [9, 8]],
         [None, 0]),
        ("mixed", [2, 2], [1, 1], [T, F], [T, T], [[7], []], [0, 1]),
        ("decode", [3, 2], [1, 0], [F, F], [T, F], [[], []], [1]),
        ("decode", [4, 2], [1, 0], [F, F], [T, F], [[], []], [2]),
        ("decode", [4, 2], [0, 0], [F, F], [F, F], [[], []], []),
        ("decode", [4, 2], [0, 0], [F, F], [F, F], [[], []], []),
    ]
    # LIFO pool: slot0 drew block 1 then (at pos 4) block 3; slot1 drew
    # block 2 — and everything is back in the pool after retirement
    assert r1.tokens == [1, 1, 1] and r2.tokens == [1, 1]
    st = pool.stats()
    assert st["blocks_in_use"] == 0 and st["blocks_cached"] == 0
    assert st["blocks_free"] == 16


def test_defaults_build_decode_and_chunk_shapes_and_no_index(monkeypatch):
    monkeypatch.delenv("PTPU_SERVE_PREFILL_CHUNK", raising=False)
    monkeypatch.delenv("PTPU_SERVE_PREFIX_CACHE", raising=False)
    model = tiny_model(seed=9)
    prompts = _prompts(4, model.config.vocab_size, seed=13)
    refs = [reference_decode(model, p, 6) for p in prompts]
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        w = eng._workers["default"]
        # the default chunk, clamped to the context; the rule's
        # prefill budget a mixed step (four chunks reach the ridge here)
        assert serving.scheduler.DEFAULT_PREFILL_CHUNK == 256
        assert w.prefill_chunk == 64 and w.prefix_cache is False
        assert w.scheduler.prefill_token_budget \
            == default_prefill_token_budget(64) == 256
        assert w.scheduler.chunk_feed.shape == (2, 64)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert model.trace_count == 2          # the decode and chunk shapes
    assert len(model._steps) == 2
    assert st["prefill_chunk"] == 64
    assert st["prefix_blocks_reused"] == 0
    assert st["blocks_shared"] == 0 and st["blocks_cached"] == 0
    assert not w.pool._sealed              # content index never touched


def test_default_engine_prefills_a_long_prompt_in_chunks(monkeypatch):
    """A default engine given a prompt longer than one chunk reaches
    its first token in ceil(len / chunk) mixed steps, not one step a
    prompt token."""
    monkeypatch.delenv("PTPU_SERVE_PREFILL_CHUNK", raising=False)
    model = tiny_model(seed=4, max_seq_len=1024)
    prompt = np.random.RandomState(2).randint(0, 64, size=600).tolist()
    with serving.ServingEngine(model, max_batch=1, max_seq_len=1024,
                               block_size=16) as eng:
        got = eng.generate(prompt, max_new_tokens=1, timeout=300)
        st = eng.stats()["default"]
    assert st["prefill_chunk"] == 256
    assert st["steps"] == 3                # 256 + 256 + 88 prompt tokens
    assert got == reference_decode(model, prompt, 1)


def test_env_flags_set_chunk_size_and_prefix_cache(monkeypatch):
    monkeypatch.setenv("PTPU_SERVE_PREFILL_CHUNK", "4")
    monkeypatch.setenv("PTPU_SERVE_PREFIX_CACHE", "1")
    model = shared_model()
    prompt = list(range(3, 17))
    ref = reference_decode(model, prompt, 5)
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        w = eng._workers["default"]
        assert w.prefill_chunk == 4 and w.prefix_cache is True
        assert w.scheduler.prefill_token_budget \
            == default_prefill_token_budget(4) == 16    # four chunks
        assert eng.generate(prompt, max_new_tokens=5, timeout=120) == ref
        # 14 prompt tokens in chunks of 4, then 4 decode steps
        assert eng.stats()["default"]["steps"] == 4 + 4


# ---------------------------------------------------------------------------
# TTFT telemetry
# ---------------------------------------------------------------------------


def test_ttft_recorded_per_request():
    from paddle_tpu.observability import metrics as obs

    model = shared_model()
    was_enabled = obs.enabled()
    obs.enable()
    reg = obs.registry()
    n0 = reg.histogram("serving/ttft").count
    try:
        with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                                   block_size=4) as eng:
            reqs = [eng.submit(p, max_new_tokens=6)
                    for p in _prompts(4, model.config.vocab_size,
                                      seed=17)]
            for r in reqs:
                r.wait(120)
    finally:
        if not was_enabled:
            obs.disable()
    assert reg.histogram("serving/ttft").count - n0 == 4
    for g in ("serving/ttft_p50", "serving/ttft_p99"):
        assert np.isfinite(reg.gauge(g).value) and reg.gauge(g).value > 0
    for r in reqs:
        assert r.ttft is not None and 0 < r.ttft <= r.latency
        assert r.first_token_time is not None


def test_ttft_none_until_first_token():
    r = GenerationRequest([1, 2], max_new_tokens=2)
    assert r.ttft is None
