"""Speculative decoding (ISSUE 13, docs/SERVING.md): draft-k /
verify-in-one-step in the continuous-batching serving engine.

Covers the tentpole and its satellites:
  * draft sources — ``NGramDrafter`` (prompt-lookup over the sequence's
    own history) and the ``ModelDrafter`` draft-model hook (drafting
    with the target model itself is pinned to PERFECT acceptance);
  * the verify window + acceptance rule — longest draft prefix matching
    the target's argmax, plus the correction token, so every window
    emits >= 1 sequential-greedy-identical token (spec-on output is
    pinned token-identical to ``reference_decode`` under staggered
    arrivals, EOS inside accepted runs, chunked prefill and the radix
    prefix cache);
  * KV rollback — ``KVBlockPool.truncate_owner`` returns rejected-draft
    tail blocks and restores the owner's reservation (the two-phase
    invariant in reverse), refuses sealed/shared blocks, and
    ``check_invariants`` covers the new truncate/rollback states;
  * the "discarded speculative steps after an EOS" contract
    (serving/engine.py docstring, docs/SERVING.md): with spec windows
    on, no post-EOS token is ever emitted and discarded-position KV
    writes are rolled back or overwritten-before-visible;
  * flag-off identity — ``PTPU_SERVE_SPEC_K`` unset builds no third
    compiled shape and no spec state, and the tokens are
    ``reference_decode``'s.
"""

import threading

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                GenerationRequest, KVBlockPool,
                                ModelDrafter, NGramDrafter, RequestQueue,
                                StepScheduler, prefix_chain_keys,
                                reference_decode)

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
    st = pool.stats()
    assert (st["blocks_free"] + st["blocks_reserved"]
            + st["blocks_owned"] + st["blocks_shared"]
            == st["blocks_total"]), st
    assert st["blocks_free"] >= 0, st
    return st


class StubDrafter:
    """Proposes a fixed token run (tests force rejections with it)."""

    def __init__(self, token=63):
        self.token = token

    def propose(self, history, k):
        return [self.token] * int(k)


# ---------------------------------------------------------------------------
# draft sources
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter()
    # suffix [7, 8] recurs earlier; the continuation after the match is
    # proposed, clamped to k
    hist = [1, 7, 8, 4, 5, 6, 7, 8]
    assert d.propose(hist, 3) == [4, 5, 6]
    assert d.propose(hist, 2) == [4, 5]
    # no recurring n-gram -> no drafts; misses cost nothing
    assert d.propose([1, 2, 3, 4], 4) == []
    assert d.propose([1, 2], 0) == []
    assert d.propose([], 4) == []
    assert d.propose([5], 4) == []


def test_ngram_drafter_prefers_full_window_match():
    """On a periodic history the nearest match sits at the history's
    end and can only offer a truncated draft — the drafter scans on to
    an earlier occurrence able to fill the whole window."""
    d = NGramDrafter()
    pat = [11, 12, 13, 14]
    hist = pat * 4
    got = d.propose(hist, 6)
    assert len(got) == 6
    # the proposal continues the period
    assert got == (pat * 3)[:6] == [11, 12, 13, 14, 11, 12]


def test_ngram_drafter_longer_ngrams_win():
    d = NGramDrafter(max_ngram=3)
    # trigram [1, 2, 3] has continuation 9; bigram [2, 3] also occurs
    # with continuation 5 — the longer (more specific) match wins
    hist = [2, 3, 5, 1, 2, 3, 9, 0, 1, 2, 3]
    assert d.propose(hist, 1) == [9]


def test_ngram_drafter_validates_config():
    with pytest.raises(ValueError):
        NGramDrafter(min_ngram=0)
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=1, min_ngram=2)


def test_model_drafter_is_greedy_continuation():
    model = shared_model()
    prompt = [3, 9, 4, 17]
    d = ModelDrafter(model)
    assert d.propose(prompt, 5) == reference_decode(model, prompt, 5)
    assert d.propose(prompt, 0) == []
    assert d.propose([], 3) == []
    # histories at the context edge propose nothing instead of raising
    assert d.propose(list(range(1, 65)), 3) == []
    with pytest.raises(TypeError):
        ModelDrafter("not a model")


# ---------------------------------------------------------------------------
# pool: truncate_owner (KV rollback) + invariants
# ---------------------------------------------------------------------------


def test_pool_truncate_restores_reservation_and_blocks():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=8)
    assert pool.reserve("a", 5)
    bids = [pool.alloc_block("a") for _ in range(4)]
    st = _conserved(pool)
    assert st["blocks_owned"] == 4 and st["blocks_reserved"] == 1
    dropped = pool.truncate_owner("a", 2)
    assert dropped == bids[2:]
    assert pool.block_table("a") == bids[:2]
    st = _conserved(pool)
    assert st["blocks_owned"] == 2 and st["blocks_reserved"] == 3
    assert pool.check_invariants() == []
    # re-crossing the same boundaries re-draws from the restored
    # reservation — and gets the same (cache-warm) blocks back LIFO
    again = [pool.alloc_block("a") for _ in range(3)]
    assert again[:2] == bids[2:]
    _conserved(pool)
    assert pool.check_invariants() == []
    # truncating to the current length (or more) is a no-op
    assert pool.truncate_owner("a", 5) == []
    assert pool.truncate_owner("a", 99) == []
    pool.free_owner("a")
    st = _conserved(pool)
    assert st["blocks_free"] == 8 and pool.check_invariants() == []


def test_pool_truncate_refuses_shared_and_sealed_blocks():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=6)
    keys = prefix_chain_keys(list(range(8)), 4)
    assert pool.reserve("a", 3)
    b1 = pool.alloc_block("a")
    pool.alloc_block("a")
    assert pool.seal_block(b1, keys[0])
    with pytest.raises(RuntimeError, match="sealed"):
        pool.truncate_owner("a", 0)
    # an adopted (refcount 2) block is never rolled back either
    assert pool.reserve("b", 3, prefix_keys=keys[:1])
    assert pool.block_table("b") == [b1]
    with pytest.raises(RuntimeError, match="refcount"):
        pool.truncate_owner("b", 0)
    assert pool.check_invariants() == []
    with pytest.raises(KeyError):
        pool.truncate_owner("nobody", 0)
    with pytest.raises(ValueError):
        pool.truncate_owner("a", -1)


def test_pool_invariants_cover_rollback_states():
    """Satellite pin: check_invariants covers the truncate/rollback
    accounting — the reserved+owned ceiling identity and the
    no-index-entry-on-the-free-list rule — and stays clean through a
    real truncate."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=6)
    assert pool.reserve("a", 4)
    pool.alloc_block("a")
    pool.alloc_block("a")
    pool.truncate_owner("a", 1)
    assert pool.check_invariants() == []
    # corrupt the ceiling: alloc/truncate accounting drift is reported
    pool._reserve_ceiling["a"] += 1
    probs = pool.check_invariants()
    assert any("ceiling" in p for p in probs), probs
    pool._reserve_ceiling["a"] -= 1
    assert pool.check_invariants() == []
    # a missing ceiling is reported too
    saved = pool._reserve_ceiling.pop("a")
    probs = pool.check_invariants()
    assert any("no reservation ceiling" in p for p in probs), probs
    pool._reserve_ceiling["a"] = saved
    # a free-list block that kept its content-index entry is reported
    keys = prefix_chain_keys(list(range(4)), 4)
    free_bid = pool._free[-1]
    pool._block_key[free_bid] = keys[0]
    pool._sealed[keys[0]] = free_bid
    probs = pool.check_invariants()
    assert any("free-list block" in p for p in probs), probs


# ---------------------------------------------------------------------------
# scheduler: acceptance rule + rollback (unit)
# ---------------------------------------------------------------------------


def _drive_prefill(sched, q, request, token=5):
    """Admit and run the prefill to completion, feeding `token` as
    every materialized output (host-side unit driving)."""
    q.submit(request)
    assert len(sched.admit(q)) == 1
    seq = next(s for s in sched.slots if s is not None)
    while seq.in_prefill:
        plan, _kind = sched.plan_step()
        for s, g in plan:
            sched.record_token(s, g, token)
    return seq


def test_scheduler_spec_acceptance_correction_and_rollback():
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=16)
    sched = StepScheduler(2, pool, 32, spec_k=3, drafter=StubDrafter(9))
    q = RequestQueue(8)
    seq = _drive_prefill(sched, q, GenerationRequest([1, 2],
                                                     max_new_tokens=16))
    assert seq.request.tokens == [5] and seq.pos == 2
    # window 1: [t0=5, 9, 9, 9] over positions 2..5 — crosses into a
    # second block (bs=4), allocated at plan time
    plan = sched.plan_spec()
    assert plan is not None and len(plan) == 1
    (s, window), = plan
    assert window == [5, 9, 9, 9]
    assert sched.spec_lens[0] == 4 and sched.positions[0] == 2
    assert sched.use_prompt[0] and sched.active[0]
    assert len(pool.block_table(seq)) == 2
    # target: accepts 9, 9 then corrects to 7 -> emit [9, 9, 7]
    n = sched.record_spec(s, window, [9, 9, 7, 3])
    assert n == 3
    assert seq.request.tokens == [5, 9, 9, 7]
    assert seq.pos == 5
    assert sched.spec_proposed == 3 and sched.spec_accepted == 2
    assert sched.spec_emitted == 3
    # pos 5 still needs 2 blocks: nothing to roll back
    assert len(pool.block_table(seq)) == 2
    assert pool.check_invariants() == []
    # window 2: all drafts rejected -> 1 correction token, the block
    # allocated for positions 5..8's tail rolls back
    plan = sched.plan_spec()
    (s, window), = plan
    assert window == [7, 9, 9, 9]
    n_blocks = len(pool.block_table(seq))
    assert n_blocks == 3  # position 8 crossed a boundary
    n = sched.record_spec(s, window, [1, 2, 3, 4])
    assert n == 1 and seq.request.tokens == [5, 9, 9, 7, 1]
    assert seq.pos == 6
    assert len(pool.block_table(seq)) == 2  # tail block returned
    assert sched.spec_blocks_rolled_back == 1
    assert int(sched.block_tables[0, 2]) == pool.NULL_BLOCK
    assert pool.check_invariants() == []
    _conserved(pool)


def test_scheduler_plan_spec_defers_to_prefill():
    """plan_spec returns None while any row is mid-prompt (the engine
    then dispatches the normal prefill shapes) and resumes after."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=16)
    sched = StepScheduler(2, pool, 32, spec_k=2, drafter=StubDrafter())
    q = RequestQueue(8)
    q.submit(GenerationRequest([1, 2, 3], max_new_tokens=4))
    assert len(sched.admit(q)) == 1
    assert sched.plan_spec() is None  # mid-prompt
    seq = next(s for s in sched.slots if s is not None)
    while seq.in_prefill:
        for s, g in sched.plan_step()[0]:
            sched.record_token(s, g, 5)
    assert sched.plan_spec() is not None
    # spec_k=0 scheduler: plan_spec is inert
    sched0 = StepScheduler(2, pool, 32)
    assert sched0.spec_k == 0 and sched0.plan_spec() is None
    assert not hasattr(sched0, "spec_feed")


def test_scheduler_spec_window_clamped_by_budgets():
    """A window never overshoots max_new_tokens or the sequence cap, so
    the admission reservation always covers its allocations."""
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=16)
    sched = StepScheduler(1, pool, 32, spec_k=6, drafter=StubDrafter())
    q = RequestQueue(8)
    seq = _drive_prefill(sched, q, GenerationRequest([1, 2],
                                                     max_new_tokens=3))
    # 1 token emitted, 2 remain -> window of at most 2 (t0 + 1 draft)
    plan = sched.plan_spec()
    (s, window), = plan
    assert len(window) == 2
    n = sched.record_spec(s, window, [8, 8])
    assert n >= 1 and len(seq.request.tokens) <= 3
    assert pool.check_invariants() == []


# ---------------------------------------------------------------------------
# engine: token identity (the oracle pin)
# ---------------------------------------------------------------------------


def test_spec_engine_token_identical_random_prompts():
    """Identity holds no matter how good the drafter is: rejected
    drafts cost nothing but compute, accepted ones are provably what
    sequential greedy would emit."""
    model = shared_model()
    prompts = _prompts(6, model.config.vocab_size, seed=19)
    refs = [reference_decode(model, p, 8) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, spec_k=4) as eng:
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert st["spec_steps"] > 0


def test_spec_engine_token_identical_wrong_drafter_rollback():
    """An adversarial always-wrong drafter forces a rollback on every
    window — output identity and pool invariants still hold."""
    model = shared_model()
    prompts = _prompts(5, model.config.vocab_size - 1, seed=3)
    refs = [reference_decode(model, p, 12) for p in prompts]
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, spec_k=5,
                               drafter=StubDrafter(63)) as eng:
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        w = eng._workers["default"]
        st = eng.stats()["default"]
    assert st["spec_accepted"] == 0 and st["spec_proposed"] > 0
    assert st["spec_blocks_rolled_back"] > 0
    assert w.pool.check_invariants() == []
    st = w.pool.stats()
    assert st["blocks_in_use"] == 0
    assert st["blocks_free"] == st["blocks_total"]


def test_spec_staggered_torture_with_chunk_and_prefix_cache():
    """The acceptance-criteria torture: staggered joins/retires with
    EOS, stacked on chunked prefill AND the radix prefix cache, all
    token-identical to reference_decode — and exactly TWO compiled
    shapes (chunk + verify window; the one-token decode shape is never
    needed when both are on)."""
    model = tiny_model(seed=5)
    assert model.trace_count == 0
    rng = np.random.RandomState(3)
    shared = rng.randint(0, 64, size=9).tolist()
    p1 = shared + rng.randint(0, 64, size=3).tolist()
    p2 = shared + rng.randint(0, 64, size=2).tolist()
    p3 = rng.randint(0, 64, size=2).tolist()
    p4 = shared + rng.randint(0, 64, size=4).tolist()
    first_tok = threading.Event()

    ref1 = reference_decode(model, p1, 12)
    eos = ref1[6]  # EOS lands mid-generation for r1
    refs = [reference_decode(model, p1, 12, eos_id=eos),
            reference_decode(model, p2, 6, eos_id=eos),
            reference_decode(model, p3, 9, eos_id=eos),
            reference_decode(model, p4, 5, eos_id=eos)]

    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, prefill_chunk=4,
                               prefix_cache=True, spec_k=4) as eng:
        r1 = eng.submit(p1, max_new_tokens=12, eos_id=eos,
                        stream=lambda *_: first_tok.set())
        assert first_tok.wait(120)  # r1 is decoding (spec windows) now
        r2 = eng.submit(p2, max_new_tokens=6, eos_id=eos)
        r3 = eng.submit(p3, max_new_tokens=9, eos_id=eos)
        outs = [r.wait(120) for r in (r1, r2, r3)]
        r4 = eng.submit(p4, max_new_tokens=5, eos_id=eos)
        out4 = r4.wait(120)
        st = eng.stats()["default"]
        pool = eng._workers["default"].pool
        assert pool.check_invariants() == []
    assert outs + [out4] == refs
    assert model.trace_count == 2
    assert st["spec_steps"] > 0
    assert st["prefix_blocks_reused"] > 0  # the legs genuinely stacked


def test_spec_no_post_eos_emission_and_kv_rolled_back():
    """Satellite pin (serving/engine.py docstring, docs/SERVING.md):
    with spec windows on, no post-EOS token is ever emitted — EOS
    inside an ACCEPTED run discards the rest of the window — and the
    discarded positions' KV writes are rolled back (or sit in blocks
    the retiring sequence owned until reap); nothing is ever dispatched
    for a finished sequence."""
    model = shared_model()
    prompt = [3, 7, 11, 2, 9]
    ref = reference_decode(model, prompt, 16)
    eos = ref[4]
    ref_eos = reference_decode(model, prompt, 16, eos_id=eos)
    seen = []
    # drafting with the target model = every draft accepted, so the
    # EOS lands INSIDE an accepted run with live tokens behind it
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4, spec_k=8,
                               drafter=ModelDrafter(model)) as eng:
        r = eng.submit(prompt, max_new_tokens=16, eos_id=eos,
                       stream=lambda rq, t, fin: seen.append((t, fin)))
        got = r.wait(120)
        w = eng._workers["default"]
    assert got == ref_eos and got[-1] == eos
    # the stream saw exactly the pre-EOS tokens, finality exactly once
    assert [t for t, _ in seen] == ref_eos
    assert [f for _, f in seen] == [False] * (len(ref_eos) - 1) + [True]
    # every step materialized before the next plan: nothing in flight
    assert w._inflight == []
    # all KV state returned; the rollback accounting stayed consistent
    assert w.pool.check_invariants() == []
    st = w.pool.stats()
    assert st["blocks_in_use"] == 0
    assert st["blocks_free"] == st["blocks_total"]


# ---------------------------------------------------------------------------
# ModelDrafter hook: perfect acceptance
# ---------------------------------------------------------------------------


def test_model_drafter_hook_perfect_acceptance():
    model = shared_model()
    prompts = _prompts(4, model.config.vocab_size, seed=23, lo=3, hi=9)
    refs = [reference_decode(model, p, 10) for p in prompts]
    with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=4, spec_k=4,
                               drafter=ModelDrafter(model)) as eng:
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]
    assert st["spec_accept_rate"] == 1.0
    # full windows: 10 tokens per row in ceil(10 / (k+1)) = 2 windows
    assert st["spec_emitted"] / st["spec_steps"] > 2


def test_spec_tokens_per_step_exceeds_one_on_repetitive_set():
    """The perf receipt shape the bench/CI gate uses: repetitive
    prompts + n-gram drafting emit > 1 token per compiled step per
    sequence (plain decoding is exactly 1)."""
    model = tiny_model(seed=0, max_seq_len=128)
    rng = np.random.RandomState(11)
    prompts = [(rng.randint(0, 64, size=4).tolist()) * 3
               for _ in range(4)]
    refs = [reference_decode(model, p, 24) for p in prompts]
    with serving.ServingEngine(model, max_batch=2, max_seq_len=128,
                               block_size=8, prefill_chunk=4,
                               spec_k=6) as eng:
        outs = [eng.generate(p, max_new_tokens=24, timeout=120)
                for p in prompts]
        st = eng.stats()["default"]
    assert outs == refs
    assert st["spec_accepted"] > 0
    # serial traffic -> one row per window: emitted/windows is the
    # per-sequence tokens-per-step
    assert st["spec_emitted"] / st["spec_steps"] > 1.2


# ---------------------------------------------------------------------------
# flag-off identity + env activation
# ---------------------------------------------------------------------------


def test_spec_off_defaults_build_no_spec_state(monkeypatch):
    """PTPU_SERVE_SPEC_K unset: no drafter, no third compiled shape, no
    spec state, and the emitted tokens are reference_decode's (the
    planner's literal plan-sequence oracle lives in
    test_serving_fastpath)."""
    monkeypatch.delenv("PTPU_SERVE_SPEC_K", raising=False)
    model = tiny_model(seed=9)
    prompts = _prompts(4, model.config.vocab_size, seed=13)
    refs = [reference_decode(model, p, 6) for p in prompts]
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        w = eng._workers["default"]
        assert w.spec_k == 0 and w.drafter is None
        assert w._spec_step is None
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert model.trace_count == 2          # the decode and chunk shapes
    assert len(model._steps) == 2
    assert not any(isinstance(k, tuple) and k and k[0] == "spec"
                   for k in model._steps)
    assert st["spec_steps"] == 0 and st["spec_proposed"] == 0
    assert st["spec_k"] == 0
    sched = w.scheduler
    assert sched.spec_k == 0 and sched.drafter is None
    assert not hasattr(sched, "spec_feed")


def test_env_flag_activates_spec(monkeypatch):
    monkeypatch.setenv("PTPU_SERVE_SPEC_K", "4")
    model = shared_model()
    prompt = list(range(3, 17))
    ref = reference_decode(model, prompt, 6)
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        w = eng._workers["default"]
        assert w.spec_k == 4
        assert isinstance(w.drafter, NGramDrafter)
        assert eng.generate(prompt, max_new_tokens=6, timeout=120) == ref
        st = eng.stats()["default"]
    assert st["spec_k"] == 4 and st["spec_steps"] > 0


def test_spec_engine_rejects_bad_drafter():
    model = shared_model()
    with pytest.raises(TypeError, match="propose"):
        serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                              block_size=4, spec_k=2,
                              drafter="not a drafter")


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_spec_metrics_surface():
    from paddle_tpu.observability import metrics as obs

    model = shared_model()
    was_enabled = obs.enabled()
    obs.enable()
    reg = obs.registry()
    base = {n: reg.counter("serving/spec_%s" % n).value
            for n in ("steps", "proposed", "accepted", "rejected")}
    try:
        with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                                   block_size=4, spec_k=4) as eng:
            reqs = [eng.submit(p, max_new_tokens=8)
                    for p in _prompts(4, model.config.vocab_size,
                                      seed=17)]
            for r in reqs:
                r.wait(120)
            st = eng.stats()["default"]
    finally:
        if not was_enabled:
            obs.disable()
    d = {n: reg.counter("serving/spec_%s" % n).value - base[n]
         for n in ("steps", "proposed", "accepted", "rejected")}
    assert d["steps"] == st["spec_steps"] > 0
    assert d["proposed"] == st["spec_proposed"]
    assert d["accepted"] + d["rejected"] == d["proposed"]
    rate = reg.gauge("serving/spec_accept_rate").value
    assert 0.0 <= rate <= 1.0
