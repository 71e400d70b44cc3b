"""Compounded speculative serving (ISSUE 18, docs/SERVING.md): tree
verification in one compiled step, the jitted on-device drafter, and
int8 draft+target compounding.

Covers the tentpole and its satellites:
  * tree topology + the host acceptance walk — level-order layout,
    deepest-root-path acceptance with lowest-chain tie-break, width 1
    bitwise the PR-12 linear prefix walk;
  * engine token identity — tree windows (NGram and jitted ModelDrafter
    draft sources, int8-compounded stores included) stay token-identical
    to ``reference_decode`` under adversarial always-wrong drafting,
    staggered joins, and EOS inside an accepted tree path;
  * KV discipline — rejected branches roll back through the
    reservation-restoring ``truncate_owner`` path (pool invariants clean
    at every boundary), the accepted path compacts via the tree-commit
    step, and the drafter's OWN pool obeys the same truncate contract;
  * flag-off identity — ``PTPU_SERVE_SPEC_TREE`` unset keeps the spec
    engine bitwise PR-12 (no tree/commit/draft compiled shapes);
  * the NGram suffix-index memoization — O(k)-per-window host cost with
    scan-identical proposals, alternate chains from other occurrence
    sites;
  * the Pallas tree-mask verify-window kernel vs its lax reference.
"""

import threading

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                ModelDrafter, NGramDrafter,
                                blocks_needed, parse_tree_shape,
                                reference_decode, spec_tree_acceptance,
                                tree_topology)

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


def _drained(pool):
    assert pool.check_invariants() == []
    st = pool.stats()
    assert st["blocks_in_use"] == 0
    assert st["blocks_free"] == st["blocks_total"]


class StubTreeDrafter:
    """Proposes fixed wrong token chains (tests force full-tree
    rejections with it)."""

    def __init__(self, tokens=(63, 62)):
        self.tokens = tokens

    def propose(self, history, k):
        return [self.tokens[0]] * int(k)

    def propose_tree(self, history, width, depth, seq_id=None):
        return [[t] * int(depth) for t in self.tokens[:int(width)]]


# ---------------------------------------------------------------------------
# topology + acceptance walk (unit)
# ---------------------------------------------------------------------------


def test_parse_tree_shape():
    assert parse_tree_shape("2x3") == (2, 3)
    assert parse_tree_shape(" 4X1 ") == (4, 1)
    assert parse_tree_shape((3, 2)) == (3, 2)
    for off in (None, "", "0", "off", "false", "no"):
        assert parse_tree_shape(off) is None
    with pytest.raises(ValueError):
        parse_tree_shape("3")
    with pytest.raises(ValueError):
        parse_tree_shape("0x2")


def test_tree_topology_level_order():
    parents, depths, anc = tree_topology(2, 3)
    C = 7
    assert parents.shape == (C,) and anc.shape == (C, C)
    # chain c: slots [1+c, 3+c, 5+c]; parent chains up the same chain
    assert list(parents) == [0, 0, 0, 1, 2, 3, 4]
    assert list(depths) == [0, 1, 1, 2, 2, 3, 3]
    # slot 5 (chain 0, level 3): visibility is exactly its root path
    assert list(np.where(anc[5])[0]) == [0, 1, 3, 5]
    # sibling branches are mutually invisible
    assert not anc[4, 1] and not anc[3, 2]
    # width 1 degenerates to the linear causal window
    _p, _d, anc1 = tree_topology(1, 4)
    assert (anc1 == np.tril(np.ones((5, 5), bool))).all()


def test_tree_acceptance_deepest_path_wins():
    # window: root=5; level1 = [7, 9]; level2 = [8, 1]  (W=2, D=2)
    window = [5, 7, 9, 8, 1]
    # target argmax: after root -> 9 (chain 1 accepted at level 1),
    # after slot 2 (the 9) -> 4; chain 0 dies at level 1
    outs = [9, 0, 4, 0, 0]
    path, emitted = spec_tree_acceptance(window, outs, 2)
    assert path == [2] and emitted == [9, 4]
    # deeper chain 0 beats shallower chain 1
    outs = [7, 3, 0, 0, 0]   # root->7, slot1->3: chain 0 depth 1... and
    window2 = [5, 7, 9, 3, 1]
    outs2 = [7, 3, 0, 6, 0]  # slot 3 accepted too -> depth 2
    assert spec_tree_acceptance(window2, outs2, 2) == ([1, 3], [7, 3, 6])
    # tie at equal depth resolves to the lowest chain index
    window3 = [5, 7, 7, 3, 1]
    outs3 = [7, 3, 9, 6, 0]
    assert spec_tree_acceptance(window3, outs3, 2) == ([1, 3], [7, 3, 6])
    # nothing accepted: the correction token alone
    assert spec_tree_acceptance([5, 7, 9], [0, 1, 2], 2) == ([], [0])
    # 1-slot window = plain decode through the tree step
    assert spec_tree_acceptance([5], [3], 2) == ([], [3])


def test_tree_acceptance_width1_is_linear_prefix_walk():
    rng = np.random.RandomState(0)
    for _ in range(50):
        k = rng.randint(1, 6)
        window = rng.randint(0, 8, size=k + 1).tolist()
        outs = rng.randint(0, 8, size=k + 1).tolist()
        path, emitted = spec_tree_acceptance(window, outs, 1)
        drafts = window[1:]
        m = 0
        while m < len(drafts) and drafts[m] == outs[m]:
            m += 1
        assert emitted == drafts[:m] + [outs[m]]
        assert path == list(range(1, m + 1))


# ---------------------------------------------------------------------------
# engine: token identity (the oracle pin)
# ---------------------------------------------------------------------------


def test_tree_engine_token_identical_random_prompts():
    model = shared_model()
    prompts = _prompts(5, model.config.vocab_size, seed=19)
    refs = [reference_decode(model, p, 10) for p in prompts]
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, spec_tree="2x2") as eng:
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
        pool = eng._workers["default"].pool
    _drained(pool)
    assert st["spec_tree"] == "2x2" and st["spec_steps"] > 0
    assert st["spec_tree_slots"] > 0
    assert np.isfinite(st["spec_accept_rate"])


def test_tree_engine_adversarial_drafter_rollback():
    """Always-wrong tree chains: every branch rolls back, output
    identity and pool invariants still hold, and the drain is clean."""
    model = shared_model()
    prompts = _prompts(4, model.config.vocab_size - 2, seed=3)
    refs = [reference_decode(model, p, 9) for p in prompts]
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, spec_tree="2x2",
                               drafter=StubTreeDrafter()) as eng:
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        w = eng._workers["default"]
        st = eng.stats()["default"]
    _drained(w.pool)
    assert st["spec_accepted"] == 0 and st["spec_proposed"] > 0
    assert st["spec_blocks_rolled_back"] > 0
    assert st["spec_tree_commits"] == 0  # no path ever needed compaction


def test_tree_staggered_joins_and_eos_inside_accepted_path():
    """Staggered joins/retires with EOS landing INSIDE an accepted tree
    path (the target-as-drafter makes every level accept): no post-EOS
    token is ever emitted, the stream sees exactly the pre-EOS tokens,
    and the accepted-path commit machinery ran."""
    model = shared_model()
    prompt = [3, 7, 11, 2, 9]
    ref = reference_decode(model, prompt, 14)
    eos = ref[4]
    ref_eos = reference_decode(model, prompt, 14, eos_id=eos)
    p2 = _prompts(1, model.config.vocab_size, seed=41, lo=4, hi=8)[0]
    ref2 = reference_decode(model, p2, 8, eos_id=eos)
    first_tok = threading.Event()
    seen = []
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, spec_tree="2x2",
                               drafter=ModelDrafter(model)) as eng:
        r = eng.submit(prompt, max_new_tokens=14, eos_id=eos,
                       stream=lambda rq, t, fin: (seen.append((t, fin)),
                                                  first_tok.set()))
        assert first_tok.wait(120)  # r1 is mid-generation: a real join
        r2 = eng.submit(p2, max_new_tokens=8, eos_id=eos)
        got = r.wait(120)
        got2 = r2.wait(120)
        st = eng.stats()["default"]
        pool = eng._workers["default"].pool
    _drained(pool)
    assert got == ref_eos and got[-1] == eos
    assert got2 == ref2
    assert [t for t, _ in seen] == ref_eos
    assert [f for _, f in seen] == [False] * (len(ref_eos) - 1) + [True]
    assert st["spec_accept_rate"] == 1.0
    assert st["spec_draft_steps"] > 0
    assert st["spec_tree_commits"] > 0


def test_int8_compounded_tree_token_identical():
    """int8 target AND int8 drafter under tree windows: token-identical
    to the dequantized-store reference, and the stats receipt shows the
    int8 weight store really is serving."""
    q = shared_model().quantized()
    prompts = _prompts(3, q.config.vocab_size, seed=31, lo=3, hi=9)
    refs = [reference_decode(q, p, 8) for p in prompts]
    with serving.ServingEngine(q, max_batch=3, max_seq_len=64,
                               block_size=4, spec_tree="2x2",
                               drafter=ModelDrafter(q)) as eng:
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
        pool = eng._workers["default"].pool
    _drained(pool)
    assert st["spec_accept_rate"] == 1.0
    assert st["weight_only_int8"] is True
    ws = st["weight_store"]
    assert ws["n_int8"] > 0 and ws["int8_bytes"] < ws["fp32_bytes"]


def test_tree_env_flag_activates(monkeypatch):
    monkeypatch.setenv("PTPU_SERVE_SPEC_TREE", "2x2")
    model = shared_model()
    prompt = list(range(3, 17))
    ref = reference_decode(model, prompt, 6)
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4) as eng:
        w = eng._workers["default"]
        assert w.spec_tree == (2, 2)
        assert isinstance(w.drafter, NGramDrafter)
        assert eng.generate(prompt, max_new_tokens=6, timeout=120) == ref


def test_tree_off_keeps_spec_engine_bitwise_pr12(monkeypatch):
    """PTPU_SERVE_SPEC_TREE unset: the linear spec engine compiles the
    same shapes under the same cache keys as before the tree existed —
    no tree window, no commit step, no draft-side steps."""
    monkeypatch.delenv("PTPU_SERVE_SPEC_TREE", raising=False)
    model = tiny_model(seed=9)
    prompts = _prompts(3, model.config.vocab_size, seed=13)
    refs = [reference_decode(model, p, 6) for p in prompts]
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4, spec_k=4) as eng:
        w = eng._workers["default"]
        assert w.spec_tree is None and w._tree_commit is None
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert not any(isinstance(k, tuple) and k
                   and k[0] in ("spec_tree", "tree_commit", "draft")
                   for k in model._steps), list(model._steps)
    assert st["spec_tree"] is None
    assert st["spec_tree_slots"] == 0 and st["spec_tree_commits"] == 0
    sched = w.scheduler
    assert sched.spec_tree is None


# ---------------------------------------------------------------------------
# jitted ModelDrafter: perfect acceptance + draft-pool truncate contract
# ---------------------------------------------------------------------------


def test_jitted_drafter_linear_perfect_acceptance():
    """The batched jitted draft path replaces the per-row host decode
    loop: drafting with the target model still accepts everything, and
    the device drafting really ran (draft_steps > 0)."""
    model = shared_model()
    prompts = _prompts(4, model.config.vocab_size, seed=23, lo=3, hi=9)
    refs = [reference_decode(model, p, 10) for p in prompts]
    with serving.ServingEngine(model, max_batch=3, max_seq_len=64,
                               block_size=4, spec_k=4,
                               drafter=ModelDrafter(model)) as eng:
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = eng.stats()["default"]
    assert st["spec_accept_rate"] == 1.0
    assert st["spec_draft_steps"] > 0
    # windows fill: far fewer compiled target steps than tokens
    assert st["spec_emitted"] / st["spec_steps"] > 2


def test_drafter_pool_truncate_accounting():
    """The drafter's own KV pool obeys the reservation-restoring
    truncate contract at every window boundary: blocks snap back to
    exactly the committed history's span, the truncate counters move,
    and invariants stay clean."""
    model = shared_model()
    d = ModelDrafter(model, block_size=16)
    d.bind(max_batch=2, max_chain=4)
    hist = list(range(3, 17))                   # 14 tokens
    got = d.propose_tree_batch([("s1", hist, 3)], width=2)
    assert got["s1"][0] == reference_decode(model, hist, 3)
    pool = d._pool
    assert pool.check_invariants() == []
    st = pool.stats()
    assert st["truncate_calls"] >= 1
    # drafting past position 18 crossed into a second 16-token block;
    # the rollback returned it and re-pointed the table
    assert st["blocks_truncated"] >= 1
    state = d._states["s1"]
    assert len(pool.block_table(state)) == blocks_needed(len(hist), 16)
    assert state.n_cached == len(hist)
    # the next window reuses the caught-up KV: only the appended span
    # prefills, and the proposals stay oracle-identical
    hist2 = hist + reference_decode(model, hist, 1)
    got2 = d.propose_tree_batch([("s1", hist2, 3)], width=2)
    assert got2["s1"][0] == reference_decode(model, hist2, 3)
    assert pool.check_invariants() == []
    d.release("s1")
    _drained(pool)


def test_jitted_drafter_rows_at_cap_ride_inactive():
    """A row whose draft span would cross the draft model's sequence
    cap drafts only its catch-up token; nothing raises and shorter
    windows still verify."""
    model = shared_model()
    d = ModelDrafter(model, block_size=16)
    d.bind(max_batch=2, max_chain=5)
    hist = list(range(1, 62))                   # 61 of 64 positions
    got = d.propose_tree_batch([("edge", hist, 4)], width=2)
    # 61 + 4 > 64: the fused scan skips the row; chain 0 is the single
    # catch-up argmax token
    assert got["edge"][0] == reference_decode(model, hist, 1)
    assert d._pool.check_invariants() == []
    # at the cap exactly: nothing draftable at all
    hist_full = list(range(0, 64))
    got = d.propose_tree_batch([("full", hist_full, 4)], width=2)
    assert got["full"] == []


# ---------------------------------------------------------------------------
# NGram drafter: suffix-index memoization + tree proposals
# ---------------------------------------------------------------------------


def test_ngram_memoized_matches_scan_and_is_o_k():
    """The per-sequence suffix index returns scan-identical proposals
    at O(k + newly committed)-per-window host cost — the steady-state
    per-window op count is bounded by a constant, not the history
    length."""
    rng = np.random.RandomState(5)
    hist = rng.randint(0, 16, size=40).tolist() + [7, 8, 4, 5, 7, 8]
    memo = NGramDrafter()
    fresh = NGramDrafter()
    assert memo.propose_for("s", hist, 4) == fresh.propose(hist, 4)
    # steady state: append one token per window, compare op deltas
    deltas = []
    for t in [4, 5, 7, 8, 4, 5, 7, 8, 4, 5]:
        hist = hist + [t]
        before = memo.index_ops
        assert memo.propose_for("s", hist, 4) == fresh.propose(hist, 4)
        deltas.append(memo.index_ops - before)
    # each window inserts <= max_ngram grams and probes a bounded
    # occurrence list; a full rescan would cost ~len(hist) per n
    assert max(deltas) < 30, deltas
    # a shrunken history (external rollback) rebuilds and stays correct
    hist = hist[:20]
    assert memo.propose_for("s", hist, 4) == fresh.propose(hist, 4)
    memo.release("s")
    assert "s" not in memo._index


def test_ngram_propose_tree_alternate_branches():
    """Period-alternating traffic — the same suffix continues two ways
    — yields one chain per continuation, exactly the windows a single
    linear draft keeps losing."""
    d = NGramDrafter()
    # the recurring suffix [5, 1, 9] continues 6 at its first site and
    # 7 at its (more recent) second
    hist = [5, 1, 9, 6, 0, 5, 1, 9, 7, 2, 5, 1, 9]
    chains = d.propose_tree(hist, width=2, depth=3, seq_id="s")
    assert len(chains) == 2
    assert {ch[0] for ch in chains} == {6, 7}
    # chain 0 is the linear proposal
    assert chains[0] == d.propose(hist, 3)
    # width 1 is exactly the linear drafter
    assert d.propose_tree(hist, width=1, depth=3) == [d.propose(hist, 3)]
    # no recurring suffix -> no chains
    assert d.propose_tree([1, 2, 3], width=2, depth=3) == []


# ---------------------------------------------------------------------------
# the Pallas tree-mask verify-window kernel
# ---------------------------------------------------------------------------


def test_paged_attention_tree_matches_reference():
    from paddle_tpu.ops.pallas_kernels import (
        paged_attention_reference, paged_attention_tree,
        paged_attention_tree_reference)
    from paddle_tpu.ops import pallas_kernels as pk

    if pk.pltpu is None:
        pytest.skip("pallas TPU support (scalar prefetch) unavailable")
    rng = np.random.RandomState(0)
    B, H, Dh, bs, Mb = 2, 2, 8, 4, 6
    W, D = 2, 2
    C = 1 + W * D
    _p, _d, anc = tree_topology(W, D)
    n_pages = Mb * B + 1
    # the whole pool, [L, pages, bs, H, Dh]; the kernels read layer 1
    k_pool = rng.randn(2, n_pages, bs, H, Dh).astype(np.float32)
    v_pool = rng.randn(2, n_pages, bs, H, Dh).astype(np.float32)
    q = rng.randn(B, C, H, Dh).astype(np.float32)
    tables = np.arange(B * Mb, dtype=np.int32).reshape(B, Mb) + 1
    pos0 = np.array([5, 9], np.int32)           # >= 1 past "prefill"
    positions = pos0[:, None] + np.arange(C, dtype=np.int32)[None, :]
    got = np.asarray(paged_attention_tree(
        k_pool, v_pool, q, tables, positions, anc.astype(np.float32),
        layer=1))
    want = np.asarray(paged_attention_tree_reference(
        k_pool, v_pool, q, tables, positions, anc, layer=1))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # width 1 tree mask == the linear spec window kernel's semantics
    _p1, _d1, anc1 = tree_topology(1, 3)
    C1 = 4
    q1 = q[:, :C1]
    pos1 = pos0[:, None] + np.arange(C1, dtype=np.int32)[None, :]
    got1 = np.asarray(paged_attention_tree(
        k_pool, v_pool, q1, tables, pos1, anc1.astype(np.float32),
        layer=1))
    lin = np.asarray(paged_attention_reference(
        k_pool, v_pool, q1, tables, pos1, layer=1))
    np.testing.assert_allclose(got1, lin, rtol=2e-5, atol=2e-5)


def test_spec_window_tree_registered():
    from paddle_tpu.ops import kernel_registry as kr

    assert "spec_window_tree" in kr.registered_kernels()
    spec = kr.get_kernel("spec_window_tree")
    ok, _why = spec.qualify()
    assert isinstance(ok, bool)
