"""The delta-rule linear-attention / latent-attention / routed-expert
serving block (serving/ling.py) against its plain reference
(perfbench/reference/ling.py), at toy widths on the CPU with seeded
weights whose gains are drawn off 1 (``init_gain_noise``): the served
path through the latent pages AND the two-part row state (prompts fed as
chunks whose sizes do not divide them, one of them longer than a scan
tile, then decoded), a mixed step that holds a continuing, a fresh and a
decode row, a slot another sequence left, the controls, the engine and
the step log's fields. The kernels, the router, the expert layer's
shares, the description and the refusals are in ``test_ling_parts.py``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import GenerationModel, ServingEngine
from perfbench.reference import ling as ref
from perfbench.runners import serve_ling
from test_zaya import ByHand, kernels  # noqa: F401  (the fixture)

SEED = 2147483659      # past 32 signed bits, as the driver's seeds are


def toy_config(**changes):
    """A configuration file's keys at toy widths (scan heads of 128
    lanes in groups of eight, so that the kernels take them), float32
    throughout so that the served path and the reference agree to
    rounding. Three layers held from published layer 1 of a period of
    three: KDA (dense), MLA, KDA."""
    cfg = dict(
        family="ling", vocab_size=96, hidden_size=64,
        num_attention_heads=8, head_dim=128, num_hidden_layers=3,
        layer_group_size=3, published={"layers_held": [1, 3]},
        first_k_dense_replace=1, intermediate_size=96,
        short_conv_kernel_size=4, kda_lower_bound=-5,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=6000000, rms_norm_eps=1e-6,
        num_experts=8, router_experts=16, experts_held_from=0,
        num_experts_per_tok=4, num_shared_experts=1, n_group=4,
        topk_group=2, routed_scaling_factor=2.5, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, init_std=0.3,
        init_gain_noise=0.2, weight_dtype="float32",
        dtypes={"weights": "float32", "activations": "float32",
                "router": "float32", "cache": "float32",
                "state": "float32"})
    cfg.update(changes)
    return cfg


_MADE = {}


def made_once(key, make):
    """The toy's weights, parameters and compiled references are the
    same in every test: make each once a process."""
    if key not in _MADE:
        _MADE[key] = make()
    return _MADE[key]


def served_model(cfg, max_seq_len=256, **block):
    config = serve_ling.generation_config(cfg, max_seq_len)
    if block:
        config.block = config.block.replace(**block)
    weights = made_once(("weights", repr(sorted(cfg.items()))),
                        lambda: serve_ling.seeded_weights(ref, cfg, SEED))
    return GenerationModel(config, weights)


def reference_logits(cfg, seq, rows):
    """The reference's logits at ``rows`` of ``seq``, the sequence padded
    to a multiple of 64 positions and the rows to 32 (a causal model's
    earlier positions do not see the padding), so that it compiles once
    a padded length."""
    name = repr(sorted(cfg.items()))
    params = made_once(("params", name),
                       lambda: ref.make_params(SEED, cfg))
    fn = made_once(("logits", name), lambda: jax.jit(
        lambda p, tokens, at: ref.logits_at(p, tokens, at, cfg)))
    tokens = np.zeros(-(-len(seq) // 64) * 64, np.int32)
    tokens[:len(seq)] = seq
    at = np.zeros(-(-len(rows) // 32) * 32, np.int32)
    at[:len(rows)] = rows
    return np.asarray(fn(params, tokens, at))[:len(rows)]


def close_to(have, want, tol=5e-4):
    """float32 on both paths: what is left is the order of float32 sums
    (a chunk's matmuls and the reference's whole-sequence ones tile
    differently; the chunked scan's closed form and the recurrence sum
    the same terms in another order), a few 1e-5 of the logits' size
    over four layers."""
    scale = np.abs(want).max()
    worst = np.abs(have - want).max()
    assert worst <= tol * scale, (worst, scale)


# -- the served path against the reference's full forward -------------------

@pytest.mark.parametrize("kernels,chunk,lengths,max_tokens", [
    ("0", 1, (9, 6), None), ("1", 7, (23, 17), None),
    ("0", 5, (23, 17), 7),
    ("0", 70, (150, 81), 72), ("1", 70, (150, 81), 72)],
    indirect=["kernels"])
def test_served_path_equals_the_reference_forward(kernels, chunk, lengths,
                                                  max_tokens):
    """Prompts fed ``chunk`` tokens a step (lengths no multiple of it),
    then decoded: every chunk after a prompt's first continues the scan
    from the row state, and a token's three predecessors for the
    convolutions come from its own chunk or from the row state. A chunk
    of 70 is two scan tiles."""
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n).tolist() for n in lengths]
    hand = ByHand(model, B=2, Mb=10, bs=16, C=chunk, max_tokens=max_tokens)
    got = [[], []]
    for b in range(2):
        hand.admit(b, 10)
    while any(hand.pos[b] < len(prompts[b]) for b in range(2)):
        fed = [prompts[b][hand.pos[b]:hand.pos[b] + chunk]
               for b in range(2)]
        if max_tokens is not None:      # a budget: one row a step
            turn = next(b for b in range(2) if fed[b])
            fed = [t if b == turn else [] for b, t in enumerate(fed)]
        for b, z in hand.feed(fed).items():
            got[b].append((hand.pos[b] - 1, z))
    seqs = [list(p) for p in prompts]
    tok = [int(np.argmax(got[b][-1][1])) for b in range(2)]
    for _ in range(5):
        for b in range(2):
            seqs[b].append(tok[b])
        at = hand.pos.copy()
        logits = hand.step(tok)
        for b in range(2):
            got[b].append((at[b], logits[b]))
        tok = [int(np.argmax(logits[b])) for b in range(2)]
    assert hand.pool.check_invariants() == []
    for b in range(2):
        close_to(np.stack([z for _p, z in got[b]]),
                 reference_logits(cfg, seqs[b], [p for p, _z in got[b]]))
    # the scan's three counters: decode rows read their state; a row's
    # first chunk starts one
    steps = np.stack(hand.counters)[:, -3:]
    assert (steps[-5:] == [2, 0, 0]).all()
    assert steps[0, 2] >= 1 and steps[0, 0] == 0
    if chunk > 1:
        assert steps[:-5, 1].sum() == sum(
            n - (n % chunk == 1) for n in lengths)


@pytest.mark.parametrize("kernels", ["0", "1"], indirect=True)
def test_a_mixed_step_holds_continuing_fresh_and_decode_rows(kernels):
    """One mixed step holds a row that continues its scan from the row
    state, a row that starts one (position 0) in a slot ANOTHER sequence
    left (its stale state and convolution inputs must not be read), a
    decode row (one token, through the one-token kernel) and an idle row
    whose state must come back untouched."""
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(11)
    old, a, b, c, d = (rng.integers(0, 96, n).tolist()
                       for n in (12, 6, 13, 6, 9))
    hand = ByHand(model, B=4, Mb=2, bs=16, C=5, max_tokens=16)
    for row in range(4):
        hand.admit(row, 2)
    # slot 0 is used and left; rows 1..3 go ahead
    hand.feed([old[:5], b[:5], [], d[:2]])
    hand.feed([old[5:10], [], c[:5], d[2:7]])
    hand.feed([old[10:], b[5:10], c[5:], d[7:]])
    stale = np.asarray(hand.pool.row_state.part("scan"))[0]
    assert np.abs(stale).max() > 1e-3
    hand.retire(0)
    hand.admit(0, 2)
    tok_c = int(np.argmax(reference_logits(cfg, c, [5])[0]))
    idle = [np.asarray(p)[3].copy() for p in hand.pool.row_state.arrays]
    # the mixed step: a starts in slot 0, b continues, c decodes, d idles
    z = hand.feed([a[:5], b[10:], [tok_c], []])
    close_to(z[0][None], reference_logits(cfg, a[:5], [4]))
    close_to(z[1][None], reference_logits(cfg, b, [12]))
    close_to(z[2][None], reference_logits(cfg, c + [tok_c], [6]))
    assert (np.stack(hand.counters)[-1, -3:] == [2, 5 + 3, 1]).all()
    for before, part in zip(idle, hand.pool.row_state.arrays):
        assert (np.asarray(part)[3] == before).all()
    # d resumes by a decode step after sitting a step out; a's second
    # chunk is ONE token (a decode-shaped end of a prompt)
    tok_d = int(np.argmax(reference_logits(cfg, d, [8])[0]))
    z = hand.feed([a[5:], [], [], [tok_d]])
    close_to(z[0][None], reference_logits(cfg, a, [5]))
    close_to(z[3][None], reference_logits(cfg, d + [tok_d], [9]))
    assert hand.pool.check_invariants() == []


def test_the_controls_are_seen(monkeypatch):
    """The benchmark's two controls of the scan: with the stored state
    ignored at every step's start the second chunk leaves the reference
    by far more than rounding (the first, from position 0, does not);
    with the state kept in bfloat16 it leaves it by bfloat16's
    rounding."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    seq = np.random.default_rng(5).integers(0, 96, 12).tolist()
    want = reference_logits(cfg, seq, [5, 11])
    scale = np.abs(want).max()
    for block, least in ((dict(scan_restarts_each_chunk=True), 1e-2),
                         (dict(state_dtype="bfloat16"), 1e-4)):
        hand = ByHand(served_model(cfg, **block), B=1, Mb=1, bs=16, C=6)
        hand.admit(0, 1)
        got = [hand.feed([seq[i:i + 6]])[0] for i in (0, 6)]
        assert np.abs(got[0] - want[0]).max() <= 5e-4 * scale
        assert np.abs(got[1] - want[1]).max() > least * scale, block
    assert hand.pool.row_state.part("scan").dtype == jnp.bfloat16


# -- through the engine -------------------------------------------------------

def test_the_engine_serves_what_the_reference_decodes(monkeypatch):
    """Through ServingEngine (scheduler, pool, row state, slots reused
    by later requests): greedy tokens equal the reference's, a batch row
    keeps the scan state of the last sequence it held (the reference's
    after the tokens that row was fed), the step log carries the block's
    fields and ``stats()`` the row state's bytes beside the pages'."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    model = served_model(cfg, max_seq_len=96)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n).tolist() for n in (30, 9, 21, 14)]
    metrics.reset()
    metrics.enable()
    try:
        eng = ServingEngine(model, max_batch=2, max_seq_len=96,
                            block_size=16, prefill_chunk=8, num_blocks=12)
        try:
            reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
            outs = [r.wait(300) for r in reqs]
            deadline = time.monotonic() + 30
            while next(iter(eng.stats().values()))["blocks_in_use"] \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            stats = next(iter(eng.stats().values()))
        finally:
            eng.close()
        scan = np.asarray(eng.row_state().part("scan"))
        recs = metrics.registry().samples("serving/step").records()
    finally:
        metrics.disable()
        metrics.reset()      # leave no record for a later test to read
    for prompt, out, req in zip(prompts, outs, reqs):
        seq = list(prompt) + list(out)
        z = reference_logits(cfg, seq[:-1],
                             list(range(len(prompt) - 1, len(seq) - 1)))
        assert (np.argmax(z, axis=-1) == np.asarray(out)).all()
        # each served token's own logit came back beside it
        np.testing.assert_allclose(np.asarray(req.top_logits),
                                   z.max(axis=-1), rtol=5e-4, atol=5e-4)
    # four requests went through two rows; the two admitted last are
    # what the rows hold now: the state after the prompt and every
    # served token but the last, which no step was fed
    assert sorted(r.slot for r in reqs) == [0, 0, 1, 1]
    states = made_once(("states", repr(sorted(cfg.items()))), lambda: jax.jit(
        lambda p, tokens, stop: ref.hidden(p, tokens, cfg, stop)[2]))
    params = made_once(("params", repr(sorted(cfg.items()))),
                       lambda: ref.make_params(SEED, cfg))
    last = {r.slot: (p, o) for p, o, r in sorted(
        zip(prompts, outs, reqs), key=lambda t: t[2].start_time)}
    for slot, (prompt, out) in last.items():
        tokens = np.zeros(64, np.int32)
        fed = len(prompt) + len(out) - 1
        tokens[:fed] = (list(prompt) + list(out))[:-1]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(states(params, tokens, np.int32(fed)))
        assert want.shape == ref.scan_state_shape(cfg) == scan[slot].shape
        close_to(scan[slot], want)
    # the two kinds of state side by side: one MLA layer's pages, two
    # KDA layers' matrices and convolution inputs a batch row
    assert stats["page_bytes"] == 13 * 16 * 256 * 4
    assert stats["row_state_parts"] == {
        "scan": 2 * 2 * 8 * 128 * 128 * 4, "conv": 2 * 18 * 8 * 128 * 4}
    assert stats["row_state_bytes"] == sum(
        stats["row_state_parts"].values())
    assert recs
    for r in recs:
        for f in ("expert_pairs", "experts_touched", "expert_rows_max",
                  "expert_slots", "state_rows", "scan_tokens",
                  "scan_fresh_rows", "global_pages_walked",
                  "global_keys_attended", "window_keys_attended"):
            assert f in r, (f, r)
        # its pages go through the latent kernel, whose page pipe hands
        # over from row to row: one MLA layer, a run a one-token row
        # (contexts of under 64 tokens in pages of 16, 32 pages a run)
        assert r["decode_rows_walked"] == r["decode_runs_walked"]
        assert r["decode_rows_opened_warm"] == max(
            r["decode_rows_walked"] - 1, 0)
        if r["kind"] == "decode":
            assert r["decode_rows_walked"] == r["slots_used"]
        # two expert layers hold half of the router's experts
        assert r["expert_slots"] == 2 * 8
        assert r["expert_pairs"] <= 2 * 4 * r["slots_used"]
        assert r["state_rows"] + r["scan_fresh_rows"] == r["rows"]
        if r["kind"] == "decode":
            assert r["scan_tokens"] == 0
            assert r["state_rows"] == r["slots_used"]
    assert sum(r["scan_fresh_rows"] for r in recs) == len(prompts)
    # one MLA layer: a key attended once a query
    decode = [r for r in recs if r["kind"] == "decode"]
    assert all(r["global_keys_attended"] == r["cached_tokens"]
               for r in decode)
